"""Eager autograd engine: a define-by-run tape over jax.vjp.

Design (TPU-native analog of the reference eager autograd,
reference: paddle/fluid/eager/backward.cc:105 RunBackward,
paddle/fluid/eager/grad_node_info.h:197 GradNodeBase):

Every differentiable eager op runs through :func:`run_op`, which

  1. executes the op's pure jax function on the unwrapped ``jax.Array`` s,
  2. if grad is required, calls ``jax.vjp`` to get a ``vjp_fn`` closed over the
     residuals (this *is* the saved-activation store — the analog of the
     reference's ``TensorWrapper`` saved inputs), and
  3. records a :class:`GradNode` linking outputs back to differentiable inputs.

``backward()`` then does the in-degree-counting queue walk the reference engine
does, calling each node's ``vjp_fn`` and accumulating cotangents into leaf
``.grad`` (reference analog: GradTensorHolder + accumulation node).

Unlike the reference there is no codegen: jax.vjp supplies every op's gradient
rule, so a single generic node type suffices.
"""
from __future__ import annotations

import threading
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..observability import scopes as _scopes
from . import static_flags

__all__ = [
    "GradNode",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "run_op",
    "backward",
    "grad",
]


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_state = _GradState()


def is_grad_enabled() -> bool:
    return _state.enabled


def set_grad_enabled(mode: bool):
    _state.enabled = bool(mode)


class _GradModeCtx:
    def __init__(self, mode: bool):
        self._mode = mode
        self._prev = None

    def __enter__(self):
        self._prev = _state.enabled
        _state.enabled = self._mode
        return self

    def __exit__(self, *exc):
        _state.enabled = self._prev
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _GradModeCtx(self._mode):
                return fn(*args, **kwargs)

        return wrapper


def no_grad(fn=None):
    """Context manager / decorator disabling grad recording."""
    ctx = _GradModeCtx(False)
    if fn is not None:
        return ctx(fn)
    return ctx


def enable_grad(fn=None):
    ctx = _GradModeCtx(True)
    if fn is not None:
        return ctx(fn)
    return ctx


class GradNode:
    """One recorded op on the tape.

    ``vjp_fn``: maps a tuple of output cotangents to a tuple of cotangents for
    the differentiable inputs. ``inputs`` are the differentiable input Tensors
    (in vjp order). ``outputs`` are weak metadata: (shape, dtype) per output so
    missing cotangents can be materialized as zeros. ``phase`` is the
    phase of the program the op ran under (``observability/scopes``, None
    outside any): its backward runs under the same one, so that a compiled
    step's backward instructions count in their forward phase.
    """

    __slots__ = ("vjp_fn", "inputs", "out_meta", "name", "single",
                 "fn_closed", "phase", "_pending")

    def __init__(self, vjp_fn, inputs, out_meta, name="op", single=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs  # list[Tensor]
        self.out_meta = out_meta  # list[(shape, jnp dtype)]
        self.name = name
        # whether the differentiated fn returned a bare array (vjp_fn then
        # expects a bare cotangent, not a 1-tuple)
        self.single = single if single is not None else len(out_meta) == 1
        self.fn_closed = None  # set by run_op; enables create_graph replay
        self.phase = _scopes.innermost()
        self._pending = None  # populated during backward

    def __repr__(self):
        return f"<GradNode {self.name} n_in={len(self.inputs)} n_out={len(self.out_meta)}>"


_profiler_mod = None  # bound on first run_op call (avoids init-order cycle)


def run_op(fn: Callable, tensors: Sequence, name: str = "op", n_outputs: Optional[int] = None,
           attrs: Optional[dict] = None):
    """Execute pure jax function ``fn`` over Tensor inputs, recording the tape.

    ``fn(*arrays) -> array | tuple[array]``. Returns Tensor or tuple of Tensors.
    Inputs with ``stop_gradient=True`` are treated as constants.
    """
    # host-tracer span per op when a profiler window is recording (analog of
    # the RecordEvent emitted by every generated AD func, eager_gen.py:1312);
    # the hot no-profiler path costs one global read + None check
    # lazy-import memoization, not per-step state — writing it at trace
    # time is exactly as correct as writing it eagerly
    global _profiler_mod  # ptlint: disable=jit-purity
    if _profiler_mod is None:
        import paddle_tpu.profiler

        _profiler_mod = paddle_tpu.profiler
    _col = _profiler_mod._active_collector
    if _col is not None:
        import time as _time

        _t0 = _time.perf_counter_ns()
        try:
            return _run_op_impl(fn, tensors, name, attrs)
        finally:
            _col.record(name, "op", _t0, _time.perf_counter_ns() - _t0)
    return _run_op_impl(fn, tensors, name, attrs)


def _run_op_impl(fn: Callable, tensors: Sequence, name: str = "op",
                 attrs: Optional[dict] = None):
    from .tensor import Tensor

    if static_flags.enabled:
        from ..static import graph as _graph

        if any(_graph.is_symbolic(t) for t in tensors):
            return _graph.record_op(fn, tensors, name, attrs=attrs)

    arrays = [t._data if isinstance(t, Tensor) else t for t in tensors]

    # AMP autocast — the analog of the reference's AmpAutoCasts step in every
    # generated AD func (fluid/eager/amp_auto_cast.h)
    from .. import amp as _amp

    if _amp.is_auto_cast_enabled():
        arrays = _amp.amp_cast_inputs(name, arrays)
        from ..amp import debugging as _dbg

        _dbg.record_op(name, str(arrays[0].dtype)
                       if arrays and hasattr(arrays[0], "dtype") else "-")

    need_grad = _state.enabled and any(
        isinstance(t, Tensor) and not t.stop_gradient for t in tensors
    )

    if not need_grad:
        out = fn(*arrays)
        single = not isinstance(out, (tuple, list))
        outs = (out,) if single else tuple(out)
        wrapped = tuple(Tensor(o, stop_gradient=True) for o in outs)
        _maybe_check_numerics(wrapped, name)
        return wrapped[0] if single else wrapped

    diff_idx = [
        i for i, t in enumerate(tensors) if isinstance(t, Tensor) and not t.stop_gradient
    ]

    def closed(*diff_arrays):
        full = list(arrays)
        for i, a in zip(diff_idx, diff_arrays):
            full[i] = a
        return fn(*full)

    out, vjp_fn = jax.vjp(closed, *[arrays[i] for i in diff_idx])
    single = not isinstance(out, (tuple, list))
    outs = (out,) if single else tuple(out)

    node = GradNode(
        vjp_fn=vjp_fn,
        inputs=[tensors[i] for i in diff_idx],
        out_meta=[(o.shape, o.dtype) for o in outs],
        name=name,
        single=single,
    )
    # re-derivable closure: create_graph replays jax.vjp through run_op so
    # the backward itself lands on the tape (double grad, reference analog:
    # the generated double-grad nodes, eager_gen higher-order AD)
    node.fn_closed = closed
    wrapped = tuple(
        Tensor(o, stop_gradient=False, grad_node=node, out_index=i)
        for i, o in enumerate(outs)
    )
    _maybe_check_numerics(wrapped, name)
    return wrapped[0] if single else wrapped


def _maybe_check_numerics(wrapped, name):
    """FLAGS_check_nan_inf hook (reference: fluid/eager/nan_inf_utils.cc,
    called from every generated AD func)."""
    from ..amp import debugging as _dbg

    if _dbg.check_numerics_enabled():
        for t in wrapped:
            _dbg.check_numerics(t, name)


def _toposort(roots: List[GradNode]) -> List[GradNode]:
    """Reverse-topological order (outputs first) over the node DAG."""
    order: List[GradNode] = []
    visited = set()
    # iterative DFS with post-order
    stack = [(n, False) for n in roots]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in visited:
            continue
        visited.add(id(node))
        stack.append((node, True))
        for t in node.inputs:
            if t._grad_node is not None and id(t._grad_node) not in visited:
                stack.append((t._grad_node, False))
    order.reverse()  # outputs-first
    return order


def _run_backward(tensors, grad_tensors, retain_graph, capture=None,
                  create_graph=False):
    """Core reverse walk. Returns (leaf_grads: id->array, leaves: id->Tensor)
    WITHOUT writing any .grad — callers decide (backward writes .grad;
    grad() reads only the requested inputs, matching the reference's
    side-effect-free paddle.grad)."""
    from .tensor import Tensor

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]

    # seed cotangents
    out_grads = {}  # id(node) -> {out_index: cotangent array}
    leaf_grads = {}  # id(tensor) -> accumulated array
    leaves = {}
    roots = []
    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            continue
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {tuple(t.shape)}"
                )
            ga = jnp.ones_like(t._data)
        else:
            ga = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        if create_graph:
            # cotangents flow as Tensors so every backward op is taped
            ga = Tensor(ga, stop_gradient=True) if not isinstance(g, Tensor) \
                else g
        node = t._grad_node
        if node is None:
            leaf_grads[id(t)] = leaf_grads.get(id(t), 0) + ga
            leaves[id(t)] = t
            continue
        slot = out_grads.setdefault(id(node), {})
        idx = t._out_index
        slot[idx] = slot[idx] + ga if idx in slot else ga
        roots.append(node)

    order = _toposort(roots)

    for node in order:
        grads_map = out_grads.get(id(node))
        if grads_map is None:
            continue
        def _match(ct, dtype):
            # accumulated cotangents can arrive in a promoted dtype (e.g.
            # f32 summed into a bf16 output under amp autocast): the vjp
            # contract requires the exact output dtype
            if isinstance(ct, Tensor):
                return ct.astype(str(dtype)) if ct._data.dtype != dtype \
                    else ct
            return ct.astype(dtype) if ct.dtype != dtype else ct

        # the op's backward, and the sums of what it hands its inputs, run
        # under the phase its forward ran under
        with _scopes.backward_of(node.phase):
            cotangents = tuple(
                _match(grads_map[i], dtype) if i in grads_map
                else (Tensor(jnp.zeros(shape, dtype)) if create_graph
                      else jnp.zeros(shape, dtype))
                for i, (shape, dtype) in enumerate(node.out_meta)
            )
            if node.vjp_fn is None:
                raise RuntimeError(
                    f"trying to backward through op '{node.name}' a second "
                    "time after its graph was freed; call "
                    "backward(retain_graph=True) the first time if you need "
                    "this")
            if create_graph:
                if node.fn_closed is None:
                    raise NotImplementedError(
                        f"create_graph through '{node.name}' (a custom "
                        "PyLayer) is not supported; its backward strips the "
                        "tape")
                closed = node.fn_closed
                n_in = len(node.inputs)
                sgl = node.single

                def replay(*flat, _closed=closed, _n=n_in, _sgl=sgl):
                    ins, cots = flat[:_n], flat[_n:]
                    _, vjp = jax.vjp(_closed, *ins)
                    out = vjp(cots[0] if _sgl else tuple(cots))
                    return tuple(out)

                replayed = run_op(replay, list(node.inputs) + list(cotangents),
                                  name=f"{node.name}_grad")
                in_grads = replayed if isinstance(replayed, tuple) \
                    else (replayed,)
            elif node.single:
                in_grads = node.vjp_fn(cotangents[0])
            else:
                in_grads = node.vjp_fn(cotangents)
            for t, g in zip(node.inputs, in_grads):
                if g is None:
                    continue
                child = t._grad_node
                if child is None:
                    leaf_grads[id(t)] = (
                        leaf_grads[id(t)] + g if id(t) in leaf_grads else g
                    )
                    leaves[id(t)] = t
                else:
                    if capture is not None and id(t) in capture:
                        # non-leaf grad requested by grad(inputs=...)
                        leaf_grads[id(t)] = (
                            leaf_grads[id(t)] + g if id(t) in leaf_grads
                            else g)
                        leaves[id(t)] = t
                    slot = out_grads.setdefault(id(child), {})
                    idx = t._out_index
                    slot[idx] = slot[idx] + g if idx in slot else g

    if not retain_graph:
        for node in order:
            node.vjp_fn = None
            node.fn_closed = None  # frees the closed-over input arrays
            node.inputs = []
    return leaf_grads, leaves


def backward(tensors, grad_tensors=None, retain_graph: bool = False):
    """Run reverse-mode accumulation from ``tensors``, writing leaf ``.grad``
    (accumulating into existing .grad like the reference accumulation node,
    fluid/eager/accumulation/accumulation_node.cc)."""
    from .tensor import Tensor

    leaf_grads, leaves = _run_backward(tensors, grad_tensors, retain_graph)
    for tid, garr in leaf_grads.items():
        t = leaves[tid]
        if t._grad is None:
            t._grad = Tensor(garr, stop_gradient=True)
        elif not isinstance(t._grad, Tensor):
            # a row-sparse (SelectedRows) grad already accumulated here;
            # mixing in a dense tape grad is order-dependent wrt hooks
            raise RuntimeError(
                "parameter holds a row-sparse (SelectedRows) gradient "
                "and also received a dense gradient; set sparse=False "
                "on the Embedding for this usage")
        else:
            t._grad = Tensor(t._grad._data + garr, stop_gradient=True)
        for hook in t._grad_hooks:
            res = hook(t._grad)
            if res is not None:
                t._grad = res


def grad(outputs, inputs, grad_outputs=None, retain_graph=False, create_graph=False,
         allow_unused=True):
    """Functional gradient: d(outputs)/d(inputs) without touching .grad.

    ``create_graph=True`` replays each op's jax.vjp THROUGH the tape, so
    the returned grads are themselves differentiable (double grad —
    reference analog: the generated higher-order grad nodes,
    fluid/eager double-grad)."""
    from .tensor import Tensor

    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    leaf_grads, _ = _run_backward(outputs, grad_outputs,
                                 retain_graph or create_graph,
                                 capture={id(t) for t in inputs},
                                 create_graph=create_graph)
    results = []
    for t in inputs:
        if id(t) not in leaf_grads:
            if getattr(t, "_sparse_grad_path", False):
                # a sparse Embedding forward routed this weight's grad
                # through the SelectedRows hook, which functional grad()
                # cannot observe — a silent None would be wrong
                raise RuntimeError(
                    "paddle.grad() cannot return the gradient of a "
                    "sparse=True Embedding weight (it flows as a "
                    "SelectedRows side effect of backward()); use "
                    "loss.backward() + weight.grad, or sparse=False")
            if not allow_unused:
                raise RuntimeError("an input tensor is unused in the graph")
            results.append(None)
        else:
            g = leaf_grads[id(t)]
            if isinstance(g, Tensor):
                results.append(g)
            else:
                results.append(Tensor(g, stop_gradient=True))
    return results

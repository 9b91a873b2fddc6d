"""Compiled training step: one XLA program for forward+backward+update.

This is the TPU-native answer to the reference's static-graph training path
(reference: Engine.fit at python/paddle/distributed/auto_parallel/static/
engine.py:1529 — trace → parallelize → run on executor): the eager model code
is traced under ``jax.jit`` (the Tensor tape works over tracers), gradients
come from the same tape, and the optimizer's pure functional ``update`` runs
inside the compiled program. With a ProcessMesh set, parameter sharding
annotations (models/*.py) become ``in_shardings`` and GSPMD partitions the
whole step over the mesh — dp/mp/sp/fsdp collectives ride ICI.

Buffer donation (``donate_argnums``) makes the update in-place in HBM, the
analog of the reference executor's inplace/buffer-reuse passes.
"""
from __future__ import annotations

import time as _time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import observability as _obs
from ..core import random as _rng
from ..observability import health as _health
from ..observability import scopes as _scopes
from ..core.autograd import grad as _autograd_grad
from ..core.tensor import Tensor
from ..distributed.auto_parallel.constraint import filtered_spec, param_spec
from ..nn.layer.layers import Layer
from ..optimizer.optimizer import Optimizer

__all__ = ["TrainStep", "ChunkPrefetcher"]


def _count_jit(miss: bool, cause: str = "first_call"):
    """TrainStep program-cache telemetry (site=train_step): __call__
    reuses the jitted step (hit); a fresh _build or an unseen run_steps
    chunk size traces a new program (miss + recompile cause)."""
    if not _obs.enabled():
        return
    reg = _obs.registry
    if miss:
        reg.counter("jit.cache_miss", tags={"site": "train_step"}).inc()
        reg.counter("jit.recompile",
                    tags={"site": "train_step", "cause": cause}).inc()
        _obs.flight_recorder.record("jit.cache_miss", site="train_step",
                                    cause=cause)
    else:
        reg.counter("jit.cache_hit", tags={"site": "train_step"}).inc()


def _ledger_observe(site: str, args):
    """Compile-ledger call observation (observability/compile_ledger):
    when step profiling is on, diff this call's argument signature
    against the site's last one so a cache miss carries its CAUSE
    (which arg's shape/dtype/static value changed). Returns
    ``(miss, cause)``; ``(False, None)`` with profiling off — the
    zero-cost path does no signature work at all."""
    from ..observability import compile_ledger as _ledger
    from ..observability import profiler as _profiler

    if not _profiler.profiling_enabled():
        return False, None
    return _ledger.observe_call(site, _ledger.signature(args))


def _ledger_compile(site: str, duration_s, cause, jit_kwargs=None):
    """Record one ledger compile. ``duration_s`` is the dispatch wall
    time of the missing call — on a miss, trace+compile run
    synchronously before the async dispatch returns, so it is compile
    time to first order."""
    from ..observability import compile_ledger as _ledger

    donated = None
    if jit_kwargs:
        dn = jit_kwargs.get("donate_argnums")
        if dn is not None:
            donated = len(dn) if isinstance(dn, (tuple, list)) else 1
    _ledger.note_compile(site, duration_s=duration_s,
                         cause=cause or "first_call",
                         donated_args=donated)


class ChunkPrefetcher:
    """Assembles ``[n, ...]`` stacked chunks from a batch iterator on a
    background thread while the device runs the current chunk (the
    DataLoader-feeding-every-step analog of reference
    python/paddle/io/reader.py:262 + fluid/framework/data_feed.cc).

    ``source`` yields per-step batches (tuples/lists of arrays or
    Tensors); each chunk stacks ``n`` of them along a new leading axis,
    ready for ``TrainStep.run_steps_stream``. A trailing partial group
    (fewer than ``n`` batches) is dropped, like drop_last.
    """

    _SENTINEL = object()

    def __init__(self, source, n: int, depth: int = 2):
        import queue
        import threading

        if n <= 0:
            raise ValueError(f"chunk size must be >= 1, got {n}")
        self._n = n
        self._q = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._terminal = None  # StopIteration / surfaced error, sticky
        self._thread = threading.Thread(
            target=self._fill, args=(iter(source),), daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Bounded put that aborts when close() poisons the feeder."""
        import queue

        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _fill(self, it):
        import numpy as np

        try:
            while not self._stop.is_set():
                group = []
                for _ in range(self._n):
                    try:
                        group.append(next(it))
                    except StopIteration:
                        self._put(self._SENTINEL)
                        return
                group = [b if isinstance(b, (tuple, list)) else (b,)
                         for b in group]
                chunk = tuple(
                    np.stack([np.asarray(
                        b[i]._data if isinstance(b[i], Tensor) else b[i])
                        for b in group])
                    for i in range(len(group[0])))
                if not self._put(chunk):
                    return
        except BaseException as e:  # surfaced on the consumer side
            self._put(e)

    def close(self):
        """Stop the fill thread and release buffered chunks (call when
        abandoning iteration early)."""
        import queue

        self._stop.set()
        self._terminal = StopIteration()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __iter__(self):
        return self

    def __next__(self):
        if self._terminal is not None:
            raise self._terminal
        item = self._q.get()
        if item is self._SENTINEL:
            self._terminal = StopIteration()
            raise self._terminal
        if isinstance(item, BaseException):
            self._terminal = item
            raise item
        return item


def _tree_map_specs(state, like_specs, mesh, like_shapes=None):
    """Optimizer state entries shaped like a param inherit its sharding;
    scalars (and entries whose shapes don't match, e.g. 8-bit quantized
    moment codes/scales) are replicated. State is {"m": [per-param], ...}
    by convention: any list matching len(params) inherits param specs."""
    out = {}
    for k, v in state.items():
        if isinstance(v, (list, tuple)) and len(v) == len(like_specs):
            if like_shapes is None:
                out[k] = [NamedSharding(mesh, s) for s in like_specs]
            else:
                out[k] = [
                    NamedSharding(mesh, s) if tuple(e.shape) == tuple(sh)
                    else NamedSharding(mesh, PartitionSpec())
                    for e, s, sh in zip(v, like_specs, like_shapes)]
        else:
            out[k] = NamedSharding(mesh, PartitionSpec())
    return out


class TrainStep:
    """Build and run a fully-compiled train step for (model, optimizer).

    Usage::

        step = TrainStep(model, opt, mesh=mesh)          # mesh optional
        loss = step(input_ids, labels)                    # compiled
        step.sync_params_to_model()                       # write back

    ``loss_fn(model, *batch) -> scalar Tensor`` defaults to calling the
    model directly (CausalLM models return the loss when labels are given).
    """

    def __init__(self, model: Layer, optimizer: Optimizer,
                 mesh=None, loss_fn: Optional[Callable] = None,
                 batch_specs: Optional[Sequence] = None,
                 grad_clip_norm: Optional[float] = None,
                 fsdp_axis: Optional[str] = None,
                 accumulate_steps: int = 1,
                 donate: bool = True):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.grad_clip_norm = grad_clip_norm
        # gradient merge (reference: auto_parallel gradient_merge pass /
        # fleet accumulate_steps): micro-batches scan INSIDE the compiled
        # step, grads average, one optimizer update
        self.accumulate_steps = max(int(accumulate_steps), 1)
        self._names = [n for n, _ in model.named_parameters()]
        self._params = [p for _, p in model.named_parameters()]
        self._trainable = [not p.stop_gradient for p in self._params]
        self.param_arrays = [p._data for p in self._params]
        self._mesh = None
        self._process_mesh = None
        self._batch_specs = batch_specs
        self._fsdp_axis = fsdp_axis
        self._donate = donate
        self._step_count = 0
        if mesh is not None:
            self._setup_mesh(mesh)
        # init AFTER sharding is known: moments inherit the param shardings
        # instead of materializing ~2x model size unsharded first
        self.opt_state = optimizer.init_state(self.param_arrays)
        self._jitted = self._build(donate)

    # ------------------------------------------------------------------ mesh
    def _setup_mesh(self, mesh):
        from ..distributed.auto_parallel.process_mesh import ProcessMesh

        if isinstance(mesh, ProcessMesh):
            self._process_mesh = mesh  # activated only while tracing
            jmesh = mesh.get_jax_mesh()
        else:
            jmesh = mesh
        self._mesh = jmesh
        self._param_specs = []
        for p in self._params:
            spec = param_spec(p, jmesh)
            if self._fsdp_axis and self._fsdp_axis in jmesh.axis_names:
                spec = self._add_fsdp(spec, p)
            self._param_specs.append(spec)
        # place current values
        self.param_arrays = [
            jax.device_put(a, NamedSharding(jmesh, s))
            for a, s in zip(self.param_arrays, self._param_specs)]

    def _add_fsdp(self, spec: PartitionSpec, p) -> PartitionSpec:
        """ZeRO-style param sharding (reference: GroupSharded stage-3,
        fleet/meta_parallel/sharding/group_sharded_stage3.py:85): shard the
        first not-yet-sharded dim over the fsdp axis."""
        parts = list(spec) + [None] * (p.ndim - len(list(spec)))
        ax = self._fsdp_axis
        used = set()
        for s in parts:
            if isinstance(s, tuple):
                used.update(s)
            elif s is not None:
                used.add(s)
        if ax in used:
            return PartitionSpec(*parts)
        size = self._mesh.shape[ax]
        for i, s in enumerate(parts):
            if s is None and p.shape[i] % size == 0 and p.shape[i] >= size:
                parts[i] = ax
                return PartitionSpec(*parts)
        return PartitionSpec(*parts)

    # ----------------------------------------------------------------- build
    def _build(self, donate: bool):
        model, optimizer = self.model, self.optimizer
        params, trainable = self._params, self._trainable
        loss_fn = self.loss_fn
        clip = self.grad_clip_norm

        process_mesh = self._process_mesh

        accumulate = self.accumulate_steps
        # health policy is compiled INTO the program (loss-scaler
        # found_inf analog): capture it at build time so the traced step
        # is deterministic regardless of later env changes
        health_on = self._health_on = _health.enabled()

        def fwd_bwd(key, param_arrays, *batch):
            from ..distributed.auto_parallel.process_mesh import get_mesh, set_mesh

            saved = [p._data for p in params]
            prev_mesh = get_mesh()
            # activate the mesh only for the duration of the trace so eager
            # code outside this TrainStep is unaffected
            if process_mesh is not None:
                set_mesh(process_mesh)
            for p, a in zip(params, param_arrays):
                p._data = a
            try:
                with _rng.rng_guard(key):
                    batch_t = tuple(Tensor(b) for b in batch)
                    if loss_fn is not None:
                        loss = loss_fn(model, *batch_t)
                    elif len(batch_t) >= 2:
                        # (inputs..., labels) convention: labels go in by
                        # keyword so CausalLM forward signatures line up
                        loss = model(*batch_t[:-1], labels=batch_t[-1])
                    else:
                        loss = model(*batch_t)
                    grads = _autograd_grad([loss], params, allow_unused=True)
            finally:
                for p, a in zip(params, saved):
                    p._data = a
                if process_mesh is not None:
                    set_mesh(prev_mesh)
            grad_arrays = [
                g._data if g is not None else jnp.zeros_like(a)
                for g, a in zip(grads, param_arrays)]
            return loss._data, grad_arrays

        def pure_step(key, lr, param_arrays, opt_state, *batch):
            if accumulate > 1:
                # gradient-merge pass: scan micro-batch slices, average
                keys = jax.random.split(key, accumulate)
                chunks = tuple(
                    b.reshape((accumulate, b.shape[0] // accumulate)
                              + b.shape[1:]) for b in batch)

                def micro(carry, xs):
                    g_acc, l_acc = carry
                    k_i = xs[0]
                    mb = xs[1:]
                    l, gs = fwd_bwd(k_i, param_arrays, *mb)
                    return ([a + g for a, g in zip(g_acc, gs)],
                            l_acc + l), None

                # fp32 accumulators: k successive bf16 adds would round
                # away low-order gradient bits before the /k average
                init = ([jnp.zeros_like(a, dtype=jnp.float32)
                         for a in param_arrays],
                        jnp.zeros((), jnp.float32))
                (g_sum, l_sum), _ = jax.lax.scan(
                    micro, init, (keys,) + chunks)
                grad_arrays = [g / accumulate for g in g_sum]
                loss_val = (l_sum / accumulate).astype(jnp.float32)
            else:
                loss_val, grad_arrays = fwd_bwd(key, param_arrays, *batch)
            gnorm = None
            if clip is not None or health_on:
                # ONE fused whole-model reduction, shared by clipping and
                # the health monitor — no per-tensor host syncs
                with _scopes.phase("grad_norm"):
                    gnorm = _health.grad_health(grad_arrays)
            if clip is not None:
                with _scopes.phase("clip"):
                    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grad_arrays = [g * scale.astype(g.dtype)
                                   for g in grad_arrays]
            with _scopes.phase("optimizer"):
                new_params, new_state = optimizer.update(
                    list(param_arrays), grad_arrays, opt_state, lr=lr)
            # frozen params pass through unchanged
            new_params = [np_ if t else a for np_, a, t in
                          zip(new_params, param_arrays, trainable)]
            if health_on:
                # skip policy: non-finite grads keep the old params/state
                # (compiled select, no host round-trip)
                with _scopes.phase("optimizer"):
                    new_params, new_state = _health.apply_policy_in_step(
                        gnorm, new_params, list(param_arrays),
                        new_state, opt_state)
                # (loss, gnorm) under one replicated out_shardings leaf:
                # a pytree-prefix leaf broadcasts over the tuple
                return (loss_val, gnorm), tuple(new_params), new_state
            return loss_val, tuple(new_params), new_state

        kwargs = {}
        if donate:
            kwargs["donate_argnums"] = (2, 3)
        if self._mesh is not None:
            mesh = self._mesh
            pspecs = tuple(NamedSharding(mesh, s) for s in self._param_specs)
            state_specs = _tree_map_specs(
                self.opt_state, self._param_specs, mesh,
                like_shapes=[tuple(a.shape) for a in self.param_arrays])
            # align the actual state arrays with the declared in_shardings
            # (derived state, e.g. quantized moment codes, inherits
            # computed shardings from the params it was built from; jit
            # with explicit in_shardings rejects the mismatch)
            placed = {}
            for k, v in self.opt_state.items():
                sp = state_specs[k]
                if isinstance(v, (list, tuple)):
                    placed[k] = [jax.device_put(e, s)
                                 for e, s in zip(v, sp)]
                else:
                    placed[k] = jax.device_put(v, sp)
            self.opt_state = placed
            repl = NamedSharding(mesh, PartitionSpec())
            bspecs = self._batch_specs
            if bspecs is not None:
                in_batch = tuple(
                    NamedSharding(mesh, filtered_spec(b, mesh))
                    for b in bspecs)
                # flat per-arg shardings; the *batch args follow the pytrees
                kwargs["in_shardings"] = (repl, repl, pspecs, state_specs,
                                          *in_batch)
            kwargs["out_shardings"] = (repl, pspecs, state_specs)
        self._pure_step = pure_step
        self._jit_kwargs = dict(kwargs)
        self._multi_jitted = {}
        self._dispatch_programs = {}
        _count_jit(miss=True, cause="first_call")
        return jax.jit(pure_step, **kwargs)

    # ------------------------------------------------------------------- run
    def __call__(self, *batch):
        _count_jit(miss=False)
        arrays = self._prepare_batch(batch)
        miss, cause = _ledger_observe("train_step", arrays)
        key = _rng.next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
        with _obs.span("train.step", args={"n": 1}):
            t0 = _time.perf_counter() if miss else 0.0
            out, self.param_arrays, self.opt_state = self._jitted(
                key, lr, tuple(self.param_arrays), self.opt_state, *arrays)
            if miss:
                _ledger_compile("train_step",
                                _time.perf_counter() - t0, cause,
                                self._jit_kwargs)
        base = self._step_count
        self._step_count += 1
        # rebind model params to the fresh arrays: the old ones were donated
        # to XLA (deleted on TPU), and eager use of the model must keep
        # working between steps. This is a pointer swap, not a copy.
        self.sync_params_to_model()
        if self._health_on:
            loss, gnorm = out
            _health.record_step(float(gnorm), source="grad", step=base)
            return Tensor(loss)
        return Tensor(out)

    def _prepare_batch(self, batch, leading_steps: Optional[int] = None):
        """Convert/validate/shard a batch. With ``leading_steps=n`` the
        arrays are stacked per-step chunks [n, batch, ...]: the leading
        axis must equal n, the divisibility check applies to the INNER
        batch dim, and shardings gain a replicated leading axis."""
        arrays = tuple(b._data if isinstance(b, Tensor) else jnp.asarray(b)
                       for b in batch)
        bdim = 0 if leading_steps is None else 1
        if leading_steps is not None:
            for a in arrays:
                if not a.ndim or a.shape[0] != leading_steps:
                    raise ValueError(
                        f"run_steps_stream({leading_steps}): stacked "
                        f"arrays need leading dim {leading_steps}, "
                        f"got {a.shape}")
        if self.accumulate_steps > 1:
            for a in arrays:
                if a.ndim > bdim and a.shape[bdim] % self.accumulate_steps:
                    raise ValueError(
                        f"gradient merge: batch dim {a.shape[bdim]} is not "
                        f"divisible by accumulate_steps="
                        f"{self.accumulate_steps}")
        if self._mesh is not None and self._batch_specs is not None:
            def shard(s):
                spec = filtered_spec(s, self._mesh)
                if leading_steps is not None:
                    spec = PartitionSpec(None, *spec)
                return NamedSharding(self._mesh, spec)

            arrays = tuple(jax.device_put(a, shard(s))
                           for a, s in zip(arrays, self._batch_specs))
        return arrays

    def run_steps(self, n: int, *batch):
        """Run ``n`` chained optimizer steps in ONE compiled program /
        device dispatch (same batch each step). Amortises the per-dispatch
        host cost — the standard pattern for TPU training loops driven
        from a single controller. Returns the last step's loss.

        The learning rate is read once and held constant for the whole
        chunk: an LRScheduler advances on host-side ``scheduler.step()``
        calls, which cannot happen inside the compiled chunk. Call
        run_steps with chunks no longer than your LR update granularity.
        """
        if n == 1:
            return self(*batch)
        if n <= 0:
            raise ValueError(f"run_steps needs n >= 1, got {n}")
        _count_jit(miss=n not in self._multi_jitted, cause="chunk_size")
        if n not in self._multi_jitted:
            pure = self._pure_step
            health_on = self._health_on

            def multi(keys, lr, params, state, *arrays):
                # lax.scan: one compiled step body regardless of n
                def body(carry, key):
                    params, state = carry
                    loss, params, state = pure(key, lr, params, state,
                                               *arrays)
                    return (params, state), loss

                (params, state), ys = jax.lax.scan(
                    body, (params, state), keys)
                if health_on:
                    # ys = (losses[n], gnorms[n]): last loss, ALL gnorms
                    # so the host can attribute non-finite steps
                    return (ys[0][-1], ys[1]), params, state
                return ys[-1], params, state

            self._multi_jitted[n] = jax.jit(multi, **self._jit_kwargs)
        arrays = self._prepare_batch(batch)
        miss, cause = _ledger_observe("train_step.run_steps",
                                      (n,) + arrays)
        keys = jnp.stack([_rng.next_key() for _ in range(n)])
        lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
        with _obs.span("train.step", args={"n": n}):
            t0 = _time.perf_counter() if miss else 0.0
            out, self.param_arrays, self.opt_state = self._multi_jitted[n](
                keys, lr, tuple(self.param_arrays), self.opt_state, *arrays)
            if miss:
                _ledger_compile("train_step.run_steps",
                                _time.perf_counter() - t0, cause,
                                self._jit_kwargs)
        base = self._step_count
        self._step_count += n
        self.sync_params_to_model()
        return Tensor(self._record_chunk_health(out, base))

    def _chunk_lrs(self, n: int):
        """Per-step learning rates for an n-step chunk; advances a host
        LRScheduler by n so chunked training matches the step-by-step
        schedule (fixes the frozen-LR caveat of run_steps)."""
        from ..optimizer.lr import LRScheduler

        lr = self.optimizer._learning_rate
        if isinstance(lr, LRScheduler):
            vals = []
            for _ in range(n):
                vals.append(float(lr()))
                lr.step()
            return jnp.asarray(vals, jnp.float32)
        return jnp.full((n,), float(lr), jnp.float32)

    def run_steps_stream(self, n: int, *stacked, lrs=None):
        """``n`` chained optimizer steps in ONE dispatch, each step
        consuming its OWN batch slice from ``stacked`` arrays of shape
        ``[n, batch, ...]`` and its own learning rate — genuine training
        on fresh data per step, not the same-batch replay of
        ``run_steps`` (reference analog: the DataLoader feeding every
        executor step, python/paddle/io/reader.py:262).

        ``lrs`` is an optional ``[n]`` float32 array; by default it is
        generated from the optimizer's scheduler (advancing it n steps).
        Pair with ``ChunkPrefetcher`` to assemble the next chunk on the
        host while the device runs the current one.
        """
        if n <= 0:
            raise ValueError(f"run_steps_stream needs n >= 1, got {n}")
        cache_key = ("stream", n)
        _count_jit(miss=cache_key not in self._multi_jitted,
                   cause="chunk_size")
        if cache_key not in self._multi_jitted:
            pure = self._pure_step
            health_on = self._health_on

            def multi(keys, lrs, params, state, *stacked_arrays):
                def body(carry, xs):
                    params, state = carry
                    key, lr = xs[0], xs[1]
                    mb = xs[2:]
                    loss, params, state = pure(key, lr, params, state, *mb)
                    return (params, state), loss

                (params, state), ys = jax.lax.scan(
                    body, (params, state), (keys, lrs) + stacked_arrays)
                if health_on:
                    return (ys[0][-1], ys[1]), params, state
                return ys[-1], params, state

            kwargs = dict(self._jit_kwargs)
            if "in_shardings" in kwargs:
                repl, _, pspecs, state_specs = kwargs["in_shardings"][:4]
                stream_specs = tuple(
                    NamedSharding(self._mesh, PartitionSpec(
                        None, *filtered_spec(b, self._mesh)))
                    for b in self._batch_specs)
                kwargs["in_shardings"] = (repl, repl, pspecs, state_specs,
                                          *stream_specs)
            self._multi_jitted[cache_key] = jax.jit(multi, **kwargs)
        arrays = self._prepare_batch(stacked, leading_steps=n)
        miss, cause = _ledger_observe("train_step.run_steps_stream",
                                      (n,) + arrays)
        if lrs is not None:
            lrs = jnp.asarray(lrs, jnp.float32)
            if lrs.shape != (n,):
                raise ValueError(f"lrs must have shape ({n},), "
                                 f"got {lrs.shape}")
        # snapshot the scheduler so a trace-time failure doesn't leave the
        # host LR schedule advanced past the steps that never ran
        from ..optimizer.lr import LRScheduler

        sched = self.optimizer._learning_rate
        snapshot = sched.state_dict() if (
            lrs is None and isinstance(sched, LRScheduler)) else None
        if lrs is None:
            lrs = self._chunk_lrs(n)
        keys = jnp.stack([_rng.next_key() for _ in range(n)])
        if n not in self._dispatch_programs:
            # once a dispatch's jit: the ledger's way back to its program
            ledger = _obs.compile_ledger
            jitted = self._multi_jitted[cache_key]
            args = ledger.abstract_args(
                (keys, lrs, tuple(self.param_arrays), self.opt_state)
                + arrays)
            self._dispatch_programs[n] = ledger.register_program(
                "train_step.run_steps_stream",
                ledger.SiteProgram(lambda: jitted.lower(*args)))
        try:
            with _obs.span("train.step", args={"n": n, "stream": True}):
                t0 = _time.perf_counter() if miss else 0.0
                out, self.param_arrays, self.opt_state = self._multi_jitted[
                    cache_key](keys, lrs, tuple(self.param_arrays),
                               self.opt_state, *arrays)
                if miss:
                    _ledger_compile("train_step.run_steps_stream",
                                    _time.perf_counter() - t0, cause,
                                    self._jit_kwargs)
        except Exception:
            if snapshot is not None:
                sched.set_state_dict(snapshot)
            raise
        base = self._step_count
        self._step_count += n
        self.sync_params_to_model()
        return Tensor(self._record_chunk_health(out, base))

    def compiled_dispatch(self, n: int):
        """The ``jax.stages.Compiled`` of exactly the jit
        ``run_steps_stream(n, ...)`` calls: same function object, donation
        and shardings, the shapes of its first call, so jit hands back the
        executable the dispatch runs (in a fresh process: a load from the
        persistent compile cache) and not a second program. Lowered on the
        first call and kept. The compile ledger keeps the
        same program for site ``train_step.run_steps_stream`` until another
        dispatch registers there, for ``observability.op_phases``; the jit's
        closure holds this step's model and optimizer, so until that first
        lowering (or ``compile_ledger.reset()``) they stay alive with it;
        the arguments are kept as shapes and shardings, never arrays."""
        if n not in self._dispatch_programs:
            raise RuntimeError(
                f"compiled_dispatch({n}): run_steps_stream({n}, ...) has not "
                "run, so the dispatch has no shapes yet")
        return self._dispatch_programs[n].compiled()

    def _record_chunk_health(self, out, base: int):
        """Unpack a chunk result; with health on, record every step's
        grad norm from ONE device->host transfer of the [n] gnorm
        vector. Returns the last-step loss array."""
        if not self._health_on:
            return out
        import numpy as np

        loss, gnorms = out
        for i, g in enumerate(np.asarray(gnorms)):
            _health.record_step(float(g), source="grad", step=base + i)
        return loss

    def sync_params_to_model(self):
        for p, a in zip(self._params, self.param_arrays):
            p._data = a

    def restore_state(self, opt_state=None):
        """Re-adopt the model's current parameter arrays (after an
        in-place ``load_state_dict``) and optionally replace the
        optimizer state — the checkpoint-resume path. Re-applies the
        mesh placement so restored host arrays match the compiled
        step's declared in_shardings."""
        arrays = [jnp.asarray(p._data) for p in self._params]
        if self._mesh is not None:
            arrays = [jax.device_put(a, NamedSharding(self._mesh, s))
                      for a, s in zip(arrays, self._param_specs)]
        self.param_arrays = arrays
        self.sync_params_to_model()
        if opt_state is None:
            return
        state = {k: [jnp.asarray(e) for e in v]
                 if isinstance(v, (list, tuple)) else jnp.asarray(v)
                 for k, v in opt_state.items()}
        if self._mesh is not None:
            specs = _tree_map_specs(
                state, self._param_specs, self._mesh,
                like_shapes=[tuple(a.shape) for a in self.param_arrays])
            placed = {}
            for k, v in state.items():
                sp = specs[k]
                if isinstance(v, (list, tuple)):
                    placed[k] = [jax.device_put(e, s)
                                 for e, s in zip(v, sp)]
                else:
                    placed[k] = jax.device_put(v, sp)
            state = placed
        self.opt_state = state

    def lower(self, *batch):
        """AOT-lower for inspection (cost_analysis) without compiling."""
        arrays = tuple(b._data if isinstance(b, Tensor) else jnp.asarray(b)
                       for b in batch)
        key = _rng.next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
        return self._jitted.lower(key, lr, tuple(self.param_arrays),
                                  self.opt_state, *arrays)

    def compile(self, *batch):
        """AOT-lower for inspection/warmup without running. With step
        profiling on, the compile lands in the compile ledger with its
        exact duration (this is the one path where compile time is
        directly measurable, not inferred from a missing dispatch) and
        its XLA memory analysis feeds the memory ledger."""
        from ..observability import profiler as _profiler

        lowered = self.lower(*batch)
        if not _profiler.profiling_enabled():
            return lowered.compile()
        t0 = _time.perf_counter()
        compiled = lowered.compile()
        dur = _time.perf_counter() - t0
        hlo_bytes = None
        try:
            ma = compiled.memory_analysis()
            hlo_bytes = int(
                getattr(ma, "generated_code_size_in_bytes", 0)) or None
        except Exception:
            pass
        from ..observability import compile_ledger as _ledger
        from ..observability import xla_cost as _xla_cost

        dn = self._jit_kwargs.get("donate_argnums")
        _ledger.note_compile(
            "train_step.aot", duration_s=dur, cause="aot_compile",
            hlo_bytes=hlo_bytes,
            donated_args=(len(dn) if isinstance(dn, (tuple, list))
                          else 1 if dn is not None else None))
        _xla_cost.record_memory_analysis("train_step.aot", compiled)
        return compiled

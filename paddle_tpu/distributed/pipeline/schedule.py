"""The whole 1F1B micro-batch pipeline schedule as ONE compiled program.

:class:`CompiledPipeline` runs every stage of a uniform pipeline on its
own device of a ``("pp"[, "dp"])`` mesh and executes the full
forward/backward/update for a batch of ``M`` micro-batches in a single
``jax.jit`` dispatch:

* Per-stage params are STACKED (leaves ``[S, ...]``) and sharded
  ``P("pp")`` so stage ``s``'s slice lives on device ``s``.
* A ``lax.scan`` over ``T = M + S - 1`` ticks drives the software
  pipeline: each tick every stage receives its upstream boundary tensor
  via :func:`~.transport.ring_shift` (XLA ``collective-permute`` — the
  payload never leaves device HBM), runs its stage function, and passes
  the result on. Stage 0 masks the ring's wrap-around edge with its own
  micro-batch input, which also zeroes cotangents through the wrap edge
  under AD.
* Gradients are computed by ``jax.value_and_grad`` INSIDE the
  ``shard_map`` body; the transpose of ``ring_shift`` is the reverse
  ring step, so the backward dependency DAG is exactly 1F1B's and XLA
  interleaves each stage's backward ticks with the remaining forward
  ticks of later micro-batches. Data-parallel gradient reduction is
  issued per layer bucket *during* backward by
  :func:`~.overlap.bucket_taps` (``PADDLE_TPU_PP_BUCKET_MB``), not as a
  trailing barrier.
* The optimizer update runs inside the same jit on the flat param list,
  so steady state is exactly one executable launch per train step:
  fixed shapes, zero recompiles (``trace_count`` asserts it, like the
  serving decode step).

:class:`CompiledStagedTrainStep` adapts a uniform
:class:`~..passes.pipeline_partition.StagedProgram` to this engine so
``Engine.fit`` can swap it in for the host-driven ``_StagedTrainStep``
when ``PADDLE_TPU_PP_TRANSPORT=device``.
"""
from __future__ import annotations

import logging
from typing import Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ... import observability as _obs
from .overlap import bucket_taps, record_bucket_gauge, make_buckets
from .transport import ring_shift

logger = logging.getLogger("paddle_tpu.distributed.pipeline")

__all__ = ["CompiledPipeline", "CompiledStagedTrainStep"]


def _tree_flat(tree):
    return jax.tree_util.tree_flatten(tree)


class CompiledPipeline:
    """One-jit 1F1B pipeline over a ``("pp"[, "dp"])`` device mesh.

    Args:
        stage_fn: ``(stage_params, h) -> h`` — the per-stage compute; the
            SAME function for every stage (uniform pipeline), applied to
            stage ``s``'s slice of ``stacked_params``.
        stacked_params: pytree whose leaves are stacked per-stage arrays
            ``[S, ...]``.
        loss_fn: ``(extra_params, h_last, y_micro) -> scalar`` mean loss
            of one micro-batch (runs on the last stage; masked
            elsewhere).
        num_stages / num_micro: pipeline depth ``S`` and micro-batch
            count ``M`` (batch size must divide by ``M``).
        optimizer: functional optimizer (``init_state``/``update``) or
            None for loss/grad-only stepping.
        extra_params: pytree of params shared across stage boundaries
            (embeddings, head, final norm); replicated on every device.
        pre_fn: ``(extra_params, x_micro) -> h0`` input embedding to the
            stage-0 boundary tensor; identity when None.
        devices: flat device list (pp-major: ``pp * dp`` entries).
        dp: data-parallel degree (batch split across it; grads bucket-
            psummed over it during backward).
    """

    def __init__(self, stage_fn: Callable, stacked_params, loss_fn: Callable,
                 num_stages: int, num_micro: int, optimizer=None,
                 extra_params=None, pre_fn: Optional[Callable] = None,
                 devices: Optional[Sequence] = None, dp: int = 1,
                 bucket_bytes: Optional[int] = None):
        self.stage_fn = stage_fn
        self.loss_fn = loss_fn
        self.pre_fn = pre_fn
        self.optimizer = optimizer
        self.S = int(num_stages)
        self.M = int(num_micro)
        self.dp = int(dp)
        self._bucket_bytes = bucket_bytes
        self.trace_count = 0

        if devices is None:
            devices = jax.devices()[: self.S * self.dp]
        devices = list(devices)
        if len(devices) < self.S * self.dp:
            raise ValueError(
                f"CompiledPipeline needs {self.S * self.dp} devices "
                f"(pp={self.S} x dp={self.dp}), got {len(devices)}")
        dev_grid = np.array(devices[: self.S * self.dp]).reshape(
            self.S, self.dp)
        if self.dp > 1:
            self.mesh = Mesh(dev_grid, ("pp", "dp"))
            self._x_spec = P(None, "dp")   # [M, mb, ...]: micro dim whole
            self._reduce_axes = ("pp", "dp")
        else:
            self.mesh = Mesh(dev_grid.reshape(self.S), ("pp",))
            self._x_spec = P()
            self._reduce_axes = ("pp",)

        stacked_sh = NamedSharding(self.mesh, P("pp"))
        repl_sh = NamedSharding(self.mesh, P())
        self.params = jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), stacked_sh),
            stacked_params)
        self.extra = jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), repl_sh),
            extra_params if extra_params is not None else {})

        flat_p, _ = _tree_flat((self.params, self.extra))
        self.opt_state = optimizer.init_state(flat_p) \
            if optimizer is not None else {}
        self.n_buckets = len(make_buckets(flat_p, self._bucket_bytes))
        record_bucket_gauge(self.n_buckets)

        # one jit for the whole schedule; params/opt_state donated so
        # steady state updates in place (donation is a no-op on cpu)
        donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
        self._jit_step = jax.jit(self._step, donate_argnums=donate)

    # ------------------------------------------------------------- traced
    def _body(self, stacked, extra, xs, ys):
        """shard_map body: local 1F1B scan + in-body AD + bucketed psum."""
        S, M = self.S, self.M
        sidx = jax.lax.axis_index("pp")

        def objective(p):
            stacked_l, extra_l = p
            if self.dp > 1:
                leaves, tdef = _tree_flat(stacked_l)
                leaves = bucket_taps(leaves, "dp", self._bucket_bytes)
                stacked_l = jax.tree_util.tree_unflatten(tdef, leaves)
            e_leaves, e_def = _tree_flat(extra_l)
            if e_leaves:
                e_leaves = bucket_taps(e_leaves, self._reduce_axes,
                                       self._bucket_bytes)
                extra_l = jax.tree_util.tree_unflatten(e_def, e_leaves)
            stage_params = jax.tree_util.tree_map(lambda a: a[0], stacked_l)

            def embed(xm):
                return self.pre_fn(extra_l, xm) if self.pre_fn is not None \
                    else xm

            bspec = jax.eval_shape(
                embed, jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype))
            from ...observability import profiler as _profiler

            if _profiler.profiling_enabled():  # ptlint: disable=jit-purity
                # trace-time geometry note: one boundary activation hops
                # the ring per tick over M+S-1 ticks; the fill/drain
                # bubble's S-1 hops are the exposed ones
                hop = bspec.dtype.itemsize
                for d in bspec.shape:
                    hop *= int(d)
                _profiler.note_pipeline_overlap("pp", hop, M, S)

            def tick(carry, t):
                y_prev, acc = carry
                recv = ring_shift(y_prev, "pp", S)
                i_in = jnp.clip(t, 0, M - 1)
                xm = jax.lax.dynamic_index_in_dim(xs, i_in, 0,
                                                  keepdims=False)
                h_in = jnp.where(sidx == 0, embed(xm), recv)
                yv = self.stage_fn(stage_params, h_in)
                out_i = jnp.clip(t - (S - 1), 0, M - 1)
                old = jax.lax.dynamic_index_in_dim(acc, out_i, 0,
                                                   keepdims=False)
                acc = jax.lax.dynamic_update_index_in_dim(
                    acc, jnp.where(t >= S - 1, yv, old), out_i, 0)
                return (yv, acc), None

            y0 = jnp.zeros(bspec.shape, bspec.dtype)
            acc0 = jnp.zeros((M,) + tuple(bspec.shape), bspec.dtype)
            (_, acc), _ = jax.lax.scan(tick, (y0, acc0),
                                       jnp.arange(M + S - 1))
            losses = jax.vmap(
                lambda h, ym: self.loss_fn(extra_l, h, ym))(acc, ys)
            # local objective scaled so the per-bucket psums over dp give
            # exactly the global-mean gradient
            local = jnp.mean(losses) / self.dp
            return jnp.where(sidx == S - 1, local, 0.0)

        loss_local, grads = jax.value_and_grad(objective)(
            (stacked, extra))
        loss = jax.lax.psum(loss_local, self._reduce_axes)
        return loss, grads[0], grads[1]

    def _step(self, params, extra, opt_state, x, y):
        self.trace_count += 1  # ptlint: disable=jit-purity
        if _obs.enabled():  # ptlint: disable=jit-purity
            _obs.registry.counter("pipeline.compiles").inc()
        M = self.M
        xs = x.reshape((M, x.shape[0] // M) + x.shape[1:])
        ys = y.reshape((M, y.shape[0] // M) + y.shape[1:])
        pipe = jax.shard_map(
            self._body, mesh=self.mesh,
            in_specs=(P("pp"), P(), self._x_spec, self._x_spec),
            out_specs=(P(), P("pp"), P()),
            check_vma=False)
        loss, g_stacked, g_extra = pipe(params, extra, xs, ys)
        flat_p, pdef = _tree_flat((params, extra))
        flat_g, _ = _tree_flat((g_stacked, g_extra))
        if self.optimizer is not None:
            new_flat, new_state = self.optimizer.update(
                flat_p, flat_g, opt_state)
            new_flat = [n.astype(p.dtype) for n, p in zip(new_flat, flat_p)]
        else:
            new_flat, new_state = flat_p, opt_state
        new_params, new_extra = jax.tree_util.tree_unflatten(pdef, new_flat)
        return loss, new_params, new_extra, new_state

    # -------------------------------------------------------------- eager
    def step(self, x, y):
        """Run one train step over the full batch; returns the loss array."""
        if _obs.enabled():
            _obs.registry.counter("pipeline.steps").inc()
        with _obs.span("pipeline.step", cat="pipeline",
                       args={"micro": self.M, "stages": self.S}):
            loss, self.params, self.extra, self.opt_state = self._jit_step(
                self.params, self.extra, self.opt_state,
                jnp.asarray(x), jnp.asarray(y))
        return loss

    def loss_and_grads(self, x, y):
        """Loss + grads without the optimizer update (parity testing)."""
        M = self.M
        xs = jnp.asarray(x).reshape(
            (M, x.shape[0] // M) + tuple(x.shape[1:]))
        ys = jnp.asarray(y).reshape(
            (M, y.shape[0] // M) + tuple(y.shape[1:]))
        pipe = jax.shard_map(
            self._body, mesh=self.mesh,
            in_specs=(P("pp"), P(), self._x_spec, self._x_spec),
            out_specs=(P(), P("pp"), P()),
            check_vma=False)
        return jax.jit(pipe)(self.params, self.extra, xs, ys)


class CompiledStagedTrainStep:
    """Engine bridge: a uniform ``StagedProgram`` on ``CompiledPipeline``.

    Drop-in for the host-driven ``_StagedTrainStep``: same
    ``__call__(*batch) -> Tensor(loss)`` contract including per-step
    writeback of updated params into the model's segment params. Raises
    ``ValueError`` at construction when the staged program is not
    uniform (differing per-stage param shapes) — callers fall back to
    the host path.
    """

    def __init__(self, staged, optimizer, micro: int,
                 devices: Optional[Sequence] = None):
        from ...core.tensor import Tensor  # noqa: F401  (writeback)

        self.staged = staged
        self.optimizer = optimizer
        self.micro = int(micro)
        stages = staged.stages
        seg_params = staged.segment_params
        n = len(stages)
        if n < 2:
            raise ValueError("compiled pipeline needs >= 2 stages")
        shapes0 = [(tuple(p.shape), str(p.dtype)) for p in seg_params[0]]
        for s in range(1, n):
            shapes_s = [(tuple(p.shape), str(p.dtype))
                        for p in seg_params[s]]
            if shapes_s != shapes0:
                raise ValueError(
                    "staged program is not uniform (stage %d params %s != "
                    "stage 0 %s); device-compiled pipeline requires "
                    "identical stages — use the host transport" %
                    (s, shapes_s, shapes0))
        stacked = [jnp.stack([jnp.asarray(seg_params[s][i]._data)
                              for s in range(n)])
                   for i in range(len(seg_params[0]))]
        stage0 = stages[0]

        def stage_fn(param_list, h):
            return stage0(param_list, h)

        def loss_fn(_extra, h, ym):
            return self.staged.loss_fn(h, ym)

        self.pipe = CompiledPipeline(
            stage_fn, stacked, loss_fn, num_stages=n, num_micro=self.micro,
            optimizer=optimizer, devices=devices)
        self._seg_params = seg_params
        self.trace_count = 0

    def __call__(self, *batch):
        from ...core.tensor import Tensor

        arrs = [b._data if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]
        x, y = arrs[0], arrs[1]
        loss = self.pipe.step(x, y)
        self.trace_count = self.pipe.trace_count
        self._writeback()
        return Tensor(loss)

    def _writeback(self):
        for i, leaf in enumerate(self.pipe.params):
            for s, plist in enumerate(self._seg_params):
                plist[i]._data = leaf[s]
                self.staged.params[s][i] = leaf[s]

    def sync_params_to_model(self):
        self._writeback()

    def restore_state(self, opt_state=None):
        flat_p, _ = _tree_flat((self.pipe.params, self.pipe.extra))
        self.pipe.opt_state = opt_state if opt_state is not None else (
            self.optimizer.init_state(flat_p)
            if self.optimizer is not None else {})

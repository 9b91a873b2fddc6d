"""Decomposed computation–collective overlap for sharded matmuls
(PADDLE_TPU_TP_OVERLAP).

The TP/DP axes run matmul-then-collective serially: every column/row
parallel linear pays full collective latency after (or before) its GEMM.
Following T3 (arxiv 2401.16677) and fused computation-collective operations
(arxiv 2305.06942), this module decomposes those matmuls into ring/chunk
steps so the communication of one chunk rides inside the computation of the
next:

* :func:`all_gather_matmul` — column-parallel forward: instead of
  ``matmul(all_gather(x), w)``, the locally-held activation block is
  multiplied while the next rank's block arrives over a ``lax.ppermute``
  ring (one step per rank, each step itself row-chunked). Its custom VJP
  reproduces the monolithic gradient DAG: dx is the decomposed
  matmul-reduce-scatter of ``g @ w.T`` (the transpose of all-gather is
  reduce-scatter) and dw contracts the ring-regathered activations in one
  2D dot — bitwise equal to ``jax.vjp`` of the monolithic composition.
* :func:`matmul_reduce_scatter` — row-parallel forward: instead of
  ``psum_scatter(matmul(x, w))``, each destination block's partial product
  is computed just-in-time and added into an accumulator that rides the
  reverse ring, so every step overlaps one block GEMM with one permute.
  Its VJP runs the dual decomposed all-gather-matmul.

Numerics contract: splitting a matmul by output ROWS is bitwise-exact (each
output row is an independent dot product), and the ring all-gather is pure
data movement, so ``all_gather_matmul`` == monolithic composition bitwise
at any ring size. ``matmul_reduce_scatter`` splits only the already-sharded
contraction the monolithic ``psum_scatter`` also splits: the per-block sums
add the same operands in the same rank order, so it is bitwise vs the
monolithic sharded composition at 2 ranks and tolerance-equal beyond
(reduction association). tests/test_tp_overlap.py enforces both.

Knobs (read at trace time, same discipline as the fusion/quant knobs):

  - ``PADDLE_TPU_TP_OVERLAP=auto|on|off`` — ``auto`` (default) behaves
    as ``on``; ``off`` routes every wired call site through the original
    serial composition, restoring pre-overlap numerics byte-for-byte.
    Ring steps are ``lax.ppermute``.
  - ``PADDLE_TPU_TP_OVERLAP_CHUNKS`` — row chunks per ring step
    (default 2). More chunks = finer overlap granularity, more launch
    overhead; chunk counts are clamped to divisors of the token dim.

The quantized path (PADDLE_TPU_MM_QUANT) composes: per-token activation
scales and per-channel weight scales are chunk-independent, so the chunked
int8/fp8 GEMM is bitwise equal to the unchunked one and overlap keeps the
PR 7 drift contract unchanged.
"""
from __future__ import annotations

import contextlib
import contextvars
import math

import jax
import jax.numpy as jnp

from ..config import knobs
from .quant import qmm

__all__ = [
    "mode", "enabled", "default_chunks", "override", "route",
    "all_gather_matmul", "matmul_reduce_scatter",
    "sharded_all_gather_matmul", "sharded_matmul_reduce_scatter",
    "chunked_mm", "region_mm", "overlap_linear",
]

_MODES = ("auto", "on", "off")

# Per-context override so a trace scope (train-step build, test) can pin the
# overlap mode / chunk count, mirroring fusion._forced.
_forced: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_tp_overlap_forced", default=(None, None))


# ------------------------------------------------------------------ knobs
def mode() -> str:
    """Resolved overlap mode: "on" or "off" ("auto" -> "on")."""
    forced = _forced.get()[0]
    raw = forced if forced is not None else \
        knobs.get_str("PADDLE_TPU_TP_OVERLAP").strip().lower()
    if raw not in _MODES:
        raise ValueError(
            f"PADDLE_TPU_TP_OVERLAP={raw!r}: expected one of {_MODES}")
    return "on" if raw == "auto" else raw


def enabled() -> bool:
    return mode() != "off"


def _raw_mode() -> str:
    """Unresolved mode: distinguishes an explicit "on" from "auto"."""
    forced = _forced.get()[0]
    raw = forced if forced is not None else \
        knobs.get_str("PADDLE_TPU_TP_OVERLAP").strip().lower()
    return raw if raw in _MODES else "auto"


def default_chunks() -> int:
    """Row chunks per ring step (PADDLE_TPU_TP_OVERLAP_CHUNKS, default 2)."""
    forced = _forced.get()[1]
    if forced is not None:
        return max(1, int(forced))
    return max(1, knobs.get_int("PADDLE_TPU_TP_OVERLAP_CHUNKS"))


@contextlib.contextmanager
def override(tp_overlap=None, chunks=None):
    """Pin overlap mode / chunk count for the current context. Forcing
    ``chunks`` also engages the model-level chunked path without an active
    mp mesh (how tests exercise overlap-on == off parity on one device)."""
    prev = _forced.get()
    tok = _forced.set((tp_overlap if tp_overlap is not None else prev[0],
                       chunks if chunks is not None else prev[1]))
    try:
        yield
    finally:
        _forced.reset(tok)


def route(op: str) -> bool:
    """Per-call-site overlap dispatch + telemetry: True means take the
    decomposed-overlap path for ``op``, False the serial composition."""
    m = mode()
    from .. import observability as _obs

    if _obs.enabled():
        _obs.registry.counter("tp.overlap_calls",
                              tags={"op": op, "mode": m}).inc()
    return m != "off"


def _note_chunks(chunks: int) -> None:
    from .. import observability as _obs

    if _obs.enabled():
        _obs.registry.gauge("tp.overlap_chunks").set(int(chunks))  # ptlint: disable=jit-purity (static chunk count)


def _note_ring_geometry(op: str, x, w, size: int) -> None:
    """Trace-time TP overlap-geometry note for the step profiler: each
    of the ring's ``size-1`` permute hops moves one x-sized buffer and
    rides inside one per-block GEMM. Static shapes only — never touches
    tracer values."""
    from ..observability import profiler as _profiler

    if not _profiler.profiling_enabled() or size <= 1:  # ptlint: disable=jit-purity (static profiling gate)
        return
    elems = 1
    for d in x.shape:
        elems *= int(d)
    hop_bytes = elems * jnp.dtype(x.dtype).itemsize
    gemm_flops = 2.0 * elems * int(w.shape[-1])  # ptlint: disable=jit-purity (static weight shape)
    _profiler.note_ring_overlap("tp", hop_bytes, gemm_flops, size - 1,
                                detail={"op": op})


# ------------------------------------------------------- chunked local GEMM
def _clamp_chunks(t: int, chunks: int) -> int:
    # largest divisor of the token dim not exceeding the requested count —
    # chunking must never change shapes, only split them
    return max(1, math.gcd(int(t), max(1, int(chunks))))  # ptlint: disable=jit-purity (trace-time shape/chunk config, never a tracer)


def _mm(a, w, quant_mode):
    return qmm(a, w, quant_mode) if quant_mode != "off" else jnp.matmul(a, w)


def _chunked_rows_mm(x, w, chunks, quant_mode="off"):
    """``x @ w`` split by leading-dim row chunks — bitwise equal to the
    monolithic matmul (each output row is an independent dot product)."""
    chunks = _clamp_chunks(x.shape[0], chunks)
    if chunks <= 1:
        return _mm(x, w, quant_mode)
    return jnp.concatenate(
        [_mm(c, w, quant_mode) for c in jnp.split(x, chunks, axis=0)], axis=0)


def _flat_dw(x, g):
    """dw = x^T g contracted over all leading dims as ONE 2D dot — the
    form that is bitwise equal to ``jax.vjp(jnp.matmul)``'s dw."""
    k, n = x.shape[-1], g.shape[-1]
    return jnp.matmul(x.reshape(-1, k).T, g.reshape(-1, n))


def _chunked_dx(g, w, chunks):
    """dx = g @ w^T split by row chunks (bitwise equal to the vjp dx)."""
    return _chunked_rows_mm(g, jnp.swapaxes(w, -1, -2), chunks)


# ------------------------------------------------------------- ring steps
def _ppermute_step(x, axis_name, size):
    # forward ring step: rank r receives rank (r-1)'s buffer
    return jax.lax.ppermute(
        x, axis_name, perm=[(i, (i + 1) % size) for i in range(size)])


# ----------------------------------------------------- ring primitive cores
def _ring_gather(x, axis_name, size):
    """All-gather along the leading dim via ring steps — pure data
    movement, bitwise equal to ``lax.all_gather(..., tiled=True)``."""
    t = x.shape[0]
    r = jax.lax.axis_index(axis_name)
    out = jnp.zeros((t * size,) + x.shape[1:], x.dtype)
    buf = x
    for step in range(size):
        src = jax.lax.rem(r - step + size, size)
        nxt = _ppermute_step(buf, axis_name, size) if step < size - 1 \
            else None
        out = jax.lax.dynamic_update_slice_in_dim(out, buf, src * t, axis=0)
        if nxt is not None:
            buf = nxt
    return out


def _agmm_impl(x, w, axis_name, size, chunks, quant_mode):
    """Ring all-gather-matmul forward: rank r multiplies block (r-step)
    at step ``step`` while shifting its buffer one hop, so every permute
    rides inside a GEMM. Output holds ALL token blocks (gathered) against
    this rank's weight columns."""
    t = x.shape[0]
    _note_ring_geometry("agmm", x, w, size)
    r = jax.lax.axis_index(axis_name)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    out = jnp.zeros((t * size,) + x.shape[1:-1] + (w.shape[-1],), out_dtype)
    buf = x
    for step in range(size):
        src = jax.lax.rem(r - step + size, size)
        nxt = _ppermute_step(buf, axis_name, size) if step < size - 1 \
            else None
        part = _chunked_rows_mm(buf, w, chunks, quant_mode)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, part.astype(out_dtype), src * t, axis=0)
        if nxt is not None:
            buf = nxt
    return out


def _mmrs_impl(x, w, axis_name, size, chunks, quant_mode):
    """Ring matmul-reduce-scatter forward: the accumulator rides the ring
    while each rank computes the partial product for the block the
    accumulator will need next — per-block sums add the same operands in
    the same rank order as ``psum_scatter(matmul(x, w))``."""
    big_t = x.shape[0]
    t = big_t // size
    _note_ring_geometry("mmrs", x, w, size)
    r = jax.lax.axis_index(axis_name)

    def partial(block_idx):
        rows = jax.lax.dynamic_slice_in_dim(x, block_idx * t, t, axis=0)
        return _chunked_rows_mm(rows, w, chunks, quant_mode)

    acc = partial(jax.lax.rem(r + size - 1, size))
    for step in range(1, size):
        acc = _ppermute_step(acc, axis_name, size)
        acc = acc + partial(jax.lax.rem(r - step + size - 1, size))
    return acc


# ------------------------------------------------------- public primitives
def all_gather_matmul(x, w, *, axis_name=None, axis_size=1, chunks=None,
                      quant_mode="off"):
    """Decomposed ``matmul(all_gather(x, tiled), w)`` (column-parallel
    forward / row-parallel backward).

    ``x``: this rank's token block ``[t, ..., k]``; ``w``: this rank's
    weight columns ``[k, n_local]``; returns ``[t*size, ..., n_local]``.
    Must be called inside a ``shard_map`` body mapped over ``axis_name``
    (or with ``axis_size <= 1``, where it degenerates to the row-chunked
    local matmul — the single-device form the bitwise tests pin down).

    The custom VJP reproduces the monolithic gradient DAG: the transpose
    of all-gather is reduce-scatter, so dx runs the dual decomposed
    :func:`matmul_reduce_scatter` ring on ``g @ w.T``; dw regathers the
    activations over the ring (pure data movement) and contracts in one
    2D dot. Gradients are straight-through full precision under quant.
    """
    chunks = default_chunks() if chunks is None else max(1, int(chunks))  # ptlint: disable=jit-purity (static chunk count)
    _note_chunks(chunks)

    if axis_name is None or axis_size <= 1:
        @jax.custom_vjp
        def local(x, w):
            return _chunked_rows_mm(x, w, chunks, quant_mode)

        def local_fwd(x, w):
            return local(x, w), (x, w)

        def local_bwd(res, g):
            x, w = res
            g = g.astype(x.dtype)
            return _chunked_dx(g, w, chunks), _flat_dw(x, g).astype(w.dtype)

        local.defvjp(local_fwd, local_bwd)
        return local(x, w)

    size = int(axis_size)  # ptlint: disable=jit-purity (static mesh-axis size)

    @jax.custom_vjp
    def agmm(x, w):
        return _agmm_impl(x, w, axis_name, size, chunks, quant_mode)

    def agmm_fwd(x, w):
        return agmm(x, w), (x, w)

    def agmm_bwd(res, g):
        x, w = res
        g = g.astype(x.dtype)
        # dx: transpose of all-gather is reduce-scatter -> dual ring
        dx = _mmrs_impl(g, jnp.swapaxes(w, -1, -2), axis_name, size,
                        chunks, "off")
        # dw: regather the activations (bitwise == lax.all_gather), one dot
        dw = _flat_dw(_ring_gather(x, axis_name, size), g).astype(w.dtype)
        return dx, dw

    agmm.defvjp(agmm_fwd, agmm_bwd)
    return agmm(x, w)


def matmul_reduce_scatter(x, w, *, axis_name=None, axis_size=1, chunks=None,
                          quant_mode="off"):
    """Decomposed ``psum_scatter(matmul(x, w), tiled)`` (row-parallel
    forward / column-parallel backward).

    ``x``: all token blocks against this rank's contraction slice
    ``[T, ..., k_local]``; ``w``: this rank's weight rows ``[k_local, n]``;
    returns this rank's token block ``[T/size, ..., n]``. Must run inside
    ``shard_map`` over ``axis_name`` (``axis_size <= 1`` degenerates to
    the row-chunked local matmul).

    VJP: the transpose of reduce-scatter is all-gather, so dx runs the
    dual decomposed :func:`all_gather_matmul` ring on ``g @ w.T`` and dw
    contracts the local activations against the ring-gathered output
    cotangent in one 2D dot.
    """
    chunks = default_chunks() if chunks is None else max(1, int(chunks))  # ptlint: disable=jit-purity (static chunk count)
    _note_chunks(chunks)

    if axis_name is None or axis_size <= 1:
        return all_gather_matmul(x, w, axis_name=None, axis_size=1,
                                 chunks=chunks, quant_mode=quant_mode)

    size = int(axis_size)  # ptlint: disable=jit-purity (static mesh-axis size)

    @jax.custom_vjp
    def mmrs(x, w):
        return _mmrs_impl(x, w, axis_name, size, chunks, quant_mode)

    def mmrs_fwd(x, w):
        return mmrs(x, w), (x, w)

    def mmrs_bwd(res, g):
        x, w = res
        g = g.astype(x.dtype)
        # dx: transpose of reduce-scatter is all-gather -> dual ring
        dx = _agmm_impl(g, jnp.swapaxes(w, -1, -2), axis_name, size,
                        chunks, "off")
        dw = _flat_dw(x, _ring_gather(g, axis_name, size)).astype(w.dtype)
        return dx, dw

    mmrs.defvjp(mmrs_fwd, mmrs_bwd)
    return mmrs(x, w)


# --------------------------------------------------- shard_map conveniences
def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def sharded_all_gather_matmul(x, w, *, mesh, axis_name="mp", chunks=None,
                              quant_mode="off"):
    """Global-array wrapper: ``x`` sharded on its leading (token) dim,
    ``w`` on its last dim; output gathered on tokens, sharded on columns."""
    from jax.sharding import PartitionSpec as P

    size = int(mesh.shape[axis_name])  # ptlint: disable=jit-purity (static mesh-axis size)
    x_spec = P(axis_name, *([None] * (x.ndim - 1)))
    w_spec = P(*([None] * (w.ndim - 1)), axis_name)
    out_spec = P(*([None] * (x.ndim - 1)), axis_name)

    def body(xl, wl):
        return all_gather_matmul(xl, wl, axis_name=axis_name,
                                 axis_size=size, chunks=chunks,
                                 quant_mode=quant_mode)

    return _shard_map(body, mesh, (x_spec, w_spec), out_spec)(x, w)


def sharded_matmul_reduce_scatter(x, w, *, mesh, axis_name="mp",
                                  chunks=None, quant_mode="off"):
    """Global-array wrapper: ``x`` sharded on its last (contraction) dim,
    ``w`` on its first dim; output sharded on the leading (token) dim."""
    from jax.sharding import PartitionSpec as P

    size = int(mesh.shape[axis_name])  # ptlint: disable=jit-purity (static mesh-axis size)
    x_spec = P(*([None] * (x.ndim - 1)), axis_name)
    w_spec = P(axis_name, *([None] * (w.ndim - 1)))
    out_spec = P(axis_name, *([None] * (x.ndim - 1)))

    def body(xl, wl):
        return matmul_reduce_scatter(xl, wl, axis_name=axis_name,
                                     axis_size=size, chunks=chunks,
                                     quant_mode=quant_mode)

    return _shard_map(body, mesh, (x_spec, w_spec), out_spec)(x, w)


# ------------------------------------------------- GSPMD model-level path
def chunked_mm(a, w, chunks=None, quant_mode="off"):
    """Raw-array decomposed matmul for jit/GSPMD call sites.

    Flattens leading dims to tokens and splits both the forward GEMM and
    the backward dx GEMM into ``chunks`` independent row blocks, so when
    ``w`` carries an mp sharding GSPMD emits one small collective per
    chunk riding inside the next chunk's GEMM instead of one big serial
    collective after the matmul. dw stays a single 2D dot (chunking the
    contraction would change the reduction order). Bitwise equal to
    ``jnp.matmul`` / ``qmm`` fwd and bwd — asserted by
    tests/test_tp_overlap.py.
    """
    chunks = default_chunks() if chunks is None else max(1, int(chunks))  # ptlint: disable=jit-purity (static chunk count)
    _note_chunks(chunks)
    lead = a.shape[:-1]
    k, n = a.shape[-1], w.shape[-1]

    @jax.custom_vjp
    def cmm(a, w):
        flat = _chunked_rows_mm(a.reshape(-1, k), w, chunks, quant_mode)
        return flat.reshape(lead + (n,))

    def cmm_fwd(a, w):
        return cmm(a, w), (a, w)

    def cmm_bwd(res, g):
        a, w = res
        g = g.astype(a.dtype)
        dx = _chunked_dx(g.reshape(-1, n), w, chunks).reshape(lead + (k,))
        return dx, _flat_dw(a, g).astype(w.dtype)

    cmm.defvjp(cmm_fwd, cmm_bwd)
    return cmm(a, w)


def _mesh_engaged() -> bool:
    from ..distributed.auto_parallel.constraint import _active_jax_mesh

    mesh = _active_jax_mesh()
    return (mesh is not None and "mp" in mesh.axis_names
            and mesh.shape["mp"] > 1)


def region_mm(a, w, quant_mode="off", op="fused_region"):
    """Overlap-aware matmul for fused epilogue regions (raw arrays).

    Inside ``fusion.linear_gelu`` / ``fusion.swiglu_linear`` the producing
    GEMM is the serial-collective hazard; when overlap routing engages this
    swaps in the decomposed :func:`chunked_mm` (bitwise equal), otherwise
    the plain ``jnp.matmul`` / ``qmm`` the region always used.

    The GSPMD rewrite engages only on an EXPLICIT opt-in — forced chunks
    (:func:`override`) or an explicit mode "on" with an active mp mesh —
    never under the default "auto": reshaping the GEMM changes how GSPMD
    partitions the surrounding trace, so default compiled programs must
    stay byte-identical to pre-overlap builds. (The eager fleet layers,
    whose collectives are real calls rather than compiler-placed, do
    overlap under "auto" — see distributed/tp_overlap.py.)
    """
    if enabled() and (_forced.get()[1] is not None or
                      (_raw_mode() != "auto" and _mesh_engaged())) \
            and route(op):
        return chunked_mm(a, w, None, quant_mode)
    return _mm(a, w, quant_mode)


def overlap_linear(x, weight, bias=None, *, op, quant_mode="off"):
    """Tensor-level decomposed linear for the model call sites.

    Returns the chunked-overlap ``x @ W (+ b)`` when overlap routing says
    so — an explicit mode "on" with an active mp mesh of
    size > 1, or a forced chunk count from :func:`override` (how
    single-device tests engage the path) — else ``None`` so the caller
    runs its verbatim serial composition. Like :func:`region_mm`, the
    default "auto" never rewrites compiled model traces.
    """
    if not enabled():
        return None
    if _forced.get()[1] is None and \
            not (_raw_mode() != "auto" and _mesh_engaged()):
        return None
    if not route(op):
        return None
    from ..core.autograd import run_op
    from ..ops._helpers import as_tensor

    chunks = default_chunks()
    ts = [as_tensor(x), as_tensor(weight)]
    if bias is not None:
        ts.append(as_tensor(bias))
        return run_op(lambda a, w, b: chunked_mm(a, w, chunks, quant_mode)
                      + b,
                      ts, name="tp_overlap_linear",
                      attrs={"op": op, "chunks": chunks, "quant": quant_mode})
    return run_op(lambda a, w: chunked_mm(a, w, chunks, quant_mode), ts,
                  name="tp_overlap_linear",
                  attrs={"op": op, "chunks": chunks, "quant": quant_mode})

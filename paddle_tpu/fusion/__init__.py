"""Fusion-aware rewrite layer over the jit/train_step pipeline.

The MFU story (ROADMAP "operator-fusion pass", Neptune arxiv 2510.08726):
after the matmuls are placed well, what keeps the chip idle is memory-bound
epilogue traffic — bias+gelu between the two MLP GEMMs, the residual-add
feeding every RMSNorm, the SwiGLU gate, the one-hot MoE dispatch einsums,
and the [tokens, vocab] logits of the LM loss. This package rewrites those
call sites into single traced regions (one ``run_op`` each) so XLA sees the
producing matmul and its epilogue as one fusion candidate, and adds an
int8/fp8 quantized-matmul hot path for the MLP blocks.

Knobs (read at trace time, captured per train-step build):

  - ``PADDLE_TPU_FUSION=auto|on|off`` — ``auto`` (default) behaves as
    ``on``. ``off`` routes every call site through the original unfused
    composition, restoring pre-fusion numerics byte-for-byte.
  - ``PADDLE_TPU_MM_QUANT=off|int8|fp8`` — quantized matmul for the MLP
    blocks (per-channel weight scales, per-token activation scales,
    straight-through full-precision gradients). Only consulted when
    fusion is enabled; never applied to attention or the LM head.
  - ``PADDLE_TPU_TP_OVERLAP=auto|on|off`` — decomposed
    computation–collective overlap for sharded matmuls (see
    :mod:`.overlap_mm`); its chunk count rides on
    ``PADDLE_TPU_TP_OVERLAP_CHUNKS``.

Bit-exactness contract: every fused epilogue in ``epilogues`` and the
chunked LM-CE path compose exactly the same jax ops in the same order as
their fallback, so fused == fallback bitwise (asserted by
tests/test_fusion.py). The fused MoE dispatch and the quantized matmul
path are tolerance-bound, not bitwise (see their module docs).
"""
from __future__ import annotations

import contextlib
import contextvars

from ..config import knobs
from . import chunked, epilogues, moe, overlap_mm, quant  # noqa: F401
from .chunked import chunked_epilogue, lm_head_chunked_ce
from .epilogues import add_rms_norm, dropout_add, linear_gelu, swiglu_linear
from .moe import fused_moe_mlp
from .overlap_mm import (all_gather_matmul, matmul_reduce_scatter,
                         overlap_linear)
from .quant import quantized_linear

__all__ = [
    "mode", "enabled", "mm_quant", "override", "route",
    "chunked_epilogue", "lm_head_chunked_ce",
    "add_rms_norm", "dropout_add", "linear_gelu", "swiglu_linear",
    "fused_moe_mlp", "quantized_linear",
    "all_gather_matmul", "matmul_reduce_scatter", "overlap_linear",
]

_FUSION_MODES = ("auto", "on", "off")
_QUANT_MODES = ("off", "int8", "fp8")

# Per-context override so a train-step build can pin the mode for the whole
# trace (distributed/auto_parallel/engine.py captures it at build time, the
# same way health/amp knobs are captured) and tests can force either path.
_forced: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_fusion_forced", default=(None, None))


def mode() -> str:
    """Resolved fusion mode: "on" or "off" ("auto" resolves to "on")."""
    forced = _forced.get()[0]
    if forced is not None:
        return "on" if forced == "auto" else forced
    raw = knobs.get_str("PADDLE_TPU_FUSION").strip().lower()
    if raw not in _FUSION_MODES:
        raise ValueError(
            f"PADDLE_TPU_FUSION={raw!r}: expected one of {_FUSION_MODES}")
    return "off" if raw == "off" else "on"


def enabled() -> bool:
    return mode() == "on"


def mm_quant() -> str:
    """Resolved quantized-matmul mode: "off", "int8" or "fp8"."""
    forced = _forced.get()[1]
    raw = forced if forced is not None else \
        knobs.get_str("PADDLE_TPU_MM_QUANT").strip().lower()
    if raw not in _QUANT_MODES:
        raise ValueError(
            f"PADDLE_TPU_MM_QUANT={raw!r}: expected one of {_QUANT_MODES}")
    if raw == "fp8" and not quant.fp8_supported():
        return "int8"
    return raw


@contextlib.contextmanager
def override(fusion=None, quant_mode=None):
    """Pin fusion / quant modes for the current context (trace scope)."""
    prev = _forced.get()
    tok = _forced.set((fusion if fusion is not None else prev[0],
                       quant_mode if quant_mode is not None else prev[1]))
    try:
        yield
    finally:
        _forced.reset(tok)


def route(op: str) -> bool:
    """Per-call-site dispatch decision + telemetry: True means take the
    fused path for ``op``, False means the verbatim fallback composition."""
    fused = enabled()
    from .. import observability as _obs

    if _obs.enabled():
        _obs.registry.counter(
            "fusion.fused_calls" if fused else "fusion.fallback_calls",
            tags={"op": op}).inc()
    return fused


def quant_route(op: str) -> str:
    """Quantized-matmul dispatch for an MLP matmul site: returns the
    resolved mode and counts the decision."""
    qm = mm_quant()
    if qm != "off":
        from .. import observability as _obs

        if _obs.enabled():
            _obs.registry.counter("fusion.quantized_matmuls",
                                  tags={"mode": qm, "op": op}).inc()
    return qm

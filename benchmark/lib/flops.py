"""Operations and bytes the algorithms need, from their shapes.

Model FLOPs per trained token are ``bench.py``'s arithmetic (6N for the
matmuls plus the causal attention term), copied here. The flash-attention
counts are the algorithm's own: recomputed work inside the backward
kernel is part of the flash algorithm and is counted; nothing else is.
"""
from __future__ import annotations

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2}


def train_flops_per_token(n_params: int, num_layers: int, hidden: int,
                          seq: int) -> dict:
    """Forward + backward FLOPs per token. ``6N`` alone, and with the
    causal attention term 6*L*h*seq (12*L*h*seq halved by the mask)."""
    return {"6N": 6.0 * n_params,
            "6N_plus_attention": 6.0 * n_params
            + 6.0 * num_layers * hidden * seq}


def shape_bytes(shape) -> int:
    dtype, dims = shape
    n = 1
    for d in dims:
        n *= d
    return n * DTYPE_BYTES[dtype]


def flash_call_cost(kind: str, bh: int, seq: int, head_dim: int,
                    operands, results) -> dict:
    """One causal flash-attention call over ``bh`` (batch x heads) rows.

    Forward: S = QK^T and O = PV, two matmuls of 2*seq*seq*head_dim each.
    Fused backward: S again, dP = dO V^T, dV = P^T dO, dQ = dS K and
    dK = dS^T Q, five such matmuls. The causal mask halves all of them.
    Bytes: every operand read once and every result written once.
    """
    matmuls = {"flash_fwd": 2, "flash_bwd": 5}[kind]
    flops = matmuls * 2.0 * seq * seq * head_dim * bh / 2.0
    nbytes = sum(shape_bytes(s) for s in operands) \
        + sum(shape_bytes(s) for s in results)
    return {"flops": flops, "bytes": float(nbytes)}


def least_seconds(cost: dict, peaks: dict) -> dict:
    """Roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two it is."""
    tc = cost["flops"] / peaks["bf16_flops"]
    tm = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(tc, tm),
            "bound": "compute" if tc >= tm else "memory"}

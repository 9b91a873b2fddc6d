#!/usr/bin/env python
"""Single-chip MoE bench (VERDICT r3 next #8): sort-based dispatch +
grouped GEMM vs the GShard one-hot einsum path; reports the dispatch
(non-GEMM) fraction of step time.

``--gmm [--live N]``: ``grouped_matmul`` alone at the two expert serving
cells' four shapes, N row blocks live (default: a block a held expert, as
in the cells) and the rest dead, with and without ``live_blocks``: a
by-hand A/B on the chip, in no cell's path."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(step, x, *rest, iters=20):
    """Two-point chained timing: run the scan at N and 3N iterations and
    difference them — the fixed dispatch and read-back cost cancels,
    leaving per-step device time."""
    import functools

    import jax.lax as lax

    @functools.partial(jax.jit, static_argnames="n")
    def chained(xx, *r, n):
        def body(c, _):
            return step(c, *r), None

        out, _ = lax.scan(body, xx, None, length=n)
        return out

    def run(n):
        out = chained(x, *rest, n=n)
        _ = np.asarray(out.reshape(-1)[:1])  # tiny on-device slice -> d2h
        t0 = time.perf_counter()
        out = chained(x, *rest, n=n)
        _ = np.asarray(out.reshape(-1)[:1])
        return time.perf_counter() - t0

    t1 = run(iters)
    t3 = run(3 * iters)
    return max(t3 - t1, 1e-9) / (2 * iters)


# rows, in, held experts, out: serve_reason_sarvam105b_l6's and
# serve_longdoc_xing4_l6's gate-and-up and down projections
GMM_SHAPES = {
    "sarvam_gate_up": (8576, 4096, 32, 4096),
    "sarvam_down": (8576, 2048, 32, 4096),
    "xing4_gate_up": (10368, 3584, 64, 2048),
    "xing4_down": (10368, 1024, 64, 3584),
}


def gmm_alone(live=None):
    """Times ``grouped_matmul`` with the first ``live`` row blocks holding
    rows (spread over the experts in order, as ``sort_dispatch`` lays them
    out) and checks that every live row is the same bits either way."""
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import (_BM,
                                                            grouped_matmul)

    on_tpu = jax.default_backend() == "tpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    kw = {} if on_tpu else dict(impl="pallas", interpret=True)
    for name, (p, kdim, e, n) in GMM_SHAPES.items():
        if not on_tpu:                      # a CPU rehearsal of the paths
            p, kdim, e, n = p // 8 // _BM * _BM, kdim // 32, e // 8, n // 8
        blocks = p // _BM
        nl = min(e if live is None else live, blocks)
        rng = np.random.RandomState(0)
        gid = np.full(blocks, e - 1, np.int32)
        gid[:nl] = np.arange(nl) * e // max(nl, 1)
        xp = np.zeros((p, kdim), np.float32)
        xp[:nl * _BM] = rng.randn(nl * _BM, kdim)
        xp, gid = jnp.asarray(xp, dt), jnp.asarray(gid)
        w = jnp.asarray(rng.randn(e, kdim, n) * 0.02, dt)
        lv = jnp.int32(nl)

        def step(g, x, ww, count):
            out = grouped_matmul(x, ww, g, count, **kw)
            # the next call's block map waits for this call's result
            return g + (out[0, 0] > 1e30).astype(g.dtype)

        t_all = timed(lambda g, x, ww: step(g, x, ww, None), gid, xp, w)
        t_live = timed(step, gid, xp, w, lv)
        a = grouped_matmul(xp, w, gid, **kw)[:nl * _BM]
        b = grouped_matmul(xp, w, gid, lv, **kw)[:nl * _BM]
        item = jnp.dtype(dt).itemsize
        live_bytes = nl * (kdim * n + _BM * (kdim + n)) * item
        print(json.dumps({
            "metric": "moe_grouped_matmul_ms", "shape": name,
            "value": round(t_live * 1e3, 4), "unit": "ms",
            "extra": {
                "backend": jax.default_backend(),
                "rows": p, "in": kdim, "experts": e, "out": n,
                "row_blocks": blocks, "live_blocks": nl,
                "without_live_blocks_ms": round(t_all * 1e3, 4),
                "live_rows_bitwise_equal": bool(jnp.array_equal(a, b)),
                "live_gbytes": round(live_bytes / 1e9, 4),
                "live_gbytes_per_s": round(live_bytes / t_live / 1e9, 1),
            }}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gmm", action="store_true",
                    help="time grouped_matmul alone at the cells' shapes")
    ap.add_argument("--live", type=int,
                    help="live row blocks (default: one a held expert)")
    args = ap.parse_args(argv)
    if args.gmm:
        return gmm_alone(args.live)

    from paddle_tpu.incubate.nn.pallas.moe_dispatch import (
        grouped_matmul, moe_ffn_sorted, sort_dispatch)

    on_tpu = jax.default_backend() == "tpu"
    S, M, DFF, E, K = (8192, 2048, 2816, 8, 2) if on_tpu \
        else (512, 128, 256, 4, 2)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(S, M), dt)
    logits = jnp.asarray(rng.randn(S, E), jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    w1 = jnp.asarray(rng.randn(E, M, 2 * DFF) * 0.02, dt)
    w2 = jnp.asarray(rng.randn(E, DFF, M) * 0.02, dt)

    t_full = timed(lambda xx, pp, a, b: moe_ffn_sorted(
        xx, pp, a, b, k=K).astype(xx.dtype), x, probs, w1, w2)

    def disp_step(xx, pp):
        d = sort_dispatch(xx, pp, K)
        # feed a cheap reduction of the dispatch back into the carry so
        # scan serializes the dispatches without adding GEMM work
        return xx + d["xp"][:xx.shape[0]] * 0
    t_disp = timed(disp_step, x, probs)

    # GShard one-hot einsum dispatch comparison (capacity = tokens/E * 2)
    cap = 2 * S * K // E

    def gshard(xx, probs, w1, w2):
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        oh = jax.nn.one_hot(top_e, E, dtype=xx.dtype)      # [S,K,E]
        pos = jnp.cumsum(oh.reshape(S * K, E), 0) - 1
        pos = pos.reshape(S, K, E)
        slot = jax.nn.one_hot(jnp.sum(pos * oh, -1), cap,
                              dtype=xx.dtype)              # [S,K,cap]
        dm = jnp.einsum("ske,skc->sec", oh, slot)
        xe = jnp.einsum("sec,sm->ecm", dm, xx)
        h = jnp.einsum("ecm,emh->ech", xe, w1)
        g, u = jnp.split(h, 2, -1)
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("ech,ehm->ecm", h, w2)
        cw = jnp.einsum("ske,skc,sk->sec", oh, slot,
                        top_p).astype(xx.dtype)
        return jnp.einsum("sec,ecm->sm", cw, ye)

    t_gshard = timed(gshard, x, probs, w1, w2)

    # FLOPs for the grouped GEMMs (2 projections, K experts per token)
    flops = 2 * S * K * M * 2 * DFF + 2 * S * K * DFF * M
    print(json.dumps({
        "metric": "moe_sorted_ffn_step_ms",
        "value": round(t_full * 1e3, 3),
        "unit": "ms",
        "extra": {
            "tokens": S, "d_model": M, "experts": E, "topk": K,
            "dispatch_ms": round(t_disp * 1e3, 3),
            "dispatch_fraction": round(t_disp / t_full, 3),
            "gshard_einsum_ms": round(t_gshard * 1e3, 3),
            "speedup_vs_gshard": round(t_gshard / t_full, 2),
            "tflops": round(flops / t_full / 1e12, 2),
        },
    }), flush=True)

    if on_tpu:
        # kernel parity on-chip: pallas vs ragged
        d = sort_dispatch(x, probs, K)
        a = grouped_matmul(d["xp"], w1, d["block_gid"], impl="pallas")
        b = grouped_matmul(d["xp"], w1, d["block_gid"], impl="ragged")
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        print(json.dumps({"metric": "moe_pallas_vs_ragged_max_abs_err",
                          "value": err, "unit": "abs"}), flush=True)


if __name__ == "__main__":
    main()

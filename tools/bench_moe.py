#!/usr/bin/env python
"""Single-chip MoE bench (VERDICT r3 next #8): sort-based dispatch +
grouped GEMM vs the GShard one-hot einsum path; reports the dispatch
(non-GEMM) fraction of step time."""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


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
        _ = np.asarray(out[:1, :1])      # tiny on-device slice -> d2h
        t0 = time.perf_counter()
        out = chained(x, *rest, n=n)
        _ = np.asarray(out[:1, :1])
        return time.perf_counter() - t0

    t1 = run(iters)
    t3 = run(3 * iters)
    return max(t3 - t1, 1e-9) / (2 * iters)


def main():
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

#!/usr/bin/env python
"""Sweep Pallas flash-attention block sizes vs the XLA composition at a
given shape (fwd+bwd), on the real chip. Informs the attention_impl gate and
default blocks (VERDICT r1: 'verify the Pallas flash-attn bwd actually
beats XLA attention at bench shapes — drop it if not')."""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--hd", type=int, default=64)
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()
    b, s, nh, hd, iters = args.batch, args.seq, args.heads, args.hd, args.iters

    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    rng = np.random.default_rng(0)
    qnp = rng.standard_normal((b, s, nh, hd))

    def bench(loss_fn, tag):
        g = jax.grad(loss_fn, argnums=(0, 1, 2))

        def step(carry):
            q, acc = carry
            gq, gk, gv = g(q, q, q)
            return q - 0.0 * gq, acc + gk.astype(jnp.float32).sum()

        def multi(carry):
            def body(c, _):
                return step(c), None
            out, _ = jax.lax.scan(body, carry, None, length=iters)
            return out

        f = jax.jit(multi, donate_argnums=0)
        try:
            out = f((jnp.asarray(qnp, dt), jnp.float32(0)))
            float(np.asarray(out[1]))
            t0 = time.perf_counter()
            out = f(out)
            float(np.asarray(out[1]))
            ms = (time.perf_counter() - t0) / iters * 1000
            print(json.dumps({"config": tag, "ms": round(ms, 2)}), flush=True)
            return ms
        except Exception as e:
            print(json.dumps({"config": tag,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
            return float("inf")

    from paddle_tpu.incubate.nn.functional.flash_attention import (
        _xla_attention)
    from paddle_tpu.incubate.nn.pallas.flash_attn import flash_attention

    bench(lambda q, k, v: _xla_attention(q, k, v, True)
          .astype(jnp.float32).sum(), "xla")

    for bq, bk in [(128, 128), (256, 256), (512, 512), (256, 512),
                   (512, 256), (1024, 1024), (s, s)]:
        if bq > s or bk > s:
            continue
        bench(lambda q, k, v, bq=bq, bk=bk: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk)
            .astype(jnp.float32).sum(), f"pallas_q{bq}_k{bk}")


if __name__ == "__main__":
    main()

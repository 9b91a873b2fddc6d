#!/usr/bin/env python
"""Per-phase breakdown of the flagship bench step (VERDICT r1 weak #1).

Times each component of the GPT-3-125M train step at bench shapes on the
real chip, chaining iterations inside one compiled program (lax.scan) and
waiting for the device with ``jax.block_until_ready`` before reading the
clock.

Usage:  python tools/profile_bench.py [--seq 512] [--batch 64]
Prints one JSON line per phase: {"phase": ..., "ms_per_iter": ...}.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _barrier(out):
    import jax

    jax.block_until_ready(out)


def timed(fn, carry, iters=8):
    """fn donates its carry and returns a same-structure carry; feed the
    output back in so donation stays valid. Times the second call."""
    out = fn(carry)
    _barrier(out)
    t0 = time.perf_counter()
    out = fn(out)
    _barrier(out)
    el = time.perf_counter() - t0
    return el / iters * 1000


def chain(step, n):
    """step: carry -> carry with a scalar readable leaf."""
    import jax

    def multi(carry):
        def body(c, _):
            return step(c), None

        out, _ = jax.lax.scan(body, carry, None, length=n)
        return out

    return jax.jit(multi, donate_argnums=0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.jit import TrainStep

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    b, s, iters = args.batch, args.seq, args.iters
    results = []

    def rec(phase, ms, note=""):
        results.append({"phase": phase, "ms_per_iter": round(ms, 2),
                        "note": note})
        print(json.dumps(results[-1]), flush=True)

    cfg = pt.models.gpt3_125M(dropout=0.0, attention_dropout=0.0)
    V, h, L, nh, hd = (cfg.vocab_size, cfg.hidden_size, cfg.num_layers,
                       cfg.num_heads, cfg.head_dim)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.default_rng(0)

    # ---- 1. full train step (the bench) --------------------------------
    pt.set_default_dtype("bfloat16" if on_tpu else "float32")
    try:
        model = pt.models.GPTForCausalLM(cfg)
    finally:
        pt.set_default_dtype("float32")
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                             parameters=model.parameters())
    step = TrainStep(model, opt, grad_clip_norm=1.0)
    ids = pt.to_tensor(rng.integers(0, V, (b, s)), dtype="int64")
    labels = pt.to_tensor(rng.integers(0, V, (b, s)), dtype="int64")
    loss = step.run_steps(iters, ids, labels)
    float(loss)
    t0 = time.perf_counter()
    loss = step.run_steps(iters, ids, labels)
    float(loss)
    full_ms = (time.perf_counter() - t0) / iters * 1000
    rec("full_train_step", full_ms,
        f"tok/s={b * s / (full_ms / 1000):.0f}")

    # ---- 2. fwd+bwd only (no clip/opt), grads via paddle tape ----------
    from paddle_tpu.core.autograd import grad as pgrad
    from paddle_tpu.core import random as prng
    from paddle_tpu.core.tensor import Tensor

    params = [p for _, p in model.named_parameters()]
    # phases donate their param carry; hand each phase its own on-device
    # copy (one dispatch) so later phases don't see deleted arrays
    _copy_all = jax.jit(lambda xs: [x + 0 for x in xs])

    def fresh_params():
        return _copy_all([p._data for p in params])

    pa = fresh_params()

    def fwdbwd(arrs):
        saved = [p._data for p in params]
        for p, a in zip(params, arrs):
            p._data = a
        try:
            with prng.rng_guard(jax.random.PRNGKey(0)):
                l = model(ids, labels=labels)
                gs = pgrad([l], params, allow_unused=True)
        finally:
            for p, a in zip(params, saved):
                p._data = a
        return [g._data if g is not None else jnp.zeros_like(a)
                for g, a in zip(gs, arrs)], l._data

    def fb_step(carry):
        arrs, acc = carry
        gs, l = fwdbwd(arrs)
        # consume grads so XLA can't DCE; keep params constant
        return [a - 0.0 * g for a, g in zip(arrs, gs)], acc + l

    f = chain(fb_step, iters)
    rec("fwd_bwd_only", timed(f, (pa, jnp.float32(0)), iters=iters),
        "no clip/optimizer")

    # ---- 3. fwd+bwd without lm_head/CE (hidden.sum loss) ----------------
    def fwdbwd_nohead(arrs):
        saved = [p._data for p in params]
        for p, a in zip(params, arrs):
            p._data = a
        try:
            with prng.rng_guard(jax.random.PRNGKey(0)):
                hsum = model.gpt(ids).astype("float32").sum()
                gs = pgrad([hsum], params, allow_unused=True)
        finally:
            for p, a in zip(params, saved):
                p._data = a
        return [g._data if g is not None else jnp.zeros_like(a)
                for g, a in zip(gs, arrs)], hsum._data

    def fbnh_step(carry):
        arrs, acc = carry
        gs, l = fwdbwd_nohead(arrs)
        return [a - 0.0 * g for a, g in zip(arrs, gs)], acc + l

    f = chain(fbnh_step, iters)
    rec("fwd_bwd_no_head_ce", timed(f, (fresh_params(), jnp.float32(0)),
                                    iters=iters), "backbone only")

    # ---- 4. lm_head + CE alone (fwd+bwd) -------------------------------
    x0 = jnp.asarray(rng.standard_normal((b, s, h)), dt)
    wte = jnp.asarray(rng.standard_normal((V, h)) * 0.02, dt)
    lab = jnp.asarray(rng.integers(0, V, (b, s)), jnp.int32)

    def ce_loss(x, w):
        logits = jnp.matmul(x, w.T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp.reshape(-1, V), lab.reshape(-1, 1), axis=1)
        return -picked.mean()

    ce_grad = jax.grad(lambda x, w: ce_loss(x, w), argnums=(0, 1))

    def ce_step(carry):
        x, w, acc = carry
        gx, gw = ce_grad(x, w)
        return x - 0.0 * gx, w - 0.0 * gw, acc + gx.astype(jnp.float32).sum()

    f = chain(ce_step, iters)
    rec("lm_head_ce_fwd_bwd", timed(f, (x0, wte, jnp.float32(0)),
                                    iters=iters))

    # ---- 5. attention alone: pallas vs XLA (fwd+bwd), all layers -------
    qnp = rng.standard_normal((b, s, nh, hd))

    def attn_loss_pallas(q, k, v):
        from paddle_tpu.incubate.nn.pallas.flash_attn import flash_attention
        out = flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    def attn_loss_xla(q, k, v):
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * (hd ** -0.5)
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e9)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", w, vh)
        return out.astype(jnp.float32).sum()

    for name, lf in (("attn_pallas_fwd_bwd", attn_loss_pallas),
                     ("attn_xla_fwd_bwd", attn_loss_xla)):
        g = jax.grad(lf, argnums=(0, 1, 2))

        def a_step(carry, g=g):
            q, acc = carry
            gq, gk, gv = g(q, q, q)
            return q - 0.0 * gq, acc + gk.astype(jnp.float32).sum()

        f = chain(a_step, iters)
        try:
            ms = timed(f, (jnp.asarray(qnp, dt), jnp.float32(0)),
                       iters=iters)
            rec(name, ms * L, f"x{L} layers; per-layer {ms:.2f}ms")
        except Exception as e:  # pallas may not support shape
            rec(name, -1, f"FAILED {type(e).__name__}: {e}")

    # ---- 6. optimizer update alone (adamw, 125M params) ----------------
    state = opt.init_state([p._data for p in params])

    def opt_step(carry):
        arrs, st, acc = carry
        gs = [a * 1e-6 for a in arrs]
        new, st = opt.update(list(arrs), gs, st, lr=jnp.float32(1e-4))
        return new, st, acc + new[0].astype(jnp.float32).sum()

    f = chain(opt_step, iters)
    rec("adamw_update", timed(f, (fresh_params(), state, jnp.float32(0)),
                              iters=iters), "incl. synthetic grads")

    # ---- 7. matmul ceiling (same shapes as the MLP) --------------------
    mm_w1 = jnp.asarray(rng.standard_normal((h, 4 * h)), dt)
    mm_w2 = jnp.asarray(rng.standard_normal((4 * h, h)), dt)
    xm = jnp.asarray(rng.standard_normal((b * s, h)), dt)

    def mm_step(carry):
        x, acc = carry
        y = x
        for _ in range(L):
            y = jnp.matmul(jnp.matmul(y, mm_w1), mm_w2)
        # x must depend on y or XLA hoists the loop-invariant chain out of
        # the scan (0.0*y is not foldable under nan semantics)
        return x - 0.0 * y, acc + y.astype(jnp.float32).sum()

    f = chain(mm_step, iters)
    ms = timed(f, (xm, jnp.float32(0)), iters=iters)
    flops = 2 * b * s * (h * 4 * h * 2) * L
    rec("matmul_chain_ceiling", ms,
        f"{flops / (ms / 1000) / 197e12:.3f} MFU-equiv")

    with open("tools/profile_bench_out.json", "w") as fo:
        json.dump({"batch": b, "seq": s, "results": results}, fo, indent=1)


if __name__ == "__main__":
    main()

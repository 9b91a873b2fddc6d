#!/usr/bin/env python3
"""The ragged paged-attention kernels alone, on the chip, at a serving
cell's shapes.

    python3 tools/ragged_kernel_bench.py [--cell CELL] [--pages 1,8] [--out DIR] [name=path/to/paged_attention.py ...]

Each ``name=path`` adds another copy of the module to the table (the
parent commit's, unpacked under ``.scratch/``); ``tree`` is this
checkout's. ``--cell`` picks the per-head kernel's shapes (bf16):

* ``serve_chat_1p3b`` (the default): T 304, 48 rows, 16 heads = 16 KV
  heads of 128, 240 pages of 128, 16 pages a row, on the three batches of
  ``chip_smoke.ragged_cell_batches``;
* ``serve_reason_nemotron3nano_l18``: T 384, 128 rows, 32 heads over 2 KV
  heads of 128, 2,400 pages of 128, 30 pages a row, on 128 decode rows at
  1,200-2,300 tokens of context (``decode_128``) and the same with one
  256-token chunk ending at 1,536 in place of the last (``chunk_1536``);
* ``head_dim_64``: chat's tokens, rows, pages and batches with 32 heads
  over 8 KV heads of 64 (a Llama-3.2-1B-class head), which the kernel
  fetches by its block pipeline a page a visit.

``--pages 1,2,8`` adds copies of the tree's module whose visits read
exactly that many pages (``tree@2``, ...: ``_RAGGED_PAGES`` set, the
byte target lifted). For each batch and implementation, one line of JSON:

* ``call_us``: host clock over a jitted chain of ``--layers`` calls, each
  fed by the one before, median of ``--reps``, a call — the kernel with
  the wrapper's XLA around it;
* ``kernel_us``: the kernel's own device time a call, from a profiler
  trace of three chains read by ``benchmark/lib/xplane.py``;
* ``visits`` (the work list's live length, a head block's pass; the
  parent's is a visit a page), ``page_visits`` (the live (row, page)
  pairs) and ``us_per_page`` (``kernel_us`` over ``page_visits``);
* ``max_abs``: worst absolute difference to a float32 composition a row
  on the device;
* ``live_pages`` and ``hbm_bound_us``: the live KV read once at the
  chip's peak bandwidth.

``--latent CELL`` times the LATENT ragged-attention kernel instead, at
that cell's shapes (``serve_longdoc_xing4_l6``: T 536, 24 rows, 32 heads,
2,048 pages of 128 x 640, 128 pages a row; ``serve_reason_sarvam105b_l6``:
T 560, 48 rows, 64 heads, 1,408 pages, 37 a row; bf16) on its batches:
Xing4.0, one 512-token chunk ending at 4,352 / 8,704 / 14,336 tokens
beside 15 decode rows at about 8.7k, and the 15 decode rows alone;
sarvam, 48 decode rows at about 2.5k, and a 512-token chunk ending at
2,048 beside 47 of them. There ``--pages 1,4,8`` adds copies of the
tree's module with ``_LATENT_PAGES`` set to each (``tree@4``, ...); each
line has the same fields, ``max_abs`` against a float32 composition a
row, ``hbm_bound_us`` the live pages' 576 values a token each read once.

Pools, tables and queries are arguments of the jitted chains, never
constants of them. ``--out DIR`` also writes all lines as one JSON file
there. It needs a TPU and says nothing on a CPU.
"""
from __future__ import annotations

import argparse
import glob
import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def _load(name, path):
    if path is None:
        return importlib.import_module(
            "paddle_tpu.incubate.nn.pallas.paged_attention")
    spec = importlib.util.spec_from_file_location("ragged_impl_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _save(args, name, results):
    """All lines as one JSON file ``name`` under ``--out``, if given."""
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, name), "w") as f:
            json.dump(results, f, indent=1)


def _trace_chains(chain, args):
    """The reduced profiler trace of three runs of ``chain(*args)``."""
    import jax

    from benchmark.lib import xplane

    tdir = tempfile.mkdtemp(prefix="ragged_bench_")
    try:
        jax.profiler.start_trace(tdir)
        for _ in range(4):          # the traced window ends at the last start
            chain(*args).block_until_ready()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        return xplane.reduce_trace(found[0])
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _time_chain(chain, args, reps):
    """Median host seconds of ``chain(*args)`` after a warm-up run."""
    chain(*args).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        chain(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _measure(mod, q, kp, vp, meta, layers, reps):
    """One implementation of the per-head kernel on one batch ->
    (output, seconds a chain of ``layers`` calls takes by the host's
    clock, median of ``reps``; the kernel's device seconds and its calls
    in a trace of three chains)."""
    import jax

    from benchmark.lib import xplane

    bt, cl, ql, qs = meta
    args = (q, kp, vp, bt, cl, ql, qs)      # arguments, never constants

    def one(qq, kp, vp, bt, cl, ql, qs):
        return mod.ragged_paged_attention(qq, kp, vp, bt, cl, ql,
                                          q_starts=qs, use_kernel=True)

    @jax.jit
    def chain(qq, *a):
        def body(c, _):
            return (qq + one(c, *a) * 1e-3).astype(qq.dtype), None
        return jax.lax.scan(body, qq, None, length=layers)[0]

    got = np.asarray(jax.jit(one)(*args), np.float32)
    chain_s = _time_chain(chain, args, reps)
    trace = _trace_chains(chain, args)
    kern = [op for c in trace["chips"].values() for op in c["ops"]
            if (xplane.classify_kernel(op) or ("",))[0] == "ragged_attn"]
    return (got, chain_s, sum(o["seconds"] for o in kern),
            sum(o["count"] for o in kern))


# cell -> tokens, heads, kv heads, head dim, pages, page, rows, pages a row
HEAD_CELLS = {
    "serve_chat_1p3b": (304, 16, 16, 128, 240, 128, 48, 16),
    "serve_reason_nemotron3nano_l18": (384, 32, 2, 128, 2400, 128, 128, 30),
    "head_dim_64": (304, 32, 8, 64, 240, 128, 48, 16),
}


def head_batches(cell, seed):
    """name -> (bt, cl, ql, qs) as the engine packs them, for one
    per-head cell."""
    t, _, _, _, pages, page, rows, pps = HEAD_CELLS[cell]
    if cell != "serve_reason_nemotron3nano_l18":
        import chip_smoke

        return chip_smoke.ragged_cell_batches(
            rows=rows, pages=pages, pages_per_seq=pps, tokens=t, chunk=256,
            page=page, seed=seed)
    rng = np.random.default_rng(seed)
    dec = [(1, int(c)) for c in rng.integers(1200, 2301, rows)]
    out = {}
    for name, packed in (("decode_128", dec),
                         ("chunk_1536", dec[:-1] + [(256, 1536)])):
        bt = np.zeros((rows, pps), np.int32)
        cl, ql, qs = (np.zeros(rows, np.int32) for _ in range(3))
        perm, used, cursor = rng.permutation(pages), 0, 0
        for r, (n, c) in enumerate(packed):
            k = -(-c // page)
            bt[r, :k] = perm[used:used + k]
            used += k
            qs[r], ql[r], cl[r] = cursor, n, c
            cursor += n
        assert used <= pages and cursor <= t
        out[name] = (bt, cl, ql, qs)
    return out


def _head_reference(q, kp, vp, bt, cl, ql, qs, scale):
    """float32 on the device, a row at a time: each token's causal
    prefix of its row's pages, its query heads in groups over the KV
    heads."""
    import jax
    import jax.numpy as jnp

    n_kv, _, page, d = kp.shape
    group = q.shape[1] // n_kv

    @jax.jit
    def one(qr, k, v, lim):
        qg = qr.reshape(qr.shape[0], n_kv, group, d)
        s = jnp.einsum("tkgd,kpd->tkgp", qg, k) * scale
        mask = jnp.arange(k.shape[1])[None, None, None, :] \
            < lim[:, None, None, None]
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("tkgp,kpd->tkgd", w, v).reshape(qr.shape)

    out = np.zeros(q.shape, np.float32)
    for r in np.flatnonzero(ql > 0):
        a, n, c = int(qs[r]), int(ql[r]), int(cl[r])
        # the whole row of the table: one shape a query length
        k, v = (x[:, bt[r]].reshape(n_kv, -1, d).astype(jnp.float32)
                for x in (kp, vp))
        out[a:a + n] = np.asarray(one(q[a:a + n].astype(jnp.float32), k, v,
                                      jnp.arange(c - n + 1, c + 1)))
    return out


def main_heads(args, impls) -> int:
    import jax.numpy as jnp

    from paddle_tpu.device.peaks import chip_peaks, require_chip

    dev = require_chip()
    t, h, n_kv, d, pages, page, n_rows, pps = HEAD_CELLS[args.cell]
    tree = impls["tree"]
    for n in args.pages:
        mod = _load("tree@%d" % n, tree.__file__)
        mod._RAGGED_PAGES, mod._RAGGED_VISIT_BYTES = n, 1 << 40
        impls["tree@%d" % n] = mod
    rng = np.random.default_rng(args.seed)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    kp, vp = arr(n_kv, pages, page, d), arr(n_kv, pages, page, d)
    q = arr(t, h, d)
    hbm = chip_peaks(dev).hbm_bytes_per_s
    results = []
    for bname, (bt, cl, ql, qs) in head_batches(args.cell,
                                                args.seed).items():
        meta = tuple(jnp.asarray(a) for a in (bt, cl, ql, qs))
        live = int(np.where(ql > 0, -(-cl // page), 0).sum())
        ref = _head_reference(q, kp, vp, bt, cl, ql, qs, d ** -0.5)
        for iname, mod in impls.items():
            got, chain_s, kern_s, kern_n = _measure(
                mod, q, kp, vp, meta, args.layers, args.reps)
            pv, n_vis = mod.ragged_visit_counts(
                t, h, kp.shape, kp.dtype, q.dtype, pps, cl, ql) \
                if hasattr(mod, "ragged_visit_counts") else (live, live)
            kern_us = 1e6 * kern_s / kern_n if kern_n else None
            line = {
                "cell": args.cell, "batch": bname, "impl": iname,
                "live_pages": live, "page_visits": pv, "visits": n_vis,
                "rows": int((ql > 0).sum()), "tokens": int(ql.sum()),
                "call_us": 1e6 * chain_s / args.layers,
                "kernel_us": kern_us, "kernel_calls_traced": kern_n,
                "us_per_page": kern_us / pv if kern_us else None,
                "max_abs": float(np.abs(got - ref).max()),
                "hbm_bound_us": 1e6 * live * 2 * n_kv * page * d * 2 / hbm,
                "device": dev.device_kind,
            }
            print(json.dumps(line), flush=True)
            results.append(line)
    _save(args, "ragged_kernel_bench_%s.json" % args.cell, results)
    return 0


# cell -> tokens, heads, latent, values, pages, page, rows, pages a row
LATENT_CELLS = {
    "serve_longdoc_xing4_l6": (536, 32, 576, 512, 2048, 128, 24, 128),
    "serve_reason_sarvam105b_l6": (560, 64, 576, 512, 1408, 128, 48, 37),
}


def latent_batches(cell, seed):
    """name -> (query tokens, context) a row, decode rows first as the
    engine packs them, for one latent cell."""
    rng = np.random.default_rng(seed)
    if cell == "serve_longdoc_xing4_l6":
        dec = [(1, int(c)) for c in rng.integers(8000, 9400, 15)]
        out = {"chunk_%d" % end: dec + [(512, end)]
               for end in (4352, 8704, 14336)}
        out["decode_15"] = dec
        return out
    dec = [(1, int(c)) for c in rng.integers(2200, 2800, 48)]
    return {"decode_48": dec, "chunk_2048": dec[:47] + [(512, 2048)]}


def _latent_reference(q, pool, bt, rows, dv, scale):
    """float32 on the device, a row at a time: each token's causal
    prefix of its row's pages."""
    import jax
    import jax.numpy as jnp

    out, start = [], 0

    @jax.jit
    def one(qr, kv, lim):
        s = jnp.einsum("thd,kd->thk", qr, kv[:, :qr.shape[-1]]) * scale
        mask = jnp.arange(kv.shape[0])[None, None, :] < lim[:, None, None]
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("thk,kv->thv", w, kv[:, :dv])

    for r, (n, ctx) in enumerate(rows):
        # the whole row of the table: one shape a query length
        kv = pool[0, bt[r]].reshape(-1, pool.shape[-1]).astype(jnp.float32)
        lim = jnp.arange(ctx - n + 1, ctx + 1)
        out.append(one(q[start:start + n].astype(jnp.float32), kv, lim))
        start += n
    got = jnp.concatenate(out, 0)
    return np.asarray(got, np.float32), start


def _measure_latent(mod, q, pool, meta, dv, scale, layers, reps, config):
    import jax

    from benchmark.lib import xing4_kernels

    bt, cl, ql, qs = meta
    args = (pool, bt, cl, ql, qs)       # arguments, never constants

    def one(qq, pool, bt, cl, ql, qs):
        return mod.ragged_latent_attention(
            qq, pool, bt, cl, ql, q_starts=qs, value_dim=dv, scale=scale,
            use_kernel=True)

    @jax.jit
    def chain(qq, *a):
        def body(c, _):
            return c.at[..., :dv].add(one(c, *a) * 1e-3), None
        return jax.lax.scan(body, qq, None, length=layers)[0]

    got = np.asarray(jax.jit(one)(q, *args), np.float32)
    chain_s = _time_chain(chain, (q,) + args, reps)
    trace = _trace_chains(chain, (q,) + args)
    calls, spent = xing4_kernels.latent_attn_calls(trace, config)
    n_vis = int(mod.latent_visits(q.shape[0], pool.shape[2], bt, cl, ql,
                                  qs)[1][0])
    return got, chain_s, spent, calls, n_vis


def main_latent(args, impls) -> int:
    import jax.numpy as jnp

    from paddle_tpu.device.peaks import chip_peaks, require_chip

    dev = require_chip()
    t, nh, d, dv, pages, page, n_rows, pps = LATENT_CELLS[args.latent]
    config = {"kv_lora_rank": d - 64, "qk_rope_head_dim": 64}
    scale = d ** -0.5
    tree = impls["tree"]
    for n in args.pages:
        mod = _load("tree@%d" % n, tree.__file__)
        mod._LATENT_PAGES = n
        impls["tree@%d" % n] = mod
    rng = np.random.default_rng(args.seed)
    dp = tree.latent_pool_dim(d)
    pool = rng.standard_normal((1, pages, page, dp), np.float32)
    pool[..., d:] = 0.0
    pool = jnp.asarray(pool, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((t, nh, d)), jnp.bfloat16)
    hbm = chip_peaks(dev).hbm_bytes_per_s
    results = []
    for bname, rows in latent_batches(args.latent, args.seed).items():
        ql = np.zeros(n_rows, np.int32)
        cl = np.zeros(n_rows, np.int32)
        qs = np.zeros(n_rows, np.int32)
        bt = np.zeros((n_rows, pps), np.int32)
        perm, cursor, used = rng.permutation(pages), 0, 0
        for r, (n, c) in enumerate(rows):
            k = -(-c // page)
            bt[r, :k] = perm[used:used + k]
            used += k
            qs[r], ql[r], cl[r] = cursor, n, c
            cursor += n
        assert used <= pages and cursor <= t
        meta = tuple(jnp.asarray(a) for a in (bt, cl, ql, qs))
        ref, live_tokens = _latent_reference(q, pool, bt, rows, dv, scale)
        live = int(sum(-(-c // page) for _, c in rows))
        pv = tree.latent_visit_counts(t, page, pps, cl, ql, qs)[0]
        for iname, mod in impls.items():
            got, chain_s, kern_s, kern_n, n_vis = _measure_latent(
                mod, q, pool, meta, dv, scale, args.layers, args.reps,
                config)
            kern_us = 1e6 * kern_s / kern_n if kern_n else None
            line = {
                "cell": args.latent, "batch": bname, "impl": iname,
                "live_pages": live, "page_visits": pv, "visits": n_vis,
                "rows": len(rows), "tokens": live_tokens,
                "call_us": 1e6 * chain_s / args.layers,
                "kernel_us": kern_us, "kernel_calls_traced": kern_n,
                "us_per_page": kern_us / pv if kern_us else None,
                "max_abs": float(np.abs(got[:live_tokens] - ref).max()),
                "hbm_bound_us": 1e6 * live * page * d * 2 / hbm,
                "device": dev.device_kind,
            }
            print(json.dumps(line), flush=True)
            results.append(line)
    _save(args, "latent_kernel_bench_%s.json" % args.latent, results)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("impls", nargs="*", help="name=path of another "
                    "paged_attention.py to time beside the tree's")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cell", choices=sorted(HEAD_CELLS),
                    default="serve_chat_1p3b",
                    help="the per-head kernel's shapes and batches")
    ap.add_argument("--latent", choices=sorted(LATENT_CELLS),
                    help="time the latent kernel at this cell's shapes")
    ap.add_argument("--pages", default="", type=lambda s: [
        int(x) for x in s.split(",") if x], help="pages a visit reads in "
        "further copies of the tree's module")
    ap.add_argument("--out", help="a directory for all lines as one JSON "
                    "file")
    args = ap.parse_args()

    impls = {"tree": _load("tree", None)}
    for spec in args.impls:
        name, path = spec.split("=", 1)
        impls[name] = _load(name, path)
    if args.latent:
        return main_latent(args, impls)
    return main_heads(args, impls)


if __name__ == "__main__":
    sys.exit(main())

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
  fetches by its block pipeline a page a visit;
* ``serve_mixed_commandaplus_l4``: T 544, 32 rows, 128 heads over 8 KV
  heads of 128, pages of 128, 244 a row: a 512-token chunk ending at
  4,608 / 16,384 / 30,720 tokens beside 31 decode rows at the cell's
  mix of contexts (``chunk_4608``, ...), and the 31 decode rows alone
  (``decode_31``), each as a full layer's call over a pool of 2,048
  pages (``full_...``) and as a windowed layer's (``window_...``,
  window 4,096) over rings of 37 pages a row, as the engine lays them.

``--pages 1,2,8`` adds copies of the tree's module whose visits read
exactly that many pages (``tree@2``, ...: ``_RAGGED_PAGES`` set, the
byte target lifted); ``--tiles 128,256`` copies whose score tile holds
that many KiB (``tree@tile128k``: ``_SCORE_TILE_BYTES``, the runs of
pages a q block scores at once); ``--q-rows 256`` copies whose big q
blocks hold that many query rows (``tree@q256``: ``_Q_BIG_ROWS``).
``--batches`` keeps the named batches alone. For each batch and
implementation, one line of JSON:

* ``call_us``: host clock over a jitted chain of ``--layers`` calls, each
  fed by the one before, median of ``--reps``, a call — the kernel with
  the wrapper's XLA around it;
* ``kernel_us``: the kernel's own device time a call, from a profiler
  trace of three chains read by ``benchmark/lib/xplane.py``;
* ``visits`` (the work list's live length, a head block's pass; the
  parent's is a visit a page), ``page_visits`` (the live (row, page)
  pairs) and ``us_per_page`` (``kernel_us`` over ``page_visits``);
* ``kpairs`` (thousands of (query head, key) pairs scored: a query token
  j of a row of n sees ``min(window, context - n + j + 1)`` keys) and
  ``us_per_kpair`` (``kernel_us`` over ``kpairs``);
* ``max_abs``: worst absolute difference to a float32 composition a row
  on the device;
* ``live_pages`` and ``hbm_bound_us``: the live KV (a windowed call's
  inside the rows' windows) read once at the chip's peak bandwidth.

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


def _measure(mod, q, kp, vp, meta, layers, reps, window=None):
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
                                          q_starts=qs, use_kernel=True,
                                          **({"window": window} if window
                                             else {}))

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
    "serve_mixed_commandaplus_l4": (544, 128, 8, 128, 2048, 128, 32, 244),
}
# cell -> window, ring pages a row of its windowed layers
WINDOW_CELLS = {"serve_mixed_commandaplus_l4": (4096, 37)}


def _pack(rows, pps, page, packed, pages, tokens, rng, ring=None):
    """(bt, cl, ql, qs) of ``packed`` [(tokens, context)] a row, rows in
    order, tokens packed in row order; pages drawn from a permutation of
    ``pages``, or with ``ring`` R row r's logical page p is ring page
    r R + p % R."""
    bt = np.zeros((rows, pps), np.int32)
    cl, ql, qs = (np.zeros(rows, np.int32) for _ in range(3))
    perm, used, cursor = rng.permutation(pages), 0, 0
    for r, (n, c) in enumerate(packed):
        k = -(-c // page)
        if ring:
            bt[r] = r * ring + np.arange(pps) % ring
        else:
            bt[r, :k] = perm[used:used + k]
            used += k
        qs[r], ql[r], cl[r] = cursor, n, c
        cursor += n
    assert used <= pages and k <= pps and cursor <= tokens
    return bt, cl, ql, qs


def _commandaplus_batches(seed):
    """The Command A+ cell's batches: 31 decode rows at the contexts of
    its traffic's pairs (a prompt and a draw of its output), beside or
    without a 512-token chunk; full and windowed."""
    path = os.path.join(_ROOT, "benchmark", "traffic", "mixed_closed32.json")
    with open(path) as f:
        pairs = sorted(json.load(f)["pairs"])
    t, _, _, _, pages, page, rows, pps = HEAD_CELLS[
        "serve_mixed_commandaplus_l4"]
    window, ring = WINDOW_CELLS["serve_mixed_commandaplus_l4"]
    rng = np.random.default_rng(seed)
    # the longest pair is the chunk's row
    dec = [(1, int(p + rng.integers(1, o + 1))) for p, o in pairs[:rows - 1]]
    mixes = {"chunk_%d" % end: dec + [(512, end)]
             for end in (4608, 16384, 30720)}
    mixes["decode_31"] = dec
    out = {}
    for name, packed in mixes.items():
        out["full_" + name] = _pack(rows, pps, page, packed, pages, t,
                                    rng) + (None, pages)
        out["window_" + name] = _pack(rows, pps, page, packed, rows * ring,
                                      t, rng, ring) + (window, rows * ring)
    return out


def head_batches(cell, seed):
    """name -> (bt, cl, ql, qs, window, pages of the pool) as the engine
    packs them, for one per-head cell."""
    t, _, _, _, pages, page, rows, pps = HEAD_CELLS[cell]
    if cell == "serve_mixed_commandaplus_l4":
        return _commandaplus_batches(seed)
    if cell != "serve_reason_nemotron3nano_l18":
        import chip_smoke

        return {name: b + (None, pages)
                for name, b in chip_smoke.ragged_cell_batches(
                    rows=rows, pages=pages, pages_per_seq=pps, tokens=t,
                    chunk=256, page=page, seed=seed).items()}
    rng = np.random.default_rng(seed)
    dec = [(1, int(c)) for c in rng.integers(1200, 2301, rows)]
    return {name: _pack(rows, pps, page, packed, pages, t, rng)
            + (None, pages)
            for name, packed in (("decode_128", dec),
                                 ("chunk_1536", dec[:-1] + [(256, 1536)]))}


def _head_reference(q, kp, vp, bt, cl, ql, qs, scale, window=None):
    """float32 on the device, a row at a time and 32 of its tokens at a
    time: each token's causal prefix of its row's pages (with a
    ``window``, its last ``window`` keys), its query heads in groups over
    the KV heads."""
    import jax
    import jax.numpy as jnp

    n_kv, _, page, d = kp.shape
    group = q.shape[1] // n_kv

    @jax.jit
    def one(qr, k, v, lim):
        qg = qr.reshape(qr.shape[0], n_kv, group, d)
        s = jnp.einsum("tkgd,kpd->tkgp", qg, k) * scale
        pos = jnp.arange(k.shape[1])[None, None, None, :]
        mask = pos < lim[:, None, None, None]
        if window is not None:
            mask = mask & (pos >= lim[:, None, None, None] - window)
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("tkgp,kpd->tkgd", w, v).reshape(qr.shape)

    out = np.zeros(q.shape, np.float32)
    for r in np.flatnonzero(ql > 0):
        a, n, c = int(qs[r]), int(ql[r]), int(cl[r])
        # the whole row of the table: one shape a query length
        k, v = (x[:, bt[r]].reshape(n_kv, -1, d).astype(jnp.float32)
                for x in (kp, vp))
        for i in range(0, n, 32):
            m = min(32, n - i)
            out[a + i:a + i + m] = np.asarray(one(
                q[a + i:a + i + m].astype(jnp.float32), k, v,
                jnp.arange(c - n + i + 1, c - n + i + m + 1)))
    return out


def main_heads(args, impls) -> int:
    import jax.numpy as jnp

    from paddle_tpu.device.peaks import chip_peaks, require_chip

    dev = require_chip()
    t, h, n_kv, d, pages, page, n_rows, pps = HEAD_CELLS[args.cell]
    tree = impls["tree"]
    for name, attr, unit, values in (
            ("tree@%d", "_RAGGED_PAGES", 1, args.pages),
            ("tree@tile%dk", "_SCORE_TILE_BYTES", 1024, args.tiles),
            ("tree@q%d", "_Q_BIG_ROWS", 1, args.q_rows)):
        for n in values:
            mod = _load(name % n, tree.__file__)
            setattr(mod, attr, n * unit)
            if attr == "_RAGGED_PAGES":
                mod._RAGGED_VISIT_BYTES = 1 << 40
            impls[name % n] = mod
    rng = np.random.default_rng(args.seed)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q = arr(t, h, d)
    hbm = chip_peaks(dev).hbm_bytes_per_s
    pools, results = {}, []
    for bname, (bt, cl, ql, qs, window, n_pages) in head_batches(
            args.cell, args.seed).items():
        if args.batches and bname not in args.batches:
            continue
        if n_pages not in pools:
            pools[n_pages] = (arr(n_kv, n_pages, page, d),
                              arr(n_kv, n_pages, page, d))
        kp, vp = pools[n_pages]
        meta = tuple(jnp.asarray(a) for a in (bt, cl, ql, qs))
        live = tree.ragged_visit_counts(t, h, kp.shape, kp.dtype, q.dtype,
                                        pps, cl, ql, window)[0]
        n, c = ql.astype(np.int64), cl.astype(np.int64)
        seen = [np.minimum(window or c_, np.arange(c_ - n_ + 1, c_ + 1))
                for n_, c_ in zip(n, c) if n_]
        kpairs = h * int(sum(x.sum() for x in seen)) / 1e3
        ref = _head_reference(q, kp, vp, bt, cl, ql, qs, d ** -0.5, window)
        for iname, mod in impls.items():
            got, chain_s, kern_s, kern_n = _measure(
                mod, q, kp, vp, meta, args.layers, args.reps, window)
            pv, n_vis = mod.ragged_visit_counts(
                t, h, kp.shape, kp.dtype, q.dtype, pps, cl, ql,
                **({"window": window} if window else {})) \
                if hasattr(mod, "ragged_visit_counts") else (live, live)
            kern_us = 1e6 * kern_s / kern_n if kern_n else None
            line = {
                "cell": args.cell, "batch": bname, "impl": iname,
                "live_pages": live, "page_visits": pv, "visits": n_vis,
                "rows": int((ql > 0).sum()), "tokens": int(ql.sum()),
                "call_us": 1e6 * chain_s / args.layers,
                "kernel_us": kern_us, "kernel_calls_traced": kern_n,
                "us_per_page": kern_us / pv if kern_us else None,
                "kpairs": kpairs,
                "us_per_kpair": kern_us / kpairs if kern_us else None,
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
    ap.add_argument("--tiles", default="", type=lambda s: [
        int(x) for x in s.split(",") if x], help="KiB of score tile in "
        "further copies of the tree's module")
    ap.add_argument("--q-rows", default="", type=lambda s: [
        int(x) for x in s.split(",") if x], help="query rows of a big q "
        "block in further copies of the tree's module")
    ap.add_argument("--batches", default="", type=lambda s: [
        x for x in s.split(",") if x], help="the batches to time (all)")
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

#!/usr/bin/env python3
"""The ragged paged-attention kernel alone, on the chip, at the serving
cell's shape (T 304, 48 rows, 16 heads of 128, 240 pages of 128, 16 pages a
row, bf16) and the three batches of ``chip_smoke.ragged_cell_batches``.

    chiprun -- python3 tools/ragged_kernel_bench.py [name=path/to/paged_attention.py ...]

Each ``name=path`` adds another copy of the module to the table (the
parent commit's, unpacked under ``.scratch/``); ``tree`` is this
checkout's. For each batch and implementation, one line of JSON:

* ``call_us``: host clock over a jitted chain of ``--layers`` calls, each
  fed by the one before, median of ``--reps``, a call — the kernel with
  the wrapper's XLA around it;
* ``kernel_us``: the kernel's own device time a call, from a profiler
  trace of three chains read by ``benchmark/lib/xplane.py``;
* ``max_abs``: worst absolute difference to the tree's XLA composition;
* ``live_pages`` and ``hbm_bound_us``: the live KV read once at the
  chip's peak bandwidth.

It needs a TPU and says nothing on a CPU.
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


def _measure(mod, q, kp, vp, meta, qs, layers, reps):
    """One implementation on one batch -> (output, seconds a chain of
    ``layers`` calls takes by the host's clock, median of ``reps``; the
    kernel's device seconds and its calls in a trace of three chains)."""
    import jax

    from benchmark.lib import xplane

    def one(qq):
        return mod.ragged_paged_attention(qq, kp, vp, *meta, q_starts=qs,
                                          use_kernel=True)

    @jax.jit
    def chain(qq):
        def body(c, _):
            return (q + one(c) * 1e-3).astype(q.dtype), None
        return jax.lax.scan(body, qq, None, length=layers)[0]

    got = np.asarray(jax.jit(one)(q), np.float32)
    chain(q).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        chain(q).block_until_ready()
        times.append(time.perf_counter() - t0)
    tdir = tempfile.mkdtemp(prefix="ragged_bench_")
    try:
        jax.profiler.start_trace(tdir)
        for _ in range(4):          # the traced window ends at the last start
            chain(q).block_until_ready()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        trace = xplane.reduce_trace(found[0])
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    kern = [op for c in trace["chips"].values() for op in c["ops"]
            if (xplane.classify_kernel(op) or ("",))[0] == "ragged_attn"]
    return (got, statistics.median(times),
            sum(o["seconds"] for o in kern), sum(o["count"] for o in kern))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("impls", nargs="*", help="name=path of another "
                    "paged_attention.py to time beside the tree's")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import chip_smoke
    from paddle_tpu.device.peaks import chip_peaks, require_chip

    dev = require_chip()
    size = chip_smoke.KERNEL_SIZE
    h, d, page = size["heads"], size["head_dim"], size["page"]
    cell = size["cell"]
    impls = {"tree": _load("tree", None)}
    for spec in args.impls:
        name, path = spec.split("=", 1)
        impls[name] = _load(name, path)

    rng = np.random.default_rng(args.seed)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    kp, vp = arr(h, cell["pages"], page, d), arr(h, cell["pages"], page, d)
    q = arr(cell["tokens"], h, d)
    batches = chip_smoke.ragged_cell_batches(page=page, seed=args.seed,
                                             **cell)
    hbm = chip_peaks(dev).hbm_bytes_per_s
    results = []
    for bname, (bt, cl, ql, qs) in batches.items():
        meta = tuple(jnp.asarray(a) for a in (bt, cl, ql))
        qs_j = jnp.asarray(qs)
        live = int(np.where(ql > 0, -(-cl // page), 0).sum())
        ref = np.asarray(impls["tree"].ragged_paged_attention(
            q, kp, vp, *meta, q_starts=qs_j, use_kernel=False), np.float32)
        for iname, mod in impls.items():
            got, chain_s, kern_s, kern_n = _measure(
                mod, q, kp, vp, meta, qs_j, args.layers, args.reps)
            line = {
                "batch": bname, "impl": iname, "live_pages": live,
                "rows": int((ql > 0).sum()), "tokens": int(ql.sum()),
                "call_us": 1e6 * chain_s / args.layers,
                "kernel_us": 1e6 * kern_s / kern_n if kern_n else None,
                "kernel_calls_traced": kern_n,
                "max_abs": float(np.abs(got - ref).max()),
                "hbm_bound_us": 1e6 * live * 2 * h * page * d * 2 / hbm,
                "device": dev.device_kind,
            }
            print(json.dumps(line), flush=True)
            results.append(line)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out",
                           "ragged_kernel_bench.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once and prints the result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Needs a TPU that ``benchmark/lib/peaks.py`` knows, and as many chips as
the cell asks for: otherwise it exits non-zero and prints no result.
See ``benchmark/README.md``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _process_age_s() -> float:
    """Seconds since this process was created (set-up starts there)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            return float(f.read().split()[0]) \
                - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS_START = time.perf_counter() - _process_age_s()
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from lib import runner
    from lib.compiles import CompileCounter
    from lib.peaks import peaks_for

    manifest = runner.load_manifest()
    wl, config, traffic = runner.cell_files(manifest, args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < wl["chips"]:
        print("bench: needs %d TPU chip(s); jax found platform=%r, %d "
              "device(s). Nothing was run."
              % (wl["chips"], dev.platform, len(devices)), file=sys.stderr)
        return 1
    peaks = peaks_for(dev.device_kind)      # an unknown chip is an error

    import paddle_tpu as pt
    from paddle_tpu.config.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    cell = runner.Cell(pt, config, traffic, args.seed, args.seconds,
                       args.trace, wl["chips"], devices[:wl["chips"]],
                       peaks, CompileCounter(), T_PROCESS_START)
    cell.log("compile cache at %s" % cache_dir)
    cell.log("cell %s: config %s, traffic %s (%s), %d chip(s): %s %s; "
             "seed %d, %g s, trace %d"
             % (wl["name"], wl["config"], wl["traffic"], traffic["kind"],
                wl["chips"], dev.platform, dev.device_kind, args.seed,
                args.seconds, args.trace))
    record = runner.run_cell(cell)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    line = runner.result_line(cell, record, manifest, wl["name"], device)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Compiles a cell's step ahead of time for a described ``v5e:2x2`` and
prints ``memory_analysis()``: what the chip's compiler refuses, it
refuses here, at no chip time. Used to fix ``num_blocks`` and the depth.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <name>

The model is built at its real size on the host (a few GB, a minute or
two); nothing runs on a TPU and no time is measured. The kind's own
``rehearse(cell, topo)`` hook does the lowering.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hlo", help="write the compiled HLO text here")
    args = ap.parse_args(argv)

    from lib import aot, runner
    from lib.compiles import CompileCounter

    manifest = runner.load_manifest()
    wl, config, traffic = runner.cell_files(manifest, args.workload)
    topo = aot.describe()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    import paddle_tpu as pt

    cell = runner.Cell(pt, config, traffic, 0, 0.0, 0, wl["chips"],
                       jax.devices()[:wl["chips"]], None,
                       CompileCounter(), time.perf_counter())
    kind = runner.load_module("kinds", traffic["kind"])
    t0 = time.perf_counter()
    compiled = kind.rehearse(cell, topo)
    cell.log("compiled for %s in %.1f s"
             % (topo.devices[0].device_kind, time.perf_counter() - t0))
    aot.report(compiled, cell.log)
    text = compiled.as_text()
    cell.log("in the compiled program: %d tpu_custom_call (Pallas), %d "
             "all-reduce, %d all-gather, %d reduce-scatter, %d "
             "collective-permute"
             % tuple(text.count(s) for s in (
                 'custom_call_target="tpu_custom_call"', " all-reduce(",
                 " all-gather(", " reduce-scatter(",
                 " collective-permute(")))
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())

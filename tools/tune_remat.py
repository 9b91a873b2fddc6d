#!/usr/bin/env python
"""Sweep selective-remat granularity / shapes / optimizer-state formats
for the 1.3B headline bench (the search that produced round 3's
0.397 -> 0.635 MFU jump; results summarized in STATUS.md).

Full per-block remat does ~8N FLOPs/token (fwd 2N + bwd 4N + remat 2N),
so 6N-credited MFU caps at 6/8 of hardware util. recompute_interval=k
skips remat on every k-th block; -k remats ONLY every k-th; 0 disables
remat. Freeing optimizer-state memory (factored/8-bit second moment) is
what makes the low-remat points compile.

Usage: python tools/tune_remat.py [config ...]
  config = interval:batch:seq[:ce_chunks[:opt_mode]]
  opt_mode: 0 = bf16-m/fp32-v, 1 = 8-bit moments, 2 = factored v
"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_one(interval, batch, seq, iters=3, ce_chunks=0, opt_mode=0):
    import paddle_tpu as pt

    # reuse the bench's build/measure/peak so the sweep cannot drift from
    # the committed headline methodology
    from bench import _build, _measure
    from paddle_tpu.device.peaks import chip_peaks, require_chip

    cfg = pt.models.gpt3_1p3B(dropout=0.0, attention_dropout=0.0,
                              recompute=interval != 0,
                              recompute_interval=interval or 1,
                              lm_ce_chunks=ce_chunks)
    okw = [dict(moment_dtype="bfloat16"),
           dict(moment_quant="8bit"),
           dict(moment_dtype="bfloat16", factored_v=True)][opt_mode]
    dev = require_chip()
    model, step, ids, labels = _build(pt, cfg, batch, seq, okw)
    el, _ = _measure(step, ids, labels, iters)
    tps = batch * seq * iters / el
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    mfu = tps * 6 * n_params / chip_peaks(dev).bf16_flops
    return {"interval": interval, "batch": batch, "seq": seq,
            "tokens_per_s": round(tps, 1), "mfu_6n": round(mfu, 4)}


def main():
    configs = []
    for arg in sys.argv[1:]:
        parts = arg.split(":")
        configs.append(tuple(int(p) for p in parts))
    if not configs:
        configs = [(0, 8, 1024, 8, 2), (2, 8, 1024, 0, 0),
                   (0, 4, 2048, 16, 2)]
    for c in configs:
        i, b, s = c[:3]
        ce = c[3] if len(c) > 3 else 0
        om = c[4] if len(c) > 4 else 0
        try:
            r = run_one(i, b, s, ce_chunks=ce, opt_mode=om)
            r["ce_chunks"] = ce
        except Exception as e:
            r = {"interval": i, "batch": b, "seq": s, "ce_chunks": ce,
                 "error": f"{type(e).__name__}: {str(e)[:200]}"}
        r["opt_mode"] = om
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()

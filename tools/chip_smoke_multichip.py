#!/usr/bin/env python3
"""Four chips, one process: the train step over a real dp x mp mesh.

Run by hand through the chip tool on a four-chip host, next to
``bench.py --multichip`` (which drives the two-stage ``CompiledPipeline``
over real chips there)::

    python3 tools/chip_smoke_multichip.py && python3 bench.py --multichip

``multichip_train_phase`` builds ``TrainStep(mesh=...)`` the way
``__graft_entry__.dryrun_multichip`` does (a dp x sp x mp ProcessMesh over
``jax.devices()[:n]``) at GPT-3 1.3B width, checks from
``addressable_shards`` that parameters and batch really sit on all the
devices, and takes a few steps on a repeated batch. Like ``chip_smoke.py``
the script refuses to run without a TPU; tests drive the phase body at
``gpt_tiny`` on host devices.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# depth cut from 24: four chips are charged four times, and the mesh, the
# shardings and the collectives are the same at any depth
MULTICHIP_SIZE = dict(num_layers=4, dp=2, mp=2, batch=8, seq=1024, steps=3)


def _shard_devices(arr):
    return sorted(s.device.id for s in arr.addressable_shards)


def multichip_train_phase(cfg, dp, mp, batch, seq, steps=3,
                          dtype="bfloat16"):
    import jax

    import chip_smoke
    from paddle_tpu.distributed.auto_parallel.process_mesh import ProcessMesh

    n = dp * mp
    if len(jax.devices()) < n:
        raise AssertionError("multichip: needs %d devices, jax found %d"
                             % (n, len(jax.devices())))
    mesh = ProcessMesh(np.arange(n).reshape(dp, 1, mp),
                       dim_names=["dp", "sp", "mp"])
    _, step, ids, labels = chip_smoke.build_train_step(
        cfg, batch, seq, dtype, mesh=mesh,
        batch_specs=[("dp", "sp"), ("dp", "sp")])

    # where things really sit: every parameter and the batch on all n
    # devices, an mp-split weight holding 1/mp of its columns per shard
    want = sorted(d.id for d in jax.devices()[:n])
    for name, a in zip(step._names, step.param_arrays):
        if _shard_devices(a) != want:
            raise AssertionError("multichip: parameter %s sits on devices "
                                 "%r, not %r" % (name, _shard_devices(a),
                                                 want))
    qkv = next(a for nm, a in zip(step._names, step.param_arrays)
               if nm.endswith("qkv_proj.weight"))
    qkv_shard = tuple(qkv.addressable_shards[0].data.shape)
    if qkv_shard != (qkv.shape[0], qkv.shape[1] // mp):
        raise AssertionError("multichip: qkv weight shard %r of %r is not "
                             "split %d ways on mp"
                             % (qkv_shard, tuple(qkv.shape), mp))
    placed = step._prepare_batch((ids, labels))
    batch_shard = tuple(placed[0].addressable_shards[0].data.shape)
    if _shard_devices(placed[0]) != want or \
            batch_shard != (batch // dp, seq):
        raise AssertionError("multichip: batch shard %r on devices %r"
                             % (batch_shard, _shard_devices(placed[0])))

    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels)))    # float() waits
        times.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError("multichip: loss not finite and falling: %r"
                             % (losses,))
    step_s = min(times[1:]) if len(times) > 1 else times[0]
    return {"mesh": {"dp": dp, "sp": 1, "mp": mp}, "devices": want,
            "layers": cfg.num_layers, "batch": batch, "seq": seq,
            "qkv_weight_shard": list(qkv_shard),
            "batch_shard": list(batch_shard),
            "losses": [round(x, 4) for x in losses],
            "compile_s": round(times[0] - step_s, 2),
            "step_s": round(step_s, 4)}


def main() -> int:
    import chip_smoke

    device = chip_smoke.require_tpu()
    import paddle_tpu as pt
    from paddle_tpu.config.compile_cache import place_compile_cache

    print("chip_smoke_multichip: device %s, compile cache dir %s"
          % (json.dumps(device), place_compile_cache()))
    size = dict(MULTICHIP_SIZE)
    cfg = chip_smoke.model_config(pt, size.pop("num_layers"))
    rep = multichip_train_phase(cfg, **size)
    print("chip_smoke_multichip: train ok: %s" % json.dumps(rep))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

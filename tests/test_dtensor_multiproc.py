"""dtensor_from_local under REAL multi-process jax.distributed: the
global is assembled from per-rank shards (VERDICT r2 next #5; reference:
python/paddle/distributed/auto_parallel/api.py:631), and
unshard_dtensor/local_value round-trip correctly."""
import multiprocessing as mp
import os
import socket

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker(rank, nprocs, coord, q):
    # JAX_PLATFORMS=cpu is inherited from conftest
    os.environ.pop("XLA_FLAGS", None)  # 1 local CPU device per process
    import jax

    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nprocs, process_id=rank)
        import paddle_tpu as pt
        from paddle_tpu.distributed import (Partial, ProcessMesh,
                                            Replicate, Shard,
                                            dtensor_from_local,
                                            local_value, unshard_dtensor)

        mesh = ProcessMesh(np.arange(nprocs), dim_names=["x"])

        # ---- Shard(0): ranks pass DISTINCT local shards ----------------
        local = np.full((3, 4), float(rank + 1), np.float32)
        dt = dtensor_from_local(pt.to_tensor(local), mesh, [Shard(0)])
        assert tuple(dt.shape) == (3 * nprocs, 4), dt.shape
        lv = local_value(dt).numpy()
        np.testing.assert_allclose(lv, local)
        full = unshard_dtensor(dt).numpy()
        expect = np.concatenate(
            [np.full((3, 4), float(r + 1), np.float32)
             for r in range(nprocs)], axis=0)
        np.testing.assert_allclose(full, expect)

        # ---- Replicate -------------------------------------------------
        rep = np.arange(6, dtype=np.float32).reshape(2, 3)
        dtr = dtensor_from_local(pt.to_tensor(rep), mesh, [Replicate()])
        assert tuple(dtr.shape) == (2, 3)
        np.testing.assert_allclose(unshard_dtensor(dtr).numpy(), rep)

        # ---- Partial: unshard sums the per-rank contributions ---------
        part = np.full((2, 2), float(10 * (rank + 1)), np.float32)
        dtp = dtensor_from_local(pt.to_tensor(part), mesh, [Partial()])
        np.testing.assert_allclose(local_value(dtp).numpy(), part)
        total = unshard_dtensor(dtp).numpy()
        np.testing.assert_allclose(
            total, sum(10.0 * (r + 1) for r in range(nprocs)))

        q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        import traceback

        q.put((rank, f"FAIL: {e}\n{traceback.format_exc()}"))
        raise


@pytest.mark.timeout(300)
def test_dtensor_from_local_multiprocess():
    nprocs = 2
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    coord = f"127.0.0.1:{_free_port()}"
    procs = [ctx.Process(target=_worker, args=(r, nprocs, coord, q))
             for r in range(nprocs)]
    for p in procs:
        p.start()
    try:
        results = {}
        for _ in range(nprocs):
            rank, status = q.get(timeout=240)
            results[rank] = status
        for p in procs:
            p.join(60)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(10)
    assert all(v == "ok" for v in results.values()), results
    assert all(p.exitcode == 0 for p in procs), [p.exitcode for p in procs]

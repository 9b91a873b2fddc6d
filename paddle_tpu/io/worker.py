"""Multi-process DataLoader workers over the native shared-memory ring
(reference: python/paddle/io/dataloader/dataloader_iter.py:368
_DataLoaderIterMultiProcess + worker.py _worker_loop:460, with the
mmap_allocator shared-memory tensor transport).

Each worker process opens two SPSC rings (native/shm_ring.cc): an index
ring (parent -> worker: pickled batch-index lists) and a result ring
(worker -> parent: pickled (batch_id, collated numpy arrays)). Batches
move as raw bytes through POSIX shm — no multiprocessing.Queue pipe copy.
"""
from __future__ import annotations

import os
import pickle
from typing import List

__all__ = ["worker_entry", "ShmWorkerPool"]

_RING_CAP = 64 << 20       # result ring: 64 MB
_IDX_CAP = 1 << 20


def worker_entry(dataset_blob: bytes, collate_blob: bytes, idx_ring_name: str,
                 out_ring_name: str, worker_id: int, seed: int):
    """Runs in the worker process. The parent sets JAX_PLATFORMS=cpu in
    the environment BEFORE spawning (env is read when the child imports
    jax during unpickling); the config update here is belt-and-braces."""
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    import numpy as np

    from ..core import native

    np.random.seed(seed + worker_id)
    dataset = pickle.loads(dataset_blob)
    collate = pickle.loads(collate_blob)
    idx_ring = native.ShmRing(idx_ring_name)
    out_ring = native.ShmRing(out_ring_name)
    try:
        while True:
            msg = pickle.loads(idx_ring.pop(timeout=3600))
            if msg is None:  # shutdown
                break
            batch_id, indices = msg
            try:
                samples = [dataset[i] for i in indices]
                payload = (batch_id, collate(samples), None)
            except Exception as e:  # ship the error to the parent
                payload = (batch_id, None, repr(e))
            try:
                out_ring.push(pickle.dumps(payload, protocol=4),
                              timeout=3600)
            except ValueError:
                # batch larger than the ring: ship a small error instead
                out_ring.push(pickle.dumps(
                    (batch_id, None,
                     f"collated batch exceeds the {_RING_CAP >> 20} MB "
                     "shm ring; lower batch_size or use num_workers=0"),
                    protocol=4), timeout=3600)
    except BrokenPipeError:
        pass


class ShmWorkerPool:
    """Parent-side pool: one (index, result) ring pair per worker."""

    def __init__(self, dataset, collate_fn, num_workers: int, seed: int = 0):
        import multiprocessing as mp

        from ..core import native
        from ..device import cpu_children

        self._native = native
        uid = f"{os.getpid()}_{id(self)}"
        self._idx_rings = []
        self._out_rings = []
        self._procs = []
        ctx = mp.get_context("spawn")
        ds_blob = dataset if isinstance(dataset, bytes) \
            else pickle.dumps(dataset, protocol=4)
        co_blob = collate_fn if isinstance(collate_fn, bytes) \
            else pickle.dumps(collate_fn, protocol=4)
        with cpu_children():
            for w in range(num_workers):
                iname = f"/pt_dl_{uid}_i{w}"
                oname = f"/pt_dl_{uid}_o{w}"
                self._idx_rings.append(
                    native.ShmRing(iname, capacity=_IDX_CAP, create=True))
                self._out_rings.append(
                    native.ShmRing(oname, capacity=_RING_CAP, create=True))
                p = ctx.Process(
                    target=worker_entry,
                    args=(ds_blob, co_blob, iname, oname, w, seed),
                    daemon=True)
                p.start()
                self._procs.append(p)
        self.num_workers = num_workers

    def dispatch(self, batch_id: int, indices: List[int]):
        w = batch_id % self.num_workers
        self._idx_rings[w].push(
            pickle.dumps((batch_id, list(indices)), protocol=4))

    def collect(self, batch_id: int, timeout: float = 300.0):
        """Pop the next result from the worker that owns batch_id (SPSC +
        in-order dispatch per worker means results arrive in order)."""
        import time as _time

        w = batch_id % self.num_workers
        deadline = _time.monotonic() + timeout
        while True:
            # short poll so a dead worker surfaces as a clear error
            # instead of a silent multi-minute hang
            try:
                raw = self._out_rings[w].pop(timeout=2.0)
                break
            except TimeoutError:
                if not self._procs[w].is_alive():
                    raise RuntimeError(
                        f"DataLoader worker {w} died (exitcode "
                        f"{self._procs[w].exitcode})") from None
                if _time.monotonic() > deadline:
                    raise
        bid, data, err = pickle.loads(raw)
        if err is not None:
            raise RuntimeError(f"DataLoader worker error: {err}")
        assert bid == batch_id, (bid, batch_id)
        return data

    def shutdown(self):
        for r in self._idx_rings:
            try:
                r.push(pickle.dumps(None), timeout=1)
            except Exception:
                pass
        for p in self._procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        for r in self._idx_rings + self._out_rings:
            try:
                r.free()
            except Exception:
                pass

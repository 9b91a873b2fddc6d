"""paddle.distributed.spawn (reference: python/paddle/distributed/spawn.py:463)."""
from __future__ import annotations

import multiprocessing as mp
import os
import socket

from ..config import knobs
from ..device import cpu_children

__all__ = ["spawn"]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker(func, rank, nprocs, master, backend, args):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_MASTER"] = master
    os.environ["PADDLE_LOCAL_RANK"] = str(rank)
    if backend:
        os.environ["PADDLE_DIST_BACKEND"] = backend
    if not knobs.get_bool("PADDLE_TPU_KEEP_BACKEND_LOGS"):
        # demote jaxlib's C++ "[Gloo] Rank N is connected..." fd-2 spam
        # to the framework logger at DEBUG before anything inits jax
        from .log_utils import install_stderr_filter

        install_stderr_filter()
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, backend=None,
          **options):
    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    master = f"127.0.0.1:{_free_port()}"
    ctx = mp.get_context("spawn")
    procs = []
    # workers are CPU orchestration (the parent has usually touched jax
    # already and holds the chip)
    with cpu_children():
        for rank in range(nprocs):
            p = ctx.Process(
                target=_worker,
                args=(func, rank, nprocs, master, backend, args),
                daemon=daemon)
            p.start()
            procs.append(p)
    if join:
        for p in procs:
            p.join()
        for p in procs:
            if p.exitcode != 0:
                raise RuntimeError(
                    f"spawned process exited with code {p.exitcode}")
    return procs

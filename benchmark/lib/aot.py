"""Ahead-of-time compilation for a described (not attached) v5e: what
``benchmark/rehearse.py`` and the kinds' ``rehearse`` hooks share.

Nothing runs and nothing is placed on the described devices: arguments
are ``jax.ShapeDtypeStruct`` with shardings on them. The program's own
code asks ``jax.default_backend()`` and builds its mesh from
``jax.devices()``, which still say "cpu" here, so the rehearsal steers
it from outside (this file), not through an option of the program.
"""
from __future__ import annotations

import contextlib
import os
from unittest import mock

import numpy as np


def describe(topology: str = "v5e:2x2"):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)


@contextlib.contextmanager
def as_tpu():
    """The library picks kernels by ``jax.default_backend()``."""
    import jax

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        yield


class Stop(Exception):
    """Raised by the captured jit in place of running."""


@contextlib.contextmanager
def capture_jit(captured: dict):
    """Inside, ``jax.jit(f, **kw)`` returns a stand-in that records
    ``f``, ``kw`` and the arguments of its first call, then raises
    ``Stop``: the program builds its step exactly as it would, and the
    rehearsal lowers that step for the described chip instead."""
    import jax

    def fake(fun, **kw):
        def call(*args):
            captured.update(fun=fun, kw=kw, args=args)
            raise Stop
        return call

    with mock.patch.object(jax, "jit", fake):
        yield


def to_struct(tree, place):
    """Arrays -> ShapeDtypeStructs placed by ``place(array)``."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=place(a)), tree)


def report(compiled, log):
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    gib = 2.0 ** 30
    log("memory_analysis per device: arguments %.3f GiB, outputs %.3f, "
        "temporaries %.3f, aliased %.3f -> total %.3f GiB of 15.75"
        % (ma.argument_size_in_bytes / gib, ma.output_size_in_bytes / gib,
           ma.temp_size_in_bytes / gib, ma.alias_size_in_bytes / gib,
           total / gib))
    return total

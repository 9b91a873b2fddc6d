"""A short ``jax.profiler`` trace of a steady part of the window, reduced
and thrown away: traces are large, and only the reduction is reported."""
from __future__ import annotations

import glob
import os
import shutil
import tempfile


class TracedPart:
    def __init__(self):
        self.dir = None

    def start(self):
        import jax

        # under TMPDIR, which the driver gives each side for itself
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def reduce(self):
        """Outside the window: parse the trace, delete the files."""
        from . import xplane

        if self.dir is None:
            return None
        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return xplane.reduce_trace(found[0]) if found else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None

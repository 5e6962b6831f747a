"""The profiler around the first seconds of the window, host spans on the
same clock, and the read-back of the trace."""
from __future__ import annotations

import contextlib
import os
import shutil


def span(name: str, **kw):
    """A host span in the profiler's own trace; free when no trace runs."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


class Tracer:
    """`with tracer.window(): ...` traces what runs inside when `on`; the
    reduced trace is then at `.trace` (the neutral structure of
    `reduce/xplane.py`)."""

    def __init__(self, on: bool, out_dir: str):
        self.on = on
        self.dir = os.path.join(out_dir, "trace")
        self.trace = None
        self._open = False

    def start(self):
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = span("bench.trace_window")
        self._span.__enter__()
        self._open = True

    def stop(self):
        if not self._open:
            return
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._open = False

    @contextlib.contextmanager
    def window(self):
        self.start()
        try:
            yield self
        finally:
            self.stop()

    def read(self):
        """Parse the trace (after the window and the memory reading), then
        delete the files: a run writes little to disk."""
        if not self.on:
            return None
        from ..reduce import xplane
        self.trace = xplane.load(xplane.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace

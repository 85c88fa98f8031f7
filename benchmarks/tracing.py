"""The traced part of a ``--trace 1`` run: the profiler around the last
seconds of the window, the benchmark's own annotations, and the program's
spans moved onto the trace's clock."""

from __future__ import annotations

import contextlib
import os
import shutil
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import common
import trace_reduce

TRACE_DIR = os.path.join(common.OUT_DIR, "trace")


class DeviceTrace:
    """Off (``on=False``) it costs nothing. On, ``maybe_start`` starts the
    profiler once the window has ``length_s`` left and ``stop`` ends it
    after the window has closed, so that neither the start nor the write
    falls inside what the run's rate is taken over."""

    def __init__(self, on: bool, length_s: float):
        self.on, self.length_s = bool(on), float(length_s)
        self.running = False
        self.done = False
        self._mark = None
        self.sync_perf: Optional[float] = None
        self.overhead_s = 0.0

    def start(self) -> None:
        """Start now (a window no longer than the trace)."""
        if not self.on or self.running or self.done:
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.running = True
        self._mark = jax.profiler.TraceAnnotation("bench_window")
        self.sync_perf = time.perf_counter()
        self._mark.__enter__()

    def maybe_start(self, elapsed: float, seconds: float) -> bool:
        """True where the profiler was started by this call; what that took
        is in ``overhead_s`` and is no part of the window's work."""
        if self.on and not self.running and not self.done \
                and elapsed >= seconds - self.length_s:
            t = time.perf_counter()
            self.start()
            self.overhead_s += time.perf_counter() - t
            return True
        return False

    @contextlib.contextmanager
    def annotate(self, name: str):
        if not self.running:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None
        jax.profiler.stop_trace()
        self.running, self.done = False, True

    def reduced(self, gap_default: str,
                spans: Sequence[Tuple[str, float, float]] = ()
                ) -> Dict[str, Any]:
        """The reduced trace. ``spans`` are the program's, as (name,
        perf_counter start, seconds): moved onto the trace's clock, they
        label idle gaps beside the benchmark's own annotations."""
        trace = trace_reduce.load_xplane(TRACE_DIR)
        window = trace_reduce.window_of(trace)
        if window is not None and self.sync_perf is not None:
            w0 = window[0]
            for name, start, dur in spans:
                trace["host"].append(
                    (name, int(w0 + (start - self.sync_perf) * 1e9),
                     int(dur * 1e9)))
        out = trace_reduce.reduce_trace(trace, gap_default=gap_default)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)  # tens of MB a run
        return out

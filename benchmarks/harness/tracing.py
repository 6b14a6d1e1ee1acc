"""The traced run's instrumentation on the benchmark's side: a profiler
window that opens inside the measured window and is reduced afterwards.
The spans in it are the program's own (``hc:<name>``,
``tpu_hc_bench.obs.timeline``); the benchmark adds none."""

from __future__ import annotations

import os
import shutil
import threading

from harness import xplane

TRACE_START_FRACTION = 0.25     # of the window, before the trace opens
TRACE_SECONDS = 6.0             # a few seconds: traces are large


class WindowTracer:
    """Opens the profiler ``TRACE_START_FRACTION`` into the window, from
    a timer thread, for ``TRACE_SECONDS`` (or the quarter of a short
    window), and closes it again; the window itself is never paused."""

    def __init__(self, trace_dir: str, seconds: float):
        self.dir = trace_dir
        self.start_after = TRACE_START_FRACTION * seconds
        self.length = min(TRACE_SECONDS, 0.25 * seconds)
        self._lock = threading.Lock()
        self._open = False
        self._timers: list[threading.Timer] = []
        shutil.rmtree(trace_dir, ignore_errors=True)

    def arm(self) -> None:
        for delay, fn in ((self.start_after, self._start),
                          (self.start_after + self.length, self.stop)):
            t = threading.Timer(delay, fn)
            t.daemon = True
            t.start()
            self._timers.append(t)

    def _start(self) -> None:
        import jax

        with self._lock:
            if not self._open:
                jax.profiler.start_trace(self.dir)
                self._open = True

    def stop(self) -> None:
        import jax

        for t in self._timers:
            t.cancel()
        with self._lock:
            if self._open:
                jax.profiler.stop_trace()
                self._open = False

    def reduce(self, chips: int) -> dict | None:
        try:
            profile = xplane.load(xplane.find_xplane(self.dir))
        except FileNotFoundError:
            return None
        red = xplane.reduce_trace(profile, chips)
        red["spans"] = xplane.host_spans(profile)
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(self.dir, ignore_errors=True)
        return red

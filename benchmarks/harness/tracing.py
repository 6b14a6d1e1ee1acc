"""The traced run's instrumentation, all on the benchmark's side: spans
around the calls into the program's compiled buckets, and a profiler
window that opens inside the measured window and is reduced afterwards."""

from __future__ import annotations

import os
import shutil
import threading

from harness import xplane

TRACE_START_FRACTION = 0.25     # of the window, before the trace opens
TRACE_SECONDS = 6.0             # a few seconds: traces are large


class _Annotated:
    """A compiled bucket with a ``bench:<kind>_<bucket>`` span around
    each call; everything else is the executable's own."""

    def __init__(self, name: str, exe):
        self._name, self._exe = xplane.SPAN_PREFIX + name, exe

    def __call__(self, *a, **k):
        import jax

        with jax.profiler.TraceAnnotation(self._name):
            return self._exe(*a, **k)

    def __getattr__(self, item):
        return getattr(self._exe, item)


def annotate_engine(engine) -> None:
    engine.compiled = {key: _Annotated(f"{key[0]}_{key[1]}", exe)
                       for key, exe in engine.compiled.items()}


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

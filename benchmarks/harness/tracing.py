"""The traced run's instrumentation on the benchmark's side: a profiler
window that opens inside the measured window and is reduced afterwards.
The spans in it are the program's own (``hc:<name>``,
``tpu_hc_bench.obs.timeline``); the benchmark adds none."""

from __future__ import annotations

import os
import shutil
import threading
import time

from harness import xplane

TRACE_START_FRACTION = 0.25     # of the window, before the trace opens
TRACE_SECONDS = 6.0             # a few seconds: traces are large


def session_class():
    """JAX's own profiler session, the class ``jax.profiler.start_trace``
    builds (jax 0.9.0: ``jax._src.lib._profiler.ProfilerSession``).  It is
    no public name, so it is looked up in one place and a JAX that has
    moved it fails here, by name, before a window opens."""
    try:
        from jax._src.lib import _profiler

        return _profiler.ProfilerSession
    except (ImportError, AttributeError) as e:
        raise RuntimeError(
            "benchmark: this JAX has no jax._src.lib._profiler."
            "ProfilerSession, which harness/tracing.py takes its trace "
            "from; a traced run cannot be made") from e


def new_session():
    """A profiler session with the options ``jax.profiler.start_trace``
    gives it (device operations, the host's TraceMe spans, the Python
    tracer).  Taken from JAX's own binding and not through
    ``start_trace`` / ``stop_trace``, because ``stop_trace`` also EXPORTS:
    it writes the trace as ``.xplane.pb`` and converts it to a
    ``.trace.json.gz`` nobody here reads, which took 100-150 s of a
    traced serve run and wrote 160-220 MB (PR 32); ``stop()`` hands the
    same serialized trace over in memory."""
    import jax

    jax.devices()       # the backend before the session, as start_trace
    return session_class()()


class WindowTracer:
    """Opens the profiler ``TRACE_START_FRACTION`` into the window, from
    a timer thread, for ``TRACE_SECONDS`` (or the quarter of a short
    window), and closes it again; the window itself is never paused.
    The trace stays in memory; ``BENCH_KEEP_TRACE`` in the environment
    writes it under ``trace_dir`` as the profiler's own export would lie
    (``tools/gap_phases.py`` reads it there)."""

    def __init__(self, trace_dir: str, seconds: float):
        self.dir = trace_dir
        self.start_after = TRACE_START_FRACTION * seconds
        self.length = min(TRACE_SECONDS, 0.25 * seconds)
        self._lock = threading.Lock()
        self._session = None
        self._xspace: bytes | None = None
        self.stop_s = None          # what closing the profiler took
        self._timers: list[threading.Timer] = []
        shutil.rmtree(trace_dir, ignore_errors=True)

    def arm(self) -> None:
        session_class()     # in the caller's thread: a timer's error is lost
        for delay, fn in ((self.start_after, self._start),
                          (self.start_after + self.length, self.stop)):
            t = threading.Timer(delay, fn)
            t.daemon = True
            t.start()
            self._timers.append(t)

    def _start(self) -> None:
        with self._lock:
            if self._session is None and self._xspace is None:
                self._session = new_session()

    def stop(self) -> None:
        for t in self._timers:
            t.cancel()
        with self._lock:
            if self._session is not None:
                t0 = time.monotonic()
                self._xspace = self._session.stop()
                self.stop_s = time.monotonic() - t0
                self._session = None

    def reduce(self, chips: int) -> dict | None:
        """The trace reduced (``xplane.reduce_trace``'s keys, ``spans``
        and the result line's ``idle_gaps``); ``reduce_s`` says what
        reading and reducing it took and ``stop_s`` what closing the
        profiler took before, which is not in it."""
        if self._xspace is None:
            return None
        t0 = time.monotonic()
        if os.environ.get("BENCH_KEEP_TRACE"):
            kept = os.path.join(self.dir, "plugins", "profile", "kept")
            os.makedirs(kept)
            with open(os.path.join(kept, "bench.xplane.pb"), "wb") as f:
                f.write(self._xspace)
        profile = xplane.parse(self._xspace)
        self._xspace = None
        red = xplane.reduce_trace(profile, chips)
        red["spans"] = xplane.host_spans(profile)
        red["idle_gaps"] = xplane.name_gaps(red["gaps"], red["spans"])
        red["reduce_s"] = time.monotonic() - t0
        red["stop_s"] = self.stop_s
        return red

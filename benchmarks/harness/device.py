"""What the benchmark asks of the machine: a TPU or no run, the table of
peaks, the memory reading, the compile counter and the process clock."""

from __future__ import annotations

import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(SystemExit):
    """Raised with exit code 2 and no result line."""


def process_start_monotonic() -> float:
    """``time.monotonic()`` at which this process was started, from
    ``/proc`` (set-up counts the interpreter's own start and imports);
    falls back to "now" where ``/proc`` is absent."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(0.0, age)
    except Exception:
        return time.monotonic()


def require_tpu(chips: int) -> dict:
    """The device record of the result line; exits 2 with no result when
    JAX found no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if jax.default_backend() != "tpu" or rec["platform"] != "tpu":
        print(f"benchmark: JAX found no TPU ({rec}); the timed path does "
              f"not fall back to the CPU", flush=True)
        raise NoAccelerator(2)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), JAX found "
              f"{len(devs)}", flush=True)
        raise NoAccelerator(2)
    return rec


def peaks(kind: str) -> dict:
    """The sourced peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in "
                       f"benchmarks/harness/peaks.json (known: "
                       f"{sorted(table)}): add the sourced figures")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest of the chips used, read after the window
    and before the reference touches the chip.  On this runtime the
    allocator's ``peak_bytes_in_use`` counts live buffers only (weights,
    state, KV pool, batch); the temporaries of the compiled programs sit
    in a region the runtime reserves apart (``peak_bytes_reserved``:
    12.1 GB beside 4.35 GB in use for the GPT-2-medium step at batch 16,
    my chip run, PR 23 — why PR 21 read 348 MiB beside 4.3 GiB).  Nothing
    else can use reserved bytes, so the peak is the sum."""
    import jax

    peak = 0
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


class CompileCounter:
    """Counts JAX's own backend-compile events (a program compiled, or
    fetched from the persistent cache) while armed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        self.total = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.total += 1
            if self.armed:
                self.count += 1

    def arm(self) -> None:
        self.count = 0
        self.armed = True

    def disarm(self) -> int:
        self.armed = False
        return self.count

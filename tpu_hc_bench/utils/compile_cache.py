"""The persistent XLA compile cache: one directory, placeable from outside.

Every entry point (launcher, ``serve``, ``bench.py``,
``scripts/bench_serve.py``, and the ``tune``/``fleet`` children, which
are launcher subprocesses) resolves the cache here, so they all share
compiles:

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory is the cache.  JAX
  reads the variable itself; this module configures nothing (whoever
  placed the cache owns its policy too), and child processes inherit it.
- unset: ``<checkout>/.jax_cache``, derived from the package's own
  location — the same path for every entry point and every run, with or
  without ``--train_dir`` — caching sub-second compiles too.  The path
  is part of the cache key, so a directory that moves with the run dir
  never hits.

``--compile_cache=off`` opts a run out of the accounting (no banner, no
manifest record); it is the only value the flag takes.

``resolve`` also starts the process's one ``jax.monitoring`` listener
(``listen``): it counts backend compiles (``process_compiles``) and
forwards JAX's compile and cache events to the set-up account
(``obs.timeline.setup_account``), whose outcomes the banners read.
"""

from __future__ import annotations

import os
import threading

from tpu_hc_bench.obs import timeline

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the package directory."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def check_flag(spec: str | None) -> None:
    """Loud flag-time check: the cache is placed by the environment, not
    by a per-run path."""
    if spec is not None and spec.strip().lower() != "off":
        raise ValueError(
            f"--compile_cache takes only 'off' (got {spec!r}): place the "
            f"cache with {ENV_VAR}=<dir>; unset, it is {default_dir()}")


def resolve(spec: str | None) -> str | None:
    """The active cache dir (created), or None under ``off``.  Call
    before anything lowers: it also starts the process's compile
    listener, so that the set-up account sees every compile."""
    import jax

    listen()
    check_flag(spec)
    if spec is not None:
        return None
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = default_dir()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # cache sub-second compiles too: warm-start wins on small
        # programs are the point, and entries are cheap
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    os.makedirs(cache_dir, exist_ok=True)
    return cache_dir


#: JAX's compile events, as time spans -> the set-up account's fields
_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
#: the persistent cache's events inside a backend compile: a hit, and
#: an entry written (JAX names the write ``cache_misses``)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "loaded",
                 "/jax/compilation_cache/cache_misses": "compiled"}
_compiles = {"n": 0, "listening": False}
_compiles_lock = threading.Lock()


def _on_span(event: str, start: float, end: float, **kw) -> None:
    field = _SPAN_EVENTS.get(event)
    if field is None:
        return
    if field == "compile_s":
        with _compiles_lock:
            _compiles["n"] += 1
    timeline.setup_event(field, start, end)


def _on_event(event: str, **kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        timeline.setup_cache(outcome)


def listen() -> None:
    """Start the process's one compile listener (once): it counts
    backend compiles (``process_compiles``) and forwards JAX's trace,
    lowering, backend-compile and cache events to the set-up account
    (``obs.timeline.setup_account``)."""
    with _compiles_lock:
        if _compiles["listening"]:
            return
        import jax.monitoring

        jax.monitoring.register_event_time_span_listener(_on_span)
        jax.monitoring.register_event_listener(_on_event)
        _compiles["listening"] = True


def process_compiles() -> int:
    """Programs THIS process has compiled or fetched from the persistent
    cache, by JAX's own backend-compile events, counted from the start
    of the listener (``resolve``, or the first call here): a difference
    of two calls is the compiles between them.  It sees no other
    process's entries in a shared cache dir, and counts compiles under
    ``--compile_cache=off`` too."""
    listen()
    with _compiles_lock:
        return _compiles["n"]

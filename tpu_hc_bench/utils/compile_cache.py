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
"""

from __future__ import annotations

import os
import threading

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
    before anything lowers."""
    import jax

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


def entry_count(cache_dir: str) -> int:
    """Files under the cache dir — the hit/miss denominator: entries
    that appear between run start and end-of-warmup are the compiles
    this run paid for."""
    return sum(len(files) for _, _, files in os.walk(cache_dir))


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiles = {"n": 0, "listening": False}
_compiles_lock = threading.Lock()


def _on_compile_event(event: str, duration: float, **kw) -> None:
    if event == _BACKEND_COMPILE:
        with _compiles_lock:
            _compiles["n"] += 1


def process_compiles() -> int:
    """Programs THIS process has compiled or fetched from the persistent
    cache, by JAX's own backend-compile events, counted from the first
    call (which starts the one listener a process keeps): a difference of
    two calls is the compiles between them.  Unlike ``entry_count`` it
    sees no other process's entries in a shared cache dir, counts
    compiles under ``--compile_cache=off`` too, and counts nothing that
    only reached the cache earlier."""
    with _compiles_lock:
        if not _compiles["listening"]:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_event)
            _compiles["listening"] = True
        return _compiles["n"]

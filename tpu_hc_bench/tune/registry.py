"""The tuned-config registry: ``artifacts/tuned/<hardware_key>.json``.

A tuned config is only meaningful on the hardware it was measured on,
so rows are keyed by a *hardware key* — chip generation + per-chip HBM
+ world size (``v5e-16gb-w4``, ``cpu-0gb-w1``).  One JSON file per
hardware key holds one row per zoo member: the winning lever overrides,
the score, and provenance (git sha, journal path, measured steps).

Consumers:

- ``--config=auto`` (``flags.BenchmarkConfig.resolve``): look up the
  row for (member, live hardware), apply its overrides to every lever
  the user left at the default, and record ``config_source=auto``; no
  row falls back LOUDLY to the BASELINE defaults
  (``config_source=baseline``) — never silently.
- ``python -m tpu_hc_bench.tune promote`` writes rows from a finished
  search journal; ``show`` renders them; ``scripts/sweep_zoo.py
  --from_registry`` re-validates them.

Environment overrides (tests, cross-machine workflows):
``TPU_HC_TUNE_REGISTRY`` points at a different registry dir;
``TPU_HC_TUNE_HW`` pins the hardware key without querying the backend.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path

__all__ = [
    "REGISTRY_ENV", "HW_ENV", "default_registry_dir", "registry_path",
    "hardware_key", "hardware_key_from_child", "load_rows", "lookup",
    "promote", "resolve_auto",
]

REGISTRY_ENV = "TPU_HC_TUNE_REGISTRY"
HW_ENV = "TPU_HC_TUNE_HW"


def default_registry_dir() -> Path:
    env = os.environ.get(REGISTRY_ENV)
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[2] / "artifacts" / "tuned"


def hardware_key(world: int | None = None) -> str:
    """``<chip-kind>-<hbm_gb>gb-w<world>`` from the live backend (or
    the ``TPU_HC_TUNE_HW`` pin).

    The three components are exactly what changes a best-known config:
    the chip generation (MXU shape/peak), the per-chip HBM (the batch
    and accumulator-dtype walls), and the world size (collective
    bytes/step, per-chip share of the global batch).
    """
    env = os.environ.get(HW_ENV)
    if env:
        return env
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind.lower().replace(" ", "_").replace("/", "_")
    hbm_gb = 0
    try:
        stats = dev.memory_stats() or {}
        hbm_gb = int(round(stats.get("bytes_limit", 0) / 2**30))
    except Exception:
        pass
    w = world if world is not None else jax.device_count()
    return f"{kind}-{hbm_gb}gb-w{w}"


def hardware_key_from_child() -> str:
    """``hardware_key()`` without initializing a backend in THIS
    process: a chip belongs to one process at a time, and the tuner and
    sweep parents go on to launch children that need it.  A short-lived
    child queries the backend and has exited — released the chip —
    before this returns.  The ``TPU_HC_TUNE_HW`` pin needs no child."""
    import subprocess
    import sys

    pinned = os.environ.get(HW_ENV)
    if pinned:
        return pinned
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(Path(__file__).resolve().parents[2]),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    probe = subprocess.run(
        [sys.executable, "-c",
         "from tpu_hc_bench.tune import registry; "
         "print(registry.hardware_key())"],
        env=env, capture_output=True, text=True, timeout=600)
    if probe.returncode != 0:
        raise RuntimeError(
            f"hardware-key probe child failed (exit {probe.returncode}):"
            f"\n{probe.stderr[-2000:]}")
    return probe.stdout.strip().splitlines()[-1]


def registry_path(hardware: str,
                  registry_dir: str | Path | None = None) -> Path:
    base = Path(registry_dir) if registry_dir else default_registry_dir()
    return base / f"{hardware}.json"


def load_rows(hardware: str,
              registry_dir: str | Path | None = None) -> dict:
    """The hardware key's member rows (``{}`` when none exist)."""
    path = registry_path(hardware, registry_dir)
    if not path.exists():
        return {}
    data = json.loads(path.read_text())
    return data.get("members", {})


def lookup(model: str, hardware: str,
           registry_dir: str | Path | None = None) -> dict | None:
    return load_rows(hardware, registry_dir).get(model)


def promote(journal: dict,
            registry_dir: str | Path | None = None,
            hardware: str | None = None) -> tuple[Path, dict]:
    """Write a finished journal's best config as the member's registry
    row (merging into the hardware file, tmp→rename committed).
    Returns (path, row)."""
    from tpu_hc_bench.tune.search import commit_json

    best = journal.get("best")
    if not best:
        raise ValueError(
            "journal has no successful measurement to promote "
            f"(status {journal.get('status')!r})")
    hardware = hardware or journal["hardware"]
    model = journal["model"]
    if journal.get("workload", "train") == "serve":
        # serving rows are lane-keyed: resolve_auto's serve lookup
        # reads `<model>@serve`, never the training key
        model = f"{model}@serve"
    rec = best.get("record") or {}
    row = {
        "overrides": dict(best["overrides"]),
        "base": dict(best.get("base") or {}),
        "score": best["score"],
        "images_per_sec_per_chip": rec.get("per_chip"),
        "goodput": rec.get("goodput"),
        "mfu_pct": rec.get("mfu_pct"),
        # the best RECORD's own measured length (a candidate promoted
        # off a shallower rung must not claim the final rung's steps)
        "measured_batches": rec.get(
            "measured_batches",
            journal["rungs"][-1]["batches"]
            if journal.get("rungs") else None),
        "search_status": journal.get("status"),
        "spent_s": journal.get("spent_s"),
    }
    path = registry_path(hardware, registry_dir)
    path.parent.mkdir(parents=True, exist_ok=True)
    if path.exists():
        data = json.loads(path.read_text())
    else:
        data = {"hardware": hardware, "members": {}}
    data["members"][model] = row
    commit_json(str(path), data)
    return path, row


def resolve_auto(cfg) -> str:
    """The ``--config=auto`` hook ``BenchmarkConfig.resolve`` calls.

    Mutates ``cfg`` in place: applies the registry row's lever
    overrides to every field the operator did not pin (an explicit
    user flag wins over the registry — the operator's
    ``--batch_size=64 --config=auto`` measures the tuned config AT that
    batch), stamps ``config_source`` (``auto`` | ``baseline``) and
    ``tuned_config``, and returns the translation note for the banner.

    "Pinned" means: named in ``cfg.explicit_flags`` when the config
    came through ``parse_flags`` (which records what the operator
    actually typed, so an explicit flag set to its default value still
    pins), else any field whose value differs from the dataclass
    default (the programmatic-construction fallback).
    """
    from tpu_hc_bench.flags import BenchmarkConfig

    hw = hardware_key()
    # the serving lane's rows are keyed `<model>@serve` — one member
    # can hold a tuned row per lane, and a training lookup can never
    # apply serving knobs (or vice versa)
    member = (f"{cfg.model}@serve"
              if getattr(cfg, "workload", "train") == "serve"
              else cfg.model)
    row = lookup(member, hw)
    if row is None:
        cfg.config_source = "baseline"
        have = sorted(load_rows(hw))
        return (f"auto->BASELINE defaults: no tuned row for "
                f"{member!r} at hardware {hw!r} "
                f"({registry_path(hw)}"
                + (f" has {', '.join(have)}" if have
                   else " does not exist")
                + ") — run `python -m tpu_hc_bench.tune search "
                  f"--model {cfg.model}`")
    defaults = {f.name: f.default
                for f in dataclasses.fields(BenchmarkConfig)}
    explicit = getattr(cfg, "explicit_flags", None)

    def pinned(k: str) -> bool:
        if explicit is not None:
            return k in explicit
        return getattr(cfg, k) != defaults.get(k)

    from tpu_hc_bench.tune.space import LEVERS, SERVE_LEVERS

    lane_levers = (SERVE_LEVERS
                   if getattr(cfg, "workload", "train") == "serve"
                   else LEVERS)
    applied, kept = [], []
    for k, v in {**row.get("base", {}), **row["overrides"]}.items():
        if not hasattr(cfg, k):
            # a stale row (flag renamed since the search) must not
            # crash every run; the tuned-config-staleness lint is the
            # loud gate for this
            kept.append(f"{k} (unknown flag)")
            continue
        if k in (LEVERS + SERVE_LEVERS) and k not in lane_levers:
            # a lane-crossed row (e.g. a hand-edited @serve row
            # spelling a training lever) — applying it would smuggle
            # the other lane's knob past resolve()'s validity matrix
            kept.append(f"{k} (not a {cfg.workload}-lane lever)")
            continue
        if not pinned(k):
            setattr(cfg, k, v)
            applied.append(f"{k}={v}")
        else:
            kept.append(f"{k}={getattr(cfg, k)} (explicit flag wins)")
    cfg.config_source = "auto"
    cfg.tuned_config = {"hardware": hw, "model": member, **row}
    note = (f"auto->tuned row {member}@{hw} "
            f"(score {row.get('score')}): "
            + (", ".join(applied) if applied else "no field changed"))
    if kept:
        note += "; kept: " + ", ".join(kept)
    return note

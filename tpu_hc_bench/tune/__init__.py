"""Budgeted autotuner over the model zoo — the closed performance loop.

Every headline number in BASELINE.md came from manual rounds of sweeping
the same handful of levers (per-chip batch, gradient accumulation,
``accum_dtype``, remat/scan, fusion threshold, gradient arm).  The
ingredients for automating that existed as separate modules — the search
driver (``scripts/sweep_zoo.py``), the objective (``obs`` goodput/MFU),
the pruner (``analysis`` lints), and cheap candidate evaluation (the
persistent compile cache) — but nothing connected them.  This package
is the connection:

- :mod:`tpu_hc_bench.tune.space` — the tunable levers per zoo member
  (batch as a power-of-two ladder, accum 1..64, accumulator dtype,
  remat/scan, fusion threshold, psum/zero1 arm) with per-member
  validity rules, plus the seeded best-known configs that used to live
  in ``sweep_zoo.py``.
- :mod:`tpu_hc_bench.tune.prune` — the static pruner: flag-time
  ``resolve()`` rejections, per-member ``analysis`` lint classes, and a
  small HBM model seeded from the best-known configs all skip
  candidates *before* paying for a run.
- :mod:`tpu_hc_bench.tune.runner` — the ONE subprocess runner (timeout,
  0/1/70/75 exit-code contract, JSON result parse) shared with
  ``scripts/sweep_zoo.py``.
- :mod:`tpu_hc_bench.tune.search` — budgeted successive halving with a
  resumable ``tune_state.json`` journal (tmp→rename commits, the
  ``utils/checkpoint.py`` idiom): measure every survivor briefly over
  one shared compile cache, keep the top half by goodput-adjusted
  throughput, re-measure longer.
- :mod:`tpu_hc_bench.tune.registry` — the tuned-config registry
  (``artifacts/tuned/<hardware_key>.json``; hardware key = chip
  generation + HBM + world size) that ``--config=auto`` consumes.

CLI::

    python -m tpu_hc_bench.tune search --model trivial --budget_s 600
    python -m tpu_hc_bench.tune show
    python -m tpu_hc_bench.tune promote --journal artifacts/tune/.../tune_state.json
"""

from tpu_hc_bench.tune.space import (  # noqa: F401
    Candidate,
    SEED_CONFIGS,
    member_space,
    seed_candidate,
)
from tpu_hc_bench.tune.registry import (  # noqa: F401
    hardware_key,
    hardware_key_from_child,
    lookup,
    promote,
)

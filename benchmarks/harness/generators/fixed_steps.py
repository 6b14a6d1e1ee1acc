"""Training traffic: one fixed batch (drawn from the seed by the
configuration's family, rows that all differ) fed every step, the
driver's synthetic protocol, for a fixed number of steps.

Parameters of a mix: ``batch_per_chip``, ``seq_len`` (or whatever shape
keys the family's ``train_batch`` reads), ``fabric``, and
``steps_per_s_nominal``: the window is a fixed amount of work,
``seconds`` long at that rate, because the driver takes its number of
steps before it starts.  The rate is read once on the chip when the mix
is written (any run's ``train.step_p50_ms`` gives it); a program that
gets faster ends the window sooner, and the metric is still all examples
over all of the window."""

from __future__ import annotations

import copy
import math


def steps(mix: dict, seconds: float) -> int:
    return max(8, int(math.ceil(mix["steps_per_s_nominal"] * seconds)))


def tiny(mix: dict) -> dict:
    mix = copy.deepcopy(mix)
    mix.update(batch_per_chip=2, seq_len=32, steps_per_s_nominal=3.0)
    return mix

"""The benchmark's one load generator.  A traffic mix is a data file of
parameters under ``benchmarks/traffic/``; its ``"generator"`` key names
the file under ``benchmarks/harness/generators/`` that turns (mix,
seconds, seed) into the window's requests or steps.  A new mix of a
generator that is there is a data file and nothing else; a new kind of
traffic (sessions over a shared prefix, say) is one new generator file,
and no file that is there changes.

What a generator file exports: a serve generator ``requests(mix,
seconds, seed, vocab_size)`` and ``warmup(mix, prefill_buckets,
max_in_flight, vocab_size, seed)``; a train generator ``steps(mix,
seconds)``; both ``tiny(mix)``, the mix at the CPU rehearsal's size.
"""

from __future__ import annotations

import importlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(HERE), "traffic")


def load_mix(name: str) -> dict:
    path = os.path.join(TRAFFIC_DIR, name + ".json")
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def generator_of(mix: dict):
    return importlib.import_module("harness.generators." + mix["generator"])


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """One independent stream per purpose; any non-negative whole seed
    (the driver's pass 2**31)."""
    return np.random.default_rng([int(seed), int(stream)])

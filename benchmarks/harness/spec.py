"""Reads ``BENCHMARK.json`` and finds, by name, the files that belong to
one configuration, one traffic mix and one metric.  Adding any of them is
adding a file and an entry; nothing here names a particular one."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                     f"{[w['name'] for w in bench['workloads']]}")


def config_of(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(ROOT, c["file"])) as f:
                cfg = json.load(f)
            cfg["name"] = name
            return cfg
    raise SystemExit(f"unknown config {name!r}")


def metrics_for(bench: dict, group: str, cell: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it under ``workloads``, and those with no such key."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def reader_of(metric: str):
    """``benchmarks/metrics/<metric>.py`` -> its ``read(ctx)``."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"metric {metric!r} has no reader at {path}")
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: list[dict], ctx: dict) -> dict:
    """name -> {"value", "unit"}; a reader that finds nothing to read
    returns None and its metric is left out of the line."""
    out = {}
    for m in metrics:
        value = reader_of(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out

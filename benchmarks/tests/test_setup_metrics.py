"""The five readers of the program's set-up account on a hand-made
account: what each reads, and nothing where the run has no ``setup_s``,
the account is empty, or the program has no account at all (a parent
that predates it)."""

import pytest

from harness import spec
from tpu_hc_bench.obs import timeline

ACCOUNT = {
    "phases": {"init": [1, 4.0], "pool": [1, 0.5], "aot": [3, 20.0],
               "inspect": [2, 1.5]},
    "programs": {
        "init": {"trace_s": 0.25, "lower_s": 0.25, "compile_s": 1.0,
                 "outcome": "uncached", "compiles": 9},
        "prefill@8": {"trace_s": 1.0, "lower_s": 2.0, "compile_s": 5.0,
                      "outcome": "compiled"},
        "decode@16": {"trace_s": 0.5, "lower_s": 1.5, "compile_s": 0.5,
                      "outcome": "loaded"},
    },
    "compiled": 1, "uncached": 9, "loaded": 1, "covered_s": 26.0,
}
WANT = {"setup.lower_s": 5.5, "setup.compile_s": 6.5,
        "setup.cache_misses": 1, "setup.init_s": 4.5,
        "setup.unaccounted_s": 30.0 - 26.0}


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_reads_the_account(name, monkeypatch):
    monkeypatch.setattr(timeline, "setup_account", lambda: ACCOUNT)
    read = spec.reader_of(name)
    assert read({"setup_s": 30.0}) == pytest.approx(WANT[name])
    assert read({}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_reads_nothing_without_an_account(name, monkeypatch):
    read = spec.reader_of(name)
    empty = dict(ACCOUNT, phases={}, programs={})
    monkeypatch.setattr(timeline, "setup_account", lambda: empty)
    assert read({"setup_s": 30.0}) is None
    monkeypatch.delattr(timeline, "setup_account")
    assert read({"setup_s": 30.0}) is None


def test_the_set_up_metrics_are_read_in_every_cell():
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    mine = [m for m in bench["per_layer"] if m["name"] in WANT]
    assert sorted(m["name"] for m in mine) == sorted(WANT)
    for m in mine:
        assert (m["layer"], m["moves"]) == ("set-up", "setup_s")
        assert m["workloads"] == cells

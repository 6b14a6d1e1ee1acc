"""Smoke test for the scaling-table harness (scripts/scaling_table.py).

One real 2-process cell through the literal CLI on a tiny member/protocol
— proves the harness end to end (hostfile + coordinator-port wiring,
rank spawn, throughput parse, table emit) in the default gate, so the
full-protocol table recorded in BASELINE.md stays reproducible.
"""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_scaling_harness_two_process_cell(tmp_path):
    out_dir = tmp_path / "scaling"
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "scaling_table.py"),
         "--worlds", "2", "--fabrics", "ici", "--models", "lenet",
         "--batch", "1", "--warmup", "1", "--batches", "2",
         "--out", str(out_dir), "--timeout", "500"],
        capture_output=True, text=True, timeout=540, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    rows = [json.loads(l) for l in
            (out_dir / "scaling.jsonl").read_text().splitlines()]
    assert len(rows) == 1
    row = rows[0]
    assert row["world"] == 2 and row["fabric"] == "ici"
    assert row["total_ex_per_sec"] > 0
    table = (out_dir / "scaling.md").read_text()
    assert "| lenet | ici | 2 |" in table
    # round 7: every cell leaves an obs.metrics artifact — rank 0 of the
    # REAL 2-process run wrote the merged record (worker-0-writes rule)
    cell = out_dir / "obs" / "w2_ici_lenet"
    assert row["metrics_dir"] == str(cell)
    manifest = json.loads((cell / "manifest.json").read_text())
    assert manifest["process_count"] == 2
    records = [json.loads(l) for l in
               (cell / "metrics.jsonl").read_text().splitlines()]
    assert records and records[-1]["kind"] == "summary"

"""Fleet orchestrator (tpu_hc_bench/fleet/, round 19).

Default lane is pure host-side work — the ``test_tune`` pattern: job
specs, pool admission (chips + the measured-anchors-first HBM model),
the scheduler's priority/gang/grow policy, deterministic churn, the
heartbeat-staleness classifier, and the WHOLE control loop driven in
virtual time over a stub backend (no subprocesses, no driver runs —
tier-1 sits against a tight 870s budget).  The load-bearing pins:

- admission is gang-or-nothing, and HBM refusals carry provenance
  (seeded vs measured — the tune/prune.hbm_model_for rule);
- a higher-priority arrival shrinks (not preempts) when shrinking
  suffices, never evicts equals, and never double-evicts while chips
  are already in flight back to the pool;
- a churn kill rides the preempt path: exit 75 → requeue → relaunch
  with ``--resume=elastic``; a completion regrows a shrunken job;
- every intentional stop (escalation SIGKILL included) requeues; a
  crash fails; a heartbeat-dead job is force-killed and requeued;
- the journal folds into the fleet goodput ledger exactly
  (chip-second arithmetic pinned), and the verdict artifact is
  regress-gateable (``fleet_goodput`` regresses DOWN).

Slow lane: the process-group kill regression (a child-spawning stub
job must not orphan its grandchild) and the real 3-member soak —
kill → elastic resume at a smaller world → regrow, params-fingerprint
control, zero orphaned processes, churn-vs-control goodput bound.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from tpu_hc_bench.fleet import churn as churn_mod
from tpu_hc_bench.fleet import report as report_mod
from tpu_hc_bench.fleet import scheduler as sched
from tpu_hc_bench.fleet.pool import DevicePool, JobSpec
from tpu_hc_bench.fleet.supervisor import (
    DONE,
    FAILED,
    FleetController,
    REFUSED,
)
from tpu_hc_bench.obs import fleet as obs_fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spec(name="a", model="trivial", batch=2, pref=4, wmin=2, prio=0,
         arrival=0.0, **kw):
    return JobSpec(name=name, model=model, batch_size=batch,
                   world_pref=pref, world_min=wmin, priority=prio,
                   arrival_s=arrival, **kw)


# ---------------------------------------------------------------------
# job spec + pool


def test_jobspec_roundtrip_and_validation():
    s = spec(flags=("--num_classes=10",))
    assert JobSpec.from_dict(s.to_dict()) == s
    with pytest.raises(ValueError, match="unknown field"):
        JobSpec.from_dict({**s.to_dict(), "chips": 4})
    with pytest.raises(ValueError, match="world_min"):
        spec(pref=2, wmin=4)
    with pytest.raises(ValueError, match="plain token"):
        spec(name="a/b")
    assert spec(batch=64, accum=8).microbatch == 8


def test_pool_gang_reserve_release():
    p = DevicePool(8)
    p.reserve("a", 4)
    p.reserve("b", 4)
    assert p.free == 0 and not p.can_reserve(1)
    with pytest.raises(ValueError, match="cannot reserve"):
        p.reserve("c", 2)
    with pytest.raises(ValueError, match="already holds"):
        p.reserve("a", 2)
    assert p.release("a") == 4
    assert p.free == 4
    assert p.release("a") == 0      # idempotent


def test_pool_hbm_admission_seeded():
    p = DevicePool(8)
    ok = p.hbm_admission(spec(batch=2))
    assert ok.fits and ok.source == "seeded"
    bad = p.hbm_admission(spec(name="big", batch=4096))
    assert not bad.fits and bad.source == "seeded"
    assert "seeded HBM anchor" in bad.reason
    # accumulation shrinks the microbatch back under the anchor
    assert p.hbm_admission(spec(name="acc", batch=4096, accum=8)).fits
    # a member outside the seed table admits with unknown provenance
    unk = p.hbm_admission(spec(name="u", model="moe_tiny", batch=4))
    assert unk.fits and unk.source == "unknown"


def test_pool_hbm_admission_measured_wins():
    # a measured OOM row at microbatch 64 caps the anchor below the
    # seeded guess — and the refusal says so
    rows = [{"model": "trivial", "overrides": {"batch_size": 64},
             "error": "hbm-oom"},
            {"model": "trivial", "overrides": {"batch_size": 16},
             "peak_hbm_bytes": 1 << 28, "hbm_bytes_limit": 1 << 30}]
    p = DevicePool(8, measured_rows=rows)
    v = p.hbm_admission(spec(batch=512))
    assert not v.fits and v.source == "measured"
    assert p.hbm_admission(spec(name="ok", batch=32)).fits
    # verdicts are cached per (model, batch, accum)
    assert p.hbm_admission(spec(batch=512)) is v
    # rows are per-model: trivial's measured anchor must not decide a
    # lenet admission (lenet falls back to its own seeded anchor)
    lv = p.hbm_admission(spec(name="l", model="lenet", batch=512))
    assert lv.fits and lv.source == "seeded"
    # a row with no model field carries no provenance: dropped
    anon = DevicePool(8, measured_rows=[
        {"overrides": {"batch_size": 2}, "error": "hbm-oom"}])
    assert anon.hbm_admission(spec(batch=2)).source == "seeded"


# ---------------------------------------------------------------------
# scheduler policy


def run_view(s, world, since=0.0, stopping=False):
    return sched.RunView(spec=s, world=world, since_s=since,
                         stopping=stopping)


def test_world_ladder_and_gang_admission():
    assert sched.world_ladder(spec()) == [4, 2]
    assert sched.world_ladder(spec(pref=6, wmin=4)) == [6, 4]
    assert sched.world_ladder(spec(), cap=2) == [2]
    # largest feasible world wins; below world_min nothing is granted
    d = sched.plan(0.0, 8, [], [sched.PendView(spec=spec())])
    assert d == [sched.Decision("admit", "a", 4, reason="fits")]
    d = sched.plan(0.0, 3, [], [sched.PendView(spec=spec())])
    assert d[0].world == 2          # gang shrinks to the ladder fit
    assert sched.plan(0.0, 1, [], [sched.PendView(spec=spec())]) == []


def test_plan_requeue_target_caps_the_ladder():
    d = sched.plan(0.0, 8, [],
                   [sched.PendView(spec=spec(), target_world=2)])
    assert d[0].world == 2


def test_plan_priority_shrinks_before_preempting():
    lo1, lo2 = spec(name="lo1"), spec(name="lo2")
    hi = spec(name="hi", prio=1)
    d = sched.plan(0.0, 0,
                   [run_view(lo1, 4), run_view(lo2, 4)],
                   [sched.PendView(spec=hi)])
    assert [x.kind for x in d] == ["shrink", "reserve"]
    assert d[0].world == 2
    # victims already at world_min: whole-gang preemption instead,
    # lowest priority first
    lo_min = spec(name="lomin", pref=2, wmin=2)
    d = sched.plan(0.0, 0, [run_view(lo_min, 2)],
                   [sched.PendView(spec=hi)])
    assert [x.kind for x in d] == ["preempt"]
    # equal priority NEVER evicts
    d = sched.plan(0.0, 0, [run_view(lo1, 4), run_view(lo2, 4)],
                   [sched.PendView(spec=spec(name="eq", prio=0))])
    assert d == []


def test_plan_shrink_reserves_beneficiary_cap():
    """The shrink pass budgets exactly world_min for the arrival — the
    RESERVE decision caps its later admission so it cannot take its
    full ladder top from the victim's freed chips (which would starve
    the victim the policy promised to keep running, smaller)."""
    v = spec(name="v")
    p = spec(name="p", prio=1)
    d = sched.plan(0.0, 0, [run_view(v, 4)], [sched.PendView(spec=p)])
    kinds = [(x.kind, x.job, x.world) for x in d]
    assert ("shrink", "v", 2) in kinds
    assert ("reserve", "p", 2) in kinds
    # next tick: v released its 4 chips and requeued at target 2; the
    # beneficiary admits at its BUDGETED 2, v re-admits beside it
    d2 = sched.plan(1.0, 4, [],
                    [sched.PendView(spec=p, target_world=2),
                     sched.PendView(spec=v, target_world=2)])
    assert [(x.kind, x.job, x.world) for x in d2] == [
        ("admit", "p", 2), ("admit", "v", 2)]


def test_plan_incoming_chips_stop_double_eviction():
    lo1, lo2 = spec(name="lo1"), spec(name="lo2")
    hi = spec(name="hi", prio=1)
    # lo1 is already stopping: its 4 chips are on the way back, so lo2
    # must NOT also be shrunk for the same pending job
    d = sched.plan(0.0, 0,
                   [run_view(lo1, 4, stopping=True), run_view(lo2, 4)],
                   [sched.PendView(spec=hi)])
    assert d == []


def test_plan_grows_one_settled_job_toward_pref():
    a, b = spec(name="a"), spec(name="b")
    running = [run_view(a, 2, since=0.0), run_view(b, 2, since=0.0)]
    # not settled yet
    assert sched.plan(1.0, 4, running, [], settle_s=5.0) == []
    d = sched.plan(10.0, 4, running, [], settle_s=5.0)
    assert len(d) == 1 and d[0].kind == "grow" and d[0].world == 4
    # pending work blocks growth (chips go to the queue first)
    assert sched.plan(10.0, 4, running,
                      [sched.PendView(spec=spec(name="p"))],
                      settle_s=5.0)[0].kind == "admit"
    # a stopping job never grows
    assert sched.plan(10.0, 4,
                      [run_view(a, 2, stopping=True)], [],
                      settle_s=5.0) == []


# ---------------------------------------------------------------------
# churn


def test_local_backend_refuses_an_accelerator(tmp_path, monkeypatch):
    """LocalBackend simulates a pool with --virtual_devices gangs; on a
    chip its concurrent children would contend for it (one process per
    chip), so it refuses — and `fleet run` says so in one line."""
    import jax

    from tpu_hc_bench.fleet import __main__ as fleet_cli
    from tpu_hc_bench.fleet.supervisor import LocalBackend

    LocalBackend()                      # the CPU test mesh: fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process per chip"):
        LocalBackend()
    with pytest.raises(SystemExit, match="fleet run: .*CPU-mesh simulator"):
        fleet_cli.main(["run", "--demo", "--chips", "2",
                        "--out", str(tmp_path / "out")])


def test_churn_parse_format_roundtrip():
    ev = churn_mod.parse_churn("kill@8:jobA, shrink@14:jobB,arrive@6:c")
    assert [e.op for e in ev] == ["arrive", "kill", "shrink"]  # sorted
    assert churn_mod.parse_churn(churn_mod.format_churn(ev)) == ev
    with pytest.raises(ValueError, match="malformed churn"):
        churn_mod.parse_churn("kill@8")
    with pytest.raises(ValueError, match="unknown churn op"):
        churn_mod.parse_churn("explode@8:jobA")


def test_seeded_churn_is_deterministic():
    a = churn_mod.seeded_churn(7, ["a", "b", "c"], 60.0,
                               kills=2, shrinks=1)
    assert a == churn_mod.seeded_churn(7, ["a", "b", "c"], 60.0,
                                       kills=2, shrinks=1)
    assert a != churn_mod.seeded_churn(8, ["a", "b", "c"], 60.0,
                                       kills=2, shrinks=1)
    assert sum(1 for e in a if e.op == "kill") == 2
    assert sum(1 for e in a if e.op == "shrink") == 1
    # events live in the soak's steady-state window
    assert all(0.2 * 60 <= e.t_s <= 0.8 * 60 for e in a)


# ---------------------------------------------------------------------
# heartbeat liveness (obs/fleet satellite)


def beat(t_unix, step=5, incarnation=0):
    return {"kind": "heartbeat", "t_unix": t_unix, "step": step,
            "incarnation": incarnation}


def test_classify_liveness_ages():
    now = 1000.0
    assert obs_fleet.classify_liveness(
        [beat(999.0)], now=now)["status"] == obs_fleet.ALIVE
    assert obs_fleet.classify_liveness(
        [beat(980.0)], now=now)["status"] == obs_fleet.STALE
    v = obs_fleet.classify_liveness([beat(900.0)], now=now)
    assert v["status"] == obs_fleet.DEAD and v["age_s"] == 100.0
    # the NEWEST beat decides, not file order
    assert obs_fleet.classify_liveness(
        [beat(900.0), beat(999.0)], now=now)["status"] == obs_fleet.ALIVE
    none = obs_fleet.classify_liveness([], now=now)
    assert none["status"] == obs_fleet.DEAD and none["age_s"] is None


def test_classify_liveness_incarnation_guard():
    now = 1000.0
    # a fresh-looking beat from an OLDER life never reads ALIVE
    v = obs_fleet.classify_liveness([beat(999.0, incarnation=0)],
                                    now=now, expect_incarnation=1)
    assert v["status"] == obs_fleet.STALE
    v = obs_fleet.classify_liveness([beat(900.0, incarnation=0)],
                                    now=now, expect_incarnation=1)
    assert v["status"] == obs_fleet.DEAD
    v = obs_fleet.classify_liveness([beat(999.0, incarnation=1)],
                                    now=now, expect_incarnation=1)
    assert v["status"] == obs_fleet.ALIVE


def test_watch_renders_liveness_column(rewind_run):
    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.obs import watch as watch_mod

    manifest, records = obs_metrics.read_run(rewind_run["dir"])
    lines = watch_mod.render(rewind_run["dir"], manifest, records)
    row = [ln for ln in lines if ln.strip().startswith("rank0:")]
    assert row
    assert any(tok in row[0] for tok in
               (obs_fleet.ALIVE, obs_fleet.STALE, obs_fleet.DEAD))


# ---------------------------------------------------------------------
# the control loop, in virtual time over a stub backend


class VirtualClock:
    def __init__(self):
        self.now = 0.0

    def monotonic(self):
        return self.now

    def wall(self):
        return 1_000_000.0 + self.now

    def sleep(self, dt):
        self.now += dt


class StubHandle:
    _next_pid = 900_000_000     # far past any real pid

    def __init__(self, clock, run_s, now, fail_code=None, hang=False,
                 ckdir=None):
        StubHandle._next_pid += 1
        self.pid = StubHandle._next_pid
        self.clock = clock
        self.end_at = None if hang else now + run_s
        self.exit_code = fail_code if fail_code is not None else 0
        self.preempt_at = None
        self.killed_at = None
        self.honors_sigterm = not hang
        self._ckdir = ckdir

    def poll(self):
        now = self.clock.monotonic()
        if self.killed_at is not None and now >= self.killed_at:
            return -9
        if self.preempt_at is not None and now >= self.preempt_at:
            return 75
        if self.end_at is not None and now >= self.end_at:
            return self.exit_code
        return None

    def send_preempt(self):
        if not self.honors_sigterm:
            return              # a hung job ignores SIGTERM
        if self.preempt_at is None:
            # emulate the emergency checkpoint commit so the requeue
            # sees a resumable job (the sentinel contract)
            if self._ckdir:
                os.makedirs(self._ckdir, exist_ok=True)
                open(os.path.join(self._ckdir,
                                  "step_00000002.complete"), "w").close()
            self.preempt_at = self.clock.monotonic() + 0.2

    def force_kill(self):
        self.killed_at = self.clock.monotonic()


class StubBackend:
    def __init__(self, clock, behaviors):
        self.clock = clock
        self.behaviors = behaviors
        self.launches = []

    def launch(self, s, world, resume, run_dir, incarnation):
        os.makedirs(run_dir, exist_ok=True)
        self.launches.append((s.name, world, resume, incarnation))
        b = dict(self.behaviors.get(s.name, {}))
        return StubHandle(self.clock, b.get("run_s", 10.0),
                          self.clock.monotonic(),
                          fail_code=b.get("fail_code"),
                          hang=b.get("hang", False),
                          ckdir=os.path.join(run_dir, "ck"))

    def harvest(self, s, run_dir, exit_code):
        return {"goodput": 0.8}


def stub_fleet(tmp_path, specs, behaviors, churn=(), chips=8, **ctl_kw):
    clock = VirtualClock()
    backend = StubBackend(clock, behaviors)
    ctl = FleetController(
        DevicePool(chips), specs, str(tmp_path / "fleet"),
        backend=backend, churn=list(churn),
        now_fn=clock.monotonic, wall_fn=clock.wall,
        sleep_fn=clock.sleep, tick_s=0.5,
        print_fn=lambda s: None,
        **{"settle_s": 1.0, "kill_grace_s": 5.0,
           "deadline_s": 300.0, **ctl_kw})
    return ctl, backend, clock


def soak_specs():
    return [
        spec(name="a", batches=10),
        spec(name="b", model="lenet", batches=10),
        spec(name="hi", prio=1, arrival=6.0, batches=10),
    ]


@pytest.fixture(scope="module")
def stub_soak(tmp_path_factory):
    """ONE virtual-time kill/shrink/regrow story shared by the journal,
    ledger, report, verdict, and CLI assertions below."""
    tmp = tmp_path_factory.mktemp("stub_soak")
    ctl, backend, clock = stub_fleet(
        tmp, soak_specs(),
        {"a": {"run_s": 20.0}, "b": {"run_s": 20.0},
         "hi": {"run_s": 5.0}},
        churn=churn_mod.parse_churn("kill@3:a"))
    result = ctl.run()
    return {"dir": ctl.out_dir, "result": result,
            "launches": backend.launches, "tmp": tmp}


def test_stub_soak_story(stub_soak):
    """The acceptance story in virtual time: churn kill → elastic
    requeue, priority arrival → shrink, completion → regrow, all jobs
    complete, zero orphans."""
    assert stub_soak["result"]["status"] == "done"
    assert stub_soak["result"]["jobs"] == {
        "a": "done", "b": "done", "hi": "done"}
    assert stub_soak["result"]["orphans"] == []
    launches = stub_soak["launches"]
    # a: first launch fresh, every relaunch elastic
    a_launches = [l for l in launches if l[0] == "a"]
    assert a_launches[0][2] == "auto"
    assert all(l[2] == "elastic" for l in a_launches[1:])
    assert len(a_launches) == 4     # initial, post-kill, shrink, grow
    assert [l[1] for l in a_launches] == [4, 4, 2, 4]
    # the higher-priority arrival got chips while a and b were running
    # — at the world the shrink pass budgeted (NOT its ladder top: the
    # freed chips beyond the budget go back to the shrink victim)
    assert ("hi", 2, "auto", 0) in launches
    events = report_mod.read_events(stub_soak["dir"])
    kinds = [e["kind"] for e in events]
    for expected in ("fleet_start", "arrive", "admit", "launch",
                     "preempt_sent", "exit", "requeue", "shrink",
                     "grow", "done", "fleet_end"):
        assert expected in kinds, expected
    # the churn kill is journaled as a preempt with its reason
    assert any(e["kind"] == "preempt_sent"
               and e.get("reason") == "churn-kill" for e in events)
    # accounting: the preempted incarnation is billed its WHOLE
    # running wall (launched ~0, killed at 3, exited ~3.5 — not just
    # the stop-grace seconds)
    first_exit = next(e for e in events
                      if e["kind"] == "exit" and e["job"] == "a")
    assert first_exit["code"] == 75
    assert first_exit["wall_s"] >= 3.0, first_exit


def test_stub_soak_ledger_arithmetic(stub_soak):
    ledger = report_mod.fleet_ledger(stub_soak["dir"])
    assert ledger is not None
    events = report_mod.read_events(stub_soak["dir"])
    exits = [e for e in events if e["kind"] == "exit"]
    productive = sum(0.8 * e["world"] * e["wall_s"] for e in exits)
    pool = 8 * ledger["wall_s"]
    assert ledger["fleet_goodput"] == pytest.approx(
        productive / pool, abs=1e-3)
    assert 0 < ledger["fleet_goodput"] < 1
    assert ledger["counts"]["kills"] == 1
    assert ledger["counts"]["grows"] >= 1
    assert ledger["counts"]["elastic_resumes"] >= 2
    assert ledger["jobs"]["a"]["incarnations"] == 4


def test_stub_soak_report_and_status_cli(stub_soak):
    import io

    from tpu_hc_bench.fleet.__main__ import main as fleet_main

    buf = io.StringIO()
    assert fleet_main(["report", stub_soak["dir"]], out=buf) == 0
    text = buf.getvalue()
    assert "goodput" in text and "worlds 4->4->2->4" in text
    buf = io.StringIO()
    assert fleet_main(["status", stub_soak["dir"]], out=buf) == 0
    text = buf.getvalue()
    assert "a" in text and "done" in text
    # unusable dirs are loud, not tracebacks
    buf = io.StringIO()
    assert fleet_main(["status", str(stub_soak["tmp"] / "nope")],
                      out=buf) == 2
    buf = io.StringIO()
    assert fleet_main(["report", str(stub_soak["tmp"] / "nope")],
                      out=buf) == 2


def test_stub_soak_verdict_artifact_and_regress(stub_soak, tmp_path):
    # a no-churn control of the same fleet
    ctl, _, _ = stub_fleet(
        tmp_path, soak_specs(),
        {"a": {"run_s": 20.0}, "b": {"run_s": 20.0},
         "hi": {"run_s": 5.0}})
    ctl.run()
    art = tmp_path / "verdict.json"
    rec = report_mod.write_verdict(stub_soak["dir"], str(art),
                                   control_dir=ctl.out_dir,
                                   bound_frac=0.5)
    on_disk = json.loads(art.read_text())
    assert on_disk == rec
    assert rec["metric"] == "fleet_goodput"
    assert rec["value"] == pytest.approx(
        report_mod.fleet_ledger(stub_soak["dir"])["fleet_goodput"])
    assert rec["extra"]["fleet_goodput_nochurn"] > 0
    assert rec["extra"]["within_bound"] is True
    assert rec["extra"]["kills"] == 1
    # the regress gate consumes it: identical rerun passes, a halved
    # fleet goodput flags as a DOWN regression
    from tpu_hc_bench.obs import regress

    ok = regress.regress_check(rec, [rec])
    assert not ok["regressions"]
    worse = json.loads(json.dumps(rec))
    worse["value"] = rec["value"] / 2
    worse["extra"]["fleet_goodput"] = rec["value"] / 2
    bad = regress.regress_check(worse, [rec])
    assert any(r["metric"] == "fleet goodput"
               for r in bad["regressions"])


def test_controller_liveness_dead_job_requeues_then_fails(tmp_path):
    """A job that hangs (ignores SIGTERM, never heartbeats) is declared
    DEAD after the grace windows, force-killed, requeued — and a
    serial crasher stops requeueing at the relaunch budget."""
    ctl, backend, clock = stub_fleet(
        tmp_path, [spec(name="h", batches=5)],
        {"h": {"hang": True}},
        startup_grace_s=2.0, dead_after_s=3.0, kill_grace_s=2.0)
    ctl.supervisor.max_relaunches = 2
    result = ctl.run()
    events = report_mod.read_events(ctl.out_dir)
    assert any(e["kind"] == "dead" for e in events)
    assert any(e["kind"] == "requeue" for e in events)
    assert result["jobs"]["h"] == "failed"
    assert any(e["kind"] == "failed"
               and e.get("exit_class") == "relaunch-budget"
               for e in events)


def test_controller_crash_fails_watchdog_class(tmp_path):
    ctl, _, _ = stub_fleet(
        tmp_path, [spec(name="w")], {"w": {"run_s": 2.0,
                                           "fail_code": 70}})
    result = ctl.run()
    assert result["jobs"]["w"] == "failed"
    events = report_mod.read_events(ctl.out_dir)
    assert any(e["kind"] == "failed"
               and e.get("exit_class") == "watchdog-timeout"
               for e in events)


def test_latest_heartbeats_tail_read(tmp_path):
    """The supervisor's per-tick liveness source reads only the file
    TAIL — newest record per host, O(1) in run length."""
    d = tmp_path / "m"
    d.mkdir()
    with open(d / "metrics.0.jsonl", "w") as f:
        for i in range(5000):       # well past one 8KB tail window
            f.write(json.dumps(beat(1000.0 + i, step=i,
                                    incarnation=1)) + "\n")
    latest = obs_fleet.latest_heartbeats(str(d))
    assert latest[0]["step"] == 4999
    v = obs_fleet.classify_liveness([latest[0]], now=6000.0,
                                    expect_incarnation=1)
    assert v["status"] == obs_fleet.ALIVE
    assert obs_fleet.latest_heartbeats(str(tmp_path / "nope")) == {}


def test_controller_crash_kills_live_jobs(tmp_path):
    """An exception inside the loop must not leave job processes
    running unsupervised: the finally force-kills every live handle."""
    ctl, backend, clock = stub_fleet(
        tmp_path, [spec(name="a", batches=5)], {"a": {"run_s": 50.0}})
    ticks = {"n": 0}
    orig_tick = ctl.tick

    def exploding_tick():
        ticks["n"] += 1
        if ticks["n"] == 3:
            raise OSError("disk full")
        orig_tick()

    ctl.tick = exploding_tick
    with pytest.raises(OSError, match="disk full"):
        ctl.run()
    st = ctl.supervisor.jobs["a"]
    # the launched stub was force-killed and reaped on the way out
    assert st.handle is None
    events = report_mod.read_events(ctl.out_dir)
    assert any(e["kind"] == "fleet_crash" for e in events)
    assert any(e["kind"] == "exit" for e in events)


def test_controller_refuses_before_spawning(tmp_path):
    """HBM-hopeless and oversized-gang jobs are refused at submission —
    the fleet never burns a gang discovering it."""
    ctl, backend, _ = stub_fleet(
        tmp_path,
        [spec(name="big", batch=4096),
         spec(name="wide", wmin=16, pref=16),
         spec(name="ok", batches=3)],
        {"ok": {"run_s": 2.0}})
    result = ctl.run()
    assert result["jobs"] == {"big": "refused", "wide": "refused",
                              "ok": "done"}
    assert [l[0] for l in backend.launches] == ["ok"]
    events = report_mod.read_events(ctl.out_dir)
    refusals = {e["job"]: e for e in events if e["kind"] == "refuse"}
    assert "seeded" == refusals["big"]["hbm_source"]
    assert "exceeds the pool" in refusals["wide"]["reason"]


# ---------------------------------------------------------------------
# runner hardening + exit-class home


def test_exit_classes_one_home():
    from tpu_hc_bench import resilience
    from tpu_hc_bench.tune import runner

    assert runner.EXIT_CLASSES is resilience.EXIT_CLASSES
    assert resilience.classify_exit(0) is None
    assert resilience.classify_exit(75) == "preempted"
    assert resilience.classify_exit(70) == "watchdog-timeout"
    assert resilience.classify_exit(1) == "zero-throughput"
    assert resilience.classify_exit(3) == "exit-3"
    assert resilience.classify_exit(-9) == "signal-9"


def test_build_cmd_positional_contract():
    from tpu_hc_bench.tune import runner

    cmd = runner.build_cmd("lenet", 32, ["--virtual_devices=4"],
                           warmup=2, batches=10, use_fp16=False)
    assert cmd[1:5] == ["-m", "tpu_hc_bench", "1", "0"]
    assert cmd[5:7] == ["32", "ici"]
    assert "--model=lenet" in cmd and "--virtual_devices=4" in cmd
    assert not any(f.startswith("--use_fp16") for f in cmd)


def test_kill_process_tree_safe_on_dead_proc():
    from tpu_hc_bench.tune import runner

    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait(timeout=30)
    runner.kill_process_tree(proc)          # must not raise
    runner.kill_process_tree(proc, sig=signal.SIGKILL)


@pytest.mark.slow
def test_kill_process_tree_reaps_grandchildren():
    """Satellite regression: a job that spawns its own children (feeder
    pools, service processes) dies as a GROUP — the grandchild must not
    survive the kill.  Stub job, no driver run."""
    from tpu_hc_bench.tune import runner

    child_src = (
        "import subprocess, sys, time\n"
        "p = subprocess.Popen([sys.executable, '-c',"
        " 'import time; print(\"gc-ready\", flush=True);"
        " time.sleep(120)'], stdout=sys.stdout)\n"
        "time.sleep(120)\n"
    )
    proc = runner.launch_one([sys.executable, "-c", child_src],
                             stdout=subprocess.PIPE)
    # wait for the grandchild to exist
    line = proc.stdout.readline()
    assert "gc-ready" in line
    pgid = os.getpgid(proc.pid)
    assert pgid == proc.pid         # its own session
    runner.kill_process_tree(proc, grace_s=2.0)
    proc.wait(timeout=30)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            pids = [int(os.path.basename(d))
                    for d in __import__("glob").glob("/proc/[0-9]*")]
            alive = [p for p in pids
                     if _pgid_of(p) == pgid]
        except OSError:
            alive = []
        if not alive:
            break
        time.sleep(0.2)
    assert not alive, f"orphaned pids in group {pgid}: {alive}"


def _pgid_of(pid):
    try:
        return os.getpgid(pid)
    except (ProcessLookupError, OSError):
        return None


# ---------------------------------------------------------------------
# fleet-blocking-wait lint


def test_fleet_blocking_wait_lint():
    from tpu_hc_bench.analysis.lints import FLEET_WAIT, lint_source_text

    src = (
        "def loop(jobs):\n"
        "    for j in jobs:\n"
        "        j.proc.wait()\n"              # flags
        "        j.thread.join()\n"            # flags
        "        j.proc.wait(5)\n"             # bounded
        "        j.thread.join(timeout=2.0)\n"  # bounded
        "        ','.join(j.names)\n"          # has an arg: not it
        "def once(j):\n"
        "    j.proc.wait()\n"                  # not in a loop
    )
    found = [f for f in lint_source_text(
        src, "tpu_hc_bench/fleet/supervisor.py")
        if f.lint == FLEET_WAIT]
    assert len(found) == 2
    assert all(f.severity == "error" for f in found)
    # scope: only the fleet package
    assert not [f for f in lint_source_text(
        src, "tpu_hc_bench/serve/engine.py") if f.lint == FLEET_WAIT]
    # suppression token works
    sup = src.replace("j.proc.wait()\n        j.thread.join()",
                      "j.proc.wait()  # thb:lint-ok[fleet-blocking-wait]"
                      "\n        j.thread.join()")
    found = [f for f in lint_source_text(
        sup, "tpu_hc_bench/fleet/supervisor.py")
        if f.lint == FLEET_WAIT]
    assert len(found) == 1


def test_repo_baseline_clean_with_fleet_lint():
    """The shipped fleet package itself holds the invariant the lint
    enforces (and the whole-repo lint gate stays green)."""
    from tpu_hc_bench.analysis import compare_to_baseline
    from tpu_hc_bench.analysis.lints import lint_repo_sources

    regressions = compare_to_baseline(lint_repo_sources())
    assert not regressions, [f.render() for f in regressions]


# ---------------------------------------------------------------------
# the real soak (slow lane): >=3 zoo members, deterministic churn,
# kill -> elastic resume at a smaller world, a regrow, the own-world
# fingerprint control, zero orphans, churn-vs-control goodput bound


SOAK_FLAGS = ("--num_classes=10", "--init_learning_rate=0.05")


def soak_real_specs():
    """Three distinct zoo members.  The heavyweight ``resnet20_cifar``
    keeps its gang busy across the kill window, so the killed lenet's
    elastic resume genuinely finds a smaller pool; the trivial member
    is the delayed priority arrival (enters via the churn schedule)."""
    return [
        spec(name="cifar-a", model="resnet20_cifar", batches=80,
             warmup=2, save_every=4, flags=SOAK_FLAGS),
        spec(name="lenet-b", model="lenet", batches=150, warmup=2,
             save_every=4, flags=SOAK_FLAGS),
        spec(name="triv-hi", prio=1, pref=2, wmin=2, arrival=9999.0,
             batches=30, warmup=2, save_every=4, flags=SOAK_FLAGS),
    ]


def _fingerprints(text_or_path, from_path=True):
    lines = (open(text_or_path).read() if from_path
             else text_or_path).splitlines()
    return [ln.split("params fingerprint:", 1)[1].strip()
            for ln in lines if "params fingerprint:" in ln]


def _resume_fingerprint(ck_src, model, world, resume, batches, tmp,
                        tag):
    """Relaunch a copy of a checkpoint dir at ``world`` and return the
    restore-time params fingerprint (the control arm of the soak's
    bitwise identity proof)."""
    import shutil

    ckdir = tmp / f"ck_{tag}"
    shutil.copytree(ck_src, ckdir)
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_hc_bench", "1", "0", "2", "ici",
         f"--model={model}", *SOAK_FLAGS,
         "--num_warmup_batches", "2", f"--num_batches={batches}",
         "--display_every", "4",
         f"--virtual_devices={world}",
         f"--resume={resume}", "--train_dir", str(ckdir)],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + \
        proc.stderr[-2000:]
    fps = _fingerprints(proc.stdout, from_path=False)
    assert fps, proc.stdout[-2000:]
    return fps[0]


@pytest.mark.slow
def test_fleet_soak_e2e(tmp_path):
    from tpu_hc_bench.fleet.supervisor import LocalBackend

    env = {"JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    # kill at 30 (the lenet is past warmup and checkpointing by then);
    # the priority arrival lands BEFORE the killed job's relaunch tick,
    # so the elastic resume finds a smaller pool
    events = churn_mod.parse_churn(
        "kill@30:lenet-b,arrive@30.5:triv-hi")
    out = str(tmp_path / "fleet")
    ctl = FleetController(
        DevicePool(8), soak_real_specs(), out,
        backend=LocalBackend(base_env=env),
        churn=events, settle_s=4.0, kill_grace_s=30.0,
        deadline_s=600.0, print_fn=lambda s: None)
    result = ctl.run()
    assert result["status"] == "done", result
    assert all(s == "done" for s in result["jobs"].values()), result

    # zero orphaned processes (the process-group contract, fleet-wide)
    assert result["orphans"] == []

    journal = report_mod.read_events(out)
    by_job: dict[str, list[dict]] = {}
    for e in journal:
        if e["kind"] == "launch":
            by_job.setdefault(e["job"], []).append(e)
    assert set(by_job) == {"cifar-a", "lenet-b", "triv-hi"}
    # the kill -> elastic resume at a SMALLER world (the arrival took
    # part of the pool between the kill and the relaunch)
    b_worlds = [e["world"] for e in by_job["lenet-b"]]
    assert len(b_worlds) >= 2, b_worlds
    assert min(b_worlds[1:]) < b_worlds[0], b_worlds
    assert any(e["resume"] == "elastic"
               for e in by_job["lenet-b"][1:])
    # ... and a regrow back up once capacity freed
    assert any(e["kind"] == "grow" for e in journal), \
        [e["kind"] for e in journal]
    assert max(b_worlds[1:]) > min(b_worlds[1:]), b_worlds

    # in-soak bitwise identity: every emergency save's fingerprint is
    # restored EXACTLY by the incarnation that follows it
    st = ctl.supervisor.jobs["lenet-b"]
    pairs = 0
    for k in range(st.incarnations - 1):
        log_k = os.path.join(st.run_dir, f"job-{k}.log")
        log_next = os.path.join(st.run_dir, f"job-{k + 1}.log")
        if not (os.path.exists(log_k) and os.path.exists(log_next)):
            continue
        if "emergency checkpoint saved" not in open(log_k).read():
            continue
        fp_save = _fingerprints(log_k)[-1]
        fp_restore = _fingerprints(log_next)[0]
        assert fp_restore == fp_save, (k, fp_save, fp_restore)
        pairs += 1

    # own-world control, EVERY surviving job: from its final
    # checkpoint, an elastic continuation at HALF the world starts
    # from params bitwise-identical to the own-world (--resume=must)
    # control — the kill-8/resume-4 identity, fleet-wide
    for s in soak_real_specs():
        ck = os.path.join(ctl.supervisor.jobs[s.name].run_dir, "ck")
        steps = sorted(int(f[len("step_"):-len(".complete")])
                       for f in os.listdir(ck)
                       if f.endswith(".complete"))
        assert steps, s.name
        topo = json.load(open(os.path.join(
            ck, f"step_{steps[-1]:08d}.topology.json")))
        own_world = int(topo["world"])
        batches = steps[-1] + 8
        fp_own = _resume_fingerprint(
            ck, s.model, own_world, "must", batches, tmp_path,
            f"{s.name}_own")
        fp_elastic = _resume_fingerprint(
            ck, s.model, max(1, own_world // 2), "elastic", batches,
            tmp_path, f"{s.name}_elastic")
        assert fp_elastic == fp_own, s.name

    # churn-vs-control goodput: the same fleet without the kill (the
    # arrival kept at the same time so only the spot-churn tax
    # differs), held to the stated bound
    out2 = str(tmp_path / "control_fleet")
    ctl2 = FleetController(
        DevicePool(8), soak_real_specs(), out2,
        backend=LocalBackend(base_env=env),
        churn=churn_mod.parse_churn("arrive@30.5:triv-hi"),
        settle_s=4.0, kill_grace_s=30.0, deadline_s=600.0,
        print_fn=lambda s: None)
    res2 = ctl2.run()
    assert res2["status"] == "done"
    churned = report_mod.fleet_ledger(out)["fleet_goodput"]
    control = report_mod.fleet_ledger(out2)["fleet_goodput"]
    art = tmp_path / "verdict.json"
    rec = report_mod.write_verdict(
        out, str(art), control_dir=out2, bound_frac=0.5,
        extra={"fingerprint_pairs": pairs})
    assert rec["extra"]["within_bound"], (churned, control)
    assert churned >= 0.5 * control, (churned, control)

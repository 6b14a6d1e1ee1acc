"""The static-analysis subsystem: HLO parser, lint passes, CI gate.

Three layers, matching the acceptance contract:

1. The definition-site HLO parser against a HAND-COUNTED fixture —
   operand references and ``-done`` async halves must be excluded, the
   exact miscounting modes ADVICE r5 flagged in the old whole-text
   regexes.
2. The AST lint passes against deliberately-planted defect fixtures
   (host sync in jit, recompile closure leak, donated-buffer reread)
   AND against the shipped zoo, where they must run clean.
3. The baseline gate plumbing: accepted keys suppress, new
   error/warning findings regress, ``info`` never gates.
4. The round-21 distributed-correctness passes: rank-taint fixtures
   that MUST flag (and clean twins that MUST NOT), dict/set-ordered
   collective loops, and the stream-schema contract checker against a
   synthetic mini-tree plus the real repo's allowlisted seams.
5. The registry/CLI plumbing: pass index completeness, inline
   suppression counted into the report JSON, the atomic ``baseline``
   subcommand, ``--changed-only`` file discovery, and the <30s
   wall-time budget on the repo source gate.

Everything here is in the default (not-slow) lane except the real
world=2 lowering, which pays a full XLA compile.
"""

import collections
import json
import os
import subprocess
import sys

import pytest

from tpu_hc_bench.analysis import contracts, dataflow, hlo, lints, registry, report

# ---------------------------------------------------------------------
# hand-counted fixture: 2 computations; entry has FIVE collective
# definition sites (1 async all-reduce pair = 1, 1 sync all-reduce,
# 1 all-gather, 1 reduce-scatter, 1 collective-permute) but many more
# collective *mentions* (operand references on the fusion/tuple lines,
# the -done line), plus a dot hidden inside a fusion with metadata.
FIXTURE_HLO = """\
HloModule fixture_module, entry_computation_layout={()->f32[2,2]{1,0}}

%add_comp (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%fused_computation (p0: f32[2,2]) -> f32[2,2] {
  %p0 = f32[2,2]{1,0} parameter(0)
  %dot.7 = f32[2,2]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/mlp/dot_general" source_file="model.py" source_line=42}
  ROOT %add.3 = f32[2,2]{1,0} add(%dot.7, %p0)
}

ENTRY %main () -> f32[2,2] {
  %c = f32[2,2]{1,0} constant({{1,2},{3,4}})
  %all-reduce-start.1 = f32[2,2]{1,0} all-reduce-start(%c), replica_groups={{0,1}}, to_apply=%add_comp
  %all-reduce-done.1 = f32[2,2]{1,0} all-reduce-done(%all-reduce-start.1)
  %all-reduce.5 = f32[2,2]{1,0} all-reduce(%all-reduce-done.1), replica_groups={{0,1}}, to_apply=%add_comp
  %all-gather.2 = f32[4,2]{1,0} all-gather(%all-reduce.5), dimensions={0}
  %reduce-scatter.3 = f32[2,2]{1,0} reduce-scatter(%all-gather.2), dimensions={0}, to_apply=%add_comp
  %collective-permute.4 = f32[2,2]{1,0} collective-permute(%reduce-scatter.3), source_target_pairs={{0,1},{1,0}}
  %fusion.1 = f32[2,2]{1,0} fusion(%collective-permute.4, %all-reduce.5), kind=kLoop, calls=%fused_computation
  ROOT %tuple.8 = f32[2,2]{1,0} add(%fusion.1, %all-reduce-done.1)
}
"""

# the hand count: definitions only, -start/-done folded
HAND_COUNT = {
    "all-reduce": 2,        # the async pair (1) + the sync one (1)
    "all-gather": 1,
    "reduce-scatter": 1,
    "collective-permute": 1,
}


def test_collective_counts_match_hand_count_exactly():
    assert hlo.collective_counts(FIXTURE_HLO) == HAND_COUNT


def test_operand_references_never_count():
    # %all-reduce.5 is defined once but *mentioned* on 2 later lines
    # (all-gather operand, fusion operand), and the async pair's names
    # recur as operand references too: 11 "all-reduce" substrings in
    # total — what a whole-text regex (the round-5 approach) counts
    assert FIXTURE_HLO.count("all-reduce") == 11
    assert hlo.collective_counts(FIXTURE_HLO)["all-reduce"] == 2


def test_async_done_unfolded_when_asked():
    raw = hlo.collective_counts(FIXTURE_HLO, fold_async=False)
    # unfolded, the -start and -done halves are distinct opcodes
    assert raw["all-reduce-start"] == 1
    assert raw["all-reduce-done"] == 1
    assert raw["all-reduce"] == 1


def test_parse_structure():
    m = hlo.parse_hlo(FIXTURE_HLO)
    assert m.name == "fixture_module"
    assert set(m.computations) == {"add_comp", "fused_computation", "main"}
    assert m.entry.name == "main"
    assert m.entry.instructions[-1].is_root
    dot = m.find("dot.7")
    assert dot is not None
    assert dot.op_name == "jit(step)/mlp/dot_general"
    assert dot.source == "model.py:42"


def test_fusion_attribution_through_metadata():
    m = hlo.parse_hlo(FIXTURE_HLO)
    attr = hlo.op_attribution(m, opcodes=("dot",))
    # the fusion's dot is attributed via its metadata op_name, not the
    # event-name substring (the fusion's own name says nothing)
    assert attr == {"fusion.1": ["jit(step)/mlp/dot_general"]}
    leaves = hlo.fusion_ops(m, "fusion.1")
    assert [i.opcode for i in leaves] == ["parameter", "dot", "add"]


# the TPU compiler's spelling: tiles and memory spaces inside the layout
# braces (``T(`` is no opcode), lines lifted from the gather arm's decode
# program before and after it stopped re-laying the KV pool out
TPU_HLO = """\
HloModule jit_decode_gather, is_scheduled=true

%fused_update (p0: f32[4,2,9,8,128], p1: f32[4,2,1,1,128], p2: s32[]) -> f32[4,2,9,8,128] {
  %p0 = f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)} parameter(0)
  %p1 = f32[4,2,1,1,128]{4,3,2,1,0:T(1,128)S(1)} parameter(1)
  %p2 = s32[]{:T(128)} parameter(2)
  ROOT %dynamic-update-slice.3 = f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)} dynamic-update-slice(%p0, %p1, %p2, %p2, %p2, %p2, %p2)
}

%body (arg: (s32[], f32[4,2,9,8,128])) -> (s32[], f32[4,2,9,8,128]) {
  %arg = (s32[]{:T(128)}, f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)}) parameter(0)
  %get-tuple-element.1 = f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)} get-tuple-element(%arg), index=1
  %n = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %dynamic_update_slice.14 = f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)} dynamic-update-slice(%get-tuple-element.1, %get-tuple-element.1, %n, %n, %n, %n, %n)
  ROOT %tuple.2 = (s32[]{:T(128)}, f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)}) tuple(%n, %dynamic_update_slice.14)
}

%fused_slice (q0: f32[4,2,9,8,128]) -> f32[2,9,8,128] {
  %q0 = f32[4,2,9,8,128]{4,1,3,2,0:T(8,128)} parameter(0)
  %slice.7 = f32[1,2,9,8,128]{4,1,3,2,0:T(8,128)} slice(%q0), slice={[1:2], [0:2], [0:9], [0:8], [0:128]}
  ROOT %bitcast.7 = f32[2,9,8,128]{3,2,1,0:T(8,128)S(1)} bitcast(%slice.7)
}

ENTRY %main (kv: f32[4,2,9,8,128], row: f32[4,2,1,1,128], i: s32[]) -> f32[4,2,9,8,128] {
  %kv = f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)} parameter(0), sharding={replicated}
  %row = f32[4,2,1,1,128]{4,3,2,1,0:T(1,128)S(1)} parameter(1)
  %i = s32[]{:T(128)} parameter(2)
  %copy.348 = f32[4,2,9,8,128]{4,1,3,2,0:T(8,128)} copy(%kv), sharding={replicated}
  %slice_bitcast_fusion = f32[2,9,8,128]{3,2,1,0:T(8,128)S(1)} fusion(%copy.348), kind=kLoop, calls=%fused_slice
  %fusion.51 = f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)} fusion(%kv, %row, %i), kind=kLoop, calls=%fused_update
  %tuple.9 = (s32[]{:T(128)}, f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)}) tuple(%i, %fusion.51)
  %while.4 = (s32[]{:T(128)}, f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)}) while(%tuple.9), condition=%body, body=%body
  ROOT %get-tuple-element.709 = f32[4,2,9,8,128]{4,3,2,1,0:T(8,128)} get-tuple-element(%while.4), index=1
}
"""


def test_tiled_layouts_do_not_hide_the_opcode():
    m = hlo.parse_hlo(TPU_HLO)
    copy = m.find("copy.348")
    assert (copy.opcode, copy.shape) == ("copy", "f32[4,2,9,8,128]")
    assert m.find("i").opcode == "parameter" and m.find("i").shape == "s32[]"
    wh = m.find("while.4")
    assert wh.opcode == "while"
    assert wh.shape == "(s32[], f32[4,2,9,8,128])"
    assert wh.called == ("body", "body")
    assert {i.opcode for i in m.entry.instructions} == {
        "parameter", "copy", "fusion", "tuple", "while",
        "get-tuple-element"}


def test_new_buffers_of_shape_passes_in_place_updates_only():
    """A copy or a slice of the held buffer is found; its parameters,
    tuple elements, the bare dynamic-update-slice of a loop body and a
    fusion rooted in one are the buffer itself and are not."""
    pool, layer = "f32[4,2,9,8,128]", "f32[2,9,8,128]"
    found = hlo.new_buffers_of_shape(TPU_HLO, (pool, layer))
    assert [i.name for i in found] == ["copy.348", "slice_bitcast_fusion"]
    clean = "\n".join(ln for ln in TPU_HLO.splitlines()
                      if "copy.348" not in ln)
    assert hlo.new_buffers_of_shape(clean, (pool, layer)) == []


# ---------------------------------------------------------------------
# lint fixtures: one deliberately-planted defect per family


HOST_SYNC_FIXTURE = """\
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def bad_step(x):
    s = x.sum()
    host = s.item()
    arr = np.asarray(x)
    jax.device_get(s)
    return x * host + arr.shape[0]

def good_host_code(x):
    return float(x.sum().item())
"""


def test_host_sync_in_jit_flagged():
    fs = lints.lint_source_text(HOST_SYNC_FIXTURE, "fixture.py")
    msgs = [f for f in fs if f.lint == lints.HOST_SYNC]
    assert len(msgs) == 3, [f.render() for f in fs]
    assert all(f.severity == "error" for f in msgs)
    lines = {int(f.location.rsplit(":", 1)[1]) for f in msgs}
    assert lines == {8, 9, 10}
    # the same .item() OUTSIDE a traced function is host code, not a bug
    assert not any("good_host_code" in f.message for f in fs)


def test_host_sync_suppression_comment():
    src = HOST_SYNC_FIXTURE.replace(
        "host = s.item()",
        "host = s.item()  # thb:lint-ok[host-sync-in-jit]")
    fs = lints.lint_source_text(src, "fixture.py")
    lines = {int(f.location.rsplit(":", 1)[1])
             for f in fs if f.lint == lints.HOST_SYNC}
    assert lines == {9, 10}


RECOMPILE_FIXTURE = """\
import jax

def train(n_steps, data):
    scale = 0
    def step(x):
        return x * scale
    jitted = jax.jit(step)
    for scale in range(n_steps):
        jitted(data)
"""


def test_recompile_closure_leak_flagged():
    fs = lints.lint_source_text(RECOMPILE_FIXTURE, "fixture.py")
    hits = [f for f in fs if f.lint == lints.RECOMPILE]
    assert len(hits) == 1
    assert hits[0].severity == "warning"
    assert "`scale`" in hits[0].message


SHAPE_BRANCH_FIXTURE = """\
import jax

@jax.jit
def f(x):
    if x.shape[0] > 128:
        return x[:128]
    return x
"""


def test_shape_vs_literal_branch_is_info_only():
    fs = lints.lint_source_text(SHAPE_BRANCH_FIXTURE, "fixture.py")
    hits = [f for f in fs if f.lint == lints.RECOMPILE]
    assert len(hits) == 1
    assert hits[0].severity == "info"
    # info findings never gate
    assert report.compare_to_baseline(hits, baseline=set()) == []


DONATION_FIXTURE = """\
import jax

def run(state, batch):
    step = jax.jit(do_step, donate_argnums=(0,))
    new_state = step(state, batch)
    loss = state.params  # read-after-donate: invalidated buffer
    return new_state, loss

def run_ok(state, batch):
    step = jax.jit(do_step, donate_argnums=(0,))
    state = step(state, batch)  # donate-and-rebind, the idiom
    return state.params
"""


def test_donation_reread_flagged_rebind_clean():
    fs = lints.lint_source_text(DONATION_FIXTURE, "fixture.py")
    hits = [f for f in fs if f.lint == lints.DONATION]
    assert len(hits) == 1
    assert "`state`" in hits[0].message
    assert int(hits[0].location.rsplit(":", 1)[1]) == 6


# ---------------------------------------------------------------------
# the shipped zoo must lint clean (3 representative members: a BN CNN,
# a transformer with the TP rule table, and the MoE member)


@pytest.mark.parametrize("name", ["resnet20_cifar", "bert_tiny", "moe_tiny"])
def test_zoo_member_lints_clean(name):
    findings = lints.lint_model(name)
    gating = [f for f in findings if f.severity in ("error", "warning")]
    assert gating == [], [f.render() for f in gating]


@pytest.fixture(scope="module")
def repo_findings():
    # ONE full repo-source scan shared by the gate test and the
    # contract-seam test below — repeating it mid-suite pays GC churn
    # over the loaded heap, not parse time
    return lints.lint_repo_sources()


def test_repo_sources_have_no_unbaselined_findings(repo_findings):
    regressions = report.compare_to_baseline(repo_findings)
    assert regressions == [], [f.render() for f in regressions]


# ---------------------------------------------------------------------
# baseline gate plumbing


def test_baseline_roundtrip_and_gate(tmp_path):
    f1 = report.Finding(lint="host-sync-in-jit", severity="error",
                        model="repo", location="pkg/mod.py:10", message="m")
    f2 = report.Finding(lint="sharding-consistency", severity="warning",
                        model="bert_tiny", location="param:qkv/kernel",
                        message="n")
    path = tmp_path / "baseline.json"
    report.save_baseline([f1], path)
    accepted = report.load_baseline(path)
    assert accepted == {f1.key}
    # accepted finding passes; novel finding regresses
    assert report.compare_to_baseline([f1], accepted) == []
    assert report.compare_to_baseline([f1, f2], accepted) == [f2]
    # line-number churn does not churn identity (key drops the line)
    moved = report.Finding(lint=f1.lint, severity=f1.severity,
                           model=f1.model, location="pkg/mod.py:99",
                           message=f1.message)
    assert report.compare_to_baseline([moved], accepted) == []


def test_non_file_locations_keep_distinct_keys():
    # only a NUMERIC (line) suffix is stripped from the key: two
    # sharding findings on different params of the same model must NOT
    # collapse to one baseline key (accepting one would mask the other)
    f_a = report.Finding(lint="sharding-consistency", severity="warning",
                         model="bert_tiny", location="param:layer_0/qkv",
                         message="m")
    f_b = report.Finding(lint="sharding-consistency", severity="warning",
                         model="bert_tiny", location="param:layer_5/out",
                         message="m")
    assert f_a.key != f_b.key
    assert report.compare_to_baseline([f_b], {f_a.key}) == [f_b]
    j = report.Finding(lint="host-sync-in-jit", severity="warning",
                       model="bert_tiny", location="jaxpr:pure_callback",
                       message="m")
    assert "pure_callback" in j.key


def test_save_baseline_merge_preserves_other_keys(tmp_path):
    # a partial (--model) --update-baseline run must only ADD keys
    f1 = report.Finding(lint="host-sync-in-jit", severity="error",
                        model="bert_tiny", location="a.py:1", message="m")
    f2 = report.Finding(lint="host-sync-in-jit", severity="error",
                        model="resnet50", location="b.py:2", message="m")
    path = tmp_path / "baseline.json"
    report.save_baseline([f1, f2], path)
    report.save_baseline([f1], path, merge=report.load_baseline(path))
    assert report.load_baseline(path) == {f1.key, f2.key}


def test_checked_in_baseline_is_loadable():
    accepted = report.load_baseline()
    assert isinstance(accepted, set)
    data = json.loads(report.BASELINE_PATH.read_text())
    assert sorted(accepted) == data["accepted"]


def test_findings_json_stable_shape():
    f = report.Finding(lint="host-sync-in-jit", severity="error",
                       model="repo", location="a.py:1", message="m")
    payload = json.loads(report.findings_to_json(
        [f], {"resnet20_cifar": {"all-reduce": 3}}))
    assert payload["findings"][0]["lint"] == "host-sync-in-jit"
    assert payload["collectives"]["resnet20_cifar"] == {"all-reduce": 3}


# ---------------------------------------------------------------------
# the real thing: the compiled world=2 step (one full XLA compile, so
# slow-lane; the counts themselves are pinned in BASELINE.md and
# re-emitted by scripts/exp_hlo_collectives_r05.py)


@pytest.mark.slow
def test_world2_lowering_counts_definition_sites(devices):
    text = hlo.lower_world_step_hlo("resnet20_cifar", batch=8, world=2)
    counts = hlo.collective_counts(text)
    # post-BN-bucketing resnet20: gradient+BN-stat fusion buckets only —
    # and definition-site counting must come in far below the raw
    # mention count the old regex reported (operand refs inflate it)
    assert set(counts) == {"all-reduce"}
    assert counts["all-reduce"] == 3
    assert text.count("all-reduce") > counts["all-reduce"]


def test_zero1_lowering_emits_reduce_scatter_all_gather(devices):
    """The zero1 arm's compiled world=2 step must shard the gradient
    path: reduce-scatter + all-gather present, all-reduce budget only
    for the loss pmean — the program property the arm exists for.
    Trivial member: cheap compile, no BN stats."""
    text = hlo.lower_world_step_hlo(
        "trivial", batch=2, world=2, variable_update="zero1",
        fusion_threshold_bytes=256, num_classes=10)
    counts = hlo.collective_counts(text)
    assert counts.get("reduce-scatter", 0) >= 1
    assert counts.get("all-gather", 0) >= 1
    assert counts.get("all-reduce", 0) <= 1     # the scalar loss pmean


def test_check_zero1_collectives_clean_and_loud():
    """The lint wrapper: clean on the healthy arm; doctored count sets
    produce collective-shape findings (the pure half, no compile)."""
    from tpu_hc_bench.analysis import lints

    assert lints.check_zero1_collectives(
        "trivial", world=2, fusion_threshold_bytes=256) == []
    # gradient path not sharded at all
    got = lints.zero1_shape_findings("m", {"all-reduce": 5})
    assert len(got) == 2 and all(f.lint == "collective-shape" for f in got)
    assert "not optimizer-sharded" in got[0].message
    # sharded, but gradient buckets ALSO riding a full all-reduce
    got = lints.zero1_shape_findings(
        "m", {"reduce-scatter": 4, "all-gather": 4, "all-reduce": 6})
    assert len(got) == 1 and "full all-reduce" in got[0].message
    # healthy: rs/ag pair + the loss pmean
    assert lints.zero1_shape_findings(
        "m", {"reduce-scatter": 2, "all-gather": 2, "all-reduce": 1}) == []


def test_overlap_off_pins_optimization_barrier(devices):
    """--overlap_grad_comm=off must compile the full-gradient-tree
    barrier into the program (comm strictly after the complete
    backward); on must not.  Asserted on the PRE-optimization text —
    the CPU backend deletes opt-barrier during optimization (no latency
    scheduling), the TPU pipeline schedules around it."""
    on = hlo.lower_world_step_hlo(
        "trivial", batch=2, world=2, fusion_threshold_bytes=256,
        num_classes=10, optimize=False)
    off = hlo.lower_world_step_hlo(
        "trivial", batch=2, world=2, fusion_threshold_bytes=256,
        num_classes=10, overlap_grad_comm="off", optimize=False)
    assert "optimization_barrier" not in on
    assert "optimization_barrier" in off
    # zero1 honors the same flag
    z_off = hlo.lower_world_step_hlo(
        "trivial", batch=2, world=2, variable_update="zero1",
        fusion_threshold_bytes=256, num_classes=10,
        overlap_grad_comm="off", optimize=False)
    assert "optimization_barrier" in z_off


# ---------------------------------------------------------------------
# round-21 dataflow passes: rank taint -> collectives.  Hazard fixtures
# that MUST flag; clean twins (the repo's own idioms) that MUST NOT.


RANK_DIVERGENT_FIXTURE = """\
import jax
from tpu_hc_bench.parallel import collectives

def commit_step(grads, step):
    if jax.process_index() == 0:
        total = collectives.psum(grads)      # only rank 0 enters
        return total
    return step

def gated_early_exit(state, rank):
    if rank != 0:
        return state
    return collectives.all_gather(state)

def laundered_through_assignment(x):
    me = jax.process_index()
    is_leader = me == 0
    if is_leader:
        collectives.broadcast_one_to_all(x)

def divergent_trip_count(queue, process_index):
    while process_index < len(queue):
        collectives.psum(queue[0])
        process_index += 1
"""


def test_rank_divergent_collectives_flagged():
    fs = lints.lint_source_text(RANK_DIVERGENT_FIXTURE, "fixture.py")
    hits = [f for f in fs if f.lint == dataflow.RANK_DIVERGENT]
    assert len(hits) == 4, [f.render() for f in fs]
    assert all(f.severity == "error" for f in hits)
    lines = {int(f.location.rsplit(":", 1)[1]) for f in hits}
    # the one-sided psum, the post-early-exit all_gather, the broadcast
    # behind a laundered taint, and the while-loop psum
    assert lines == {6, 13, 19, 23}
    assert any("early exit" in f.message for f in hits)
    assert any("while-loop" in f.message for f in hits)


RANK_CLEAN_FIXTURE = """\
import jax
from tpu_hc_bench.parallel import collectives
from tpu_hc_bench.utils import sync

def log_on_worker_zero(metrics, step):
    if jax.process_index() == 0:
        print("step", step, metrics)     # rank-gated HOST work: fine
    return step

def single_host_fast_path(flag):
    # the utils.sync idiom: process_count() is uniform across ranks,
    # so this branch does NOT diverge — every rank takes the same arm
    if jax.process_count() <= 1:
        return bool(flag)
    return sync.all_processes_any(flag)

def matched_arms(x, rank):
    if rank == 0:
        y = collectives.psum(x)
    else:
        y = collectives.psum(x * 0)      # both arms issue the psum
    return y

def raise_only_guard(cfg, rank):
    if rank >= cfg.world:
        raise ValueError("rank out of range")   # no collectives follow
"""


def test_rank_divergence_clean_twins_do_not_flag():
    fs = lints.lint_source_text(RANK_CLEAN_FIXTURE, "fixture.py")
    hits = [f for f in fs if f.lint == dataflow.RANK_DIVERGENT]
    assert hits == [], [f.render() for f in hits]


NONDET_ORDER_FIXTURE = """\
from tpu_hc_bench.parallel import collectives

def allreduce_by_dict_walk(grads):
    for name, g in grads.items():
        grads[name] = collectives.psum(g)

def barrier_per_set_member(x):
    for h in {"alpha", "beta"}:
        collectives.barrier(x)

def allreduce_sorted(grads):
    for name, g in sorted(grads.items()):
        grads[name] = collectives.psum(g)    # canonical order: fine

def fold_host_side(stats):
    out = 0.0
    for k, v in stats.items():
        out += v                             # no collective: fine
    return out
"""


def test_nondeterministic_collective_order():
    fs = lints.lint_source_text(NONDET_ORDER_FIXTURE, "fixture.py")
    hits = [f for f in fs if f.lint == dataflow.NONDET_ORDER]
    assert len(hits) == 2, [f.render() for f in fs]
    assert all(f.severity == "error" for f in hits)
    lines = {int(f.location.rsplit(":", 1)[1]) for f in hits}
    assert lines == {4, 8}       # the dict walk and the set literal
    assert any("insertion" in f.message for f in hits)
    assert any("hash order" in f.message for f in hits)


def test_dataflow_suppression_counted_into_report_json():
    src = RANK_DIVERGENT_FIXTURE.replace(
        "total = collectives.psum(grads)      # only rank 0 enters",
        "total = collectives.psum(grads)  "
        "# tpu-hc: disable=rank-divergent-collective")
    counters = collections.Counter()
    fs = lints.lint_source_text(src, "fixture.py", counters=counters)
    lines = {int(f.location.rsplit(":", 1)[1])
             for f in fs if f.lint == dataflow.RANK_DIVERGENT}
    assert 6 not in lines and len(lines) == 3
    assert counters[dataflow.RANK_DIVERGENT] == 1
    # the suppression hit survives into the report payload
    payload = json.loads(report.findings_to_json(
        [], suppressed=dict(counters)))
    assert payload["suppressed"] == {dataflow.RANK_DIVERGENT: 1}


# ---------------------------------------------------------------------
# the stream-schema contract checker: a synthetic mini-tree with a
# planted typo'd read, a phantom kind, and a dead stream field — then
# the real repo, where every contract finding must be an allowlisted
# (info) seam


def _mini_tree(tmp_path):
    obs = tmp_path / "tpu_hc_bench" / "obs"
    obs.mkdir(parents=True)
    (obs / "metrics.py").write_text(
        'def _of_kind(records, kind):\n'
        '    return [r for r in records if r.get("kind") == kind]\n'
        '\n'
        'def summarize(records):\n'
        '    steps = [r for r in records if r.get("kind") == "step"]\n'
        '    ghosts = _of_kind(records, "phantom")\n'
        '    return {\n'
        '        "good": sum(r.get("good_key", 0) for r in steps),\n'
        '        "typo": sum(r.get("typo_keyy", 0) for r in steps),\n'
        '        "ghost": len(ghosts),\n'
        '    }\n')
    pkg = tmp_path / "tpu_hc_bench"
    (pkg / "writer.py").write_text(
        'def emit(writer, x, now):\n'
        '    writer.event("step", good_key=x, dead_field=2 * x)\n'
        '    return {"kind": "hb", "dead_field": now}\n')
    return tmp_path


def test_contract_checker_flags_orphans(tmp_path):
    root = _mini_tree(tmp_path)
    no_allow = tmp_path / "missing_allowlist.json"
    fs = contracts.check_stream_contracts(root=root,
                                          allowlist_path=no_allow)
    warn = sorted(f.location for f in fs if f.severity == "warning")
    # the typo'd field read and the never-emitted kind gate; the
    # correctly-spelled good_key and the written kinds do not
    assert warn == ["obs/metrics.py::kind=phantom",
                    "obs/metrics.py::typo_keyy"], \
        [f.render() for f in fs]
    infos = [f for f in fs if f.severity == "info"]
    assert any(f.location == "stream-writers"
               and "dead_field" in f.message for f in infos)
    assert any(f.location == "stream-writers::kinds"
               and "hb" in f.message for f in infos)


def test_contract_allowlist_downgrades_to_visible_info(tmp_path):
    root = _mini_tree(tmp_path)
    allow = tmp_path / "allow.json"
    allow.write_text(json.dumps({
        "reads": {"typo_keyy": "test seam: external writer",
                  "phantom": "test seam: external kind"},
        "writes": {"dead_field": "forensics only", "hb": "external"},
    }))
    fs = contracts.check_stream_contracts(root=root, allowlist_path=allow)
    assert all(f.severity == "info" for f in fs), [f.render() for f in fs]
    # the allowlisted seam is REPORTED (visible), not silenced, and
    # carries its reason
    seam = [f for f in fs if f.location.endswith("::typo_keyy")]
    assert len(seam) == 1
    assert "test seam: external writer" in seam[0].message
    # info never gates
    assert report.compare_to_baseline(fs, baseline=set()) == []


def test_contract_extract_sides(tmp_path):
    root = _mini_tree(tmp_path)
    reads, kind_reads = contracts.extract_reads(root)
    assert {"good_key", "typo_keyy", "kind"} <= set(reads)
    assert {"step", "phantom"} <= set(kind_reads)
    broad, stream, kind_writes = contracts.extract_writes(root)
    assert {"good_key", "dead_field", "kind"} <= set(broad)
    assert set(stream) == {"good_key", "dead_field"}
    assert set(kind_writes) == {"step", "hb"}


def test_repo_contract_findings_all_allowlisted_info(repo_findings):
    fs = [f for f in repo_findings
          if f.lint in (contracts.ORPHAN_READ, contracts.ORPHAN_WRITE)]
    assert fs, "contract pass produced no findings — seams went silent"
    gating = [f for f in fs if f.severity in ("error", "warning")]
    assert gating == [], [f.render() for f in gating]
    # the r20 zero-component-normalizer seam round-trips through the
    # allowlist: visible as info, never silent
    assert any(f.location.endswith("::queue_wait") for f in fs), \
        [f.render() for f in fs]


# ---------------------------------------------------------------------
# registry + CLI plumbing


def test_pass_registry_index_complete():
    rows = registry.pass_index()
    names = {r[0] for r in rows}
    assert {"host-sync-in-jit", "recompile-hazard",
            dataflow.RANK_DIVERGENT, dataflow.NONDET_ORDER,
            contracts.ORPHAN_READ, contracts.ORPHAN_WRITE} <= names
    assert len(rows) >= 18
    for name, severity, scope, doc, _example in rows:
        assert severity in ("error", "warning", "info"), name
        assert scope in ("jit", "file", "repo", "model"), name
        assert doc, f"pass {name} registered without a doc line"
    assert registry.default_severity(dataflow.RANK_DIVERGENT) == "error"
    assert registry.default_severity("no-such-pass") == "warning"


def test_changed_python_files_discovery(tmp_path):
    root = __import__("pathlib").Path(lints.__file__).resolve().parents[2]
    files = registry.changed_python_files(root)
    if files is None:
        pytest.skip("git unavailable in this environment")
    assert all(str(p).endswith(".py") for p in files)
    # a non-repo directory fails OPEN (None -> caller uses full tree)
    assert registry.changed_python_files(tmp_path) is None


def test_baseline_subcommand_dry_run_then_update(tmp_path, monkeypatch):
    from tpu_hc_bench.analysis import __main__ as cli
    f1 = report.Finding(lint="host-sync-in-jit", severity="error",
                        model="repo", location="x.py:3", message="m")
    f2 = report.Finding(lint="dead-info", severity="info",
                        model="repo", location="y.py:1", message="m")
    monkeypatch.setattr(
        lints, "lint_repo_sources",
        lambda root=None, files=None, counters=None: [f1, f2])
    path = tmp_path / "baseline.json"
    # dry run against an empty baseline: diff -> exit 1, file untouched
    assert cli.main(["baseline", "--baseline", str(path)]) == 1
    assert not path.exists()
    # --update writes it (error/warning keys only; info never baselines)
    assert cli.main(["baseline", "--update", "--baseline", str(path)]) == 0
    assert report.load_baseline(path) == {f1.key}
    # now the dry run agrees, and no tmp litter remains from the
    # atomic tmp -> fsync -> rename write
    assert cli.main(["baseline", "--baseline", str(path)]) == 0
    assert [p.name for p in tmp_path.iterdir()] == ["baseline.json"]


def test_save_baseline_reports_key_diff(tmp_path):
    f1 = report.Finding(lint="a-lint", severity="error", model="repo",
                        location="a.py:1", message="m")
    f2 = report.Finding(lint="b-lint", severity="error", model="repo",
                        location="b.py:1", message="m")
    path = tmp_path / "b.json"
    added, removed = report.save_baseline([f1], path)
    assert (added, removed) == ([f1.key], [])
    added, removed = report.save_baseline([f2], path)
    assert (added, removed) == ([f2.key], [f1.key])


def test_repo_source_gate_under_wall_budget(tmp_path):
    # the ISSUE's default-lane budget: the full repo source gate (every
    # file pass over the tree + the repo-scope contract/staleness
    # passes) must stay interactive.  Measured on the REAL CLI in a
    # fresh subprocess — an in-process rerun here would time GC churn
    # over the loaded suite's heap, not the gate — using the gate's own
    # wall_s as threaded into the report JSON.  rc 0 doubles as the
    # "repo baseline is up to date" acceptance check.
    # wall_s on a contended runner times the neighbors, not the gate:
    # one retry absorbs transient load while a genuinely slow gate
    # still fails both measurements.
    out = tmp_path / "report.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_hc_bench.analysis", "baseline",
             "--json", str(out)],
            capture_output=True, text=True, timeout=120, env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "baseline up to date" in proc.stdout
        payload = json.loads(out.read_text())
        if payload["wall_s"] < 30.0:
            break
    assert payload["wall_s"] < 30.0, payload["wall_s"]
    assert "findings" in payload

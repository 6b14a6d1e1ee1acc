"""Definition-site HLO text parser.

Why a parser instead of a regex over the whole module text (the round-5
approach of ``scripts/exp_hlo_collectives_r05.py``): in HLO text every
*consumer* of an instruction repeats its name —

    %all-reduce.1 = f32[2,2] all-reduce(%dot.1), ...
    ROOT %fusion = f32[2,2] fusion(f32[2,2] %all-reduce.1), ...

so a bare substring match counts the all-reduce twice (once at its
definition, once per operand reference), and async pairs
(``all-reduce-start`` + ``all-reduce-done``) count a third time.  This
parser recognizes only *definition sites* — lines of the shape
``[ROOT] %name = <shape> opcode(operands), attrs`` — so each executed op
is seen exactly once, and ``-done``/``-update`` halves of async pairs
are folded into their ``-start``.

The parse is deliberately line-based and tolerant: XLA's text format is
stable at the granularity we consume (one instruction per line inside a
computation body; computations delimited by ``name (params) -> type {``
and ``}``), and anything unrecognized is simply skipped rather than an
error, so new attribute syntax can't break the counters.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = [
    "COLLECTIVE_OPCODES",
    "HloInstruction",
    "HloComputation",
    "HloModule",
    "parse_hlo",
    "collective_counts",
    "fusion_ops",
    "op_attribution",
    "new_buffers_of_shape",
    "shape_text",
]

# Cross-device collective opcodes (sync spellings; async spellings are
# these + "-start"/"-done").  collective-permute appears for ppermute
# pipelines, all-to-all for expert parallelism.
COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
})

# Async-pair suffix folding: the "-start" half carries the op, the
# "-done" (and copy/collective "-update") half is the wait.
_START_SUFFIX = "-start"
_DONE_SUFFIXES = ("-done", "-update")

# `[ROOT] %name = <rest>`; names may be %-less in some dump flavors.
_DEF_RE = re.compile(
    r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>\S.*)$")
# first identifier immediately followed by "(" in <rest> is the opcode:
# shape tokens (f32[2,8]{1,0}, (f32[2], u32[]) tuples, pred[], token[])
# are never an identifier directly followed by "(".
_OPCODE_RE = re.compile(r"\b(?P<op>[a-zA-Z][\w\-]*)\(")
# ... except inside a layout: the TPU compiler's text carries tiles and
# memory spaces there, ``f32[2,8]{1,0:T(8,128)S(1)}``, and ``T(`` is no
# opcode.  Layouts are cut out before the opcode and the shape are read.
_LAYOUT_RE = re.compile(r"(?<=\])\{[^{}]*\}")
_METADATA_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="(?P<n>[^"]*)"')
_SOURCE_RE = re.compile(
    r'source_file="(?P<f>[^"]*)"(?:\s+source_line=(?P<l>\d+))?')
# called-computation attributes: fusion calls=, reduce to_apply=, while
# body=/condition=, conditional branch_computations={...}
_CALLS_RE = re.compile(
    r"\b(?:calls|to_apply|body|condition)=%?(?P<c>[\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{(?P<cs>[^}]*)\}")
# `[ENTRY] %name (params...) -> type {`
_COMP_RE = re.compile(
    r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")


@dataclass
class HloInstruction:
    name: str                       # without the leading %
    opcode: str                     # e.g. "all-reduce-start", "fusion"
    is_root: bool = False
    op_name: str = ""               # metadata={op_name="..."} (jax path)
    source: str = ""                # metadata source_file:source_line
    called: tuple[str, ...] = ()    # computations this op calls
    text: str = ""                  # the raw definition line
    shape: str = ""                 # result shape without layouts, e.g.
                                    # "f32[2,8]" or "(f32[2], u32[])"

    @property
    def base_opcode(self) -> str:
        """Opcode with the async ``-start`` suffix stripped."""
        if self.opcode.endswith(_START_SUFFIX):
            return self.opcode[:-len(_START_SUFFIX)]
        return self.opcode

    @property
    def is_async_done(self) -> bool:
        return self.opcode.endswith(_DONE_SUFFIXES)


@dataclass
class HloComputation:
    name: str
    is_entry: bool = False
    instructions: list[HloInstruction] = field(default_factory=list)


@dataclass
class HloModule:
    name: str = ""
    computations: dict[str, HloComputation] = field(default_factory=dict)

    @property
    def entry(self) -> HloComputation:
        for c in self.computations.values():
            if c.is_entry:
                return c
        raise ValueError(f"module {self.name!r} has no ENTRY computation")

    def find(self, instr_name: str) -> HloInstruction | None:
        """Look up a definition by name across all computations."""
        want = instr_name.lstrip("%")
        for comp in self.computations.values():
            for ins in comp.instructions:
                if ins.name == want:
                    return ins
        return None


def _parse_instruction(line: str) -> HloInstruction | None:
    m = _DEF_RE.match(line)
    if not m:
        return None
    rest = m.group("rest")
    bare = _LAYOUT_RE.sub("", rest)
    om = _OPCODE_RE.search(bare)
    if not om:
        return None
    meta = _METADATA_OP_NAME_RE.search(rest)
    src = _SOURCE_RE.search(rest)
    called = tuple(_CALLS_RE.findall(rest))
    bm = _BRANCHES_RE.search(rest)
    if bm:
        called += tuple(
            c.strip().lstrip("%") for c in bm.group("cs").split(",")
            if c.strip())
    return HloInstruction(
        name=m.group("name"),
        opcode=om.group("op"),
        is_root=bool(m.group("root")),
        op_name=meta.group("n") if meta else "",
        source=(f"{src.group('f')}:{src.group('l') or '?'}" if src else ""),
        called=called,
        text=line.strip(),
        shape=bare[:om.start()].strip(),
    )


def parse_hlo(text: str) -> HloModule:
    """Parse HLO text into computations of definition-site instructions."""
    module = HloModule()
    current: HloComputation | None = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if stripped.startswith("HloModule"):
            parts = stripped.split(None, 2)
            module.name = parts[1].rstrip(",") if len(parts) > 1 else ""
            continue
        cm = _COMP_RE.match(stripped)
        if cm and "=" not in stripped.split("(", 1)[0]:
            current = HloComputation(
                name=cm.group("name"), is_entry=bool(cm.group("entry")))
            module.computations[current.name] = current
            continue
        if stripped == "}":
            current = None
            continue
        if current is None:
            continue
        ins = _parse_instruction(stripped)
        if ins is not None:
            current.instructions.append(ins)
    return module


def _iter_instructions(module: HloModule):
    for comp in module.computations.values():
        yield from comp.instructions


def collective_counts(module: HloModule | str,
                      fold_async: bool = True) -> dict[str, int]:
    """Count collective-op *definitions* per base opcode.

    Operand references never count (only definitions are parsed); with
    ``fold_async`` (default) an ``all-reduce-start``/``all-reduce-done``
    pair counts as ONE ``all-reduce`` (the ``-done`` half is skipped and
    the ``-start`` spelling is normalized to the sync name).
    """
    if isinstance(module, str):
        module = parse_hlo(module)
    counts: dict[str, int] = {}
    for ins in _iter_instructions(module):
        if fold_async and ins.is_async_done:
            continue
        op = ins.base_opcode if fold_async else ins.opcode
        # membership is tested on the async-suffix-free family so the
        # unfolded spellings ("all-reduce-start"/"-done") still count
        family = op
        for suf in (_START_SUFFIX, *_DONE_SUFFIXES):
            if family.endswith(suf):
                family = family[:-len(suf)]
                break
        if family in COLLECTIVE_OPCODES:
            counts[op] = counts.get(op, 0) + 1
    return counts


def fusion_ops(module: HloModule,
               instr: HloInstruction | str) -> list[HloInstruction]:
    """The leaf ops a (fusion) instruction actually executes.

    For a ``fusion`` op, the instructions of its fused computation
    (recursively through nested calls); for anything else, the
    instruction itself.  This is what makes trace/HLO attribution honest:
    a device event named ``fusion.123`` says nothing, but its fused
    computation's ``dot``s and their ``metadata op_name`` paths say
    exactly which model layer the time belongs to.
    """
    if isinstance(instr, str):
        found = module.find(instr)
        if found is None:
            return []
        instr = found
    if not instr.called:
        return [instr]
    out: list[HloInstruction] = []
    seen: set[str] = set()

    def walk(comp_name: str):
        if comp_name in seen:
            return
        seen.add(comp_name)
        comp = module.computations.get(comp_name)
        if comp is None:
            return
        for ins in comp.instructions:
            out.append(ins)
            for c in ins.called:
                walk(c)

    for c in instr.called:
        walk(c)
    return out


def shape_text(dims, dtype: str = "f32") -> str:
    """An array shape as ``HloInstruction.shape`` spells it."""
    return f"{dtype}[{','.join(str(d) for d in dims)}]"


# opcodes whose result names a buffer that already exists: views, tuple
# plumbing, and the update that writes into its own operand
_NO_NEW_BUFFER = frozenset({
    "parameter", "get-tuple-element", "bitcast", "dynamic-update-slice"})


def new_buffers_of_shape(module: HloModule | str,
                         shapes) -> list[HloInstruction]:
    """Instructions, in any computation, that produce a NEW array of one
    of ``shapes`` (layout-free spellings, ``"f32[24,16,641,16,128]"``).

    What a program that holds a large buffer in place must NOT contain:
    a ``copy`` (a re-layout), a ``slice``, a ``scatter`` or a fusion
    whose result is another array of the buffer's shape.  Parameters,
    tuple elements, bitcasts and ``dynamic-update-slice`` (alone or as a
    fusion's root) name the buffer they were given and do not count;
    tuple-shaped results (a ``while`` carrying the buffer) never match a
    single array's shape.
    """
    if isinstance(module, str):
        module = parse_hlo(module)
    shapes = frozenset(shapes)

    def in_place(ins: HloInstruction) -> bool:
        if ins.opcode != "fusion":
            return ins.opcode in _NO_NEW_BUFFER
        roots = [i for c in ins.called if c in module.computations
                 for i in module.computations[c].instructions if i.is_root]
        return bool(roots) and all(
            r.opcode == "dynamic-update-slice" for r in roots)

    return [ins for ins in _iter_instructions(module)
            if ins.shape in shapes and not in_place(ins)]


_FIRST_ARRAY = re.compile(r"[a-z0-9]+\[[0-9,]*\]")


def ops_by_scope(module: HloModule | str, scopes,
                 by_name: dict | None = None) -> dict[str, str]:
    """``{"<instruction>:<result shape>": scope}`` for every instruction,
    in any computation, whose ``op_name`` path holds one of ``scopes``
    (``jax.named_scope`` names) as a whole component; the first that
    appears on the path wins.  ``by_name`` gives the scope of
    instructions that carry none, by the prefix of their own name (a
    kernel the compiler names itself).  The key is how a device trace
    names an operation: the instruction and the shape of its (first)
    result."""
    if isinstance(module, str):
        module = parse_hlo(module)
    out = {}
    for ins in _iter_instructions(module):
        scope = next((c for c in ins.op_name.split("/") if c in scopes),
                     None) or next(
            (v for k, v in (by_name or {}).items()
             if ins.name.startswith(k)), None)
        shape = _FIRST_ARRAY.search(ins.shape)
        if scope and shape:
            out[f"{ins.name}:{shape.group(0)}"] = scope
    return out


def op_attribution(module: HloModule, opcodes: tuple[str, ...] = ("dot",),
                   entry_only: bool = True) -> dict[str, list[str]]:
    """Map each instruction -> ``metadata op_name`` paths of the
    matching leaf opcodes it executes (through fusions).

    E.g. ``op_attribution(m, ("dot",))["loop_fusion.12"]`` lists the jax
    op paths (``.../moe/expert_mm/dot_general``...) of every dot that
    fusion computes — the substring-free way to decide whether a traced
    fusion is expert matmul, attention, or router work.

    ``entry_only=False`` indexes every computation's instructions, not
    just the entry's: trace events name the ops executed inside while
    loops / conditionals (e.g. a ``lax.map``-chunked MoE dispatch), and
    those are defined in body computations the entry never lists.
    """
    instructions = (module.entry.instructions if entry_only
                    else list(_iter_instructions(module)))
    attribution: dict[str, list[str]] = {}
    for ins in instructions:
        leaves = fusion_ops(module, ins)
        names = [l.op_name for l in leaves
                 if l.base_opcode in opcodes and not l.is_async_done]
        if names:
            attribution[ins.name] = names
    return attribution


def lower_world_step_hlo(model_name: str, batch: int = 2,
                         world: int = 2, attention_impl: str = "dense",
                         moe_impl: str = "einsum", optimize: bool = True,
                         **config_overrides) -> str:
    """Optimized-HLO text of the zoo member's compiled world=N train step.

    ``optimize=False`` returns the pre-optimization (StableHLO) text of
    the lowered step instead — needed for program properties the CPU
    backend erases during optimization (e.g. the ``optimization_barrier``
    the ``--overlap_grad_comm=off`` arm pins across the gradient tree:
    the TPU pipeline schedules around it, the CPU pipeline deletes it),
    and cheaper when no compile is needed.

    A ``world``-virtual-device single-process data mesh compiles the
    identical program a ``world``-process run executes (same mesh shape,
    same partitioner input), so collective counts need no hardware — the
    round-5 insight of ``scripts/exp_hlo_collectives_r05.py``, now
    reusable for any member.  Must run under ``JAX_PLATFORMS=cpu`` with
    the device count set before backend init (the CLI does both).

    Extra ``config_overrides`` pass through to ``BenchmarkConfig``, so
    step variants are lowerable too (e.g. ``fusion_threshold_bytes=1``
    compiles the per-tensor-crossing step the fusion buckets replace).
    """
    import jax
    import jax.numpy as jnp

    from tpu_hc_bench import flags
    from tpu_hc_bench.data.synthetic import SyntheticImages, SyntheticTokens
    from tpu_hc_bench.models import create_model, get_model_spec
    from tpu_hc_bench.topology import build_mesh, compute_layout
    from tpu_hc_bench.train import step as step_mod

    cfg = flags.BenchmarkConfig(model=model_name, batch_size=batch,
                                attention_impl=attention_impl,
                                moe_impl=moe_impl,
                                **config_overrides).resolve()
    layout = compute_layout(num_hosts=1, workers_per_host=world,
                            chips_per_host=world)
    mesh = build_mesh(layout)
    spec = get_model_spec(model_name)
    kwargs = {}
    if spec.attention or spec.is_text:
        kwargs["attention_impl"] = attention_impl
    if spec.moe:
        kwargs["moe_impl"] = moe_impl
    model, spec = create_model(model_name, dtype=jnp.bfloat16, **kwargs)
    if spec.is_text:
        raw = SyntheticTokens(batch * world, spec.input_shape[0],
                              vocab_size=spec.vocab_size,
                              causal_lm=spec.causal_lm).batch()
    else:
        raw = SyntheticImages(batch * world, spec.input_shape,
                              num_classes=cfg.num_classes).batch()
    if cfg.variable_update == "zero1":
        # zero1 states carry stacked [world, k] optimizer leaves sharded
        # over the data axis — the layout the step's in_specs name
        state = step_mod.make_zero1_state(model, cfg, raw, world)
        state = step_mod.place_zero1_state(state, mesh)
    else:
        state = step_mod.make_train_state(model, cfg, raw)
        state = step_mod.replicate_state(state, mesh)
    dev_batch = step_mod.shard_batch(raw, mesh)
    step_fn = step_mod.build_train_step(mesh, cfg, spec)
    # the builder returns a wrapper around its jitted shard_map; jitting
    # the wrapper inlines it, giving a lowerable handle on the SAME program
    lowered = jax.jit(step_fn).lower(state, dev_batch, jax.random.PRNGKey(0))
    if not optimize:
        return lowered.as_text()
    return lowered.compile().as_text()

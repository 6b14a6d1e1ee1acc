"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside its limit.

Serve cells: once the window has closed, a sample of the requests it
finished (the longest, then those with the most rows of logits tapped
from the timed programs in a burst of steps the seed places) is run
through the reference once, prompt and served tokens together.
Compared are (a) the widest and the mean gap by which a served token's
logit lies below the best, in the reference computed as the serve arm
states (``reference_precision``): a token altered anywhere or a wrong
cache row opens it; and (b) ``logit_error_excess``: by what share the
RMS error of the program's own logits against the float32 reference
exceeds the error that the stated precision itself makes over the same
rows.  A program that computes as stated reads about 0; one that also
stores its tensors in a lower precision reads well above.

Train cells: the reference follows the compiled step's first three
steps from the seed; compared are each step's loss and, by the worst
part, the norms of the first gradient and of the three steps' change.

Nothing here names a model: the plain reference, the parts and the
optimizer come from the configuration's family and train arm
(``adapters.family_of``, ``adapters.optimizer_of``).
"""

from __future__ import annotations

import json

import numpy as np

from harness import adapters


def limits_of(cfg: dict) -> dict:
    """The limits live with the configuration (``"limits"`` in its file),
    each set from measured readings recorded in PERF.md."""
    return cfg["limits"]


CHECK_REQUESTS = 20     # finished requests held against the reference
LOGIT_ROWS = 32         # rows of tapped logits per request, padded
# where the stated precision IS the reference's own (the CPU rehearsal),
# the yardstick is this share of the logits' own spread instead of 0
ERROR_FLOOR = 1e-4


def sample_with_rows(records: list[dict], by_rid: dict, tapped: dict,
                     k: int = CHECK_REQUESTS) -> list[dict]:
    """The sample held against the reference: the longest finished
    request, then the finished requests with the most tapped rows of
    logits (the tap's burst is placed by the seed), ``k`` in all.  Each:
    prompt, served tokens, and ``rows``: ``(offset, logits [vocab])``
    where offset 0 is the prefill's row (it chooses ``served[0]``) and
    offset ``j + 1`` the decode step's that was fed ``served[j]``.

    A decode row is its request's by the first page of its table, which
    the request's prefill wrote last before that step; a row whose
    length and fed token do not fit that request's answer is left out."""
    ok = {r["id"]: r for r in records if r.get("status", "ok") == "ok"
          and len(r["generated"]) >= 1}
    if not ok:
        return []
    rows: dict = {rid: [] for rid in ok}
    by_prompt = {np.asarray(r["prompt"], np.int32).tobytes(): rid
                 for rid, r in by_rid.items()}
    prefills = [(by_prompt.get(np.asarray(prompt, np.int32).tobytes()),
                 page, logits)
                for prompt, page, logits in tapped.get("prefill", ())]
    for rid, _, logits in prefills:
        if rid in ok and not rows[rid]:
            rows[rid].append((0, logits))
    owner: dict = {}
    seen = 0
    for toks, pages, lengths, active, logits, before in tapped.get(
            "decode", ()):
        for rid, page, _ in prefills[seen:before]:
            owner[page] = rid
        seen = max(seen, before)
        for i in np.flatnonzero(active):
            rid = owner.get(int(pages[i]))
            if rid not in ok:
                continue
            served = ok[rid]["generated"]
            j = int(lengths[i]) - len(by_rid[rid]["prompt"])
            if 0 <= j < len(served) - 1 and served[j] == int(toks[i]):
                rows[rid].append((j + 1, logits[i]))
    size = lambda r: r["prompt_len"] + len(r["generated"])    # noqa: E731
    longest = max(ok.values(), key=lambda r: (size(r), -r["id"]))["id"]
    order = sorted((rid for rid in ok if rid != longest),
                   key=lambda rid: (-len(rows[rid]), rid))
    return [{"rid": rid,
             "prompt": np.asarray(by_rid[rid]["prompt"], np.int32),
             "served": np.asarray(ok[rid]["generated"], np.int32),
             "rows": rows[rid][:LOGIT_ROWS]}
            for rid in [longest] + order[:max(0, k - 1)]]


def build_compare_fn(cfg: dict, max_ctx: int, max_out: int, stated: str,
                     controls=()):
    """One request through the reference, once per precision.  Returns
    the jitted ``(params, tokens [1, max_ctx], start, served [max_out],
    row_at [LOGIT_ROWS], row_on [LOGIT_ROWS], program [LOGIT_ROWS,
    vocab]) -> dict`` with

    - ``gap`` [max_out]: the stated-precision reference's best logit
      minus its logit of the served token, at the ``max_out`` positions
      from ``start``;
    - ``sse_program``, ``sse_stated``, ``ss_logits``: over the rows
      switched on, the squared distance from the float32 reference's
      logits of the program's tapped logits and of the stated-precision
      reference's, and the float32 logits' own sum of squares (each row
      taken about its mean: a constant added to a row changes no
      probability);
    - ``sse_between``: the same between the program's logits and the
      stated-precision reference's (recorded, not compared);
    - for each lower precision in ``controls``: ``gap`` for the token
      that precision puts first, and its ``sse``."""
    import jax
    import jax.numpy as jnp

    ref = adapters.family_of(cfg).reference

    def slab(params, tokens, start, precision):
        h = ref.hidden_states(params, tokens, cfg, precision)
        rows = jax.lax.dynamic_slice_in_dim(h[0], start, max_out, axis=0)
        return ref.logits_of(params, rows, precision)

    def about_mean(x):
        return x - jnp.mean(x, axis=-1, keepdims=True)

    def compare(params, tokens, start, served, row_at, row_on, program):
        with jax.default_matmul_precision("highest"):
            exact = slab(params, tokens, start, "f32")
            said = exact if stated == "f32" else slab(params, tokens, start,
                                                      stated)
            lower = {c: slab(params, tokens, start, c) for c in controls}
        best = jnp.max(said, axis=-1)
        pick = lambda tok: jnp.take_along_axis(        # noqa: E731
            said, tok[:, None], axis=-1)[:, 0]
        at = about_mean(exact[row_at])
        sse = lambda x: jnp.sum(row_on[:, None] * jnp.square(   # noqa: E731
            about_mean(x) - at))
        out = {"gap": best - pick(served),
               "sse_program": sse(program), "sse_stated": sse(said[row_at]),
               "sse_between": jnp.sum(row_on[:, None] * jnp.square(
                   about_mean(program) - about_mean(said[row_at]))),
               "ss_logits": jnp.sum(row_on[:, None] * jnp.square(at))}
        for c, x in lower.items():
            out[c] = {"gap": best - pick(jnp.argmax(x, axis=-1)),
                      "sse": sse(x[row_at])}
        return out

    return jax.jit(compare)


def gap_stats(gaps: np.ndarray) -> dict:
    """Over the served positions: the widest gap, the mean gap, and the
    share of tokens that are not the reference's first."""
    return {"max": float(gaps.max()), "mean": float(gaps.mean()),
            "mismatch": float((gaps > 0).mean()), "n": int(gaps.size)}


def serve_stats(cfg: dict, seed: int, sample: list[dict], max_ctx: int,
                max_out: int, stated: str, controls=()) -> dict:
    """``{"program": stats, <control>: stats}`` over the sample:
    ``gap_stats`` of every served token against the reference in the
    ``stated`` precision, and ``excess``: by what share the logits' RMS
    error against the float32 reference exceeds the stated-precision
    reference's own error over the same rows (``rows`` of them)."""
    ref = adapters.family_of(cfg).reference
    params = ref.make_params(cfg, seed)
    fn = build_compare_fn(cfg, max_ctx, max_out, stated, tuple(controls))
    vocab = adapters.family_of(cfg).vocab_size(cfg)
    gaps: dict = {"program": []}
    sums = {"program": 0.0, "stated": 0.0, "between": 0.0, "logits": 0.0,
            "rows": 0}
    for s in sample:
        n = len(s["served"])
        # the last served token is never fed back
        feed = np.concatenate([s["prompt"], s["served"][:-1]])
        tokens = np.zeros((1, max_ctx), np.int32)
        tokens[0, :len(feed)] = feed
        start = len(s["prompt"]) - 1
        # the slab may not run off the end: shift it back and read the
        # served rows at their offset
        shift = max(0, start + max_out - max_ctx)
        served = np.zeros((max_out,), np.int32)
        served[shift:shift + n] = s["served"]
        row_at = np.zeros((LOGIT_ROWS,), np.int32)
        row_on = np.zeros((LOGIT_ROWS,), np.float32)
        program = np.zeros((LOGIT_ROWS, vocab), np.float32)
        for i, (offset, logits) in enumerate(s.get("rows", ())):
            row_at[i], row_on[i], program[i] = shift + offset, 1.0, logits
        got = fn(params, tokens, np.int32(start - shift), served, row_at,
                 row_on, program)
        gaps["program"].append(np.asarray(got["gap"])[shift:shift + n])
        sums["program"] += float(got["sse_program"])
        sums["stated"] += float(got["sse_stated"])
        sums["between"] += float(got["sse_between"])
        sums["logits"] += float(got["ss_logits"])
        sums["rows"] += int(row_on.sum())
        for c in controls:
            gaps.setdefault(c, []).append(
                np.asarray(got[c]["gap"])[shift:shift + n])
            sums[c] = sums.get(c, 0.0) + float(got[c]["sse"])
    # the yardstick: the stated precision's own error, or the floor
    yard = max(sums["stated"], ERROR_FLOOR ** 2 * sums["logits"])
    out = {}
    for side, g in gaps.items():
        st = gap_stats(np.concatenate(g))
        st["rows"] = sums["rows"]
        st["excess"] = (float(np.sqrt(sums[side] / yard)) - 1.0
                        if sums["rows"] else float("inf"))
        out[side] = st
    if sums["rows"]:
        # recorded beside the numbers compared: how large the stated
        # precision's own error is, and how far the program's logits lie
        # from the stated-precision reference's, in units of that error
        out["program"]["stated_error_share"] = float(
            np.sqrt(sums["stated"] / sums["logits"]))
        out["program"]["distance_from_stated"] = float(
            np.sqrt(sums["between"] / yard))
    return out


def serve_numbers_from(cfg: dict, st: dict | None) -> dict:
    """name -> (value, limit) from one side's stats."""
    lim = limits_of(cfg)["serve"]
    if not st:       # nothing finished: nothing to hold, and that fails
        st = {"max": float("inf"), "mean": float("inf"),
              "excess": float("inf"), "n": 0, "rows": 0}
    return {"served_logit_gap_max": (st["max"], lim["served_logit_gap_max"]),
            "served_logit_gap_mean": (st["mean"],
                                      lim["served_logit_gap_mean"]),
            "logit_error_excess": (st["excess"], lim["logit_error_excess"]),
            "served_tokens_compared": (float(st["n"]), None),
            "logit_rows_compared": (float(st["rows"]), None)}


def serve_numbers(cfg: dict, seed: int, sample: list[dict], max_ctx: int,
                  max_out: int) -> dict:
    """name -> (value, limit) for a serve cell."""
    if not sample:
        return serve_numbers_from(cfg, None)
    return serve_numbers_from(cfg, serve_stats(
        cfg, seed, sample, max_ctx, max_out,
        cfg["serve_arm"]["reference_precision"])["program"])


# ---------------------------------------------------------------------
# training


REFERENCE_ROWS = 4      # rows per block of the reference's backward


_REFERENCE_STEPS: dict = {}


def _reference_steps_fn(cfg: dict, precision: str, steps: int, nblk: int):
    """The jitted ``(weights, blocks of rows, lr) -> (losses, first
    gradient's part norms, change's part norms)``, built once per
    process and size."""
    import jax
    import jax.numpy as jnp

    key = (json.dumps(cfg, sort_keys=True, default=str), precision, steps,
           nblk)
    if key in _REFERENCE_STEPS:
        return _REFERENCE_STEPS[key]
    fam = adapters.family_of(cfg)
    ref = fam.reference
    opt = adapters.optimizer_of(cfg["train_arm"])

    def part_norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(x)))
                for k, x in fam.reference_parts(tree, cfg).items()}

    def gradient(p, blocks):
        def body(acc, blk):
            l, g = jax.value_and_grad(ref.loss_fn)(p, blk, cfg, precision)
            return (acc[0] + l / nblk,
                    jax.tree.map(lambda a, b: a + b / nblk, acc[1], g)), None

        zero = jax.tree.map(jnp.zeros_like, p)
        (loss, g), _ = jax.lax.scan(body, (jnp.zeros(()), zero), blocks)
        return loss, g

    @jax.jit
    def run(p0, blocks, lr):
        with jax.default_matmul_precision("highest"):
            p, state, losses, g1 = p0, opt.init(p0), [], None
            for t in range(1, steps + 1):
                loss, g = gradient(p, blocks)
                p, state = opt.update(p, state, g, float(t), lr)
                losses.append(loss)
                if t == 1:
                    g1 = part_norms(g)
            delta = part_norms(jax.tree.map(lambda a, b: a - b, p, p0))
        return jnp.stack(losses), g1, delta

    _REFERENCE_STEPS[key] = run
    return run


def reference_train(cfg: dict, seed: int, batch, lr: float,
                    precision: str | None = None, steps: int = 3,
                    fault: str | None = None) -> dict:
    """The reference's first ``steps`` optimizer steps from the seed's
    weights on ``batch``: each step's loss, per-part norm of the first
    gradient, per-part norm of the parameters' change after the steps.
    Backward in blocks of rows with one layer's activations kept at a
    time, so it fits beside nothing else on the chip.  ``precision``
    other than the train arm's ``reference_precision`` is the
    lower-precision control, ``fault`` a planted fault (``half_batch``:
    the second half of the rows left out, the mean taken over the rest):
    the reference put in the program's place."""
    import jax.numpy as jnp

    ref = adapters.family_of(cfg).reference
    precision = precision or cfg["train_arm"]["reference_precision"]
    batch = tuple(np.asarray(x) for x in batch)
    if fault == "half_batch":
        h = batch[0].shape[0] // 2
        batch = tuple(np.concatenate([x[:h], x[:h]]) for x in batch)
    elif fault is not None:
        raise ValueError(f"no fault {fault!r} planted in the reference")
    n = batch[0].shape[0]
    rows = min(REFERENCE_ROWS, n)
    nblk = n // rows
    blocks = tuple(jnp.asarray(x.reshape((nblk, rows) + x.shape[1:]))
                   for x in batch)
    run = _reference_steps_fn(cfg, precision, steps, nblk)
    losses, g1, delta = run(ref.make_params(cfg, seed), blocks,
                            jnp.float32(lr))
    flat = lambda d: {k: float(np.asarray(v)) for k, v in d.items()}  # noqa
    return {"losses": [float(x) for x in np.asarray(losses)],
            "grad_norms": flat(g1), "update_norms": flat(delta)}


def worst_leaf_gap(got: dict, want: dict, skip=()) -> tuple[float, tuple]:
    """Worst, over the leaves, of |got norm - want norm| over the larger
    of the reference's norm of that leaf and of its median leaf."""
    med = float(np.median(list(want.values())))
    worst, at = 0.0, None
    for k, w in want.items():
        if k in skip:
            continue
        gap = abs(got[k] - w) / max(w, med)
        if not gap <= worst:
            worst, at = gap, k
    return worst, at


def train_compare(program: dict, reference: dict) -> dict:
    """The numbers of a train cell, without limits: each step's loss gap
    (relative), worst-leaf gap of the first gradient's norm, worst-leaf
    gap of the three steps' change.  Leaves whose reference gradient is
    under a thousandth of the median leaf's move under Adam by round-off
    alone and are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    g_ref = reference["grad_norms"]
    med = float(np.median(list(g_ref.values())))
    dead = {k for k, v in g_ref.items() if v < 1e-3 * med}
    out["grad_norm_gap_worst_leaf"], at_g = worst_leaf_gap(
        program["grad_norms"], g_ref)
    out["update_norm_gap_worst_leaf"], at_u = worst_leaf_gap(
        program["update_norms"], reference["update_norms"], skip=dead)
    out["_where"] = {"grad": at_g, "update": at_u, "left_out": sorted(
        dead, key=str)}
    return out


def train_numbers_from(cfg: dict, got: dict, want: dict) -> dict:
    """name -> (value, limit): ``got`` (the program's readings, or a
    control's) held against the reference's ``want``."""
    lim = limits_of(cfg)["train"]
    cmp = train_compare(got, want)
    where = cmp.pop("_where")
    print(f"[check] worst leaves: {where}", flush=True)
    return {name: (value, lim[name]) for name, value in cmp.items()}


def verdict(numbers: dict) -> bool:
    """Every number with a limit is at or under it (NaN fails)."""
    return all(value <= limit for value, limit in numbers.values()
               if limit is not None)


def report_lines(numbers: dict) -> list[str]:
    return [f"compared {name} = {value!r}"
            + (f" (limit {limit!r})" if limit is not None else " (count)")
            for name, (value, limit) in numbers.items()]


def as_json(numbers: dict) -> dict:
    return {name: {"value": _num(value), "limit": limit}
            for name, (value, limit) in numbers.items()}


def _num(v: float):
    return v if np.isfinite(v) else json.dumps(v)

"""Mixture-of-Experts FFN + expert parallelism (GShard/Switch style).

Beyond-reference capability (the reference is DP-only, SURVEY.md §2c —
expert parallelism listed "absent"): sparse MoE layers for the decoder
family, designed TPU-first.

The dispatch is the classic GShard einsum formulation: per-group (= per
batch row) top-k routing builds dense ``dispatch``/``combine`` tensors of
shape ``[B, S, E, C]`` (C = expert capacity), and all data movement is
einsum contractions — no gather/scatter, no dynamic shapes, every op lands
on the MXU.  Expert parallelism is pure GSPMD: the expert-major parameter
tensors ``wi [E, H, F]`` / ``wo [E, F, H]`` are sharded over the mesh
"model" axis (``train.step.tp_param_spec`` rules), tokens stay sharded
over "data", and XLA's SPMD partitioner inserts the expert all-to-alls for
the ``[E, ...]``-sharded einsums itself — the same GSPMD arm the tensor-
parallel path rides (``--expert_parallel`` ↦ mesh model axis).

Router details: router logits in float32 (softmax stability under bf16
params); top-k selection by iterative argmax masking; capacity overflow
tokens are dropped (their combine weight is zero, the residual connection
carries them through — standard Switch behavior); the Switch load-balance
auxiliary loss is sown into the ``"losses"`` collection and picked up by
``train.step._loss_and_updates``.

A second routing convention rides the same module (``score="sigmoid"``,
the DeepSeek-V3 lineage): independent sigmoid scores in float32, the
top-k chosen on ``score + router_bias`` (a per-expert selection bias that
never enters the weights), the chosen scores normalized to sum to 1,
SwiGLU experts (``gated``), and a shared expert every token passes
through (``shared_ffn``).  Softmax routing takes the same path (the
share path, ``MoEFFN._share``) once any of its fields is set: the top-k
of a softmax over all experts, their probabilities renormalised to sum
to 1, gated experts.  ``experts_held=(lo, hi)`` makes the layer one
chip's share of an expert-parallel deployment: it routes over all
``num_experts``, holds the tensors of experts ``lo..hi-1`` only, and
computes THEIR part of the result with the ragged (zero-drop) dispatch;
what the absent experts would add is left out (on one chip the layer
runs without its exchange, and nothing stands in for the absent chips).
At a few rows an expert (a decode step) the grouped matmuls read every
held expert's tensors anyway and their time follows which experts the
step happened to leave empty, so up to ``DENSE_ROWS`` rows the share is
computed as every held expert over every row, weighed by the row's gate
for it (0 where it was not picked): the same sum, zero-drop too, at a
cost no routing moves.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

# Switch-Transformer convention: aux = E * Σ_e f_e · p̄_e, weighted into the
# total loss at this coefficient (Fedus et al. use 1e-2).
AUX_LOSS_COEF = 0.01


# The sigmoid share: up to this many rows, once every expert expects a
# row (rows x top_k >= experts), every held expert multiplies every row
# (``MoEFFN._dense``).  2 FLOPs a row a weight hide under the weight's
# 2-byte read up to ~240 rows on a v5e (197 TFLOP/s over 819 GB/s).
DENSE_ROWS = 256


def topk_select(probs: jax.Array, top_k: int):
    """Shared top-k routing selection over the trailing expert axis.

    ``probs``: [..., E] router softmax.  Returns ``(masks, gates,
    choices, aux)``: per-k one-hot masks [..., E], per-k gate weights
    [...] normalized to sum to 1 per token, per-k argmax indices [...],
    and the Switch load-balance aux (E * Σ_e f_e · p̄_e from the k=0
    assignment, token means over all leading axes).  Both dispatch impls
    (einsum capacity routing, ragged grouped matmuls) derive from this
    one selection so they cannot diverge.
    """
    e = probs.shape[-1]
    masks, gates, choices = [], [], []
    p = probs
    for _ in range(top_k):
        idx = jnp.argmax(p, axis=-1)
        mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)
        choices.append(idx)
        gates.append((p * mask).sum(-1))
        masks.append(mask)
        p = p * (1.0 - mask)
    token_axes = tuple(range(probs.ndim - 1))
    aux = e * jnp.sum(masks[0].mean(token_axes) * probs.mean(token_axes))
    # normalize the selected gates to sum to 1 per token (top-2 convention)
    denom = jnp.maximum(sum(gates), 1e-9)
    gates = [g / denom for g in gates]
    return masks, gates, choices, aux


def sigmoid_topk(scores: jax.Array, bias: jax.Array, top_k: int,
                 normalize: bool = True, scale: float = 1.0):
    """Sigmoid-score routing: ``scores`` [..., E] float32 in (0, 1);
    the ``top_k`` largest of ``scores + bias`` are chosen, and a chosen
    expert's weight is its own score (the bias only moves the choice),
    over the chosen scores' sum when ``normalize``.  Returns ``(choices
    [..., k] int32, gates [..., k] float32)``.  Softmax routing with
    top-k renormalisation is the same selection over softmax scores
    with no bias."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20)
    return idx.astype(jnp.int32), gates * scale


def top_k_routing(probs: jax.Array, top_k: int, capacity: int):
    """Build dispatch/combine tensors from router probabilities.

    ``probs``: [B, S, E] float32 router softmax.  Returns
    ``(dispatch [B,S,E,C] bool-ish float, combine [B,S,E,C] float32,
    aux_loss scalar)``.  Routing is per-group (group = batch row): each
    expert accepts at most ``capacity`` tokens *per group*, assigned in
    sequence order with earlier-k choices taking priority (GShard's
    position-in-expert cumsum).
    """
    b, s, e = probs.shape
    masks, gates, _, aux_loss = topk_select(probs, top_k)

    dispatch = jnp.zeros((b, s, e, capacity), probs.dtype)
    combine = jnp.zeros((b, s, e, capacity), probs.dtype)
    offset = jnp.zeros((b, 1, e), probs.dtype)
    for mask, gate in zip(masks, gates):
        # position of each token within its expert's queue (per group)
        pos = jnp.cumsum(mask, axis=1) - mask + offset   # [B, S, E]
        offset = offset + mask.sum(axis=1, keepdims=True)
        mask = mask * (pos < capacity)                   # drop overflow
        pos_tok = (pos * mask).sum(-1).astype(jnp.int32)  # [B, S]
        slot = jax.nn.one_hot(pos_tok, capacity, dtype=probs.dtype)
        placed = mask[..., None] * slot[:, :, None, :]   # [B, S, E, C]
        dispatch = dispatch + placed
        combine = combine + gate[..., None, None] * placed
    return dispatch, combine, aux_loss


class MoEFFN(nn.Module):
    """Sparse MoE feed-forward block: drop-in for a transformer's dense FFN.

    Expert-major params (``wi [E, H, F]``, ``wo [E, F, H]``) so expert
    parallelism is a single leading-dim PartitionSpec.  Router in f32,
    activations follow ``dtype`` (bf16-safe).  Two dispatch impls:

    - ``impl="einsum"`` (default): GShard dense dispatch/combine tensors.
      Fully GSPMD-shardable — the expert-parallel path — but pays the
      O(B·S·E·C) dispatch einsums and drops capacity-overflow tokens.
    - ``impl="ragged"``: sort token-expert pairs by expert and run the
      experts as grouped matmuls (``jax.lax.ragged_dot``, the TPU's
      native MoE primitive).  No capacity concept (zero token drops), no
      dispatch matmuls, no padding waste; single-shard expert compute, so
      it is the fast path for DP runs (``--expert_parallel`` requires
      einsum).
    """

    hidden: int
    ffn: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25      # einsum path: slots per expert =
                                       # ceil(cf * k * S / E); lower = more
                                       # drops, less dispatch memory — the
                                       # long-context pressure valve
    dtype: Any = jnp.float32
    impl: str = "einsum"
    ragged_chunk: int = 8192           # ragged path: max token-pair rows
                                       # per grouped matmul; larger inputs
                                       # run as a lax.map over chunks so
                                       # Mosaic's scoped-VMEM tiling never
                                       # sees an oversized operand
    ragged_f_chunk: int = 0            # ragged path: optionally tile the
                                       # FFN (F) dim of the [E,H,F]/[E,F,H]
                                       # weights (0 = full width).  Round 4
                                       # measured full width FASTER at every
                                       # reachable shape (the round-3 bs=16
                                       # failure was a whole-program compile
                                       # crash, not this kernel's VMEM —
                                       # see BASELINE.md MoE); the knob
                                       # stays for exploration
    score: str = "softmax"             # "sigmoid": see the module docstring
    gated: bool = False                # SwiGLU experts (``wg`` beside wi/wo)
    shared_ffn: int = 0                # width of the shared expert, 0 = none
    experts_held: tuple | None = None  # (lo, hi): this chip's share
    routed_scale: float = 1.0
    norm_topk: bool = True
    param_dtype: Any = jnp.float32     # of the expert and shared tensors

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        e = self.num_experts
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router score {self.score!r}")
        if (self.score == "sigmoid" or self.gated or self.shared_ffn
                or self.experts_held):
            return self._share(x)

        router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="router")
        probs = jax.nn.softmax(router(x.astype(jnp.float32)), axis=-1)

        init = nn.initializers.lecun_normal(batch_axis=(0,))
        wi = self.param("wi", init, (e, h, self.ffn))
        wo = self.param("wo", init, (e, self.ffn, h))

        if self.impl == "ragged":
            _, gate_list, choices, aux = topk_select(
                probs.reshape(b * s, e), self.top_k)
            y = self._ragged(x, jnp.stack(choices, 1),
                             jnp.stack(gate_list, 1), (wi, wo), e)
        elif self.impl == "einsum":
            y, aux = self._einsum(x, probs, wi, wo, s, e)
        else:
            raise ValueError(f"unknown moe impl {self.impl!r}")
        self.sow("losses", "moe_aux", aux)
        return y.astype(x.dtype)

    def _einsum(self, x, probs, wi, wo, s, e):
        # per-group (= per batch row) expert capacity, floor of 4 slots
        import math

        capacity = max(4, math.ceil(self.capacity_factor * self.top_k * s / e))
        dispatch, combine, aux = top_k_routing(probs, self.top_k, capacity)
        # the [B,S,E,C] dispatch/combine tensors dominate the layer's
        # activation memory (they are saved for backward); store them in
        # the compute dtype — dispatch is 0/1 exactly, combine gates lose
        # only bf16 rounding on weights the router learned in f32
        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)

        xin = jnp.einsum("bsec,bsh->ebch", dispatch, x.astype(self.dtype))
        act = nn.gelu(jnp.einsum("ebch,ehf->ebcf", xin,
                                 wi.astype(self.dtype)))
        out = jnp.einsum("ebcf,efh->ebch", act, wo.astype(self.dtype))
        y = jnp.einsum("bsec,ebch->bsh", combine, out)
        return y, aux

    def _share(self, x):
        """The share path: route over all ``num_experts``, compute the
        held experts' part (ragged, zero-drop) plus the shared expert.
        ``score="sigmoid"``: sigmoid scores, the top-k chosen on ``score
        + router_bias`` (``sigmoid_topk``); ``"softmax"``: a softmax over
        all experts, the top-k chosen on it and their probabilities
        renormalised to sum to 1 (``norm_topk``); the scores float32
        either way.  Sows ``stats/picks_held`` [b, s]: how many of each
        token's ``top_k`` picks landed on an expert held here, and
        ``stats/choices`` [b * s, k]: the picks."""
        if self.impl != "ragged":
            raise ValueError("the share path dispatches ragged "
                             f"(zero-drop) only, not {self.impl!r}")
        b, s, h = x.shape
        e = self.num_experts
        lo, hi = self.experts_held or (0, e)
        if not 0 <= lo < hi <= e:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"0..{e}")
        router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST,
                          name="router")
        logits = router(x.astype(jnp.float32))
        if self.score == "sigmoid":
            bias = self.param("router_bias", nn.initializers.zeros, (e,),
                              jnp.float32)
            choices, gates = sigmoid_topk(
                jax.nn.sigmoid(logits).reshape(b * s, e), bias, self.top_k,
                self.norm_topk, self.routed_scale)
        else:
            choices, gates = sigmoid_topk(
                jax.nn.softmax(logits, axis=-1).reshape(b * s, e), 0.0,
                self.top_k, self.norm_topk, self.routed_scale)
        self.sow("stats", "choices", choices)
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        held = hi - lo
        ws = [self.param(n, init, shape, self.param_dtype)
              for n, shape in (("wg", (held, h, self.ffn)),
                               ("wi", (held, h, self.ffn)),
                               ("wo", (held, self.ffn, h)))
              if n != "wg" or self.gated]
        local = choices - lo
        mine = (local >= 0) & (local < held)
        self.sow("stats", "picks_held",
                 mine.sum(-1).astype(jnp.int32).reshape(b, s))
        # absent experts' pairs sort behind every held group and are
        # counted in none: the grouped matmuls leave their rows out
        share = (self._dense if e <= b * s * self.top_k
                 and b * s <= DENSE_ROWS else self._ragged)
        y = share(x, jnp.where(mine, local, held),
                  jnp.where(mine, gates, 0.0), ws, held)
        if self.shared_ffn:
            dense = lambda f, name: nn.Dense(        # noqa: E731
                f, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name)
            xs = x.astype(self.dtype)
            y = y + dense(h, "shared_down")(
                nn.silu(dense(self.shared_ffn, "shared_gate")(xs))
                * dense(self.shared_ffn, "shared_up")(xs))
        return y.astype(x.dtype)

    def _dense(self, x, choices, gates, ws, groups):
        """``_ragged``'s sum without the dispatch: every group's expert
        over every row, the hidden activations weighed by the row's gate
        for that group (0 where the row did not pick it), one
        contraction over groups and width.  Its work depends on the
        shapes alone."""
        b, s, h = x.shape
        n = b * s
        flat = jnp.broadcast_to(x.reshape(n, h).astype(self.dtype),
                                (groups, n, h))
        *w_in, wo_c = (w.astype(self.dtype) for w in ws)
        # [groups, N]: a picked group's gate, 0 elsewhere (and for the
        # id ``groups`` = no group, which one_hot leaves out)
        combine = jnp.einsum(
            "nkg,nk->gn", jax.nn.one_hot(choices, groups,
                                         dtype=jnp.float32), gates)
        up = [jnp.einsum("gnh,ghf->gnf", flat, w) for w in w_in]
        h1 = nn.silu(up[0]) * up[1] if len(up) == 2 else nn.gelu(up[0])
        h1 = h1 * combine[..., None].astype(self.dtype)
        return jnp.einsum("gnf,gfh->nh", h1, wo_c).reshape(b, s, h)

    def _ragged(self, x, choices, gates, ws, groups):
        """``choices`` / ``gates`` [N, k]: each token's picks as group
        ids in ``0..groups`` (``groups`` itself = no group: the pair's
        row is computed by no expert and weighs 0) and their weights;
        ``ws`` the expert tensors ``(wi, wo)`` or ``(wg, wi, wo)``."""
        b, s, h = x.shape
        e, k = groups, self.top_k
        n = b * s
        flat = x.reshape(n, h).astype(self.dtype)

        # token-major (token, choice) pairs sorted by expert -> grouped
        # matmuls over contiguous expert segments
        pair_expert = choices.reshape(n * k)
        pair_token = jnp.repeat(jnp.arange(n), k)
        order = jnp.argsort(pair_expert)
        xs = flat[pair_token[order]]                      # [N*k, H]
        ws_c = tuple(w.astype(self.dtype) for w in ws)

        total = n * k
        if total <= self.ragged_chunk:
            group_sizes = jnp.bincount(pair_expert, length=e + 1)[
                :e].astype(jnp.int32)
            out = self._grouped_ffn(xs, group_sizes, *ws_c)
        else:
            # chunked grouped matmuls (round 2): big batchxseq blew past
            # Mosaic's scoped-VMEM tiling limit (BASELINE.md r1: 19.4M >
            # 16M at bs=16/seq=1024).  A contiguous slice of the sorted
            # pair array is still expert-sorted, so each chunk is a valid
            # ragged_dot with its own histogram; padding rows are tagged
            # past the last expert (keeps sortedness, no group) and
            # dropped after.
            chunk = self.ragged_chunk
            pad = (-total) % chunk
            seg = jnp.concatenate(
                [pair_expert[order],
                 jnp.full((pad,), e, pair_expert.dtype)])
            xs_p = jnp.pad(xs, ((0, pad), (0, 0)))
            chunks = (total + pad) // chunk
            seg_c = seg.reshape(chunks, chunk)
            sizes = jax.nn.one_hot(seg_c, e, dtype=jnp.int32).sum(1)

            def body(args):
                xc, sz = args
                return self._grouped_ffn(xc, sz, *ws_c)

            out = jax.lax.map(body, (xs_p.reshape(chunks, chunk, h), sizes))
            out = out.reshape(chunks * chunk, h)[:total]
        # inverse-permute back to token-major pair order; weighted sum
        # over each token's k picks (pure gathers, no scatter); a row no
        # group computed is left out whatever the kernel wrote there
        inv = jnp.argsort(order)
        out = out[inv].reshape(n, k, h)
        w = gates[..., None].astype(self.dtype)
        y = jnp.where(choices[..., None] < e, out * w, 0).sum(axis=1)
        return y.reshape(b, s, h)

    def _grouped_ffn(self, xs, sizes, *ws):
        """Expert FFN over one expert-sorted row block: two grouped
        matmuls, with the FFN dim tiled to ``ragged_f_chunk``.

        The full-width contraction hands Mosaic a [E, F, H] weight block
        whose scoped-VMEM footprint scales with F (the round-3 bs=16
        failure); slicing F keeps every ragged_dot's weight tile small
        while the row dim stays the whole (expert-sorted) chunk.  gelu is
        elementwise over F, so per-slice activation is exact, and the
        second matmul's F-contraction distributes over slices as a sum —
        a lax.scan accumulates it without materializing [rows, F].
        """
        if len(ws) == 3:
            if self.ragged_f_chunk:
                raise ValueError("ragged_f_chunk tiles the two-matrix "
                                 "(gelu) experts only")
            wg_c, wi_c, wo_c = ws
            h1 = (nn.silu(jax.lax.ragged_dot(xs, wg_c, sizes))
                  * jax.lax.ragged_dot(xs, wi_c, sizes))
            return jax.lax.ragged_dot(h1, wo_c, sizes)
        wi_c, wo_c = ws
        f = wi_c.shape[-1]
        fc = self.ragged_f_chunk
        if not fc or f <= fc:
            h1 = nn.gelu(jax.lax.ragged_dot(xs, wi_c, sizes))
            return jax.lax.ragged_dot(h1, wo_c, sizes)
        e, h = wi_c.shape[0], wi_c.shape[1]
        pad = (-f) % fc
        if pad:
            # zero-pad F: gelu(0)=0 and wo's zero rows contribute 0
            wi_c = jnp.pad(wi_c, ((0, 0), (0, 0), (0, pad)))
            wo_c = jnp.pad(wo_c, ((0, 0), (0, pad), (0, 0)))
        nf = (f + pad) // fc
        wi_t = wi_c.reshape(e, h, nf, fc).transpose(2, 0, 1, 3)
        wo_t = wo_c.reshape(e, nf, fc, h).transpose(1, 0, 2, 3)

        def slice_body(acc, ws):
            wi_s, wo_s = ws
            h1 = nn.gelu(jax.lax.ragged_dot(xs, wi_s, sizes))
            return acc + jax.lax.ragged_dot(h1, wo_s, sizes), None

        acc0 = jnp.zeros((xs.shape[0], h), self.dtype)
        out, _ = jax.lax.scan(slice_body, acc0, (wi_t, wo_t))
        return out

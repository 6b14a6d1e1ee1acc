"""Paged-KV prefill/decode programs for the decoder zoo members.

The training models are Flax modules whose ``__call__`` is a full
prefill-shaped forward; serving needs *incremental* decode — one token
per request per step, attending over everything generated so far.
Rather than fork the model definitions, this module re-walks each
family's OWN param tree functionally (the ``pp_embed``/``pp_head``
discipline ``parallel.pipeline`` established): every matmul/norm is the
family's own Flax sub-module ``.apply``'d onto its param subtree, and
only the attention inner product — the part that must read a KV cache
— is reimplemented, with the same f32-softmax/1-over-sqrt(d)
convention as ``parallel.sequence.dense_attention``.  Numerical parity
with ``model.apply`` over the full context is pinned by
``tests/test_serve.py`` and ``tests/test_zz_decode_kernels.py``.

**Paged KV cache** (vLLM): one pool of fixed-size pages per run,
``k_pages``/``v_pages`` shaped ``[layers, kv_heads, pages, page_size,
lanes]`` with ``lanes`` = ``head_dim`` zero-padded to the 128-lane
tile — one (page, kv head) is a contiguous tile-aligned slab, the only
page slice the TPU compiler will DMA (``ops.paged_attention``).  A
request holds a page *table* (int32 page indices); the
decode step reads its keys through the table and scatters the new
token's K/V into ``table[pos // page]``.  Page 0 is the reserved
*trash* page: padded/inactive rows write there (and are masked on
read), so one compiled program serves any admission pattern.

**Decode attention arms** (round 18, ``--decode_attention``):

- ``gather`` — the reference: gather the tables' pages of one layer
  into a dense worst-case ``[kv_heads, b, S, lanes]`` temporary —
  head-major, as the pool rests — and attend over it (``_gather_rows``,
  ``_attend_rows``).  Simple, and the parity anchor for everything
  else.  No program copies or re-lays out the pool: reads gather from
  it, writes are in-place slabs (``_write_pool``).
- ``paged`` — ``ops.paged_decode_attention``: a Pallas flash-decode
  kernel that reads K/V *directly through the page tables* (no dense
  gather ever materializes; online softmax over pages; block size =
  ``--decode_block_pages``).  The fresh token's K/V — not yet in the
  pool — merge into the online softmax through the kernel's returned
  logsumexp, so the scatter stays the one vectorized write at the end
  of the step.  The paged arm also fuses each residual-add with the
  following norm (``ops.fused_residual_norm``).

**Quantization arms** (``--quant``):

- ``int8_w`` — ``quantize_weights``: the decode projections (QKV,
  attention out, dense FFN / SwiGLU) held as per-output-channel int8
  with f32 scales, dequantized *at the matmul* (the scale multiplies
  the matmul output — never a dense f32 weight copy in the layer
  loop; the ``dequantize-in-hot-loop`` lint enforces the form).  MoE
  expert tensors stay f32 (the ragged dispatch owns them).
- ``int8_kv`` — the page pool is int8 with one f32 scale per (layer,
  page), written at prefill (per-chunk amax) and on every append (the
  touched page is dequantized, extended, and requantized — one
  vectorized op over all layers, outside the layer loop), and
  consumed *inside* the paged kernel.  Requires the paged arm.

Two compiled shapes per family, both AOT-lowered at engine warmup
(``obs.efficiency.aot_compile``):

- ``prefill``: batch 1 over a padded prompt-length bucket — computes
  the whole prompt's K/V in one pass, writes the pages, and returns
  the first generated token (the TTFT token).
- ``decode``: one token for a batch-bucket of in-flight requests at
  *per-row* cache depths (the continuous-batching shape).

**The cache is a tree** (``init_kv_state``).  A family whose every
layer attends over keys and values carries the pair ``(k_pages,
v_pages)`` above.  A family with a per-layer MIXER KIND
(``_Family.mixers``: ``"attn"`` | ``"kda"``) carries ``{"pages":
(k_pages, v_pages), "state": {"S", "conv"}}``: pages for its attention
layers ONLY (the pool's leading axis counts those), and a
slot-addressed recurrent state for its gated delta-rule layers —
``S [kda_layers, slots, heads, d_k, d_v]`` float32 and the short
convolution's tail ``conv [kda_layers, K-1, slots, 3 * heads * d]`` in
the compute dtype (slots beside channels: the two minor axes tile, so a
row's taps are read and written where the leaf rests).  A request owns pages AND one slot from admit to
finish; its slot index rides in one more column of its table (the
LAST: column 0 stays its first page), so both programs keep their
positional signatures.  Slot 0 is the trash slot, as page 0 is the
trash page.  Prefill runs the chunked form of the recurrence over the
padded bucket with the padding made inert and writes the state from
zero whatever the slot held; decode runs one recurrence step for every
slot at once, in place on the donated buffer (a slot no active row
names keeps its state: decay 1, beta 0).  ``jax.named_scope`` names the
four parts (``kda``, ``gqa``, ``moe``, ``head``) in both programs, and
``part_of_ops`` maps a compiled program's operations to them.

Supported families: ``GPTLM`` (gpt2*, moe*: learned positions, dense
or MoE FFN), ``LlamaLM`` (llama*: RoPE, GQA, SwiGLU) and
``SolarOpen2LM`` (solar_open2*: gated NoPE GQA every fourth layer,
gated delta-rule layers between, sigmoid-routed MoE with a shared
expert as a chip's share; gather arm, unquantized).  Everything else
that claims ``causal_lm`` fails loudly at engine construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_hc_bench.ops._pallas import pad_up as _pad_up
from tpu_hc_bench.ops.fused_residual_ln import fused_residual_norm
from tpu_hc_bench.ops.paged_attention import paged_decode_attention

_NEG_INF = -1e30
_QUANT_EPS = 1e-8
_LANES = 128        # TPU lane tile: the pool's minor dim is a multiple

QUANT_ARMS = ("off", "int8_w", "int8_kv")
DECODE_ATTENTION_ARMS = ("gather", "paged")


def _softmax_attend(q, keys, values, mask):
    """Single-query attention over dense token-major cache rows: the
    plain reference of ``_attend_rows`` and of the paged kernel (tests,
    ``chip_smoke.py``); no serve program calls it.

    ``q`` [b, 1, heads, d]; ``keys``/``values`` [b, S, heads, d];
    ``mask`` [b, S] bool (True = attend).  Same convention as
    ``parallel.sequence.dense_attention``: f32 scores, 1/sqrt(d) scale,
    probabilities cast back to the value dtype.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, keys,
                   preferred_element_type=jnp.float32) * (1.0 / d ** 0.5)
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(values.dtype), values)


def _qeinsum(spec, x, leaf, dtype):
    """Scale-fused quantized matmul: the int8 kernel feeds the einsum
    directly and the per-output-channel scale multiplies the matmul
    OUTPUT — the form that never materializes a dense f32 weight copy
    (and the form the ``dequantize-in-hot-loop`` lint accepts)."""
    return (jnp.einsum(spec, x, leaf["q"].astype(dtype))
            * leaf["scale"].astype(dtype))


def _quantize_leaf(w, contract_axes) -> dict:
    """Per-output-channel symmetric int8: amax over the contraction
    axes, scale = amax/127 (floored so all-zero channels stay finite)."""
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=contract_axes, keepdims=True)
    scale = jnp.maximum(amax / 127.0, _QUANT_EPS)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": jnp.squeeze(scale, axis=contract_axes)}


def _with_path(tree: dict, path: tuple, value) -> dict:
    """A copy of ``tree`` with the node at ``path`` replaced (shallow
    copies along the path only; untouched subtrees are shared)."""
    d = dict(tree)
    if len(path) == 1:
        d[path[0]] = value
    else:
        d[path[0]] = _with_path(tree[path[0]], path[1:], value)
    return d


@dataclasses.dataclass
class _Family:
    """One decoder family's functional pieces over its own param tree."""

    model: Any
    num_layers: int
    heads: int
    kv_heads: int
    head_dim: int
    norm_kind: str              # "layernorm" (GPT) | "rmsnorm" (Llama)
    embed_decode: Callable      # (params, tokens [b], positions [b]) -> [b,1,H]
    layer_params: Callable      # (params, l) -> layer subtree
    attn_norm: Callable         # (p_l, x) -> normed
    attn_norm_params: Callable  # (p_l) -> (gamma, beta|None)
    qkv: Callable               # (p_l, x, positions [b,s]) -> q, k, v
                                # ([b,s,heads,d], [b,s,kvh,d] x2; RoPE
                                # families rotate inside)
    attn_out: Callable          # (p_l, ctx [b,s,heads,d]) -> [b,s,H]
    ffn: Callable               # (p_l, x normed) -> [b,s,H]
    ffn_norm: Callable          # (p_l, x) -> normed
    ffn_norm_params: Callable   # (p_l) -> (gamma, beta|None)
    quant_paths: Callable       # (l) -> [(param path, contract axes)]
                                # quantize_weights' int8_w walk
    mixers: tuple = ()          # per layer "attn" | "kda"; () = all "attn"
    counters: tuple = ()        # names of the int32 scalars a decode step
                                # appends to its tokens (``next_tokens
                                # [b + len(counters)]``: no extra transfer)
    picks_per_token: int = 0    # routed-expert picks a token makes over
                                # all layers (0: no share is counted)

    @property
    def kv_layers(self) -> tuple:
        """The layers that keep keys and values in pages, in order."""
        return tuple(l for l in range(self.num_layers)
                     if not self.mixers or self.mixers[l] == "attn")

    @property
    def state_layers(self) -> tuple:
        """The layers that keep a recurrent state in a slot."""
        return tuple(l for l in range(self.num_layers)
                     if self.mixers and self.mixers[l] == "kda")

    def embed_prefill(self, params, tokens):
        # positions arange(s) — exactly the training forward's layout
        x, _ = self.model.pp_embed(params, tokens, None)
        return x

    def head(self, params, x):
        return self.model.pp_head(params, x)


def build_family(model, quant: str = "off") -> _Family:
    """The family adapter for a constructed decoder module.

    ``quant="int8_w"`` swaps the projection callables for scale-fused
    int8 einsums over the tree ``quantize_weights`` produces; every
    other leaf (embeddings, norms, biases, head, MoE experts) is read
    exactly as in the f32 adapter.
    """
    from tpu_hc_bench.models.gpt import GPTLM
    from tpu_hc_bench.models.llama import LlamaLM, RMSNorm, apply_rope

    if quant not in QUANT_ARMS:
        raise ValueError(f"quant must be one of {QUANT_ARMS}: {quant!r}")
    int8_w = quant == "int8_w"

    if isinstance(model, GPTLM):
        if model.scan_layers:
            raise ValueError(
                "serving decodes the unrolled layer_i param layout; "
                "--scan_layers checkpoints are not servable")
        d = model.hidden // model.heads
        dt = model.dtype

        def embed_decode(params, tokens, positions):
            wte = params["wte"]["embedding"].astype(dt)
            wpe = params["wpe"]["embedding"].astype(dt)
            return (wte[tokens] + wpe[positions])[:, None]

        if int8_w:
            def qkv(p_l, x, positions):
                del positions           # learned positions live in embed
                a = p_l["MultiHeadAttention_0"]["qkv"]
                out = (_qeinsum("bsh,hknd->bsknd", x, a["kernel"], dt)
                       + a["bias"].astype(dt))
                return out[:, :, 0], out[:, :, 1], out[:, :, 2]

            def attn_out(p_l, ctx):
                o = p_l["MultiHeadAttention_0"]["out"]
                return (_qeinsum("bsnd,ndh->bsh", ctx, o["kernel"], dt)
                        + o["bias"].astype(dt))
        else:
            def qkv(p_l, x, positions):
                del positions           # learned positions live in embed
                qkv_all = nn.DenseGeneral((3, model.heads, d),
                                          dtype=dt).apply(
                    {"params": p_l["MultiHeadAttention_0"]["qkv"]}, x)
                return qkv_all[:, :, 0], qkv_all[:, :, 1], qkv_all[:, :, 2]

            def attn_out(p_l, ctx):
                return nn.DenseGeneral(
                    model.hidden, axis=(-2, -1), dtype=dt).apply(
                    {"params": p_l["MultiHeadAttention_0"]["out"]}, ctx)

        def ffn(p_l, h):
            if model.num_experts:
                from tpu_hc_bench.models.moe import MoEFFN

                # serving ALWAYS dispatches ragged (grouped matmuls):
                # the einsum path drops capacity-overflow tokens, which
                # is tolerable batch-shaping noise in training but a
                # correctness hazard when serving (a request's token
                # silently losing its FFN), and it would also make
                # incremental decode diverge from the full forward.
                # Zero drops == ideal top-k == prefill/decode agree
                # exactly; param tree is impl-independent.  Expert
                # tensors stay f32 under int8_w (the ragged grouped
                # matmuls own their layout).
                return MoEFFN(
                    model.hidden, model.ffn, model.num_experts,
                    top_k=model.top_k, dtype=dt, impl="ragged",
                    ragged_f_chunk=model.moe_f_chunk,
                ).apply({"params": p_l["moe"]}, h)
            if int8_w:
                h = (_qeinsum("bsh,hf->bsf", h, p_l["fc"]["kernel"], dt)
                     + p_l["fc"]["bias"].astype(dt))
                h = nn.gelu(h)
                return (_qeinsum("bsf,fh->bsh", h, p_l["proj"]["kernel"],
                                 dt)
                        + p_l["proj"]["bias"].astype(dt))
            h = nn.Dense(model.ffn, dtype=dt).apply(
                {"params": p_l["fc"]}, h)
            h = nn.gelu(h)
            return nn.Dense(model.hidden, dtype=dt).apply(
                {"params": p_l["proj"]}, h)

        def quant_paths(l):
            base = (f"layer_{l}", "MultiHeadAttention_0")
            paths = [(base + ("qkv", "kernel"), (0,)),
                     (base + ("out", "kernel"), (0, 1))]
            if not model.num_experts:
                paths += [((f"layer_{l}", "fc", "kernel"), (0,)),
                          ((f"layer_{l}", "proj", "kernel"), (0,))]
            return paths

        return _Family(
            model=model, num_layers=model.num_layers, heads=model.heads,
            kv_heads=model.heads, head_dim=d, norm_kind="layernorm",
            embed_decode=embed_decode,
            layer_params=lambda params, l: params[f"layer_{l}"],
            attn_norm=lambda p_l, x: nn.LayerNorm(dtype=dt).apply(
                {"params": p_l["ln1"]}, x),
            attn_norm_params=lambda p_l: (p_l["ln1"]["scale"],
                                          p_l["ln1"]["bias"]),
            qkv=qkv,
            attn_out=attn_out,
            ffn=ffn,
            ffn_norm=lambda p_l, x: nn.LayerNorm(dtype=dt).apply(
                {"params": p_l["ln2"]}, x),
            ffn_norm_params=lambda p_l: (p_l["ln2"]["scale"],
                                         p_l["ln2"]["bias"]),
            quant_paths=quant_paths,
        )

    if isinstance(model, LlamaLM):
        if model.scan_layers:
            raise ValueError(
                "serving decodes the unrolled layer_i param layout; "
                "--scan_layers checkpoints are not servable")
        d = model.hidden // model.heads
        dt = model.dtype

        def embed_decode(params, tokens, positions):
            del positions               # RoPE rotates inside attention
            emb = params["tok_embed"]["embedding"].astype(dt)
            return emb[tokens][:, None]

        if int8_w:
            def qkv(p_l, x, positions):
                a = p_l["attn"]
                q = _qeinsum("bsh,hnd->bsnd", x, a["wq"]["kernel"], dt)
                k = _qeinsum("bsh,hnd->bsnd", x, a["wk"]["kernel"], dt)
                v = _qeinsum("bsh,hnd->bsnd", x, a["wv"]["kernel"], dt)
                return (apply_rope(q, positions),
                        apply_rope(k, positions), v)

            def attn_out(p_l, ctx):
                return _qeinsum("bsnd,ndh->bsh", ctx,
                                p_l["attn"]["wo"]["kernel"], dt)

            def ffn(p_l, h):
                gate = _qeinsum("bsh,hf->bsf", h, p_l["gate"]["kernel"],
                                dt)
                up = _qeinsum("bsh,hf->bsf", h, p_l["up"]["kernel"], dt)
                return _qeinsum("bsf,fh->bsh", nn.silu(gate) * up,
                                p_l["down"]["kernel"], dt)
        else:
            def qkv(p_l, x, positions):
                a = p_l["attn"]
                q = nn.DenseGeneral((model.heads, d), use_bias=False,
                                    dtype=dt).apply({"params": a["wq"]}, x)
                k = nn.DenseGeneral((model.num_kv_heads, d),
                                    use_bias=False,
                                    dtype=dt).apply({"params": a["wk"]}, x)
                v = nn.DenseGeneral((model.num_kv_heads, d),
                                    use_bias=False,
                                    dtype=dt).apply({"params": a["wv"]}, x)
                return (apply_rope(q, positions),
                        apply_rope(k, positions), v)

            def attn_out(p_l, ctx):
                return nn.DenseGeneral(
                    model.hidden, axis=(-2, -1), use_bias=False,
                    dtype=dt).apply({"params": p_l["attn"]["wo"]}, ctx)

            def ffn(p_l, h):
                gate = nn.Dense(model.ffn, use_bias=False,
                                dtype=dt).apply({"params": p_l["gate"]}, h)
                up = nn.Dense(model.ffn, use_bias=False, dtype=dt).apply(
                    {"params": p_l["up"]}, h)
                return nn.Dense(model.hidden, use_bias=False,
                                dtype=dt).apply(
                    {"params": p_l["down"]}, nn.silu(gate) * up)

        def quant_paths(l):
            return [((f"layer_{l}", "attn", "wq", "kernel"), (0,)),
                    ((f"layer_{l}", "attn", "wk", "kernel"), (0,)),
                    ((f"layer_{l}", "attn", "wv", "kernel"), (0,)),
                    ((f"layer_{l}", "attn", "wo", "kernel"), (0, 1)),
                    ((f"layer_{l}", "gate", "kernel"), (0,)),
                    ((f"layer_{l}", "up", "kernel"), (0,)),
                    ((f"layer_{l}", "down", "kernel"), (0,))]

        return _Family(
            model=model, num_layers=model.num_layers, heads=model.heads,
            kv_heads=model.num_kv_heads, head_dim=d, norm_kind="rmsnorm",
            embed_decode=embed_decode,
            layer_params=lambda params, l: params[f"layer_{l}"],
            attn_norm=lambda p_l, x: RMSNorm(dtype=dt).apply(
                {"params": p_l["attn_norm"]}, x),
            attn_norm_params=lambda p_l: (p_l["attn_norm"]["scale"], None),
            qkv=qkv,
            attn_out=attn_out,
            ffn=ffn,
            ffn_norm=lambda p_l, x: RMSNorm(dtype=dt).apply(
                {"params": p_l["mlp_norm"]}, x),
            ffn_norm_params=lambda p_l: (p_l["mlp_norm"]["scale"], None),
            quant_paths=quant_paths,
        )

    from tpu_hc_bench.models.solar_open2 import SolarOpen2LM

    if isinstance(model, SolarOpen2LM):
        if quant != "off":
            raise ValueError(
                "--quant has no arm for the solar_open2 family (its "
                "recurrent state and routed experts stay unquantized)")
        dt = model.dtype
        norm = lambda name: (lambda p_l, x: RMSNorm(      # noqa: E731
            eps=model.eps, dtype=dt).apply({"params": p_l[name]}, x))

        def ffn(p_l, h):
            """-> (y, held picks per token [b, s])"""
            y, st = model.moe_module().apply(
                {"params": p_l["moe"]}, h, mutable=["stats"])
            return y, st["stats"]["picks_held"][0]

        return _Family(
            model=model, num_layers=model.num_layers, heads=model.heads,
            kv_heads=model.kv_heads, head_dim=model.head_dim,
            norm_kind="rmsnorm",
            embed_decode=lambda params, tokens, positions: params[
                "tok_embed"]["embedding"].astype(dt)[tokens][:, None],
            layer_params=lambda params, l: {
                k: params[f"layer_{l}_{k}"]
                for k in ("norm1", "mixer", "norm2", "moe")},
            attn_norm=norm("norm1"),
            attn_norm_params=lambda p_l: (p_l["norm1"]["scale"], None),
            qkv=None, attn_out=None,    # the mixers' own functions
            ffn=ffn,
            ffn_norm=norm("norm2"),
            ffn_norm_params=lambda p_l: (p_l["norm2"]["scale"], None),
            quant_paths=lambda l: [],
            mixers=tuple("attn" if model.mixer_kind(l) == "gqa" else "kda"
                         for l in range(model.num_layers)),
            counters=("moe_picks_held",),
            picks_per_token=model.top_k * model.num_layers,
        )

    raise ValueError(
        f"no paged-decode family for {type(model).__name__} (supported: "
        "GPTLM, LlamaLM, SolarOpen2LM); non-causal members serve "
        "single-forward requests instead")


def quantize_weights(family: _Family, params: dict) -> dict:
    """The ``--quant=int8_w`` param tree: every decode projection kernel
    replaced by ``{"q": int8, "scale": f32 per-output-channel}``;
    embeddings, norms, biases, the head, and MoE expert tensors are the
    original leaves (shared, not copied)."""
    out = params
    for l in range(family.num_layers):
        for path, caxes in family.quant_paths(l):
            leaf = params
            for k in path:
                leaf = leaf[k]
            out = _with_path(out, path, _quantize_leaf(leaf, caxes))
    return out


def init_kv_pages(family: _Family, num_pages: int, page_size: int,
                  dtype) -> tuple[jax.Array, jax.Array]:
    """The zeroed page pool: ``[L, kv_heads, pages, page_size, lanes]``
    x2, ``L`` the layers that attend over pages, ``lanes`` = head_dim
    padded up to the 128-lane tile."""
    shape = (len(family.kv_layers), family.kv_heads, num_pages, page_size,
             _pad_up(family.head_dim, _LANES))
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _pool_rows(new, lanes: int):
    """``[L, n, kvh, d]`` fresh K/V rows -> ``[L, kvh, n, lanes]``: the
    pool's head-major order, head_dim zero-padded to the pool's lanes."""
    new = jnp.swapaxes(new, 1, 2)
    return jnp.pad(new, ((0, 0),) * 3 + ((0, lanes - new.shape[-1]),))


def _write_pool(pool, slabs, page_idx, offset):
    """Slab ``i`` of ``slabs [L, kvh, n, r, lanes]`` into
    ``pool[:, :, page_idx[i], offset[i]:offset[i] + r]``, in place.

    ``n`` ``dynamic_update_slice``s on the (donated) pool in the layout
    it rests in — a decode row (``r`` = 1) or a whole prompt page (``r``
    = page_size) at a time, every layer and kv head at once.  A scatter
    over ``(page, offset)`` pairs wants the pool page-major and makes
    XLA re-lay the whole pool out and back around it; a slab does not.
    Slabs land in order: where targets coincide (only ever on the trash
    page 0) the later one stays.
    """
    def write(i, pool):
        slab = jax.lax.dynamic_slice_in_dim(slabs, i, 1, axis=2)
        return jax.lax.dynamic_update_slice(
            pool, slab, (0, 0, page_idx[i], offset[i], 0))

    return jax.lax.fori_loop(0, slabs.shape[2], write, pool)


def _write_prompt_pages(pool, new, table, length):
    """Prefill's page write: ``new`` [L, s, kvh, d] into the pages of
    ``table [w]``, a whole page at a time; pages past the prompt (and
    the shared-prefix slots a cache hit zeroed) go to the trash page 0.
    The last page's rows past ``length`` hold pad-token K/V: masked on
    every read and overwritten by the appends."""
    page_size, lanes = pool.shape[3], pool.shape[4]
    s = new.shape[1]
    chunks = _pad_up(s, page_size) // page_size
    rows = jnp.pad(_pool_rows(new, lanes),
                   ((0, 0), (0, 0), (0, chunks * page_size - s), (0, 0)))
    idx = jnp.arange(chunks)
    page_idx = jnp.where(idx * page_size < length,
                         table[jnp.clip(idx, 0, table.shape[0] - 1)], 0)
    return _write_pool(
        pool, rows.reshape(*rows.shape[:2], chunks, page_size, lanes),
        page_idx, jnp.zeros_like(page_idx))


def _gather_rows(pool, layer: int, tables):
    """Layer ``layer``'s cache rows through ``tables [b, w]``, straight
    from the 5-D pool: ``[kvh, b, w * page_size, lanes]``, head-major
    as the pool rests.  ONE gather, a ``[page_size, lanes]`` slab per
    (head, row, slot) index with the static layer in it: a
    ``pool[layer]`` slice would stand as a temporary of its own, a slab
    of all heads per page lands page-major and is transposed after, and
    a head-minor result pushes its transpose onto the whole pool.
    """
    _, kvh, _, page_size, lanes = pool.shape
    head = jnp.arange(kvh, dtype=tables.dtype)[:, None, None]
    idx = jnp.stack(jnp.broadcast_arrays(
        jnp.asarray(layer, tables.dtype), head, tables[None]), axis=-1)
    rows = jax.lax.gather(
        pool, idx,
        jax.lax.GatherDimensionNumbers(
            offset_dims=(3, 4), collapsed_slice_dims=(0, 1, 2),
            start_index_map=(0, 1, 2)),
        slice_sizes=(1, 1, 1, page_size, lanes),
        mode="promise_in_bounds")           # [kvh, b, w, ps, lanes]
    return rows.reshape(kvh, tables.shape[0], -1, lanes)


def _attend_rows(q, k_rows, v_rows, k_new, v_new, lengths):
    """Single-query attention over head-major cache rows plus the
    fresh token: ``_softmax_attend``'s arithmetic in the pool's order.

    ``q`` [b, heads, d]; ``k_rows``/``v_rows`` [kvh, b, span, lanes]
    (``_gather_rows``; lanes past ``d`` are zero and stay in the
    contraction); ``k_new``/``v_new`` [b, kvh, d], the token's own K/V,
    one more score column (not yet in the pool); ``lengths`` [b] masks
    the rows.  GQA is a reshape of ``q`` to [b, kvh, group, d]: head
    ``h`` reads kv head ``h // group`` and no row is repeated.
    Returns [b, heads, d].
    """
    b, heads, d = q.shape
    kvh, _, span, lanes = k_rows.shape
    qg = q.reshape(b, kvh, heads // kvh, d)
    scale = 1.0 / d ** 0.5
    s_rows = jnp.einsum(
        "bhgd,hbkd->bhgk",
        jnp.pad(qg, ((0, 0),) * 3 + ((0, lanes - d),)), k_rows,
        preferred_element_type=jnp.float32) * scale
    s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_new,
                       preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(span)[None, :] < lengths[:, None]
    s = jnp.concatenate(
        [jnp.where(valid[:, None, None, :], s_rows, _NEG_INF),
         s_new[..., None]], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(v_rows.dtype)
    ctx = (jnp.einsum("bhgk,hbkd->bhgd", p[..., :span], v_rows)[..., :d]
           + p[..., span:] * v_new[:, :, None, :])
    return ctx.reshape(b, heads, d)


def init_kv_state(family: _Family, num_pages: int, page_size: int,
                  dtype, quant: str = "off", slots: int = 0):
    """The engine's cache carry, a tree: ``(k_pages, v_pages)`` — int8
    pools plus per-(layer, page) f32 scales under ``int8_kv`` (scales
    start at 1, matching the zeroed pool) — or, for a family with
    recurrent-state layers, ``{"pages": (k_pages, v_pages), "state":
    {"S", "conv"}}`` with ``slots`` state slots (slot 0 the trash
    slot): ``S`` float32 whatever ``dtype`` is."""
    if family.state_layers:
        m = family.model
        n = m.kda_heads * m.kda_head_dim
        layers = len(family.state_layers)
        return {
            "pages": init_kv_pages(family, num_pages, page_size, dtype),
            "state": {
                "S": jnp.zeros((layers, slots, m.kda_heads, m.kda_head_dim,
                                m.kda_head_dim), jnp.float32),
                "conv": jnp.zeros((layers, m.conv_kernel - 1, slots, 3 * n),
                                  dtype)}}
    if quant == "int8_kv":
        kp, vp = init_kv_pages(family, num_pages, page_size, jnp.int8)
        sc = jnp.ones((family.num_layers, num_pages), jnp.float32)
        return kp, vp, sc, sc
    return init_kv_pages(family, num_pages, page_size, dtype)


def build_page_copy_fn():
    """The copy-on-write program (round 25): duplicate physical page
    ``src`` into ``dst`` across every KV leaf, all layers at once.

    The f32/int8 pools ``[L, kvh, pages, ps, lanes]`` index pages on
    axis 2 and the int8_kv per-(layer, page) scale planes ``[L,
    pages]`` on axis 1; one tree_map covers both quant arms, and an
    int8 page is copied in its final quantized layout, scale and all
    (no dequant round-trip).  Args at call time: ``(kv, src [], dst [])``;
    one AOT program per engine (page count is baked into the pool
    shapes, not the program), warmed beside the decode buckets so a
    first mid-traffic COW is never a compile.
    """

    def page_copy(kv, src, dst):
        def copy(x):
            if x.ndim == 2:                         # scale plane
                return x.at[:, dst].set(x[:, src])
            return x.at[:, :, dst].set(x[:, :, src])

        if isinstance(kv, dict):        # a state slot is never shared
            return dict(kv, pages=jax.tree_util.tree_map(copy, kv["pages"]))
        return jax.tree_util.tree_map(copy, kv)

    return page_copy


def _write_quantized_chunks(pages_q, scales, new, table, length,
                            page_size, table_width):
    """Prefill's int8 page write: ``new`` [L, s, kvh, d] chunked into
    pages, one amax-derived scale per (layer, chunk), chunks past the
    prompt routed to the trash page 0."""
    num_layers, s = new.shape[0], new.shape[1]
    s_pad = _pad_up(s, page_size)
    if s_pad != s:
        new = jnp.pad(new, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    c = s_pad // page_size
    chunks = new.reshape(num_layers, c, page_size, *new.shape[2:])
    idx = jnp.arange(c)
    cpage = jnp.where(idx * page_size < length,
                      table[jnp.clip(idx, 0, table_width - 1)], 0)
    amax = jnp.max(jnp.abs(chunks), axis=(2, 3, 4))
    sc = jnp.maximum(amax / 127.0, _QUANT_EPS)              # [L, c]
    q = jnp.clip(jnp.round(chunks / sc[:, :, None, None, None]),
                 -127, 127).astype(jnp.int8)
    q = jnp.pad(q.transpose(0, 3, 1, 2, 4),         # [L, kvh, c, ps, d]
                ((0, 0),) * 4 + ((0, pages_q.shape[-1] - q.shape[-1]),))
    return (pages_q.at[:, :, cpage].set(q),
            scales.at[:, cpage].set(sc))


def _append_quantized(pages_q, scales, page_idx, offset, new):
    """Decode's int8 append: the touched page is dequantized with its
    stored scale, the new row written, and the page requantized with a
    fresh amax — ONE vectorized op over all layers and rows, outside
    the layer loop.  Rows past the append offset are zeroed BEFORE the
    amax: a page recycled from a retired request (the allocator never
    scrubs) still holds the previous occupant's values at those
    offsets, and trusting them would quantize this request's fresh
    token with a scale inflated by someone else's garbage (reads are
    masked either way; the fresh row's precision is what's at stake)."""
    b = page_idx.shape[0]
    rows = jnp.arange(b)
    old = pages_q[:, :, page_idx]               # [L, kvh, b, ps, lanes]
    sc = scales[:, page_idx][:, None, :, None, None]        # [L,1,b,1,1]
    page = old.astype(jnp.float32) * sc
    page_size = page.shape[3]
    own = (jnp.arange(page_size)[None, :]
           <= offset[:, None])                      # [b, ps]
    page = jnp.where(own[None, None, :, :, None], page, 0.0)
    page = page.at[:, :, rows, offset].set(
        _pool_rows(new.astype(jnp.float32), page.shape[-1]))
    amax = jnp.max(jnp.abs(page), axis=(1, 3, 4))           # [L, b]
    new_sc = jnp.maximum(amax / 127.0, _QUANT_EPS)
    q = jnp.clip(jnp.round(page / new_sc[:, None, :, None, None]),
                 -127, 127).astype(jnp.int8)
    return (pages_q.at[:, :, page_idx].set(q),
            scales.at[:, page_idx].set(new_sc))


def build_prefill_fn(family: _Family, page_size: int, table_width: int,
                     quant: str = "off"):
    """The (batch-1, padded prompt bucket) prefill program.

    Args at call time: ``(params, kv, tokens [1, s], length [],
    table [w])`` where ``kv`` is the engine's KV carry
    (``init_kv_state``).  Returns ``(next_token [1], logits [1, vocab],
    kv)`` with the prompt's K/V scattered into the table's pages (pad
    positions routed to the trash page 0; int8 pools get per-page
    scales from the chunked write).

    ``table`` here is the WRITE table, and that is the prefix-cache
    seam (round 25): a cache-hit admission passes a copy with the
    shared slots zeroed, so their stores route to the trash page —
    the shared physical pages already hold bitwise-identical K/V from
    the prefill that populated them — while the full dense pass still
    runs (``next_token`` needs attention over every prompt position)
    and the request's DECODE table keeps the real shared page ids.
    Skipping a shared slot is a page-table edit, never a new program;
    under int8_kv the same routing skips the quantized chunk store,
    so a cached page is quantized once and shared in its final
    int8+scale layout.
    """
    from tpu_hc_bench.parallel.sequence import dense_attention

    if family.state_layers:
        return _build_hybrid_prefill_fn(family, table_width)

    def prefill(params, kv, tokens, length, table):
        s = tokens.shape[1]
        positions = jnp.arange(s)[None, :]
        x = family.embed_prefill(params, tokens)
        group = family.heads // family.kv_heads
        new_k, new_v = [], []
        for l in range(family.num_layers):
            p_l = family.layer_params(params, l)
            h = family.attn_norm(p_l, x)
            q, k, v = family.qkv(p_l, h, positions)
            new_k.append(k)
            new_v.append(v)
            if group > 1:
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            # causal masking alone is sufficient under right-padding:
            # the only logits read are at `length - 1`, whose keys
            # j <= length - 1 are all valid prompt positions
            ctx = dense_attention(q, k, v, causal=True)
            x = x + family.attn_out(p_l, ctx)
            x = x + family.ffn(p_l, family.ffn_norm(p_l, x))
        x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        logits = family.head(params, x_last)[:, 0]      # [1, vocab]
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        kn = jnp.stack([k[0] for k in new_k])       # [L, s, kvh, d]
        vn = jnp.stack([v[0] for v in new_v])
        if quant == "int8_kv":
            k_pages, v_pages, k_scales, v_scales = kv
            # zero the pad positions: their (garbage-token) K/V would
            # otherwise inflate the last page's amax scale
            valid = (jnp.arange(s) < length)[None, :, None, None]
            kn = jnp.where(valid, kn, 0.0)
            vn = jnp.where(valid, vn, 0.0)
            k_pages, k_scales = _write_quantized_chunks(
                k_pages, k_scales, kn, table, length, page_size,
                table_width)
            v_pages, v_scales = _write_quantized_chunks(
                v_pages, v_scales, vn, table, length, page_size,
                table_width)
            return next_token, logits, (k_pages, v_pages,
                                        k_scales, v_scales)
        k_pages, v_pages = kv
        return next_token, logits, (
            _write_prompt_pages(k_pages, kn, table, length),
            _write_prompt_pages(v_pages, vn, table, length))

    return prefill


def build_decode_fn(family: _Family, page_size: int, table_width: int,
                    attention: str = "gather", quant: str = "off",
                    block_pages: int = 0):
    """The one-token-per-row decode program for a batch bucket.

    Args at call time: ``(params, kv, tokens [b], tables [b, w],
    lengths [b], active [b])`` where ``lengths`` is each row's cache
    depth (== the fed token's position) and ``kv`` the engine's KV
    carry.  Inactive rows compute on the trash page and write back to
    it; retirement and admission are pure host-side bookkeeping, never
    a new shape.  Returns ``(next_tokens [b], logits [b, vocab], kv)``.

    ``attention="gather"`` is the dense-gather reference;
    ``"paged"`` runs ``ops.paged_decode_attention`` straight over the
    page tables with ``block_pages`` pages per kernel block and fuses
    the residual-add+norm pairs (``ops.fused_residual_norm``).
    """
    if attention not in DECODE_ATTENTION_ARMS:
        raise ValueError(f"attention must be one of "
                         f"{DECODE_ATTENTION_ARMS}: {attention!r}")
    if quant == "int8_kv" and attention != "paged":
        raise ValueError("int8_kv scales are consumed inside the paged "
                         "kernel; the gather reference has no "
                         "scale-fused read path")
    if family.state_layers and (attention != "gather" or quant != "off"):
        raise ValueError(
            "a family with recurrent-state layers decodes on the gather "
            "arm, unquantized (--decode_attention=paged and --quant have "
            "no kernel that knows its cache tree)")
    ppb = max(1, block_pages)

    def scatter_new(kv, tables, lengths, active, kn, vn):
        b = lengths.shape[0]
        rows = jnp.arange(b)
        page_idx = jnp.where(
            active,
            tables[rows, jnp.clip(lengths // page_size, 0,
                                  table_width - 1)], 0)
        offset = lengths % page_size
        if quant == "int8_kv":
            k_pages, v_pages, k_scales, v_scales = kv
            k_pages, k_scales = _append_quantized(
                k_pages, k_scales, page_idx, offset, kn)
            v_pages, v_scales = _append_quantized(
                v_pages, v_scales, page_idx, offset, vn)
            return k_pages, v_pages, k_scales, v_scales
        k_pages, v_pages = kv
        lanes = k_pages.shape[-1]
        return (
            _write_pool(k_pages, _pool_rows(kn, lanes)[:, :, :, None],
                        page_idx, offset),
            _write_pool(v_pages, _pool_rows(vn, lanes)[:, :, :, None],
                        page_idx, offset))

    if family.state_layers:
        return _build_hybrid_decode_fn(family, table_width, scatter_new)

    def decode_gather(params, kv, tokens, tables, lengths, active):
        k_pages, v_pages = kv
        x = family.embed_decode(params, tokens, lengths)
        new_k, new_v = [], []
        for l in range(family.num_layers):
            p_l = family.layer_params(params, l)
            h = family.attn_norm(p_l, x)
            q, k, v = family.qkv(p_l, h, lengths[:, None])
            new_k.append(k[:, 0])
            new_v.append(v[:, 0])
            # a layer's gathers wait for its own q: left free, the
            # scheduler starts all 2 x L of them at once in the small
            # buckets, and rows that fit the chip's fast memory one
            # layer at a time are spilled to HBM, L layers at a time
            q, tabs = jax.lax.optimization_barrier((q, tables))
            ctx = _attend_rows(
                q[:, 0], _gather_rows(k_pages, l, tabs),
                _gather_rows(v_pages, l, tabs), k[:, 0], v[:, 0],
                lengths)
            x = x + family.attn_out(p_l, ctx[:, None])
            x = x + family.ffn(p_l, family.ffn_norm(p_l, x))
        logits = family.head(params, x)[:, 0]
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        kn = jnp.stack(new_k, axis=0)               # [L, b, kvh, d]
        vn = jnp.stack(new_v, axis=0)
        return (next_tokens, logits,
                scatter_new(kv, tables, lengths, active, kn, vn))

    def decode_paged(params, kv, tokens, tables, lengths, active):
        if quant == "int8_kv":
            k_pages, v_pages, k_scales, v_scales = kv
        else:
            k_pages, v_pages = kv
            k_scales = v_scales = None
        group = family.heads // family.kv_heads
        scale = 1.0 / family.head_dim ** 0.5
        x = family.embed_decode(params, tokens, lengths)
        new_k, new_v = [], []
        delta = None        # the pending residual add, fused into the
                            # NEXT norm (ops.fused_residual_norm)
        for l in range(family.num_layers):
            p_l = family.layer_params(params, l)
            if delta is None:
                h = family.attn_norm(p_l, x)
            else:
                g, bta = family.attn_norm_params(p_l)
                x, h = fused_residual_norm(x, delta, g, bta,
                                           kind=family.norm_kind)
            q, k, v = family.qkv(p_l, h, lengths[:, None])
            new_k.append(k[:, 0])
            new_v.append(v[:, 0])
            # the WHOLE pool rides the kernel operand with a static
            # layer index — a k_pages[l] slice here would materialize
            # a per-layer pool copy as a temp, the very bytes the
            # kernel exists to not spend
            o_cache, lse = paged_decode_attention(
                q[:, 0], k_pages, v_pages, tables, lengths,
                pages_per_block=ppb, layer=l, return_lse=True,
                k_scales=k_scales, v_scales=v_scales)
            # the fresh token's K/V are not in the pool yet: fold them
            # into the kernel's online softmax through its logsumexp
            # (softmax over [cache, fresh] == lse-weighted mix; rows
            # with an empty cache get lse ~ -inf -> weight 1 on fresh)
            kf, vf = k[:, 0], v[:, 0]               # [b, kvh, d]
            if group > 1:
                kf = jnp.repeat(kf, group, axis=1)
                vf = jnp.repeat(vf, group, axis=1)
            s_new = jnp.sum(
                q[:, 0].astype(jnp.float32) * kf.astype(jnp.float32),
                axis=-1) * scale                    # [b, heads]
            w_new = jax.nn.sigmoid(s_new - lse)
            ctx = (o_cache.astype(jnp.float32)
                   * (1.0 - w_new)[..., None]
                   + vf.astype(jnp.float32) * w_new[..., None])
            a_out = family.attn_out(p_l, ctx.astype(x.dtype)[:, None])
            g2, b2 = family.ffn_norm_params(p_l)
            x, h2 = fused_residual_norm(x, a_out, g2, b2,
                                        kind=family.norm_kind)
            delta = family.ffn(p_l, h2)
        x = x + delta
        logits = family.head(params, x)[:, 0]
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        kn = jnp.stack(new_k, axis=0)               # [L, b, kvh, d]
        vn = jnp.stack(new_v, axis=0)
        return (next_tokens, logits,
                scatter_new(kv, tables, lengths, active, kn, vn))

    return decode_paged if attention == "paged" else decode_gather


# ---------------------------------------------------------------------
# families with a per-layer mixer kind: pages for the attention layers,
# a slot-addressed recurrent state for the gated delta-rule layers


def _build_hybrid_prefill_fn(family: _Family, table_width: int):
    """Prefill over a padded bucket for a family with ``kda`` layers.

    ``table`` is ``[table_width + 1]``: the pages, then the request's
    state slot.  The chunked recurrence runs over the whole bucket with
    the padded positions made inert (``beta`` = 0, ``g`` = 0); the state
    entering is zero whatever the slot held, the state leaving and the
    convolution's tail at ``length`` go to the slot."""
    from tpu_hc_bench.models import solar_open2 as so
    from tpu_hc_bench.parallel.sequence import dense_attention

    m = family.model
    group = family.heads // family.kv_heads
    n = m.kda_heads * m.kda_head_dim
    kv_index = {l: i for i, l in enumerate(family.kv_layers)}
    st_index = {l: i for i, l in enumerate(family.state_layers)}

    def prefill(params, kv, tokens, length, table):
        k_pages, v_pages = kv["pages"]
        S, conv = kv["state"]["S"], kv["state"]["conv"]
        s = tokens.shape[1]
        slot = table[table_width]
        valid = jnp.arange(s) < length
        x = family.embed_prefill(params, tokens)
        new_k, new_v = {}, {}
        for l in range(family.num_layers):
            p_l = family.layer_params(params, l)
            u = family.attn_norm(p_l, x)
            if l in kv_index:
                with jax.named_scope("gqa"):
                    q, k, v = so.gqa_inputs(p_l["mixer"], u, family.heads,
                                            family.kv_heads)
                    new_k[l], new_v[l] = k[0], v[0]
                    # causal masking alone suffices under right-padding
                    ctx = dense_attention(
                        q, jnp.repeat(k, group, axis=2),
                        jnp.repeat(v, group, axis=2), causal=True)
                    x = x + so.gqa_output(p_l["mixer"], ctx, u)
            else:
                with jax.named_scope("kda"):
                    li = st_index[l]
                    tail0 = jnp.zeros((1, m.conv_kernel - 1, 3 * n),
                                      u.dtype)
                    q, k, v, g, beta, padded = so.kda_inputs(
                        p_l["mixer"], u, tail0, m.kda_heads, m.neg_eigval)
                    g = jnp.where(valid[None, :, None, None], g, 0.0)
                    beta = jnp.where(valid[None, :, None], beta, 0.0)
                    o, s_end = so.kda_sequence(
                        q[0], k[0], v[0], g[0], beta[0],
                        jnp.zeros(S.shape[2:], jnp.float32))
                    x = x + so.kda_output(p_l["mixer"], o[None], u, m.eps)
                    S = jax.lax.dynamic_update_slice(
                        S, s_end[None, None], (li, slot, 0, 0, 0))
                    tail = jax.lax.dynamic_slice_in_dim(
                        padded[0], length, m.conv_kernel - 1, axis=0)
                    conv = jax.lax.dynamic_update_slice(
                        conv, tail[None, :, None].astype(conv.dtype),
                        (li, 0, slot, 0))
            with jax.named_scope("moe"):
                y, _ = family.ffn(p_l, family.ffn_norm(p_l, x))
                x = x + y
        with jax.named_scope("head"):
            x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
            logits = family.head(params, x_last)[:, 0]
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with jax.named_scope("gqa"):
            kn = jnp.stack([new_k[l] for l in family.kv_layers])
            vn = jnp.stack([new_v[l] for l in family.kv_layers])
            pages = (
                _write_prompt_pages(k_pages, kn, table[:table_width],
                                    length),
                _write_prompt_pages(v_pages, vn, table[:table_width],
                                    length))
        return next_token, logits, {
            "pages": pages, "state": {"S": S, "conv": conv}}

    return prefill


def _build_hybrid_decode_fn(family: _Family, table_width: int,
                            scatter_new):
    """One token a row for a family with ``kda`` layers, gather arm.

    ``tables`` is ``[b, table_width + 1]`` (pages, then the slot).  A
    ``kda`` layer runs ONE recurrence step over every slot of the state
    at once, in place: the rows' q, k, v, g, beta are scattered to slot
    order first (a few KB a row), and a slot that no active row names
    gets decay 1 and beta 0, which leave it as it was (inactive rows
    name the trash slot 0).  No row's state is gathered out of the pool
    or scattered back.  ``scatter_new`` is ``build_decode_fn``'s page
    write.  Returns ``(next_tokens [b + 1], logits, kv)``:
    the last entry counts the active rows' picks that landed on an
    expert held here (``family.counters``)."""
    from tpu_hc_bench.models import solar_open2 as so

    m = family.model
    kv_index = {l: i for i, l in enumerate(family.kv_layers)}
    st_index = {l: i for i, l in enumerate(family.state_layers)}

    def decode(params, kv, tokens, tables, lengths, active):
        k_pages, v_pages = kv["pages"]
        S, conv = kv["state"]["S"], kv["state"]["conv"]
        n_slots = S.shape[1]
        tabs = tables[:, :table_width]
        slots = jnp.where(active, tables[:, table_width], 0)
        x = family.embed_decode(params, tokens, lengths)
        new_k, new_v = {}, {}
        held = jnp.zeros((), jnp.int32)

        def to_slots(rows):
            return jnp.zeros((n_slots,) + rows.shape[1:],
                             rows.dtype).at[slots].set(rows)

        for l in range(family.num_layers):
            p_l = family.layer_params(params, l)
            u = family.attn_norm(p_l, x)
            if l in kv_index:
                with jax.named_scope("gqa"):
                    q, k, v = so.gqa_inputs(p_l["mixer"], u, family.heads,
                                            family.kv_heads)
                    new_k[l], new_v[l] = k[:, 0], v[:, 0]
                    q, tb = jax.lax.optimization_barrier((q, tabs))
                    ctx = _attend_rows(
                        q[:, 0], _gather_rows(k_pages, kv_index[l], tb),
                        _gather_rows(v_pages, kv_index[l], tb),
                        k[:, 0], v[:, 0], lengths)
                    x = x + so.gqa_output(p_l["mixer"], ctx[:, None], u)
            else:
                with jax.named_scope("kda"):
                    li = st_index[l]
                    q, k, v, g, beta, padded = so.kda_inputs(
                        p_l["mixer"], u,
                        jnp.swapaxes(conv[li][:, slots], 0, 1),
                        m.kda_heads, m.neg_eigval)
                    g = jnp.where(active[:, None, None], g[:, 0], 0.0)
                    beta = jnp.where(active[:, None], beta[:, 0], 0.0)
                    s_l = jax.lax.dynamic_index_in_dim(S, li, 0, False)
                    s_l, o = so.kda_step(
                        s_l, to_slots(q[:, 0]), to_slots(k[:, 0]),
                        to_slots(v[:, 0]), to_slots(g), to_slots(beta))
                    S = jax.lax.dynamic_update_index_in_dim(S, s_l, li, 0)
                    # a tap at a time: every operand axis but the
                    # channels is then an index of the scatter, and
                    # the leaf is written in the layout it rests in
                    for t in range(m.conv_kernel - 1):
                        conv = conv.at[li, t, slots].set(
                            padded[:, 1 + t].astype(conv.dtype))
                    x = x + so.kda_output(p_l["mixer"], o[slots][:, None],
                                          u, m.eps)
            with jax.named_scope("moe"):
                y, picks = family.ffn(p_l, family.ffn_norm(p_l, x))
                x = x + y
                held = held + jnp.sum(jnp.where(active, picks[:, 0], 0))
        with jax.named_scope("head"):
            logits = family.head(params, x)[:, 0]
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with jax.named_scope("gqa"):
            pages = scatter_new(
                kv["pages"], tabs, lengths, active,
                jnp.stack([new_k[l] for l in family.kv_layers]),
                jnp.stack([new_v[l] for l in family.kv_layers]))
        return (jnp.concatenate([next_tokens, held[None]]), logits,
                {"pages": pages, "state": {"S": S, "conv": conv}})

    return decode


PARTS = ("kda", "gqa", "moe", "head")
# the grouped-matmul kernel ``jax.lax.ragged_dot`` lowers to carries its
# own name and no scope: in these programs only the experts issue it
_KERNEL_PARTS = {"ragged-dot": "moe"}


def part_of_ops(hlo_text: str, parts: tuple = PARTS) -> dict:
    """``{"<instruction>:<result shape>": part}`` for a compiled
    program's operations under one of the ``jax.named_scope`` parts
    (``analysis.hlo.ops_by_scope``): the key is how a device trace names
    the operation (its events carry the instruction, not the scope)."""
    from tpu_hc_bench.analysis import hlo

    return hlo.ops_by_scope(hlo_text, parts, _KERNEL_PARTS)

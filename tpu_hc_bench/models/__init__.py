"""Model zoo + registry — the tf_cnn_benchmarks ``--model=`` dispatch.

The reference drives tf_cnn_benchmarks' model zoo through a single
``--model`` flag (pinned to resnet50 at ``run-tf-sing-ucx-openmpi.sh:34,66``;
BASELINE.json additionally names inception3, vgg16, and BERT-base MLM).
This registry reproduces that dispatch for the TPU-native zoo, including
tf_cnn_benchmarks' ``trivial`` model (flatten + one dense layer) used as a
pipeline smoke test.

``flops_per_example`` is the *forward-pass* FLOP count at the canonical
input shape, used for MFU accounting (train step ~= 3x forward).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp


class TrivialModel(nn.Module):
    """tf_cnn_benchmarks' `trivial`: flatten -> dense(num_classes)."""

    num_classes: int = 1000
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        del train
        x = x.astype(self.dtype).reshape((x.shape[0], -1))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    create: Callable[..., nn.Module]   # (num_classes, dtype) -> Module
    input_shape: tuple[int, ...]       # per-example, NHWC for images;
                                       # (seq_len,) token ids for text
    flops_per_example: float           # forward FLOPs at input_shape
    is_text: bool = False
    default_image_size: int = 224
    supports_s2d: bool = False         # stem accepts space_to_depth=True
    vocab_size: int = 30522            # text models: synthetic-data label space
    causal_lm: bool = False            # text models: next-token objective
    moe: bool = False                  # factory accepts moe_impl
    attention: bool = False            # image transformer (ViT): factory
                                       # accepts attention_impl/remat
    fused_conv: bool = False           # factory accepts fused_conv (the
                                       # Pallas bottleneck segment, v1
                                       # bottleneck resnets only)
    integer_input: bool = False        # [B, ...] int32 id inputs
                                       # (NCF: SyntheticIds feeds it)
    ctc: bool = False                  # CTC objective over spectrogram
                                       # frames (deepspeech2:
                                       # SyntheticSpeech feeds it)


def _registry() -> dict[str, ModelSpec]:
    from tpu_hc_bench.models import (
        alexnet, bert, cifar_resnet, deepspeech, densenet, googlenet, gpt,
        granite4h, inception, llama, mellum2, mobilenet, nasnet, ncf,
        resnet, small_cnns, solar_open2, vgg, vit,
    )

    specs = [
        ModelSpec("trivial", TrivialModel, (224, 224, 3), 2 * 150528 * 1000),
        ModelSpec("alexnet", alexnet.alexnet, (224, 224, 3), 1.43e9),
        ModelSpec("googlenet", googlenet.googlenet, (224, 224, 3), 3.0e9),
        # forward FLOPs below are 2*MACs of the conv/FC layers at the
        # canonical shape (same convention as the resnet figures)
        ModelSpec("lenet", small_cnns.lenet, (28, 28, 3), 2.46e7,
                  default_image_size=28),
        ModelSpec("overfeat", small_cnns.overfeat, (231, 231, 3), 7.53e9,
                  default_image_size=231),
        ModelSpec("mobilenet", mobilenet.mobilenet, (224, 224, 3), 1.16e9),
        # NASNet-A: 2*MACs — mobile 564M, large 23.8B multiply-adds
        ModelSpec("nasnet", nasnet.nasnet, (224, 224, 3), 1.13e9),
        ModelSpec("nasnetlarge", nasnet.nasnetlarge, (331, 331, 3), 4.76e10,
                  default_image_size=331),
        ModelSpec("densenet40_k12", densenet.densenet40_k12, (32, 32, 3),
                  5.08e8, default_image_size=32),
        ModelSpec("densenet100_k12", densenet.densenet100_k12, (32, 32, 3),
                  1.88e9, default_image_size=32),
        # ResNet fwd GFLOPs at 224^2 (2*MACs): v1.5 figures
        ModelSpec("resnet18", resnet.resnet18, (224, 224, 3), 3.64e9,
                  supports_s2d=True),
        ModelSpec("resnet34", resnet.resnet34, (224, 224, 3), 7.34e9,
                  supports_s2d=True),
        ModelSpec("resnet50", resnet.resnet50, (224, 224, 3), 8.2e9,
                  supports_s2d=True, fused_conv=True),
        ModelSpec("resnet101", resnet.resnet101, (224, 224, 3), 15.7e9,
                  supports_s2d=True, fused_conv=True),
        ModelSpec("resnet152", resnet.resnet152, (224, 224, 3), 23.1e9,
                  supports_s2d=True, fused_conv=True),
        # v2 (full preactivation) — same conv stack, same 2*MAC figures
        ModelSpec("resnet50_v2", resnet.resnet50_v2, (224, 224, 3), 8.2e9,
                  supports_s2d=True),
        ModelSpec("resnet101_v2", resnet.resnet101_v2, (224, 224, 3), 15.7e9,
                  supports_s2d=True),
        ModelSpec("resnet152_v2", resnet.resnet152_v2, (224, 224, 3), 23.1e9,
                  supports_s2d=True),
        # CIFAR 6n+2 family (He 2015 §4.2), 32x32
        ModelSpec("resnet20_cifar", cifar_resnet.resnet20_cifar, (32, 32, 3),
                  8.2e7, default_image_size=32),
        ModelSpec("resnet32_cifar", cifar_resnet.resnet32_cifar, (32, 32, 3),
                  1.4e8, default_image_size=32),
        ModelSpec("resnet44_cifar", cifar_resnet.resnet44_cifar, (32, 32, 3),
                  1.9e8, default_image_size=32),
        ModelSpec("resnet56_cifar", cifar_resnet.resnet56_cifar, (32, 32, 3),
                  2.5e8, default_image_size=32),
        ModelSpec("resnet110_cifar", cifar_resnet.resnet110_cifar, (32, 32, 3),
                  5.1e8, default_image_size=32),
        ModelSpec("vgg11", vgg.vgg11, (224, 224, 3), 15.2e9),
        ModelSpec("vgg16", vgg.vgg16, (224, 224, 3), 30.9e9),
        ModelSpec("vgg19", vgg.vgg19, (224, 224, 3), 39.3e9),
        # ViT-B/16: 17.6G multiply-adds at 224^2 (the figure papers quote)
        # -> 35.2e9 under this registry's 2*MACs convention
        ModelSpec("vit_b16", vit.vit_b16, (224, 224, 3), 35.2e9,
                  attention=True),
        # ViT-L/16: ~61.6G multiply-adds at 224^2 -> 2*MACs
        ModelSpec("vit_l16", vit.vit_l16, (224, 224, 3), 123.2e9,
                  attention=True),
        # 2*MACs at 32^2/patch-8: 17 tokens x 4 layers + patchify + head
        ModelSpec("vit_tiny", vit.vit_tiny, (32, 32, 3), 5.3e6,
                  default_image_size=32, attention=True),
        ModelSpec("inception3", inception.inception_v3, (299, 299, 3), 11.4e9,
                  default_image_size=299),
        ModelSpec("inception4", inception.inception_v4, (299, 299, 3), 24.5e9,
                  default_image_size=299),
        # DeepSpeech2 (tf_cnn's speech member): 2 strided convs + 5x800
        # summed BiGRU + CTC; fwd FLOPs ~= 2*MACs at [300, 161] frames
        ModelSpec("deepspeech2", deepspeech.deepspeech2, (300, 161),
                  1.0e10, ctc=True),
        ModelSpec("deepspeech2_tiny", deepspeech.deepspeech2_tiny,
                  (64, 32), 2.0e7, ctc=True),
        # NCF/NeuMF (tf_cnn's recommendation member, MLPerf ml-20m
        # shape): fwd FLOPs ~= 2*MACs of the MLP tower + fused head
        # (embedding gathers are bandwidth, not MACs)
        ModelSpec("ncf", ncf.ncf, (2,), 2.8e5, integer_input=True),
        ModelSpec("ncf_tiny", ncf.ncf_tiny, (2,), 5.0e3,
                  integer_input=True),
        ModelSpec("bert_base", bert.bert_base_mlm, (128,), 2 * 110e6 * 128,
                  is_text=True),
        ModelSpec("bert_large", bert.bert_large_mlm, (128,), 2 * 335e6 * 128,
                  is_text=True),
        # ~4.5M params, seq 64: CPU-smoke/test variant of the MLM path
        ModelSpec("bert_tiny", bert.bert_tiny_mlm, (64,), 2 * 4.5e6 * 64,
                  is_text=True, vocab_size=1024),
        # decoder family (causal LM; beyond-reference — see models/gpt.py)
        ModelSpec("gpt2", gpt.gpt2, (1024,), 2 * 124e6 * 1024,
                  is_text=True, vocab_size=gpt.GPT2_VOCAB, causal_lm=True),
        ModelSpec("gpt2_medium", gpt.gpt2_medium, (1024,), 2 * 355e6 * 1024,
                  is_text=True, vocab_size=gpt.GPT2_VOCAB, causal_lm=True),
        # sparse MoE decoder: FLOPs figure counts *active* params per token
        # (top-2 of 8 experts ~= 2x FFN of the dense 124M trunk)
        ModelSpec("gpt2_moe", gpt.gpt2_moe, (1024,), 2 * 180e6 * 1024,
                  is_text=True, vocab_size=gpt.GPT2_VOCAB, causal_lm=True,
                  moe=True),
        ModelSpec("moe_tiny", gpt.moe_tiny, (64,), 2 * 3e6 * 64,
                  is_text=True, vocab_size=1024, causal_lm=True, moe=True),
        # modern decoder family: RMSNorm + RoPE + SwiGLU + GQA
        ModelSpec("llama_1b", llama.llama_1b, (2048,), 2 * 1.1e9 * 2048,
                  is_text=True, vocab_size=32000, causal_lm=True),
        # ~0.8M params: embed 131k + untied head 131k + 4 layers x ~136k
        ModelSpec("llama_tiny", llama.llama_tiny, (64,), 2 * 0.8e6 * 64,
                  is_text=True, vocab_size=1024, causal_lm=True),
        # hybrid linear/softmax attention + sigmoid-routed MoE, as one
        # chip's share of an EP8 deployment (serve lane): ~0.81 B
        # parameters multiplied per token of the 3.31 B held
        ModelSpec("solar_open2_250b_ep8", solar_open2.solar_open2_250b_ep8,
                  (2048,), 2 * 0.81e9 * 2048, is_text=True,
                  vocab_size=24576, causal_lm=True),
        ModelSpec("solar_open2_tiny", solar_open2.solar_open2_tiny, (64,),
                  2 * 0.2e6 * 64, is_text=True,
                  vocab_size=solar_open2.TINY["vocab_size"], causal_lm=True),
        # Mamba-2 (SSD) layers beside NoPE GQA one layer in ten, a dense
        # SwiGLU FFN, muP multipliers, a tied head: 3.19 B parameters, all
        # multiplied per token (serve lane)
        ModelSpec("granite_4_0_h_micro", granite4h.granite_4_0_h_micro,
                  (2048,), 2 * 3.19e9 * 2048, is_text=True,
                  vocab_size=100352, causal_lm=True),
        ModelSpec("granite4h_tiny", granite4h.granite4h_tiny, (64,),
                  2 * 0.2e6 * 64, is_text=True,
                  vocab_size=granite4h.TINY["vocab_size"], causal_lm=True),
        # sliding-window and YaRN full attention, 3 : 1, over top-8 of 64
        # softmax-routed SwiGLU experts: a serving stage of two periods
        # (8 of 28 layers), ~0.79 B parameters multiplied per token of the
        # 3.79 B held (serve lane)
        ModelSpec("mellum2_12b_a2_5b_8l", mellum2.mellum2_12b_a2_5b_8l,
                  (2048,), 2 * 0.79e9 * 2048, is_text=True,
                  vocab_size=98304, causal_lm=True),
        ModelSpec("mellum2_tiny", mellum2.mellum2_tiny, (64,),
                  2 * 0.1e6 * 64, is_text=True,
                  vocab_size=mellum2.TINY["vocab_size"], causal_lm=True),
    ]
    return {s.name: s for s in specs}


_ALIASES = {
    "resnet50_v1.5": "resnet50",
    "inception_v3": "inception3",
    "bert": "bert_base",
    "bert-base": "bert_base",
    "lenet5": "lenet",
    "densenet": "densenet40_k12",
    "mobilenet_v1": "mobilenet",
    "inception_v4": "inception4",
    # tf_cnn_benchmarks names the CIFAR family bare resnet<depth>
    "resnet20": "resnet20_cifar",
    "resnet32": "resnet32_cifar",
    "resnet44": "resnet44_cifar",
    "resnet56": "resnet56_cifar",
    "resnet110": "resnet110_cifar",
}


def get_model_spec(name: str) -> ModelSpec:
    reg = _registry()
    key = _ALIASES.get(name.lower(), name.lower())
    if key not in reg:
        raise ValueError(f"unknown model {name!r}; have {sorted(reg)}")
    return reg[key]


def list_models() -> list[str]:
    return sorted(_registry())


def create_model(name: str, num_classes: int = 1000, dtype=jnp.float32,
                 attention_impl: str = "dense", space_to_depth: bool = False,
                 seq_len: int | None = None,
                 gradient_checkpointing: bool = False,
                 moe_impl: str = "einsum", seq_axis: str | None = None,
                 moe_capacity_factor: float = 1.25,
                 fused_conv: bool = False, rnn_impl: str = "hoisted",
                 scan_layers: bool = False, moe_f_chunk: int = 0):
    spec = get_model_spec(name)
    kwargs: dict[str, Any] = {"num_classes": num_classes, "dtype": dtype}
    if getattr(spec, "ctc", False):
        # RNN members: hoisted (input projections batched out of the
        # scan, the round-4 default) vs flax (linen.RNN A/B control)
        kwargs["rnn_impl"] = rnn_impl
    elif rnn_impl != "hoisted":
        raise ValueError(f"--rnn_impl only applies to RNN members, not {name}")
    if spec.moe:
        kwargs["moe_impl"] = moe_impl
        kwargs["moe_capacity_factor"] = moe_capacity_factor
        kwargs["moe_f_chunk"] = moe_f_chunk
    elif moe_impl != "einsum":
        raise ValueError(f"--moe_impl only applies to MoE members, not {name}")
    elif moe_capacity_factor != 1.25:
        raise ValueError(
            f"--moe_capacity_factor only applies to MoE members, not {name}")
    if seq_axis is not None and not spec.is_text:
        raise ValueError(f"--sequence_parallel only applies to text models, "
                         f"not {name}")
    if spec.attention or spec.is_text:  # transformers: kernel + remat knobs
        kwargs["attention_impl"] = attention_impl
        kwargs["remat"] = gradient_checkpointing
    if scan_layers:
        import inspect

        if "scan_layers" not in inspect.signature(spec.create).parameters:
            raise ValueError(
                f"--scan_layers is not supported for {name} (decoder "
                "families only: gpt2*/moe*/llama*)")
        kwargs["scan_layers"] = True
    if spec.is_text:
        kwargs["seq_axis"] = seq_axis
        if seq_len is not None:
            # long-context override: rescale the linear-in-seq FLOP figure
            # (conservative — ignores the quadratic attention term); the
            # factory grows its position table only if seq_len demands it
            kwargs["max_len"] = seq_len
            spec = dataclasses.replace(
                spec, input_shape=(seq_len,),
                flops_per_example=spec.flops_per_example
                * seq_len / spec.input_shape[0],
            )
    else:
        if gradient_checkpointing and not spec.attention:
            raise ValueError(
                "--gradient_checkpointing currently applies to transformer "
                f"members only, not {name}")
        if seq_len is not None:
            raise ValueError(
                f"--seq_len only applies to text models, not {name}")
    if spec.supports_s2d:
        kwargs["space_to_depth"] = space_to_depth
    elif space_to_depth:
        raise ValueError(f"--use_space_to_depth: {name} has no s2d stem")
    if spec.fused_conv:
        kwargs["fused_conv"] = fused_conv
    elif fused_conv:
        raise ValueError(
            f"--fused_conv applies to the v1 bottleneck resnets, not {name}")
    return spec.create(**kwargs), spec

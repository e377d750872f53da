"""Config schema for all architectures + the four assigned input-shape cells.

Each assigned arch gets one file in this package exporting FULL (exact brief
numbers) and SMOKE (reduced, CPU-runnable) configs.  The dry-run, tests, and
benchmarks all consume these dataclasses — there is no other config source.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    dispatch: str = "einsum"        # "einsum" (GShard baseline) | "gather" (sorted, optimized)
    router_aux_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    conv_kernel: int = 4
    expansion: int = 2
    head_dim: int = 64              # P
    n_groups: int = 1
    chunk: int = 64
    # Zamba-style hybrid: a single shared attention block applied every k
    # SSM blocks (0 = pure SSM stack)
    shared_attn_every: int = 0
    shared_attn_lora_rank: int = 0


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora_rank: int = 64
    chunk: int = 32


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Whisper-style encoder (frontend stubbed to precomputed embeddings)."""
    n_layers: int = 32
    n_frames: int = 1500


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                      # dense | moe | rwkv | hybrid | vlm | audio
    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None   # default d_model // n_heads
    act: str = "swiglu"              # swiglu | geglu | gelu
    norm: str = "rms"                # rms | ln
    norm_eps: Optional[float] = None  # None: 1e-6 for rms, 1e-5 for ln
    use_bias: bool = False
    qk_norm: bool = False
    parallel_block: bool = False     # command-r style parallel attn+mlp
    rope_theta: float = 10000.0
    rope_pct: float = 1.0            # stablelm: rotary on 25% of head dim
    tie_embeddings: bool = True
    embed_scale: bool = False        # gemma: x *= sqrt(d)
    logit_softcap: float = 0.0       # grok: 30.0
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    encoder: Optional[EncoderConfig] = None
    n_prefix: int = 0                # vlm: number of (stub) patch-embedding tokens
    dtype: str = "bfloat16"
    # runtime knobs (overridable per run)
    remat: str = "full"              # none | full
    q_chunk: int = 512
    kv_chunk: int = 1024
    loss_chunk: int = 512
    # dry-run cost-mode knobs: XLA cost_analysis counts scan bodies ONCE, so
    # the cost lowering unrolls every scan (at reduced layer count) and uses
    # single-block attention; see launch/dryrun.py
    scan_unroll: bool = False
    attn_full_scores: bool = False
    # logical sharding strategy on the fixed physical mesh:
    #   "2d" — Megatron-style: weights (data x model), TP activations (baseline)
    #   "dp" — pure data parallel + ZeRO: weights replicated, optimizer fully
    #          sharded, batch over every axis.  Right choice for small archs
    #          where TP collectives dominate (see EXPERIMENTS.md §Perf).
    mesh_strategy: str = "2d"
    # decode KV cache dtype: "model" (= dtype) | "int8" (per-token-per-head
    # symmetric quantization; halves decode HBM traffic — hillclimb lever)
    kv_cache_dtype: str = "model"
    # serving weight dtype: "model" (= dtype) | "int8" (block-scaled packed
    # weights, core.quant: decode streams 1 byte/weight + ~3% scale overhead
    # instead of 2-4 — the launch/serve --quantize path; roofline models it)
    weight_dtype: str = "model"

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def jdtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        d, hd = self.d_model, self.hd
        n_attn = d * self.n_heads * hd + 2 * d * self.n_kv * hd + self.n_heads * hd * d
        if self.act in ("swiglu", "geglu"):
            n_mlp = 3 * d * self.d_ff
        else:
            n_mlp = 2 * d * self.d_ff
        if self.family == "rwkv":
            # time-mix: r,k,v,g,o (5 d^2) + decay lora; channel-mix 2*d*d_ff
            per_layer = 5 * d * d + 2 * d * self.rwkv.decay_lora_rank + 2 * d * self.d_ff
            n = self.n_layers * per_layer
        elif self.family == "hybrid":
            s = self.ssm
            d_in = s.expansion * d
            nheads = d_in // s.head_dim
            per_m = (
                d * (2 * d_in + 2 * s.n_groups * s.d_state + nheads)  # in_proj
                + d_in * d                                            # out_proj
                + s.conv_kernel * (d_in + 2 * s.n_groups * s.d_state)  # conv
            )
            n = self.n_layers * per_m
            if s.shared_attn_every:
                n += n_attn + 2 * d * self.d_ff  # one shared block (gelu mlp)
        elif self.family == "moe":
            m = self.moe
            expert = 3 * d * m.d_ff_expert if self.act in ("swiglu", "geglu") else 2 * d * m.d_ff_expert
            per_layer = n_attn + m.num_experts * expert + m.n_shared_experts * expert + d * m.num_experts
            n = self.n_layers * per_layer
        elif self.family == "audio":
            enc_layers = self.encoder.n_layers
            # decoder layers have an extra cross-attention
            n = enc_layers * (n_attn + n_mlp) + self.n_layers * (2 * n_attn + n_mlp)
        else:
            n = self.n_layers * (n_attn + n_mlp)
        n += self.vocab * d
        if not self.tie_embeddings:
            n += self.vocab * d
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k + shared experts only)."""
        if self.family != "moe":
            return self.param_count()
        m = self.moe
        d = self.d_model
        expert = 3 * d * m.d_ff_expert if self.act in ("swiglu", "geglu") else 2 * d * m.d_ff_expert
        n_attn = d * self.n_heads * self.hd + 2 * d * self.n_kv * self.hd + self.n_heads * self.hd * d
        per_layer = n_attn + (m.top_k + m.n_shared_experts) * expert + d * m.num_experts
        return self.n_layers * per_layer + self.vocab * d


# ---------------------------------------------------------------------------
# The four assigned input-shape cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}

#: long_500k requires sub-quadratic attention; per the brief it runs only for
#: SSM/hybrid/linear-attention archs and is skipped (documented) for
#: full-attention archs.
LONG_CONTEXT_ARCHS = ("rwkv6-1.6b", "zamba2-1.2b")


def shape_applicable(arch_id: str, family: str, shape: str) -> bool:
    if shape == "long_500k":
        return arch_id in LONG_CONTEXT_ARCHS
    return True

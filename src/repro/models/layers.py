"""Shared transformer layers.  Every projection routes through core.blas.

All layers are functional: params are nested dicts of jnp arrays, so they
stack cleanly along a leading layer axis for lax.scan and shard via the
path->PartitionSpec rules in launch/sharding.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blas, distributed, quant
from repro.core.act_sharding import constrain


# --------------------------------------------------------------------------
# Weight quantization pass (block-scaled int8 serving weights, core.quant)
# --------------------------------------------------------------------------

#: projection weights the serving quantization pass packs.  Everything else
#: (norm scales, biases, router logits, embedding/unembedding tables) stays
#: full precision: they are tiny, accuracy-critical, or already f32.
QUANT_WEIGHT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
)


def quantize_weights(params: dict, spec: "quant.QuantSpec" = None) -> dict:
    """Replace every projection weight in a params tree with a block-scaled
    int8 `QuantizedTensor` (leading layer-stack dims quantize per layer and
    slice through `lax.scan` untouched).

    Dense/attention 2-D weights (stacked to 3-D by the layer scan) are
    stored output-major (`QuantSpec.transpose`): the decode step consumes
    them as y = W^T x on every token, so packing them in the orientation the
    kernel streams is the layout half of the co-design.  MoE expert stacks
    (an extra expert axis, consumed by batched GEMMs as h @ W per expert)
    keep the GEMM orientation; `models.moe` routes them through
    `batched_gemm`'s packed path.  The returned tree has the same structure,
    so step functions jit against it unchanged.
    """
    spec = spec or quant.QuantSpec(block_m=64, block_n=None, transpose=True)

    def walk(node, in_expert: bool):
        if isinstance(node, dict):
            expert = in_expert or "router" in node
            return {
                k: (walk(v, expert and k != "shared")
                    if isinstance(v, dict)
                    else _quantize_leaf(k, v, spec, expert and k != "shared"))
                for k, v in node.items()
            }
        return node

    return walk(params, False)


def _quantize_leaf(key, leaf, spec: "quant.QuantSpec", in_expert: bool):
    if key not in QUANT_WEIGHT_KEYS or not hasattr(leaf, "ndim"):
        return leaf
    # weight packing happens once, on concrete arrays, at serve startup,
    # one matrix of a stack at a time: validate so a NaN/Inf weight fails
    # loudly HERE, not as a non-finite scale corrupting every decode step
    # (the quantize degenerate contract)
    if in_expert and leaf.ndim >= 3:
        # expert-stacked (.., E, d, f): consumed as a batched GEMM right-hand
        # side — keep the (k, n) orientation, per-expert block scales
        espec = quant.QuantSpec(block_m=spec.block_m, block_n=spec.block_n,
                                transpose=False)
        return quant.pack(leaf, espec)
    return quant.pack(leaf, spec)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def init_norm(d: int, kind: str = "rms", dtype=jnp.bfloat16):
    if kind == "rms":
        return {"scale": jnp.zeros((d,), dtype)}  # stored as (1+scale) offset form
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


#: each norm's epsilon where the config names none (`ModelConfig.norm_eps`)
NORM_EPS = {"rms": 1e-6, "ln": 1e-5}


def apply_norm(params: dict, x: jnp.ndarray, kind: str = "rms",
               eps: Optional[float] = None) -> jnp.ndarray:
    eps = NORM_EPS[kind] if eps is None else eps
    if kind == "rms":
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params["bias"], eps)


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., T, H, hd); positions: (..., T) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., T, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., T, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA; memory-safe chunked softmax; optional prefix-LM mask)
# --------------------------------------------------------------------------

def repeat_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, T, nkv, hd) -> (B, T, nkv*groups, hd)."""
    if groups == 1:
        return k
    b, t, nk, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, nk, groups, hd)).reshape(
        b, t, nk * groups, hd
    )


def _qk_scores(q, k):
    """QK^T as a fused batched GEMM over the B*H batch axis.

    q (B,Tq,H,hd), k (B,Tk,H,hd) -> (B,H,Tq,Tk) f32.  Routing through
    blas.batched_gemm means the pallas backend runs one bgemm launch for all
    heads instead of an opaque einsum.
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    qb = jnp.moveaxis(q.astype(jnp.float32), 2, 1).reshape(b * h, tq, hd)
    kb = jnp.moveaxis(k.astype(jnp.float32), 2, 1).reshape(b * h, tk, hd)
    s = blas.batched_gemm(qb, kb, transpose_b=True)
    return s.reshape(b, h, tq, tk)


def _attn_combine(p, v):
    """PV as a fused batched GEMM: p (B,H,Tq,Tk) f32, v (B,Tk,H,hd)
    -> (B,H,Tq,hd) f32."""
    b, h, tq, tk = p.shape
    hd = v.shape[-1]
    vb = jnp.moveaxis(v.astype(jnp.float32), 2, 1).reshape(b * h, tk, hd)
    out = blas.batched_gemm(p.reshape(b * h, tq, tk), vb)
    return out.reshape(b, h, tq, hd)


def _attend_block(q, k, v, qpos, kpos, causal: bool, prefix_len):
    """q (B,Tq,H,hd), k/v (B,Tk,H,hd) -> scores softmaxed in f32, out (B,Tq,H,hd).

    Used for a single query chunk against a key range; builds the (Tq, Tk)
    score block only.  qpos is (1, Tq) for a shared query offset or (B, Tq)
    when every batch slot sits at its own position (continuous-batching
    decode over a ragged slot grid).
    """
    scale = q.shape[-1] ** -0.5
    s = _qk_scores(q, k) * scale
    if causal:
        m = qpos[:, :, None] >= kpos[None, None, :]
        if prefix_len is not None:
            m = m | (kpos[None, None, :] < prefix_len)
        s = jnp.where(m[:, None], s, -1e30)
    return s


def attention_core(
    q: jnp.ndarray,  # (B, Tq, H, hd)
    k: jnp.ndarray,  # (B, Tk, H, hd)  (already GQA-expanded)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    prefix_len: Optional[int] = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    q_offset: Optional[jnp.ndarray] = None,  # decode: absolute pos of q[0]; (B,) = per-slot
    full_scores: bool = False,
) -> jnp.ndarray:
    """Flash-style attention in pure JAX: lax.scan over q chunks with an inner
    scan over kv chunks keeping online-softmax stats.  Never materializes the
    (Tq, Tk) score matrix — required for the 32k/500k shape cells.
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    offset = q_offset if q_offset is not None else jnp.asarray(tk - tq, jnp.int32)
    # (1, 1) shared offset, or (B, 1) per-slot offsets: every mask below is
    # built from (1|B, Tq) query positions and broadcasts over heads.
    off = jnp.asarray(offset, jnp.int32).reshape(-1, 1)

    if full_scores or tq * tk <= 4096 * 1024:  # small: single block, simplest HLO
        qpos = jnp.arange(tq, dtype=jnp.int32)[None, :] + off
        kpos = jnp.arange(tk, dtype=jnp.int32)
        s = _attend_block(q, k, v, qpos, kpos, causal, prefix_len)
        s = constrain(s, "dp", "tp", None, None)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.moveaxis(_attn_combine(p, v), 1, 2).astype(q.dtype)

    # cdiv chunking with masked final blocks.  Regression note: this used to
    # search for the largest DIVISOR <= the chunk size, which degrades prime
    # tq/tk to chunk size 1 — an 8191-token prompt ran 8191^2 scan steps.
    qc = min(q_chunk, tq)
    kc = min(kv_chunk, tk)
    nq, nk = -(-tq // qc), -(-tk // kc)
    pad_q, pad_k = nq * qc - tq, nk * kc - tk
    if pad_q:
        # fringe query rows compute garbage and are sliced off after the scan
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        # fringe keys are masked out of the scores (kpos < tk below); the V
        # fringe is zero-padded so it cannot poison the accumulator
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    scale = hd ** -0.5
    kpos_all = jnp.arange(nk * kc, dtype=jnp.int32).reshape(nk, kc)
    k_blocks = constrain(k.reshape(b, nk, kc, h, hd), "dp", None, None, "tp", "tp?")
    v_blocks = constrain(v.reshape(b, nk, kc, h, hd), "dp", None, None, "tp", "tp?")

    def q_step(_, q_in):
        qi, qblk = q_in  # index, (B, qc, H, hd)
        qpos = qi * qc + jnp.arange(qc, dtype=jnp.int32)[None, :] + off  # (1|B, qc)
        qf = qblk.astype(jnp.float32) * scale
        # hoist the loop-invariant (B*H, qc, hd) layout of q out of the kv
        # scan; only the per-step k/v blocks get transposed inside it
        qb = jnp.moveaxis(qf, 2, 1).reshape(b * h, qc, hd)

        def kv_step(carry, kv_in):
            m_run, l_run, acc = carry
            ki, kblk, vblk, kpos = kv_in
            kb = jnp.moveaxis(kblk.astype(jnp.float32), 2, 1).reshape(b * h, kc, hd)
            s = blas.batched_gemm(qb, kb, transpose_b=True).reshape(b, h, qc, kc)
            if causal or pad_k:
                mask = None
                if causal:
                    mask = qpos[:, :, None] >= kpos[None, None, :]
                    if prefix_len is not None:
                        mask = mask | (kpos[None, None, :] < prefix_len)
                if pad_k:
                    kmask = (kpos < tk)[None, None, :]
                    mask = kmask if mask is None else mask & kmask
                s = jnp.where(mask[:, None], s, -1e30)
            s = constrain(s, "dp", "tp", None, None)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_run - m_new)
            l_new = alpha * l_run + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha[..., 0][..., None] * acc + _attn_combine(p, vblk)
            return (m_new, l_new, acc), None

        init = (
            constrain(jnp.full((b, h, qc, 1), -1e30, jnp.float32), "dp", "tp"),
            constrain(jnp.zeros((b, h, qc, 1), jnp.float32), "dp", "tp"),
            constrain(jnp.zeros((b, h, qc, hd), jnp.float32), "dp", "tp", None, "tp?"),
        )
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step,
            init,
            (
                jnp.arange(nk, dtype=jnp.int32),
                jnp.moveaxis(k_blocks, 1, 0),
                jnp.moveaxis(v_blocks, 1, 0),
                kpos_all,
            ),
        )
        out = (acc / l_f).astype(q.dtype)  # (B, H, qc, hd)
        return None, constrain(jnp.moveaxis(out, 1, 2), "dp", None, "tp", "tp?")

    q_xs = constrain(
        jnp.moveaxis(q.reshape(b, nq, qc, h, hd), 1, 0), None, "dp", None, "tp", "tp?"
    )
    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq, dtype=jnp.int32), q_xs))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * qc, h, hd)
    return out[:, :tq] if pad_q else out


# --------------------------------------------------------------------------
# GQA attention layer
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    rope_theta: float = 10000.0
    use_bias: bool = False
    causal: bool = True
    use_rope: bool = True
    qk_norm: bool = False
    full_scores: bool = False  # dry-run cost mode: skip chunked scans


def init_attention(key, cfg: AttnConfig, dtype=jnp.bfloat16) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, h * hd)) * std).astype(dtype),
        "wk": (jax.random.normal(k2, (d, kv * hd)) * std).astype(dtype),
        "wv": (jax.random.normal(k3, (d, kv * hd)) * std).astype(dtype),
        "wo": (jax.random.normal(k4, (h * hd, d)) * std).astype(dtype),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _cache_write(buf: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Write `new` (B, T, ...) into `buf` (B, S, ...) at sequence offset `pos`.

    Scalar pos: one slice write at the same offset for every row (prefill and
    batch-at-a-time decode).  (B,) pos: each slot writes at its own position —
    the continuous-batching ragged slot grid.
    """
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(buf, new, (0, pos) + (0,) * (buf.ndim - 2))
    return jax.vmap(
        lambda b_, n_, p_: jax.lax.dynamic_update_slice(b_, n_, (p_,) + (0,) * (b_.ndim - 1))
    )(buf, new, pos)


def _cache_write_kv(bufs: tuple, qt: "quant.QuantizedTensor", pos: jnp.ndarray) -> tuple:
    """Scatter a freshly quantized KV block — packed int8 values AND their
    per-(token, head) scales — into the cache in lockstep.

    `bufs` is (values_buf (B, S, H, hd) int8, scales_buf (B, S, H, 1)); `qt`
    is `quant.quantize_kv`'s output for the new (B, T, H, hd) block.  One
    vmapped scatter writes both leaves at the same ragged per-slot offsets,
    so a value row can never land without its scale (the invariant the
    dequant/flash read paths rely on).
    """
    vbuf, sbuf = bufs
    new = (qt.values.astype(vbuf.dtype), qt.scales.astype(sbuf.dtype))
    if pos.ndim == 0:
        return tuple(
            jax.lax.dynamic_update_slice(b, n, (0, pos) + (0,) * (b.ndim - 2))
            for b, n in zip(bufs, new)
        )
    write = jax.vmap(
        lambda bv, bs, nv, ns, p: (
            jax.lax.dynamic_update_slice(bv, nv, (p,) + (0,) * (bv.ndim - 1)),
            jax.lax.dynamic_update_slice(bs, ns, (p,) + (0,) * (bs.ndim - 1)),
        )
    )
    return write(vbuf, sbuf, *new, pos)


def _paged_write_coords(page_table: jnp.ndarray, pos, t: int,
                        page_size: int) -> tuple:
    """(pages, offs) flat scatter coordinates for writing a (B, T) token
    block through the page table: token (b, i) at logical position pos_b + i
    lands in physical page `page_table[b, (pos_b + i) // page_size]` at row
    `(pos_b + i) % page_size`.  Scalar pos broadcasts (prefill / batch
    decode); (B,) pos is the ragged slot grid.  Dead table entries point at
    the trash page, so frozen inactive slots scatter harmlessly; the clip
    keeps even an at-capacity frozen position in-bounds."""
    b = page_table.shape[0]
    posk = jnp.asarray(pos, jnp.int32).reshape(-1, 1) + jnp.arange(t, dtype=jnp.int32)[None]
    posk = jnp.broadcast_to(posk, (b, t))
    pages = jnp.take_along_axis(
        page_table.astype(jnp.int32), posk // page_size, axis=1, mode="clip")
    return pages.reshape(-1), (posk % page_size).reshape(-1)


def _paged_cache_write(buf: jnp.ndarray, new: jnp.ndarray, pages, offs) -> jnp.ndarray:
    """Scatter `new` (B, T, H, ...) into the page POOL `buf`
    (num_pages, page_size, H, ...) at the flat (pages, offs) coordinates."""
    flat = new.reshape((-1,) + new.shape[2:])
    return buf.at[pages, offs].set(flat.astype(buf.dtype))


def _paged_cache_write_kv(bufs: tuple, qt: "quant.QuantizedTensor",
                          pages, offs) -> tuple:
    """Paged analog of `_cache_write_kv`: packed int8 values AND their
    per-(token, head) scales scatter through the SAME page-table coordinates,
    so a value row can never land in the pool without its scale."""
    vbuf, sbuf = bufs
    return (_paged_cache_write(vbuf, qt.values, pages, offs),
            _paged_cache_write(sbuf, qt.scales, pages, offs))


def _flash_eligible(cfg: "AttnConfig") -> bool:
    """ONE attention engine under the pallas backend: every mask variant
    (causal, prefix-LM, non-causal), both cache dtypes, and GQA lower to
    `ops.flash_attention`; `attention_core` survives only as the xla/ref
    oracle.  The single exception is the dry-run cost mode (full_scores),
    which exists to keep the score matmuls visible to HLO cost analysis."""
    return blas.get_backend() == "pallas" and not cfg.full_scores


def _slot_kv_lens(pos, t: int, b: int) -> jnp.ndarray:
    """(B,) real KV length per slot AFTER this step's write: a scalar pos
    broadcasts, a (B,) per-slot vector is the continuous-batching ragged
    slot grid."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32) + t, (b,))


def _flash_cache_attention(q, kv, vv, pos, t: int, groups: int, *,
                           causal: bool = True, prefix_len=None,
                           ks=None, vs=None, page_table=None):
    """Attention over the KV cache via the flash Pallas kernel.

    q (B, T, H, hd); kv/vv (B, S, KVH, hd) cache buffers — dense bf16/f32,
    or (with ks/vs (B, S, KVH, 1) per-(token, head) scales) PACKED int8
    values dequantized in-kernel at 1 byte/element.  pos is the pre-write
    cache position (scalar, or (B,) for the continuous-batching ragged slot
    grid).  Everything streams in the cache's NATIVE layout — one grid row
    per slot, each block holding all of that slot's heads — so no
    transposed copy of the cache is ever materialized between the scatter
    and the launch; GQA head sharing folds into the kernel's head walk (no
    repeat_kv materialization), per-row real lengths mask the dead capacity
    tail, and
    `causal`/`prefix_len` select the mask in-kernel (satellite fix: the old
    packed path hardcoded causal=True and eligibility-gated everything
    else out to the dequant fallback).

    With `page_table` (B, max_pages) the kv/vv (and ks/vs) operands are the
    paged POOL (num_pages, page_size, KVH, ...), looked up through the table
    in-kernel (a one-token step takes the kernel's decode path) — ragged +
    paged + quantized is still ONE launch.
    """
    lens = _slot_kv_lens(pos, t, q.shape[0])
    from repro.kernels import ops
    out = ops.flash_attention(q, kv, vv, k_scales=ks, v_scales=vs,
                              kv_lens=lens, page_table=page_table,
                              kv_groups=groups, causal=causal,
                              prefix_len=prefix_len)
    return out.astype(q.dtype)


def attention_dispatch(
    q: jnp.ndarray,  # (B, Tq, H, hd)
    k: jnp.ndarray,  # (B, Tk, KVH, hd) — UN-expanded GQA heads
    v: jnp.ndarray,
    *,
    causal: bool = True,
    prefix_len: Optional[int] = None,
    q_offset: Optional[jnp.ndarray] = None,
    groups: int = 1,
    full_scores: bool = False,
) -> jnp.ndarray:
    """The single attention entry point for cache-less operands (training
    forward, encoder self-attention, whisper cross-attention — and the
    dense-cache path, whose buffers are plain arrays too): pallas lowers to
    the flash kernel with the mask folded in-kernel; xla/ref run the
    `attention_core` oracle.  `q_offset` (the pre-write cache position)
    doubles as the real-KV-length seed — flash masks the dead capacity tail
    via per-row kv_lens, the oracle via its causal offset."""
    if blas.get_backend() == "pallas" and not full_scores:
        kv_lens = (None if q_offset is None
                   else _slot_kv_lens(q_offset, q.shape[1], q.shape[0]))
        from repro.kernels import ops
        return ops.flash_attention(
            q, k, v, kv_lens=kv_lens, kv_groups=groups, causal=causal,
            prefix_len=prefix_len,
        ).astype(q.dtype)
    return attention_core(
        q, repeat_kv(k, groups), repeat_kv(v, groups), causal=causal,
        prefix_len=prefix_len, q_offset=q_offset, full_scores=full_scores,
    )


def _live_kv_len(pos, t: int, capacity: int) -> int:
    """Static upper bound on the live KV prefix after this step's write.
    Concrete pos (eager oracle calls) gives the exact bound; a traced pos
    (jit'd serving step) cannot shrink a static slice shape, so it stays at
    capacity — the flash path never pays this, it culls dead key blocks
    in-kernel.  The reduction runs in numpy: inside a trace (e.g. the
    scanned-layers forward) even a concrete pos constant would come back
    from jnp ops as a tracer."""
    if isinstance(pos, jax.core.Tracer):
        return capacity
    return min(capacity, int(np.max(np.asarray(pos))) + t)


def attention_layer(
    params: dict,
    x: jnp.ndarray,  # (B, T, d)
    cfg: AttnConfig,
    *,
    positions: jnp.ndarray,          # (T,) or (B, T) absolute positions of x tokens
    cache: Optional[dict] = None,    # {"k": (B, S, kv, hd), "v": ..., "pos": scalar | (B,)}
    prefix_len: Optional[int] = None,
    residual: Optional[jnp.ndarray] = None,  # (B, T, d) fused into the wo flush
):
    """Returns (out, new_cache).  With a cache, x is the new-token block
    (decode: T == 1) appended at cache["pos"]; a (B,) pos vector appends each
    slot at its own ragged position (continuous batching).  `residual` (the
    transformer block's skip connection) is added inside the output
    projection's fused epilogue, so the returned `out` already includes it."""
    b, t, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim

    if cfg.use_bias:
        # bias adds fused into the projection kernels' accumulator flush:
        # 3 launches / 3 HBM writes instead of 6
        q = blas.matmul_fused(x, params["wq"], bias=params["bq"])
        k = blas.matmul_fused(x, params["wk"], bias=params["bk"])
        v = blas.matmul_fused(x, params["wv"], bias=params["bv"])
    else:
        q = blas.matmul(x, params["wq"])
        k = blas.matmul(x, params["wk"])
        v = blas.matmul(x, params["wv"])
    q = constrain(q.reshape(b, t, h, hd), "dp", None, "tp", "tp?")
    k = constrain(k.reshape(b, t, kv, hd), "dp", None, "tp", "tp?")
    v = constrain(v.reshape(b, t, kv, hd), "dp", None, "tp", "tp?")
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    groups = h // kv
    out = None
    if cache is not None:
        pos = cache["pos"]
        page_table = cache.get("page_table")
        if page_table is not None:
            # paged KV (ISSUE 7): cache["k"]/["v"] are the GLOBAL page pool
            # (num_pages, page_size, KVH, ...) shared by every slot, and the
            # (B, max_pages) table names each slot's logical key blocks.
            # Writes scatter through the table (values + scales in lockstep
            # for int8); the flash read does the same lookup inside its KV
            # index map, so ragged + paged + quantized stays ONE launch.
            page_size = cache["k"].shape[1]
            capacity = page_table.shape[1] * page_size
            pages, offs = _paged_write_coords(page_table, pos, t, page_size)
            quantized = cache["k"].dtype == jnp.int8
            if quantized:
                kq, vq = quant.quantize_kv(k), quant.quantize_kv(v)
                ck, cks = _paged_cache_write_kv(
                    (cache["k"], cache["k_scale"]), kq, pages, offs)
                cv, cvs = _paged_cache_write_kv(
                    (cache["v"], cache["v_scale"]), vq, pages, offs)
                new_cache = {"k": ck, "v": cv, "k_scale": cks,
                             "v_scale": cvs, "pos": pos + t}
            else:
                ck = _paged_cache_write(cache["k"], k, pages, offs)
                cv = _paged_cache_write(cache["v"], v, pages, offs)
                cks = cvs = None
                new_cache = {"k": ck, "v": cv, "pos": pos + t}
            if _flash_eligible(cfg):
                out = _flash_cache_attention(q, ck, cv, pos, t, groups,
                                             causal=cfg.causal,
                                             prefix_len=prefix_len,
                                             ks=cks, vs=cvs,
                                             page_table=page_table)
            else:
                # xla/ref fallback: gather the LIVE pages only — the pool
                # holds every slot's pages, so reading it whole would scale
                # fallback bytes with POOL capacity instead of live tokens
                # (satellite fix; the ratio guard pins exactly that)
                live = _live_kv_len(pos, t, capacity)
                n_live = -(-live // page_size)
                gathered = n_live * page_size
                ratio = quant.paged_fallback_byte_ratio(
                    live, gathered, hd, packed=quantized)
                bound = quant.paged_fallback_byte_ratio(
                    live, live + page_size - 1, hd, packed=quantized)
                assert ratio <= bound, (
                    f"paged fallback gathered {gathered} tokens for "
                    f"{live} live ones (page_size={page_size}): bytes must "
                    f"scale with live tokens, never the pool"
                )
                pts = page_table[:, :n_live].astype(jnp.int32)

                def gather(pool):
                    return pool[pts].reshape((b, gathered) + pool.shape[2:])

                if quantized:
                    k_full = quant.dequantize_kv(
                        gather(ck)[:, :live], gather(cks)[:, :live], x.dtype)
                    v_full = quant.dequantize_kv(
                        gather(cv)[:, :live], gather(cvs)[:, :live], x.dtype)
                else:
                    k_full = gather(ck)[:, :live]
                    v_full = gather(cv)[:, :live]
            q_offset = pos
        elif cache["k"].dtype == jnp.int8:
            # int8 KV cache: block-scaled packed storage (core.quant
            # per-(token, head) scales), values + scales scattered in
            # lockstep.  Halves the decode-cell attention byte term (§Perf).
            kq, vq = quant.quantize_kv(k), quant.quantize_kv(v)
            ck, cks = _cache_write_kv((cache["k"], cache["k_scale"]), kq, pos)
            cv, cvs = _cache_write_kv((cache["v"], cache["v_scale"]), vq, pos)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs, "pos": pos + t}
            if _flash_eligible(cfg):
                # pallas: the flash kernel streams the PACKED int8 tiles and
                # dequantizes in-kernel — the cache is never expanded to
                # full precision in HBM, GQA head sharing happens in the
                # kernel's index map (no repeat_kv materialization), and the
                # mask (causal / prefix-LM / non-causal) folds in-kernel
                out = _flash_cache_attention(q, ck, cv, pos, t, groups,
                                             causal=cfg.causal,
                                             prefix_len=prefix_len,
                                             ks=cks, vs=cvs)
            else:
                # xla/ref: exact dequantization oracle semantics — over the
                # LIVE prefix only (satellite fix: dequantizing the full
                # capacity-S buffer cost more HBM bytes than the bf16 cache
                # the int8 path replaced)
                live = _live_kv_len(pos, t, ck.shape[1])
                ratio = quant.kv_fallback_byte_ratio(live, ck.shape[1], hd)
                assert ratio <= 1.0, (
                    f"int8-KV fallback dequant would stream {ratio:.2f}x the "
                    f"bytes of the bf16 cache it replaced "
                    f"(live={live}, capacity={ck.shape[1]}, head_dim={hd})"
                )
                k_full = quant.dequantize_kv(ck[:, :live], cks[:, :live], x.dtype)
                v_full = quant.dequantize_kv(cv[:, :live], cvs[:, :live], x.dtype)
        else:
            ck = _cache_write(cache["k"], k.astype(cache["k"].dtype), pos)
            cv = _cache_write(cache["v"], v.astype(cache["v"].dtype), pos)
            new_cache = {"k": ck, "v": cv, "pos": pos + t}
            if _flash_eligible(cfg):
                # pallas: the flash kernel streams the dense cache buffer
                # untouched (native layout, no slice/copy) and masks the
                # dead capacity tail via per-row kv_lens
                out = _flash_cache_attention(q, ck, cv, pos, t, groups,
                                             causal=cfg.causal,
                                             prefix_len=prefix_len)
            else:
                # oracle fallback reads only the live prefix: the causal
                # offset hides the dead tail anyway, but a NON-causal cached
                # launch would otherwise attend stale capacity rows
                live = _live_kv_len(pos, t, ck.shape[1])
                k_full, v_full = ck[:, :live], cv[:, :live]
        q_offset = pos
    else:
        k_full, v_full = k, v
        q_offset = None

    if out is None:
        out = attention_dispatch(
            q, k_full, v_full, causal=cfg.causal, prefix_len=prefix_len,
            q_offset=q_offset, groups=groups, full_scores=cfg.full_scores,
        )
    # residual (the block's skip connection) fuses into the output
    # projection's flush: attn-out + residual is one HBM write.  Under TP
    # serving this is the attention layer boundary: local heads contract
    # against the wo shard and ONE psum reduces across members, with the
    # residual added after the reduction.
    out = out.reshape(b, t, h * hd)
    if distributed.tp_active():
        out = distributed.row_parallel_fused(out, params["wo"],
                                             residual=residual)
    else:
        out = blas.matmul_fused(out, params["wo"], residual=residual)
    return out, new_cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, kind: str = "swiglu", dtype=jnp.bfloat16, use_bias=False) -> dict:
    ks = jax.random.split(key, 3)
    std = d ** -0.5
    if kind in ("swiglu", "geglu"):
        p = {
            "w_gate": (jax.random.normal(ks[0], (d, d_ff)) * std).astype(dtype),
            "w_up": (jax.random.normal(ks[1], (d, d_ff)) * std).astype(dtype),
            "w_down": (jax.random.normal(ks[2], (d_ff, d)) * (d_ff ** -0.5)).astype(dtype),
        }
    else:  # gelu / relu two-matrix MLP
        p = {
            "w_up": (jax.random.normal(ks[0], (d, d_ff)) * std).astype(dtype),
            "w_down": (jax.random.normal(ks[1], (d_ff, d)) * (d_ff ** -0.5)).astype(dtype),
        }
        if use_bias:
            p["b_up"] = jnp.zeros((d_ff,), dtype)
            p["b_down"] = jnp.zeros((d,), dtype)
    return p


def mlp(params: dict, x: jnp.ndarray, kind: str = "swiglu",
        residual: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """MLP forward with every epilogue fused into the GEMM flush.

    SwiGLU/GEGLU is the dual-GEMM form: silu(x@Wg) * (x@Wu) is ONE
    `matmul_fused` launch (two accumulators, gate multiply in the epilogue)
    instead of two GEMMs + an elementwise kernel, and the down projection
    carries the optional block residual — 2 HBM output writes per MLP where
    the unfused chain made 4-5.  `residual` (the transformer block's skip
    connection) is included in the returned value when given.
    """
    if kind in ("swiglu", "geglu"):
        act = "silu" if kind == "swiglu" else "gelu"
        mid = blas.matmul_fused(
            x, params["w_gate"], w2=params["w_up"], activation=act
        )
        mid = constrain(mid, "dp", None, "tp")
        # TP serving: local FFN slice -> row-parallel down projection, the
        # MLP layer boundary's single psum (residual post-reduction)
        if distributed.tp_active():
            return distributed.row_parallel_fused(mid, params["w_down"],
                                                  residual=residual)
        return blas.matmul_fused(mid, params["w_down"], residual=residual)
    # plain gelu MLP (whisper-style, with bias): bias+gelu fuse into the up
    # projection, bias+residual into the down projection
    hdn = blas.matmul_fused(
        x, params["w_up"], bias=params.get("b_up"), activation="gelu"
    )
    if distributed.tp_active():
        return distributed.row_parallel_fused(
            hdn, params["w_down"], bias=params.get("b_down"),
            residual=residual)
    return blas.matmul_fused(
        hdn, params["w_down"], bias=params.get("b_down"), residual=residual
    )


# --------------------------------------------------------------------------
# Embedding / head
# --------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d)) * (d ** -0.5)).astype(dtype)}


def embed(params: dict, tokens: jnp.ndarray, scale: bool = False) -> jnp.ndarray:
    out = jnp.take(params["table"], tokens, axis=0)
    if scale:
        out = out * jnp.asarray(math.sqrt(out.shape[-1]), out.dtype)
    return out


def unembed(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Tied head: logits = x @ table^T (f32 accumulate)."""
    return jnp.einsum(
        "btd,vd->btv", x, params["table"], preferred_element_type=jnp.float32
    )


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """logits (B, T, V) f32, labels (B, T) int32 -> scalar mean loss."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)

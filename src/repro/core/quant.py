"""Block-scaled int8 weight quantization: the bandwidth lever for L1/L2 BLAS.

The paper's central measurement is that GEMV-class ops are bandwidth-bound —
off-the-shelf hardware reaches 5-7% of peak on XGEMV while GEMM reaches
15-57% — and our own BENCH_kernels.json reproduces it (gemm ~112 GFLOP/s,
gemv ~6).  Every A element is touched once, so the only remaining lever is
moving fewer bytes.  This module provides that lever: symmetric block-scaled
int8 quantization of weight matrices, streamed packed through the kernels
and dequantized on the fly against the existing f32 accumulator
(W8A16-style), quartering (vs f32) or halving (vs bf16) the HBM weight
traffic of the O(1)-reuse decode path.

Layout co-design: a serving weight W (d, f) is consumed as y = W^T x on
every decode step.  `QuantSpec.transpose=True` stores the packed values in
(f, d) "output-major" order at quantization time, so the decode kernels
stream the weight exactly as it sits in HBM (no transpose_a remapping and no
per-step materialized W.T), and the host fast path can hit the contiguous
int8 matvec.  Logical shape bookkeeping (`QuantizedTensor.shape`) stays in
the original (d, f) orientation, so callers are layout-blind.

Numerics: per-(block_m, block_n) f32 scale s = max|block| / 127, values
round-to-nearest-even int8.  The elementwise error is bounded by s/2, which
makes matvec error rigorously boundable per output row — see
`matvec_error_bound`; tests assert the bound across dtypes and backends.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

INT8_MAX = 127.0

#: longest contraction the host (XLA:CPU) int8 dot stays on its fast emitted
#: loop for on this class of host; past it the int8 path degrades badly and
#: the dequantization fallback is faster (measured, see bench_quantized)
HOST_FAST_MAX_K = 2048


def _fit_block(block: Optional[int], dim: int) -> int:
    """Largest divisor of `dim` that is <= block (None -> dim itself).

    Quantization blocks must tile the matrix exactly; shrinking to the
    nearest divisor keeps `quantize` total on awkward (prime, padded) dims
    at the cost of more scales, never at the cost of correctness.
    """
    if block is None or block >= dim:
        return dim
    b = max(1, block)
    while dim % b:
        b -= 1
    return b


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Static description of a block-scaled quantization.

    block_m/block_n are the scale-block extents over the STORED layout's
    rows/cols (None = the whole extent: one scale spanning that axis).
    transpose=True stores values as logical.T — the decode/HBM layout (see
    module docstring).
    """

    block_m: Optional[int] = 64
    block_n: Optional[int] = None
    dtype: str = "int8"
    transpose: bool = False

    def __post_init__(self):
        if self.dtype != "int8":
            raise ValueError(f"only int8 quantization is supported, got {self.dtype!r}")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Packed int8 values + per-block f32 scales, a jit/scan-friendly pytree.

    values: (..., M, N) int8 in STORED orientation (transposed=True means
            stored = logical.T over the last two dims);
    scales: (..., M/qm, N/qn) f32, one per (qm, qn) block of `values`;
    block:  (qm, qn) static;
    transposed: static layout marker.

    Leading dims are free: a layer-stacked (L, f, d) weight or an
    expert-stacked (E, d, f) MoE weight quantizes in one shot and slices
    through `lax.scan`/vmap like any other pytree (aux data is static).
    """

    values: jnp.ndarray
    scales: jnp.ndarray
    block: Tuple[int, int]
    transposed: bool = False

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.values, self.scales), (self.block, self.transposed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, scales = children
        block, transposed = aux
        return cls(values=values, scales=scales, block=block, transposed=transposed)

    # -- shape bookkeeping -------------------------------------------------
    @property
    def stored_shape(self) -> tuple:
        return self.values.shape

    @property
    def shape(self) -> tuple:
        """LOGICAL shape (transpose undone), matching the array it replaces."""
        s = self.values.shape
        if self.transposed:
            return s[:-2] + (s[-1], s[-2])
        return s

    @property
    def ndim(self) -> int:
        return self.values.ndim

    @property
    def packed_itemsize(self) -> int:
        return self.values.dtype.itemsize

    # -- numerics ----------------------------------------------------------
    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        """Exact W8A16 semantics: values * per-block scale, in LOGICAL
        orientation.  This is the oracle every backend's quantized output is
        tested against."""
        out = _expand_scales(self.scales, self.block, self.values.shape) * self.values.astype(
            jnp.float32
        )
        if self.transposed:
            out = jnp.swapaxes(out, -2, -1)
        return out.astype(dtype)

    def elementwise_bound(self) -> jnp.ndarray:
        """Per-element |x - dequantize| upper bound (scale/2), full shape,
        LOGICAL orientation."""
        b = _expand_scales(self.scales, self.block, self.values.shape) * 0.5
        return jnp.swapaxes(b, -2, -1) if self.transposed else b


def _expand_scales(scales: jnp.ndarray, block: Tuple[int, int], shape: tuple) -> jnp.ndarray:
    """(..., sm, sn) block scales -> (..., m, n) per-element scales."""
    qm, qn = block
    m, n = shape[-2:]
    lead = shape[:-2]
    s = jnp.broadcast_to(
        scales[..., :, None, :, None],
        lead + (m // qm, qm, n // qn, qn),
    )
    return s.reshape(shape)


@jax.jit
def _all_finite(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.isfinite(x).all()


def quantize(x: jnp.ndarray, spec: QuantSpec = QuantSpec(),
             validate: bool = False) -> QuantizedTensor:
    """Symmetric per-block int8 quantization over the last two dims.

    Leading dims are treated as independent matrices (layer/expert stacks).

    Degenerate-input contract (the robustness guarantees tests pin):

    - **All-zero blocks** get scale 0 and quantize to exact zeros; dequant
      reproduces exact zeros.  No division by zero anywhere: the inverse
      scale is computed through ``1 / max(scale, 1e-30)`` and masked to 0
      for zero scales.
    - **Subnormal-max blocks** (``0 < max|block| < ~1e-38``) produce a
      finite (possibly zero, if ``amax / 127`` underflows) scale and finite
      values — the round/clip pipeline bounds every value in [-127, 127]
      even when the intermediate product overflows.
    - **NaN/Inf inputs** PROPAGATE to the block's scale (NaN in -> NaN
      scale, Inf in -> Inf scale; the packed values of such a block are
      unspecified), so a downstream scale-finiteness check — the serving
      invariant in `launch.faults` — always detects the corruption; nothing
      silently launders a non-finite weight into a plausible scale.  With
      ``validate=True`` (concrete inputs only; `pack`, which packs the
      serving weights at start-up, always validates) non-finite inputs
      raise ``ValueError`` up front instead;
      traced inputs cannot be validated and always use the propagate path.
    """
    if x.ndim < 2:
        raise ValueError(f"quantize needs a matrix, got shape {x.shape}")
    if validate and not isinstance(x, jax.core.Tracer):
        if not bool(_all_finite(x)):
            raise ValueError(
                "quantize(validate=True): input contains NaN/Inf — refusing "
                "to pack a corrupt tensor (the scale would be non-finite)")
    if spec.transpose:
        x = jnp.swapaxes(x, -2, -1)
    m, n = x.shape[-2:]
    qm, qn = _fit_block(spec.block_m, m), _fit_block(spec.block_n, n)
    lead = x.shape[:-2]
    xb = x.astype(jnp.float32).reshape(lead + (m // qm, qm, n // qn, qn))
    amax = jnp.max(jnp.abs(xb), axis=(-3, -1))                      # (..., sm, sn)
    scales = amax / INT8_MAX
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    q = jnp.round(xb * inv[..., :, None, :, None])
    values = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8).reshape(x.shape)
    return QuantizedTensor(values=values, scales=scales, block=(qm, qn),
                           transposed=spec.transpose)


@functools.partial(jax.jit, static_argnums=3, donate_argnums=0)
def _pack_into(packed: QuantizedTensor, x: jnp.ndarray, i: jnp.ndarray,
               spec: QuantSpec) -> QuantizedTensor:
    """`packed` with matrix `i` of the stack `x` (leading dims flattened)
    quantized into its place."""
    flat = lambda a: a.reshape((-1,) + a.shape[-2:])
    qt = quantize(flat(x)[i], spec)
    return QuantizedTensor(
        values=flat(packed.values).at[i].set(qt.values).reshape(
            packed.values.shape),
        scales=flat(packed.scales).at[i].set(qt.scales).reshape(
            packed.scales.shape),
        block=qt.block, transposed=qt.transposed)


def pack(x: jnp.ndarray, spec: QuantSpec = QuantSpec()) -> QuantizedTensor:
    """`quantize(x, spec, validate=True)` for a weight, one matrix of its
    leading (layer or expert) dims at a time, under one compiled call per
    shape: the float32 temporaries span one matrix, not the stack, so a
    stack that fills most of the device packs beside itself.  Values and
    scales are bitwise those of `quantize`."""
    if not isinstance(x, jax.core.Tracer) and not bool(_all_finite(x)):
        raise ValueError(
            "pack: input contains NaN/Inf — refusing to pack a corrupt "
            "tensor (the scale would be non-finite)")
    if x.ndim <= 2:
        return quantize(x, spec)
    # a loop inside one program would not do: the compiler hoists the
    # float32 cast of the whole stack out of it
    packed = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                          jax.eval_shape(lambda a: quantize(a, spec), x))
    for i in range(math.prod(x.shape[:-2])):
        packed = _pack_into(packed, x, jnp.int32(i), spec)
    return packed


def scales_finite(qt: QuantizedTensor) -> bool:
    """The quant-scale finiteness invariant: True iff every block scale is
    finite.  A False here means a NaN/Inf input was quantized somewhere
    upstream (see the `quantize` degenerate-input contract)."""
    return bool(jnp.isfinite(qt.scales).all())


def is_quantized(x) -> bool:
    return isinstance(x, QuantizedTensor)


# --------------------------------------------------------------------------
# Error bounds (the documented accuracy contract)
# --------------------------------------------------------------------------

def matvec_error_bound(qt: QuantizedTensor, x: jnp.ndarray,
                       activation_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Rigorous per-output bound for |op(W_q) x - op(W) x|, W the logical 2-D
    matrix behind `qt`, computing y = W^T x if qt.transposed is the serving
    layout (i.e. always y over the STORED row axis: y = values' logical op
    such that output dim = stored rows).

    For the exact-dequant (W8A16) paths the bound per output row i is

        err_i <= sum_b  s[i_blk, b] / 2 * sum_{j in b} |x_j|

    (|w - w_hat| <= s/2 elementwise).  With `activation_scales` sx (one per
    stored column block — the host W8A8 fast path), two more terms appear:
    |w_hat| * sx/2 for the activation rounding against the dequantized
    weight, and s*sx/4 for the cross term:

        err_i <= sum_b [ s[i,b]/2 * L1(x_b) + sx_b/2 * L1(w_hat[i, b]) +
                         s[i,b] * sx_b / 4 * n_b ]

    Returns the (m,) bound over stored rows (= the GEMV output axis).
    """
    if qt.values.ndim != 2:
        raise ValueError("matvec_error_bound covers 2-D quantized matrices")
    m, n = qt.values.shape
    qm, qn = qt.block
    sm, sn = qt.scales.shape
    l1 = jnp.sum(jnp.abs(x.astype(jnp.float32)).reshape(sn, qn), axis=1)   # (sn,)
    bound_blk = 0.5 * qt.scales * l1[None, :]                              # (sm, sn)
    if activation_scales is not None:
        sx = activation_scales.astype(jnp.float32).reshape(sn)
        # per-row L1 of the dequantized weight within each column block
        w_row_l1 = (
            jnp.sum(jnp.abs(qt.values.astype(jnp.float32)).reshape(m, sn, qn), axis=2)
            * jnp.repeat(qt.scales, qm, axis=0)
        )                                                                  # (m, sn)
        extra = 0.5 * w_row_l1 * sx[None, :] + 0.25 * jnp.repeat(
            qt.scales, qm, axis=0
        ) * sx[None, :] * qn
        return jnp.repeat(jnp.sum(bound_blk, axis=1), qm) + jnp.sum(extra, axis=1)
    return jnp.repeat(jnp.sum(bound_blk, axis=1), qm)                      # (m,)


# --------------------------------------------------------------------------
# KV-cache quantization (per-(token, head) block scales)
# --------------------------------------------------------------------------
#
# The decode roofline has exactly two large byte terms: the weight stream
# (packed above) and the KV cache read inside attention.  The KV analog of
# the weight spec is one scale per (token, head): a cache entry (..., T, H,
# hd) quantizes its last two dims (H, hd) with block (1, hd), so scales are
# (..., T, H, 1) and every flash-attention key/value tile dequantizes with a
# single per-row multiply against the f32 softmax accumulator.  Scales stay
# f32 so the elementwise s/2 bound holds exactly (a rounded scale would add
# a 127*s*2^-8 term that is the same order as the bound itself).

#: the per-(token, head) KV spec: one scale per head-vector
KV_SPEC = QuantSpec(block_m=1, block_n=None)


def quantize_kv(x: jnp.ndarray) -> QuantizedTensor:
    """Per-(token, head) symmetric int8 quantization of a K or V block.

    x is (..., H, hd) — typically (B, T, H, hd): every leading dim is
    independent, so one call quantizes a whole written block and the values
    and scales scatter into the cache in lockstep.  Returns a
    `QuantizedTensor` with values (..., H, hd) int8 and scales (..., H, 1)
    f32, block (1, hd).
    """
    return quantize(x, KV_SPEC)


def dequantize_kv(values: jnp.ndarray, scales: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Exact dequantization of packed KV storage: values (..., H, hd) int8 *
    scales (..., H, 1) — the oracle semantics every attention backend is
    tested against."""
    return (values.astype(jnp.float32) * scales.astype(jnp.float32)).astype(dtype)


def attention_error_bound(
    q: jnp.ndarray,         # (BH, Tq, D) f32 — the UNQUANTIZED queries
    k_scales: jnp.ndarray,  # (BHkv, Tk, 1) f32 per-(token, head) key scales
    v_hat: jnp.ndarray,     # (BHkv, Tk, D) f32 DEQUANTIZED values
    v_scales: jnp.ndarray,  # (BHkv, Tk, 1) f32 value scales
    *,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Rigorous per-output bound for |attn(q, K_q, V_q) - attn(q, K, V)|.

    Write p for the exact softmax weights and p' for the weights computed
    from the quantized keys.  Each logit moves by at most

        eps_i = scale * L1(q_i) * max_j s_k[j] / 2

    (|k - k_hat| <= s_k/2 elementwise), so p'_j / p_j in [e^-2eps, e^2eps]
    and ||p' - p||_1 <= 2 (e^{2 eps_i} - 1).  The output error then splits
    as sum_j p'_j (v'_j - v_j) + sum_j (p'_j - p_j) v_j:

        err_{i,d} <= max_j s_v[j]/2
                     + 2 (e^{2 eps_i} - 1) * max_j (|v_hat[j,d]| + s_v[j]/2)

    (the v_j in the second term is bounded through the dequantized values).
    The maxima run over ALL keys, which upper-bounds any causal/length
    mask's visible subset.  Returns the (BH, Tq, D) bound; GQA-shared K/V
    (BHkv < BH) broadcast per query-head group.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    groups = q.shape[0] // k_scales.shape[0]
    qf = q.astype(jnp.float32)
    sk = jnp.repeat(k_scales.astype(jnp.float32), groups, axis=0)   # (BH, Tk, 1)
    sv = jnp.repeat(v_scales.astype(jnp.float32), groups, axis=0)
    vh = jnp.repeat(jnp.abs(v_hat.astype(jnp.float32)), groups, axis=0)
    eps = scale * jnp.sum(jnp.abs(qf), axis=-1) * jnp.max(sk[..., 0], axis=-1, keepdims=True) / 2.0
    p_l1 = 2.0 * (jnp.exp(2.0 * eps) - 1.0)                         # (BH, Tq)
    v_term = jnp.max(vh + sv / 2.0, axis=1)                         # (BH, D)
    sv_max = jnp.max(sv[..., 0], axis=-1)                           # (BH,)
    return (sv_max[:, None, None] / 2.0
            + p_l1[..., None] * v_term[:, None, :])


def packed_kv_bytes(tokens: int, heads: int, head_dim: int,
                    scale_bytes: int = 4) -> int:
    """HBM bytes of one K or V stream over `tokens` cache entries: 1 byte per
    element plus one scale per (token, head)."""
    return tokens * heads * (head_dim + scale_bytes)


def kv_traffic_ratio(head_dim: int, *, full_bytes_per_elem: int = 2,
                     scale_bytes: int = 4) -> float:
    """full-precision KV bytes / packed bytes — the structural claim of the
    int8 KV cache (~1.9x vs bf16 at hd=64)."""
    return full_bytes_per_elem * head_dim / (head_dim + scale_bytes)


def kv_fallback_byte_ratio(live_tokens: int, capacity: int, head_dim: int,
                           *, full_bytes_per_elem: float = 2.0,
                           scale_bytes: int = 4) -> float:
    """Bytes the exact-dequant fallback streams per K/V head-vector, relative
    to what a full-precision cache of the same CAPACITY would have streamed:
    (packed reads + one scale per (token, head)) over the live prefix vs
    `full_bytes_per_elem` per element over the capacity buffer.  The guard
    the int8 fallback asserts — dequantizing the whole capacity-S buffer
    (live_tokens == capacity, plus the expansion write) silently costs MORE
    HBM traffic than the bf16 cache the int8 path replaced; slicing to the
    live prefix keeps the ratio <= 1 whenever live <= capacity *
    traffic_ratio."""
    packed = live_tokens * (head_dim + scale_bytes)
    full = capacity * head_dim * full_bytes_per_elem
    return packed / full


def paged_fallback_byte_ratio(live_tokens: int, gathered_tokens: int,
                              head_dim: int, *, packed: bool = False,
                              full_bytes_per_elem: float = 2.0,
                              scale_bytes: int = 4) -> float:
    """Bytes the PAGED xla/ref fallback streams per K/V head-vector, relative
    to a full-precision read of exactly the LIVE prefix.  `gathered_tokens`
    is page_size * n_pages_gathered — the tokens the pool gather actually
    touches.  The guard the paged fallback asserts: gathering the whole pool
    (gathered ~ pool capacity) makes this ratio grow with POOL size, while a
    live-pages-only gather bounds it by one partial page of over-read,
    ratio <= paged_fallback_byte_ratio(live, live + page_size - 1, ...) —
    i.e. fallback bytes scale with live tokens, never with pool capacity."""
    per_tok = (head_dim + scale_bytes) if packed else (
        head_dim * full_bytes_per_elem)
    full = max(1, live_tokens) * head_dim * full_bytes_per_elem
    return gathered_tokens * per_tok / full


# --------------------------------------------------------------------------
# Traffic model (what packing buys, in HBM bytes — asserted structurally)
# --------------------------------------------------------------------------

def packed_weight_bytes(shape: tuple, block: Tuple[int, int] = (64, None)) -> int:
    """HBM bytes of an int8 block-scaled weight: 1 byte/element + one f32
    scale per (qm, qn) block."""
    m, n = shape[-2:]
    lead = 1
    for d in shape[:-2]:
        lead *= d
    qm, qn = _fit_block(block[0], m), _fit_block(block[1], n)
    return lead * (m * n + (m // qm) * (n // qn) * 4)


def weight_traffic_ratio(shape: tuple, *, full_bytes_per_elem: int = 4,
                         block: Tuple[int, int] = (64, None)) -> float:
    """full-precision weight bytes / packed bytes — the structural claim the
    quantized bench asserts (>= 2x vs bf16, ~3.97x vs f32 at default blocks)."""
    m, n = shape[-2:]
    lead = 1
    for d in shape[:-2]:
        lead *= d
    full = lead * m * n * full_bytes_per_elem
    return full / packed_weight_bytes(shape, block)


# --------------------------------------------------------------------------
# Host fast path: contiguous int8 matvec (the CPU analog of int8 streaming)
# --------------------------------------------------------------------------

def host_fast_path_eligible(qt: QuantizedTensor) -> bool:
    """The XLA host backend has one genuinely fast int8 form: a contiguous
    (m, n) @ (n,) int8 dot (row-major streaming, exactly the bandwidth-bound
    access pattern) with a short-enough contraction (`HOST_FAST_MAX_K`).
    Per-row-block scales (a single column block) let the whole contraction
    run packed and apply scales on the (m,) result."""
    return (qt.values.ndim == 2 and qt.scales.shape[-1] == 1
            and qt.values.shape[-1] <= HOST_FAST_MAX_K)


@jax.jit
def quantize_activation(x: jnp.ndarray):
    """Dynamic symmetric per-call activation quantization: (x8, sx).

    Runs under jit, so the NaN/Inf contract is the propagate half of
    `quantize`'s: a non-finite activation yields a non-finite `sx` (never a
    silently plausible scale), which the serve-time finiteness invariant
    (`launch.faults.check_cache_finite` / --check-invariants) detects."""
    xf = x.astype(jnp.float32)
    sx = jnp.max(jnp.abs(xf)) / INT8_MAX
    inv = jnp.where(sx > 0, 1.0 / jnp.maximum(sx, 1e-30), 0.0)
    x8 = jnp.clip(jnp.round(xf * inv), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return x8, sx


@functools.partial(jax.jit, static_argnames=("qm",))
def _qdot(values, scales_col, x8, sx, *, qm: int):
    p = jnp.dot(values, x8, preferred_element_type=jnp.int32)              # (m,)
    return p.astype(jnp.float32) * (jnp.repeat(scales_col, qm) * sx)


def gemv_host(qt: QuantizedTensor, x: jnp.ndarray) -> jnp.ndarray:
    """y = values @ x over the stored layout via one int8 dot (W8A8-dynamic).

    The activation is quantized per call with a single symmetric scale; the
    int32 partials are rescaled by (weight row-block scale * activation
    scale).  This reads 1 byte/weight instead of 4 — the measured >=1.5x
    GEMV/decode win on bandwidth-bound shapes (bench_quantized.py).  The
    extra activation-rounding error is covered by `matvec_error_bound(...,
    activation_scales=)`; exact W8A16 semantics are available via
    `dequantize()` and are what the Pallas kernels implement in-kernel.

    Eager calls split into two XLA dispatches so x8 is a *parameter* of the
    dot program: XLA:CPU otherwise fuses the whole quantization chain into
    the dot's operand loop and recomputes it per output row, burning most of
    the bandwidth win (measured ~2.5x overhead).  Traced calls (inside an
    outer jit, e.g. a decode step) cannot split and accept the fused form.
    """
    if not host_fast_path_eligible(qt):
        raise ValueError(
            "gemv_host needs a 2-D tensor with per-row-block scales and "
            f"contraction <= {HOST_FAST_MAX_K}"
        )
    qm = qt.block[0]
    if isinstance(x, jax.core.Tracer) or isinstance(qt.values, jax.core.Tracer):
        xf = x.astype(jnp.float32)
        sx = jnp.max(jnp.abs(xf)) / INT8_MAX
        inv = jnp.where(sx > 0, 1.0 / jnp.maximum(sx, 1e-30), 0.0)
        x8 = jnp.clip(jnp.round(xf * inv), -INT8_MAX, INT8_MAX).astype(jnp.int8)
        p = jnp.dot(qt.values, x8, preferred_element_type=jnp.int32)
        return p.astype(jnp.float32) * (jnp.repeat(qt.scales[:, 0], qm) * sx)
    x8, sx = quantize_activation(x)
    return _qdot(qt.values, qt.scales[:, 0], x8, sx, qm=qm)


def activation_scale(x: jnp.ndarray) -> jnp.ndarray:
    """The per-call activation scale `gemv_host` uses (for error bounds)."""
    return jnp.max(jnp.abs(x.astype(jnp.float32))) / INT8_MAX


# --------------------------------------------------------------------------
# Lockstep sharding (ISSUE 10 — tensor-parallel packed weights)
# --------------------------------------------------------------------------

def align_blocks_for_sharding(qt: QuantizedTensor, shards: int,
                              dim: int = 0) -> QuantizedTensor:
    """Subdivide the scale grid so an even `shards`-way split of stored
    dimension `dim` never cuts through a quant block.

    The new block extent is gcd(block, local_extent): every old block is an
    integer number of new blocks, so the move is pure metadata — scales are
    repeated (old // new)x along the axis and `dequantize()` is bitwise
    unchanged.  After alignment, values and scales shard in lockstep under
    the SAME PartitionSpec and every local shard is a self-consistent
    QuantizedTensor.
    """
    if dim not in (0, 1):
        raise ValueError(f"dim must be 0 or 1, got {dim}")
    if shards <= 1:
        return qt
    ax = dim - 2  # stored trailing axes: (..., m, n)
    size = qt.values.shape[ax]
    if size % shards:
        raise ValueError(
            f"stored dim {dim} of size {size} not divisible by {shards}")
    old = qt.block[dim]
    new = math.gcd(old, size // shards)
    if new == old:
        return qt
    scales = jnp.repeat(qt.scales, old // new, axis=ax)
    block = (new, qt.block[1]) if dim == 0 else (qt.block[0], new)
    return QuantizedTensor(values=qt.values, scales=scales, block=block,
                           transposed=qt.transposed)


def shard_quantized(qt: QuantizedTensor, shards: int, dim: int = 0) -> list:
    """Split a QuantizedTensor into `shards` equal QuantizedTensors along
    stored dimension `dim`, values and scale grid in lockstep."""
    qt = align_blocks_for_sharding(qt, shards, dim=dim)
    ax = dim - 2
    vals = jnp.split(qt.values, shards, axis=ax)
    scls = jnp.split(qt.scales, shards, axis=ax)
    return [
        QuantizedTensor(values=v, scales=s, block=qt.block,
                        transposed=qt.transposed)
        for v, s in zip(vals, scls)
    ]


def unshard_quantized(parts: list, dim: int = 0) -> QuantizedTensor:
    """Reassemble `shard_quantized` output: bitwise inverse (same values,
    same scale grid, same block metadata)."""
    if not parts:
        raise ValueError("unshard_quantized needs at least one shard")
    first = parts[0]
    for p in parts[1:]:
        if p.block != first.block or p.transposed != first.transposed:
            raise ValueError("shards disagree on block/transposed metadata")
    ax = dim - 2
    return QuantizedTensor(
        values=jnp.concatenate([p.values for p in parts], axis=ax),
        scales=jnp.concatenate([p.scales for p in parts], axis=ax),
        block=first.block,
        transposed=first.transposed,
    )

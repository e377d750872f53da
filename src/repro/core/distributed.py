"""Parallel BLAS on the mesh — the paper's S5.5 (REDEFINE tile arrays) mapped
onto shard_map + jax.lax collectives.

The paper attaches its PE to every tile of a b x b array and block-partitions
the output matrix; speed-up approaches b^2 as the per-tile compute-to-comm
ratio n/b grows (Fig 12).  Here the "tiles" are mesh devices and the NoC is
ICI; the three GEMM schedules below are the classic distributed realizations,
in increasing overlap quality:

  all_gather_gemm : gather B then one local GEMM (baseline; bursty, no overlap)
  ring_gemm       : Cannon-style — B circulates via collective_permute while
                    the matching A-panel matmul runs; XLA overlaps the permute
                    DMA with the MXU work.  This is the paper's AE5
                    (prefetch next block while computing) at mesh scale.
  psum_gemm       : k-sharded partial products + one all-reduce (SUMMA-
                    reduce); right schedule when k is the sharded dim.

All take/return *global* arrays under jit-with-mesh; shard_map declares the
per-device views.

Numerics: every schedule accumulates in promote_types(f32, operand) — the
PR 2 contract the single-device BLAS layer pins — so bf16 operands reduce in
f32 and f64 operands (x64 mode) keep f64 partials through the collectives.

Packed operands (ISSUE 10): the B operand of every schedule may be a
block-scaled `core.quant.QuantizedTensor` (stored, non-transposed layout).
Its int8 values and f32 scale grid shard IN LOCKSTEP (the grid is first
subdivided at the shard boundaries — `quant.align_blocks_for_sharding`, a
lossless metadata move), the COLLECTIVES move the packed bytes (int8 values
+ scale rows: ~1.06 B/element instead of 4), and each device dequantizes
after the wire hop.  This is the KBLAS co-design argument applied at the
network level: the operand layout that halves HBM traffic quarters the
interconnect traffic too (`roofline.tp_interconnect_byte_ratio`).

Tensor-parallel SERVING (`serve --tp N`) does not call these whole-matrix
schedules per step; it keeps the weight shards resident and runs the
Megatron row-parallel boundary below (`row_parallel_fused`): int8-packed
partial matvecs + exactly ONE psum per layer boundary, with the fused
epilogue applied strictly after the reduction.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import quant as _quant


# --------------------------------------------------------------------------
# Shared helpers
# --------------------------------------------------------------------------

def _acc_dtype(a, b):
    """promote_types(f32, operands): f32 floor for low-precision inputs,
    f64 preserved under x64 (satellite fix — the prototypes used to hardcode
    f32 and silently degraded f64 accumulation)."""
    b_dt = jnp.float32 if _quant.is_quantized(b) else b.dtype
    return jnp.promote_types(jnp.float32, jnp.result_type(a.dtype, b_dt))


def _prep_packed(b, shards: int, dim: int = 0):
    """Validate + block-align a packed B operand for lockstep sharding."""
    if b.transposed:
        raise ValueError(
            "collective GEMMs stream packed B in its stored (k, n) layout; "
            "quantize with transpose=False (or pre-swap) instead")
    if b.values.ndim != 2:
        raise ValueError(
            f"collective GEMMs take a 2-D packed B, got {b.values.shape}")
    return _quant.align_blocks_for_sharding(b, shards, dim=dim)


def _qt_spec(b, spec: P):
    """QuantizedTensor -> same-structure spec tree: values and the (aligned)
    scale grid shard with the SAME PartitionSpec — lockstep by construction."""
    return jax.tree.map(lambda _: spec, b)


# --------------------------------------------------------------------------
# Whole-matrix collective GEMM schedules
# --------------------------------------------------------------------------

def all_gather_gemm(a, b, mesh, axis: str = "model"):
    """a: (m, k) row-sharded over axis; b: (k, n) row-sharded over axis.
    Gathers B (the (p-1)/p bytes the roofline charges) then one local GEMM.
    Output row-sharded like A.  A packed B is gathered PACKED — int8 values
    and scale rows on the wire — and dequantized after the gather."""
    packed = _quant.is_quantized(b)
    if packed:
        b = _prep_packed(b, mesh.shape[axis])
    acc = _acc_dtype(a, b)
    out_dt = a.dtype

    def body(a_loc, b_loc):
        if packed:
            b_full = _quant.QuantizedTensor(
                values=jax.lax.all_gather(b_loc.values, axis, tiled=True),
                scales=jax.lax.all_gather(b_loc.scales, axis, tiled=True),
                block=b_loc.block, transposed=False,
            ).dequantize(jnp.float32)
        else:
            b_full = jax.lax.all_gather(b_loc, axis, tiled=True)
        return jnp.dot(a_loc, b_full, preferred_element_type=acc).astype(out_dt)

    b_spec = _qt_spec(b, P(axis, None)) if packed else P(axis, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), b_spec),
        out_specs=P(axis, None),
        check_vma=False,
    )(a, b)


def ring_gemm(a, b, mesh, axis: str = "model"):
    """Cannon ring: same sharding contract as all_gather_gemm, but B moves
    one hop per step while the previous panel's matmul runs (compute/comm
    overlap — the paper's prefetch enhancement, AE5).  A packed B circulates
    packed: each hop ppermutes the int8 shard + its scale rows and the
    receiving device dequantizes locally."""
    p = mesh.shape[axis]
    packed = _quant.is_quantized(b)
    if packed:
        b = _prep_packed(b, p)
    acc = _acc_dtype(a, b)
    out_dt = a.dtype

    def body(a_loc, b_loc):
        # a_loc: (m/p, k); b_loc: (k/p, n).  Panel j of A pairs with the
        # B-shard that started on device j.
        idx = jax.lax.axis_index(axis)
        kb = (b_loc.values if packed else b_loc).shape[0]
        n = (b_loc.values if packed else b_loc).shape[1]
        perm = [(i, (i - 1) % p) for i in range(p)]  # shift towards lower idx

        def step(i, carry):
            out, b_cur = carry
            j = (idx + i) % p
            a_panel = jax.lax.dynamic_slice_in_dim(a_loc, j * kb, kb, axis=1)
            panel = b_cur.dequantize(jnp.float32) if packed else b_cur
            out = out + jnp.dot(a_panel, panel, preferred_element_type=acc)
            b_nxt = jax.tree.map(lambda t: jax.lax.ppermute(t, axis, perm),
                                 b_cur)
            return out, b_nxt

        out0 = jnp.zeros((a_loc.shape[0], n), acc)
        out, _ = jax.lax.fori_loop(0, p, step, (out0, b_loc))
        return out.astype(out_dt)

    b_spec = _qt_spec(b, P(axis, None)) if packed else P(axis, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), b_spec),
        out_specs=P(axis, None),
        check_vma=False,
    )(a, b)


def psum_gemm(a, b, mesh, axis: str = "model"):
    """a: (m, k) col-sharded; b: (k, n) row-sharded -> partial products +
    all-reduce.  Output replicated over axis.  A packed B dequantizes
    locally (this schedule moves no weight bytes at all — only the output
    reduction crosses the wire); the reduction runs in the promoted
    accumulator dtype and casts only after the psum."""
    packed = _quant.is_quantized(b)
    if packed:
        b = _prep_packed(b, mesh.shape[axis])
    acc = _acc_dtype(a, b)
    out_dt = a.dtype

    def body(a_loc, b_loc):
        b_l = b_loc.dequantize(jnp.float32) if packed else b_loc
        part = jnp.dot(a_loc, b_l, preferred_element_type=acc)
        return jax.lax.psum(part, axis).astype(out_dt)

    b_spec = _qt_spec(b, P(axis, None)) if packed else P(axis, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), b_spec),
        out_specs=P(None, None),
        check_vma=False,
    )(a, b)


def block_parallel_gemm(a, b, mesh, row_axis: str = "data", col_axis: str = "model"):
    """2D SUMMA: C block-partitioned over (row_axis x col_axis) — literally
    the paper's output-block-per-tile partition (each REDEFINE tile owns an
    (n/b x n/b) block of C).  A panels broadcast along rows, B panels along
    columns, local GEMM per step.  A packed B broadcasts PACKED panels
    (values + scale blocks) and dequantizes after the hop."""
    pr, pc = mesh.shape[row_axis], mesh.shape[col_axis]
    packed = _quant.is_quantized(b)
    if packed:
        b = _prep_packed(b, pr, dim=0)
        b = _quant.align_blocks_for_sharding(b, pc, dim=1)
    acc = _acc_dtype(a, b)
    out_dt = a.dtype

    def body(a_loc, b_loc):
        # a_loc: (m/pr, k/pc); b_loc: (k/pr, n/pc)
        def _bcast(x, axis, j):
            # broadcast device j's shard along `axis` (all-gather + select:
            # compiles to a collective-broadcast pattern)
            g = jax.lax.all_gather(x, axis)             # (p, ...)
            return g[j]

        def step(j, out):
            a_panel = _bcast(a_loc, col_axis, j)        # (m/pr, k/pc) from col j
            if packed:
                b_panel = _quant.QuantizedTensor(
                    values=_bcast(b_loc.values, row_axis, j),
                    scales=_bcast(b_loc.scales, row_axis, j),
                    block=b_loc.block, transposed=False,
                ).dequantize(jnp.float32)
            else:
                b_panel = _bcast(b_loc, row_axis, j)    # (k/pr, n/pc) from row j
            return out + jnp.dot(a_panel, b_panel, preferred_element_type=acc)

        steps = pc  # == pr panels along k
        n_loc = (b_loc.values if packed else b_loc).shape[1]
        out0 = jnp.zeros((a_loc.shape[0], n_loc), acc)
        out = jax.lax.fori_loop(0, steps, step, out0)
        return out.astype(out_dt)

    b_spec = (_qt_spec(b, P(row_axis, col_axis)) if packed
              else P(row_axis, col_axis))
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(row_axis, col_axis), b_spec),
        out_specs=P(row_axis, col_axis),
        check_vma=False,
    )(a, b)


# --------------------------------------------------------------------------
# Tensor-parallel serving context (ISSUE 10)
# --------------------------------------------------------------------------
#
# `serve --tp N` wraps each step function in ONE shard_map (launch/steps.py);
# inside it the model code is mesh-agnostic except at the two Megatron
# row-parallel boundaries per layer (attention wo, MLP w_down), where
# models/layers.py routes through `row_parallel_fused` when `tp_active()`.
# The context is thread-local and set only while the TP step bodies trace,
# so single-device serving never sees it.

class _TPState(threading.local):
    def __init__(self):
        self.axis = None
        self.size = 0
        self.routes = []


_tp = _TPState()


@contextlib.contextmanager
def tp_serving(axis: str, size: int):
    """Mark code traced inside as running per-member under a TP shard_map
    over mesh axis `axis` with `size` members."""
    prev = (_tp.axis, _tp.size)
    _tp.axis, _tp.size = axis, int(size)
    try:
        yield
    finally:
        _tp.axis, _tp.size = prev


def tp_active() -> bool:
    return _tp.axis is not None and _tp.size > 1


def tp_axis() -> str:
    return _tp.axis


def tp_size() -> int:
    return _tp.size


def tp_routes() -> list:
    """Trace-time routing log: (route, decode_shaped) per row-parallel call,
    route in {"packed_int8", "dequant", "dense"}.  The serve parity tests'
    routing spy reads this to prove decode/verify projections took the
    collective packed-int8 path, not a dequantize-then-shard fallback."""
    return list(_tp.routes)


def clear_tp_routes() -> None:
    _tp.routes.clear()


def _log_route(route: str, decode_shaped) -> None:
    _tp.routes.append((route, bool(decode_shaped)))


def _packed_row_partial_psum(xb, w, axis: str):
    """Packed W8A8 row-parallel matvec block: bitwise identical to the
    single-device `quant.gemv_host` rows it shards.

    xb: (B, k_loc) — each member's slice of the decode activations;
    w: the member's weight shard, stored output-major (f, k_loc) with
    per-row-block scales (f/qm, 1) — the SAME scale column every member
    holds (lockstep sharding repeats it across the contraction split).

    Exactness argument, term by term:
      - activation scale: all-gather of the per-row local maxima + a local
        max.  max is associative and the gather moves exact f32s, so sx is
        bit-equal to the single-device full-row scale (and, deliberately,
        NOT a pmax: keeping it off the all-reduce op lets the conformance
        harness pin "all-reduce count == psums per boundary" in HLO);
      - int8 quantization of the local slice = the matching slice of the
        single-device x8 (same floats in, same round/clip);
      - int32 partial dot + ONE integer psum: integer addition is
        associative, so the reduced total equals the single-device int32
        dot bit-for-bit;
      - the identical rescale (repeat(weight row-block scales) * sx) in the
        identical multiply order, applied to the replicated total.
    """
    qm = w.block[0]
    xf = xb.astype(jnp.float32)
    local_max = jnp.max(jnp.abs(xf), axis=-1)                     # (B,)
    amax = jnp.max(jax.lax.all_gather(local_max, axis), axis=0)   # (B,) exact
    sx = amax / _quant.INT8_MAX
    inv = jnp.where(sx > 0, 1.0 / jnp.maximum(sx, 1e-30), 0.0)
    x8 = jnp.clip(jnp.round(xf * inv[:, None]),
                  -_quant.INT8_MAX, _quant.INT8_MAX).astype(jnp.int8)
    part = jax.lax.dot_general(x8, w.values, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)  # (B, f)
    total = jax.lax.psum(part, axis)                              # int32: exact
    row_scale = jnp.repeat(w.scales[:, 0], qm)                    # (f,)
    return total.astype(jnp.float32) * (row_scale[None, :] * sx[:, None])


def row_parallel_fused(x, w, *, bias=None, residual=None):
    """Megatron row-parallel projection under TP serving: the layer-boundary
    reduction, with the fused epilogue applied strictly AFTER it.

    x: (..., t, k_loc) — LOCAL feature rows (produced by this member's
    column-parallel heads / FFN slice); w: the member's shard of the
    logical (k, f) down-projection (contraction sharded, output full).
    Returns epilogue(reduce_p(x @ w_p)) replicated over the axis — exactly
    ONE all-reduce per call, so a transformer layer costs two (attention
    out + MLP down), and bias/residual see the REDUCED accumulator (same
    fused semantics as the single-device `blas.matmul_fused`).

    Decode/verify-shaped packed weights run `_packed_row_partial_psum`:
    int8 shards all the way to an integer psum, bit-identical to the
    single-device packed matvec.  Prefill-shaped or non-eligible calls use
    the same dequantize-f32 fallback the single-device path uses, with the
    partial-sum reduction in the promoted accumulator dtype.
    """
    from repro.core import blas as _blas
    from repro.core import epilogue as _epilogue

    axis = tp_axis()
    epi = _epilogue.make(None, bias=bias, gate=None, residual=residual)
    lead = x.shape[:-1]
    f = w.shape[-1]
    k_loc = x.shape[-1]
    xb = x.reshape(-1, k_loc)
    res = None if residual is None else residual.reshape(xb.shape[0], f)
    decode_shaped = x.ndim >= 3 and (x.shape[-2] == 1
                                     or _blas.in_verify_window())
    if _quant.is_quantized(w):
        # eligibility mirrors the single-device host fast path, judged on
        # the GLOBAL contraction (k_loc * tp) so both runs route alike
        packed_ok = (decode_shaped and w.transposed and w.values.ndim == 2
                     and w.scales.shape[-1] == 1
                     and k_loc * tp_size() <= _quant.HOST_FAST_MAX_K)
        if packed_ok:
            _log_route("packed_int8", decode_shaped)
            h = _packed_row_partial_psum(xb, w, axis)
        else:
            _log_route("dequant", decode_shaped)
            acc = _blas._acc_dtype(xb)
            part = jnp.matmul(xb.astype(acc), _blas._deq(w).astype(acc))
            h = jax.lax.psum(part, axis)
    else:
        _log_route("dense", decode_shaped)
        acc = _blas._acc_dtype(x)
        part = jnp.dot(xb, w, preferred_element_type=acc).astype(acc)
        h = jax.lax.psum(part, axis)
    out = epi.apply(h, bias=bias, residual=res).astype(x.dtype)
    return out.reshape(*lead, f)

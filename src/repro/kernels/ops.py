"""Public wrappers for the Pallas kernels.

Responsibilities:
  - ragged-shape handling (the paper's DOT2/DOT3 fringe problem): the
    gemv/bgemv/bgemm/blas1/attention kernels run cdiv grids and mask their
    fringes in-kernel, so those wrappers pass real shapes straight through;
    gemm still pads here;
  - block-shape selection via core.tiling — `tiling.autotune_block_shape`,
    the AE4 analytic ranking plus (REPRO_AUTOTUNE=1) empirical measurement
    of the top-K candidates, cached per (op, shape, dtype, backend); the
    decode bgemv's tiles and batch block from `tiling.plan_bgemv`;
  - fused-epilogue plumbing (core.epilogue): bias/activation/residual and
    the dual-GEMM gate operand travel alongside the GEMM operands into the
    kernels' last-k-step flush;
  - interpret-mode fallback on non-TPU hosts (this container is CPU-only;
    interpret=True executes the kernel bodies in Python for validation).

Each public wrapper is a thin plan-resolving function over an inner jit'd
call with static block parameters, so repeated calls hit the trace cache.
Block resolution runs in Python: on an *eager* call the autotuner may
benchmark candidates on the live backend; when the wrapper is traced inside
an outer jit (operands are tracers) it serves the cached/analytic plan —
run the op once eagerly (or a benchmark sweep) to warm the tune cache.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro.core import epilogue as _epilogue
from repro.core import quant as _quant
from repro.core import tiling
from repro.kernels import attention as _attention
from repro.kernels import bgemm as _bgemm
from repro.kernels import bgemv as _bgemv
from repro.kernels import blas1 as _blas1
from repro.kernels import gemm as _gemm
from repro.kernels import gemv as _gemv
from repro.kernels import mamba2 as _mamba2
from repro.kernels import rwkv6 as _rwkv6


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def _epi_spec(activation, gate, bias, residual) -> _epilogue.Epilogue:
    return _epilogue.make(activation, bias=bias, gate=gate, residual=residual)


def _time_once(fn) -> float:
    jax.block_until_ready(fn())  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    dt1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return min(dt1, time.perf_counter() - t0)


def _resolve_blocks(op, m, n, k, dtype, block_m, block_n, block_k,
                    bench_factory, *, gate=False, residual=False,
                    quantized=False):
    """(block_m, block_n, block_k) for the call: explicit args win, else the
    autotuned/analytic plan.  Benchmarks only run on eager calls (concrete
    operands) with REPRO_AUTOTUNE=1; traced calls read the cache.  The
    epilogue flags charge the fused variant's extra VMEM against the plan
    budget and key its cache entries separately from the unfused op.
    `quantized` plans the weight operand at its true packed width (1 B) —
    bigger feasible blocks, higher arithmetic intensity — and keys the cache
    separately from the full-precision plan."""
    if block_m is not None and block_n is not None and block_k is not None:
        return block_m, block_n, block_k
    bench_fn = bench_factory if (tiling.autotune_enabled() and
                                 bench_factory is not None) else None
    blk = tiling.autotune_block_shape(
        op, m, n, k, dtype_bytes=dtype.itemsize,
        backend=jax.default_backend(), bench_fn=bench_fn,
        gate=gate, residual=residual, quantized=quantized,
    )
    return block_m or blk.bm, block_n or blk.bn, block_k or blk.bk


def _align_block(block: int, q: int) -> int:
    """Kernel-tile extent compatible with scale blocks of extent q: a
    multiple of q when block >= q (tiles hold whole scale blocks), else a
    divisor of q (tiles share one scale; `kernels.gemv.fit_block_to_quant`)
    — the VMEM-budgeted plan is never inflated to a coarse scale block."""
    return _gemv.fit_block_to_quant(block, q)


def _pad_quant(qt, row_mult: int, col_mult: int):
    """Pad packed values and their scales over the STORED last-2 axes so the
    kernel's divisibility contract holds; zero scale blocks dequantize the
    padding to exact zeros.  row_mult/col_mult come from `_align_block`: a
    multiple of the quant block (scales pad in lockstep), or a divisor of
    it (the dim is already a multiple of the tile — both pads are no-ops)."""
    qm, qn = qt.block
    v, s = qt.values, qt.scales
    v, _ = tiling.pad_dim_to(v, v.ndim - 2, row_mult)
    v, _ = tiling.pad_dim_to(v, v.ndim - 1, col_mult)
    s, _ = tiling.pad_dim_to(s, s.ndim - 2, max(1, row_mult // qm))
    s, _ = tiling.pad_dim_to(s, s.ndim - 1, max(1, col_mult // qn))
    return v, s


# --------------------------------------------------------------------------
# GEMM / GEMV
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "activation", "out_dtype"),
)
def _gemm_call(a, b, b2, bias, residual, *, block_m, block_n, block_k,
               activation, out_dtype):
    m, k = a.shape
    quantized = _quant.is_quantized(b)
    n = b.shape[1]  # QuantizedTensor.shape is the LOGICAL (k, n)
    epi = _epi_spec(activation, b2, bias, residual)
    bm, bn, bk = (min(block_m, tiling.round_up(m, 8)),
                  min(block_n, tiling.round_up(n, 128)),
                  min(block_k, tiling.round_up(k, 128)))
    q_kw = {}
    if quantized:
        # kernel tiles must hold whole scale blocks; padding keeps the
        # packed values and their scales in lockstep (zero-scale padding)
        layout = "nk" if b.transposed else "kn"
        qa, qb = b.block
        if layout == "nk":
            bn, bk = _align_block(bn, qa), _align_block(bk, qb)
            row_mult, col_mult = bn, bk
        else:
            bk, bn = _align_block(bk, qa), _align_block(bn, qb)
            row_mult, col_mult = bk, bn
        bv, bs = _pad_quant(b, row_mult, col_mult)
        q_kw = {"scales": bs, "q_block": b.block, "b_layout": layout}
        if b2 is not None:
            b2v, b2s = _pad_quant(b2, row_mult, col_mult)
            b2 = b2v
            q_kw["b2_scales"] = b2s
        b = bv
    else:
        b, _ = tiling.pad_dim_to(b, 0, bk)
        b, _ = tiling.pad_dim_to(b, 1, bn)
        if b2 is not None:
            b2, _ = tiling.pad_dim_to(b2, 0, bk)
            b2, _ = tiling.pad_dim_to(b2, 1, bn)
    a, _ = tiling.pad_dim_to(a, 0, bm)
    a, _ = tiling.pad_dim_to(a, 1, bk)
    if bias is not None:
        bias, _ = tiling.pad_dim_to(bias.reshape(1, n), 1, bn)
    if residual is not None:
        residual, _ = tiling.pad_dim_to(residual, 0, bm)
        residual, _ = tiling.pad_dim_to(residual, 1, bn)
    out = _gemm.gemm(a, b, b2=b2, bias=bias, residual=residual, epilogue=epi,
                     block_m=bm, block_n=bn, block_k=bk, out_dtype=out_dtype,
                     interpret=_interpret(), **q_kw)
    return out[:m, :n]


def gemm(a: jnp.ndarray, b: jnp.ndarray, *, b2=None, bias=None, residual=None,
         activation=None, block_m=None, block_n=None, block_k=None,
         out_dtype=None):
    """epilogue(a (m,k) @ b (k,n) [, a @ b2]) -> (m, n).

    `b`/`b2` may be block-scaled `core.quant.QuantizedTensor` weights: the
    kernel streams the packed int8 values (in their stored layout) and
    dequantizes in-kernel; the tiling plan then runs at the true packed
    operand width.

    Block defaults come from `tiling.autotune_block_shape("gemm", ...)` at
    the real operand width — the analytic AE4 plan, or the measured winner
    when tuning is on.
    """
    m, k = a.shape
    n = b.shape[1]
    if b.shape[0] != k:
        raise ValueError(f"gemm shape mismatch: {a.shape} @ {b.shape}")
    quantized = _quant.is_quantized(b)
    if quantized and b2 is not None and (
        not _quant.is_quantized(b2) or b2.block != b.block
        or b2.transposed != b.transposed
    ):
        raise ValueError("dual-GEMM operands must share one quantization spec")
    _check_epilogue_shapes(b2, bias, residual, b.shape, (n,), (m, n))
    tracer = isinstance(a, jax.core.Tracer)

    def bench(blk):
        # measure the variant actually being called: epilogue operands and
        # all — an unfused winner can lose (or blow VMEM) once the dual-GEMM
        # doubles the B stream and accumulators
        za = jnp.zeros((m, k), a.dtype)
        zb = jnp.zeros((k, n), b.dtype)
        zb2 = None if b2 is None else jnp.zeros((k, n), b2.dtype)
        zbias = None if bias is None else jnp.zeros((n,), bias.dtype)
        zres = None if residual is None else jnp.zeros((m, n), residual.dtype)
        return _time_once(lambda: _gemm_call(
            za, zb, zb2, zbias, zres, block_m=blk.bm, block_n=blk.bn,
            block_k=blk.bk, activation=activation, out_dtype=out_dtype))

    bm, bn, bk = _resolve_blocks("gemm", m, n, k, a.dtype, block_m, block_n,
                                 block_k,
                                 None if (tracer or quantized) else bench,
                                 gate=b2 is not None,
                                 residual=residual is not None,
                                 quantized=quantized)
    return _gemm_call(a, b, b2, bias, residual, block_m=bm, block_n=bn,
                      block_k=bk, activation=activation, out_dtype=out_dtype)


def _check_epilogue_shapes(gate_op, bias, residual, gate_shape, bias_shape,
                           res_shape):
    if gate_op is not None and gate_op.shape != gate_shape:
        raise ValueError(f"epilogue gate operand shape {gate_op.shape} != {gate_shape}")
    if bias is not None and bias.shape != bias_shape:
        raise ValueError(f"epilogue bias shape {bias.shape} != {bias_shape}")
    if residual is not None and residual.shape != res_shape:
        raise ValueError(f"epilogue residual shape {residual.shape} != {res_shape}")


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def _gemv_call(a, x, *, block_m, block_n):
    # no padding: the kernel masks the ragged column fringe in-VMEM and
    # Pallas clips the ragged output rows on the write
    m, n = a.shape
    bm, bn = min(block_m, tiling.round_up(m, 8)), min(block_n, tiling.round_up(n, 128))
    if _quant.is_quantized(a):
        if a.transposed:
            raise ValueError("ops.gemv streams A in its stored layout; "
                             "quantize with transpose=False")
        return _gemv.gemv(a.values, x, scales=a.scales, q_block=a.block,
                          out_dtype=x.dtype, block_m=bm, block_n=bn,
                          interpret=_interpret())
    return _gemv.gemv(a, x, block_m=bm, block_n=bn, interpret=_interpret())


def gemv(a: jnp.ndarray, x: jnp.ndarray, *, block_m=None, block_n=None):
    """a (m, n) @ x (n,) -> (m,).  Block defaults route through
    `tiling.plan_gemm` (via the autotune cache) at the real operand width —
    the row block is the plan's bm, the streamed n sweep its bk.  A
    `QuantizedTensor` a streams packed int8 with in-kernel dequantization."""
    m, n = a.shape
    if x.shape[0] != n:
        raise ValueError(f"gemv shape mismatch: {a.shape} @ {x.shape}")
    quantized = _quant.is_quantized(a)
    tracer = isinstance(x, jax.core.Tracer)

    def bench(blk):
        za, zx = jnp.zeros((m, n), a.dtype), jnp.zeros((n,), x.dtype)
        return _time_once(lambda: _gemv_call(za, zx, block_m=blk.bm,
                                             block_n=blk.bk))

    # gemv is plan_gemm's (m, 1, n) cell: bm rows x bk streamed columns;
    # quantized plans at the packed 1-byte width (the A stream IS the weight)
    bm, _, bn = _resolve_blocks(
        "gemv", m, 1, n,
        jnp.dtype(jnp.int8) if quantized else a.dtype, block_m, 128,
        block_n, None if (tracer or quantized) else bench,
        quantized=quantized)
    return _gemv_call(a, x, block_m=bm, block_n=bn)


# --------------------------------------------------------------------------
# Batched GEMM / GEMV (fused-launch batch execution layer)
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "activation", "out_dtype"),
)
def _bgemm_call(a, b, b2, bias, residual, *, block_m, block_n, block_k,
                activation, out_dtype):
    # no padding: the kernel runs a cdiv grid, masks the ragged k fringe
    # in-VMEM and Pallas clips the ragged m/n output tiles on the write —
    # admission prefills with ragged prompt lengths launch on their real
    # shapes instead of round-tripping padded copies through HBM
    batch, m, k = a.shape
    quantized = _quant.is_quantized(b)
    n = b.shape[-1]  # QuantizedTensor.shape is the LOGICAL (..., k, n)
    epi = _epi_spec(activation, b2, bias, residual)
    bm, bn, bk = (min(block_m, tiling.round_up(m, 8)),
                  min(block_n, tiling.round_up(n, 128)),
                  min(block_k, tiling.round_up(k, 128)))
    q_kw = {}
    if quantized:
        # kernel tiles align to the scale grid (multiples of q, or divisors
        # of q when the plan's tile is smaller than a scale block); the
        # packed values/scales are exact q multiples, so no padding either
        layout = "nk" if b.transposed else "kn"
        qa, qb = b.block
        if layout == "nk":
            bn, bk = _align_block(bn, qa), _align_block(bk, qb)
        else:
            bk, bn = _align_block(bk, qa), _align_block(bn, qb)
        q_kw = {"scales": b.scales, "q_block": b.block, "b_layout": layout}
        if b2 is not None:
            q_kw["b2_scales"] = b2.scales
            b2 = b2.values
        b = b.values
    if bias is not None:
        bias = bias.reshape(1, n)
    return _bgemm.bgemm(a, b, b2=b2, bias=bias, residual=residual,
                        epilogue=epi, block_m=bm, block_n=bn, block_k=bk,
                        out_dtype=out_dtype, interpret=_interpret(), **q_kw)


def bgemm(a: jnp.ndarray, b: jnp.ndarray, *, b2=None, bias=None, residual=None,
          activation=None, block_m=None, block_n=None, block_k=None,
          out_dtype=None):
    """epilogue(a (batch,m,k) @ b ((batch,)k,n) [, a @ b2]) -> (batch, m, n);
    2-D b/b2 broadcast.

    Block shapes default to the per-member `tiling.autotune_block_shape`
    plan (the batch axis costs no extra VMEM): analytic AE4 ranking, or the
    measured winner when REPRO_AUTOTUNE=1.
    """
    batch, m, k = a.shape
    n = b.shape[-1]
    # validate BEFORE padding: pad_dim_to would silently absorb a k mismatch
    if b.shape[-2] != k or (b.ndim == 3 and b.shape[0] != batch):
        raise ValueError(f"bgemm shape mismatch: {a.shape} @ {b.shape}")
    quantized = _quant.is_quantized(b)
    if quantized and b2 is not None and (
        not _quant.is_quantized(b2) or b2.block != b.block
        or b2.transposed != b.transposed
    ):
        raise ValueError("dual-GEMM operands must share one quantization spec")
    _check_epilogue_shapes(b2, bias, residual, b.shape, (n,), (batch, m, n))
    tracer = isinstance(a, jax.core.Tracer)

    def bench(blk):
        # measure the fused variant actually being called (see ops.gemm)
        za = jnp.zeros((batch, m, k), a.dtype)
        zb = jnp.zeros(b.shape, b.dtype)
        zb2 = None if b2 is None else jnp.zeros(b2.shape, b2.dtype)
        zbias = None if bias is None else jnp.zeros((n,), bias.dtype)
        zres = (None if residual is None
                else jnp.zeros((batch, m, n), residual.dtype))
        return _time_once(lambda: _bgemm_call(
            za, zb, zb2, zbias, zres, block_m=blk.bm, block_n=blk.bn,
            block_k=blk.bk, activation=activation, out_dtype=out_dtype))

    # plan under the REAL operand width: an f32/f64 tile may not claim the
    # bf16 block's VMEM footprint (key differs from "gemm": the batched grid
    # amortizes broadcast-B fetches, so measured winners may differ too)
    bm, bn, bk = _resolve_blocks("bgemm", m, n, k, a.dtype, block_m, block_n,
                                 block_k,
                                 None if (tracer or quantized) else bench,
                                 gate=b2 is not None,
                                 residual=residual is not None,
                                 quantized=quantized)
    return _bgemm_call(a, b, b2, bias, residual, block_m=bm, block_n=bn,
                       block_k=bk, activation=activation, out_dtype=out_dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "activation", "transpose_a")
)
def _bgemv_call(a, x, a2, bias, residual, layer, *, block_m, block_n,
                activation, transpose_a):
    quantized = _quant.is_quantized(a)
    if quantized:
        # the packed weight streams in its STORED layout: logical transposes
        # were folded in at quantization time (QuantSpec.transpose), so the
        # caller's transpose_a must cancel against the storage orientation
        if transpose_a != a.transposed:
            raise ValueError(
                "quantized bgemv streams the stored layout; quantize with "
                f"transpose={transpose_a} to request op=A^T={transpose_a}"
            )
        transpose_a = False
        m, n = a.values.shape[-2:]
    elif transpose_a:
        n, m = a.shape[-2:]
    else:
        m, n = a.shape[-2:]
    batch = x.shape[0]
    epi = _epi_spec(activation, a2, bias, residual)
    q_kw = {}
    if quantized:
        q_kw = {"scales": a.scales, "q_block": a.block}
        if a2 is not None:
            q_kw["a2_scales"] = a2.scales
            a2 = a2.values
        a = a.values
    # the tiles and the batch block follow from the shapes and dtypes: a
    # broadcast weight (the decode step; with `layer`, one layer of a stack)
    # puts the whole batch in one block, so each weight tile is read once
    # per call; a per-member (3-D) weight takes one member a block.  No
    # padding: the kernel runs a cdiv grid, masks the ragged contraction
    # fringe in-VMEM and Pallas clips the ragged output fringes on the write.
    if quantized:
        qm, qn = q_kw["q_block"]
        block_m = block_m and _align_block(block_m, qm)
        block_n = block_n and _align_block(block_n, qn)
    bb, bm, bn = tiling.plan_bgemv(batch, m, n, a.dtype, x.dtype,
                                   gate=a2 is not None,
                                   residual=residual is not None,
                                   broadcast=a.ndim == 2 or layer is not None,
                                   block_m=block_m, block_n=block_n)
    if quantized:
        bm, bn = _align_block(bm, qm), _align_block(bn, qn)
    if bias is not None:
        bias = bias.reshape(1, m)
    return _bgemv.bgemv(a, x, a2=a2, bias=bias, residual=residual,
                        epilogue=epi, transpose_a=transpose_a, block_b=bb,
                        block_m=bm, block_n=bn, layer=layer,
                        interpret=_interpret(), **q_kw)


def bgemv(a: jnp.ndarray, x: jnp.ndarray, *, a2=None, bias=None, residual=None,
          activation=None, transpose_a=False, layer=None, block_m=None,
          block_n=None):
    """epilogue(op(a) @ x[b] [, op(a2) @ x[b]]) -> (batch, m).

    a is ((batch,) m, n) — or ((batch,) n, m) under transpose_a, which
    streams the weight in its HBM layout (op = A^T) instead of requiring a
    materialized transpose; 2-D a broadcasts across the batch (the serving
    decode case: the whole batch in one block, each weight tile read once).
    bias is (m,), residual (batch, m).  Blocks default to
    `tiling.plan_bgemv`'s.

    With `layer` (a traced () int), a (and a2) is a layer stack, one such
    broadcast weight per layer on a leading axis, and the kernel reads
    layer `layer` out of it in place: the decode step's layer scan passes
    its stacks so, instead of copying each layer's weights out first.

    A `QuantizedTensor` a (and a2) is the packed serving weight: int8 tiles
    stream at 1 byte/element, converted in-kernel and scaled on the f32
    accumulator.  Its stored layout already encodes the op (transpose folded
    in at quantization time), so transpose_a must match `a.transposed`.
    """
    if _quant.is_quantized(a):
        # logical orientation bookkeeping: .shape undoes the stored transpose
        m, n = (a.shape[-2:][::-1]) if transpose_a else a.shape[-2:]
        if a2 is not None and (not _quant.is_quantized(a2)
                               or a2.block != a.block
                               or a2.transposed != a.transposed):
            raise ValueError("dual-GEMV operands must share one quantization spec")
    elif transpose_a:
        n, m = a.shape[-2:]
    else:
        m, n = a.shape[-2:]
    per_member = a.ndim == 3 and layer is None
    if (x.shape[-1] != n or (per_member and a.shape[0] != x.shape[0])
            or (layer is not None and a.ndim != 3)):
        raise ValueError(f"bgemv shape mismatch: {a.shape} @ {x.shape}")
    _check_epilogue_shapes(a2, bias, residual, a.shape, (m,), (x.shape[0], m))
    return _bgemv_call(a, x, a2, bias, residual, layer, block_m=block_m,
                       block_n=block_n, activation=activation,
                       transpose_a=transpose_a)


# --------------------------------------------------------------------------
# Level 1
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_n",))
def dot(x: jnp.ndarray, y: jnp.ndarray, *, block_n=2048):
    n = x.shape[0]
    bn = min(block_n, tiling.round_up(n, 128))
    x, _ = tiling.pad_dim_to(x, 0, bn)
    y, _ = tiling.pad_dim_to(y, 0, bn)
    return _blas1.dot(x, y, block_n=bn, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_n",))
def nrm2(x: jnp.ndarray, *, block_n=2048):
    n = x.shape[0]
    bn = min(block_n, tiling.round_up(n, 128))
    x, _ = tiling.pad_dim_to(x, 0, bn)
    return _blas1.nrm2(x, block_n=bn, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_n",))
def axpy(alpha, x: jnp.ndarray, y: jnp.ndarray, *, block_n=2048):
    n = x.shape[0]
    bn = min(block_n, tiling.round_up(n, 128))
    x, _ = tiling.pad_dim_to(x, 0, bn)
    y, _ = tiling.pad_dim_to(y, 0, bn)
    return _blas1.axpy(alpha, x, y, block_n=bn, interpret=_interpret())[:n]


# --------------------------------------------------------------------------
# Attention / scans
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("causal", "prefix_len", "block_q", "block_k", "kv_groups"),
)
def flash_attention(q, k, v, *, k_scales=None, v_scales=None, kv_lens=None,
                    page_table=None, kv_groups=1, causal=True, prefix_len=None,
                    block_q=128, block_k=128):
    """(B, Tq, H, D) x (B, Tk, KVH, D) -> (B, Tq, H, D), in the KV cache's
    native layout — one grid row per slot, each block holding all of its
    heads, so the cache streams as it sits in HBM (no transposed copy
    materialized).

    No padding at all: the kernel runs cdiv grids and masks the ragged key
    fringe in-kernel (scores via kpos < kv_len, V rows zeroed), with ragged
    query blocks clipped on the output write — on the decode hot path the
    cache buffers reach the launch untouched, whatever their capacity.

    With `k_scales`/`v_scales` (k's layout with D -> 1,
    core.quant.quantize_kv), K/V are packed int8 streamed at 1 byte/element
    and dequantized in-kernel.  `kv_groups` > 1 shares each stored K/V head
    across that many consecutive query heads (GQA) via the index map — no
    materialized repeat.  `kv_lens` (B,) replaces the shared real KV
    length with a per-slot one (continuous-batching ragged slot decode).
    `prefix_len` relaxes the causal mask over the first prefix_len absolute
    key positions (prefix-LM, e.g. the paligemma patch prefix).

    With `page_table` (B, max_pages) the k/v (and scale) operands are the
    paged KV POOL (num_pages, page_size, KVH, D), and the table lookups ride
    in SMEM via scalar prefetch.  A launch of one query a slot (the decode
    step) takes the decode path: each grid step reads P pages of a slot and
    attends every head at once, and steps past a slot's live pages move
    nothing.  More queries a slot (verify windows) walk the table row one
    page-sized key block at a time.  Either way a ragged, paged, quantized
    decode step is exactly one launch, and the launch keeps this function's
    name (`flash_attention`), which the compiled custom call takes.

    This is the ONE attention engine: every mask variant (causal, prefix-LM,
    non-causal), both cache dtypes, GQA, and the paged pool layout route
    here under the pallas backend — `models.layers.attention_core` survives
    only as the xla/ref oracle these launches are pinned against.
    """
    return _attention.attention(
        q, k, v, k_scales=k_scales, v_scales=v_scales, kv_lens=kv_lens,
        page_table=page_table, kv_groups=kv_groups, causal=causal,
        prefix_len=prefix_len, block_q=block_q, block_k=block_k,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6(r, k, v, w_log, u, *, chunk=32):
    bh, t, _ = r.shape
    c = min(chunk, t)
    pads = (-t) % c
    if pads:
        r, k, v, w_log = (
            tiling.pad_dim_to(z, 1, c)[0] for z in (r, k, v, w_log)
        )
    out = _rwkv6.rwkv6(r, k, v, w_log, u, chunk=c, interpret=_interpret())
    return out[:, :t]


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba2_ssd(x, a_log, b, c, *, chunk=64):
    bh, t, _ = x.shape
    ck = min(chunk, t)
    pads = (-t) % ck
    if pads:
        x = tiling.pad_dim_to(x, 1, ck)[0]
        b = tiling.pad_dim_to(b, 1, ck)[0]
        c = tiling.pad_dim_to(c, 1, ck)[0]
        a_log = tiling.pad_dim_to(a_log, 1, ck)[0]
    out = _mamba2.ssd(x, a_log, b, c, chunk=ck, interpret=_interpret())
    return out[:, :t]

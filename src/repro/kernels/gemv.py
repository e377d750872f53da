"""Blocked GEMV Pallas kernel (the paper's bandwidth-bound 40%-of-peak case).

GEMV has O(1) reuse — every A element is touched once — so the kernel's only
job is to stream A tiles through VMEM at full HBM bandwidth while the VPU
does the multiply-accumulate (using the MXU for a rank-1-output matmul would
waste 127/128 of the systolic array; the paper makes the same observation
when its DOT4 utilization collapses for DGEMV).  The row-block accumulator
lives in an f32 VMEM scratch across the n-sweep.

Two bandwidth levers live here:

  - masked tails: the grid is cdiv-shaped and the kernel masks the ragged
    column fringe in-VMEM (out-of-range output rows are clipped by Pallas on
    the write), so callers do not have to pad — the paper's DOT2/DOT3 fringe
    handling moved inside the kernel;
  - block-scaled int8 weights (core.quant): when `scales` is passed, A is a
    packed int8 tile streamed at 1 byte/element and dequantized on the fly
    against the f32 accumulator (W8A16).  The O(1)-reuse op moves 4x fewer
    HBM weight bytes vs f32 at the cost of one VPU multiply per element that
    was already bandwidth-idle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _compat


def dequant_tile(v, s, qm: int, qn: int, dtype=jnp.float32):
    """Per-block dequantization of a VMEM tile: v (bm, bn) int8, s
    (bm//qm, bn//qn) f32 -> (bm, bn) `dtype`, where (qm, qn) is the
    EFFECTIVE in-tile quant block (`scale_layout`).  Shared by every kernel
    that streams packed weights (gemv/bgemv/gemm/bgemm)."""
    bm, bn = v.shape
    vb = v.astype(dtype).reshape(bm // qm, qm, bn // qn, qn)
    return (vb * s.astype(dtype)[:, None, :, None]).reshape(bm, bn)


def scale_layout(tile: tuple, q_block: tuple):
    """How a values tile maps onto its scale grid, per stored axis.

    A tile no smaller than the scale block holds whole blocks (tile extents
    aligned to multiples of q upstream); a tile SMALLER than the scale
    block must divide it, so every tile sees exactly one scale along that
    axis and consecutive tiles share it (the block index divides down).
    Returns (scale_tile_shape, block_index_divisors, effective_q) — the
    scale BlockSpec is `scale_tile_shape` indexed at
    (i // divisor_m, j // divisor_n), and `dequant_tile` runs at
    `effective_q`.  This is what lets the VMEM-budgeted kernel block plan
    survive coarse scale blocks (e.g. the default whole-row serving spec)
    instead of being silently inflated to the scale-block extent.
    """
    (tm, tn), (qm, qn) = tile, q_block
    st = (max(1, tm // qm), max(1, tn // qn))
    div = (max(1, qm // tm), max(1, qn // tn))
    q_eff = (min(qm, tm), min(qn, tn))
    return st, div, q_eff


def fit_block_to_quant(block: int, q: int) -> int:
    """Largest kernel-tile extent <= `block` compatible with scale blocks of
    extent `q`: a multiple of q when block >= q, else a divisor of q (so no
    tile straddles a scale-block boundary)."""
    if block >= q:
        return block - block % q
    b = max(1, block)
    while q % b:
        b -= 1
    return b


def _gemv_kernel(a_ref, x_ref, *refs, nn: int, n: int, block_n: int,
                 q_block):
    s_ref = refs[0] if q_block else None
    o_ref, acc_ref = refs[-2], refs[-1]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    if q_block:
        a = dequant_tile(a, s_ref[...], *q_block, dtype=acc_ref.dtype)
    else:
        a = a.astype(acc_ref.dtype)             # (bm, bn)
    x = x_ref[...].astype(acc_ref.dtype)        # (1, bn)
    # mask the ragged column fringe: OOB tile reads are undefined (NaN in
    # interpret mode) and must not reach the accumulator
    cols = j * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    prod = jnp.where(cols < n, a * x, 0.0)
    acc_ref[...] += jnp.sum(prod, axis=1, keepdims=True)  # (bm, 1)

    @pl.when(j == nn - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemv(
    a: jnp.ndarray,  # (m, n); int8 packed values when `scales` is given
    x: jnp.ndarray,  # (n,)
    *,
    scales: jnp.ndarray = None,   # (m/qm, n/qn) f32 block scales
    q_block: tuple = None,        # (qm, qn) quant block (with scales)
    out_dtype=None,
    block_m: int = 512,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = A @ x (dequantizing A in-kernel when packed).  Ragged m/n are
    handled by in-kernel masking — no caller-side padding required."""
    m, n = a.shape
    assert (scales is None) == (q_block is None)
    block_m, block_n = min(block_m, m), min(block_n, n)
    q_eff = None
    if q_block is not None:
        qm, qn = q_block
        assert m % qm == 0 and n % qn == 0, ((m, n), q_block)
        # kernel tiles align to the scale grid (multiples of q, or divisors
        # of q when the plan's tile is smaller than a scale block)
        block_m = fit_block_to_quant(block_m, qm)
        block_n = fit_block_to_quant(block_n, qn)
        s_tile, s_div, q_eff = scale_layout((block_m, block_n), q_block)
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n))
    kernel = functools.partial(_gemv_kernel, nn=grid[1], n=n, block_n=block_n,
                               q_block=q_eff)
    operands = [a, x[None, :]]
    in_specs = [
        pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
    ]
    if scales is not None:
        operands.append(scales)
        in_specs.append(
            pl.BlockSpec(s_tile, lambda i, j: (i // s_div[0], j // s_div[1]))
        )
    out_dt = out_dtype or (x.dtype if scales is not None else a.dtype)
    # accumulate in max(f32, operand dtype): f64 stays f64 (DGEMV proper)
    acc_dt = jnp.promote_types(jnp.float32, out_dt)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), out_dt),
        scratch_shapes=[pltpu.VMEM((block_m, 1), acc_dt)],
        compiler_params=_compat.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(*operands)
    return out[:, 0]

"""Batched row-blocked GEMV Pallas kernel (the bandwidth-bound fix).

Single GEMV is the paper's worst case — 40% of peak, O(1) reuse, the MXU
idles.  Batching is the classic remedy (KBLAS, arXiv:1410.1726): many small
matvecs fused into one launch saturate the memory system that one matvec
cannot.  The grid is (m/bm, batch, n/bn) with the n sweep innermost so the
per-(batch, row-block) f32 accumulator stays resident in VMEM.

Two A layouts:
  - batched A (batch, m, n): per-request matrices;
  - broadcast A (m, n): one shared weight matrix against a batch of vectors
    — the serving decode case (every request multiplies the same W).  The
    A tile's index_map ignores the batch coordinate, and the batch axis
    sits between the row-block and the n sweep in the grid, so when the
    weight's n extent is a single tile (nn == 1) the A index is unchanged
    across consecutive batch steps: each row block of W is streamed once
    for the whole batch, raising the arithmetic intensity of the weight
    traffic from O(1) to O(batch).  Wider weights refetch per member (the
    pipeline only elides DMAs between consecutive steps) but still avoid
    batch copies of W in HBM.

transpose_a=True computes y[b] = A^T x[b] by swapping the tile index map
(the A tile is loaded as (bn, bk-rows) and contracted over rows) — the
model-layer decode projection x @ W is exactly W^T x, and this flag lets it
stream W in its HBM layout instead of materializing W.T on every decode
step.

The last-n-step flush applies the fused epilogue (core.epilogue): bias,
activation, residual and the dual-GEMV gate multiply (`a2`: a second weight
matrix with its own accumulator, so a decode-step SwiGLU
silu(W_g^T x) * (W_u^T x) is one launch) run on the VMEM-resident
accumulator before the single HBM write.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.kernels import _compat
from repro.kernels.gemm import epi_operands_match
from repro.kernels.gemv import dequant_tile, fit_block_to_quant, scale_layout


def _bgemv_kernel(
    a_ref, x_ref, *refs, nn: int, n: int, block_n: int, a_batched: bool,
    trans: bool, epi: Epilogue, q_block
):
    # refs: [a_scales] [a2] [a2_scales] [bias] [residual] o acc [acc2]
    refs = list(refs)
    a_s_ref = refs.pop(0) if q_block else None
    a2_ref = refs.pop(0) if epi.gate else None
    a2_s_ref = refs.pop(0) if (epi.gate and q_block) else None
    bias_ref = refs.pop(0) if epi.bias else None
    res_ref = refs.pop(0) if epi.residual else None
    o_ref, acc_ref = refs[0], refs[1]
    acc2_ref = refs[2] if epi.gate else None

    j = pl.program_id(2)  # grid (m/bm, batch, n/bn): n sweep innermost

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if epi.gate:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    x = x_ref[0].astype(acc_ref.dtype)  # (1, bn)
    # mask the ragged contraction fringe in-VMEM (cdiv grid, no caller-side
    # padding): OOB tile reads are undefined and must not reach the
    # accumulator.  The output-dim (m) fringe needs no mask — Pallas clips
    # the out-of-range rows on the write.
    mask_n = n % block_n != 0

    def contract(ref, s_ref):
        if q_block:
            # packed int8 weight tile (bm, bn): dequantize on the fly
            # against the f32 accumulator — the weight streamed 1 B/elem
            a = dequant_tile(ref[...], s_ref[...], *q_block, dtype=acc_ref.dtype)
        else:
            a = (ref[0] if a_batched else ref[...]).astype(acc_ref.dtype)
        if trans:
            # a is (bn, bm): contract over rows -> (1, bm)
            prod = a * x[0][:, None]
            if mask_n:
                rows = j * block_n + jax.lax.broadcasted_iota(
                    jnp.int32, (block_n, 1), 0)
                prod = jnp.where(rows < n, prod, 0.0)
            return jnp.sum(prod, axis=0, keepdims=True)
        # a is (bm, bn): contract over cols -> (bm, 1)
        prod = a * x
        if mask_n:
            cols = j * block_n + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_n), 1)
            prod = jnp.where(cols < n, prod, 0.0)
        return jnp.sum(prod, axis=1, keepdims=True)

    acc_ref[...] += contract(a_ref, a_s_ref)
    if epi.gate:
        acc2_ref[...] += contract(a2_ref, a2_s_ref)

    @pl.when(j == nn - 1)
    def _flush():
        h = epi.apply(
            acc_ref[...],
            acc2=acc2_ref[...] if epi.gate else None,
            bias=bias_ref[...] if epi.bias else None,       # (bm,1) / (1,bm)
            residual=res_ref[0] if epi.residual else None,
        )
        o_ref[0] = h.astype(o_ref.dtype)


def bgemv(
    a: jnp.ndarray,  # ((batch,) m, n), or ((batch,) n, m) when transpose_a
    x: jnp.ndarray,  # (batch, n)
    *,
    a2: jnp.ndarray = None,        # same layout as a: dual-GEMV gate operand
    bias: jnp.ndarray = None,      # (m, 1), or (1, m) when transpose_a
    residual: jnp.ndarray = None,  # (batch, m, 1), or (batch, 1, m) when transpose_a
    epilogue: Epilogue = Epilogue(),
    transpose_a: bool = False,
    scales: jnp.ndarray = None,     # (m/qm, n/qn) f32: a is packed int8
    a2_scales: jnp.ndarray = None,  # same structure for the gate operand
    q_block: tuple = None,          # (qm, qn) quant block
    out_dtype=None,
    block_m: int = 512,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """y[b] = epilogue(op(A[b]) @ x[b] [, op(A2[b]) @ x[b]]) -> (batch, m);
    2-D A broadcasts, op = A^T under transpose_a.

    With `scales`/`q_block`, A (and A2) are block-scaled packed int8 weights
    (core.quant) streamed through VMEM at 1 byte/element and dequantized
    in-kernel against the f32 accumulator — the serving decode case where
    the broadcast weight dominates HBM traffic.  Quantized weights are
    pre-laid-out output-major (QuantSpec.transpose), so transpose_a is not
    combined with them.
    """
    a_batched = a.ndim == 3
    if transpose_a:
        n, m = a.shape[-2:]
    else:
        m, n = a.shape[-2:]
    batch, nx = x.shape
    assert nx == n, (a.shape, x.shape)
    if a_batched:
        assert a.shape[0] == batch, (a.shape, x.shape)
    assert epi_operands_match(epilogue, a2, bias, residual)
    if a2 is not None:
        assert a2.shape == a.shape, (a.shape, a2.shape)
    assert (scales is None) == (q_block is None)
    if q_block is not None:
        assert not transpose_a and not a_batched, (
            "packed weights stream in their stored (output-major) layout; "
            "quantize with QuantSpec(transpose=True) instead of transpose_a"
        )
        assert (a2 is None) == (a2_scales is None)
        qm, qn = q_block
        assert m % qm == 0 and n % qn == 0, ((m, n), q_block)
        block_m = fit_block_to_quant(min(block_m, m), qm)
        block_n = fit_block_to_quant(min(block_n, n), qn)
    block_m, block_n = min(block_m, m), min(block_n, n)
    # batch between the row block and the n sweep: a broadcast-A tile with
    # nn == 1 keeps a constant index across consecutive batch steps, so each
    # W row block is fetched once for the whole batch.  The grid is
    # cdiv-shaped: ragged m/n are masked in-kernel (contraction fringe) or
    # clipped by Pallas on the output write — no caller-side padding.
    q_eff = None
    if q_block is not None:
        s_tile, s_div, q_eff = scale_layout((block_m, block_n), q_block)
    grid = (pl.cdiv(m, block_m), batch, pl.cdiv(n, block_n))
    kernel = functools.partial(
        _bgemv_kernel, nn=grid[2], n=n, block_n=block_n, a_batched=a_batched,
        trans=transpose_a, epi=epilogue, q_block=q_eff,
    )
    # tile/accumulator orientation follows the A layout: (bm, bn) tiles with
    # a (bm, 1) accumulator, or (bn, bm) tiles with a (1, bm) accumulator
    # under transpose_a (no transposition inside the kernel datapath).
    if transpose_a:
        a_block, a_idx = (block_n, block_m), lambda i, bi, j: (j, i)
        ab_block, ab_idx = (1, block_n, block_m), lambda i, bi, j: (bi, j, i)
        acc_shape, bias_shape = (1, block_m), (1, m)
        out_shape, out_block = (batch, 1, m), (1, 1, block_m)
        out_idx = lambda i, bi, j: (bi, 0, i)
        bias_block, bias_idx = (1, block_m), (lambda i, bi, j: (0, i))
    else:
        a_block, a_idx = (block_m, block_n), lambda i, bi, j: (i, j)
        ab_block, ab_idx = (1, block_m, block_n), lambda i, bi, j: (bi, i, j)
        acc_shape, bias_shape = (block_m, 1), (m, 1)
        out_shape, out_block = (batch, m, 1), (1, block_m, 1)
        out_idx = lambda i, bi, j: (bi, i, 0)
        bias_block, bias_idx = (block_m, 1), (lambda i, bi, j: (i, 0))
    a_spec = (
        pl.BlockSpec(ab_block, ab_idx) if a_batched else pl.BlockSpec(a_block, a_idx)
    )
    out_dt = out_dtype or (x.dtype if scales is not None else a.dtype)
    # accumulate in max(f32, operand dtype): f64 stays f64 (DGEMV proper)
    acc_dtype = jnp.promote_types(jnp.float32, out_dt)
    s_spec = None
    if scales is not None:
        s_spec = pl.BlockSpec(
            s_tile, lambda i, bi, j: (i // s_div[0], j // s_div[1])
        )
    operands = [a, x[:, None, :]]
    in_specs = [a_spec, pl.BlockSpec((1, 1, block_n), lambda i, bi, j: (bi, 0, j))]
    scratch = [pltpu.VMEM(acc_shape, acc_dtype)]
    if scales is not None:
        operands.append(scales)
        in_specs.append(s_spec)
    if epilogue.gate:
        operands.append(a2)
        in_specs.append(a_spec)
        if scales is not None:
            operands.append(a2_scales)
            in_specs.append(s_spec)
        scratch.append(pltpu.VMEM(acc_shape, acc_dtype))
    if epilogue.bias:
        assert bias.shape == bias_shape, (bias.shape, bias_shape)
        operands.append(bias)
        in_specs.append(pl.BlockSpec(bias_block, bias_idx))
    if epilogue.residual:
        assert residual.shape == out_shape, (residual.shape, out_shape)
        operands.append(residual)
        in_specs.append(pl.BlockSpec(out_block, out_idx))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, out_idx),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dt),
        scratch_shapes=scratch,
        compiler_params=_compat.compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*operands)
    return out[:, 0, :] if transpose_a else out[:, :, 0]

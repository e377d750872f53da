"""Batched blocked GEMM Pallas kernel — one fused launch for N small GEMMs.

The paper's PE hits 74% of peak on DGEMM but the serving workload is not one
big GEMM: it is a *batch* of per-request matmuls (attention QK^T/PV, MoE
expert FFNs).  Launching them one by one leaves the memory system idle
between kernels — the KBLAS observation for batched GPU BLAS.  This kernel
folds the batch into the grid (m/bm, n/bn, batch, k/bk) so the Pallas
pipeline double-buffers tiles *across* batch members as well as across
blocks, and the whole batch is one launch.

Two B layouts:
  - batched B (batch, k, n): independent right-hand sides (attention, MoE
    experts with per-expert weights);
  - broadcast B (k, n): one shared weight matrix applied to every batch
    member (the serving case — same projection for every request).  The
    B tile's index_map ignores the batch coordinate, and the batch axis
    sits INSIDE the (i, j) output-tile coordinates in the grid, so whenever
    the weight's k extent is a single tile (nk == 1 — the common
    d_model-sized projection) the B index is unchanged across consecutive
    batch steps and the pipeline fetches it once per (i, j) for the whole
    batch.  Multi-k-tile weights still refetch per batch member (the
    pipeline only elides DMAs between consecutive steps); even then the
    broadcast layout avoids materializing batch copies of B in HBM.

Per-batch-member f32 VMEM accumulator, flushed on the last k step, exactly
like the single GEMM kernel (the accumulate term never touches HBM).  The
last-k-step flush also applies the fused epilogue (core.epilogue): bias,
activation, residual and the dual-GEMM gate multiply (`b2`, e.g. the MoE
expert SwiGLU where every expert's silu(h@Wg)*(h@Wu) is one launch) run on
the VMEM-resident accumulator instead of round-tripping HBM per op.
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


def _bgemm_kernel(a_ref, b_ref, *refs, nk: int, ka: int, block_k: int,
                  b_batched: bool, epi: Epilogue, q_block, b_layout: str):
    # refs: [b_scales] [b2] [b2_scales] [bias] [residual] o acc [acc2]
    refs = list(refs)
    b_s_ref = refs.pop(0) if q_block else None
    b2_ref = refs.pop(0) if epi.gate else None
    b2_s_ref = refs.pop(0) if (epi.gate and q_block) else None
    bias_ref = refs.pop(0) if epi.bias else None
    res_ref = refs.pop(0) if epi.residual else None
    o_ref, acc_ref = refs[0], refs[1]
    acc2_ref = refs[2] if epi.gate else None

    k = pl.program_id(3)  # grid (m/bm, n/bn, batch, k/bk): k innermost

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if epi.gate:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    a_tile = a_ref[0]
    # mask the ragged k fringe in-VMEM (cdiv grid, no caller-side padding):
    # BOTH operands are zeroed past ka so the dot accumulates 0*0 — one-sided
    # masking would still contract garbage (0 * NaN).  The m/n fringes need
    # no mask: Pallas clips the out-of-range output tile on the write.
    mask_k = ka % block_k != 0
    if mask_k:
        kpos = k * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        a_tile = jnp.where(kpos < ka, a_tile, 0)

    def contract(ref, s_ref):
        b_tile = ref[0] if b_batched else ref[...]
        if q_block:
            # packed int8 weight tile: dequantize in-kernel, in the STORED
            # orientation, against the accumulator (1 B/element streamed)
            s_tile = s_ref[0] if b_batched else s_ref[...]
            b_tile = dequant_tile(b_tile, s_tile, *q_block, dtype=acc_ref.dtype)
        if b_layout == "nk":
            # output-major storage (QuantSpec.transpose): contract over k
            # on both operands' trailing axes — no data transpose
            if mask_k:
                b_tile = jnp.where(kpos < ka, b_tile, 0)
            return jax.lax.dot_general(
                a_tile, b_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_ref.dtype,
            )
        if mask_k:
            b_tile = jnp.where(kpos.reshape(block_k, 1) < ka, b_tile, 0)
        return jnp.dot(a_tile, b_tile, preferred_element_type=acc_ref.dtype)

    acc_ref[...] += contract(b_ref, b_s_ref)
    if epi.gate:
        acc2_ref[...] += contract(b2_ref, b2_s_ref)

    @pl.when(k == nk - 1)
    def _flush():
        h = epi.apply(
            acc_ref[...],
            acc2=acc2_ref[...] if epi.gate else None,
            bias=bias_ref[...] if epi.bias else None,       # (1, bn) broadcasts
            residual=res_ref[0] if epi.residual else None,  # (bm, bn)
        )
        o_ref[0] = h.astype(o_ref.dtype)


def bgemm(
    a: jnp.ndarray,  # (batch, m, k)
    b: jnp.ndarray,  # (batch, k, n) or (k, n) broadcast across the batch
    *,
    b2: jnp.ndarray = None,        # same layout as b: dual-GEMM gate operand
    bias: jnp.ndarray = None,      # (1, n) broadcast across batch and rows
    residual: jnp.ndarray = None,  # (batch, m, n)
    epilogue: Epilogue = Epilogue(),
    scales: jnp.ndarray = None,     # per-block f32 scales: b is packed int8
    b2_scales: jnp.ndarray = None,  # same structure for the gate operand
    q_block: tuple = None,          # (qm, qn) quant block over b's STORED axes
    b_layout: str = "kn",
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """C[b] = epilogue(A[b] @ B[b] [, A[b] @ B2[b]]) (2-D B/B2 broadcast).
    Dims must divide the blocks (ops.bgemm pads first — the paper's
    DOT2/DOT3 fringe handling).

    With `scales`/`q_block`, B (and B2) are block-scaled packed int8 weights
    (core.quant, batched or broadcast) streamed at 1 byte/element and
    dequantized in-kernel; b_layout="nk" streams output-major storage
    (QuantSpec.transpose) without materializing the transpose.
    """
    batch, m, ka = a.shape
    b_batched = b.ndim == 3
    if b_layout == "nk":
        n, kb = b.shape[-2:]
    else:
        kb, n = b.shape[-2:]
    assert ka == kb, (a.shape, b.shape)
    if b_batched:
        assert b.shape[0] == batch, (a.shape, b.shape)
    assert epi_operands_match(epilogue, b2, bias, residual)
    assert (scales is None) == (q_block is None)
    if q_block is not None:
        assert (b2 is None) == (b2_scales is None)
        qa, qb = q_block
        sk, sn = (qb, qa) if b_layout == "nk" else (qa, qb)
        assert ka % sk == 0 and n % sn == 0, ((ka, n), q_block, b_layout)
        block_k = fit_block_to_quant(min(block_k, ka), sk)
        block_n = fit_block_to_quant(min(block_n, n), sn)
    block_m, block_n, block_k = (min(block_m, m), min(block_n, n), min(block_k, ka))
    # batch between (i, j) and k: consecutive steps sweep k within one batch
    # member, then advance the member — so a broadcast-B tile with nk == 1
    # keeps a constant index across the whole batch (fetched once per (i, j)).
    # The grid is cdiv-shaped: the ragged k fringe is masked in-kernel and
    # the m/n fringes are clipped on the output write — no caller padding.
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n), batch, pl.cdiv(ka, block_k))
    if b_layout == "nk":
        b_blk, b_idx = (block_n, block_k), lambda i, j, bi, k: (j, k)
    else:
        b_blk, b_idx = (block_k, block_n), lambda i, j, bi, k: (k, j)
    q_eff = None
    if q_block is not None:
        s_blk, s_div, q_eff = scale_layout(b_blk, q_block)
        s_idx = lambda i, j, bi, k: tuple(
            c // d for c, d in zip(b_idx(i, j, bi, k), s_div)
        )
    kernel = functools.partial(
        _bgemm_kernel, nk=grid[3], ka=ka, block_k=block_k,
        b_batched=b_batched, epi=epilogue, q_block=q_eff, b_layout=b_layout,
    )
    if b_batched:
        b_spec = pl.BlockSpec((1,) + b_blk, lambda i, j, bi, k: (bi,) + b_idx(i, j, bi, k))
        s_spec = (pl.BlockSpec((1,) + s_blk, lambda i, j, bi, k: (bi,) + s_idx(i, j, bi, k))
                  if q_block else None)
    else:
        # index_map drops the batch coordinate: the broadcast-B serving case.
        b_spec = pl.BlockSpec(b_blk, b_idx)
        s_spec = pl.BlockSpec(s_blk, s_idx) if q_block else None
    out_dt = out_dtype or a.dtype
    # accumulate in max(f32, operand dtype): f64 stays f64 (DGEMM proper)
    acc_dtype = jnp.promote_types(jnp.float32, a.dtype)
    operands = [a, b]
    in_specs = [
        pl.BlockSpec((1, block_m, block_k), lambda i, j, bi, k: (bi, i, k)),
        b_spec,
    ]
    scratch = [pltpu.VMEM((block_m, block_n), acc_dtype)]
    if scales is not None:
        operands.append(scales)
        in_specs.append(s_spec)
    if epilogue.gate:
        assert b2.shape == b.shape, (b.shape, b2.shape)
        operands.append(b2)
        in_specs.append(b_spec)
        if scales is not None:
            operands.append(b2_scales)
            in_specs.append(s_spec)
        scratch.append(pltpu.VMEM((block_m, block_n), acc_dtype))
    if epilogue.bias:
        assert bias.shape == (1, n), (bias.shape, n)
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, bi, k: (0, j)))
    if epilogue.residual:
        assert residual.shape == (batch, m, n), (residual.shape, (batch, m, n))
        operands.append(residual)
        in_specs.append(
            pl.BlockSpec((1, block_m, block_n), lambda i, j, bi, k: (bi, i, j))
        )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_m, block_n), lambda i, j, bi, k: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, m, n), out_dt),
        scratch_shapes=scratch,
        compiler_params=_compat.compiler_params(
            "parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*operands)

"""Blocked GEMM Pallas kernel — the paper's PE mapped onto a TPU core.

Co-design correspondence (DESIGN.md S2):
  - 4x4 register block        -> (bm, bn, bk) MXU-aligned VMEM tiles
  - DOT4 fused datapath (AE2) -> `jnp.dot(..., preferred_element_type=f32)`
                                 feeding the 128x128 systolic MXU
  - LM + Load-Store CFU (AE1) -> BlockSpec-declared HBM->VMEM tiles
  - block load/store (AE3)    -> whole-tile DMAs (one descriptor per tile)
  - 4x bandwidth (AE4)        -> block aspect ratio from core.tiling
  - prefetch (AE5)            -> Pallas grid pipelining double-buffers the
                                 next (i, j, k) tiles while the MXU runs;
                                 k is innermost ("arbitrary") so the f32
                                 accumulator tile stays resident in VMEM.

The kernel accumulates in an f32 VMEM scratch tile and writes the output
tile once on the last k step — the accumulate-move the paper counts as its
third n^3 flop term happens entirely inside VMEM, never touching HBM.

Epilogue fusion (core.epilogue) extends that last-k-step flush: bias add,
silu/gelu/relu activation, residual add and the dual-GEMM gate multiply
(`b2`: a second right-hand side accumulated into its own VMEM scratch, so
SwiGLU's silu(A@Wg) * (A@Wu) is one launch) all run on the f32 accumulator
tile while it is still VMEM-resident.  A fused layer op writes its output
to HBM once instead of round-tripping every intermediate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.kernels import _compat
from repro.kernels.gemv import dequant_tile, fit_block_to_quant, scale_layout


def _gemm_kernel(a_ref, b_ref, *refs, nk: int, epi: Epilogue, q_block,
                 b_layout: str):
    # refs: [b_scales] [b2] [b2_scales] [bias] [residual] o acc [acc2] —
    # presence driven by the static epilogue/quant spec, so each variant
    # compiles its own minimal kernel.
    refs = list(refs)
    b_s_ref = refs.pop(0) if q_block else None
    b2_ref = refs.pop(0) if epi.gate else None
    b2_s_ref = refs.pop(0) if (epi.gate and q_block) else None
    bias_ref = refs.pop(0) if epi.bias else None
    res_ref = refs.pop(0) if epi.residual else None
    o_ref, acc_ref = refs[0], refs[1]
    acc2_ref = refs[2] if epi.gate else None

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if epi.gate:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    a = a_ref[...]

    def contract(ref, s_ref):
        b = ref[...]
        if q_block:
            # packed int8 weight tile streamed at 1 B/element, dequantized
            # on the fly in its STORED orientation against the accumulator
            b = dequant_tile(b, s_ref[...], *q_block, dtype=acc_ref.dtype)
        if b_layout == "nk":
            # output-major storage (QuantSpec.transpose): tile is (bn, bk),
            # contract both operands over their k axis — no data transpose
            return jax.lax.dot_general(
                a, b, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_ref.dtype,
            )
        return jnp.dot(a, b, preferred_element_type=acc_ref.dtype)

    acc_ref[...] += contract(b_ref, b_s_ref)
    if epi.gate:
        acc2_ref[...] += contract(b2_ref, b2_s_ref)

    @pl.when(k == nk - 1)
    def _flush():
        h = epi.apply(
            acc_ref[...],
            acc2=acc2_ref[...] if epi.gate else None,
            bias=bias_ref[...] if epi.bias else None,       # (1, bn) broadcasts
            residual=res_ref[...] if epi.residual else None,
        )
        o_ref[...] = h.astype(o_ref.dtype)


def gemm(
    a: jnp.ndarray,  # (m, k)
    b: jnp.ndarray,  # (k, n) — or (n, k) packed storage when b_layout="nk"
    *,
    b2: jnp.ndarray = None,        # same layout as b: dual-GEMM gate operand
    bias: jnp.ndarray = None,      # (1, n)
    residual: jnp.ndarray = None,  # (m, n)
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
    """C = epilogue(A @ B [, A @ B2]) with explicit VMEM tiling.  Dims must
    divide the blocks (ops.gemm pads first — the paper's DOT2/DOT3 fringe
    handling).

    With `scales`/`q_block`, B (and B2) are block-scaled packed int8 weights
    (core.quant) streamed at 1 byte/element and dequantized in-kernel;
    b_layout="nk" streams a weight stored output-major (QuantSpec.transpose)
    without materializing its transpose.
    """
    m, ka = a.shape
    if b_layout == "nk":
        n, kb = b.shape
    else:
        kb, n = b.shape
    assert ka == kb, (a.shape, b.shape)
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
    assert m % block_m == 0 and n % block_n == 0 and ka % block_k == 0, (
        (m, n, ka),
        (block_m, block_n, block_k),
    )
    q_eff = None
    if q_block is not None:
        b_tile = ((block_n, block_k) if b_layout == "nk"
                  else (block_k, block_n))
        s_blk, s_div, q_eff = scale_layout(b_tile, q_block)
    grid = (m // block_m, n // block_n, ka // block_k)
    kernel = functools.partial(_gemm_kernel, nk=grid[2], epi=epilogue,
                               q_block=q_eff, b_layout=b_layout)
    out_dt = out_dtype or a.dtype
    # accumulate in max(f32, operand dtype): f64 stays f64 (DGEMM proper)
    acc_dtype = jnp.promote_types(jnp.float32, a.dtype)
    if b_layout == "nk":
        b_spec = pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k))
        s_idx = (lambda i, j, k: (j // s_div[0], k // s_div[1])) if q_block else None
    else:
        b_spec = pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j))
        s_idx = (lambda i, j, k: (k // s_div[0], j // s_div[1])) if q_block else None
    operands = [a, b]
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
        b_spec,
    ]
    scratch = [pltpu.VMEM((block_m, block_n), acc_dtype)]
    if scales is not None:
        operands.append(scales)
        in_specs.append(pl.BlockSpec(s_blk, s_idx))
    if epilogue.gate:
        assert b2.shape == b.shape, (b.shape, b2.shape)
        operands.append(b2)
        in_specs.append(b_spec)
        if scales is not None:
            operands.append(b2_scales)
            in_specs.append(pl.BlockSpec(s_blk, s_idx))
        scratch.append(pltpu.VMEM((block_m, block_n), acc_dtype))
    if epilogue.bias:
        assert bias.shape == (1, n), (bias.shape, n)
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)))
    if epilogue.residual:
        assert residual.shape == (m, n), (residual.shape, (m, n))
        operands.append(residual)
        in_specs.append(pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dt),
        scratch_shapes=scratch,
        compiler_params=_compat.compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*operands)


def epi_operands_match(epi: Epilogue, gate_op, bias, residual) -> bool:
    """Spec flags and operand presence must agree (shared by the kernels)."""
    return (
        epi.gate == (gate_op is not None)
        and epi.bias == (bias is not None)
        and epi.residual == (residual is not None)
    )

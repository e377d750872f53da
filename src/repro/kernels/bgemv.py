"""Batched GEMV Pallas kernel: the decode step's projections.

Single GEMV is the paper's worst case — 40% of peak, O(1) reuse.  Batching
is the classic remedy (KBLAS, arXiv:1410.1726), and decode is the case
where it pays most: every live slot multiplies the SAME weight matrix, so
each weight element has B uses, one per slot, if it is read once for all.

Two A layouts, chosen by `a.ndim`:
  - broadcast A (m, n): one weight for the whole batch, the serving decode
    case.  The batch sits inside the block: the grid is (m/bm, n/bn) with
    the n sweep innermost, x is one (B, bn) block, and each step contracts
    one weight tile against every member's vector in ONE MXU product into a
    (B, bm) f32 accumulator, so each weight tile is read from HBM exactly
    once per call and the output (B, m) is written lane-dense.  A batch too
    large for one block beside the tiles under the VMEM budget
    (`tiling.plan_bgemv`) splits into the fewest batch blocks that fit,
    grid (m/bm, B/bb, n/bn): the weight is then read once per batch block.
  - batched A (batch, m, n): one matrix per member and nothing to share;
    the grid is (m/bm, batch, n/bn), one member a block.
  - a layer stack (L, m, n) with `layer`: a broadcast A per layer.  The
    layer index rides in SMEM as a scalar-prefetch operand and every weight
    (and scale) index map leads with it, so the decode step's layer scan
    hands the kernel its whole stack and the kernel reads the layer's tiles
    straight from HBM: no per-layer copy of the weights, which XLA would
    otherwise cut out of the stack (into VMEM where it fits) before the
    kernel ran.  Grid and blocks are the broadcast case's.

The weight tile is (bm, bn), contracted as x @ tile^T (dim 1 with dim 1,
no data transpose), or (bn, bm) under transpose_a, contracted as x @ tile:
the model-layer projection x @ W streamed in its HBM (d, f) layout instead
of a W.T materialized every decode step.  Products are taken at the
operands' own precision: bf16 x bf16 into f32, f32 and f64 at HIGHEST.

Packed int8 weights (`scales`/`q_block`, core.quant, stored output-major)
stream at 1 byte/element; the tile is converted to the activations' dtype
(exact for int8) and contracted unscaled, the activations as they are
(W8A16: they are never quantized).  One scale per qm output rows across the
whole contraction — the serving layout — multiplies the (B, bm) accumulator
once, in the flush, before the epilogue; a scale grid that changes along
n scales each sub-block's partial before it is added.

Ragged shapes need no caller-side padding: the grid is cdiv-shaped, the
contraction fringe is masked in-VMEM on its last step (both operands, so
no 0 * garbage reaches the accumulator), and Pallas clips the output
fringes on the write.

The last-n-step flush applies the fused epilogue (core.epilogue): bias,
activation, the dual-GEMV gate multiply (`a2`: a second weight with its own
accumulator, so a decode-step SwiGLU silu(W_g^T x) * (W_u^T x) is one
launch) and the residual, on the VMEM-resident accumulator before the
single HBM write.
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
from repro.kernels.gemv import fit_block_to_quant, scale_layout


def _row_scales(s, qm: int, bm: int):
    """(g, 1) scales of consecutive qm-row groups -> (1, bm), each repeated
    over its group's lanes.  A select, not a product: a garbage scale read
    past a ragged m fringe stays in its own (clipped) columns."""
    g = s.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (g, bm), 1)
    lo = jax.lax.broadcasted_iota(jnp.int32, (g, bm), 0) * qm
    mine = (col >= lo) & (col < lo + qm)
    return jnp.sum(jnp.where(mine, s, 0.0), axis=0, keepdims=True)


def _bgemv_kernel(
    *refs, n_axis: int, nn: int, n: int, block_n: int, stacked: bool,
    trans: bool, epi: Epilogue, q_block, scale_in_flush: bool, precision
):
    # refs: [layer] a x [a_scales] [a2] [a2_scales] [bias] [residual]
    #       o acc [acc2]; the layer index is read by the index maps alone
    refs = list(refs[1:] if stacked else refs)
    a_ref, x_ref = refs.pop(0), refs.pop(0)
    a_s_ref = refs.pop(0) if q_block else None
    a2_ref = refs.pop(0) if epi.gate else None
    a2_s_ref = refs.pop(0) if (epi.gate and q_block) else None
    bias_ref = refs.pop(0) if epi.bias else None
    res_ref = refs.pop(0) if epi.residual else None
    o_ref, acc_ref = refs[0], refs[1]
    acc2_ref = refs[2] if epi.gate else None
    bm = acc_ref.shape[1]

    j = pl.program_id(n_axis)  # the n sweep, innermost

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if epi.gate:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    def accumulate(mask: bool):
        x = x_ref[...]                                   # (bb, bn)
        cd = x.dtype if q_block else jnp.promote_types(x.dtype, a_ref.dtype)
        if mask:
            # the ragged contraction fringe: zero it on BOTH operands
            cols = j * block_n + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_n), 1)
            x = jnp.where(cols < n, x, 0)
        x = x.astype(cd)
        dims = (((1,), (0,)), ((), ())) if trans else (((1,), (1,)), ((), ()))

        def dot(xs, a):
            return jax.lax.dot_general(xs, a, dims, precision=precision,
                                       preferred_element_type=acc_ref.dtype)

        def contract(ref, s_ref):
            a = ref[...]      # (bm, bn), or (bn, bm) under transpose_a
            if mask:
                shape = (block_n, 1) if trans else (1, block_n)
                pos = j * block_n + jax.lax.broadcasted_iota(
                    jnp.int32, shape, 0 if trans else 1)
                a = jnp.where(pos < n, a, 0)
            a = a.astype(cd)
            if not q_block or scale_in_flush:
                return dot(x, a)
            # the scale changes along n: scale each sub-block's partial
            qm, qn = q_block
            part = 0.0
            for c in range(block_n // qn):
                sub = slice(c * qn, (c + 1) * qn)
                part += dot(x[:, sub], a[:, sub]) * _row_scales(
                    s_ref[:, c:c + 1], qm, bm)
            return part

        acc_ref[...] += contract(a_ref, a_s_ref)
        if epi.gate:
            acc2_ref[...] += contract(a2_ref, a2_s_ref)

    if n % block_n:
        pl.when(j < nn - 1)(lambda: accumulate(False))
        pl.when(j == nn - 1)(lambda: accumulate(True))
    else:
        accumulate(False)

    @pl.when(j == nn - 1)
    def _flush():
        acc = acc_ref[...]
        acc2 = acc2_ref[...] if epi.gate else None
        if scale_in_flush:
            acc = acc * _row_scales(a_s_ref[...], q_block[0], bm)
            if epi.gate:
                acc2 = acc2 * _row_scales(a2_s_ref[...], q_block[0], bm)
        h = epi.apply(
            acc, acc2=acc2,
            bias=bias_ref[...] if epi.bias else None,        # (1, bm)
            residual=res_ref[...] if epi.residual else None,  # (bb, bm)
        )
        o_ref[...] = h.astype(o_ref.dtype)


def bgemv(
    a: jnp.ndarray,  # ((batch,) m, n), or ((batch,) n, m) when transpose_a
    x: jnp.ndarray,  # (batch, n)
    *,
    a2: jnp.ndarray = None,        # same layout as a: dual-GEMV gate operand
    bias: jnp.ndarray = None,      # (1, m)
    residual: jnp.ndarray = None,  # (batch, m)
    epilogue: Epilogue = Epilogue(),
    transpose_a: bool = False,
    scales: jnp.ndarray = None,     # (m/qm, n/qn) f32: a is packed int8
    a2_scales: jnp.ndarray = None,  # same structure for the gate operand
    q_block: tuple = None,          # (qm, qn) quant block
    layer: jnp.ndarray = None,      # () int: a holds one weight per layer
    out_dtype=None,
    block_b: int = None,
    block_m: int = 512,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """y[b] = epilogue(op(A[b]) @ x[b] [, op(A2[b]) @ x[b]]) -> (batch, m);
    2-D A broadcasts, op = A^T under transpose_a.

    A broadcast A runs `block_b` batch rows a block (default: the whole
    batch, so each weight tile is read once); a 3-D A one member a block.
    With `layer`, a 3-D A (and A2, scales) is a layer stack instead, one
    broadcast weight per layer on the leading axis: the index maps read
    layer `layer`'s tiles straight out of the stack, so no per-layer copy
    of the weight is made.

    With `scales`/`q_block`, A (and A2) are block-scaled packed int8 weights
    (core.quant) streamed at 1 byte/element, converted in-kernel and scaled
    on the accumulator — the serving decode case where the broadcast weight
    dominates HBM traffic.  Quantized weights are pre-laid-out output-major
    (QuantSpec.transpose), so transpose_a is not combined with them.
    """
    stacked = layer is not None
    a_batched = a.ndim == 3 and not stacked
    if stacked:
        assert a.ndim == 3, a.shape
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
    block_m, block_n = min(block_m, m), min(block_n, n)
    q_eff, scale_in_flush = None, False
    if q_block is not None:
        assert not transpose_a and not a_batched, (
            "packed weights stream in their stored (output-major) layout; "
            "quantize with QuantSpec(transpose=True) instead of transpose_a"
        )
        assert (a2 is None) == (a2_scales is None)
        qm, qn = q_block
        assert m % qm == 0 and n % qn == 0, ((m, n), q_block)
        block_m = fit_block_to_quant(block_m, qm)
        block_n = fit_block_to_quant(block_n, qn)
        s_tile, s_div, q_eff = scale_layout((block_m, block_n), q_block)
        # one scale per row group across the whole contraction: it can
        # wait for the flush (the serving layout, QuantSpec(block_n=None))
        scale_in_flush = qn == n
    if a_batched:
        block_b, nb = 1, batch
    else:
        block_b = min(block_b or batch, batch)
        nb = pl.cdiv(batch, block_b)
    mb, nn = pl.cdiv(m, block_m), pl.cdiv(n, block_n)
    # the batch-block axis only where there is more than one batch block:
    # (m/bm, n/bn) is the decode grid, each weight tile read once
    grid = (mb, nb, nn) if nb > 1 else (mb, nn)

    def ids(*g):  # grid coordinates -> (row block, batch block, n step)
        g = g[:len(grid)]   # a layer stack's index maps also get the layer
        return g[0], (g[1] if nb > 1 else 0), g[-1]

    def spec(block, index):
        return pl.BlockSpec(block, lambda *g: index(*ids(*g)))

    def layer_spec(block, index):
        # the layer's block of a layer stack: its axis squeezed out
        if not stacked:
            return spec(block, index)
        return pl.BlockSpec((None,) + block,
                            lambda *g: (g[-1][0],) + index(*ids(*g)))

    if transpose_a:
        a_block, a_idx = (block_n, block_m), lambda i, b, j: (j, i)
    else:
        a_block, a_idx = (block_m, block_n), lambda i, b, j: (i, j)
    if a_batched:
        # one member a block: the batch dim squeezed out of every block
        a_spec = spec((None,) + a_block, lambda i, b, j: (b,) + a_idx(i, b, j))
        x_op, x_spec = x[:, None, :], spec((None, 1, block_n),
                                           lambda i, b, j: (b, 0, j))
        o_shape = (batch, 1, m)
        o_spec = spec((None, 1, block_m), lambda i, b, j: (b, 0, i))
    else:
        a_spec = layer_spec(a_block, a_idx)
        x_op, x_spec = x, spec((block_b, block_n), lambda i, b, j: (b, j))
        o_shape = (batch, m)
        o_spec = spec((block_b, block_m), lambda i, b, j: (b, i))
    out_dt = out_dtype or (x.dtype if scales is not None else a.dtype)
    # accumulate in max(f32, operand dtype): f64 stays f64 (DGEMV proper)
    acc_dtype = jnp.promote_types(jnp.float32, out_dt)
    cd = x.dtype if scales is not None else jnp.promote_types(x.dtype, a.dtype)
    precision = (jax.lax.Precision.HIGHEST if jnp.dtype(cd).itemsize >= 4
                 else None)
    acc_shape = (1 if a_batched else block_b, block_m)
    operands, in_specs = [a, x_op], [a_spec, x_spec]
    scratch = [pltpu.VMEM(acc_shape, acc_dtype)]
    s_spec = None
    if scales is not None:
        s_spec = layer_spec(s_tile,
                            lambda i, b, j: (i // s_div[0], j // s_div[1]))
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
        assert bias.shape == (1, m), (bias.shape, m)
        operands.append(bias)
        in_specs.append(spec((1, block_m), lambda i, b, j: (0, i)))
    if epilogue.residual:
        assert residual.shape == (batch, m), (residual.shape, (batch, m))
        operands.append(residual.reshape(o_shape))
        in_specs.append(o_spec)
    kernel = functools.partial(
        _bgemv_kernel, n_axis=len(grid) - 1, nn=nn, n=n, block_n=block_n,
        stacked=stacked, trans=transpose_a, epi=epilogue, q_block=q_eff,
        scale_in_flush=scale_in_flush, precision=precision,
    )
    if stacked:
        # the layer index rides in SMEM, ahead of the grid, for index maps
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=o_spec, scratch_shapes=scratch)
        operands.insert(0, jnp.asarray(layer, jnp.int32).reshape(1))
    else:
        grid_spec = pl.GridSpec(grid=grid, in_specs=in_specs,
                                out_specs=o_spec, scratch_shapes=scratch)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(o_shape, out_dt),
        compiler_params=_compat.compiler_params(
            *("parallel",) * (len(grid) - 1), "arbitrary"),
        interpret=interpret,
    )(*operands)
    return out.reshape(batch, m)

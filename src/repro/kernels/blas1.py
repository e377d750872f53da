"""Level-1 BLAS Pallas kernels: ddot / daxpy / dnrm2.

These are the paper's 20%-of-peak case: pure streaming reductions with zero
reuse.  The kernels tile the vector into (1, bn) VMEM strips; partial sums
accumulate in an SMEM-sized scratch and the scalar result is written on the
last grid step.  daxpy is one fully-parallel DAG level (paper Fig 3) and
needs no scratch at all.

Accumulation runs in max(f32, operand dtype): low-precision operands widen
to f32, and f64 operands (the paper's D-prefix DDOT/DNRM2/DAXPY proper)
accumulate in f64 instead of being silently degraded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _compat


def _reduce_kernel(x_ref, y_ref, o_ref, acc_ref, *, nn: int, n: int,
                   block_n: int, mode: str):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(acc_ref.dtype)
    y = y_ref[...].astype(acc_ref.dtype)
    # mask the ragged tail in-kernel (no caller padding): OOB strip reads
    # are undefined and must not reach the accumulator
    cols = j * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    acc_ref[...] += jnp.sum(jnp.where(cols < n, x * y, 0.0), keepdims=True)

    @pl.when(j == nn - 1)
    def _flush():
        acc = acc_ref[...]
        if mode == "nrm2":
            acc = jnp.sqrt(acc)
        o_ref[...] = acc.astype(o_ref.dtype)


def _reduce(x, y, mode, block_n, interpret):
    (n,) = x.shape
    block_n = min(block_n, n)
    grid = (pl.cdiv(n, block_n),)
    kernel = functools.partial(_reduce_kernel, nn=grid[0], n=n,
                               block_n=block_n, mode=mode)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.promote_types(jnp.float32, x.dtype))],
        compiler_params=_compat.compiler_params("arbitrary"),
        interpret=interpret,
    )(x[None, :], y[None, :])
    return out[0, 0]


def dot(x: jnp.ndarray, y: jnp.ndarray, *, block_n: int = 2048, interpret: bool = False):
    return _reduce(x, y, "dot", block_n, interpret)


def nrm2(x: jnp.ndarray, *, block_n: int = 2048, interpret: bool = False):
    return _reduce(x, x, "nrm2", block_n, interpret)


def _axpy_kernel(alpha_ref, x_ref, y_ref, o_ref):
    acc = alpha_ref.dtype
    o_ref[...] = (alpha_ref[0, 0] * x_ref[...].astype(acc) + y_ref[...].astype(acc)).astype(o_ref.dtype)


def axpy(alpha, x: jnp.ndarray, y: jnp.ndarray, *, block_n: int = 2048, interpret: bool = False):
    # ragged n needs no in-kernel mask: axpy is elementwise, the tail strip's
    # undefined lanes never cross an accumulator, and Pallas clips the write
    (n,) = x.shape
    block_n = min(block_n, n)
    alpha = jnp.asarray(alpha, jnp.promote_types(jnp.float32, x.dtype)).reshape(1, 1)
    out = pl.pallas_call(
        _axpy_kernel,
        grid=(pl.cdiv(n, block_n),),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype),
        compiler_params=_compat.compiler_params("parallel"),
        interpret=interpret,
    )(alpha, x[None, :], y[None, :])
    return out[0]

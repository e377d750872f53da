"""Chunked Mamba2 SSD Pallas kernel (scalar-per-head decay).

Same re-blocking move as the RWKV6 kernel, but the decay is a scalar per
(head, step), so the pairwise discount matrix is (C x C) — cheap — and both
heavy contractions (C B^T and A X) hit the MXU.  This is the semiseparable
matmul view of SSMs: the chunked algorithm turns a length-T dependency chain
into T/C GEMM blocks plus a rank-N carry, which is precisely the paper's
"break the accumulation chain with blocking" insight (S4.3.5).

    H_t = exp(a_t) H_{t-1} + b_t x_t^T        (a_t <= 0 log-decay)
    y_t = c_t^T H_t

All pairwise exponents are sums of log-decays over forward intervals, so
they are <= 0 and overflow-free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _compat


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *, chunk: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)     # (C, P)
    a = a_ref[...].astype(jnp.float32)   # (1, C)
    b = b_ref[0].astype(jnp.float32)     # (C, N)
    c = c_ref[0].astype(jnp.float32)     # (C, N)

    L = jnp.cumsum(a, axis=1)            # (1, C) inclusive
    Lc = L.T                             # (C, 1)

    # inter-chunk: y_t += exp(L_t) * c_t^T H0
    y = jnp.exp(Lc) * jax.lax.dot_general(
        c, h_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                    # (C, P)

    # intra-chunk: A[t,s] = (c_t . b_s) exp(L_t - L_s), s <= t (inclusive)
    E = Lc - L                           # (C, C); E[t,s] = L_t - L_s
    mask = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    A = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(jnp.minimum(E, 0.0)) * mask
    y += jax.lax.dot_general(
        A, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[0] = y.astype(y_ref.dtype)

    # carry: H <- exp(L_C) H0 + (b * exp(L_C - L))^T x
    l_last = L[0, -1]
    b_scaled = b * jnp.exp(l_last - L.T)  # (C, N), exponents <= 0
    h_ref[...] = jnp.exp(l_last) * h_ref[...] + jax.lax.dot_general(
        b_scaled, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def ssd(
    x: jnp.ndarray,      # (BH, T, P)
    a_log: jnp.ndarray,  # (BH, T) log-decay <= 0
    b: jnp.ndarray,      # (BH, T, N)
    c: jnp.ndarray,      # (BH, T, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y (BH, T, P).  T must divide by `chunk` (ops pads)."""
    bh, t, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    grid = (bh, t // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, chunk), lambda bb, i: (bb, i)),
            pl.BlockSpec((1, chunk, n), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, i: (bb, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bb, i: (bb, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=_compat.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(x, a_log, b, c)

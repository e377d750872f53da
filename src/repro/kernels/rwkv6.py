"""Chunked RWKV6 (Finch) WKV Pallas kernel.

The WKV6 recurrence
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T          (w_t = log-decay <= 0)

is a stream of rank-1 GEMV updates — the paper's Level-2 DAG (Fig 4) with a
data-dependent diagonal discount.  Token-at-a-time execution is dependency-
bound exactly like the paper's DDOT accumulator chain (20% of peak), so the
kernel re-blocks time into chunks (the 4x4-block move, applied to the time
dimension): within a chunk all pairwise interactions become one (C x C)
matrix, the cross-chunk carry is a single (K x V) state held in VMEM scratch
across the sequential grid axis.

Numerical-stability invariant: every exponent evaluated is a sum of log-decays
over a *forward* interval and therefore <= 0 — the kernel computes pairwise
exponents  E[t, s] = Lprev[t] - L[s]  (valid only for s < t, masked) directly
instead of factoring into exp(Lprev[t]) * exp(-L[s]) whose second factor
overflows under strong decay.  Cost: the intra-chunk attention is O(C^2 K)
VPU work; with C = 32 this is < 3% of the layer's GEMM flops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _compat


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *, nt: int, chunk: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)   # (C, K)
    k = k_ref[0].astype(jnp.float32)   # (C, K)
    v = v_ref[0].astype(jnp.float32)   # (C, V)
    w = w_ref[0].astype(jnp.float32)   # (C, K) log-decay <= 0
    u = u_ref[0].astype(jnp.float32)   # (1, K)

    L = jnp.cumsum(w, axis=0)          # L[t] = sum_{j<=t} w_j
    Lprev = L - w                      # exclusive cumsum (L[t-1], with L[-1] = 0)

    # ---- inter-chunk: contribution of carried state S ----------------------
    q_tilde = r * jnp.exp(Lprev)                               # (C, K) exp <= 0 safe
    y = jax.lax.dot_general(
        q_tilde, s_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # (C, V)

    # ---- intra-chunk: pairwise form with provably <= 0 exponents -----------
    # E[t, s, i] = Lprev[t, i] - L[s, i]  (== sum_{j=s+1}^{t-1} w_j for s < t)
    E = Lprev[:, None, :] - L[None, :, :]                      # (C, C, K)
    mask = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)  # strict lower
    A = jnp.sum(
        r[:, None, :] * k[None, :, :] * jnp.exp(jnp.minimum(E, 0.0)), axis=-1
    ) * mask                                                   # (C, C)
    y += jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # diagonal "bonus" term: y_t += (r_t . (u * k_t)) v_t
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)           # (C, 1)
    y += diag * v

    y_ref[0] = y.astype(y_ref.dtype)

    # ---- state update: S <- D(exp(L_C)) S + (k * exp(L_C - L))^T v ---------
    l_last = L[-1:, :]                                         # (1, K)
    k_scaled = k * jnp.exp(l_last - L)                         # exponent <= 0 safe
    s_ref[...] = jnp.exp(l_last).T * s_ref[...] + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def rwkv6(
    r: jnp.ndarray,      # (BH, T, K)
    k: jnp.ndarray,      # (BH, T, K)
    v: jnp.ndarray,      # (BH, T, V)
    w_log: jnp.ndarray,  # (BH, T, K) log-decay <= 0
    u: jnp.ndarray,      # (BH, K)
    *,
    chunk: int = 32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y (BH, T, V).  T must divide by `chunk` (ops pads)."""
    bh, t, kk = r.shape
    vv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    grid = (bh, t // chunk)
    kernel = functools.partial(_wkv6_kernel, nt=grid[1], chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, kk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, vv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, kk), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, vv), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, vv), r.dtype),
        scratch_shapes=[pltpu.VMEM((kk, vv), jnp.float32)],
        compiler_params=_compat.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(r, k, v, w_log, u[:, None, :])

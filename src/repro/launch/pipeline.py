"""GPipe-style pipeline parallelism over a mesh axis (optional PP mode).

Layer-stacked params are split into S contiguous stages (sharded over the
`stage` axis, dim 0); microbatches stream through the stages with the
activation handoff done by collective_permute.  Tick t: stage s processes
microbatch (t - s); the classic (M + S - 1)-tick schedule with bubble
fraction (S-1)/(M+S-1).

This is the paper's NoC-pipelined tile execution (S5.5) in its sequential-
dependency form: where block-parallel GEMM partitions *independent* output
blocks, a layer stack is a dependency chain, so the tiles pipeline instead.

Correctness is asserted against the sequential scan in
tests/test_distributed.py; the dry-run exposes it as an alternate config
(pp_demo) showing the collective-permute schedule in the HLO.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stacked_params, x_micro, block_fn, mesh, axis: str = "stage"):
    """Run x through all L stacked layers, S-stage pipelined.

    stacked_params: pytree with leading dim L (L % S == 0), sharded over
        `axis` at dim 0 inside shard_map (each stage holds L/S layers).
    x_micro: (M, mb, T, d) microbatched input (replicated).
    block_fn(layer_params, x) -> x  — one layer.

    Returns (M, mb, T, d) outputs (replicated; produced on the last stage
    and broadcast via masked psum).
    """
    s = mesh.shape[axis]

    def stage_fn(params_loc, h):
        def body(carry, lp):
            return block_fn(lp, carry), None

        out, _ = jax.lax.scan(body, h, params_loc)
        return out

    def pipe(params_loc, x_loc):
        sid = jax.lax.axis_index(axis)
        m = x_loc.shape[0]
        ticks = m + s - 1
        fwd_perm = [(i, i + 1) for i in range(s - 1)]

        def tick(t, carry):
            out_buf, h_in = carry
            # stage 0 pulls microbatch t (clamped; masked later)
            x0 = jax.lax.dynamic_index_in_dim(
                x_loc, jnp.clip(t, 0, m - 1), 0, keepdims=False
            )
            inp = jnp.where(sid == 0, x0, h_in)
            h_out = stage_fn(params_loc, inp)
            # hand off to the next stage
            h_next = jax.lax.ppermute(h_out, axis, fwd_perm)
            # last stage commits microbatch t-(s-1)
            widx = t - (s - 1)
            valid = (widx >= 0) & (widx < m) & (sid == s - 1)
            c = jnp.clip(widx, 0, m - 1)
            old = jax.lax.dynamic_index_in_dim(out_buf, c, 0, keepdims=False)
            new = jnp.where(valid, h_out, old)
            out_buf = jax.lax.dynamic_update_index_in_dim(out_buf, new, c, 0)
            return out_buf, h_next

        out0 = jnp.zeros_like(x_loc)
        h0 = jnp.zeros_like(x_loc[0])
        out_buf, _ = jax.lax.fori_loop(0, ticks, tick, (out0, h0))
        # broadcast the last stage's buffer to everyone (masked psum)
        mask = (sid == s - 1).astype(out_buf.dtype)
        return jax.lax.psum(out_buf * mask, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stacked_params)
    return jax.shard_map(
        pipe, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, x_micro)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe bubble overhead — the PP analog of the paper's alpha (Eq 7)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)

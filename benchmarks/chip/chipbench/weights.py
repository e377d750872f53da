"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, in the type they are served in (bf16), and
hands them to the program through the adapter; the reference makes the same
ones again after the window.  Norm weights and biases are random too, so
that the comparison covers the paths that carry them.

Layout: per-layer weights are stacked on a leading layer axis; a projection
is stored (in, out), so y = x @ w.  A norm has `w` (and `b` for LayerNorm);
RMSNorm's `w` is the multiplier itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.spec import Spec

#: spread of the random norm weights around 1 and of the random biases
NORM_SPREAD = 0.1


def shapes(s: Spec) -> dict:
    """{name: shape} of every leaf, layer leaves with their layer axis."""
    d, L, q, kv = s.hidden, s.layers, s.heads * s.head_dim, s.kv_heads * s.head_dim
    out = {"embed": (s.vocab, d), "final_norm.w": (d,),
           "ln1.w": (L, d), "ln2.w": (L, d),
           "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv), "wo": (L, q, d),
           "w_gate": (L, d, s.ff), "w_up": (L, d, s.ff), "w_down": (L, s.ff, d)}
    if not s.tied:
        out["head"] = (d, s.vocab)
    if s.norm == "layer":
        out.update({"final_norm.b": (d,), "ln1.b": (L, d), "ln2.b": (L, d)})
    if s.qkv_bias:
        out.update({"bq": (L, q), "bk": (L, kv), "bv": (L, kv)})
    return out


def _leaf(key, name: str, shape) -> jnp.ndarray:
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith(".w"):
        x = 1.0 + NORM_SPREAD * z
    elif name.endswith(".b") or name in ("bq", "bk", "bv"):
        x = NORM_SPREAD * z
    elif name == "embed":
        x = z * shape[-1] ** -0.5
    else:                      # a projection (in, out): fan-in scaling
        x = z * shape[-2] ** -0.5
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=0)
def _make(s: Spec, key) -> dict:
    names = sorted(shapes(s))
    keys = jax.random.split(key, len(names))
    return {n: _leaf(k, n, shapes(s)[n]) for n, k in zip(names, keys)}


def key_of(seed: int):
    """A PRNG key for any non-negative seed, however many bits it has."""
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return jax.random.fold_in(key, seed // 2 ** 32)


def make(s: Spec, seed: int) -> dict:
    """Every weight of the model as a flat {name: bf16 array} on the
    default device."""
    return _make(s, key_of(seed))

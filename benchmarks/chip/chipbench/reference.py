"""The plain reference: the configuration's model in float32 `jax.numpy`.

No cache, no kernels, no batching: one sequence at a time, each layer one
jitted call over the whole sequence with causal attention, every product at
`precision="highest"`.  It reads the model from the configuration file
(`spec.Spec`) and its weights from `weights.make`, and imports nothing of
the program.

The same forward, with `bits` set, is the control: every product's operands
(weights per output feature, activations per token) and the attention keys
and values (per token and head) rounded to `bits`-bit integers, as a change
that computed in the next lower precision would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.spec import Spec

PRECISION = jax.lax.Precision.HIGHEST
#: sequences are padded at the end to a multiple of this, so that a few
#: compiled shapes serve every length; causal attention leaves the real
#: positions untouched by the padding
BUCKET = 256
#: positions whose logits are formed at once
ROWS = 512

LAYER_KEYS = ("ln1.w", "ln1.b", "ln2.w", "ln2.b", "wq", "wk", "wv", "wo",
              "bq", "bk", "bv", "w_gate", "w_up", "w_down")


def fake_quant(x, bits: int, axis: int):
    """Symmetric integer rounding of `x` with one scale per slice along
    `axis` (absolute maximum to the largest code)."""
    top = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -top, top) * scale


def _mm(a, w, bits):
    w = w.astype(jnp.float32)
    if bits:
        a, w = fake_quant(a, bits, -1), fake_quant(w, bits, 0)
    return jnp.matmul(a, w, precision=PRECISION)


def _norm(s: Spec, x, w, b):
    if s.norm == "layer":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + s.norm_eps) * w + b
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + s.norm_eps) * w


def _rope(s: Spec, x):
    """Rotary embedding on the first `rotary_dim` features of each head,
    the two halves of that span rotated against each other."""
    t, r = x.shape[0], s.rotary_dim
    half = r // 2
    freqs = s.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(s: Spec, bits, x, w, layer):
    lw = {k: w[k][layer] for k in LAYER_KEYS if k in w}
    f = lambda k: lw[k].astype(jnp.float32)
    t, hd = x.shape[0], s.head_dim
    h = _norm(s, x, f("ln1.w"), f("ln1.b") if s.norm == "layer" else 0.0)
    q, k, v = (_mm(h, lw[n], bits) for n in ("wq", "wk", "wv"))
    if s.qkv_bias:
        q, k, v = q + f("bq"), k + f("bk"), v + f("bv")
    q = _rope(s, q.reshape(t, s.heads, hd))
    k = _rope(s, k.reshape(t, s.kv_heads, hd))
    v = v.reshape(t, s.kv_heads, hd)
    if bits:
        k, v = fake_quant(k, bits, -1), fake_quant(v, bits, -1)
    groups = s.heads // s.kv_heads
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k, precision=PRECISION) * hd ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=PRECISION)
    x = x + _mm(o.reshape(t, s.heads * hd), lw["wo"], bits)
    h = _norm(s, x, f("ln2.w"), f("ln2.b") if s.norm == "layer" else 0.0)
    mid = jax.nn.silu(_mm(h, lw["w_gate"], bits)) * _mm(h, lw["w_up"], bits)
    return x + _mm(mid, lw["w_down"], bits)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(s: Spec, bits, x, rows, w, head, tokens):
    """Final norm and logits at `rows` of `x`; returns (best logit minus
    the logit of `tokens`, argmax) per row."""
    h = _norm(s, x[rows], w["final_norm.w"].astype(jnp.float32),
              w["final_norm.b"].astype(jnp.float32) if s.norm == "layer"
              else 0.0)
    logits = _mm(h, head, bits)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(logits, -1) - got, jnp.argmax(logits, -1).astype(jnp.int32)


def hidden(s: Spec, w: dict, tokens: np.ndarray, bits=None):
    """Residual stream after the last layer, (T_padded, d), for one
    sequence."""
    t = len(tokens)
    padded = np.zeros(-(-t // BUCKET) * BUCKET, np.int32)
    padded[:t] = tokens
    x = _embed(w["embed"], jnp.asarray(padded))
    stacked = {k: w[k] for k in LAYER_KEYS if k in w}
    for layer in range(s.layers):
        x = _layer(s, bits, x, stacked, jnp.int32(layer))
    return x


def gaps(s: Spec, w: dict, seqs, control_bits=None) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over `seqs` = [(prompt, served tokens)]: the
    logits at the prompt's last position and at each served token but the
    last are those its next token was chosen from.  With `control_bits`,
    also the widest such gap of the tokens the control ranks first at the
    same positions.  A token outside the vocabulary reads as an infinite
    gap."""
    head_w = w["embed"].T if s.tied else w["head"]
    norm_w = {k: w[k] for k in ("final_norm.w", "final_norm.b") if k in w}
    out = {"program": 0.0, "control": 0.0 if control_bits else None}
    for prompt, served in seqs:
        served = np.asarray(served, np.int64)
        if served.size == 0:
            continue
        if served.min() < 0 or served.max() >= s.vocab:
            out["program"] = float("inf")
            continue
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              served[:-1].astype(np.int32)])
        rows = np.arange(len(prompt) - 1, len(seq))
        x = hidden(s, w, seq)
        xc = hidden(s, w, seq, control_bits) if control_bits else None
        for lo in range(0, len(rows), ROWS):
            # every chunk is ROWS long (the tail repeats its last row), so
            # one compiled head serves them all
            n = min(ROWS, len(rows) - lo)
            r = np.full(ROWS, rows[lo + n - 1], np.int32)
            r[:n] = rows[lo:lo + n]
            want = np.zeros(ROWS, np.int32)
            want[:n] = served[lo:lo + n]
            r, want = jnp.asarray(r), jnp.asarray(want)
            gap, _ = _head(s, None, x, r, norm_w, head_w, want)
            out["program"] = max(out["program"], float(jnp.max(gap[:n])))
            if xc is not None:
                _, first = _head(s, control_bits, xc, r, norm_w, head_w, want)
                cgap, _ = _head(s, None, x, r, norm_w, head_w, first)
                out["control"] = max(out["control"], float(jnp.max(cgap[:n])))
    return out

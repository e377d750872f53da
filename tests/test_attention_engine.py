"""ONE attention engine: pallas-backend dispatch + parity per mask variant.

The serving stack used to run TWO attention engines — the flash Pallas
kernel for packed causal decode and `layers.attention_core` for everything
else (prefix-LM, non-causal, dense prefill) — and the duplicate path is
where the parity bugs lived.  This suite pins the unification:

  - flash kernel parity vs the `kernels.ref` / `attention_core` oracles for
    every mask variant (causal, prefix-LM, non-causal) across GQA groups,
    ragged/prime Tq/Tk, and dense/int8 caches;
  - a dispatch spy proving `attention_core` is UNREACHABLE from
    `attention_layer` (and the whisper cross-attention) under the pallas
    backend, for any (mask, cache-dtype) combination;
  - the satellite regression: a non-causal layer never launches the kernel
    with causal=True (the old packed path hardcoded it).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blas, quant
from repro.kernels import ops, ref
from repro.models import layers
from repro.models import transformer as tf
from repro.models.registry import get_config

F32 = jnp.float32


def _cmp(a, b, rtol=2e-4, atol=2e-4):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=rtol, atol=atol,
    )


# --------------------------------------------------------------------------
# Kernel-level prefix-LM masking vs the ref oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("tq,tk,pfx", [(16, 16, 4), (97, 97, 5), (64, 64, 33)])
def test_flash_prefix_lm_matches_ref(tq, tk, pfx):
    """In-kernel prefix-LM: the first pfx ABSOLUTE key positions are
    bidirectionally visible, text after stays causal — prime/ragged extents
    exercise the fringe masking, pfx=33 crosses a block boundary."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (4, tq, 32), F32)
    k = jax.random.normal(ks[1], (4, tk, 32), F32)
    v = jax.random.normal(ks[2], (4, tk, 32), F32)
    # the kernel's (B, T, H, D) layout, one head per slot
    out = ops.flash_attention(q[:, :, None], k[:, :, None], v[:, :, None],
                              causal=True, prefix_len=pfx,
                              block_q=32, block_k=32)[:, :, 0]
    _cmp(out, ref.attention(q, k, v, causal=True, prefix_len=pfx))
    # the prefix mask must actually change the result vs plain causal
    plain = ref.attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - plain))) > 1e-3


def test_flash_prefix_lm_with_kv_lens():
    """prefix-LM + per-row real KV lengths — the vlm admission-prefill shape
    (4-D cache layout, GQA, ragged slot lengths) vs the lens oracle."""
    B, H, KV, T, S, d, pfx = 2, 4, 2, 12, 40, 16, 4
    g = H // KV
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, T, H, d), F32)
    k = jax.random.normal(ks[1], (B, S, KV, d), F32)
    v = jax.random.normal(ks[2], (B, S, KV, d), F32)
    slot_lens = jnp.asarray([12, 31], jnp.int32)
    out = ops.flash_attention(q, k, v, kv_lens=slot_lens, kv_groups=g,
                              causal=True, prefix_len=pfx, block_k=16)
    lens = jnp.repeat(slot_lens, H)  # the oracle's per-(slot, head) rows
    flat = lambda z: jnp.moveaxis(z, 2, 1).reshape(-1, z.shape[1], z.shape[3])
    want = ref.attention_lens(
        flat(q), jnp.repeat(flat(k), g, axis=0), jnp.repeat(flat(v), g, axis=0),
        lens, causal=True, prefix_len=pfx,
    )
    _cmp(jnp.moveaxis(out, 2, 1).reshape(-1, T, d), want)


# --------------------------------------------------------------------------
# Engine parity: flash dispatch vs the attention_core oracle (no cache)
# --------------------------------------------------------------------------

CASES = [
    # (causal, prefix_len, tq, tk, groups)
    (True, None, 37, 37, 1),    # prime square
    (True, None, 29, 61, 3),    # ragged + GQA (decode-aligned offset)
    (True, 5, 37, 37, 1),       # prefix-LM over a prime extent
    (True, 5, 41, 41, 3),       # prefix-LM + GQA
    (False, None, 29, 61, 3),   # cross-attention shape (whisper)
    (False, None, 97, 13, 1),   # non-causal, prime Tq > Tk
]


@pytest.mark.parametrize("causal,prefix_len,tq,tk,groups", CASES)
def test_engine_parity_no_cache(causal, prefix_len, tq, tk, groups):
    """attention_dispatch under pallas (flash kernel) vs under xla (the
    attention_core oracle) — identical operands, per mask variant."""
    b, kvh, hd = 2, 2, 16
    h = kvh * groups
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, tq, h, hd), F32)
    k = jax.random.normal(ks[1], (b, tk, kvh, hd), F32)
    v = jax.random.normal(ks[2], (b, tk, kvh, hd), F32)
    kw = dict(causal=causal, prefix_len=prefix_len, groups=groups)
    with blas.use_backend("pallas"):
        out_flash = layers.attention_dispatch(q, k, v, **kw)
    out_core = layers.attention_dispatch(q, k, v, **kw)  # xla -> oracle
    _cmp(out_flash, out_core)


# --------------------------------------------------------------------------
# Engine parity through attention_layer: dense and int8 caches
# --------------------------------------------------------------------------

def _attn_cfg(causal=True, h=4, kvh=2, hd=16):
    return layers.AttnConfig(d_model=h * hd, n_heads=h, n_kv=kvh, head_dim=hd,
                             causal=causal)


def _dense_cache(key, b, s, kvh, hd, pos):
    """Capacity-S cache pre-filled with random rows: the dead tail beyond
    the live prefix is garbage, so parity also proves both engines mask it."""
    k1, k2 = jax.random.split(key)
    return {
        "k": jax.random.normal(k1, (b, s, kvh, hd), F32),
        "v": jax.random.normal(k2, (b, s, kvh, hd), F32),
        "pos": pos,
    }


def _int8_cache(key, b, s, kvh, hd, pos):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "k": jax.random.randint(k1, (b, s, kvh, hd), -127, 128, jnp.int8),
        "v": jax.random.randint(k2, (b, s, kvh, hd), -127, 128, jnp.int8),
        "k_scale": jax.random.uniform(k3, (b, s, kvh, 1), F32, 0.01, 0.1),
        "v_scale": jax.random.uniform(k4, (b, s, kvh, 1), F32, 0.01, 0.1),
        "pos": pos,
    }


CACHE_CASES = [
    # (name, int8, causal, prefix_len, t, pos)
    ("dense_prefill_causal", False, True, None, 19, jnp.zeros((), jnp.int32)),
    ("dense_prefill_prefix", False, True, 4, 19, jnp.zeros((), jnp.int32)),
    ("dense_decode_ragged", False, True, None, 1, jnp.asarray([7, 23], jnp.int32)),
    ("int8_prefill_causal", True, True, None, 19, jnp.zeros((), jnp.int32)),
    ("int8_prefill_prefix", True, True, 4, 19, jnp.zeros((), jnp.int32)),
    ("int8_decode_ragged", True, True, None, 1, jnp.asarray([7, 23], jnp.int32)),
    ("int8_non_causal", True, False, None, 5, jnp.zeros((), jnp.int32)),
]


@pytest.mark.parametrize("name,int8,causal,prefix_len,t,pos",
                         CACHE_CASES, ids=[c[0] for c in CACHE_CASES])
def test_engine_parity_with_cache(name, int8, causal, prefix_len, t, pos):
    """Full attention_layer runs (projections + cache write + attention)
    under pallas vs xla: the flash cache path — dense bf16/f32 or packed
    int8, prefill-shaped or ragged per-slot decode, every mask — must match
    the oracle path, which now also exercises the live-prefix dequant slice
    (satellite fix) on the xla side."""
    b, s, hd = 2, 37, 16
    cfg = _attn_cfg(causal=causal)
    params = layers.init_attention(jax.random.PRNGKey(3), cfg, dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(4), (b, t, cfg.d_model), F32)
    positions = (pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
                 if pos.ndim else jnp.arange(t, dtype=jnp.int32) + pos)
    mk = _int8_cache if int8 else _dense_cache
    outs = {}
    for backend in ("pallas", "xla"):
        cache = mk(jax.random.PRNGKey(5), b, s, cfg.n_kv, hd, pos)
        with blas.use_backend(backend):
            out, new_cache = layers.attention_layer(
                params, x, cfg, positions=positions, cache=cache,
                prefix_len=prefix_len,
            )
        outs[backend] = np.asarray(out, np.float32)
        assert np.asarray(jnp.max(jnp.abs(out))).item() < 1e6
    np.testing.assert_allclose(outs["pallas"], outs["xla"], rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# Dispatch spy: attention_core unreachable under pallas
# --------------------------------------------------------------------------

def _forbid_core(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("attention_core reached under the pallas backend")
    monkeypatch.setattr(layers, "attention_core", boom)


def _spy_flash(monkeypatch):
    calls = []
    real = ops.flash_attention

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(ops, "flash_attention", spy)
    return calls


def test_attention_core_unreachable_under_pallas(monkeypatch):
    """Acceptance: for EVERY (mask, cache-dtype) combination attention_layer
    supports, the pallas backend routes through ops.flash_attention and
    never calls attention_core — proven by making the oracle raise."""
    calls = _spy_flash(monkeypatch)
    _forbid_core(monkeypatch)
    b, s, hd = 2, 37, 16
    x19 = jax.random.normal(jax.random.PRNGKey(8), (b, 19, 64), F32)
    x1 = x19[:, :1]
    combos = 0
    with blas.use_backend("pallas"):
        for int8 in (False, True):
            mk = _int8_cache if int8 else _dense_cache
            for causal, prefix_len in ((True, None), (True, 4), (False, None)):
                cfg = _attn_cfg(causal=causal)
                params = layers.init_attention(jax.random.PRNGKey(9), cfg, dtype=F32)
                # prefill-shaped (scalar pos)
                cache = mk(jax.random.PRNGKey(10), b, s, cfg.n_kv, hd,
                           jnp.zeros((), jnp.int32))
                layers.attention_layer(
                    params, x19, cfg, positions=jnp.arange(19, dtype=jnp.int32),
                    cache=cache, prefix_len=prefix_len,
                )
                combos += 1
                # ragged per-slot decode
                pos = jnp.asarray([7, 23], jnp.int32)
                cache = mk(jax.random.PRNGKey(11), b, s, cfg.n_kv, hd, pos)
                layers.attention_layer(
                    params, x1, cfg, positions=pos[:, None],
                    cache=cache, prefix_len=prefix_len,
                )
                combos += 1
        # cache-less launches (training forward / encoder self-attention)
        for causal, prefix_len in ((True, None), (True, 4), (False, None)):
            cfg = _attn_cfg(causal=causal)
            params = layers.init_attention(jax.random.PRNGKey(12), cfg, dtype=F32)
            layers.attention_layer(
                params, x19, cfg, positions=jnp.arange(19, dtype=jnp.int32),
                prefix_len=prefix_len,
            )
            combos += 1
    assert len(calls) == combos and combos == 15


def test_model_forwards_route_through_flash_under_pallas(monkeypatch):
    """Whole-model proof for the awkward families: whisper (non-causal
    encoder + cross-attention + causal decoder) and paligemma (prefix-LM
    vlm prefill) forwards never touch attention_core under pallas."""
    calls = _spy_flash(monkeypatch)
    _forbid_core(monkeypatch)
    b, t = 2, 8
    with blas.use_backend("pallas"):
        for arch in ("whisper-large-v3", "paligemma-3b"):
            cfg = get_config(arch, "smoke")
            params = tf.init_params(jax.random.PRNGKey(0), cfg)
            tokens = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0, cfg.vocab)
            batch = {"tokens": tokens}
            if cfg.family == "vlm":
                batch["patches"] = jax.random.normal(
                    jax.random.PRNGKey(2), (b, cfg.n_prefix, cfg.d_model), F32)
            if cfg.family == "audio":
                batch["frames"] = jax.random.normal(
                    jax.random.PRNGKey(2), (b, cfg.encoder.n_frames, cfg.d_model), F32)
            hidden, _, _ = tf.forward(params, batch, cfg)
            assert bool(jnp.isfinite(hidden.astype(jnp.float32)).all())
    assert calls, "no flash launches recorded"
    # whisper's encoder/cross-attention must arrive as non-causal launches
    assert any(kw.get("causal") is False for kw in calls)
    # paligemma's prefill must arrive with the prefix-LM mask in-kernel
    assert any(kw.get("prefix_len") for kw in calls)


def test_non_causal_layer_never_takes_causal_path(monkeypatch):
    """Satellite regression: the old packed flash path hardcoded causal=True
    (non-causal + int8 simply fell back).  Now a causal=False layer must
    reach the kernel with causal=False — for the int8 cache, the dense
    cache, and the cache-less launch alike — and match the xla oracle."""
    b, s, t, hd = 2, 37, 5, 16
    cfg = _attn_cfg(causal=False)
    params = layers.init_attention(jax.random.PRNGKey(13), cfg, dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(14), (b, t, cfg.d_model), F32)
    positions = jnp.arange(t, dtype=jnp.int32)
    for mk in (_int8_cache, _dense_cache, None):
        calls = _spy_flash(monkeypatch)
        outs = {}
        for backend in ("pallas", "xla"):
            cache = None if mk is None else mk(
                jax.random.PRNGKey(15), b, s, cfg.n_kv, hd, jnp.zeros((), jnp.int32))
            with blas.use_backend(backend):
                out, _ = layers.attention_layer(
                    params, x, cfg, positions=positions, cache=cache)
            outs[backend] = np.asarray(out, np.float32)
        assert calls and all(kw.get("causal") is False for kw in calls), calls
        np.testing.assert_allclose(outs["pallas"], outs["xla"],
                                   rtol=2e-3, atol=2e-3)
        monkeypatch.undo()

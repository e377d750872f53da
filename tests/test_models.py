"""Per-arch smoke tests (reduced configs, CPU): loss, decode, cache parity.

The brief requires: instantiate a REDUCED config of each assigned family and
run one forward/train step asserting output shapes + no NaNs.  We also check
the decode path against the full forward (cache correctness).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as tf
from repro.models.registry import ARCH_IDS, get_config
from repro.optim import adamw

B, T = 2, 16
KEY = jax.random.PRNGKey(0)


def _batch(cfg, key=KEY, t=T):
    tokens = jax.random.randint(key, (B, t), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(key, (B, cfg.n_prefix, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(key, (B, cfg.encoder.n_frames, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, "smoke")
    params = tf.init_params(KEY, cfg)
    batch = _batch(cfg)

    hidden, aux, _ = tf.forward(params, batch, cfg)
    t_expect = T + (cfg.n_prefix if cfg.family == "vlm" else 0)
    assert hidden.shape == (B, t_expect, cfg.d_model)
    assert jnp.isfinite(hidden.astype(jnp.float32)).all()

    loss, grads = jax.jit(jax.value_and_grad(lambda p: tf.lm_loss(p, batch, cfg)))(params)
    assert jnp.isfinite(loss) and float(loss) > 0
    gnorm = adamw.global_norm(grads)
    assert jnp.isfinite(gnorm) and float(gnorm) > 0

    # one optimizer step moves the loss
    optcfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=10)
    opt = adamw.init(params, optcfg)
    new_params, _, _ = adamw.update(grads, opt, params, optcfg)
    changed = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)))), params, new_params)
    assert max(jax.tree.leaves(changed)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode(arch):
    cfg = get_config(arch, "smoke")
    params = tf.init_params(KEY, cfg)
    batch = _batch(cfg)
    enc = cfg.encoder.n_frames if cfg.family == "audio" else 0
    cache = tf.init_cache(cfg, B, T + 8 + (cfg.n_prefix if cfg.family == "vlm" else 0), enc_frames=enc)
    logits, cache = tf.prefill(params, batch, cache, cfg)
    assert logits.shape == (B, cfg.vocab)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        logits, cache = tf.decode_step(params, tok, cache, cfg)
        assert jnp.isfinite(logits).all()
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("arch", ["codeqwen1.5-7b", "rwkv6-1.6b", "zamba2-1.2b", "moonshot-v1-16b-a3b"])
def test_decode_matches_forward(arch):
    """Prefill+decode logits must equal the full-sequence forward logits —
    the KV-cache/state path is semantically invisible.

    MoE caveat: capacity-based dropping is sequence-length dependent (a
    train-time semantic), so the MoE arch runs with drop-free capacity here;
    decode never drops (one token per step always fits)."""
    cfg = get_config(arch, "smoke")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0)
        )
    params = tf.init_params(KEY, cfg)
    t_total = 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, t_total), 0, cfg.vocab)

    # full forward logits at every position
    hidden, _, _ = tf.forward(params, {"tokens": tokens}, cfg)
    full_logits = tf._logits_chunk(params, hidden, cfg)

    # prefill on the first k tokens, then decode one at a time
    k = 6
    cache = tf.init_cache(cfg, B, t_total)
    logits, cache = tf.prefill(params, {"tokens": tokens[:, :k]}, cache, cfg)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits[:, k - 1]), rtol=2e-3, atol=2e-3
    )
    for i in range(k, t_total):
        logits, cache = tf.decode_step(params, tokens[:, i : i + 1], cache, cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, i]), rtol=2e-3, atol=2e-3,
            err_msg=f"{arch} decode step {i}",
        )


def test_loss_decreases_over_training():
    """A few hundred steps on a tiny model: loss must drop substantially
    (end-to-end learning sanity for the whole substrate)."""
    from repro.configs.base import ShapeCell
    from repro.data.pipeline import SyntheticLM
    from repro.launch import steps as steps_lib

    cfg = dataclasses.replace(get_config("stablelm-1.6b", "smoke"), n_layers=2)
    cell = ShapeCell("tiny", 32, 8, "train")
    optcfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60, weight_decay=0.0)
    params = tf.init_params(KEY, cfg)
    state = {"params": params, "opt": adamw.init(params, optcfg)}
    step_fn = jax.jit(steps_lib.make_train_step(cfg, optcfg), donate_argnums=(0,))
    src = SyntheticLM(cfg, cell, seed=0)
    losses = []
    for step in range(60):
        batch = {k: jnp.asarray(v) for k, v in src.batch(0).items()}  # fixed batch: memorization test
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_microbatched_train_step_matches_plain():
    from repro.launch import steps as steps_lib

    cfg = get_config("internlm2-20b", "smoke")
    optcfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    params = tf.init_params(KEY, cfg)
    batch = _batch(cfg)
    s0 = {"params": params, "opt": adamw.init(params, optcfg)}
    s1, m1 = jax.jit(steps_lib.make_train_step(cfg, optcfg, microbatches=1))(s0, batch)
    s0b = {"params": params, "opt": adamw.init(params, optcfg)}
    s2, m2 = jax.jit(steps_lib.make_train_step(cfg, optcfg, microbatches=2))(s0b, batch)
    # losses equal (mean over same tokens); grads equal up to fp reorder
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    diff = jax.tree.map(
        lambda a, b_: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)))),
        s1["params"], s2["params"],
    )
    assert max(jax.tree.leaves(diff)) < 2e-4


def test_prefix_lm_mask_semantics():
    """paligemma: patch-prefix tokens attend bidirectionally, text is causal
    (attention_core prefix_len) — checked against an explicit masked softmax."""
    import jax.numpy as jnp
    from repro.models import layers

    b, t, h, hd, pfx = 1, 10, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, hd), jnp.float32)
    out = layers.attention_core(q, k, v, causal=True, prefix_len=pfx)

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    qpos = jnp.arange(t)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = (qpos >= kpos) | (kpos < pfx)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    # prefix token 0 must see token 3 (bidirectional inside the prefix)
    s_causal = jnp.where((qpos >= kpos)[None, None], jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5, -jnp.inf)
    ref_causal = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_causal, -1), v)
    assert float(jnp.max(jnp.abs(ref - ref_causal))) > 1e-3


def test_attention_core_chunked_matches_full_scores():
    """The long-context lax.scan path (online softmax over kv chunks) must
    agree with the single-block softmax path — it only triggers above the
    tq*tk threshold, so the model smoke tests never reach it."""
    from repro.models import layers

    b, h, hd = 1, 2, 32
    tq, tk = 2304, 2048  # tq*tk > 4096*1024 -> chunked scan path
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, tq, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, tk, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, tk, h, hd), jnp.float32)
    out_chunked = layers.attention_core(q, k, v, causal=True)
    out_full = layers.attention_core(q, k, v, causal=True, full_scores=True)
    np.testing.assert_allclose(
        np.asarray(out_chunked), np.asarray(out_full), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("causal", [True, False])
def test_attention_core_prime_lengths_chunked(causal):
    """Regression: the chunk-size selection searched for the largest DIVISOR
    of tq/tk, so prime lengths degraded to qc=kc=1 — an 8191-token prompt ran
    8191^2 scan steps (this test would effectively hang).  cdiv chunking with
    masked final blocks keeps the configured chunk sizes for any length and
    must still match the single-block softmax."""
    from repro.models import layers

    b, h, hd = 1, 2, 16
    tq = tk = 2311  # prime, and 2311^2 > 4096*1024 -> chunked scan path
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, tq, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, tk, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, tk, h, hd), jnp.float32)
    out_chunked = layers.attention_core(q, k, v, causal=causal)
    out_full = layers.attention_core(q, k, v, causal=causal, full_scores=True)
    np.testing.assert_allclose(
        np.asarray(out_chunked), np.asarray(out_full), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("arch", ["internlm2-20b", "stablelm-1.6b"],
                         ids=["rms", "ln"])
def test_norm_eps_reaches_the_norms(arch):
    """`ModelConfig.norm_eps` is the epsilon of every RMSNorm and LayerNorm
    of the forward; left unset it is the norm's own (1e-6 rms, 1e-5 ln)."""
    from repro.models import layers
    cfg = dataclasses.replace(get_config(arch, "smoke"), dtype="float32")
    params = tf.init_params(KEY, cfg)
    batch = _batch(cfg)

    def hidden(c):
        return np.asarray(tf.forward(params, batch, c)[0])

    base = hidden(cfg)
    np.testing.assert_array_equal(base, hidden(dataclasses.replace(
        cfg, norm_eps=layers.NORM_EPS[cfg.norm])))
    assert not np.allclose(base, hidden(dataclasses.replace(cfg, norm_eps=1.0)),
                           atol=1e-3)


def test_norm_eps_of_the_registry():
    """internlm2-20b publishes rms_norm_eps 1e-5; every other entry, and
    internlm2's smoke size, keep the norm's default."""
    from repro.configs import internlm2_20b
    assert internlm2_20b.FULL.norm_eps == 1e-5
    for arch in ARCH_IDS:
        for variant in ("full", "smoke"):
            cfg = get_config(arch, variant)
            if cfg is not internlm2_20b.FULL:
                assert cfg.norm_eps is None, (arch, variant)


def _bgemv_launches(fn, *args):
    """Scalar-prefetch operand counts of every bgemv pallas_call in fn's
    jaxpr, nested jaxprs (jit, scan, checkpoint) included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "pallas_call" and "_bgemv_kernel" in
                    eqn.params["jaxpr"].debug_info.func_src_info):
                found.append(eqn.params["grid_mapping"].num_index_operands)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("quantize", [False, True])
def test_pallas_decode_reads_projections_in_place(quantize, monkeypatch):
    """Under the pallas backend a one-token decode step leaves the block
    projections in their layer stacks: each of a layer's six bgemv launches
    (q, k, v, o, the dual gate/up, down) takes the layer index as its one
    scalar-prefetch operand and reads the layer in place.  The logits are
    bitwise those of the same step with every layer sliced out first."""
    from repro.core import blas
    from repro.models import layers
    cfg = get_config("stablelm-1.6b", "smoke")
    params = tf.init_params(KEY, cfg)
    if quantize:
        params = layers.quantize_weights(params)
    cache = tf.init_cache(cfg, B, 16, per_slot=True)
    tok = jax.random.randint(jax.random.PRNGKey(5), (B, 1), 0, cfg.vocab)
    step = jax.jit(lambda p, t, c: tf.decode_step(p, t, c, cfg)[0])
    with blas.use_backend("pallas"):
        assert _bgemv_launches(step, params, tok, cache) == [1] * 6
        got = step(params, tok, cache)
        monkeypatch.setattr(tf, "_in_place", lambda *a: {})
        step = jax.jit(lambda p, t, c: tf.decode_step(p, t, c, cfg)[0])
        assert _bgemv_launches(step, params, tok, cache) == [0] * 6
        want = step(params, tok, cache)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_in_place_only_for_the_pallas_decode_step():
    """The stacks are read in place only by a one-token step with a cache
    under pallas; prefill, verify windows, training, the xla backend and
    MoE expert stacks (4-D) keep the scan's per-layer slices."""
    from repro.core import blas
    cfg = get_config("stablelm-1.6b", "smoke")
    stacked = jax.eval_shape(lambda: tf.init_params(KEY, cfg))["layers"]
    one, many = jnp.zeros((B, 1, cfg.d_model)), jnp.zeros((B, 3, cfg.d_model))
    cache = {"pos": jnp.zeros((B,), jnp.int32)}
    with blas.use_backend("pallas"):
        assert tf._in_place(stacked, one, cache) == tf.IN_PLACE_PROJECTIONS
        assert tf._in_place(stacked, many, cache) == {}
        assert tf._in_place(stacked, one, None) == {}
        moe_like = {**stacked, "ffn": {
            k: jax.ShapeDtypeStruct((2, 4) + w.shape[1:], w.dtype)
            for k, w in stacked["ffn"].items()}}
        assert tf._in_place(moe_like, one, cache)["ffn"] == ()
    assert tf._in_place(stacked, one, cache) == {}

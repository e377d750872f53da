"""Step-function builders: the jit targets for training and serving."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import blas
from repro.models import transformer as tf
from repro.optim import adamw


def make_train_step(cfg: ModelConfig, optcfg: adamw.AdamWConfig, microbatches: int = 1):  # noqa: C901
    """Training step with microbatch gradient accumulation.

    Accumulation bounds activation memory: the remat'd backward holds the
    stacked layer carries for one microbatch only (B_local/microbatches rows).
    The f32 accumulation buffer is sharded exactly like the params, so it
    adds only params_bytes*4/chips per device.
    """

    def loss_fn(params, batch):
        return tf.lm_loss(params, batch, cfg)

    def train_step(state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])

            mb = jax.tree.map(split, batch)
            acc_dt = jnp.float32 if optcfg.accum_dtype == "float32" else jnp.bfloat16
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), state["params"]
            )

            def acc_step(carry, mbatch):
                tot, g_acc = carry
                l, g = jax.value_and_grad(loss_fn)(state["params"], mbatch)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(acc_dt), g_acc, g
                )
                return (tot + l, g_acc), None

            (loss, grads), _ = jax.lax.scan(acc_step, (jnp.zeros(()), zeros), mb)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        new_params, new_opt, metrics = adamw.update(grads, state["opt"], state["params"], optcfg)
        return {"params": new_params, "opt": new_opt}, {"loss": loss, **metrics}

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch, cache):
        logits, cache = tf.prefill(params, batch, cache, cfg)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, cache

    return prefill_step


def make_serve_step(cfg: ModelConfig, act_fault=None):
    """One greedy decode step: (params, token (B,1), cache) -> (token, cache).
    Jit with donate_argnums=(2,) so the cache updates in place.
    act_fault (static): fault-injection harness only — builds a POISONED
    variant of the step that adds NaN/Inf into the post-embedding
    activations (see transformer.forward); serve swaps it in for exactly
    the decode rounds a FaultPlan names."""

    def serve_step(params, token, cache):
        logits, cache = tf.decode_step(params, token, cache, cfg,
                                       act_fault=act_fault)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, cache

    return serve_step


def make_decode_step_slots(cfg: ModelConfig, act_fault=None):
    """Masked continuous-batching decode step over the ragged slot grid.

    (params, token (B,1), cache{pos: (B,)}, active (B,) bool) -> (token, cache).

    Every slot computes every step — the batch shape never changes, so there
    is exactly one jit trace and (under the pallas backend) every projection
    stays one fused broadcast-weight bgemv launch at any occupancy.  Inactive
    slots' positions are frozen so a freed slot neither advances nor overflows
    its KV row while it waits for the next admission; its (discarded) write
    lands on a position that the admission graft wipes anyway.
    Jit with donate_argnums=(2,) so the cache updates in place.
    act_fault (static): see `make_serve_step` — the fault-injection variant.
    """

    def decode_step_slots(params, token, cache, active):
        pos0 = cache["pos"]
        logits, cache = tf.decode_step(params, token, cache, cfg,
                                       act_fault=act_fault)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        cache = {**cache, "pos": jnp.where(active, pos0 + 1, pos0)}
        return next_tok, cache

    return decode_step_slots


def make_verify_step_slots(cfg: ModelConfig, k: int, act_fault=None):
    """Speculative verify over the ragged slot grid: score k draft tokens
    per slot in one forward pass and accept the longest greedy prefix.

    (params, tokens (B, k+1), cache{pos: (B,)}, active (B,) bool)
        -> (preds (B, k+1), acc (B,), cache)

    tokens[:, 0] is each slot's last COMMITTED token (what plain decode
    would feed), tokens[:, 1:] the k drafts.  The whole window runs through
    `tf.verify_step` — projections as (B, k+1, d) skinny GEMMs amortizing
    one weight stream over k+1 tokens, attention through the one flash
    kernel with per-row kv_lens = pos + k + 1, KV for all k+1 candidates
    written quantized/paged as usual.

    preds[:, j] = argmax logits at window position j: what greedy decode
    emits after seeing tokens[:, :j+1].  Draft j is correct iff it equals
    the model's own prediction at the previous position, so the accepted
    count is the longest matching prefix:

        acc = sum_j prod_{i<=j} [preds[:, i] == tokens[:, i+1]]   in [0, k]

    and the slot emits acc+1 tokens this round: preds[:, :acc+1].
    preds[:, 0] never depends on the drafts (causal attention), so with
    acc == 0 this is EXACTLY the plain decode step — greedy token parity
    with --speculate 0 holds by construction, per token id, regardless of
    drafter quality.

    Rollback is a pos rewind, not a cache wipe: pos advances by acc+1 only,
    so the k-acc rejected writes become the masked-dead tail past kv_lens
    that PR 5/6 pinned as the cache invariant (the next verify round
    overwrites them).  Inactive slots freeze exactly like the plain step.
    Jit with donate_argnums=(2,); act_fault as in `make_serve_step`.
    """
    if k < 1:
        raise ValueError(f"speculation needs k >= 1 drafts, got {k}")

    def verify_step_slots(params, tokens, cache, active):
        pos0 = cache["pos"]
        # Trace under the verify-window flag: the quantized xla path must
        # score every window row with the SAME packed per-row matvec the
        # t=1 decode step uses — a dequantize+GEMM fallback rounds
        # differently and flips near-tied argmaxes, breaking token parity.
        with blas.verify_window():
            logits, cache = tf.verify_step(params, tokens, cache, cfg,
                                           act_fault=act_fault)
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, k+1)
        match = (preds[:, :-1] == tokens[:, 1:]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)            # (B,)
        cache = {**cache, "pos": jnp.where(active, pos0 + acc + 1, pos0)}
        return preds, acc, cache

    return verify_step_slots


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        return tf.lm_loss(params, batch, cfg)

    return eval_step


# --------------------------------------------------------------------------
# Tensor-parallel serving steps (ISSUE 10)
# --------------------------------------------------------------------------
#
# One shard_map wraps each single-device step builder above.  Inside it the
# model runs with a LOCAL config (n_heads/n_kv/d_ff divided by tp): the
# column-parallel projections then produce exactly this member's contiguous
# slice of heads / FFN features with zero code changes (their per-member
# math is a bitwise slice of the single-device op), and the two row-parallel
# boundaries per layer route through `distributed.row_parallel_fused` (one
# psum each — the only collectives in the step).  Tokens/logits come back
# replicated, so the serving drivers see the same (token, cache) contract.

import dataclasses  # noqa: E402

from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import distributed  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402

TP_AXIS = "model"


def tp_mesh(tp: int):
    """1-D ("model",) mesh over the first `tp` host devices."""
    return make_test_mesh((tp,), (TP_AXIS,))


def tp_local_config(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The per-member view of the model: heads, KV heads and FFN width
    divided by tp (d_model stays global — the residual stream is replicated
    between the per-layer psums)."""
    for field, val in (("n_heads", cfg.n_heads), ("n_kv", cfg.n_kv),
                      ("d_ff", cfg.d_ff)):
        if val % tp:
            raise ValueError(f"--tp {tp} must divide {field}={val}")
    return dataclasses.replace(
        cfg, n_heads=cfg.n_heads // tp, n_kv=cfg.n_kv // tp,
        d_ff=cfg.d_ff // tp)


def _tp_wrap(build, cfg: ModelConfig, mesh, in_specs, out_specs):
    """shard_map a step builder; the body traces under `tp_serving` so
    models/layers.py routes row-parallel boundaries through the collective
    path (and the routing log records which kernel each one took)."""
    p = mesh.shape[TP_AXIS]

    def wrapped(*argv):
        with distributed.tp_serving(TP_AXIS, p):
            fn = build(tp_local_config(cfg, p))
            return fn(*argv)

    return jax.shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_tp_prefill_step(cfg: ModelConfig, mesh, pspecs, cspecs):
    """(params, batch, cache) -> (next_tok, cache); batch dict replicated
    (P() broadcasts as a pytree prefix), params/cache per the TP specs."""
    return _tp_wrap(make_prefill_step, cfg, mesh,
                    in_specs=(pspecs, P(), cspecs),
                    out_specs=(P(), cspecs))


def make_tp_serve_step(cfg: ModelConfig, mesh, pspecs, cspecs, act_fault=None):
    build = functools.partial(make_serve_step, act_fault=act_fault)
    return _tp_wrap(build, cfg, mesh,
                    in_specs=(pspecs, P(), cspecs),
                    out_specs=(P(), cspecs))


def make_tp_decode_step_slots(cfg: ModelConfig, mesh, pspecs, cspecs,
                              act_fault=None):
    build = functools.partial(make_decode_step_slots, act_fault=act_fault)
    return _tp_wrap(build, cfg, mesh,
                    in_specs=(pspecs, P(), cspecs, P()),
                    out_specs=(P(), cspecs))


def make_tp_verify_step_slots(cfg: ModelConfig, mesh, k: int, pspecs, cspecs,
                              act_fault=None):
    build = functools.partial(make_verify_step_slots, k=k, act_fault=act_fault)
    return _tp_wrap(build, cfg, mesh,
                    in_specs=(pspecs, P(), cspecs, P()),
                    out_specs=(P(), P(), cspecs))

"""Model assembly for all assigned architecture families.

Families: dense (+vlm prefix variant), moe, rwkv, hybrid (zamba2), audio
(whisper encoder-decoder).  All stacks scan over layer-stacked params (keeps
HLO small enough to SPMD-partition for 512 devices on one CPU core) with
optional remat.  Caches are layer-stacked pytrees scanned alongside params.

Public API:
    init_params(key, cfg)              -> params pytree
    init_cache(cfg, batch, max_len)    -> cache pytree (decode shapes)
    forward(params, batch, cfg)        -> (hidden (B,T,d), aux_loss)
    lm_loss(params, batch, cfg)        -> scalar loss (chunked-vocab softmax)
    prefill(params, batch, cache, cfg) -> (last-position logits, cache)
    decode_step(params, token, cache, cfg) -> (logits (B,V), cache)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import blas, distributed
from repro.core.act_sharding import constrain
from repro.models import layers, mamba, moe, rwkv
from repro.models.layers import AttnConfig


# --------------------------------------------------------------------------
# Block builders
# --------------------------------------------------------------------------

def _attn_cfg(cfg: ModelConfig, causal: bool = True, use_rope: Optional[bool] = None) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv=cfg.n_kv,
        head_dim=cfg.hd,
        rope_theta=cfg.rope_theta,
        use_bias=cfg.use_bias,
        causal=causal,
        use_rope=cfg.family != "audio" if use_rope is None else use_rope,
        qk_norm=cfg.qk_norm,
        full_scores=cfg.attn_full_scores,
    )


def init_dense_block(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "attn": layers.init_attention(k1, _attn_cfg(cfg), dtype),
    }
    if cfg.family == "moe":
        p["ffn"] = moe.init_moe(k2, cfg.d_model, cfg.moe, cfg.act, dtype)
    else:
        p["ffn"] = layers.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype, cfg.use_bias)
    if not cfg.parallel_block:
        p["ln2"] = layers.init_norm(cfg.d_model, cfg.norm, dtype)
    return p


def dense_block(params, x, cfg: ModelConfig, *, positions, cache=None, prefix_len=None):
    """Returns (x, new_cache, aux).

    The skip connections ride the fused epilogues: the attention output
    projection and the MLP down projection each add their residual inside
    the kernel flush (layers.attention_layer/mlp `residual=`), so the block
    writes each stream update to HBM once instead of GEMM-out + add.
    """
    acfg = _attn_cfg(cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.parallel_block:
        h = layers.apply_norm(params["ln1"], x, cfg.norm, cfg.norm_eps)
        a, new_cache = layers.attention_layer(
            params["attn"], h, acfg, positions=positions, cache=cache,
            prefix_len=prefix_len, residual=x,
        )  # a = x + attn(h)
        if cfg.family == "moe":
            m, aux = moe.moe_layer(params["ffn"], h, cfg.moe, cfg.act)
            return a + m, new_cache, aux
        return layers.mlp(params["ffn"], h, cfg.act, residual=a), new_cache, aux
    a, new_cache = layers.attention_layer(
        params["attn"], layers.apply_norm(params["ln1"], x, cfg.norm, cfg.norm_eps), acfg,
        positions=positions, cache=cache, prefix_len=prefix_len, residual=x,
    )  # a = x + attn(...)
    h = layers.apply_norm(params["ln2"], a, cfg.norm, cfg.norm_eps)
    if cfg.family == "moe":
        m, aux = moe.moe_layer(params["ffn"], h, cfg.moe, cfg.act)
        return a + m, new_cache, aux
    return layers.mlp(params["ffn"], h, cfg.act, residual=a), new_cache, aux


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig):
    dtype = cfg.jdtype
    keys = jax.random.split(key, 8)
    params = {"embed": layers.init_embedding(keys[0], cfg.vocab, cfg.d_model, dtype),
              "final_norm": layers.init_norm(cfg.d_model, cfg.norm, dtype)}
    if not cfg.tie_embeddings:
        params["head"] = {
            "w": (jax.random.normal(keys[1], (cfg.d_model, cfg.vocab)) * cfg.d_model ** -0.5).astype(dtype)
        }

    if cfg.family in ("dense", "moe", "vlm"):
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: init_dense_block(k, cfg, dtype))(lkeys)
    elif cfg.family == "rwkv":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: rwkv.init_rwkv_block(k, cfg, dtype))(lkeys)
    elif cfg.family == "hybrid":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: init_mamba_wrap(k, cfg, dtype))(lkeys)
        s = cfg.ssm
        if s.shared_attn_every:
            n_occ = cfg.n_layers // s.shared_attn_every
            k1, k2, k3 = jax.random.split(keys[3], 3)
            params["shared_attn"] = {
                "ln1": layers.init_norm(cfg.d_model, cfg.norm, dtype),
                "ln2": layers.init_norm(cfg.d_model, cfg.norm, dtype),
                "attn": layers.init_attention(k1, _attn_cfg(cfg), dtype),
                "ffn": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, "gelu", dtype),
            }
            if s.shared_attn_lora_rank:
                r = s.shared_attn_lora_rank
                d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
                lk = jax.random.split(k3, 6)
                std = d ** -0.5
                params["shared_lora"] = {
                    "qa": (jax.random.normal(lk[0], (n_occ, d, r)) * std).astype(dtype),
                    "qb": jnp.zeros((n_occ, r, h * hd), dtype),
                    "ka": (jax.random.normal(lk[1], (n_occ, d, r)) * std).astype(dtype),
                    "kb": jnp.zeros((n_occ, r, kv * hd), dtype),
                    "va": (jax.random.normal(lk[2], (n_occ, d, r)) * std).astype(dtype),
                    "vb": jnp.zeros((n_occ, r, kv * hd), dtype),
                }
    elif cfg.family == "audio":
        enc_keys = jax.random.split(keys[4], cfg.encoder.n_layers)
        dec_keys = jax.random.split(keys[5], cfg.n_layers)
        params["enc_layers"] = jax.vmap(lambda k: init_encoder_block(k, cfg, dtype))(enc_keys)
        params["dec_layers"] = jax.vmap(lambda k: init_decoder_block(k, cfg, dtype))(dec_keys)
        params["enc_final_norm"] = layers.init_norm(cfg.d_model, cfg.norm, dtype)
    else:
        raise ValueError(cfg.family)
    return params


def init_mamba_wrap(key, cfg, dtype):
    return mamba.init_mamba_block(key, cfg, dtype)


def init_encoder_block(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "ln2": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "attn": layers.init_attention(k1, _attn_cfg(cfg, causal=False, use_rope=False), dtype),
        "ffn": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype, cfg.use_bias),
    }


def init_decoder_block(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "ln_x": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "ln2": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "attn": layers.init_attention(k1, _attn_cfg(cfg, causal=True, use_rope=False), dtype),
        "xattn": layers.init_attention(k2, _attn_cfg(cfg, causal=False, use_rope=False), dtype),
        "ffn": layers.init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act, dtype, cfg.use_bias),
    }


# --------------------------------------------------------------------------
# Sinusoidal positions (whisper-style, for the audio family)
# --------------------------------------------------------------------------

def sinusoidal(t: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d))
    pe = jnp.concatenate([jnp.sin(pos * div), jnp.cos(pos * div)], axis=-1)
    return pe.astype(dtype)


# --------------------------------------------------------------------------
# Forward (train / prefill path, full sequences)
# --------------------------------------------------------------------------

#: the block projections a decode step reads in place from their stacks
IN_PLACE_PROJECTIONS = {"attn": ("wq", "wk", "wv", "wo"),
                        "ffn": ("w_gate", "w_up", "w_down")}


def _in_place(params_stacked, x, cache) -> dict:
    """{sub-tree: keys} of the layer-stacked 2-D projections that the decode
    step's bgemv reads out of their stacks: a pallas one-token step with a
    cache (no gradient flows through it).  MoE expert stacks (4-D) and
    anything else keep the scan's slices."""
    if (cache is None or x.shape[1] != 1 or blas.get_backend() != "pallas"
            or distributed.tp_active()):
        return {}
    return {sub: tuple(k for k in keys if k in params_stacked.get(sub, {})
                       and params_stacked[sub][k].ndim == 3)
            for sub, keys in IN_PLACE_PROJECTIONS.items()}


def _scan_blocks(params_stacked, x, body, cfg: ModelConfig, cache=None,
                 in_place=None):
    """lax.scan over layer-stacked params (+ optional stacked cache).

    body(layer_params, x, layer_cache) -> (x, new_layer_cache, aux)

    in_place ({sub-tree: keys}, see `_in_place`): those leaves reach the
    body as `blas.LayerSlice`s of their whole stack at the layer's index,
    so the projection kernel reads the layer in place; the scan slices the
    rest.
    """
    kept = {sub: {k: params_stacked[sub][k] for k in keys}
            for sub, keys in (in_place or {}).items() if keys}
    scanned, idx = params_stacked, None
    if kept:
        scanned = {key: ({k: v for k, v in sub.items() if k not in kept[key]}
                         if key in kept else sub)
                   for key, sub in params_stacked.items()}
        idx = jnp.arange(jax.tree.leaves(params_stacked)[0].shape[0],
                         dtype=jnp.int32)

    def step(carry, xs):
        x, aux = carry
        lp, lc, i = xs
        if kept:
            lp = {**lp, **{sub: {**lp[sub], **{
                k: blas.LayerSlice(w, i) for k, w in ws.items()}}
                for sub, ws in kept.items()}}
        # Megatron-SP analog: the residual stream between blocks is sharded
        # over ("dp", seqres) — the scan's saved carries shrink by the model
        # axis, which is what lets 100B+ train cells fit 16 GiB/chip.
        x = constrain(x, "dp", "seqres", None)
        x, new_c, a = body(lp, x, lc)
        return (constrain(x, "dp", "seqres", None), aux + a), new_c

    fn = jax.checkpoint(step) if cfg.remat == "full" else step
    (x, aux), new_cache = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32)), (scanned, cache, idx),
        unroll=True if cfg.scan_unroll else 1,
    )
    return x, aux, new_cache


def forward(params, batch, cfg: ModelConfig, cache=None, act_fault=None):
    """batch: {"tokens": (B,T)} + family extras ("patches"/"frames").
    Returns (hidden (B,T,d), aux_loss, new_cache).

    act_fault (static, fault-injection harness only): a float added into the
    post-embedding activations — launch.faults builds a SEPARATE jit'd step
    with act_fault=nan/inf so one chosen decode round runs with corrupted
    activations flowing through every layer, the KV write, and the logits,
    exactly like a real numeric fault."""
    tokens = batch["tokens"]
    b, t = tokens.shape
    x = constrain(
        layers.embed(params["embed"], tokens, scale=cfg.embed_scale), "dp", "sp", None
    )
    if act_fault is not None:
        x = x + jnp.asarray(act_fault, x.dtype)

    prefix_len = None
    if cfg.family == "vlm" and "patches" in batch:
        # prefill/train: prepend the (stub) patch embeddings; during decode
        # the prefix already lives in the KV cache.
        patches = batch["patches"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        t = x.shape[1]
        prefix_len = cfg.n_prefix

    pos0 = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)
    if pos0.ndim:
        # per-slot serving cache: each batch slot decodes at its own ragged
        # position -> (B, T) positions (rope and the causal mask broadcast)
        positions = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    else:
        positions = jnp.arange(t, dtype=jnp.int32) + pos0

    if cfg.family in ("dense", "moe", "vlm"):
        pos = cache["pos"] if cache is not None else None
        # one page table serves every layer: the layer scan slices the pool's
        # leading layer axis, while the table (pure logical->physical routing)
        # broadcasts into each layer's cache dict exactly like pos
        page_table = cache.get("page_table") if cache is not None else None
        ckeys = ()
        if cache is not None:
            ckeys = ("k", "v") + (("k_scale", "v_scale") if "k_scale" in cache else ())
        scan_cache = None if cache is None else {k_: cache[k_] for k_ in ckeys}

        def body(lp, x, lc):
            lcc = None if lc is None else {**lc, "pos": pos}
            if lcc is not None and page_table is not None:
                lcc["page_table"] = page_table
            x, nc, aux = dense_block(lp, x, cfg, positions=positions, cache=lcc, prefix_len=prefix_len)
            nc = None if nc is None else {k_: nc[k_] for k_ in ckeys}
            return x, nc, aux

        x, aux, new_scan = _scan_blocks(
            params["layers"], x, body, cfg, scan_cache,
            in_place=_in_place(params["layers"], x, cache))
        new_cache = None if cache is None else {**new_scan, "pos": pos + t}
        if new_cache is not None and page_table is not None:
            # table updates are host-side page-pointer writes (admission /
            # CoW); the jit'd step passes it through untouched
            new_cache["page_table"] = page_table
    elif cfg.family == "rwkv":
        pos = cache["pos"] if cache is not None else None
        scan_cache = None if cache is None else {"tm": cache["tm"], "cm": cache["cm"]}

        def body(lp, x, lc):
            x, st = rwkv.rwkv_block(lp, x, cfg, lc)
            return x, st, jnp.zeros((), jnp.float32)

        x, aux, new_scan = _scan_blocks(params["layers"], x, body, cfg, scan_cache)
        new_cache = None if cache is None else {**new_scan, "pos": pos + t}
    elif cfg.family == "hybrid":
        x, aux, new_cache = _hybrid_forward(params, x, cfg, positions, cache)
    elif cfg.family == "audio":
        x, aux, new_cache = _audio_forward(params, x, batch, cfg, positions, cache)
    else:
        raise ValueError(cfg.family)

    x = layers.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return x, aux, new_cache


def _hybrid_forward(params, x, cfg: ModelConfig, positions, cache=None):
    """Zamba2: mamba stack with a shared attention block every k layers."""
    s = cfg.ssm
    every = s.shared_attn_every or (cfg.n_layers + 1)
    n_occ = cfg.n_layers // every if s.shared_attn_every else 0
    aux = jnp.zeros((), jnp.float32)

    def mamba_body(lp, x, lc):
        x, st = mamba.mamba_block(lp, x, cfg, lc)
        return x, st, jnp.zeros((), jnp.float32)

    tree_slice = lambda tr, a, b_: jax.tree.map(lambda z: z[a:b_], tr)
    new_mamba_states = []
    new_attn_caches = []
    start = 0
    occ = 0
    while start < cfg.n_layers:
        end = min(start + every, cfg.n_layers)
        seg_params = tree_slice(params["layers"], start, end)
        seg_cache = tree_slice(cache["mamba"], start, end) if cache is not None else None
        x, a, seg_new = _scan_blocks(seg_params, x, mamba_body, cfg, seg_cache)
        aux = aux + a
        new_mamba_states.append(seg_new)
        if end - start == every and occ < n_occ:
            attn_cache = None
            if cache is not None:
                attn_cache = {"k": cache["attn"]["k"][occ], "v": cache["attn"]["v"][occ], "pos": cache["pos"]}
            x, new_ac = _shared_attn_block(params, x, cfg, positions, occ, attn_cache)
            new_attn_caches.append(new_ac)
            occ += 1
        start = end

    new_cache = None
    if cache is not None:
        nm = jax.tree.map(lambda *zs: jnp.concatenate(zs, axis=0), *new_mamba_states)
        na = {
            "k": jnp.stack([c["k"] for c in new_attn_caches]) if new_attn_caches else cache["attn"]["k"],
            "v": jnp.stack([c["v"] for c in new_attn_caches]) if new_attn_caches else cache["attn"]["v"],
        }
        new_cache = {"mamba": nm, "attn": na, "pos": new_attn_caches[0]["pos"] if new_attn_caches else cache["pos"]}
    return x, aux, new_cache


def _shared_attn_block(params, x, cfg: ModelConfig, positions, occ: int, cache=None):
    sp = params["shared_attn"]
    acfg = _attn_cfg(cfg)
    attn_params = sp["attn"]
    if "shared_lora" in params:
        lo = params["shared_lora"]
        lora = lambda base, a, b_: base + blas.matmul(a[occ].astype(jnp.float32), b_[occ].astype(jnp.float32)).astype(base.dtype)
        attn_params = dict(attn_params)
        attn_params["wq"] = lora(attn_params["wq"], lo["qa"], lo["qb"])
        attn_params["wk"] = lora(attn_params["wk"], lo["ka"], lo["kb"])
        attn_params["wv"] = lora(attn_params["wv"], lo["va"], lo["vb"])
    a, new_cache = layers.attention_layer(
        attn_params, layers.apply_norm(sp["ln1"], x, cfg.norm, cfg.norm_eps), acfg,
        positions=positions, cache=cache, residual=x,
    )
    x = layers.mlp(sp["ffn"], layers.apply_norm(sp["ln2"], a, cfg.norm, cfg.norm_eps), "gelu",
                   residual=a)
    return x, new_cache


def _audio_forward(params, x_dec, batch, cfg: ModelConfig, positions, cache=None):
    """Whisper: bidirectional encoder over (stub) frames; causal decoder with
    cross-attention.  With a cache, encoder output comes from cache["enc"]."""
    b, t, d = x_dec.shape
    acfg_self = _attn_cfg(cfg, causal=True, use_rope=False)
    acfg_cross = _attn_cfg(cfg, causal=False, use_rope=False)

    if cache is not None and "enc" in cache:
        enc = cache["enc"]
    else:
        frames = batch["frames"].astype(x_dec.dtype)  # (B, F, d) stub frontend
        f = frames.shape[1]
        enc = frames + sinusoidal(f, d, frames.dtype)[None]
        enc_pos = jnp.arange(f, dtype=jnp.int32)

        def enc_body(lp, x, lc):
            h, _ = layers.attention_layer(
                lp["attn"], layers.apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps),
                _attn_cfg(cfg, causal=False, use_rope=False), positions=enc_pos,
                residual=x,
            )
            x = layers.mlp(lp["ffn"], layers.apply_norm(lp["ln2"], h, cfg.norm, cfg.norm_eps),
                           cfg.act, residual=h)
            return x, lc, jnp.zeros((), jnp.float32)

        enc, _, _ = _scan_blocks(params["enc_layers"], enc, enc_body, cfg, None)
        enc = layers.apply_norm(params["enc_final_norm"], enc, cfg.norm, cfg.norm_eps)

    # decoder: sinusoidal positions (simplification of whisper's learned
    # embedding, DESIGN.md); with a cache the table covers max_len and is
    # sliced at the current position.
    if cache is not None:
        pe = sinusoidal(cache["k"].shape[2], d, x_dec.dtype)
        x = x_dec + jax.lax.dynamic_slice_in_dim(pe, cache["pos"], t, axis=0)[None]
    else:
        x = x_dec + sinusoidal(t, d, x_dec.dtype)[None]

    enc_pos = jnp.arange(enc.shape[1], dtype=jnp.int32)

    def dec_body(lp, x, lc):
        self_cache = None if lc is None else {"k": lc["k"], "v": lc["v"], "pos": cache["pos"]}
        h, new_sc = layers.attention_layer(
            lp["attn"], layers.apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps), acfg_self,
            positions=positions, cache=self_cache, residual=x,
        )
        x = h
        # cross attention: q from decoder, k/v from encoder output (biases
        # fused into the projection flush when present)
        hx = layers.apply_norm(lp["ln_x"], x, cfg.norm, cfg.norm_eps)
        if cfg.use_bias:
            q = blas.matmul_fused(hx, lp["xattn"]["wq"], bias=lp["xattn"]["bq"])
            k = blas.matmul_fused(enc, lp["xattn"]["wk"], bias=lp["xattn"]["bk"])
            v = blas.matmul_fused(enc, lp["xattn"]["wv"], bias=lp["xattn"]["bv"])
        else:
            q = blas.matmul(hx, lp["xattn"]["wq"])
            k = blas.matmul(enc, lp["xattn"]["wk"])
            v = blas.matmul(enc, lp["xattn"]["wv"])
        bq_, tq_, _ = hx.shape
        q = q.reshape(bq_, tq_, cfg.n_heads, cfg.hd)
        k = k.reshape(bq_, enc.shape[1], cfg.n_kv, cfg.hd)
        v = v.reshape(bq_, enc.shape[1], cfg.n_kv, cfg.hd)
        # one attention engine: the dispatcher lowers this non-causal launch
        # to the flash kernel under pallas (GQA folded in its index map, no
        # repeat_kv materialization) and to the attention_core oracle on
        # xla/ref
        ho = layers.attention_dispatch(
            q, k, v, causal=False, groups=cfg.n_heads // cfg.n_kv,
            full_scores=cfg.attn_full_scores,
        )
        x = blas.matmul_fused(
            ho.reshape(bq_, tq_, cfg.n_heads * cfg.hd), lp["xattn"]["wo"],
            residual=x,
        )
        x = layers.mlp(lp["ffn"], layers.apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps),
                       cfg.act, residual=x)
        new_lc = None if lc is None else new_sc
        return x, new_lc, jnp.zeros((), jnp.float32)

    dec_cache = None if cache is None else {"k": cache["k"], "v": cache["v"]}
    x, aux, new_dec = _scan_blocks(params["dec_layers"], x, dec_body, cfg, dec_cache)
    new_cache = None
    if cache is not None:
        new_cache = {"enc": enc, "k": new_dec["k"], "v": new_dec["v"], "pos": cache["pos"] + t}
    return x, aux, new_cache


# --------------------------------------------------------------------------
# Loss (chunked-vocab softmax cross-entropy: never materializes (B,T,V))
# --------------------------------------------------------------------------

def _logits_chunk(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "btd,vd->btv", x, params["embed"]["table"], preferred_element_type=jnp.float32
        )
    else:
        logits = jnp.einsum(
            "btd,dv->btv", x, params["head"]["w"], preferred_element_type=jnp.float32
        )
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def lm_loss(params, batch, cfg: ModelConfig):
    """Next-token loss.  For vlm, loss is over text positions only."""
    x, aux, _ = forward(params, batch, cfg)
    if cfg.family == "vlm":
        x = x[:, cfg.n_prefix :]
    labels = batch["labels"]
    b, t = labels.shape
    ck = min(cfg.loss_chunk, t)
    while t % ck:  # largest divisor of t not exceeding loss_chunk (vlm: t-n_prefix)
        ck -= 1
    nchunk = t // ck
    xs = constrain(jnp.moveaxis(x.reshape(b, nchunk, ck, -1), 1, 0), None, "dp", None, None)
    ls = jnp.moveaxis(labels.reshape(b, nchunk, ck), 1, 0)

    def step(tot, inp):
        xc, lc = inp
        logits = constrain(_logits_chunk(params, xc, cfg), "dp", None, "tp")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(logz - gold), None

    fn = jax.checkpoint(step) if cfg.remat == "full" else step
    tot, _ = jax.lax.scan(
        fn, jnp.zeros((), jnp.float32), (xs, ls), unroll=True if cfg.scan_unroll else 1
    )
    return tot / (b * t) + aux


# --------------------------------------------------------------------------
# Decode path
# --------------------------------------------------------------------------

#: families whose decode cache is a pure per-slot attention KV cache — the
#: ones the continuous-batching scheduler (per-slot positions + slot grafts)
#: supports.  State-space/recurrent caches need per-leaf batch-axis handling
#: and stay on the batch-at-a-time scheduler for now.
SLOT_CACHE_FAMILIES = ("dense", "moe", "vlm")


def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_frames: int = 0,
               per_slot: bool = False, page_size: int = 0,
               num_pages: Optional[int] = None):
    """Allocate the decode cache pytree (zeros).

    per_slot=True allocates a (batch,)-vector "pos" instead of a scalar: each
    slot tracks its own sequence position so finished sequences can be
    replaced without draining the rest of the batch (continuous batching).

    page_size > 0 allocates the PAGED representation instead of the dense
    per-slot buffers (dense/moe/vlm families): a global page pool
    (n_layers, num_pages, page_size, KVH, ...) — int8 value pages with
    lockstep f32 scale pages when kv_cache_dtype == "int8" — plus one
    (batch, ceil(max_len / page_size)) int32 page table whose entries start
    at the reserved trash page 0.  num_pages defaults to full dense-
    equivalent capacity + the trash page, so a no-sharing run can never
    exhaust the pool; the host allocator (launch.paging) is what turns
    shared prefixes into extra effective capacity.
    """
    dt = cfg.jdtype
    kv, hd = cfg.n_kv, cfg.hd
    if per_slot and cfg.family not in SLOT_CACHE_FAMILIES:
        raise ValueError(
            f"per-slot cache supports families {SLOT_CACHE_FAMILIES}, got {cfg.family!r}"
        )
    pos0 = jnp.zeros((batch,), jnp.int32) if per_slot else jnp.zeros((), jnp.int32)
    if page_size:
        if cfg.family not in SLOT_CACHE_FAMILIES:
            raise ValueError(
                f"paged KV cache supports families {SLOT_CACHE_FAMILIES}, "
                f"got {cfg.family!r}"
            )
        max_pages = -(-max_len // page_size)
        if num_pages is None:
            num_pages = 1 + batch * max_pages
        pool = {
            "page_table": jnp.zeros((batch, max_pages), jnp.int32),
            "pos": pos0,
        }
        shape = (cfg.n_layers, num_pages, page_size, kv, hd)
        if cfg.kv_cache_dtype == "int8":
            pool["k"] = jnp.zeros(shape, jnp.int8)
            pool["v"] = jnp.zeros(shape, jnp.int8)
            pool["k_scale"] = jnp.zeros(shape[:-1] + (1,), jnp.float32)
            pool["v_scale"] = jnp.zeros(shape[:-1] + (1,), jnp.float32)
        else:
            pool["k"] = jnp.zeros(shape, dt)
            pool["v"] = jnp.zeros(shape, dt)
        return pool
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.kv_cache_dtype == "int8":
            # block-scaled packed KV storage (core.quant.quantize_kv):
            # int8 values + one f32 scale per (token, head), written in
            # lockstep and streamed packed by the int8-KV flash kernel under
            # the pallas backend (dequantization-oracle read under xla/ref).
            # Scales stay f32 so the elementwise s/2 quantization bound is
            # exact; the byte overhead is 4/hd per element (~6% at hd=64).
            return {
                "k": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), jnp.int8),
                "v": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), jnp.int8),
                "k_scale": jnp.zeros((cfg.n_layers, batch, max_len, kv, 1), jnp.float32),
                "v_scale": jnp.zeros((cfg.n_layers, batch, max_len, kv, 1), jnp.float32),
                "pos": pos0,
            }
        return {
            "k": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), dt),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), dt),
            "pos": pos0,
        }
    if cfg.family == "rwkv":
        d = cfg.d_model
        nh = d // cfg.rwkv.head_dim
        p = cfg.rwkv.head_dim
        zl = lambda *s: jnp.zeros((cfg.n_layers,) + s, jnp.float32)
        return {
            "tm": {"x_prev": jnp.zeros((cfg.n_layers, batch, d), dt), "s": zl(batch, nh, p, p)},
            "cm": {"x_prev": jnp.zeros((cfg.n_layers, batch, d), dt)},
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "hybrid":
        s = cfg.ssm
        d_in = s.expansion * cfg.d_model
        nh = d_in // s.head_dim
        d_xbc = d_in + 2 * s.n_groups * s.d_state
        n_occ = cfg.n_layers // s.shared_attn_every if s.shared_attn_every else 0
        return {
            "mamba": {
                "conv": jnp.zeros((cfg.n_layers, batch, s.conv_kernel - 1, d_xbc), dt),
                "h": jnp.zeros((cfg.n_layers, batch, nh, s.d_state, s.head_dim), jnp.float32),
            },
            "attn": {
                "k": jnp.zeros((max(n_occ, 1), batch, max_len, kv, hd), dt),
                "v": jnp.zeros((max(n_occ, 1), batch, max_len, kv, hd), dt),
            },
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "audio":
        return {
            "enc": jnp.zeros((batch, enc_frames or cfg.encoder.n_frames, cfg.d_model), dt),
            "k": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), dt),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), dt),
            "pos": jnp.zeros((), jnp.int32),
        }
    raise ValueError(cfg.family)


def graft_pages(cache: dict, mini: dict, rows: jnp.ndarray, toks: jnp.ndarray,
                pages: jnp.ndarray, offs: jnp.ndarray) -> dict:
    """Graft admission-prefill tokens into the paged pool, token by token.

    `cache` is a paged cache (init_cache(..., page_size=...)); `mini` is the
    dense scalar-pos mini cache admission prefilled into.  Token i copies
    mini row `rows[i]`, position `toks[i]` — every layer at once, values and
    scale pages in lockstep — into pool page `pages[i]` at row `offs[i]`.
    The host only enumerates the NON-SHARED suffix of each admitted prompt
    here: tokens covered by a matched prefix are pure page-table pointer
    writes and never touch the pool, where a dense slot cache would re-copy
    the whole capacity row per admission.
    """
    new = dict(cache)
    for key in ("k", "v", "k_scale", "v_scale"):
        if key in cache:
            src = mini[key][:, rows, toks]            # (L, N, H, ...)
            new[key] = cache[key].at[:, pages, offs].set(
                src.astype(cache[key].dtype))
    return new


def copy_pages(cache: dict, src: jnp.ndarray, dst: jnp.ndarray) -> dict:
    """Copy-on-write device op: duplicate pool pages `src` into `dst` across
    every layer (values + scales in lockstep).  The host allocator decides
    WHEN (a write is about to land in a page with refcount > 1); this is the
    whole device-side cost of divergence — one page, not a capacity row."""
    new = dict(cache)
    for key in ("k", "v", "k_scale", "v_scale"):
        if key in cache:
            new[key] = cache[key].at[:, dst].set(cache[key][:, src])
    return new


def prefill(params, batch, cache, cfg: ModelConfig):
    """Run the prompt through the model, filling the cache.
    Returns (last-position logits (B, V), cache)."""
    x, _, cache = forward(params, batch, cfg, cache=cache)
    logits = _logits_chunk(params, x[:, -1:, :], cfg)[:, 0]
    return logits, cache


def decode_step(params, token, cache, cfg: ModelConfig, act_fault=None):
    """One decode step.  token (B, 1) int32.  Returns (logits (B,V), cache).
    act_fault: see `forward` (fault-injection harness only)."""
    x, _, cache = forward(params, {"tokens": token}, cfg, cache=cache,
                          act_fault=act_fault)
    logits = _logits_chunk(params, x, cfg)[:, 0]
    return logits, cache


def verify_step(params, tokens, cache, cfg: ModelConfig, act_fault=None):
    """Speculative verify: run a (B, T) window of already-chosen tokens
    through the model in ONE forward pass and return logits at EVERY
    position, (B, T, V).  Structurally this is `decode_step` at T > 1 —
    same cache write path (per-slot positions, quantized/paged as
    configured), but the projections see (B, T, d) activations and route
    through the batched GEMM kernels instead of per-token GEMVs: one weight
    stream amortized over T tokens, the Level-2 -> Level-3 intensity shift
    speculative decoding exists for.  KV for all T candidates is written;
    the scheduler rewinds `pos` past rejected suffixes, leaving them as the
    masked-dead cache tail the per-row kv_lens invariant already tolerates.
    """
    x, _, cache = forward(params, {"tokens": tokens}, cfg, cache=cache,
                          act_fault=act_fault)
    logits = _logits_chunk(params, x, cfg)
    return logits, cache

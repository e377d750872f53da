"""The one file that calls the program.

`serve()` takes a registry name, and a configuration cut in depth cannot go
through it, so this adapter calls the continuous scheduler that `serve()`
calls, under the Pallas backend, with a paged KV cache.  It hands the
program the benchmark's weights in the program's parameter tree: the
scheduler asks `init_params` for them, and the adapter answers.  When the
program grows a public entry that takes a config and weights, this file is
the one a later benchmark change switches.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time
import types
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp

from repro.core import blas
from repro.launch import compile_cache
from repro.launch import serve as serve_mod
from repro.models import transformer as tf
from repro.models.registry import get_config

from chipbench.spec import Spec

#: no request ends early: every one runs its whole generation budget
NO_EOS = -1


def enable_cache(path: Path) -> None:
    """JAX's persistent compilation cache at `path`, inside the checkout,
    for every program however quick to compile: a run after the first in a
    checkout compiles nothing.  The program keeps its cache where
    `JAX_COMPILATION_CACHE_DIR` says, so the variable is set to `path`
    whatever the environment held: a cache outside the checkout could be
    shared with another checkout."""
    os.environ[compile_cache.ENV] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def program_config(spec: Spec, raw: dict):
    """The program's config: the registry entry, cut to the file's depth,
    with the file's overrides; it has to match every other number the
    file states."""
    prog = raw["program"]
    cfg = get_config(prog["registry"], prog.get("variant", "full"))
    cfg = dataclasses.replace(cfg, n_layers=spec.layers,
                              **prog.get("overrides", {}))
    if spec.kv_cache == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    want = {"family": "dense", "act": "swiglu", "dtype": "bfloat16",
            "d_model": spec.hidden, "n_heads": spec.heads,
            "n_kv": spec.kv_heads, "hd": spec.head_dim, "d_ff": spec.ff,
            "vocab": spec.vocab, "rope_theta": spec.rope_theta,
            "norm": {"layer": "ln", "rms": "rms"}[spec.norm],
            "use_bias": spec.qkv_bias, "tie_embeddings": spec.tied}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{spec.name}: the program's config {got} differs "
                         f"from the file's {want}")
    return cfg


def program_params(spec: Spec, w: dict) -> dict:
    """The benchmark's weights in the program's parameter tree."""
    def norm(prefix):
        if spec.norm == "layer":
            return {"scale": w[prefix + ".w"], "bias": w[prefix + ".b"]}
        # the program's RMSNorm multiplies by (1 + scale); w - 1 is exact
        # in bf16 for w in [0.5, 2)
        return {"scale": w[prefix + ".w"] - jnp.ones((), w[prefix + ".w"].dtype)}

    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if spec.qkv_bias:
        attn.update({k: w[k] for k in ("bq", "bk", "bv")})
    params = {"embed": {"table": w["embed"]}, "final_norm": norm("final_norm"),
              "layers": {"ln1": norm("ln1"), "ln2": norm("ln2"), "attn": attn,
                         "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}}
    if not spec.tied:
        params["head"] = {"w": w["head"]}
    return params


def _check_tree(cfg, params) -> None:
    def layout(tree):
        return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)

    want = layout(jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg)))
    got = layout(params)
    if want != got:
        raise ValueError("the benchmark's weights do not match the program's "
                         f"parameter tree:\n{got}\nwant\n{want}")


def warm_up_lengths(slots: int, page_size: int, cap: int) -> list[int]:
    """Generation lengths of the warm-up queue, whose admissions come in
    groups of every size from `slots` down to 1.

    The scheduler's admission writes the page table and positions of the
    admitted rows with eager operations whose shapes follow the group's
    size, and a slot that fills a page grows its run with another; each
    new shape compiles once.  The warm-up runs them all before the window.
    Slot s's first request ends at round T_{s+1}, and every later request
    lasts one round, so the round T_k frees slots 0..k-1 together: a group
    of k.  T_1 = page_size + 1 rounds fill a page.  The last slot's first
    request has the window's longest length, so the warm-up compiles the
    window's KV capacity; it is cut once the queue is empty (`serve`)."""
    if cap < page_size + slots + 1:
        raise ValueError(f"a cap of {cap} tokens is too short for the warm-up")
    first = [page_size + 2 + s for s in range(slots - 1)] + [cap]
    return first + [2] * (slots * (slots - 1) // 2)


def _one_queue(queues: list, calls: int) -> None:
    """The hooks in `serve` lean on the scheduler's internals: one request
    queue a call, a deadline check every round.  A run in which they do not
    hold is no measurement."""
    if len(queues) != calls:
        raise RuntimeError(f"the scheduler made {len(queues)} request queues "
                           f"in {calls} calls; the adapter can cut only one "
                           "a call")


def serve(spec: Spec, raw: dict, prompts, gen_lens, seconds: float, seed: int,
          make_weights, schedule=()) -> tuple[dict, float]:
    """Serve the queue; every request's deadline is the window's end, so the
    scheduler itself cuts what still runs then, and the requests it has not
    admitted by then leave the queue unserved.  A warm-up pass over the
    first prompts comes first (`warm_up_lengths`), so that nothing compiles
    inside the window.  `make_weights()` returns the benchmark's weights;
    `schedule` holds (seconds since t0, callback) pairs, in order: each
    callback is called at the scheduler's round boundaries from that time
    on, with the number of requests admitted so far, until it returns True;
    at the window's end it is called with None and has to finish.  Returns
    the window's stats and its t0 on `time.time()`'s clock."""
    cfg = program_config(spec, raw)
    params = program_params(spec, make_weights())
    _check_tree(cfg, params)
    warm_gens = warm_up_lengths(spec.slots, spec.page_size, max(gen_lens))
    queues = []

    def deque(items):
        queues.append(collections.deque(items))
        return queues[-1]

    def call(queue, gens, expired):
        with mock.patch.object(tf, "init_params", lambda key, c: params), \
                mock.patch.object(serve_mod, "_deadline_expired", expired), \
                mock.patch.object(serve_mod, "collections",
                                  types.SimpleNamespace(deque=deque)), \
                blas.use_backend("pallas"):
            return serve_mod._serve_continuous(
                cfg, queue, list(gens), spec.slots, seed % 2 ** 31, NO_EOS,
                quantize="int8" if spec.weights == "int8" else "none",
                page_size=spec.page_size,
                deadline_ms=[seconds * 1e3] * len(queue))

    capped = spec.slots - 1    # the warm-up's request of the longest length
    warm = call(list(prompts[:len(warm_gens)]), warm_gens,
                lambda deadline_ms, rid, t0: rid == capped and queues
                and not queues[-1])
    _one_queue(queues, 1)
    if warm["status"].count("ok") != len(warm_gens) - 1:
        raise RuntimeError(f"the warm-up finished {warm['status'].count('ok')} "
                           f"of {len(warm_gens) - 1} requests")

    due = sorted(schedule, key=lambda p: p[0])
    expired = serve_mod._deadline_expired
    cleared = []

    def deadline_expired(deadline_ms, rid, t0):
        now = time.time() - t0
        closing = now >= seconds
        while due and (closing or now >= due[0][0]):
            admitted = None if closing else len(prompts) - len(queues[-1])
            if not due[0][1](admitted):
                if closing:
                    raise RuntimeError("a scheduled call did not finish when "
                                       "the window closed")
                break
            due.pop(0)
        if closing and not cleared:
            # the window is over: requests not yet admitted are never sent,
            # so the scheduler stops once the live ones are cut
            queues[-1].clear()
            cleared.append(time.time() - t0)
        return expired(deadline_ms, rid, t0)

    stats = call(prompts, gen_lens, deadline_expired)
    _one_queue(queues, 2)
    if not cleared or queues[-1]:
        raise RuntimeError("the request queue was not emptied at the window's "
                           "end: the scheduler checked no deadline after it")
    if due:
        raise RuntimeError(f"{len(due)} scheduled calls in the window never ran")
    t0 = time.time() - stats["elapsed_s"]
    return stats, t0

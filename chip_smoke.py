#!/usr/bin/env python3
"""Serve stablelm-1.6b at published width on a TPU, end to end, and check it.

    python chip_smoke.py              # one chip: the Pallas serving path
    python chip_smoke.py --chips 4    # four chips: --tp 4 against tp=1

One chip, two phases, each through `serve()` on the continuous scheduler
with `backend="pallas"` (8 requests at batch 8, 128-token prompts, 32
generated tokens each, random weights from a seed):

  bf16     bf16 weights and a bf16 KV cache in 16-token pages (the
           continuous scheduler's default);
  int8     int8 weights, an int8 KV cache and a paged pool (the composed
           path users run).

Each phase checks results, not only exit codes: every request finishes with
its full token budget; the last-position prefill logits of the Pallas path
agree with the `ref` backend on the same params and prompts (relative L2
error at most LOGITS_RTOL); and the compiled decode step holds Mosaic
kernels (`tpu_custom_call`), so no XLA path took the kernels' place.

`--chips 4` runs only the tensor-parallel phase: `serve(tp=4)` on the xla
backend with int8 weights and int8 KV, against the same call at tp=1 in this
process.  Both must serve every request its full token budget; each device
must hold a quarter of the bytes of the sharded projection weights; and the
last-position prefill logits of the tp=4 step must agree with the tp=1 step
on the same params and prompts (relative L2 error at most LOGITS_RTOL).  On
the chip the sharded program sums in another order than the one-device
program, so greedy tokens of a random-weight model can part ways; how many
agree is printed, not required.

Set-up time (parameter init and compilation) and serve wall clock are
printed per phase; they are bring-up facts, not benchmark metrics.  The last
line of standard output is one JSON object naming the device.  Without a TPU
the script exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH, VARIANT = "stablelm-1.6b", "full"
BATCH, REQUESTS, PROMPT_LEN, GEN = 8, 8, 128, 32
PAGE_SIZE = 16
SEED = 0
# bf16 weights and activations through 24 layers, in two accumulation
# orders: the Pallas kernels and the plain-jnp oracle agree to about 1e-2.
# The same bound holds tp=4 to tp=1, which differ in the same way.
LOGITS_RTOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg) -> None:
    """A failed check ends the run with a traceback and a non-zero exit
    (an `assert` would vanish under `python -O`)."""
    if not ok:
        raise SmokeFailure(msg)


def prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return [rng.integers(3, vocab, size=(PROMPT_LEN,), dtype=np.int32)
            for _ in range(REQUESTS)]


def serve_phase(name: str, **kw) -> dict:
    from repro.launch.serve import serve
    from repro.models.registry import get_config

    cfg = get_config(ARCH, VARIANT)
    t0 = time.perf_counter()
    stats = serve(ARCH, VARIANT, batch=BATCH, prompts=prompts(cfg.vocab),
                  gen_lens=[GEN] * REQUESTS, seed=SEED, eos=-1,
                  scheduler="continuous", verbose=True, **kw)
    total = time.perf_counter() - t0
    log(f"{name}: setup {total - stats['elapsed_s']:.1f}s (init + compile), "
        f"serve {stats['elapsed_s']:.2f}s, {stats['completed']} requests, "
        f"{stats['tokens']} tokens")
    lens = [len(o) for o in stats["outputs"]]
    check(stats["completed"] == REQUESTS,
          f"{name}: {stats['completed']} of {REQUESTS} requests completed")
    check(lens == [GEN] * REQUESTS, f"{name}: token budgets not met: {lens}")
    check(all(s == "ok" for s in stats["status"]),
          f"{name}: request statuses {stats['status']}")
    return stats


def compare_logits(what: str, got, want, vocab: int) -> None:
    """Last-position prefill logits `got` against `want`: shape, finite,
    relative L2 error at most LOGITS_RTOL."""
    import numpy as np

    check(got.shape == (BATCH, vocab), f"{what}: logits {got.shape}")
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          f"{what}: non-finite prefill logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"{what}: logits rel L2 {rel:.3e} (limit {LOGITS_RTOL}), max abs "
        f"{np.max(np.abs(got - want)):.3e}, argmax agreement {agree:.3f}")
    check(rel <= LOGITS_RTOL, f"{what}: logits disagree: {rel}")


def check_phase(name: str, cfg, quantize: str, page_size) -> None:
    """Prefill logits Pallas vs ref, and the decode step's Mosaic kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import blas
    from repro.launch import steps as steps_lib
    from repro.launch.serve import _quantize_params
    from repro.models import transformer as tf

    params = _quantize_params(tf.init_params(jax.random.PRNGKey(SEED), cfg),
                              quantize)
    tokens = jnp.asarray(np.stack(prompts(cfg.vocab)[:BATCH]))
    cache = tf.init_cache(cfg, BATCH, PROMPT_LEN + GEN)
    logits = {}
    for backend in ("pallas", "ref"):
        t0 = time.perf_counter()
        with blas.use_backend(backend):
            fn = jax.jit(lambda p, b, c: tf.prefill(p, b, c, cfg)[0])
            logits[backend] = np.asarray(
                fn(params, {"tokens": tokens}, cache), np.float32)
        log(f"{name}: {backend} prefill logits in "
            f"{time.perf_counter() - t0:.1f}s (compile included)")
    compare_logits(f"{name}: pallas vs ref", logits["pallas"], logits["ref"],
                   cfg.vocab)

    kw = dict(per_slot=True)
    if page_size:
        kw.update(page_size=page_size,
                  num_pages=1 + BATCH * (-(-(PROMPT_LEN + GEN) // page_size)))
    slot = tf.init_cache(cfg, BATCH, PROMPT_LEN + GEN, **kw)
    with blas.use_backend("pallas"):
        text = jax.jit(steps_lib.make_decode_step_slots(cfg)).lower(
            params, jnp.zeros((BATCH, 1), jnp.int32), slot,
            jnp.ones(BATCH, bool)).compile().as_text()
    n = text.count("tpu_custom_call")
    log(f"{name}: compiled decode step holds {n} tpu_custom_call sites")
    check(n > 0, f"{name}: no Pallas kernel in the compiled decode step")


def one_chip() -> None:
    from repro.models.registry import get_config

    cfg = get_config(ARCH, VARIANT)
    log(f"{ARCH} {VARIANT}: {cfg.n_layers} layers, d={cfg.d_model}, "
        f"{cfg.n_heads} heads, d_ff={cfg.d_ff}, vocab {cfg.vocab}")
    serve_phase("bf16", backend="pallas")
    check_phase("bf16", cfg, "none", None)
    serve_phase("int8", backend="pallas", quantize="int8", kv_cache="int8",
                kv_page_size=PAGE_SIZE)
    check_phase("int8", dataclasses.replace(cfg, kv_cache_dtype="int8"),
                "int8", PAGE_SIZE)


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import blas
    from repro.launch import sharding as sharding_lib
    from repro.launch import steps as steps_lib
    from repro.launch.serve import _make_tp_context, _quantize_params
    from repro.models import transformer as tf
    from repro.models.registry import get_config

    tp = 4
    kw = dict(backend="xla", quantize="int8", kv_cache="int8")
    one = serve_phase("tp1", **kw)
    many = serve_phase(f"tp{tp}", tp=tp, **kw)
    same = sum(a == b for a, b in zip(one["outputs"], many["outputs"]))
    log(f"tp{tp} greedy tokens identical to tp1 in {same} of {REQUESTS} "
        f"requests (reported, not required)")
    for r, (a, b) in enumerate(zip(one["outputs"], many["outputs"])):
        if a != b:
            i = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
            log(f"request {r} first differs at token {i}: {a[i]} vs {b[i]}")

    # where the weights live: each device holds a quarter of every sharded
    # projection, and the replicated leaves (embeddings, norms) whole
    cfg = get_config(ARCH, VARIANT)
    params = _quantize_params(tf.init_params(jax.random.PRNGKey(SEED), cfg),
                              "int8")
    ctx = _make_tp_context(cfg, params, tp)
    sharded = sharding_lib.TP_COL_PARALLEL + sharding_lib.TP_ROW_PARALLEL
    per_dev, per_dev_all, total, total_all = {}, {}, 0, 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(ctx["params"]):
        names = {getattr(k, "key", None) for k in path}
        for shard in leaf.addressable_shards:
            d = str(shard.device)
            per_dev_all[d] = per_dev_all.get(d, 0) + shard.data.nbytes
            if names & set(sharded):
                per_dev[d] = per_dev.get(d, 0) + shard.data.nbytes
        total_all += leaf.nbytes
        if names & set(sharded):
            total += leaf.nbytes
    shares = {d: b / total for d, b in per_dev.items()}
    log(f"sharded projection bytes {total}, share per device "
        f"{sorted(shares.values())}")
    log(f"all weight bytes {total_all}, per device "
        f"{sorted(per_dev_all.values())}")
    check(len(shares) == tp and
          all(abs(s - 1 / tp) <= 0.01 for s in shares.values()),
          f"sharded weights are not split in {tp}: {shares}")

    # the prefill logits of the step serve runs at tp=4, against tp=1
    cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    batch = {"tokens": jnp.asarray(np.stack(prompts(cfg.vocab)[:BATCH]))}
    cache = tf.init_cache(cfg, BATCH, PROMPT_LEN + GEN)
    cspecs = sharding_lib.tp_cache_specs(cache)
    logits_of = lambda c: lambda p, b, kv: tf.prefill(p, b, kv, c)[0]
    with blas.use_backend("xla"):
        want = jax.jit(logits_of(cfg))(params, batch, cache)
        step = steps_lib._tp_wrap(logits_of, cfg, ctx["mesh"],
                                  (ctx["pspecs"], P(), cspecs), P())
        got = jax.jit(step)(ctx["params"], batch, jax.device_put(
            cache, sharding_lib.to_shardings(cspecs, ctx["mesh"])))
    compare_logits(f"tp{tp} vs tp1", np.asarray(got, np.float32),
                   np.asarray(want, np.float32), cfg.vocab)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the Pallas serving phases; 4: only --tp 4 "
                         "against tp=1")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} devices,"
              f" JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    log(f"device {dev.device_kind} x{len(devices)}, compile cache "
        f"{compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

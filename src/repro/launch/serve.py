"""Batched serving driver: prefill + greedy decode, two schedulers.

Schedulers
----------
- "continuous" (default): real continuous batching over a fixed slot grid
  whose KV cache is a pool of pages with one page table per slot
  (`paging.SlotPages` keeps the page policy).  The moment a sequence
  finishes (EOS or its generation budget) its slot is freed and the next
  pending request is admitted at the next step boundary — an admission
  prefill on the fixed grid shape whose rows are grafted into the freed
  slots' pages, no waiting for the rest of the batch to drain.  Per-slot
  position state lives in the jit'd decode step (cache["pos"] is a
  (batch,) vector; the masked step freezes finished slots), so the donated
  KV cache keeps updating in place while occupancy stays high.  The decode batch shape never changes, so under
  --backend pallas every projection stays one fused broadcast-A `bgemv`
  launch at any occupancy — the bandwidth amortization the batch exists for
  (KBLAS, arXiv:1410.1726: throughput scales with live batch members, not
  launches).
- "batch": batch-at-a-time — admit `batch` requests, drain them all, then
  admit the next group.  Kept as the baseline the continuous scheduler is
  measured against (benchmarks/bench_serve.py).

Both schedulers serve the pending queue strictly FIFO and report per-request
TTFT, tok/s, decode-step counts and mean live-slot occupancy in serve()'s
stats.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --variant smoke --requests 16 --batch 4 --prompt-len 32 --gen 16 \
        --scheduler continuous --backend pallas
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blas, quant
from repro.launch import compile_cache
from repro.launch import draft as draft_lib
from repro.launch import faults as faults_lib
from repro.launch import paging
from repro.launch import sharding as sharding_lib
from repro.launch import spans as spans_lib
from repro.launch import steps as steps_lib
from repro.models import transformer as tf
from repro.models.registry import get_config

#: KV page size of the continuous scheduler when none is given
PAGE_SIZE = 16


def serve(arch: str, variant: str = "smoke", requests: Optional[int] = None, batch: int = 4,
          prompt_len: int = 32, gen: int = 16, seed: int = 0, eos: int = 2,
          verbose: bool = True, backend: str = "xla",
          scheduler: str = "continuous",
          gen_lens: Optional[Sequence[int]] = None,
          prompts: Optional[Sequence[np.ndarray]] = None,
          quantize: str = "none", kv_cache: str = "model",
          prefill_chunk: Optional[int] = None,
          kv_page_size: Optional[int] = None, prefix_reuse: bool = True,
          deadline_ms=None, pool_pages: Optional[int] = None,
          check_invariants: bool = False, faults=None,
          speculate: Optional[int] = None, tp: int = 1):
    """Serve `requests` synthetic prompts through greedy decode.

    quantize="int8" packs every projection weight with block-scaled int8
    (layers.quantize_weights) before serving: the bandwidth-bound decode
    path — one broadcast-weight bgemv over every weight matrix per token —
    streams 1 byte/weight instead of 2-4, with in-kernel dequantization
    under the pallas backend and packed host matvecs under xla.

    kv_cache="int8" packs the OTHER large decode byte term the same way:
    the KV cache stores block-scaled int8 (one f32 scale per (token, head),
    core.quant.quantize_kv), written in lockstep with the values and — under
    the pallas backend — streamed packed through the int8-KV flash attention
    kernel with in-kernel dequantization.  Composing both flags runs the
    fully-quantized decode byte path: weights AND KV at ~1 byte/element.

    gen_lens: optional per-request generation budgets (defaults to `gen` for
    every request) — the mixed-length distribution is where continuous
    batching wins.  A budget < 1 still yields one token (the prefill
    output).  eos=-1 disables early stopping (tokens are non-negative).
    prompts: optional explicit prompt list (tests pass the same prompts to a
    sequential oracle).  The continuous scheduler admits ragged prompt
    lengths (one admission prefill per distinct length per round); the
    batch scheduler requires uniform lengths and raises otherwise.
    prefill_chunk: continuous scheduler only — split every admission prefill
    into chunks of at most this many tokens, INTERLEAVED with decode steps,
    so a long-prompt admission no longer stalls every live slot's next token
    (TTFT head-of-line blocking under mixed traffic).  Chunk c continues the
    same cache-carrying prefill at the mini cache's position, so the grafted
    cache — and every generated token — is bit-identical to the unchunked
    admission's.
    Under --backend pallas the batched decode routes its
    projections through the fused batched kernels: every (B, 1, d) matmul is
    one bgemv launch over the request batch with broadcast weights.

    kv_page_size: the size in tokens of the KV cache's pages.  The
    continuous scheduler always stores the KV cache PAGED — a global pool of
    pages plus a per-slot page table — in pages of `kv_page_size` tokens,
    or PAGE_SIZE (16) when it is None.  Admission there is page-pointer
    writes: the prompt is hashed page by page against previously admitted
    prompts (prefix_reuse, default on), a matched prefix is backed by the
    SAME physical pages with a refcount bump, only the unshared suffix is
    grafted into the pool, and the first divergent write copies-on-write
    exactly one page.  Freed slots return their pages to a free list.  The
    batch scheduler keeps dense (batch, cache_len) buffers when it is None.
    Under the xla backend greedy tokens are bit-identical to a dense cache;
    under pallas the one-query paged decode is its own kernel, which sums
    in another order than dense flash, so tokens can differ from a dense
    run where logits nearly tie.  Stats gain `kv_page_size`, `pages_live`,
    `pages_shared`, `cow_copies` and `paged_capacity_multiplier` (logical /
    physical pages — >1 exactly when prefixes are shared).

    Operational robustness (ISSUE 8):

    pool_pages: override the paged pool size (default: sized so exhaustion
    cannot happen; the batch scheduler needs kv_page_size with it).  A small
    pool turns page pressure into real scheduling: admission BLOCKS at the
    allocator watermark (free + reclaimable pages, FIFO head first —
    backpressure, not a crash), and an allocation failure during decode-time
    page growth PREEMPTS a victim slot (newest admission first) whose
    request is re-queued at the head and later recomputed — continuous
    scheduler: re-prefill of prompt + already-emitted tokens, continuing the
    greedy stream bit-identically; batch scheduler: full recompute from the
    original prompt, same final tokens under greedy decoding.  (Caveat: the
    vlm family redraws its random patch embeds per admission, so a preempted
    vlm request's recompute is NOT bit-exact.) A request that cannot fit
    even a fully-free pool is terminally "rejected".

    speculate=k runs greedy SPECULATIVE decoding (ISSUE 9): every decode
    round, each live slot verifies k self-drafted candidate tokens (n-gram
    prompt-lookup over its own prompt + emitted stream, launch/draft.py —
    no second model) in ONE forward pass over a (batch, k+1) window, and
    commits the longest prefix that matches the model's own greedy argmax
    (launch/steps.py: make_verify_step_slots).  The emitted token stream is
    BIT-IDENTICAL to speculate=None by construction — the window's first
    position is the plain decode step, and a draft is accepted only when
    it equals exactly the token greedy decode would have picked — so draft
    quality affects throughput only.  The win is arithmetic intensity: the
    projection matvecs (Level-2, bandwidth-bound) become (batch, k+1, d)
    skinny GEMMs amortizing one packed weight stream over k+1 tokens per
    slot (the paper's Level-2 -> Level-3 reformulation applied at the
    scheduler).  KV for all k+1 candidates is written quantized/paged as
    usual; rejection is a per-slot `pos` rewind that leaves the dead tail
    masked past `kv_lens` (never a cache wipe), and under paged KV a
    write-window check enforces that rejected writes can never land in a
    page shared with another slot (refcount > 1).  Composes with both
    schedulers, --quantize int8, --kv-cache int8, --kv-page-size and
    --prefill-chunk; stats gain spec_tokens_per_step (committed tokens per
    slot per verify round), spec_acceptance_rate and spec_accept_hist.

    deadline_ms: per-request wall-clock budget (scalar or one per request),
    measured from serve start and enforced at decode-round boundaries — an
    expired request keeps its emitted tokens and finishes with status
    "timeout".  deadline_ms=0 deterministically yields exactly the prefill
    token.

    faults: a fault spec string ("exhaust@2,nan@5"), a
    launch.faults.FaultPlan, or None — deterministic injection of allocator
    exhaustion, graft failure, NaN/Inf activations and corrupt quant scales
    (see launch/faults.py).  check_invariants=True runs the page/refcount/
    finiteness invariant sweep every decode round (tests and CI smokes).

    Every request ends in exactly one terminal `status`: "ok",
    "preempted_resumed", "timeout" or "rejected"; stats count
    `preemptions`, `rejections` and `timeouts`, and `faults_fired` /
    `faults_unfired` record the injection log.

    Returns a stats dict: completed/tokens/prefills/decode_steps counters,
    tok_s, mean live-slot `occupancy`, per-request `ttft` (seconds to first
    generated token), `outputs` (greedy token ids per request, in submission
    order) and per-request admit/finish decode-step indices.  The
    continuous scheduler also returns its program spans, `spans` (one per
    decode round, token read-back and admission group, and in set-up, before
    t0, the weight packing `serve.pack`, whose length is `pack_s`;
    `launch/spans.py`).
    """
    cfg = get_config(arch, variant)
    rng = np.random.default_rng(seed)
    # request count comes from whichever of prompts/gen_lens/requests is
    # given (default 16); an explicit `requests` that disagrees is an error,
    # never a silent truncation.
    if prompts is not None:
        n = len(prompts)
    elif gen_lens is not None:
        n = len(gen_lens)
    else:
        n = requests if requests is not None else 16
    if requests is not None and requests != n:
        raise ValueError(f"requests={requests} but {n} prompts/gen_lens given")
    if prompts is None:
        prompts = [
            rng.integers(3, cfg.vocab, size=(prompt_len,), dtype=np.int32)
            for _ in range(n)
        ]
    prompts = [np.asarray(p, np.int32) for p in prompts]
    if gen_lens is None:
        gen_lens = [gen] * n
    if len(gen_lens) != n:
        raise ValueError(f"{len(gen_lens)} gen_lens for {n} requests")
    if quantize not in ("none", "int8"):
        raise ValueError(f"quantize must be 'none' or 'int8', got {quantize!r}")
    if kv_cache not in ("model", "int8"):
        raise ValueError(f"kv_cache must be 'model' or 'int8', got {kv_cache!r}")
    if kv_cache == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if prefill_chunk is not None and prefill_chunk < 1:
        raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
    if prefill_chunk is not None and scheduler != "continuous":
        raise ValueError("prefill_chunk interleaves admission chunks with "
                         "decode steps and needs --scheduler continuous")
    if speculate is not None:
        if speculate < 1:
            raise ValueError(f"speculate needs >= 1 draft tokens, got "
                             f"{speculate}")
        if cfg.family not in tf.SLOT_CACHE_FAMILIES:
            raise ValueError(
                f"speculative decoding rewinds per-slot KV positions and "
                f"supports {tf.SLOT_CACHE_FAMILIES} families; {cfg.family!r} "
                f"has recurrent state that cannot roll back"
            )
    if kv_page_size is not None:
        if kv_page_size < 1:
            raise ValueError(f"kv_page_size must be >= 1, got {kv_page_size}")
        if cfg.family not in tf.SLOT_CACHE_FAMILIES:
            raise ValueError(
                f"paged KV cache supports {tf.SLOT_CACHE_FAMILIES} families "
                f"(per-slot KV caches); {cfg.family!r} keeps the dense cache"
            )
    plan = faults_lib.as_plan(faults)
    if "qscale" in plan.events and kv_cache != "int8":
        raise ValueError("qscale faults corrupt KV quantization scales and "
                         "need kv_cache='int8'")
    if pool_pages is not None:
        if kv_page_size is None and scheduler != "continuous":
            raise ValueError("pool_pages sizes the paged pool and needs "
                             "kv_page_size")
        if pool_pages < 2:
            raise ValueError(f"pool_pages needs >= 2 (trash + 1 allocatable), "
                             f"got {pool_pages}")
    if deadline_ms is not None:
        deadline_ms = ([float(deadline_ms)] * n if np.isscalar(deadline_ms)
                       else [None if d is None else float(d) for d in deadline_ms])
        if len(deadline_ms) != n:
            raise ValueError(f"{len(deadline_ms)} deadline_ms for {n} requests")
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > 1:
        if backend != "xla":
            raise ValueError("tensor-parallel serving shards the packed host "
                             "matvec path and needs --backend xla")
        if cfg.family != "dense":
            raise ValueError(
                f"--tp shards attention heads and FFN features of the dense "
                f"family; {cfg.family!r} is not wired for the model axis")
        for field, val in (("n_heads", cfg.n_heads), ("n_kv", cfg.n_kv),
                           ("d_ff", cfg.d_ff)):
            if val % tp:
                raise ValueError(f"--tp {tp} must divide {field}={val}")
        if len(jax.devices()) < tp:
            raise ValueError(
                f"--tp {tp} needs {tp} devices but only "
                f"{len(jax.devices())} are visible; emulate host devices "
                f"with XLA_FLAGS=--xla_force_host_platform_device_count={tp} "
                f"(must be set before jax initializes)")
    with blas.use_backend(backend):
        if scheduler == "continuous":
            if cfg.family not in tf.SLOT_CACHE_FAMILIES:
                raise ValueError(
                    f"continuous scheduler supports {tf.SLOT_CACHE_FAMILIES} "
                    f"families (per-slot KV caches); {cfg.family!r} needs "
                    f"--scheduler batch"
                )
            stats = _serve_continuous(cfg, prompts, list(gen_lens), batch, seed,
                                      eos, quantize, prefill_chunk,
                                      page_size=kv_page_size or PAGE_SIZE,
                                      prefix_reuse=prefix_reuse,
                                      deadline_ms=deadline_ms,
                                      pool_pages=pool_pages,
                                      check_invariants=check_invariants,
                                      plan=plan, speculate=speculate, tp=tp)
        elif scheduler == "batch":
            stats = _serve_batch(cfg, prompts, list(gen_lens), batch, seed, eos,
                                 quantize, page_size=kv_page_size,
                                 deadline_ms=deadline_ms,
                                 pool_pages=pool_pages,
                                 check_invariants=check_invariants,
                                 plan=plan, speculate=speculate, tp=tp)
        else:
            raise ValueError(f"scheduler must be 'continuous' or 'batch', got {scheduler!r}")
    stats["tp"] = tp
    if verbose:
        paged_info = ""
        if "pages_live" in stats:
            paged_info = (f", pages {stats['pages_live']} live / "
                          f"{stats['pages_shared']} shared, "
                          f"{stats['cow_copies']} CoW, capacity "
                          f"x{stats['paged_capacity_multiplier']:.2f}")
        robust_info = ""
        if stats["preemptions"] or stats["rejections"] or stats["timeouts"]:
            robust_info = (f", {stats['preemptions']} preemptions / "
                           f"{stats['rejections']} rejections / "
                           f"{stats['timeouts']} timeouts")
        if stats.get("faults_fired"):
            robust_info += f", faults fired {stats['faults_fired']}"
        if "spec_tokens_per_step" in stats:
            robust_info += (f", spec {stats['spec_tokens_per_step']:.2f} "
                            f"tok/step (accept "
                            f"{stats['spec_acceptance_rate']:.2f})")
        print(f"[serve] {arch} ({scheduler}): {stats['completed']} requests, "
              f"{stats['tokens']} tokens in {stats['elapsed_s']:.2f}s -> "
              f"{stats['tok_s']:.1f} tok/s ({stats['prefills']} prefills, "
              f"{stats['decode_steps']} decode steps, "
              f"occupancy {stats['occupancy']:.2f}{paged_info}{robust_info})",
              flush=True)
    return stats


def _new_stats(nreq: int) -> dict:
    return {
        "completed": 0, "tokens": 0, "prefills": 0, "decode_steps": 0,
        "outputs": [[] for _ in range(nreq)],
        # per-token arrival timestamps (seconds since serve start), one per
        # outputs entry.  One verify round can commit SEVERAL tokens at a
        # single wall-clock instant — they share the round's completion
        # time — so TTFT/ITL percentiles stay truthful at speculate=k>1
        # instead of pretending tokens arrived one per round.
        "token_times": [[] for _ in range(nreq)],
        "ttft": [None] * nreq,
        "admit_step": [None] * nreq,
        "finish_step": [None] * nreq,
        # terminal status per request: "ok" (completed untouched),
        # "preempted_resumed" (completed, but was preempted and recomputed
        # at least once), "timeout" (deadline_ms expired at a decode-round
        # boundary), "rejected" (can never fit the page pool) — None while
        # in flight
        "status": [None] * nreq,
        "preemptions": 0,     # slots preempted (victims of pool pressure)
        "rejections": 0,      # requests that can never fit the pool
        "timeouts": 0,        # requests cut by their deadline
        # worst case over the run, measured between consecutive decode steps
        # while live slots exist: wall clock, and — deterministically — how
        # many admission-prefill tokens were processed in the gap (the
        # head-of-line blocking chunked admission exists to bound)
        "max_stall_ms": 0.0,
        "max_stall_prefill_tokens": 0,
    }


def _record_token(stats: dict, rid: int, tok_val: int, eos: int,
                  remaining: int, preempted: bool = False,
                  t_now=None) -> bool:
    """Append one generated token for request `rid`; returns True if the
    request just finished (EOS, or its budget has `remaining` <= 0 tokens
    left AFTER this one).  The single budget/EOS rule both schedulers use —
    keep it in one place so they cannot drift.  `preempted` marks whether
    the request was ever preempted, for the terminal status.  `t_now` is
    the token's arrival time (seconds since serve start): every accepted
    token of a verify round shares the round's completion time."""
    stats["outputs"][rid].append(tok_val)
    stats["token_times"][rid].append(t_now)
    stats["tokens"] += 1
    if tok_val == eos or remaining <= 0:
        stats["finish_step"][rid] = stats["decode_steps"]
        stats["completed"] += 1
        stats["status"][rid] = "preempted_resumed" if preempted else "ok"
        return True
    return False


def _timeout(stats: dict, rid: int) -> None:
    """Terminal bookkeeping for a deadline expiry at a decode-round
    boundary: emitted tokens are kept, the request counts as completed with
    status "timeout"."""
    stats["status"][rid] = "timeout"
    stats["timeouts"] += 1
    stats["finish_step"][rid] = stats["decode_steps"]
    stats["completed"] += 1


def _deadline_expired(deadline_ms, rid: int, t0: float) -> bool:
    dl = deadline_ms[rid] if deadline_ms else None
    return dl is not None and (time.time() - t0) * 1e3 >= dl


def _finalize(stats: dict, occ: list, t0: float) -> dict:
    dt = time.time() - t0
    stats["elapsed_s"] = dt
    stats["tok_s"] = stats["tokens"] / dt if dt > 0 else 0.0
    stats["occupancy"] = float(np.mean(occ)) if occ else 0.0
    if stats.get("spec_slot_steps"):
        # committed tokens per slot per verify round — the structural
        # amortization factor (1.0 would mean every draft was rejected and
        # speculation degenerated to plain decode)
        stats["spec_tokens_per_step"] = (stats["spec_emitted"]
                                         / stats["spec_slot_steps"])
        prop = stats["spec_drafts_proposed"]
        stats["spec_acceptance_rate"] = (stats["spec_drafts_accepted"] / prop
                                         if prop else 0.0)
    return stats


def _cache_len(cfg, prompts, gen_lens: Sequence[int]) -> int:
    """Slot capacity: the worst-case prompt + its OWN generation budget (the
    continuous scheduler admits ragged prompt lengths per slot)."""
    need = max(len(p) + g for p, g in zip(prompts, gen_lens))
    return need + (cfg.n_prefix if cfg.family == "vlm" else 0)


def _prefill_extras(cfg, rng, n: int, enc: int) -> dict:
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = jnp.asarray(
            rng.standard_normal((n, cfg.n_prefix, cfg.d_model)).astype(np.float32)
        )
    if cfg.family == "audio":
        extras["frames"] = jnp.asarray(
            rng.standard_normal((n, enc, cfg.d_model)).astype(np.float32)
        )
    return extras


def _quantize_params(params, quantize: str, spans):
    """The serving weights: with `quantize="int8"` every projection packed
    (`layers.quantize_weights`), as one `serve.pack` span in `spans` with
    the leaves packed and their bytes before and after."""
    if quantize != "int8":
        return params
    from repro.models import layers
    with spans.span("serve.pack") as pack:
        packed = jax.block_until_ready(layers.quantize_weights(params))
        pairs = [(w, q) for w, q in zip(
            jax.tree.leaves(params),
            jax.tree.leaves(packed, is_leaf=quant.is_quantized))
            if quant.is_quantized(q)]
        pack.set(leaves=len(pairs),
                 bytes_in=sum(w.nbytes for w, _ in pairs),
                 bytes_out=sum(q.values.nbytes + q.scales.nbytes
                               for _, q in pairs))
    return packed


def _make_tp_context(cfg, params, tp: int):
    """Shard the serve params for `--tp N`: 1-D ("model",) mesh, Megatron
    column/row layout (launch.sharding.tp_param_specs), packed weights
    block-aligned first so int8 values and scale grids split in lockstep.
    Weights are device_put ONCE here — every per-step jit then consumes
    them already resident at the shard_map's required sharding (no per-call
    resharding).  Returns None at tp=1 so the single-device path is
    untouched."""
    if tp <= 1:
        return None
    mesh = steps_lib.tp_mesh(tp)
    params = sharding_lib.tp_align_params(params, tp)
    pspecs = sharding_lib.tp_param_specs(params, cfg, mesh)
    params = jax.device_put(params, sharding_lib.to_shardings(pspecs, mesh))
    return {"mesh": mesh, "pspecs": pspecs, "params": params}


def _slot_programs(cfg, spec, fault_kinds, tp_ctx, slot_cache, mini_zero):
    """The continuous scheduler's jitted programs: the admission prefill
    (its zero template is reused every round: no donation), the slot decode
    step — the verify step under speculation, one (B, k+1) window launch
    per round — and its poisoned variants for the NaN/Inf faults in
    `fault_kinds`.  Also returns `put_slot`, which places a slot cache at
    the sharding the steps take (so the donated buffers never reshard per
    step), and the mini cache template placed likewise.  Under --tp the
    steps are the sharded ones, with cache specs taken from a real slot
    cache, page pool included (`slot_cache()`)."""
    if tp_ctx is None:
        prefill = steps_lib.make_prefill_step(cfg)

        def mk_step(act_fault=None):
            if spec:
                return steps_lib.make_verify_step_slots(cfg, spec,
                                                        act_fault=act_fault)
            return steps_lib.make_decode_step_slots(cfg, act_fault=act_fault)

        def put_slot(c):
            return c
    else:
        mesh, pspecs = tp_ctx["mesh"], tp_ctx["pspecs"]
        mini_specs = sharding_lib.tp_cache_specs(mini_zero)
        slot_specs = sharding_lib.tp_cache_specs(slot_cache())
        slot_shardings = sharding_lib.to_shardings(slot_specs, mesh)
        prefill = steps_lib.make_tp_prefill_step(cfg, mesh, pspecs, mini_specs)

        def mk_step(act_fault=None):
            if spec:
                return steps_lib.make_tp_verify_step_slots(
                    cfg, mesh, spec, pspecs, slot_specs, act_fault=act_fault)
            return steps_lib.make_tp_decode_step_slots(
                cfg, mesh, pspecs, slot_specs, act_fault=act_fault)

        def put_slot(c):
            return jax.device_put(c, slot_shardings)

        mini_zero = jax.device_put(
            mini_zero, sharding_lib.to_shardings(mini_specs, mesh))
    decode = jax.jit(mk_step(), donate_argnums=(2,))
    faulted = {
        kind: jax.jit(mk_step(act_fault=val), donate_argnums=(2,))
        for kind, val in (("nan", float("nan")), ("inf", float("inf")))
        if kind in fault_kinds
    }
    return jax.jit(prefill), decode, faulted, put_slot, mini_zero


def _serve_continuous(cfg, prompts, gen_lens, batch, seed, eos, quantize="none",
                      prefill_chunk=None, page_size=PAGE_SIZE, prefix_reuse=True,
                      deadline_ms=None, pool_pages=None,
                      check_invariants=False, plan=None, speculate=None,
                      tp=1):
    """Slot-level admission: finished sequences free their slot immediately;
    each free slot prefills the next FIFO request into the shared cache.

    With `prefill_chunk`, an admission prefill longer than the chunk runs as
    a sequence of fixed-size chunk prefills through the SAME cache-carrying
    prefill step (positions continue at the mini cache's pos), and every
    chunk boundary is a decode opportunity for the live slots — one long
    admission costs each live slot at most one chunk of prefill work between
    its tokens instead of the whole prompt.

    The slot cache is a pool of `page_size`-token pages plus a page table
    per slot.  Every page decision — the admission watermark, placement
    with prefix sharing and copy-on-write, growth, victim choice, release —
    is `paging.SlotPages`'s; this function makes the device writes that
    mirror them: admission writes the slot's page-table row (matched shared
    prefix pages + fresh pages) and grafts only the unshared suffix tokens;
    a finished slot's row is repointed at the trash page.  The decode step
    itself is one masked launch over the slot grid, reading and writing
    straight through the page table.

    Robustness layer.  Admission reserves only the pages the prompt plus the
    first decode write need; decode GROWS the slot's page run on demand at
    page boundaries.  The FIFO head blocks at the watermark (free +
    reclaimable pages) — backpressure — instead of crashing the pool.  A
    growth (or injected) allocation failure preempts a victim slot and
    re-queues its request at the queue head; the re-admission prefills the
    ORIGINAL PROMPT + ALREADY-EMITTED TOKENS, which by the chunked-prefill
    parity property continues the greedy stream bit-identically
    (already-emitted tokens are never re-recorded).  Per-request deadlines
    are enforced at decode-round boundaries (terminal status "timeout"); a
    request whose prompt can never fit the whole pool is terminally
    "rejected".  `plan` (a faults.FaultPlan) injects deterministic
    exhaustion/graft/NaN/Inf/scale faults, and `check_invariants` sweeps
    the allocator/page-table/finiteness invariants every round."""
    plan = plan if plan is not None else faults_lib.FaultPlan({})
    nreq = len(prompts)
    spec = int(speculate or 0)
    # speculate=k headroom: a verify round writes KV for all k+1 window
    # positions before the acceptance decision, so the last live round may
    # scribble up to k slots past a sequence's final committed position
    # (the masked-dead tail rollback leaves behind)
    cache_len = _cache_len(cfg, prompts, gen_lens) + spec
    rng = np.random.default_rng(seed + 1)

    # the recorder starts before set-up: its records move onto the clock
    # of t0 below, so a set-up span (`serve.pack`) ends before 0
    spans = spans_lib.Spans(time.time())
    params = _quantize_params(tf.init_params(jax.random.PRNGKey(seed), cfg),
                              quantize, spans)
    tp_ctx = _make_tp_context(cfg, params, tp)
    if tp_ctx is not None:
        params = tp_ctx["params"]
    mini_zero = tf.init_cache(cfg, batch, cache_len)

    # vlm prompts carry per-admission random patch embeds in front of the
    # tokens, so equal token ids do NOT mean equal KV: never share
    n_prefix = cfg.n_prefix if cfg.family == "vlm" else 0
    pages = paging.SlotPages(page_size, batch, cache_len, num_pages=pool_pages,
                             share=prefix_reuse and cfg.family != "vlm",
                             n_prefix=n_prefix)
    graft_fn = jax.jit(tf.graft_pages, donate_argnums=(0,))
    copy_fn = jax.jit(tf.copy_pages, donate_argnums=(0,))

    def slot_cache():
        return tf.init_cache(cfg, batch, cache_len, per_slot=True,
                             page_size=page_size, num_pages=pages.num_pages)

    prefill_fn, decode_fn, decode_faulted, put_slot, mini_zero = \
        _slot_programs(cfg, spec, plan.events, tp_ctx, slot_cache, mini_zero)
    if spec:
        drafter = draft_lib.make_drafter("ngram")

    # compile outside the timed region (throwaway buffers), so the stats
    # measure scheduling, not jit.  Ragged prompts still trace one extra
    # prefill per distinct length inside the loop.
    warm_in = {"tokens": jnp.zeros((batch, len(prompts[0])), jnp.int32)}
    warm_in.update(_prefill_extras(cfg, rng, batch, 0))
    warm_tok0, warm_mini = prefill_fn(params, warm_in, mini_zero)
    zc = jnp.zeros((batch * (len(prompts[0]) + n_prefix),), jnp.int32)
    warm_cache = graft_fn(put_slot(slot_cache()), warm_mini, zc, zc, zc, zc)
    warm_cache = copy_fn(warm_cache, jnp.zeros((1,), jnp.int32),
                         jnp.zeros((1,), jnp.int32))
    if spec:
        warm_tok, _, warm_cache = decode_fn(
            params, jnp.zeros((batch, spec + 1), jnp.int32), warm_cache,
            jnp.zeros(batch, bool))
    else:
        warm_tok, warm_cache = decode_fn(params, jnp.zeros((batch, 1), jnp.int32),
                                         warm_cache, jnp.zeros(batch, bool))
    jax.block_until_ready(warm_tok)
    del warm_mini, warm_cache, warm_tok, warm_tok0

    pending = collections.deque(enumerate(prompts))  # FIFO: popleft serves arrival order
    cache = put_slot(slot_cache())
    # the token block and active mask live on device; the host only touches
    # rows on admission/finish events, so a steady decode step has no H2D
    # transfer (same as the batch-at-a-time loop)
    tok_dev = jnp.zeros((batch, 1), jnp.int32)
    active_dev = jnp.zeros(batch, bool)
    slot_req = np.full(batch, -1)
    slot_left = np.zeros(batch, np.int64)
    slot_pos = np.zeros(batch, np.int64)        # next decode write position
    slot_last = np.zeros(batch, np.int64)       # last COMMITTED token (spec
    preempted_ever = [False] * nreq             # window pos 0)
    active = np.zeros(batch, bool)
    # the device mask went stale via a free/preempt outside admission; the
    # next decode round refreshes it once instead of per event
    dirty = [False]
    stats = _new_stats(nreq)
    pack = spans.last("serve.pack")
    stats["pack_s"] = pack["end"] - pack["start"] if pack else 0.0
    stats["kv_page_size"] = page_size
    stats.update(pages.peaks)
    if spec:
        stats.update({"speculate": spec, "spec_slot_steps": 0,
                      "spec_emitted": 0, "spec_drafts_proposed": 0,
                      "spec_drafts_accepted": 0,
                      # spec_accept_hist[a] = verify rounds (per slot) that
                      # accepted exactly a of the k drafts
                      "spec_accept_hist": [0] * (spec + 1)})

    occ = []
    t0 = time.time()
    spans.rebase(t0)
    # inter-token stall trackers for LIVE slots: the first record of the
    # current busy period (a stall is measured between the ends of two
    # `serve.round.wait`s inside one), and admission-prefill tokens
    # processed since the last decode step
    busy_from = [0]
    prefill_gap = [0]
    # an admission group was grafted since the last decode round
    grafted = [False]

    def exhausted():
        return plan.take("exhaust")

    def trash_row(s):
        """Repoint slot s's table row at trash, so the frozen slot's masked
        decode writes can never land in a recycled page."""
        nonlocal cache
        cache["page_table"] = cache["page_table"].at[s].set(paging.TRASH_PAGE)

    def free_slot(s):
        """Release slot s's pages and trash its row.  Shared by finish,
        timeout and preemption."""
        if spec and slot_req[s] >= 0:
            # preempted requests get a fresh begin() at re-admission
            drafter.forget(int(slot_req[s]))
        active[s] = False
        slot_req[s] = -1
        dirty[0] = True
        pages.release(s)
        trash_row(s)

    def preempt(s):
        """Evict slot s (its pages may already be released by `pages`) and
        put its request back at the HEAD of the queue.  The re-admission
        prefills the original prompt + every token already emitted, so the
        greedy stream continues bit-identically; emitted tokens are never
        re-recorded."""
        vid = slot_req[s]
        stats["preemptions"] += 1
        preempted_ever[vid] = True
        free_slot(s)
        pending.appendleft((vid, prompts[vid]))

    def poison_scale():
        """qscale fault: write Inf into a live KV quantization scale — the
        corruption check_cache_finite exists to catch."""
        nonlocal cache
        if "k_scale" not in cache:
            return
        arr = cache["k_scale"]
        live = [s for s in range(batch) if active[s] and pages.slots[s]]
        loc = pages.slots[live[0]][0] if live else paging.TRASH_PAGE
        idx = (0, loc) + (0,) * (arr.ndim - 2)
        cache["k_scale"] = arr.at[idx].set(jnp.inf)

    def decode_round():
        """One masked decode step over the live slots + host bookkeeping —
        called from the main loop AND between admission prefill chunks.
        Round boundaries are where deadlines are enforced, injected faults
        fire, page runs grow, and (under --check-invariants) the full
        invariant sweep runs.  A round that steps a slot is one
        `serve.round` span."""
        with spans.span("serve.round", step=stats["decode_steps"]) as rnd:
            if not step_round(rnd):
                rnd.drop()

    def step_round(rnd):
        """The body of `decode_round`; False when no slot was stepped."""
        nonlocal tok_dev, cache, active_dev
        step_idx = stats["decode_steps"]
        # deadline sweep FIRST: boundaries are the only cut points, so a
        # deadline_ms=0 request deterministically keeps exactly its prefill
        # token
        for s in range(batch):
            if active[s] and _deadline_expired(deadline_ms, slot_req[s], t0):
                _timeout(stats, slot_req[s])
                free_slot(s)
        if not active.any():
            active_dev = jnp.asarray(active)
            dirty[0] = False
            return False
        # injected faults for THIS round
        if plan.at_step("preempt", step_idx):
            v = pages.victim(active)
            if v is not None:
                preempt(v)
        for s in range(batch):
            if active[s]:
                # grow the run over this round's writes: positions
                # slot_pos[s] .. slot_pos[s]+spec (a verify round writes all
                # k+1 candidates before acceptance)
                writes, victims = pages.grow(s, int(slot_pos[s]) + spec, active,
                                             exhausted)
                for pidx, p in writes:
                    cache["page_table"] = cache["page_table"].at[s, pidx].set(p)
                for v in victims:
                    preempt(v)
        if spec:
            # CoW hazard gate: every page a verify round may write must be
            # exclusively owned — a rejected-draft write into a page with
            # refcount > 1 would corrupt another slot's committed prefix.
            # Structural (admission CoWs/unpublishes the write page, growth
            # pages are fresh), enforced every round.
            faults_lib.check_write_window(pages.alloc, active, pages.slots,
                                          slot_pos, page_size, spec)
        if plan.at_step("qscale", step_idx):
            poison_scale()
        fn = decode_fn
        for kind in ("nan", "inf"):
            if plan.at_step(kind, step_idx):
                fn = decode_faulted[kind]
        if dirty[0]:
            active_dev = jnp.asarray(active)
            dirty[0] = False
        stepped = active.copy()
        if not stepped.any():
            return False
        occ.append(stepped.sum() / batch)
        # KV positions each stepped slot attends: its cache length after
        # this round's write (k+1 positions under speculation, read once)
        rnd.set(live=int(stepped.sum()),
                kv_tokens=int((slot_pos[stepped] + 1 + spec).sum()),
                after_admit=grafted[0])
        grafted[0] = False
        prev_wait = spans.last("serve.round.wait", busy_from[0])
        if spec:
            # verify window per live slot: [last committed token] + k
            # drafts.  One H2D for the grid — the drafts are host state
            # (n-gram lookup over prompt + emitted), so the steady-state
            # zero-transfer property of plain decode is traded for the
            # k+1-token GEMM amortization the window exists for.
            win = np.zeros((batch, spec + 1), np.int32)
            for s in range(batch):
                if stepped[s]:
                    win[s, 0] = slot_last[s]
                    win[s, 1:] = drafter.propose(int(slot_req[s]), spec)
            preds, acc, cache = fn(params, jnp.asarray(win), cache,
                                   active_dev)
            with spans.span("serve.round.wait") as wait:
                tok_np = np.asarray(preds)      # (B, k+1) greedy argmaxes
                acc_np = np.asarray(acc)        # (B,) accepted draft counts
        else:
            tok_dev, cache = fn(params, tok_dev, cache, active_dev)
            with spans.span("serve.round.wait") as wait:
                tok_np = np.asarray(tok_dev)[:, 0]
        stats["decode_steps"] += 1
        t_now = wait.record["end"]
        if prev_wait is not None:
            stats["max_stall_ms"] = max(stats["max_stall_ms"],
                                        (t_now - prev_wait["end"]) * 1e3)
        stats["max_stall_prefill_tokens"] = max(
            stats["max_stall_prefill_tokens"], prefill_gap[0])
        prefill_gap[0] = 0
        for s in range(batch):
            if not stepped[s]:
                continue
            rid = slot_req[s]
            if spec:
                # longest-accepted-prefix commit: positions 0..acc are the
                # model's own greedy picks (draft j accepted iff it equals
                # pred j-1), position acc is the bonus token.  The device
                # already rewound pos to pos0+acc+1; rejected writes sit in
                # the masked-dead tail past kv_lens.  All committed tokens
                # share this round's completion timestamp.
                n_acc = int(acc_np[s])
                stats["spec_slot_steps"] += 1
                stats["spec_drafts_proposed"] += spec
                stats["spec_drafts_accepted"] += n_acc
                stats["spec_accept_hist"][n_acc] += 1
                for tv in tok_np[s, :n_acc + 1]:
                    slot_pos[s] += 1
                    slot_left[s] -= 1
                    stats["spec_emitted"] += 1
                    drafter.observe(rid, int(tv))
                    if _record_token(stats, rid, int(tv), eos, slot_left[s],
                                     preempted=preempted_ever[rid],
                                     t_now=t_now):
                        # budget/EOS can land mid-window: later accepted
                        # tokens are DROPPED, exactly where plain decode
                        # would have stopped — parity is a prefix property
                        free_slot(s)
                        break
                else:
                    slot_last[s] = int(tok_np[s, n_acc])
            else:
                slot_pos[s] += 1
                slot_left[s] -= 1
                if _record_token(stats, rid, int(tok_np[s]), eos,
                                 slot_left[s],
                                 preempted=preempted_ever[rid], t_now=t_now):
                    free_slot(s)
        if dirty[0]:
            active_dev = jnp.asarray(active)
            dirty[0] = False
        pages.sample()
        if check_invariants:
            faults_lib.check_serve_invariants(
                alloc=pages.alloc, table=cache["page_table"], active=active,
                slot_pages=pages.slots, cache=cache)
        return True

    while pending or active.any():
        if not active.any():
            # nobody live to stall: an admission from an idle grid is free
            busy_from[0] = len(spans.records)
            prefill_gap[0] = 0
        # admission: every free slot takes the next pending request at this
        # step boundary — no waiting for the batch to drain.  Like decode,
        # the admission prefill runs on the fixed grid shape (one launch per
        # distinct prompt length this round; padding rows are dropped at the
        # graft), so a lone admission is not a degenerate batch-1 launch.
        # Under pool pressure the FIFO head BLOCKS at the watermark —
        # backpressure, never skip-ahead — and a request that could not fit
        # even a fully-free pool is terminally "rejected".  A re-queued
        # (preempted) request's admission prompt is its original prompt +
        # every token it already emitted, so the greedy continuation is
        # bit-identical.
        admits = []       # (slot, rid, admission_prompt, n_already_emitted)
        reserved = 0      # pages this round's earlier picks will allocate
        blocked = False
        for s in range(batch):
            if active[s] or blocked:
                continue
            while pending:
                rid, base = pending[0]
                em = stats["outputs"][rid]
                adm = (np.concatenate([base, np.asarray(em, np.int32)])
                       if em else base)
                need = pages.need(adm)
                if need is None:
                    pending.popleft()
                    stats["status"][rid] = "rejected"
                    stats["rejections"] += 1
                    continue  # same slot, next request
                if not pages.fits(need + reserved, active):
                    blocked = True  # FIFO head blocks; no skip-ahead
                    break
                reserved += need
                pending.popleft()
                admits.append((s, rid, adm, len(em)))
                break
        by_len = {}
        for adm_t in admits:
            by_len.setdefault(len(adm_t[2]), []).append(adm_t)
        for plen in sorted(by_len):
            group = by_len[plen]
            with spans.span("serve.admit", plen=plen, rows=len(group),
                            rids=[rid for _, rid, _, _ in group]):
                block = np.zeros((batch, plen), np.int32)
                for i, (_, _, adm, _) in enumerate(group):
                    block[i] = adm
                csize = plen if prefill_chunk is None else min(prefill_chunk, plen)
                mini = mini_zero
                tok0 = None
                for start in range(0, plen, csize):
                    if start and active.any():
                        # a chunk boundary is a decode opportunity: every live
                        # slot advances one token before the next prefill chunk
                        decode_round()
                    batch_in = {"tokens": jnp.asarray(block[:, start:start + csize])}
                    if start == 0:
                        # patches/frames ride on the first chunk only (the vlm
                        # prefix sits at the front of the sequence)
                        batch_in.update(_prefill_extras(cfg, rng, batch, 0))
                    tok0, mini = prefill_fn(params, batch_in, mini)
                    stats["prefills"] += 1
                    if active.any():
                        prefill_gap[0] += min(csize, plen - start)
                # page-pointer admission: a matched prefix is already
                # resident in the pool, so only the unshared suffix tokens
                # are grafted out of the mini cache
                pl = pages.place(
                    [(s, adm, gen_lens[rid] - n_em - 1 > 0)
                     for s, rid, adm, n_em in group], active)
                for v in pl.victims:
                    preempt(v)
                if plan.take("graft"):
                    # simulated graft failure, injected BEFORE the donating
                    # graft call: the device cache is untouched, so recovery
                    # is pure bookkeeping — back out every placement and
                    # requeue the whole group at the queue head
                    for (s, _, _, _), ok in zip(group, pl.placed):
                        if ok:
                            pages.release(s)
                    for rid in reversed([r for _, r, _, _ in group]):
                        pending.appendleft((rid, prompts[rid]))
                    continue
                total = plen + n_prefix
                srows = jnp.asarray([s for s, _, _, _ in group])
                cache["page_table"] = cache["page_table"].at[srows].set(
                    jnp.asarray(pl.rows, jnp.int32))
                cache["pos"] = cache["pos"].at[srows].set(total)
                cache = graft_fn(cache, mini,
                                 *(jnp.asarray(c, jnp.int32) for c in pl.graft))
                for src, dst in pl.cow:
                    # after the graft: a CoW source may be a page this very
                    # group grafts (identical prompts share it), and the
                    # graft never writes a CoW destination
                    cache = copy_fn(cache, jnp.asarray([src]),
                                    jnp.asarray([dst]))
                grafted[0] = True
                safe = np.full(batch, batch, np.int32)
                for i, (s, _, _, _) in enumerate(group):
                    if pl.placed[i]:
                        safe[i] = s
                tok_dev = tok_dev.at[jnp.asarray(safe)].set(tok0, mode="drop")
                pages.sample()
                for (_, rid, _, _), ok in reversed(list(zip(group, pl.placed))):
                    if not ok:
                        # placement-failed members go back to the queue head
                        # in their original order
                        pending.appendleft((rid, prompts[rid]))
                tok0_np = np.asarray(tok0)[:, 0]  # sync BEFORE stamping TTFT
                t_first = time.time() - t0
                for i, (s, rid, adm, n_em) in enumerate(group):
                    if not pl.placed[i]:
                        continue
                    if stats["ttft"][rid] is None:
                        # a resumed request keeps its FIRST admission's TTFT and
                        # admit step — the preemption cost shows up in latency,
                        # not as a fresh arrival
                        stats["ttft"][rid] = t_first
                        stats["admit_step"][rid] = stats["decode_steps"]
                    rem = gen_lens[rid] - n_em - 1
                    if spec:
                        # (re)seed the drafter with the FULL admission context —
                        # prompt + already-emitted for a resumed request — then
                        # mirror the prefill token like any committed token
                        drafter.begin(rid, adm)
                        drafter.observe(rid, int(tok0_np[i]))
                    if not _record_token(stats, rid, int(tok0_np[i]), eos, rem,
                                         preempted=preempted_ever[rid],
                                         t_now=t_first):
                        active[s] = True
                        slot_req[s] = rid
                        slot_left[s] = rem
                        slot_last[s] = int(tok0_np[i])
                        slot_pos[s] = total
                    else:
                        if spec:
                            drafter.forget(rid)
                        # finished on its prefill token: nothing will ever
                        # be decoded into these pages
                        pages.release(s)
                        trash_row(s)
                pages.sample()
                # refresh the device mask per GROUP (not per round): a later
                # group's chunk-boundary decode must advance this group's slots
                active_dev = jnp.asarray(active)
                dirty[0] = False
        if not active.any():
            continue  # remaining pending requests all finished at prefill
        decode_round()
    pages.sample()
    stats.update(pages.peaks)
    # conservation at end-of-serve ALWAYS (cheap): a leak here is a real
    # production bug even when nothing was injected
    pages.leak_check()
    stats["faults_fired"] = list(plan.fired)
    stats["faults_unfired"] = plan.pending()
    stats["spans"] = spans.records
    return _finalize(stats, occ, t0)


def _serve_batch(cfg, prompts, gen_lens, batch, seed, eos, quantize="none",
                 page_size=None, deadline_ms=None, pool_pages=None,
                 check_invariants=False, plan=None, speculate=None, tp=1):
    """Batch-at-a-time baseline: a finished sequence's slot idles until the
    whole batch drains.  The queue is still served strictly FIFO.

    page_size stores each group's KV paged (fresh pages per slot, released
    as each member finishes).  No prefix sharing here — all slots prefill
    into their pages in one launch, so there is nothing admitted "earlier"
    to share with; the capacity multiplier stays 1.0 by construction and
    the continuous scheduler is where dedupe pays.

    Robustness layer (ISSUE 8).  Rows reserve only the pages the prompt +
    first decode write need and GROW on demand at page boundaries; group
    size is capped so every member's reservation fits the pool.  A growth
    (or injected) allocation failure preempts the NEWEST live member —
    batch-at-a-time admission cannot re-enter mid-stream (uniform prompt
    lengths), so preemption here is a FULL recompute: the victim's emitted
    tokens are discarded and its request re-served from the original prompt
    in a later group, which greedy decoding makes bit-identical.  Deadlines
    cut at decode-round boundaries; `plan` injects the same fault kinds as
    the continuous scheduler."""
    plan = plan if plan is not None else faults_lib.FaultPlan({})
    nreq = len(prompts)
    prompt_len = len(prompts[0])
    if any(len(p) != prompt_len for p in prompts):
        raise ValueError(
            "batch scheduler stacks prompts into one (batch, T) prefill and "
            "needs uniform prompt lengths; ragged prompts need --scheduler "
            "continuous (per-slot prefill)"
        )
    spec = int(speculate or 0)
    # verify-round KV headroom past the final committed position, as in the
    # continuous scheduler
    cache_len = _cache_len(cfg, prompts, gen_lens) + spec
    enc = cfg.encoder.n_frames if cfg.family == "audio" else 0
    n_prefix = cfg.n_prefix if cfg.family == "vlm" else 0
    rng = np.random.default_rng(seed + 1)

    params = _quantize_params(tf.init_params(jax.random.PRNGKey(seed), cfg),
                              quantize, spans_lib.Spans(time.time()))
    tp_ctx = _make_tp_context(cfg, params, tp)
    if tp_ctx is not None:
        params = tp_ctx["params"]

    paged = page_size is not None
    if paged:
        max_pages = -(-cache_len // page_size)
        num_pages = pool_pages if pool_pages is not None else 1 + batch * max_pages
        # pages through the first decode write; later writes grow on demand
        need_admit = prompt_len // page_size + 1

    if tp_ctx is None:
        def mk_prefill():
            return steps_lib.make_prefill_step(cfg)

        def mk_serve(act_fault=None):
            return steps_lib.make_serve_step(cfg, act_fault=act_fault)

        def mk_verify(act_fault=None):
            return steps_lib.make_verify_step_slots(cfg, spec,
                                                    act_fault=act_fault)

        def put_group(c):
            return c
    else:
        mesh, pspecs = tp_ctx["mesh"], tp_ctx["pspecs"]
        group_kwargs = dict(enc_frames=enc, per_slot=spec > 0)
        if paged:
            group_kwargs.update(page_size=page_size, num_pages=num_pages)
        gspecs = sharding_lib.tp_cache_specs(
            tf.init_cache(cfg, batch, cache_len, **group_kwargs))
        group_shardings = sharding_lib.to_shardings(gspecs, mesh)

        def mk_prefill():
            return steps_lib.make_tp_prefill_step(cfg, mesh, pspecs, gspecs)

        def mk_serve(act_fault=None):
            return steps_lib.make_tp_serve_step(cfg, mesh, pspecs, gspecs,
                                                act_fault=act_fault)

        def mk_verify(act_fault=None):
            return steps_lib.make_tp_verify_step_slots(
                cfg, mesh, spec, pspecs, gspecs, act_fault=act_fault)

        def put_group(c):
            return jax.device_put(c, group_shardings)

    prefill_fn = jax.jit(mk_prefill(), donate_argnums=(2,))
    if spec:
        # speculation needs per-row positions even on the batch scheduler:
        # rows accept ragged prefix lengths per round, so the group cache is
        # per-slot (pos (B,)) and the decode step is the masked verify step
        decode_fn = jax.jit(mk_verify(), donate_argnums=(2,))
        decode_faulted = {
            kind: jax.jit(mk_verify(act_fault=val), donate_argnums=(2,))
            for kind, val in (("nan", float("nan")), ("inf", float("inf")))
            if kind in plan.events
        }
        drafter = draft_lib.make_drafter("ngram")
    else:
        decode_fn = jax.jit(mk_serve(), donate_argnums=(2,))
        decode_faulted = {
            kind: jax.jit(mk_serve(act_fault=val), donate_argnums=(2,))
            for kind, val in (("nan", float("nan")), ("inf", float("inf")))
            if kind in plan.events
        }

    pending = collections.deque(enumerate(prompts))
    stats = _new_stats(nreq)
    preempted_ever = [False] * nreq
    if paged:
        stats.update({"kv_page_size": page_size, "pages_live": 0,
                      "pages_shared": 0, "paged_capacity_multiplier": 0.0,
                      "cow_copies": 0})
    if spec:
        stats.update({"speculate": spec, "spec_slot_steps": 0,
                      "spec_emitted": 0, "spec_drafts_proposed": 0,
                      "spec_drafts_accepted": 0,
                      "spec_accept_hist": [0] * (spec + 1)})

    def group_cache(nact):
        """Fresh cache for one group: the nact live rows get page runs
        covering prompt + first decode write; padding (and later, finished)
        rows route every access to the trash page."""
        if not paged:
            return (put_group(tf.init_cache(cfg, batch, cache_len,
                                            enc_frames=enc,
                                            per_slot=spec > 0)), None, None)
        cache = tf.init_cache(cfg, batch, cache_len, enc_frames=enc,
                              per_slot=spec > 0,
                              page_size=page_size, num_pages=num_pages)
        galloc = paging.PageAllocator(num_pages, page_size)
        row_pages = [galloc.alloc(need_admit) if i < nact else []
                     for i in range(batch)]
        table = np.zeros((batch, cache["page_table"].shape[1]), np.int64)
        for i in range(nact):
            table[i, :len(row_pages[i])] = row_pages[i]
        cache["page_table"] = jnp.asarray(table, jnp.int32)
        stats["pages_live"] = max(stats["pages_live"], galloc.pages_live())
        stats["paged_capacity_multiplier"] = max(
            stats["paged_capacity_multiplier"], galloc.capacity_multiplier())
        return put_group(cache), galloc, row_pages

    # compile outside the timed region, mirroring the continuous scheduler
    warm_in = {"tokens": jnp.zeros((batch, prompt_len), jnp.int32)}
    warm_in.update(_prefill_extras(cfg, rng, batch, enc))
    # warm with ZERO live rows (all-trash table): same trace, and a pool too
    # small for a full group — or for any group at all — must reject at
    # admission time, not blow up allocating a throwaway warmup cache
    warm_tok, warm_cache = prefill_fn(params, warm_in, group_cache(0)[0])
    if spec:
        warm_p, warm_a, warm_cache = decode_fn(
            params, jnp.zeros((batch, spec + 1), jnp.int32), warm_cache,
            jnp.zeros(batch, bool))
        jax.block_until_ready(warm_p)
        del warm_cache, warm_tok, warm_p, warm_a
    else:
        warm_tok, warm_cache = decode_fn(params, warm_tok, warm_cache)
        jax.block_until_ready(warm_tok)
        del warm_cache, warm_tok

    occ = []
    t0 = time.time()

    while pending:
        if paged:
            if need_admit > num_pages - 1:
                # no request can fit even a fully-free pool (uniform prompt
                # lengths: if one cannot, none can) — reject, never hang
                while pending:
                    rid, _ = pending.popleft()
                    stats["status"][rid] = "rejected"
                    stats["rejections"] += 1
                break
            # cap the group so every member's reservation fits up front;
            # growth pressure during the drain is resolved by preemption
            gsize = min(batch, (num_pages - 1) // need_admit, len(pending))
        else:
            gsize = min(batch, len(pending))
        group = [pending.popleft() for _ in range(gsize)]
        nact = len(group)
        prompt_block = np.stack(
            [p for _, p in group] + [np.zeros(prompt_len, np.int32)] * (batch - nact)
        )
        batch_in = {"tokens": jnp.asarray(prompt_block)}
        batch_in.update(_prefill_extras(cfg, rng, batch, enc))
        cache, galloc, row_pages = group_cache(nact)
        tok, cache = prefill_fn(params, batch_in, cache)
        stats["prefills"] += 1
        tok_np = np.asarray(tok)[:, 0]  # sync BEFORE stamping TTFT
        done = np.zeros(batch, bool)
        done[nact:] = True
        left = np.zeros(batch, np.int64)
        # per-row next write position + last committed token: lockstep for
        # plain decode (every live row advances 1/round), ragged under
        # speculation (each row advances by its own accepted count)
        row_pos = np.full(batch, prompt_len + n_prefix, np.int64)
        row_last = np.zeros(batch, np.int64)

        def release_row(i):
            nonlocal cache
            galloc.release(row_pages[i])
            row_pages[i] = []
            cache["page_table"] = cache["page_table"].at[i].set(paging.TRASH_PAGE)

        def preempt_row(i):
            """Full-recompute preemption: discard the victim's emitted
            tokens and re-serve its original prompt in a later group."""
            rid = group[i][0]
            stats["preemptions"] += 1
            preempted_ever[rid] = True
            stats["tokens"] -= len(stats["outputs"][rid])
            stats["outputs"][rid] = []
            stats["token_times"][rid] = []
            done[i] = True
            if paged:
                release_row(i)
            pending.appendleft(group[i])

        t_first = time.time() - t0
        for i, (rid, _) in enumerate(group):
            if stats["ttft"][rid] is None:
                # a re-served (preempted) request keeps its FIRST ttft
                stats["ttft"][rid] = t_first
                stats["admit_step"][rid] = stats["decode_steps"]
            left[i] = gen_lens[rid] - 1
            row_last[i] = int(tok_np[i])
            if spec:
                # full recompute on preemption means the context is always
                # just the original prompt + this group's emissions
                drafter.begin(rid, group[i][1])
                drafter.observe(rid, int(tok_np[i]))
            done[i] = _record_token(stats, rid, int(tok_np[i]), eos, left[i],
                                    preempted=preempted_ever[rid],
                                    t_now=t_first)
            if done[i] and paged:
                release_row(i)
        last_decode = None  # batch boundary: nobody is live across it
        while not done.all():
            step_idx = stats["decode_steps"]
            for i, (rid, _) in enumerate(group):
                if not done[i] and _deadline_expired(deadline_ms, rid, t0):
                    _timeout(stats, rid)
                    done[i] = True
                    if paged:
                        release_row(i)
            if plan.at_step("preempt", step_idx):
                live = [i for i in range(nact) if not done[i]]
                if live:
                    preempt_row(live[-1])
            if paged:
                # grow every live row's run to cover this round's write
                # window: position row_pos[i] (plain decode) through
                # row_pos[i]+spec (all k+1 verify candidates)
                for i in range(nact):
                    last_idx = (int(row_pos[i]) + spec) // page_size
                    while not done[i] and len(row_pages[i]) <= last_idx:
                        widx = len(row_pages[i])
                        if plan.take("exhaust"):
                            preempt_row([j for j in range(nact)
                                         if not done[j]][-1])
                            if done[i]:
                                break
                        while not galloc.free_pages() and not done[i]:
                            live = [j for j in range(nact) if not done[j]]
                            if live == [i]:
                                # i already owns every pool page and still
                                # needs more: a full recompute can never
                                # help — this sequence simply does not fit
                                # the pool.  Terminal rejection, never a
                                # requeue livelock.
                                rid = group[i][0]
                                stats["tokens"] -= len(stats["outputs"][rid])
                                stats["outputs"][rid] = []
                                stats["token_times"][rid] = []
                                stats["status"][rid] = "rejected"
                                stats["rejections"] += 1
                                done[i] = True
                                release_row(i)
                                break
                            preempt_row(live[-1])
                        if done[i]:
                            break
                        newp = galloc.alloc(1)[0]
                        row_pages[i].append(newp)
                        cache["page_table"] = cache["page_table"].at[i, widx].set(newp)
                if spec:
                    # no prefix sharing on this scheduler, so every page is
                    # exclusive by construction — the check keeps the
                    # invariant honest anyway (refcounts are per-allocator)
                    faults_lib.check_write_window(
                        galloc, [not d for d in done], row_pages, row_pos,
                        page_size, spec)
            if done.all():
                break
            if plan.at_step("qscale", step_idx) and "k_scale" in cache:
                live = [i for i in range(nact) if not done[i]]
                loc = ((row_pages[live[0]][0] if paged else live[0])
                       if live else 0)
                arr = cache["k_scale"]
                cache["k_scale"] = arr.at[(0, loc) + (0,) * (arr.ndim - 2)].set(jnp.inf)
            fn = decode_fn
            for kind in ("nan", "inf"):
                if plan.at_step(kind, step_idx):
                    fn = decode_faulted[kind]
            occ.append((~done).sum() / batch)
            if spec:
                win = np.zeros((batch, spec + 1), np.int32)
                for i, (rid, _) in enumerate(group):
                    if not done[i]:
                        win[i, 0] = row_last[i]
                        win[i, 1:] = drafter.propose(rid, spec)
                preds, acc, cache = fn(params, jnp.asarray(win), cache,
                                       jnp.asarray(~done))
                tok_blk = np.asarray(preds)
                acc_np = np.asarray(acc)
            else:
                tok, cache = fn(params, tok, cache)
                tok_np = np.asarray(tok)[:, 0]
            stats["decode_steps"] += 1
            now = time.time()
            if last_decode is not None:
                stats["max_stall_ms"] = max(stats["max_stall_ms"],
                                            (now - last_decode) * 1e3)
            last_decode = now
            t_now = now - t0
            for i, (rid, _) in enumerate(group):
                if done[i]:
                    continue
                if spec:
                    n_acc = int(acc_np[i])
                    stats["spec_slot_steps"] += 1
                    stats["spec_drafts_proposed"] += spec
                    stats["spec_drafts_accepted"] += n_acc
                    stats["spec_accept_hist"][n_acc] += 1
                    for tv in tok_blk[i, :n_acc + 1]:
                        row_pos[i] += 1
                        left[i] -= 1
                        stats["spec_emitted"] += 1
                        drafter.observe(rid, int(tv))
                        done[i] = _record_token(stats, rid, int(tv), eos,
                                                left[i],
                                                preempted=preempted_ever[rid],
                                                t_now=t_now)
                        if done[i]:
                            break
                    if not done[i]:
                        row_last[i] = int(tok_blk[i, n_acc])
                else:
                    row_pos[i] += 1
                    left[i] -= 1
                    done[i] = _record_token(stats, rid, int(tok_np[i]), eos,
                                            left[i],
                                            preempted=preempted_ever[rid],
                                            t_now=t_now)
                if done[i] and paged:
                    release_row(i)
            if paged:
                stats["pages_live"] = max(stats["pages_live"], galloc.pages_live())
            if check_invariants:
                faults_lib.check_serve_invariants(
                    alloc=galloc, table=cache.get("page_table"),
                    active=[not d for d in done],
                    slot_pages=row_pages if paged else None, cache=cache)
        if paged:
            # conservation at every group drain: all of the group's pages
            # must be back on the free list
            galloc.leak_check()
    stats["faults_fired"] = list(plan.fired)
    stats["faults_unfired"] = plan.pending()
    return _finalize(stats, occ, t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--scheduler", default="continuous", choices=("continuous", "batch"),
                    help="continuous: slot-level admission; batch: drain-then-refill baseline")
    ap.add_argument("--backend", default="xla", choices=("xla", "pallas", "ref"),
                    help="core.blas backend; pallas fuses decode into bgemv")
    ap.add_argument("--quantize", default="none", choices=("none", "int8"),
                    help="int8: block-scaled packed serving weights — the "
                         "bandwidth-bound decode path streams 1 byte/weight")
    ap.add_argument("--kv-cache", default="model", choices=("model", "int8"),
                    help="int8: block-scaled packed KV cache — attention "
                         "streams ~1 byte/element of K/V (combine with "
                         "--quantize int8 for the fully-quantized decode "
                         "byte path)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="continuous scheduler: split admission prefills "
                         "into chunks of at most this many tokens, "
                         "interleaved with decode steps (0 = unchunked) — "
                         "bounds the inter-token stall a long admission "
                         "inflicts on live slots")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="KV page size in tokens: the cache is a global pool "
                         "of pages + a per-slot page table.  --scheduler "
                         "continuous always pages (0 = 16-token pages) and "
                         "stores a repeated prompt prefix once; --scheduler "
                         "batch pages only when this is set (0 = dense).  "
                         "Freed slots return their pages.  Under --backend "
                         "pallas paged decode is its own kernel and is not "
                         "bitwise equal to a dense cache")
    ap.add_argument("--prefix-reuse", default="on", choices=("on", "off"),
                    help="continuous scheduler: hash admitted prompts "
                         "page by page and back a matched prefix with the "
                         "SAME physical pages (copy-on-write on divergence)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="override the paged pool size (0 = sized so "
                         "exhaustion cannot happen).  Small pools exercise "
                         "the backpressure/preemption path: admission blocks "
                         "at the watermark and page-growth failures preempt "
                         "the newest slot, whose request is recomputed "
                         "bit-identically")
    ap.add_argument("--speculate", type=int, default=0,
                    help="greedy speculative decoding: verify this many "
                         "self-drafted tokens (n-gram prompt-lookup, no "
                         "second model) per slot per step in one (B, k+1) "
                         "window — projections become skinny GEMMs sharing "
                         "one weight stream.  Emitted tokens are "
                         "bit-identical to --speculate 0 (0 = off)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard attention heads, "
                         "FFN features and KV heads over a 'model' mesh "
                         "axis (Megatron col/row layout, packed int8 "
                         "weight shards, one psum per layer boundary).  "
                         "Needs --backend xla and >= N devices — emulate "
                         "with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline, enforced at "
                         "decode-round boundaries (status 'timeout'; "
                         "emitted tokens are kept)")
    ap.add_argument("--check-invariants", action="store_true",
                    help="run the page-refcount/table/finiteness invariant "
                         "sweep every decode round (launch/faults.py)")
    ap.add_argument("--faults", default=os.environ.get(faults_lib.FAULTS_ENV, ""),
                    help="deterministic fault plan, e.g. 'exhaust@2,nan@5' "
                         f"(default: ${faults_lib.FAULTS_ENV}); kinds: "
                         f"{', '.join(faults_lib.KINDS)}")
    args = ap.parse_args()
    compile_cache.enable()
    serve(args.arch, args.variant, args.requests, args.batch, args.prompt_len,
          args.gen, backend=args.backend, scheduler=args.scheduler,
          quantize=args.quantize, kv_cache=args.kv_cache,
          prefill_chunk=args.prefill_chunk or None,
          kv_page_size=args.kv_page_size or None,
          prefix_reuse=args.prefix_reuse == "on",
          pool_pages=args.pool_pages or None,
          deadline_ms=args.deadline_ms,
          check_invariants=args.check_invariants,
          faults=args.faults or None,
          speculate=args.speculate or None, tp=args.tp)


if __name__ == "__main__":
    main()

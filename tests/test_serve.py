"""Continuous-batching serve scheduler: FIFO fairness, slot reuse, parity.

The acceptance contract for the scheduler:
  - the pending queue is served strictly FIFO (regression: it used to be
    `pending.pop()` — LIFO — so early requests starved);
  - a finished sequence frees its slot immediately and the next request is
    admitted BEFORE the batch drains (slot reuse);
  - greedy outputs are identical to the per-request sequential oracle (the
    per-slot ragged-position machinery changes scheduling, not semantics);
    the continuous scheduler always pages its KV cache (16-token pages when
    no size is given), and the oracle decodes a dense cache;
  - mean live-slot occupancy and decode-step count beat batch-at-a-time on a
    mixed-length distribution;
  - under the pallas backend the masked decode step still routes through the
    fused broadcast-A bgemv at partial occupancy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blas
from repro.launch import steps as steps_lib
from repro.launch.serve import serve
from repro.models import transformer as tf
from repro.models.registry import get_config

ARCH = "stablelm-1.6b"
NO_EOS = -1  # token ids are non-negative: disables early stopping


def _prompts(n, prompt_len, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, size=(prompt_len,), dtype=np.int32) for _ in range(n)]


def _sequential_oracle(prompts, gen_lens, seed=0, eos=NO_EOS, quantize="none",
                       kv_cache="model", backend="xla", arch=ARCH):
    """Per-request decode through the ORIGINAL scalar-pos machinery: batch 1,
    one request at a time, same cache capacity as the schedulers use."""
    import contextlib
    import dataclasses
    cfg = get_config(arch, "smoke")
    if kv_cache == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    ctx = blas.use_backend(backend) if backend != "xla" else contextlib.nullcontext()
    with ctx:
        return _run_oracle(cfg, prompts, gen_lens, seed, eos, quantize)


def _run_oracle(cfg, prompts, gen_lens, seed, eos, quantize):
    params = tf.init_params(jax.random.PRNGKey(seed), cfg)
    if quantize == "int8":
        from repro.models import layers
        params = layers.quantize_weights(params)
    prefill_fn = jax.jit(steps_lib.make_prefill_step(cfg))
    decode_fn = jax.jit(steps_lib.make_serve_step(cfg))
    cache_len = max(len(p) + g for p, g in zip(prompts, gen_lens))
    outs = []
    for prompt, budget in zip(prompts, gen_lens):
        cache = tf.init_cache(cfg, 1, cache_len)
        tok, cache = prefill_fn(params, {"tokens": jnp.asarray(prompt[None])}, cache)
        seq = [int(np.asarray(tok)[0, 0])]
        while len(seq) < budget and seq[-1] != eos:
            tok, cache = decode_fn(params, tok, cache)
            seq.append(int(np.asarray(tok)[0, 0]))
        outs.append(seq)
    return outs


# --------------------------------------------------------------------------
# Greedy-output parity vs the sequential oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheduler", ["continuous", "batch"])
def test_scheduler_matches_sequential_oracle(scheduler):
    cfg = get_config(ARCH, "smoke")
    gen_lens = [3, 7, 4, 6, 5]
    prompts = _prompts(5, 8, cfg.vocab)
    stats = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
                  verbose=False, scheduler=scheduler, prompts=prompts)
    assert stats["completed"] == 5
    want = _sequential_oracle(prompts, gen_lens)
    assert stats["outputs"] == want
    assert [len(o) for o in stats["outputs"]] == gen_lens


def test_continuous_handles_ragged_prompts():
    """Per-slot prefill admits mixed prompt lengths; slot capacity must cover
    the worst-case prompt+budget (regression: cache was sized from prompts[0],
    and dynamic_update_slice silently CLAMPS out-of-range KV writes, so longer
    requests corrupted the cache instead of erroring)."""
    cfg = get_config(ARCH, "smoke")
    rng = np.random.default_rng(11)
    plens = [8, 14, 5, 11]
    gen_lens = [6, 10, 4, 8]
    prompts = [rng.integers(3, cfg.vocab, size=(pl,), dtype=np.int32) for pl in plens]
    stats = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
                  verbose=False, scheduler="continuous", prompts=prompts)
    assert stats["outputs"] == _sequential_oracle(prompts, gen_lens)
    # the stacked batch prefill cannot take ragged prompts — loud, not wrong
    with pytest.raises(ValueError, match="uniform prompt lengths"):
        serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
              verbose=False, scheduler="batch", prompts=prompts)


@pytest.mark.parametrize("scheduler", ["continuous", "batch"])
def test_zero_and_one_token_budgets_terminate(scheduler):
    """Degenerate budgets must finish at the prefill token, not hang
    (regression: the batch decode loop tested `left == 0` exactly, so a
    0-budget request decremented past zero and never terminated)."""
    gen_lens = [0, 3, 1]
    stats = serve(ARCH, "smoke", batch=2, prompt_len=8, gen_lens=gen_lens,
                  eos=NO_EOS, verbose=False, scheduler=scheduler)
    assert stats["completed"] == 3
    assert [len(o) for o in stats["outputs"]] == [1, 3, 1]


def test_eos_frees_slot_early():
    """A naturally-emitted EOS finishes the request before its budget."""
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(4, 8, cfg.vocab, seed=3)
    gen_lens = [12] * 4
    # pick an eos id that actually appears in the unconstrained outputs
    free = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
                 verbose=False, scheduler="continuous", prompts=prompts)
    # (at its FIRST occurrence: request 0 stops there)
    first = free["outputs"][0]
    eos = first[2]
    stop = first.index(eos)
    stats = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=eos,
                  verbose=False, scheduler="continuous", prompts=prompts)
    assert stats["completed"] == 4
    # stopped at the EOS, not the budget
    assert len(stats["outputs"][0]) == stop + 1 < gen_lens[0]
    assert stats["outputs"][0][-1] == eos
    want = _sequential_oracle(prompts, gen_lens, eos=eos)
    assert stats["outputs"] == want


# --------------------------------------------------------------------------
# Quantized serving (block-scaled int8 weights, core.quant)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheduler", ["continuous", "batch"])
def test_quantized_decode_matches_quantized_oracle(scheduler):
    """Greedy decode with packed int8 weights is deterministic: the
    continuous/batch schedulers produce EXACTLY the tokens the per-request
    sequential oracle produces from the same quantized params — scheduling
    and batching change nothing about the quantized math (every slot's
    matvec is batch-row independent)."""
    cfg = get_config(ARCH, "smoke")
    gen_lens = [3, 7, 4, 6]
    prompts = _prompts(4, 8, cfg.vocab, seed=19)
    stats = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
                  verbose=False, scheduler=scheduler, prompts=prompts,
                  quantize="int8")
    assert stats["completed"] == 4
    want = _sequential_oracle(prompts, gen_lens, quantize="int8")
    assert stats["outputs"] == want


def test_quantized_greedy_close_to_full_precision():
    """Accuracy smoke: with random smoke-scale weights, packed int8 decode
    agrees with full-precision decode on most greedy tokens (quantization
    shifts logits within the per-block bound; occasional near-tie flips are
    expected and fine)."""
    cfg = get_config(ARCH, "smoke")
    gen_lens = [8] * 4
    prompts = _prompts(4, 8, cfg.vocab, seed=23)
    kw = dict(batch=2, gen_lens=gen_lens, eos=NO_EOS, verbose=False,
              scheduler="continuous", prompts=prompts)
    full = serve(ARCH, "smoke", **kw)
    packed = serve(ARCH, "smoke", quantize="int8", **kw)
    toks_full = [t for o in full["outputs"] for t in o]
    toks_packed = [t for o in packed["outputs"] for t in o]
    agree = sum(a == b for a, b in zip(toks_full, toks_packed))
    assert agree / len(toks_full) >= 0.5, (toks_full, toks_packed)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("scheduler", ["continuous", "batch"])
def test_combined_quantized_decode_matches_oracle(scheduler, backend):
    """The fully-quantized decode byte path: int8 weights AND the block-
    scaled int8 KV cache together.  Greedy tokens must be EXACTLY the
    per-request sequential oracle's on the SAME backend — under pallas that
    is end-to-end through the int8-KV flash kernel and packed bgemv, so
    scheduling, slot grafts, per-slot kv_lens and the packed KV scatter
    change bytes moved, never the math."""
    cfg = get_config(ARCH, "smoke")
    gen_lens = [3, 7, 4, 6]
    prompts = _prompts(4, 8, cfg.vocab, seed=29)
    stats = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
                  verbose=False, scheduler=scheduler, prompts=prompts,
                  quantize="int8", kv_cache="int8", backend=backend)
    assert stats["completed"] == 4
    want = _sequential_oracle(prompts, gen_lens, quantize="int8",
                              kv_cache="int8", backend=backend)
    assert stats["outputs"] == want


def test_combined_quantized_pallas_streams_packed_kv(monkeypatch):
    """Under the pallas backend with the int8 KV cache, every decode-step
    attention must route through the int8-KV flash kernel with PACKED
    operands (int8 values + per-(token, head) scales) — never a
    dequantized cache — while the projections stay packed bgemv."""
    from repro.kernels import ops

    flash_calls = []
    real_flash = ops.flash_attention

    def spy(q, k, v, **kw):
        flash_calls.append((k.dtype, kw.get("k_scales") is not None,
                            kw.get("kv_lens") is not None, kw.get("kv_groups")))
        return real_flash(q, k, v, **kw)

    monkeypatch.setattr(ops, "flash_attention", spy)
    stats = serve(ARCH, "smoke", requests=2, batch=2, prompt_len=4,
                  gen_lens=[2, 2], eos=NO_EOS, verbose=False,
                  backend="pallas", scheduler="continuous",
                  quantize="int8", kv_cache="int8")
    assert stats["completed"] == 2
    assert flash_calls, "int8-KV serve never hit the packed flash kernel"
    assert all(dt == jnp.int8 for dt, _, _, _ in flash_calls)  # packed tiles
    assert all(scaled for _, scaled, _, _ in flash_calls)
    assert all(lens for _, _, lens, _ in flash_calls)          # per-slot lens


def test_combined_quantized_gqa_arch_matches_oracle():
    """GQA end to end: internlm2-20b's smoke config has n_kv < n_heads, so
    under pallas the int8-KV flash kernel runs with kv_groups > 1 through
    its 4-D cache-layout index maps — greedy tokens must still match the
    per-request sequential oracle exactly."""
    cfg = get_config("internlm2-20b", "smoke")
    assert cfg.n_kv < cfg.n_heads  # the point of this test
    gen_lens = [3, 5, 4]
    prompts = _prompts(3, 8, cfg.vocab, seed=31)
    stats = serve("internlm2-20b", "smoke", batch=2, gen_lens=gen_lens,
                  eos=NO_EOS, verbose=False, scheduler="continuous",
                  prompts=prompts, quantize="int8", kv_cache="int8",
                  backend="pallas")
    assert stats["completed"] == 3
    want = _sequential_oracle(prompts, gen_lens, quantize="int8",
                              kv_cache="int8", backend="pallas",
                              arch="internlm2-20b")
    assert stats["outputs"] == want


def test_quantized_decode_routes_through_packed_bgemv(monkeypatch):
    """Under the pallas backend the quantized decode projections stay ONE
    broadcast bgemv launch per weight — now with a packed QuantizedTensor
    operand (in-kernel dequant), not a dequantized array.  A broadcast
    weight is one 2-D weight, or one layer of a layer stack (`layer`) that
    the kernel reads in place."""
    from repro.core import quant
    from repro.kernels import ops

    calls = []
    real_bgemv = ops.bgemv

    def spy(a, x, **kw):
        ndim = a.ndim - (kw.get("layer") is not None)
        calls.append((quant.is_quantized(a), ndim, x.shape[0]))
        return real_bgemv(a, x, **kw)

    monkeypatch.setattr(ops, "bgemv", spy)
    serve(ARCH, "smoke", requests=2, batch=2, prompt_len=4, gen_lens=[2, 2],
          eos=NO_EOS, verbose=False, backend="pallas", scheduler="continuous",
          quantize="int8")
    assert calls, "quantized pallas decode never hit the fused bgemv path"
    quantized_calls = [c for c in calls if c[0]]
    assert quantized_calls, "no packed operand reached bgemv"
    assert all(ndim == 2 for _, ndim, _ in quantized_calls)  # broadcast weights
    assert {b for _, _, b in quantized_calls} == {2}         # full slot grid


# --------------------------------------------------------------------------
# FIFO fairness (regression: the queue used to be served LIFO)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheduler", ["continuous", "batch"])
def test_fifo_arrival_order(scheduler):
    stats = serve(ARCH, "smoke", requests=6, batch=2, prompt_len=8, gen=4,
                  eos=NO_EOS, verbose=False, scheduler=scheduler)
    admit = stats["admit_step"]
    # earlier arrivals are never admitted after later ones...
    assert admit == sorted(admit), admit
    # ...and with equal budgets they also finish in arrival order
    finish = stats["finish_step"]
    assert finish == sorted(finish), finish
    assert all(t is not None for t in stats["ttft"])
    ttft = stats["ttft"]
    assert ttft == sorted(ttft), ttft


# --------------------------------------------------------------------------
# Slot-level admission: reuse before global drain, occupancy, step count
# --------------------------------------------------------------------------

def test_slot_reused_before_batch_drains():
    gen_lens = [2, 10, 2, 2, 2]
    stats = serve(ARCH, "smoke", batch=2, prompt_len=8, gen_lens=gen_lens,
                  eos=NO_EOS, verbose=False, scheduler="continuous")
    # request 1 is still decoding (finishes at step 9) when requests 2..4 are
    # admitted into the slot request 0 freed at step 1
    assert stats["finish_step"][1] > stats["admit_step"][2]
    assert stats["finish_step"][1] > stats["admit_step"][4]
    # slot-level admission: requests 2..4 each trigger their own admission
    # round (prefill launch) instead of waiting for a fresh batch
    assert stats["prefills"] == 4  # {0,1} together, then 2, 3, 4
    # the freed slot is back-filled every step while the queue is non-empty,
    # so only request 1's lone tail drags occupancy below 1.0
    bat = serve(ARCH, "smoke", batch=2, prompt_len=8, gen_lens=gen_lens,
                eos=NO_EOS, verbose=False, scheduler="batch")
    assert stats["occupancy"] > bat["occupancy"]
    assert stats["decode_steps"] < bat["decode_steps"]


def test_continuous_beats_batch_on_mixed_lengths():
    """The bandwidth argument, scheduler edition: on a mixed-length request
    set the continuous scheduler does strictly fewer decode steps for the
    same tokens, at strictly higher mean live-slot occupancy."""
    rng = np.random.default_rng(7)
    gen_lens = rng.integers(2, 17, size=10).tolist()
    kw = dict(batch=2, prompt_len=8, gen_lens=gen_lens, eos=NO_EOS, verbose=False)
    cont = serve(ARCH, "smoke", scheduler="continuous", **kw)
    bat = serve(ARCH, "smoke", scheduler="batch", **kw)
    assert cont["outputs"] == bat["outputs"]  # scheduling, not semantics
    assert cont["tokens"] == bat["tokens"]
    assert cont["decode_steps"] < bat["decode_steps"]
    assert cont["occupancy"] > bat["occupancy"]


# --------------------------------------------------------------------------
# Per-slot cache plumbing
# --------------------------------------------------------------------------

def test_continuous_scheduler_pages_by_default():
    """The continuous scheduler has one KV layout: without a page size it
    serves 16-token pages, through `serve` as through the scheduler."""
    from repro.launch import serve as serve_mod
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(2, 4, cfg.vocab)
    stats = serve_mod._serve_continuous(cfg, prompts, [2, 2], 2, 0, NO_EOS)
    assert stats["kv_page_size"] == serve_mod.PAGE_SIZE == 16
    assert stats["pages_live"] > 0
    via_serve = serve(ARCH, "smoke", batch=2, prompts=prompts, gen_lens=[2, 2],
                      eos=NO_EOS, verbose=False, scheduler="continuous")
    assert via_serve["kv_page_size"] == 16
    assert via_serve["outputs"] == stats["outputs"]


def test_per_slot_cache_rejects_stateful_families():
    cfg = get_config("rwkv6-1.6b", "smoke")
    with pytest.raises(ValueError, match="per-slot cache"):
        tf.init_cache(cfg, 2, 16, per_slot=True)
    with pytest.raises(ValueError, match="continuous scheduler"):
        serve("rwkv6-1.6b", "smoke", requests=2, batch=2, prompt_len=8, gen=2,
              verbose=False, scheduler="continuous")


def test_decode_step_slots_freezes_inactive_positions():
    cfg = get_config(ARCH, "smoke")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    decode_fn = jax.jit(steps_lib.make_decode_step_slots(cfg))
    cache = tf.init_cache(cfg, 3, 16, per_slot=True)
    cache = {**cache, "pos": jnp.asarray([4, 7, 2], jnp.int32)}
    tok = jnp.ones((3, 1), jnp.int32)
    active = jnp.asarray([True, False, True])
    _, cache = decode_fn(params, tok, cache, active)
    assert np.asarray(cache["pos"]).tolist() == [5, 7, 3]


# --------------------------------------------------------------------------
# The decode path stays on the fused bgemv at partial occupancy
# --------------------------------------------------------------------------

def test_partial_occupancy_decode_routes_through_bgemv(monkeypatch):
    from repro.kernels import ops

    calls = []
    real_bgemv = ops.bgemv

    def spy(a, x, **kw):
        # one layer of a layer stack read in place is a broadcast weight too
        calls.append((a.ndim - (kw.get("layer") is not None), x.shape[0]))
        return real_bgemv(a, x, **kw)

    monkeypatch.setattr(ops, "bgemv", spy)
    # 3 requests on a 2-slot grid: the tail of the run decodes at partial
    # occupancy, and every decode projection must still be one broadcast-A
    # bgemv launch over the full slot grid
    serve(ARCH, "smoke", requests=3, batch=2, prompt_len=4, gen_lens=[2, 4, 2],
          eos=NO_EOS, verbose=False, backend="pallas", scheduler="continuous")
    assert calls, "pallas decode never hit the fused bgemv path"
    assert all(ndim == 2 for ndim, _ in calls)      # broadcast (2-D) weights
    assert {b for _, b in calls} == {2}             # full slot grid every launch


# --------------------------------------------------------------------------
# Chunked admission prefill: token parity + no live-slot starvation
# --------------------------------------------------------------------------

def test_chunked_prefill_token_parity_and_no_starvation():
    """Splitting a long admission prefill into chunks interleaved with decode
    steps changes WHEN live slots decode, never what anyone generates — and
    bounds the head-of-line stall at one chunk of prefill work."""
    cfg = get_config(ARCH, "smoke")
    rng = np.random.default_rng(41)
    shorts = [rng.integers(3, cfg.vocab, size=(6,), dtype=np.int32) for _ in range(2)]
    longp = rng.integers(3, cfg.vocab, size=(48,), dtype=np.int32)
    prompts = shorts + [longp]
    # slot 0's request finishes fast and frees the slot; the 48-token prompt
    # is then admitted while slot 1 is still live (13 tokens left)
    gen_lens = [3, 16, 4]
    kw = dict(batch=2, gen_lens=gen_lens, eos=NO_EOS, verbose=False,
              scheduler="continuous", prompts=prompts)
    un = serve(ARCH, "smoke", **kw)
    ch = serve(ARCH, "smoke", prefill_chunk=8, **kw)
    want = _sequential_oracle(prompts, gen_lens)
    assert un["outputs"] == want
    assert ch["outputs"] == want
    # unchunked: the live slot waits out the whole 48-token prefill between
    # two of its tokens; chunked: at most one 8-token chunk
    assert un["max_stall_prefill_tokens"] == 48
    assert ch["max_stall_prefill_tokens"] == 8
    # the live slot actually decodes DURING the admission: decode steps
    # advance between chunks, so the long request is admitted later (in
    # decode-step time) than under the unchunked scheduler
    assert ch["admit_step"][2] > un["admit_step"][2]
    assert ch["max_stall_ms"] > 0 and un["max_stall_ms"] > 0


def test_chunked_prefill_parity_pallas_quantized():
    """Chunked admission composes with the fully-quantized pallas decode
    path (int8 weights + int8 KV through the flash kernel): greedy tokens
    stay identical to the unchunked scheduler and the sequential oracle."""
    cfg = get_config(ARCH, "smoke")
    rng = np.random.default_rng(43)
    prompts = [rng.integers(3, cfg.vocab, size=(n,), dtype=np.int32)
               for n in (5, 5, 24)]
    gen_lens = [2, 10, 3]
    kw = dict(batch=2, gen_lens=gen_lens, eos=NO_EOS, verbose=False,
              scheduler="continuous", prompts=prompts, backend="pallas",
              quantize="int8", kv_cache="int8")
    un = serve(ARCH, "smoke", **kw)
    ch = serve(ARCH, "smoke", prefill_chunk=8, **kw)
    want = _sequential_oracle(prompts, gen_lens, quantize="int8",
                              kv_cache="int8", backend="pallas")
    assert un["outputs"] == want
    assert ch["outputs"] == want
    assert ch["max_stall_prefill_tokens"] < un["max_stall_prefill_tokens"]


def test_prefill_chunk_requires_continuous_scheduler():
    with pytest.raises(ValueError, match="continuous"):
        serve(ARCH, "smoke", requests=2, batch=2, prompt_len=8, gen=2,
              verbose=False, scheduler="batch", prefill_chunk=4)
    with pytest.raises(ValueError, match=">= 1"):
        serve(ARCH, "smoke", requests=2, batch=2, prompt_len=8, gen=2,
              verbose=False, scheduler="continuous", prefill_chunk=0)


# --------------------------------------------------------------------------
# Tensor-parallel serving (--tp 2): token parity + packed-path routing spy
# --------------------------------------------------------------------------
#
# jax locks the device count at first init, so the TP cells run in
# subprocesses with a FORCED 2-device host platform.  The contract is
# greedy-token IDENTITY: sharding the packed weights, KV heads and page
# pools across the mesh changes where bytes live and what crosses the wire,
# never which token argmax wins.  (The int8 cells are bitwise by
# construction — integer psum is exact; the fp cells pin that psum
# reassociation never crosses an argmax boundary on this grid.)

import subprocess
import sys
import textwrap
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent.parent / "src")

_TP_CELLS = """
import itertools, os
import numpy as np
from repro.core import distributed as D
from repro.launch.serve import serve
from repro.models.registry import get_config

SCHED = {sched!r}
cfg = get_config("stablelm-1.6b", "smoke")
rng = np.random.default_rng(7)
prompts = [rng.integers(3, cfg.vocab, size=(5,), dtype=np.int32)
           for _ in range(3)]
gen_lens = [4, 6, 5]

# paged/speculate are parity-preserving at tp=1 (pinned elsewhere), so one
# reference per (quantize, kv_cache) serves the whole composed sub-grid —
# which also makes every tp=2 composed cell answer to the PLAIN tp=1 run
refs = {{}}
for quantize, kv, page, spec in itertools.product(
        ("none", "int8"), ("model", "int8"), (None, 4), (None, 4)):
    if (quantize, kv) not in refs:
        refs[(quantize, kv)] = serve(
            "stablelm-1.6b", "smoke", batch=2, prompts=prompts,
            gen_lens=gen_lens, eos=-1, verbose=False, scheduler=SCHED,
            quantize=quantize, kv_cache=kv)["outputs"]
    D.clear_tp_routes()
    got = serve("stablelm-1.6b", "smoke", batch=2, prompts=prompts,
                gen_lens=gen_lens, eos=-1, verbose=False, scheduler=SCHED,
                quantize=quantize, kv_cache=kv, kv_page_size=page,
                speculate=spec, tp=2)
    cell = (quantize, kv, page, spec)
    assert got["tp"] == 2, got
    assert got["completed"] == 3, (cell, got)
    assert got["outputs"] == refs[(quantize, kv)], (cell, got["outputs"],
                                                    refs[(quantize, kv)])
    routes = D.tp_routes()
    assert routes, cell
    if quantize == "int8":
        # the routing spy: decode-shaped projections through the boundary
        # MUST take the collective packed-int8 path (int32 partials + one
        # integer psum), and must NEVER fall back to dequant-then-matmul
        assert any(k == "packed_int8" and ds for k, ds in routes), (cell, routes)
        assert not any(k == "dequant" and ds for k, ds in routes), (cell, routes)
    else:
        assert any(k == "dense" for k, ds in routes), (cell, routes)
    print("cell OK", SCHED, cell, flush=True)
print("ALL CELLS OK", SCHED)
"""


def _run_tp_cells(scheduler, timeout=1200):
    import os
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = _SRC
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_TP_CELLS.format(sched=scheduler))],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, (
        f"STDOUT:\n{res.stdout[-4000:]}\nSTDERR:\n{res.stderr[-4000:]}")
    assert f"ALL CELLS OK {scheduler}" in res.stdout


def test_tp2_token_parity_continuous_composed_cells():
    """--tp 2 greedy tokens == 1-device on every composed cell:
    {fp, int8 weights} x {dense, int8 KV} x {16-token (default), 4-token
    pages} x {spec off, 4}, continuous scheduler, with the packed-int8
    routing spy."""
    _run_tp_cells("continuous")


def test_tp2_token_parity_batch_composed_cells():
    """Same composed grid under the batch-at-a-time scheduler."""
    _run_tp_cells("batch")

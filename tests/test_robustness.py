"""Preemptible, fault-tolerant serving (ISSUE 8).

The acceptance contract:
  - the page allocator treats lifecycle violations (double free, freeing a
    shared page, retaining a dead page) as hard PageErrors and proves
    conservation via `leak_check()`; `can_admit` is the watermark the
    scheduler's backpressure stands on;
  - exhaustion edge cases neither hang nor corrupt: an admission that can
    never fit the pool is terminally "rejected", CoW at zero free pages
    raises cleanly with refcounts intact, grafting an empty coordinate set
    is a no-op;
  - a preempted request — whether the pressure is real (small pool) or
    injected (fault plan) — is recomputed to BIT-IDENTICAL greedy tokens on
    both schedulers (the continuous one always paged, the batch one paged
    here), int8 KV included, and finishes with status "preempted_resumed";
  - request deadlines cut at decode-round boundaries with status "timeout"
    (deadline 0 deterministically yields exactly the prefill token) without
    disturbing other requests' outputs;
  - the fault-injection harness is deterministic (plans parse, fire exactly
    once, and log), and the invariant sweep (--check-invariants) catches
    injected NaN activations and corrupt quant scales as InvariantViolation;
  - quantization honours its degenerate-input contract: zero/subnormal
    blocks stay finite, NaN/Inf propagate to the scale (never silently
    laundered), validate=True refuses corrupt concrete inputs.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.launch import faults as faults_lib
from repro.launch import paging
from repro.launch.serve import serve
from repro.models import transformer as tf
from repro.models.registry import get_config

from test_serve import _sequential_oracle, ARCH, NO_EOS


def _prompts(n, plen, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, size=(plen,), dtype=np.int32)
            for _ in range(n)]


# --------------------------------------------------------------------------
# Allocator lifecycle hard errors + conservation
# --------------------------------------------------------------------------

def test_allocator_double_free_is_hard_error():
    a = paging.PageAllocator(num_pages=4, page_size=2)
    (p,) = a.alloc(1)
    assert a.release([p]) == [p]
    with pytest.raises(paging.PageError, match="double free"):
        a.release([p])
    with pytest.raises(paging.PageError, match="double free"):
        a.free([p])
    a.leak_check()  # failed frees left no corruption behind


def test_allocator_retain_dead_page_is_hard_error():
    a = paging.PageAllocator(num_pages=4, page_size=2)
    (p,) = a.alloc(1)
    a.release([p])
    with pytest.raises(paging.PageError, match="dead page"):
        a.retain([p])
    # the trash page is never live, so retaining it is the same error
    with pytest.raises(paging.PageError, match="dead page"):
        a.retain([paging.TRASH_PAGE])
    a.leak_check()


def test_allocator_free_shared_page_is_hard_error():
    a = paging.PageAllocator(num_pages=4, page_size=2)
    (p,) = a.alloc(1)
    a.retain([p])
    with pytest.raises(paging.PageError, match="shared page"):
        a.free([p])
    assert a.refcount(p) == 2  # refused atomically, refcount untouched
    a.release([p])
    a.free([p])  # exclusively owned now: hard-free is legal
    assert a.refcount(p) == 0
    a.leak_check()


def test_leak_check_catches_corruption():
    a = paging.PageAllocator(num_pages=6, page_size=4)
    pages = a.alloc(3)
    a.leak_check()  # healthy: 2 free + 3 live + trash == 6
    # a page vanishing from the books (neither free nor live) is a leak
    del a._ref[pages[0]]
    with pytest.raises(paging.PageError, match="leak"):
        a.leak_check()
    a._ref[pages[0]] = 1
    a.leak_check()
    # a freed page still published in the prefix registry is dangling
    a.register_prefix(list(range(12)), pages)
    del a._ref[pages[2]]
    a._free.append(pages[2])
    with pytest.raises(paging.PageError, match="still registered"):
        a.leak_check()


def test_can_admit_watermark():
    a = paging.PageAllocator(num_pages=6, page_size=4)  # 5 allocatable
    assert a.can_admit(20)            # 5 pages, exactly the pool
    assert not a.can_admit(21)        # 6 pages can never fit
    a.alloc(4)                        # 1 free left
    assert a.can_admit(4)
    assert not a.can_admit(5)
    # ... unless the scheduler can preempt pages back
    assert a.can_admit(5, reclaimable=1)
    assert a.can_admit(20, reclaimable=4)
    assert not a.can_admit(21, reclaimable=100)  # reclaim can't exceed pool
    assert a.can_admit(0)


# --------------------------------------------------------------------------
# Exhaustion edge cases
# --------------------------------------------------------------------------

def test_cow_at_zero_free_pages_raises_cleanly():
    a = paging.PageAllocator(num_pages=2, page_size=4)  # 1 allocatable
    (p,) = a.alloc(1)
    a.retain([p])
    with pytest.raises(paging.PoolExhausted):
        a.cow(p)  # cow allocs BEFORE decrementing: failure changes nothing
    assert a.refcount(p) == 2 and a.cow_copies == 0
    a.leak_check()
    a.release([p])
    a.release([p])
    a.leak_check()


def test_graft_pages_empty_coords_is_noop():
    cfg = get_config(ARCH, "smoke")
    cache = tf.init_cache(cfg, 2, 16, per_slot=True, page_size=4, num_pages=8)
    mini = tf.init_cache(cfg, 2, 8)
    mini = {**mini, "k": mini["k"] + 1.0}  # a spurious copy would show up
    empty = jnp.zeros((0,), jnp.int32)
    out = tf.graft_pages(cache, mini, empty, empty, empty, empty)
    assert float(jnp.abs(out["k"]).sum()) == 0.0
    assert out["k"].shape == cache["k"].shape


@pytest.mark.parametrize("scheduler", ["continuous", "batch"])
def test_admission_larger_than_pool_rejects_without_hanging(scheduler):
    """A 12-token prompt needs 4 pages (3 prompt + first decode write); a
    5-page pool with trash has 4 allocatable... so use pool_pages=4 (3
    allocatable): the request can NEVER fit and must be terminally rejected,
    not retried forever."""
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(2, 12, cfg.vocab, seed=3)
    stats = serve(ARCH, "smoke", batch=2, gen_lens=[3, 3], eos=NO_EOS,
                  verbose=False, scheduler=scheduler, prompts=prompts,
                  kv_page_size=4, pool_pages=4)
    assert stats["status"] == ["rejected", "rejected"]
    assert stats["rejections"] == 2 and stats["completed"] == 0
    assert stats["outputs"] == [[], []]


def test_rejection_spares_admissible_requests():
    """Mixed queue: the oversized request is rejected, the rest are served
    to oracle parity."""
    cfg = get_config(ARCH, "smoke")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(3, cfg.vocab, size=(pl,), dtype=np.int32)
               for pl in (4, 12, 4)]
    gen_lens = [4, 3, 5]
    stats = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
                  verbose=False, scheduler="continuous", prompts=prompts,
                  kv_page_size=4, pool_pages=4)
    assert stats["status"][1] == "rejected" and stats["outputs"][1] == []
    want = _sequential_oracle([prompts[0], prompts[2]], [4, 5])
    assert stats["outputs"][0] == want[0]
    assert stats["outputs"][2] == want[1]
    assert stats["rejections"] == 1


# --------------------------------------------------------------------------
# Preemption with exact recompute: bit-identical to the unfaulted run
# --------------------------------------------------------------------------

@pytest.mark.parametrize("prefix_reuse", [True, False])
def test_preempt_recompute_parity_continuous_paged(prefix_reuse):
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(4, 10, cfg.vocab, seed=5)
    gen_lens = [6, 9, 5, 7]
    common = dict(batch=2, gen_lens=gen_lens, prompts=prompts, eos=NO_EOS,
                  verbose=False, scheduler="continuous", kv_page_size=4,
                  prefix_reuse=prefix_reuse)
    base = serve(ARCH, "smoke", **common)
    assert base["preemptions"] == 0 and base["status"] == ["ok"] * 4
    fx = serve(ARCH, "smoke", faults="exhaust@1", check_invariants=True,
               **common)
    assert fx["outputs"] == base["outputs"]
    assert fx["preemptions"] >= 1
    assert "preempted_resumed" in fx["status"]
    assert all(s in ("ok", "preempted_resumed") for s in fx["status"])
    assert ("exhaust", 1) in fx["faults_fired"]
    assert fx["faults_unfired"] == {}


def test_preempt_parity_int8_kv_under_real_pool_pressure():
    """No injection: a small pool makes growth genuinely exhaust, and the
    preempt -> requeue -> resume path must still reproduce the default-pool
    byte-identical stream — on the fully-quantized KV path."""
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(4, 10, cfg.vocab, seed=9)
    gen_lens = [7, 8, 6, 9]
    common = dict(batch=2, gen_lens=gen_lens, prompts=prompts, eos=NO_EOS,
                  verbose=False, scheduler="continuous", kv_page_size=4,
                  kv_cache="int8")
    base = serve(ARCH, "smoke", **common)
    fx = serve(ARCH, "smoke", pool_pages=7, **common)
    assert fx["outputs"] == base["outputs"]
    assert fx["preemptions"] >= 1
    assert "preempted_resumed" in fx["status"]


def test_preempt_recompute_parity_batch_paged():
    """The batch scheduler recovers by FULL recompute (it keeps no partial
    stream); greedy decoding makes the final tokens identical anyway."""
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(4, 8, cfg.vocab, seed=11)
    gen_lens = [5, 8, 6, 4]
    common = dict(batch=2, gen_lens=gen_lens, prompts=prompts, eos=NO_EOS,
                  verbose=False, scheduler="batch", kv_page_size=4)
    base = serve(ARCH, "smoke", **common)
    fx = serve(ARCH, "smoke", faults="exhaust@0", check_invariants=True,
               **common)
    assert fx["outputs"] == base["outputs"]
    assert fx["preemptions"] >= 1
    assert "preempted_resumed" in fx["status"]
    assert ("exhaust", 0) in fx["faults_fired"]


def test_preempt_fault_dense_continuous():
    """preempt@K force-preempts with no pool pressure at all (the default
    pool of 16-token pages never runs short here): the continuous scheduler
    must requeue and resume bit-identically too."""
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(3, 8, cfg.vocab, seed=13)
    gen_lens = [6, 7, 5]
    common = dict(batch=2, gen_lens=gen_lens, prompts=prompts, eos=NO_EOS,
                  verbose=False, scheduler="continuous")
    base = serve(ARCH, "smoke", **common)
    fx = serve(ARCH, "smoke", faults="preempt@2", **common)
    assert fx["outputs"] == base["outputs"]
    assert fx["preemptions"] == 1
    assert fx["status"].count("preempted_resumed") == 1
    assert fx["faults_fired"] == [("preempt", 2)]


# --------------------------------------------------------------------------
# Deadlines
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheduler", ["continuous", "batch"])
def test_deadline_zero_yields_exactly_the_prefill_token(scheduler):
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(3, 8, cfg.vocab, seed=17)
    stats = serve(ARCH, "smoke", batch=2, gen_lens=[4, 4, 4], eos=NO_EOS,
                  verbose=False, scheduler=scheduler, prompts=prompts,
                  deadline_ms=0.0)
    assert [len(o) for o in stats["outputs"]] == [1, 1, 1]
    assert stats["status"] == ["timeout"] * 3
    assert stats["timeouts"] == 3
    # the kept token is the true prefill token
    want = _sequential_oracle(prompts, [1, 1, 1])
    assert stats["outputs"] == want


def test_per_request_deadline_leaves_others_untouched():
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(3, 8, cfg.vocab, seed=19)
    gen_lens = [5, 6, 4]
    stats = serve(ARCH, "smoke", batch=2, gen_lens=gen_lens, eos=NO_EOS,
                  verbose=False, scheduler="continuous", prompts=prompts,
                  deadline_ms=[0.0, None, None])
    want = _sequential_oracle(prompts, gen_lens)
    assert stats["status"][0] == "timeout" and len(stats["outputs"][0]) == 1
    assert stats["outputs"][0] == want[0][:1]
    assert stats["outputs"][1] == want[1]
    assert stats["outputs"][2] == want[2]
    assert stats["timeouts"] == 1 and stats["status"][1:] == ["ok", "ok"]


# --------------------------------------------------------------------------
# Fault plans: parsing, determinism, validation
# --------------------------------------------------------------------------

def test_fault_plan_parse_fire_and_log():
    plan = faults_lib.FaultPlan.parse("exhaust@2, exhaust@0, nan@5")
    assert bool(plan)
    assert plan.take("exhaust") is True      # occurrence 0
    assert plan.take("exhaust") is False     # occurrence 1
    assert plan.take("exhaust") is True      # occurrence 2
    assert plan.at_step("nan", 4) is False
    assert plan.at_step("nan", 5) is True
    assert plan.at_step("nan", 5) is False   # fires exactly once
    assert plan.pending() == {}
    assert plan.fired == [("exhaust", 0), ("exhaust", 2), ("nan", 5)]


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="kind@index"):
        faults_lib.FaultPlan.parse("exhaust")
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults_lib.FaultPlan.parse("frobnicate@3")
    assert not faults_lib.FaultPlan.parse(None)
    assert not faults_lib.FaultPlan.parse("")
    plan = faults_lib.FaultPlan.parse("graft@1")
    assert faults_lib.as_plan(plan) is plan
    assert not faults_lib.as_plan(None)


def test_serve_rejects_bad_fault_and_pool_args():
    with pytest.raises(ValueError, match="unknown fault kind"):
        serve(ARCH, "smoke", requests=1, verbose=False, faults="bogus@1")
    with pytest.raises(ValueError, match="kv_cache='int8'"):
        serve(ARCH, "smoke", requests=1, verbose=False, faults="qscale@1")
    # the batch scheduler pages only with a page size; the continuous one
    # always pages, so a pool size alone is enough there
    with pytest.raises(ValueError, match="kv_page_size"):
        serve(ARCH, "smoke", requests=1, verbose=False, pool_pages=8,
              scheduler="batch")
    with pytest.raises(ValueError, match="pool_pages"):
        serve(ARCH, "smoke", requests=1, verbose=False, kv_page_size=4,
              pool_pages=1)


def test_unfired_faults_are_reported():
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(1, 6, cfg.vocab, seed=23)
    stats = serve(ARCH, "smoke", batch=1, gen_lens=[2], eos=NO_EOS,
                  verbose=False, scheduler="continuous", prompts=prompts,
                  faults="preempt@999")
    assert stats["faults_fired"] == []
    assert stats["faults_unfired"] == {"preempt": [999]}
    assert stats["status"] == ["ok"]


# --------------------------------------------------------------------------
# Invariant harness: injected corruption is DETECTED
# --------------------------------------------------------------------------

def test_nan_fault_trips_finiteness_invariant():
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(2, 8, cfg.vocab, seed=29)
    with pytest.raises(faults_lib.InvariantViolation, match="non-finite"):
        serve(ARCH, "smoke", batch=2, gen_lens=[6, 6], eos=NO_EOS,
              verbose=False, scheduler="continuous", prompts=prompts,
              faults="nan@1", check_invariants=True)


def test_qscale_fault_trips_scale_invariant():
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(2, 8, cfg.vocab, seed=31)
    with pytest.raises(faults_lib.InvariantViolation, match="quant scale"):
        serve(ARCH, "smoke", batch=2, gen_lens=[6, 6], eos=NO_EOS,
              verbose=False, scheduler="continuous", prompts=prompts,
              kv_page_size=4, kv_cache="int8",
              faults="qscale@1", check_invariants=True)


def test_check_cache_finite_units():
    faults_lib.check_cache_finite({"k": jnp.zeros((2, 2)),
                                   "v": jnp.zeros((2, 2))})
    with pytest.raises(faults_lib.InvariantViolation, match="KV value"):
        faults_lib.check_cache_finite({"k": jnp.asarray([[jnp.inf]])})
    # int8 value pools are skipped; their scale pools are the invariant
    faults_lib.check_cache_finite({"k": jnp.zeros((2, 2), jnp.int8)})
    with pytest.raises(faults_lib.InvariantViolation, match="quant scale"):
        faults_lib.check_cache_finite({
            "k": jnp.zeros((2, 2), jnp.int8),
            "k_scale": jnp.asarray([[jnp.nan]]),
        })


def test_check_page_table_units():
    a = paging.PageAllocator(num_pages=8, page_size=4)
    pages = a.alloc(2)
    table = np.full((2, 4), paging.TRASH_PAGE, np.int64)
    table[0, :2] = pages
    active = [True, False]
    slot_pages = [list(pages), []]
    faults_lib.check_serve_invariants(alloc=a, table=table, active=active,
                                      slot_pages=slot_pages)
    # device row disagreeing with the host page list
    bad = table.copy()
    bad[0, 1] = 7
    with pytest.raises(faults_lib.InvariantViolation, match="!= host"):
        faults_lib.check_page_table(bad, a, active, slot_pages)
    # inactive row routing into the pool (use-after-free in waiting)
    bad = table.copy()
    bad[1, 0] = pages[0]
    with pytest.raises(faults_lib.InvariantViolation, match="inactive"):
        faults_lib.check_page_table(bad, a, active, slot_pages)
    # table entry pointing at a freed page
    a.release(pages)
    with pytest.raises(faults_lib.InvariantViolation, match="freed page"):
        faults_lib.check_page_table(table, a, active, slot_pages)


# --------------------------------------------------------------------------
# Quantization degenerate-input contract
# --------------------------------------------------------------------------

def test_quantize_subnormal_block_stays_finite():
    x = jnp.full((8, 8), 1e-39, jnp.float32)  # subnormal amax
    qt = quant.quantize(x)
    assert quant.scales_finite(qt)
    assert bool(jnp.isfinite(qt.dequantize()).all())
    assert int(jnp.abs(qt.values).max()) <= 127


def test_quantize_nan_inf_propagate_to_scale():
    x = jnp.zeros((8, 8), jnp.float32).at[3, 3].set(jnp.nan)
    qt = quant.quantize(x)
    assert not quant.scales_finite(qt)  # NaN in -> NaN scale, never laundered
    x = jnp.zeros((8, 8), jnp.float32).at[0, 0].set(jnp.inf)
    qt = quant.quantize(x)
    assert not quant.scales_finite(qt)
    # the serving invariant is exactly this check on the KV scale pool
    with pytest.raises(faults_lib.InvariantViolation):
        faults_lib.check_cache_finite({"k": qt.values, "k_scale": qt.scales})


def test_quantize_validate_refuses_corrupt_input():
    bad = jnp.zeros((8, 8), jnp.float32).at[0, 0].set(jnp.nan)
    with pytest.raises(ValueError, match="NaN/Inf"):
        quant.quantize(bad, validate=True)
    ok = jnp.ones((8, 8), jnp.float32)
    qt = quant.quantize(ok, validate=True)
    assert quant.scales_finite(qt)


def test_quantize_kv_degenerate_blocks():
    z = jnp.zeros((2, 3, 4, 8), jnp.float32)
    qt = quant.quantize_kv(z)
    assert quant.scales_finite(qt)
    assert float(jnp.abs(quant.dequantize_kv(qt.values, qt.scales)).max()) == 0.0
    bad = z.at[0, 0, 0, 0].set(jnp.inf)
    qt = quant.quantize_kv(bad)
    assert not quant.scales_finite(qt)


# --------------------------------------------------------------------------
# Graft-failure rollback + end-of-serve conservation
# --------------------------------------------------------------------------

@pytest.mark.parametrize("page_size", [4, None])
def test_graft_failure_rolls_back_and_retries(page_size):
    """graft@0 fails the FIRST admission (continuous scheduler, 4-token
    pages and the default 16); the scheduler must back the placement out
    page-exactly and serve every request on retry — end-of-serve leak_check
    (always on) proves conservation."""
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts(3, 8, cfg.vocab, seed=37)
    gen_lens = [4, 5, 3]
    common = dict(batch=2, gen_lens=gen_lens, prompts=prompts, eos=NO_EOS,
                  verbose=False, scheduler="continuous", kv_page_size=page_size)
    base = serve(ARCH, "smoke", **common)
    fx = serve(ARCH, "smoke", faults="graft@0", check_invariants=True,
               **common)
    assert fx["outputs"] == base["outputs"]
    assert ("graft", 0) in fx["faults_fired"]
    assert fx["completed"] == 3

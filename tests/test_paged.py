"""Paged KV cache (ISSUE 7): page-table kernel math, allocator policy,
shared-prefix reuse and copy-on-write.

The acceptance contract:
  - a one-query paged launch (the decode step) takes the decode path, P
    pages a grid step with every head at once, and matches the exact paged
    (dequant) oracle (kernels.ref.attention_paged*) numerically for every
    length x page-size x GQA x dense/int8 cell; a launch of more queries
    (verify windows) keeps the flash kernel, BIT-identical to the dense
    flash kernel run at block_k = page_size (same blocks, same order);
  - the host allocator (launch.paging) enforces refcounts, exact-tail
    partial-page matching, first-writer-wins registration and CoW
    bookkeeping, and can never hand out the trash page;
  - paged serving is greedy-token identical to the dense cache on BOTH
    schedulers, with prefix sharing ON and OFF, and a shared prefix raises
    the effective-capacity multiplier above 1 with cow_copies counted;
  - under the pallas backend a paged decode step (dense and int8) stays ONE
    flash launch: every slot-grid attention call carries the page table —
    there is no gather-then-attend fallback on the hot path;
  - the xla/ref fallback's gather scales with live pages, never the pool
    (quant.paged_fallback_byte_ratio pins the bound).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import blas, quant
from repro.kernels import attention, ops, ref
from repro.launch import paging
from repro.launch.serve import serve
from repro.models import transformer as tf
from repro.models.registry import get_config

from test_serve import _sequential_oracle, ARCH, NO_EOS


# --------------------------------------------------------------------------
# PageAllocator: host-side policy, no device in sight
# --------------------------------------------------------------------------

def test_allocator_roundtrip_and_exhaustion():
    a = paging.PageAllocator(num_pages=6, page_size=4)
    assert a.free_pages() == 5  # page 0 is the trash page, never handed out
    pages = a.alloc(3)
    assert paging.TRASH_PAGE not in pages
    assert len(set(pages)) == 3
    assert a.pages_live() == 3 and a.free_pages() == 2
    with pytest.raises(paging.PoolExhausted):
        a.alloc(3)
    freed = a.release(pages)
    assert sorted(freed) == sorted(pages)
    assert a.pages_live() == 0 and a.free_pages() == 5
    # freed pages are allocatable again
    assert len(a.alloc(5)) == 5


def test_allocator_refcounts_and_shared():
    a = paging.PageAllocator(num_pages=4, page_size=2)
    (p,) = a.alloc(1)
    a.retain([p])
    assert a.refcount(p) == 2 and a.shared(p)
    assert a.release([p]) == []          # one ref left: not freed
    assert a.release([p]) == [p]         # now it is
    assert a.refcount(p) == 0


def test_allocator_match_register_exact_tail():
    a = paging.PageAllocator(num_pages=16, page_size=4)
    prompt = list(range(100, 110))       # 2 full pages + 2-token tail
    pages = a.alloc(3)
    a.register_prefix(prompt, pages)

    # identical prompt: full match including the partial tail
    m, covered = a.match_prefix(prompt)
    assert m == pages and covered == 10
    # longer prompt with the same start: full pages only — a partial page
    # key is exact-tail (count-sensitive), never a sub-prefix match
    m, covered = a.match_prefix(prompt + [1, 2])
    assert m == pages[:2] and covered == 8
    # shorter prompt: the 2 full pages match, the foreign tail does not
    m, covered = a.match_prefix(prompt[:9])
    assert m == pages[:2] and covered == 8
    # different first page: nothing matches (hash chain breaks at page 0)
    m, covered = a.match_prefix([0] + prompt[1:])
    assert m == [] and covered == 0


def test_allocator_invalidate_and_release_unregister():
    a = paging.PageAllocator(num_pages=16, page_size=4)
    prompt = list(range(8))
    pages = a.alloc(2)
    a.register_prefix(prompt, pages)
    a.invalidate(pages[1])               # diverging write unpublishes page 1
    m, covered = a.match_prefix(prompt)
    assert m == pages[:1] and covered == 4
    a.release(pages)                     # refs hit zero: registry fully drops
    m, covered = a.match_prefix(prompt)
    assert (m, covered) == ([], 0)


def test_allocator_first_writer_wins_and_cow():
    a = paging.PageAllocator(num_pages=16, page_size=4)
    prompt = list(range(6))
    first = a.alloc(2)
    a.register_prefix(prompt, first)
    second = a.alloc(2)
    a.register_prefix(prompt, second)    # same chain: must NOT re-register
    m, _ = a.match_prefix(prompt)
    assert m == first
    # CoW bookkeeping: shared page loses our ref, fresh page gains one
    a.retain([first[1]])
    newp = a.cow(first[1])
    assert newp not in first and a.refcount(newp) == 1
    assert a.refcount(first[1]) == 1 and a.cow_copies == 1
    with pytest.raises(AssertionError):
        a.cow(first[1])                  # no longer shared


def test_allocator_capacity_multiplier_counts_logical_pages():
    a = paging.PageAllocator(num_pages=16, page_size=4)
    pages = a.alloc(2)
    assert a.capacity_multiplier() == 1.0
    a.retain(pages)                      # a second slot shares both pages
    a.retain(pages)                      # and a third
    assert a.pages_logical() == 6 and a.pages_live() == 2
    assert a.capacity_multiplier() == pytest.approx(3.0)
    assert a.pages_shared() == 2


# --------------------------------------------------------------------------
# SlotPages: the continuous scheduler's page policy, host-only
# --------------------------------------------------------------------------

def _place(sp, members, active=None):
    """Place one group and check what every placement must hold: each
    member's table row mirrors its run, and the graft is padded to one
    shape per prompt length."""
    active = [False] * len(sp.slots) if active is None else active
    pl = sp.place(members, active)
    total = len(members[0][1]) + sp.n_prefix
    assert all(len(c) == len(sp.slots) * total for c in pl.graft)
    for i, (s, _, _) in enumerate(members):
        run = sp.slots[s] if pl.placed[i] else []
        assert pl.rows[i, :len(run)].tolist() == run
        assert not pl.rows[i, len(run):].any()
    return pl


def test_slot_pages_rejects_a_prompt_larger_than_the_pool():
    sp = paging.SlotPages(4, slots=2, capacity=64, num_pages=4)  # 3 usable
    # pages through the first decode write: 11 tokens -> 3, 12 -> 4
    assert sp.need(list(range(11))) == 3
    assert sp.need(list(range(12))) is None
    # the default pool holds every slot's full row, plus slack and trash
    assert paging.SlotPages(4, slots=2, capacity=64).num_pages == 1 + 2 * 17


def test_slot_pages_fifo_head_blocks_at_the_watermark():
    sp = paging.SlotPages(4, slots=3, capacity=32, num_pages=7, share=False)
    _place(sp, [(0, list(range(7)), True)])        # 2 pages, 4 stay free
    active = [True, False, False]
    assert sp.reclaimable(active) == 2
    assert sp.reclaimable([False] * 3) == 0
    # the head takes 3 pages; the next one's 3 more only fit by preempting
    reserved = sp.need(list(range(10, 21)))
    assert reserved == 3 and sp.fits(reserved, active)
    need = sp.need(list(range(30, 41)))
    assert sp.fits(need + reserved, active)        # 6 <= 4 free + 2 live
    assert not sp.fits(need + reserved, [False] * 3)
    assert not sp.fits(need + reserved + 1, active)  # blocks: no skip-ahead


def test_slot_pages_identical_prompts_share_the_tail_then_cow():
    """Two identical 10-token prompts (not a page multiple) in one group:
    the second shares all three pages, partial tail included; both decode,
    so the first member's write page is shared and copied, and the second
    owns the tail and unpublishes it."""
    sp = paging.SlotPages(4, slots=2, capacity=32, num_pages=16)
    toks = list(range(3, 13))
    pl = _place(sp, [(0, toks, True), (1, toks, True)])
    assert pl.placed == [True, True] and pl.victims == []
    a, b, c = sp.slots[1]
    assert sp.slots[0][:2] == [a, b]
    (src, dst), = pl.cow
    assert (src, sp.slots[0][2]) == (c, dst)
    # the first member grafts every token, into the pages it had before
    # the copy, so the copy has to follow the graft; the second grafts none
    n = len(toks)
    assert pl.graft[0][:n] == [0] * n and pl.graft[1][:n] == list(range(n))
    assert pl.graft[2][:n] == [a] * 4 + [b] * 4 + [c] * 2
    assert pl.graft[2][n:] == [paging.TRASH_PAGE] * n
    sp.sample()
    assert sp.peaks["cow_copies"] == 1 and sp.peaks["pages_shared"] == 2
    assert sp.peaks["paged_capacity_multiplier"] == pytest.approx(6 / 4)
    # the unpublished tail no longer matches: a third copy shares 2 pages
    assert sp.need(toks) == 1


def test_slot_pages_growth_under_exhaustion_preempts_the_newest():
    sp = paging.SlotPages(4, slots=3, capacity=32, num_pages=16, share=False)
    for s in range(3):                             # one group each: 0 oldest
        _place(sp, [(s, list(range(10 * s, 10 * s + 3)), True)])
    active = [True, True, True]
    first = list(sp.slots[0])
    writes, victims = sp.grow(0, 4, active, exhausted=lambda: True)
    assert victims == [2] and sp.slots[2] == []
    assert writes == [(1, sp.slots[0][1])] and sp.slots[0][:1] == first
    active[2] = False
    # a slot that is itself the newest is evicted before it grows
    writes, victims = sp.grow(1, 4, active, exhausted=lambda: True)
    assert (writes, victims) == ([], [1])
    # a growth inside the run it has takes nothing and asks no fault
    assert sp.grow(0, 7, [True, False, False], exhausted=None) == ([], [])
    # a full pool preempts without any injection
    sp = paging.SlotPages(4, slots=2, capacity=32, num_pages=3, share=False)
    _place(sp, [(0, [1, 2], True)])
    _place(sp, [(1, [3, 4], True)])
    writes, victims = sp.grow(0, 4, [True, True])
    assert victims == [1] and writes == [(1, sp.slots[0][1])]
    # ... and evicts the growing slot itself when it is the only one left
    assert sp.grow(0, 8, [True, False]) == ([], [0]) and sp.slots[0] == []


def test_slot_pages_victim_search_skips_all_shared_slots():
    sp = paging.SlotPages(4, slots=3, capacity=32, num_pages=16)
    toks = list(range(3, 13))
    # slots 0 and 1 hold the same three pages and nothing else (neither
    # decodes, so nobody copies the tail); slot 2 is the newest
    _place(sp, [(0, toks, False)])
    assert sp.need(toks) == 1     # all three pages match; the tail is copied
    _place(sp, [(1, toks, False)])
    _place(sp, [(2, list(range(40, 45)), True)])
    assert sp.slots[0] == sp.slots[1]
    active = [True, True, True]
    assert sp.victim(active) == 2
    assert sp.victim(active, spared=[2]) is None   # releasing frees nothing
    sp.release(1)
    assert sp.victim([True, False, True], spared=[2]) == 0


def test_slot_pages_leak_check_after_every_release():
    sp = paging.SlotPages(4, slots=4, capacity=32, num_pages=12)
    toks = list(range(3, 13))
    _place(sp, [(0, toks, True), (1, toks, True)])
    _place(sp, [(2, toks + [99, 98], True), (3, [7] * 12, False)])
    active = [True] * 4
    for s in range(3):
        sp.grow(s, len(sp.slots[s]) * 4 + 5, active)
    for s in (2, 0, 3, 1, 1):                      # a second release: no-op
        sp.release(s)
        sp.leak_check()
    assert sp.alloc.pages_live() == 0
    assert sp.alloc.free_pages() == sp.num_pages - 1


# --------------------------------------------------------------------------
# Paged flash kernel: page-table index math vs dense flash vs exact oracle
# --------------------------------------------------------------------------

def _paged_kernel_case(seq_len, page_size, groups, quantized, seed=0,
                       q_len=1):
    """Build one ragged paged cell of `q_len` queries a slot and return
    (paged, dense, oracle) outputs.  The dense kernel runs at
    block_k=page_size on the contiguous gather of the same pages, so with
    q_len > 1, where both launches run the flash kernel, it visits identical
    key blocks in identical order: the paged launch must then be
    BIT-identical, not just close.  A one-query paged launch takes the
    decode path, which blocks the keys its own way."""
    rng = np.random.default_rng(seed)
    b, kvh, hd = 2, 2, 8
    h = kvh * groups
    seq_len = max(seq_len, q_len)
    lens = np.array([seq_len, max(q_len, seq_len // 2)], np.int32)
    n_pages = -(-seq_len // page_size)
    tk = n_pages * page_size

    q = jnp.asarray(rng.standard_normal((b, q_len, h, hd)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((b, tk, kvh, hd)), jnp.float32)
    vd = jnp.asarray(rng.standard_normal((b, tk, kvh, hd)), jnp.float32)
    # shuffled table: logical page j of slot s lives at a random pool page
    perm = rng.permutation(np.arange(1, 1 + b * n_pages)).reshape(b, n_pages)
    table = jnp.asarray(perm, jnp.int32)
    num_pages = 1 + b * n_pages

    def to_pool(dense):
        pool = np.zeros((num_pages, page_size) + dense.shape[2:], dense.dtype)
        for s in range(b):
            for j in range(n_pages):
                pool[perm[s, j]] = dense[s, j * page_size:(j + 1) * page_size]
        return jnp.asarray(pool)

    kv_lens = jnp.asarray(lens)                  # the kernel's, per slot
    row_lens = jnp.asarray(np.repeat(lens, h))   # the oracle's, per head
    kw = dict(kv_groups=groups, causal=True, block_k=page_size)
    if quantized:
        kq, vq = quant.quantize_kv(kd), quant.quantize_kv(vd)
        paged = ops.flash_attention(
            q, to_pool(np.asarray(kq.values)), to_pool(np.asarray(vq.values)),
            k_scales=to_pool(np.asarray(kq.scales)),
            v_scales=to_pool(np.asarray(vq.scales)),
            kv_lens=kv_lens, page_table=table, **kw)
        dense = ops.flash_attention(q, kq.values, vq.values,
                                    k_scales=kq.scales, v_scales=vq.scales,
                                    kv_lens=kv_lens, **kw)
        oracle = ref.attention_paged_kv_dequant(
            q, to_pool(np.asarray(kq.values)), to_pool(np.asarray(kq.scales)),
            to_pool(np.asarray(vq.values)), to_pool(np.asarray(vq.scales)),
            table, row_lens, causal=True)
    else:
        paged = ops.flash_attention(q, to_pool(np.asarray(kd)),
                                    to_pool(np.asarray(vd)),
                                    kv_lens=kv_lens, page_table=table, **kw)
        dense = ops.flash_attention(q, kd, vd, kv_lens=kv_lens, **kw)
        oracle = ref.attention_paged(q, to_pool(np.asarray(kd)),
                                     to_pool(np.asarray(vd)),
                                     table, row_lens, causal=True)
    return paged, dense, oracle


@settings(deadline=None, max_examples=8)
@given(seq_len=st.integers(min_value=1, max_value=21),
       page_size=st.integers(min_value=1, max_value=8),
       groups=st.integers(min_value=1, max_value=3),
       quantized=st.integers(min_value=0, max_value=1))
def test_paged_flash_matches_dense_flash_and_oracle(seq_len, page_size,
                                                    groups, quantized):
    """One query a slot: the paged launch takes the decode path, whose key
    blocking differs from the dense kernel's, so both are held to the exact
    oracle (bit identity with the dense kernel is the shared-kernel case
    below)."""
    with blas.use_backend("pallas"):
        paged, dense, oracle = _paged_kernel_case(seq_len, page_size, groups,
                                                  bool(quantized))
    np.testing.assert_allclose(np.asarray(paged), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


@settings(deadline=None, max_examples=6)
@given(seq_len=st.integers(min_value=1, max_value=21),
       page_size=st.integers(min_value=1, max_value=8),
       groups=st.integers(min_value=1, max_value=3),
       quantized=st.integers(min_value=0, max_value=1),
       q_len=st.integers(min_value=2, max_value=5))
def test_paged_flash_window_is_dense_flash_bitwise(seq_len, page_size, groups,
                                                   quantized, q_len):
    """More than one query a slot (a verify window): paged and dense launches
    share the flash kernel, so the page table may change where keys live and
    nothing else: bit for bit the dense kernel at block_k = page_size."""
    with blas.use_backend("pallas"):
        paged, dense, oracle = _paged_kernel_case(seq_len, page_size, groups,
                                                  bool(quantized), q_len=q_len)
    assert jnp.array_equal(paged, dense), (
        "paged flash must be bit-identical to dense flash at "
        f"block_k=page_size (seq={seq_len} ps={page_size} g={groups} "
        f"int8={quantized} q_len={q_len})")
    np.testing.assert_allclose(np.asarray(paged), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


# the decode path's own cases: a slot length of one key, of exactly P pages,
# of P pages and one key, and off a page boundary
_DECODE_LENGTHS = {
    "one": lambda p, ps: 1,
    "p_pages": lambda p, ps: p * ps,
    "p_pages_plus_1": lambda p, ps: p * ps + 1,
    "ragged": lambda p, ps: p * ps + ps // 2 + (ps == 1) * 3 + 1,
}


def _decode_case(kind, page_size, groups, quantized, seed):
    """Three slots through a shuffled page table: slot 0 of the `kind`
    length, slot 1 more than one group of P pages longer, slot 2 of a few
    keys; every table column past a slot's last page is dead (trash page 0).
    Returns (decode path, oracle)."""
    rng = np.random.default_rng(seed)
    b, kvh, hd = 3, 2, 8
    h = kvh * groups
    dtype = jnp.int8 if quantized else jnp.bfloat16
    p = attention.decode_group_pages(page_size, kvh, hd, dtype, quantized,
                                     max_pages=1 << 20)
    first = _DECODE_LENGTHS[kind](p, page_size)
    lens = np.array([first,
                     first + 2 * p * page_size + int(rng.integers(0, 5)),
                     int(rng.integers(1, 4))], np.int32)
    n_pages = -(-lens // page_size)
    cols = int(n_pages.max()) + 2
    assert attention.decode_group_pages(page_size, kvh, hd, dtype, quantized,
                                        cols) == p
    num_pages = 1 + int(n_pages.sum()) + 3           # spare pages, unread
    perm = rng.permutation(np.arange(1, num_pages))
    table = np.zeros((b, cols), np.int32)
    at = 0
    for s, n in enumerate(n_pages):
        table[s, :n] = perm[at:at + n]
        at += n
    shape = (num_pages, page_size, kvh, hd)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
    table, kv_lens = jnp.asarray(table), jnp.asarray(lens)
    row_lens = jnp.asarray(np.repeat(lens, h))
    kw = dict(kv_lens=kv_lens, page_table=table, kv_groups=groups)
    if quantized:
        kq, vq = quant.quantize_kv(k), quant.quantize_kv(v)
        got = ops.flash_attention(q, kq.values, vq.values, k_scales=kq.scales,
                                  v_scales=vq.scales, **kw)
        want = ref.attention_paged_kv_dequant(
            q, kq.values, kq.scales, vq.values, vq.scales, table, row_lens)
    else:
        # bf16 pages, as served; f32 queries, so the output is not rounded
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        got = ops.flash_attention(q, k, v, **kw)
        want = ref.attention_paged(q, k, v, table, row_lens)
    return got, want


@pytest.mark.parametrize("kind", list(_DECODE_LENGTHS))
@settings(deadline=None, max_examples=4)
@given(page_size=st.integers(min_value=1, max_value=16),
       groups=st.integers(min_value=1, max_value=3),
       quantized=st.integers(min_value=0, max_value=1),
       seed=st.integers(min_value=0, max_value=2 ** 31))
def test_paged_decode_path_matches_oracle(kind, page_size, groups, quantized,
                                          seed):
    """The decode path against the exact paged oracles: bf16-class and int8
    pools, GQA, page sizes 1-16, slot lengths on and off the group and page
    boundaries, slots whose live pages differ by more than one group, and
    dead table columns."""
    with blas.use_backend("pallas"):
        got, want = _decode_case(kind, page_size, groups, bool(quantized),
                                 seed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ref_paged_oracle_matches_dense_oracle():
    """gather_pages + attention_lens == attention over the contiguous kv."""
    rng = np.random.default_rng(3)
    b, h, kvh, hd, ps, npg = 2, 4, 2, 8, 4, 3
    tk = ps * npg
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((b, tk, kvh, hd)), jnp.float32)
    vd = jnp.asarray(rng.standard_normal((b, tk, kvh, hd)), jnp.float32)
    lens = jnp.asarray([tk, tk - 3])
    perm = rng.permutation(np.arange(1, 1 + b * npg)).reshape(b, npg)
    pool_k = np.zeros((1 + b * npg, ps, kvh, hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    for s in range(b):
        for j in range(npg):
            pool_k[perm[s, j]] = kd[s, j * ps:(j + 1) * ps]
            pool_v[perm[s, j]] = vd[s, j * ps:(j + 1) * ps]
    got = ref.attention_paged(q, jnp.asarray(pool_k), jnp.asarray(pool_v),
                              jnp.asarray(perm, jnp.int32),
                              jnp.repeat(lens, h), causal=True)
    flat = ref.attention_lens(
        jnp.moveaxis(q, 2, 1).reshape(b * h, 1, hd),
        jnp.repeat(jnp.moveaxis(kd, 2, 1), h // kvh, 1).reshape(b * h, tk, hd),
        jnp.repeat(jnp.moveaxis(vd, 2, 1), h // kvh, 1).reshape(b * h, tk, hd),
        jnp.repeat(lens, h), causal=True)
    want = jnp.moveaxis(flat.reshape(b, h, 1, hd), 1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# Full model: paged cache == dense cache, eager and under both backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend,kv_cache", [("xla", "model"),
                                              ("pallas", "model"),
                                              ("pallas", "int8")])
def test_paged_forward_greedy_parity(backend, kv_cache):
    """prefill + decode through a shuffled page table produce the same
    greedy tokens as the dense cache — the page table changes WHERE bytes
    live, never what attention computes."""
    cfg = get_config(ARCH, "smoke")
    if kv_cache == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    b, plen, gen, ps = 2, 9, 4, 4
    cache_len = plen + gen
    n_pages = -(-cache_len // ps)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(3, cfg.vocab, size=(b, plen)), jnp.int32)

    def run(cache):
        tok, cache = tf.prefill(params, {"tokens": prompts}, cache, cfg)
        toks = [jnp.argmax(tok, -1)]
        for _ in range(gen - 1):
            lg, cache = tf.decode_step(params, toks[-1][:, None], cache, cfg)
            toks.append(jnp.argmax(lg, -1))
        return np.stack([np.asarray(t) for t in toks], 1)

    with blas.use_backend(backend):
        dense = run(tf.init_cache(cfg, b, cache_len))
        pcache = tf.init_cache(cfg, b, cache_len, page_size=ps,
                               num_pages=4 * b * n_pages)  # oversized pool
        perm = rng.permutation(np.arange(1, 1 + b * n_pages)).reshape(b, n_pages)
        pcache["page_table"] = jnp.asarray(perm, jnp.int32)
        paged = run(pcache)
    assert (dense == paged).all(), (dense, paged)


# --------------------------------------------------------------------------
# Serving: parity, sharing, CoW, one-launch routing
# --------------------------------------------------------------------------

def _shared_prefix_prompts(vocab, n=6, sys_len=10, tail=3, seed=11):
    """Every even request starts with the same system prompt (sys_len NOT a
    page multiple at ps=4, so the tail page is shared AND write-hazardous)."""
    rng = np.random.default_rng(seed)
    sysp = rng.integers(3, vocab, size=(sys_len,), dtype=np.int32)
    out = []
    for i in range(n):
        if i % 2 == 0:
            out.append(np.concatenate(
                [sysp, rng.integers(3, vocab, size=(tail,), dtype=np.int32)]))
        else:
            out.append(rng.integers(3, vocab, size=(sys_len + tail,),
                                    dtype=np.int32))
    return out


@pytest.mark.parametrize("backend,kv_cache,reuse", [
    ("xla", "model", True),
    ("pallas", "model", True),
    ("pallas", "int8", True),
    ("pallas", "model", False),
])
def test_paged_serve_matches_oracle_continuous(backend, kv_cache, reuse):
    cfg = get_config(ARCH, "smoke")
    prompts = _shared_prefix_prompts(cfg.vocab)
    gen_lens = [4, 2, 5, 3, 4, 2]
    stats = serve(ARCH, "smoke", batch=3, eos=NO_EOS, verbose=False,
                  backend=backend, scheduler="continuous", prompts=prompts,
                  gen_lens=gen_lens, kv_cache=kv_cache, kv_page_size=4,
                  prefix_reuse=reuse)
    assert stats["completed"] == len(prompts)
    want = _sequential_oracle(prompts, gen_lens, kv_cache=kv_cache,
                              backend=backend)
    assert stats["outputs"] == want
    if reuse:
        assert stats["pages_shared"] > 0
        assert stats["paged_capacity_multiplier"] > 1.0
    else:
        assert stats["pages_shared"] == 0
        assert stats["paged_capacity_multiplier"] == 1.0


def test_paged_serve_matches_oracle_batch_scheduler():
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts_uniform(cfg.vocab)
    gen_lens = [3, 5, 2, 4, 3]
    stats = serve(ARCH, "smoke", batch=2, eos=NO_EOS, verbose=False,
                  backend="pallas", scheduler="batch", prompts=prompts,
                  gen_lens=gen_lens, kv_cache="int8", kv_page_size=4)
    assert stats["completed"] == len(prompts)
    want = _sequential_oracle(prompts, gen_lens, kv_cache="int8",
                              backend="pallas")
    assert stats["outputs"] == want
    assert stats["pages_live"] > 0
    assert stats["paged_capacity_multiplier"] == 1.0  # no admission history


def _prompts_uniform(vocab, n=5, plen=9, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, size=(plen,), dtype=np.int32)
            for _ in range(n)]


def test_paged_chunked_admission_parity():
    """Chunked prefill composes with paged admission: the graft happens once
    after the last chunk, through the same page-table coordinates."""
    cfg = get_config(ARCH, "smoke")
    prompts = _shared_prefix_prompts(cfg.vocab)
    gen_lens = [4, 2, 5, 3, 4, 2]
    base = serve(ARCH, "smoke", batch=3, eos=NO_EOS, verbose=False,
                 backend="pallas", scheduler="continuous", prompts=prompts,
                 gen_lens=gen_lens, kv_page_size=4)
    chunked = serve(ARCH, "smoke", batch=3, eos=NO_EOS, verbose=False,
                    backend="pallas", scheduler="continuous", prompts=prompts,
                    gen_lens=gen_lens, kv_page_size=4, prefill_chunk=4)
    assert chunked["outputs"] == base["outputs"]


def test_copy_on_write_divergence_matches_oracle():
    """Two slots admitted with IDENTICAL prompts share every page including
    the partial tail; their first decode writes diverge the tail, so one of
    them must CoW.  A third, different request is admitted into the first
    finisher's freed pages while the second is still decoding — if CoW or
    the free list mishandled the shared pages, the survivor would read
    recycled garbage and drift off the sequential oracle."""
    cfg = get_config(ARCH, "smoke")
    rng = np.random.default_rng(5)
    shared = rng.integers(3, cfg.vocab, size=(10,), dtype=np.int32)  # 10 % 4 != 0
    prompts = [shared.copy(), shared.copy(),
               rng.integers(3, cfg.vocab, size=(6,), dtype=np.int32),
               rng.integers(3, cfg.vocab, size=(6,), dtype=np.int32)]
    gen_lens = [2, 9, 6, 3]   # request 0 frees early, request 1 keeps reading
    stats = serve(ARCH, "smoke", batch=2, eos=NO_EOS, verbose=False,
                  backend="pallas", scheduler="continuous", prompts=prompts,
                  gen_lens=gen_lens, kv_page_size=4)
    want = _sequential_oracle(prompts, gen_lens, backend="pallas")
    assert stats["outputs"] == want
    assert stats["cow_copies"] >= 1, "shared partial tail never copied"
    assert stats["pages_shared"] >= 1
    assert stats["paged_capacity_multiplier"] > 1.0


def test_identical_prompts_in_one_admission_match_the_oracle():
    """Two identical prompts admitted in ONE group share their partial
    tail, and the member that copies it (the first) must read the tokens
    its own graft wrote: the copy follows the graft.  Copied the other way
    round, the first member's long decode drifts off the oracle."""
    cfg = get_config(ARCH, "smoke")
    shared = np.random.default_rng(0).integers(3, cfg.vocab, size=(10,),
                                               dtype=np.int32)
    prompts = [shared.copy(), shared.copy()]
    gen_lens = [12, 2]
    stats = serve(ARCH, "smoke", batch=2, eos=NO_EOS, verbose=False,
                  prompts=prompts, gen_lens=gen_lens, kv_page_size=4)
    assert stats["cow_copies"] == 1
    assert stats["outputs"] == _sequential_oracle(prompts, gen_lens)


def test_paged_decode_is_one_flash_launch(monkeypatch):
    """Routing spy: under the pallas backend EVERY slot-grid attention call
    of a paged serve — ragged lens, int8 pages and all — is one
    ops.flash_attention launch carrying the page table.  No call sees a
    pre-gathered dense KV the size of the pool."""
    flash_calls = []
    real_flash = ops.flash_attention

    def spy(q, k, v, **kw):
        flash_calls.append((q.shape[1] == 1 and kw.get("kv_lens") is not None,
                            kw.get("page_table") is not None,
                            k.dtype, kw.get("k_scales") is not None))
        return real_flash(q, k, v, **kw)

    monkeypatch.setattr(ops, "flash_attention", spy)
    from repro.models import layers
    monkeypatch.setattr(layers, "attention_core", _boom, raising=True)
    stats = serve(ARCH, "smoke", requests=3, batch=2, prompt_len=6,
                  gen_lens=[3, 2, 3], eos=NO_EOS, verbose=False,
                  backend="pallas", scheduler="continuous",
                  kv_cache="int8", kv_page_size=4)
    assert stats["completed"] == 3
    # one-token slot-grid calls: the decode hot path (the admission MINI
    # prefill is a dense scalar-pos cache and legitimately has no table)
    decode_calls = [c for c in flash_calls if c[0]]
    assert decode_calls, "paged serve never decoded through flash"
    assert all(paged for _, paged, _, _ in decode_calls), (
        "a slot-grid attention call bypassed the page table")
    assert all(dt == jnp.int8 for _, _, dt, _ in decode_calls)
    assert all(scaled for _, _, _, scaled in decode_calls)


def _boom(*a, **k):  # the dense fallback must be unreachable under pallas
    raise AssertionError("paged pallas serve fell back to attention_core")


def _launch(kind):
    """Operands of one launch of each kind the serving stack makes:
    (q, k, v, keyword arguments)."""
    rng = np.random.default_rng(0)
    b, h, hd, ps = 2, 4, 8, 4

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    table = jnp.asarray([[3, 1, 0], [2, 4, 5]], jnp.int32)
    lens = jnp.asarray([6, 11], jnp.int32)
    pool = (6, ps, h, hd)
    return {
        "paged_decode": (arr(b, 1, h, hd), arr(*pool), arr(*pool),
                         dict(kv_lens=lens, page_table=table)),
        "paged_verify": (arr(b, 3, h, hd), arr(*pool), arr(*pool),
                         dict(kv_lens=lens, page_table=table)),
        "dense_decode": (arr(b, 1, h, hd), arr(b, 12, h, hd),
                         arr(b, 12, h, hd), dict(kv_lens=lens)),
        "prefill": (arr(b, 8, h, hd), arr(b, 8, h, hd), arr(b, 8, h, hd),
                    {}),
        "chunked_admission": (arr(b, 4, h, hd), arr(b, 12, h, hd),
                              arr(b, 12, h, hd),
                              dict(kv_lens=jnp.asarray([8, 8], jnp.int32))),
        "cross": (arr(b, 3, h, hd), arr(b, 7, h, hd), arr(b, 7, h, hd),
                  dict(causal=False)),
        "prefix_lm": (arr(b, 8, h, hd), arr(b, 8, h, hd), arr(b, 8, h, hd),
                      dict(prefix_len=3)),
    }[kind]


@pytest.mark.parametrize("kind", ["paged_decode", "paged_verify",
                                  "dense_decode", "prefill",
                                  "chunked_admission", "cross", "prefix_lm"])
def test_only_one_query_paged_launches_take_the_decode_path(kind,
                                                            monkeypatch):
    """The decode path is chosen by the operands alone: a page table and one
    query a slot.  Every other launch runs the flash kernel as before."""
    routes = []
    real_decode, real_flash = attention._paged_decode, attention._flash_kernel

    def decode(*a, **kw):
        routes.append("decode")
        return real_decode(*a, **kw)

    def flash(*a, **kw):
        routes.append("flash")
        return real_flash(*a, **kw)

    monkeypatch.setattr(attention, "_paged_decode", decode)
    monkeypatch.setattr(attention, "_flash_kernel", flash)
    q, k, v, kw = _launch(kind)
    out = attention.attention(q, k, v, interpret=True, **kw)
    assert out.shape == q.shape
    assert routes and set(routes) == {
        "decode" if kind == "paged_decode" else "flash"}, routes


def test_paged_serve_routes_decode_and_windows(monkeypatch):
    """A paged serve with speculation and chunked admission: each one-query
    launch on the page table takes the decode path, and verify windows,
    admission chunks and prefills keep the flash kernel."""
    calls = []
    real_attention, real_decode = attention.attention, attention._paged_decode

    def spy_attention(q, k, v, **kw):
        calls.append({"q_len": q.shape[1],
                      "paged": kw.get("page_table") is not None,
                      "decode": False})
        return real_attention(q, k, v, **kw)

    def spy_decode(*a, **kw):
        calls[-1]["decode"] = True
        return real_decode(*a, **kw)

    monkeypatch.setattr(attention, "attention", spy_attention)
    monkeypatch.setattr(attention, "_paged_decode", spy_decode)
    jax.clear_caches()   # trace every launch afresh, so the spies see it
    cfg = get_config(ARCH, "smoke")
    prompts = _prompts_uniform(cfg.vocab, n=3)
    for speculate in (None, 2):
        stats = serve(ARCH, "smoke", batch=2, eos=NO_EOS, verbose=False,
                      backend="pallas", scheduler="continuous",
                      prompts=prompts, gen_lens=[4, 6, 3], kv_page_size=4,
                      prefill_chunk=4, speculate=speculate)
        assert stats["completed"] == 3
    for c in calls:
        assert c["decode"] == (c["paged"] and c["q_len"] == 1), c
    kinds = {(c["paged"], c["q_len"] > 1) for c in calls}
    assert (True, False) in kinds, "no paged decode step seen"
    assert (True, True) in kinds, "no verify window seen"
    assert (False, True) in kinds, "no admission prefill seen"


# --------------------------------------------------------------------------
# Fallback byte accounting: live pages, never the pool
# --------------------------------------------------------------------------

def test_paged_fallback_byte_ratio_scales_with_live_tokens():
    hd = 64
    # gathering the live pages costs at most one page of rounding overhead
    for live in (1, 5, 31, 128):
        for ps in (4, 16):
            gathered = -(-live // ps) * ps
            ratio = quant.paged_fallback_byte_ratio(live, gathered, hd)
            bound = quant.paged_fallback_byte_ratio(live, live + ps - 1, hd)
            assert ratio <= bound
    # the ratio is a pure function of gathered tokens: pool capacity never
    # enters — gathering a 10x larger pool WOULD blow the bound
    assert quant.paged_fallback_byte_ratio(8, 8, hd) == pytest.approx(1.0)
    assert quant.paged_fallback_byte_ratio(8, 80, hd) == pytest.approx(10.0)
    # packed int8 pages gather ~half the bytes of bf16 ones
    packed = quant.paged_fallback_byte_ratio(8, 8, hd, packed=True)
    assert packed == pytest.approx((hd + 4) / (2.0 * hd))


def test_paged_xla_fallback_reads_live_pages_only():
    """Eager decode (concrete pos) through a deliberately HUGE pool: the
    fallback gather is sliced by the live page count, so the oversized pool
    must change neither the result nor trip the byte-ratio guard."""
    cfg = get_config(ARCH, "smoke")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    b, plen, ps = 2, 7, 4
    cache_len = plen + 3
    n_pages = -(-cache_len // ps)
    rng = np.random.default_rng(2)
    prompts = jnp.asarray(rng.integers(3, cfg.vocab, size=(b, plen)), jnp.int32)

    def run(num_pages):
        cache = tf.init_cache(cfg, b, cache_len, page_size=ps,
                              num_pages=num_pages)
        table = np.arange(1, 1 + b * n_pages).reshape(b, n_pages)
        cache["page_table"] = jnp.asarray(table, jnp.int32)
        tok, cache = tf.prefill(params, {"tokens": prompts}, cache, cfg)
        seq = [jnp.argmax(tok, -1)]
        for _ in range(2):
            lg, cache = tf.decode_step(params, seq[-1][:, None], cache, cfg)
            seq.append(jnp.argmax(lg, -1))
        return np.stack([np.asarray(t) for t in seq], 1)

    small = run(1 + b * n_pages)
    huge = run(16 * b * n_pages)   # 16x pool: same tokens, same guard
    assert (small == huge).all()


# --------------------------------------------------------------------------
# Cache plumbing: init/graft/copy
# --------------------------------------------------------------------------

def test_init_cache_paged_shapes_and_int8_lockstep():
    cfg = dataclasses.replace(get_config(ARCH, "smoke"), kv_cache_dtype="int8")
    cache = tf.init_cache(cfg, 3, 17, per_slot=True, page_size=4)
    n_pages = -(-17 // 4)
    assert cache["page_table"].shape == (3, n_pages)
    assert cache["page_table"].dtype == jnp.int32
    assert cache["pos"].shape == (3,)
    assert cache["k"].shape == (cfg.n_layers, 1 + 3 * n_pages, 4, cfg.n_kv, cfg.hd)
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].shape == cache["k"].shape[:-1] + (1,)
    assert cache["k_scale"].dtype == jnp.float32


def test_graft_and_copy_pages_roundtrip():
    cfg = get_config(ARCH, "smoke")
    cache = tf.init_cache(cfg, 2, 8, per_slot=True, page_size=4)
    mini = tf.init_cache(cfg, 2, 8)
    rng = np.random.default_rng(0)
    mk = jnp.asarray(rng.standard_normal(mini["k"].shape), mini["k"].dtype)
    mini = dict(mini, k=mk)
    # token (row 1, position 5) -> page 3, offset 2
    cache = tf.graft_pages(cache, mini, *(jnp.asarray([c], jnp.int32)
                                          for c in (1, 5, 3, 2)))
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 3, 2]),
                                  np.asarray(mk[:, 1, 5]))
    # CoW copy duplicates the page across every layer
    cache = tf.copy_pages(cache, jnp.asarray([3]), jnp.asarray([4]))
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 4]),
                                  np.asarray(cache["k"][:, 3]))

"""Flash-style blocked attention Pallas kernel.

This is the paper's blocking insight carried beyond BLAS: attention is two
chained GEMMs whose intermediate (the score matrix) never needs to exist in
HBM.  Exactly like the GEMM kernel keeps its f32 accumulator tile resident in
VMEM across the k sweep (AE5), this kernel keeps the online-softmax running
statistics (m, l) and the output accumulator resident across the key sweep,
so HBM traffic is O(T*D) instead of O(T^2).

Causal masking uses decode-style alignment: the query block sits at the END
of the key range (offset = Tk - Tq), which serves both training (Tq == Tk)
and single-step decode (Tq == 1) with one kernel.  `prefix_len` relaxes the
causal mask for the first `prefix_len` absolute key positions (prefix-LM:
the paligemma patch prefix attends bidirectionally, text stays causal), and
`causal=False` drops it entirely (encoder self-attention, whisper
cross-attention) — every mask variant the model zoo uses is in-kernel, so
the serving stack needs exactly ONE attention engine.

Two byte levers live here on top of the blocking:

  - packed int8 K/V (core.quant per-(token, head) scales): when
    `k_scales`/`v_scales` are passed, the K and V tiles stream at 1
    byte/element and dequantize in-kernel with one per-row multiply against
    the f32 softmax accumulator — the decode step's OTHER large byte term
    (after the weight stream, quantized in PR 4) at roughly half the HBM
    traffic, with no extra launches;
  - GQA without materialization: `kv_groups` > 1 maps `g` consecutive query
    heads onto one stored K/V head (the head walk inside the block), so
    grouped-query attention never expands the cache to the full head count
    in HBM.

Operand layout: the KV cache's NATIVE (B, T, H, D) layout, where one grid
row is one slot and each block carries all of its heads, so the kernel
streams the cache exactly as it sits in HBM (no moveaxis/reshape
materialization between the cache and the launch: the layout half of the
co-design, same as QuantSpec.transpose for weights).  Whole-head blocks
keep the block's last two dims equal to the array's (KVH, D), which the TPU
compiler accepts at any key-block or page size; a one-head block
(1, bk, 1, D) is refused.

Per-slot serving lengths: `kv_lens` (one real KV length per slot)
replaces the static kv_len/offset pair with an in-kernel SMEM scalar read,
so a continuous-batching decode step — every slot at its own ragged
position — runs the ragged grid in ONE launch with per-slot causal
alignment.

Paged KV: with `page_table` the K/V operands are a GLOBAL page
pool `(num_pages, page_size, KVH, D)` shared by every slot, and the table
`(B, max_pages)` names which physical page holds each slot's j-th logical
page, so page j of a slot holds logical key positions
[j*page_size, (j+1)*page_size).  Two launches read the pool, chosen by the
operands' shapes alone:

  - a launch of more than one query a slot (verify windows) runs the flash
    kernel above with the key block pinned to the page size: the KV index
    map does one lookup, `pt[slot, j]` instead of `slot`, through a
    scalar-prefetch operand (PrefetchScalarGridSpec), and the masking, GQA
    walk, per-slot lengths and int8 dequant are the dense cache path's;
  - a launch of ONE query a slot, the continuous scheduler's decode step,
    takes the decode path (`_paged_decode`).  One page a grid step and a
    walk of the heads with two tiny dots each cost ~70x the step's K/V
    bytes at the HBM peak on a v5e, paid in step overhead and serial
    per-head work, not in bytes.  The decode path's grid is (slot, group):
    each step reads P pages of one slot, P page-sized blocks of the same
    pool each looked up in the table, and folds them into every head at
    once — the scores are the elementwise q * k product reduced over D, the
    output the p * v product summed over the keys, the online-softmax state
    (H, 1) and the accumulator (H, D) in f32 as in the flash kernel.  P
    comes from the page size, KVH, D and dtype under the VMEM budget.  A
    step past a slot's last live page moves no bytes (its blocks repeat the
    indices before them) and computes nothing.  The pages go through
    BlockSpecs, not manual copies: Mosaic refuses a DMA out of a pool whose
    head size is below the 128 lanes (stablelm-1.6b has 64), as a slice of
    its padded lane dim.

Dead table entries point at page 0 (a reserved trash page), so every
lookup stays in bounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tiling
from repro.kernels import _compat

NEG_INF = -1e30


def _flash_kernel(
    *args,
    nk: int, bq: int, bk: int, scale: float, causal: bool, prefix_len: int,
    q_len: int, offset: int, kv_len: int, quantized: bool, dynamic_len: bool,
    heads: int, groups: int, paged: bool,
):
    # scalar-prefetch operands come first: [page_table] [kv_lens]
    args = list(args)
    if paged:
        # the page table is consumed entirely by the index maps — the kernel
        # body never touches it (positions are logical already)
        args.pop(0)
    len_ref = args.pop(0) if dynamic_len else None
    q_ref, k_ref, v_ref, *refs = args
    # refs: [k_scales] [v_scales] o m l acc
    ks_ref = refs.pop(0) if quantized else None
    vs_ref = refs.pop(0) if quantized else None
    o_ref, m_ref, l_ref, acc_ref = refs

    def tile(ref, head):
        # one head's (bt, d) tile of a (1, bt, H, d) block
        return ref[0, :, head, :]

    r = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(hh):
        kv_head = hh // groups
        if dynamic_len:
            # per-slot real KV length: the causal offset and the key mask
            # become per-slot scalars instead of launch-time constants
            kvl = len_ref[r]
            off = kvl - q_len
            mask_k = True
        else:
            kvl = kv_len
            off = offset
            mask_k = kv_len < nk * bk  # keys beyond kv_len are tile padding

        # Block culling (paper AE3 analog: skip whole-block work that the
        # dependency structure proves dead): causally-invisible blocks, and
        # blocks lying entirely in the key padding.
        first_k = ik * bk
        last_q = iq * bq + bq - 1 + off
        visible = first_k < kvl
        if causal:
            causal_vis = first_k <= last_q
            if prefix_len:
                # prefix-LM: blocks inside the bidirectional prefix stay live
                # even above the causal diagonal
                causal_vis = jnp.logical_or(causal_vis, first_k < prefix_len)
            visible = jnp.logical_and(visible, causal_vis)

        @pl.when(visible)
        def _body():
            q = tile(q_ref, hh).astype(jnp.float32) * scale      # (bq, d)
            k = tile(k_ref, kv_head).astype(jnp.float32)          # (bk, d)
            v = tile(v_ref, kv_head).astype(jnp.float32)          # (bk, d)
            if quantized:
                # packed int8 K/V tiles: one per-(token, head) scale row each
                # — dequantized on the fly against the f32 accumulator
                k = k * tile(ks_ref, kv_head)                     # (bk, 1)
                v = v * tile(vs_ref, kv_head)
            if dynamic_len or mask_k:
                # cdiv grid, no caller padding: fringe rows of the V tile are
                # undefined OOB reads and must be zeroed — a masked score
                # only guards the K side (p=0 still poisons the PV dot as
                # 0 * NaN).  Garbage K columns are covered by the kpos mask.
                kcol = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                v = jnp.where(kcol < kvl, v, 0.0)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                     # (bq, bk)
            if causal or mask_k:
                qpos = (iq * bq + off
                        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
                kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                keep = jnp.full((bq, bk), True)
                if causal:
                    cmask = qpos >= kpos
                    if prefix_len:
                        # bidirectional within the first prefix_len absolute
                        # key positions, causal after (the `kpos < kvl`
                        # key-validity mask below still bounds the prefix)
                        cmask |= kpos < prefix_len
                    keep &= cmask
                if mask_k:
                    keep &= kpos < kvl
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[hh]                                    # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                                # (bq, bk)
            alpha = jnp.exp(m_prev - m_new)                       # (bq, 1)
            l_ref[hh] = alpha * l_ref[hh] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:, hh, :] = alpha * acc_ref[:, hh, :] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[hh] = m_new

    for hh in range(heads):
        attend(hh)

    @pl.when(ik == nk - 1)
    def _flush():
        # normalize each head in the f32 accumulator, then write the block
        # with ONE whole-block store: a per-head store into a packed bf16
        # (bq, H, D) block is a sublane-strided write Mosaic refuses
        for hh in range(heads):
            acc_ref[:, hh, :] = acc_ref[:, hh, :] / l_ref[hh]
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


# --------------------------------------------------------------------------
# Paged decode: one query per slot, many pages a grid step, every head at once
# --------------------------------------------------------------------------

#: pages of a slot one grid step reads at most: each is an operand of its
#: own, so this bounds the launch's operand count and the unrolled body
_MAX_GROUP_PAGES = 16
#: keys one grid step reads at most
_GROUP_KEYS = 256
#: f32 vregs that one chunk's (keys, KVH, D) tile of K or V should fill: the
#: chunk is the unit of the online-softmax update
_CHUNK_VREGS = 32


def _vmem_tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a (rows, cols) slab: rows pad to the dtype's sublane
    tile (8 for f32, 16 for bf16, 32 for int8), cols to the 128 lanes."""
    item = jnp.dtype(dtype).itemsize
    return (tiling.round_up(rows, tiling.SUBLANE * 4 // item)
            * tiling.round_up(cols, tiling.MXU_DIM) * item)


def decode_group_pages(page_size: int, kvh: int, d: int, dtype,
                       quantized: bool, max_pages: int) -> int:
    """P, the pages of a slot that one grid step reads: the largest power
    of two up to `_MAX_GROUP_PAGES` that holds at most `_GROUP_KEYS` keys,
    that a table row can fill, and whose double-buffered K and V pages (and
    their scales) take at most a quarter of the VMEM budget."""
    page = _vmem_tile_bytes(kvh, d, dtype)
    if quantized:
        page += _vmem_tile_bytes(kvh, 1, jnp.float32)
    page *= page_size * 2 * 2            # K and V, two buffers each
    p = 1
    while (2 * p <= min(_MAX_GROUP_PAGES, max_pages)
           and 2 * p * page_size <= _GROUP_KEYS
           and 2 * p * page <= tiling.DEFAULT_VMEM_BUDGET // 4):
        p *= 2
    return p


def _decode_chunk_keys(page_size: int, kvh: int, d: int) -> int:
    """Keys per online-softmax update: the largest divisor of the page size
    whose f32 (keys, KVH, D) tile fills at most `_CHUNK_VREGS` vregs."""
    per_key = tiling.cdiv(kvh, tiling.SUBLANE) * tiling.cdiv(d, tiling.MXU_DIM)
    target = max(1, _CHUNK_VREGS // per_key)
    return max(c for c in range(1, min(target, page_size) + 1)
               if page_size % c == 0)


def _live_pages(len_ref, r, page_size: int, max_pages: int):
    """Pages of slot r that hold a key below its length."""
    return jnp.minimum(pl.cdiv(len_ref[r], page_size), max_pages)


def _group_page(r, j, pt_ref, len_ref, *, i: int, pages: int,
                page_size: int, max_pages: int):
    """Pool page of operand i at grid step (r, j): the table's entry for
    column j * P + i of slot r.  A step past the slot's last group repeats
    that group, and an operand past the last live page repeats its page of
    the group before, so neither moves a byte (the pipeline copies a block
    only when its index changes); in a slot's first group it reads the
    trash page 0, fetched once for as long as the operand stays there."""
    live = _live_pages(len_ref, r, page_size, max_pages)
    jj = jnp.minimum(j, jnp.maximum(pl.cdiv(live, pages), 1) - 1)
    col = jj * pages + i
    held = jnp.where(jj > 0, pt_ref[r, jnp.maximum(col - pages, 0)], 0)
    return jnp.where(col < live, pt_ref[r, jnp.minimum(col, max_pages - 1)],
                     held)


def _paged_decode_kernel(
    pt_ref, len_ref, q_ref, *refs,
    page_size: int, pages: int, chunk: int, max_pages: int, groups: int,
    scale: float, quantized: bool,
):
    del pt_ref  # read by the index maps alone
    per_page = 4 if quantized else 2
    page_refs = [refs[i * per_page:(i + 1) * per_page] for i in range(pages)]
    o_ref, m_ref, l_ref, acc_ref = refs[pages * per_page:]
    r = pl.program_id(0)
    j = pl.program_id(1)
    kvl = jnp.minimum(len_ref[r], max_pages * page_size)
    live = _live_pages(len_ref, r, page_size, max_pages)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(refs, first: int, kpos0):
        """Fold keys [first, first + chunk) of one page, logical positions
        kpos0 onwards, into every head's softmax state."""
        tile = [ref[0, first:first + chunk] for ref in refs]
        k = tile[0].astype(jnp.float32)                  # (C, KVH, D)
        v = tile[1].astype(jnp.float32)
        if quantized:
            k = k * tile[2]                              # (C, KVH, 1) scales
            v = v * tile[3]
        # the one query sits at position kvl - 1, so the causal mask is the
        # length mask; V rows past it are zeroed (p = 0 does not clear a
        # non-finite row: 0 * NaN)
        kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, 1), 0)
        keep = kpos < kvl
        v = jnp.where(keep, v, 0.0)
        q = q_ref[0].astype(jnp.float32) * scale         # (g, KVH, D)
        for g in range(groups):
            # all KV heads at once: the scores are the elementwise q * k
            # product reduced over D, the output the p * v product summed
            # over the keys
            s = jnp.sum(q[g][None] * k, axis=-1, keepdims=True)  # (C, KVH, 1)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[g]                                    # (KVH, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=0)
            acc_ref[g] = alpha * acc_ref[g] + jnp.sum(p * v, axis=0)
            m_ref[g] = m_new

    for i, refs_i in enumerate(page_refs):
        col = j * pages + i

        @pl.when(col < live)
        def _page(refs_i=refs_i, col=col):
            for first in range(0, page_size, chunk):
                attend(refs_i, first, col * page_size + first)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_decode(q, k, v, k_scales, v_scales, kv_lens, page_table, *,
                  kv_groups: int, scale: float, interpret: bool):
    """One query per slot over the page pool: grid (slots, table columns /
    P), each step reading P pages of one slot through P page-sized blocks
    of the same pool (module docstring)."""
    b, _, h, d = q.shape
    _, page_size, kvh, _ = k.shape
    max_pages = page_table.shape[1]
    quantized = k_scales is not None
    pages = decode_group_pages(page_size, kvh, d, k.dtype, quantized,
                               max_pages)
    g = kv_groups
    # query head kv * g + j attends stored head kv: as (g, KVH, D), each of
    # the g rows lines up with the stored heads of a K/V page
    qg = q.reshape(b, kvh, g, d).swapaxes(1, 2)
    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, pages=pages,
        chunk=_decode_chunk_keys(page_size, kvh, d), max_pages=max_pages,
        groups=g, scale=scale, quantized=quantized)
    q_spec = pl.BlockSpec((1, g, kvh, d), lambda r, j, *_: (r, 0, 0, 0))
    pools = [k, v]
    if quantized:
        pools += [k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)]
    operands, in_specs = [qg], [q_spec]
    for i in range(pages):
        index = functools.partial(_group_page, i=i, pages=pages,
                                  page_size=page_size, max_pages=max_pages)
        for pool in pools:
            operands.append(pool)
            in_specs.append(pl.BlockSpec(
                (1, page_size, kvh, pool.shape[-1]),
                lambda r, j, pt, lens, index=index: (
                    index(r, j, pt, lens), 0, 0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pl.cdiv(max_pages, pages)),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((g, kvh, 1), jnp.float32),
                pltpu.VMEM((g, kvh, 1), jnp.float32),
                pltpu.VMEM((g, kvh, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=_compat.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32).reshape(b),
      *operands)
    return out.swapaxes(1, 2).reshape(q.shape)


def attention(
    q: jnp.ndarray,  # (B, Tq, H, D)
    k: jnp.ndarray,  # (B, Tk, H // kv_groups, D); int8 when k_scales is given
    v: jnp.ndarray,  # same layout as k
    *,
    k_scales: jnp.ndarray = None,  # k's layout with D -> 1, f32
    v_scales: jnp.ndarray = None,
    kv_lens: jnp.ndarray = None,   # (B,) int32 per-slot KV lengths
    page_table: jnp.ndarray = None,  # (B, max_pages) int32: k/v are the pool
    kv_groups: int = 1,            # query heads per stored K/V head (GQA)
    causal: bool = True,
    prefix_len: int | None = None,  # prefix-LM: bidirectional first keys
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    q_len: int | None = None,
    kv_len: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """q_len/kv_len are the REAL lengths when q/k/v carry extra rows
    (both default to the operand extents — no caller padding is required:
    the grids are cdiv-shaped and the kernel masks the key fringe itself,
    the paper's DOT2/DOT3 fringe handling moved inside the kernel).  Keys
    at positions >= kv_len are masked to -inf and their V rows zeroed; the
    causal offset aligns the real query range to the END of the real key
    range, independent of any extra rows on either side.
    `kv_lens` makes the real length per slot — the continuous-batching
    ragged slot grid — instead of a launch constant; it rides in
    SMEM as a scalar-prefetch operand.  With `k_scales`/`v_scales` the K/V
    tiles are packed int8 (core.quant.quantize_kv) and dequantize
    in-kernel.  `prefix_len` (with causal=True) makes the first `prefix_len`
    ABSOLUTE key positions bidirectionally visible (prefix-LM); it is
    ignored when causal=False (everything is visible already).

    The operands stream the KV cache's native (B, T, H, D) layout.  One
    grid row is one slot, and its blocks hold EVERY head: `(1, bq, H, D)` for q
    and `(1, bk, KVH, D)` for K/V, so the last two block dims always equal
    the array's (the TPU's (8, 128) tiling rule holds at any block_q,
    block_k or page size) and no transposed copy of the cache is ever
    materialized.  The body walks the heads with 2-D tiles.
    With `page_table` the k/v (and scale) operands are the PAGE POOL
    `(num_pages, page_size, KVH, D)` and the logical key stream of slot b is
    `pool[page_table[b, 0]], pool[page_table[b, 1]], ...`.  One query a
    slot takes the decode path (P pages a grid step, every head at once;
    block_q and block_k do not apply); more queries pin block_k to
    page_size and the KV index map does the one table lookup.
    """
    paged = page_table is not None
    b, tq, h, d = q.shape
    _, tk, kvh, _ = k.shape
    if paged:
        tk *= page_table.shape[-1]  # logical per-slot capacity
    assert h == kvh * kv_groups, (q.shape, k.shape, kv_groups)
    quantized = k_scales is not None
    assert (k_scales is None) == (v_scales is None)
    q_len = tq if q_len is None else q_len
    kv_len = tk if kv_len is None else kv_len
    if scale is None:
        scale = d ** -0.5
    if paged and tq == 1:
        # one query a slot, the decode step: P pages a grid step and every
        # head at once (module docstring)
        if kv_lens is None:
            kv_lens = jnp.full((b,), kv_len, jnp.int32)
        return _paged_decode(q, k, v, k_scales, v_scales, kv_lens,
                             page_table, kv_groups=kv_groups, scale=scale,
                             interpret=interpret)
    block_q = min(block_q, tq)
    # paged: one key block per page — the block grid IS the page table row
    block_k = k.shape[1] if paged else min(block_k, tk)
    # cdiv grids, no divisibility contract: the key fringe is masked
    # in-kernel (kpos/kvl on the scores, zeroed V rows) and the ragged
    # query-block rows are clipped by Pallas on the output write
    grid = (b, pl.cdiv(tq, block_q), pl.cdiv(tk, block_k))
    dynamic_len = kv_lens is not None
    kernel = functools.partial(
        _flash_kernel,
        nk=grid[2],
        bq=block_q,
        bk=block_k,
        scale=scale,
        causal=causal,
        prefix_len=int(prefix_len or 0),
        q_len=q_len,
        offset=kv_len - q_len,
        kv_len=kv_len,
        quantized=quantized,
        dynamic_len=dynamic_len,
        heads=h,
        groups=kv_groups,
        paged=paged,
    )
    # index maps take the grid indices and then the scalar-prefetch refs
    q_spec = pl.BlockSpec((1, block_q, h, d), lambda r, i, j, *_: (r, i, 0, 0))
    if paged:
        # the ONE page-table lookup: logical key block j of slot r lives in
        # physical page pt[r, j] of the pool — masking, GQA fold and scales
        # layout are the dense cache path verbatim
        kv_idx = lambda r, i, j, pt, *_: (pt[r, j], 0, 0, 0)
    else:
        kv_idx = lambda r, i, j, *_: (r, j, 0, 0)
    kv_spec = pl.BlockSpec((1, block_k, kvh, d), kv_idx)
    s_spec = pl.BlockSpec((1, block_k, kvh, 1), kv_idx)
    prefetch = []
    if paged:
        prefetch.append(page_table.astype(jnp.int32))
    if dynamic_len:
        prefetch.append(kv_lens.astype(jnp.int32).reshape(b))
    operands = [q, k, v]
    in_specs = [q_spec, kv_spec, kv_spec]
    if quantized:
        operands += [k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)]
        in_specs += [s_spec, s_spec]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((h, block_q, 1), jnp.float32),
                pltpu.VMEM((h, block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, h, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_compat.compiler_params(
            "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*prefetch, *operands)

"""Compile-only checks of the serving path's Pallas kernels for a TPU v5e.

Each test lowers one kernel at stablelm-1.6b's published widths (d=2048,
32 heads of 64, d_ff=5632), or at internlm2-20b's where it says so (the
int8 projections), with `interpret=False` and compiles it for a
DESCRIBED v5e chip: the TPU compiler is installed here even where no chip
is attached, so it refuses what the chip would refuse (block shapes off the
(8, 128) tiling, scoped VMEM overruns).  Nothing runs, so these tests say
nothing about results or speed; the interpret-mode suites cover results.

The topology is described only inside the module fixture below: loading the
TPU library at import time would make every pytest-xdist worker contend
for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import epilogue, quant, tiling
from repro.kernels import attention, bgemm, bgemv, gemm

D, H, HD, FF = 2048, 32, 64, 5632   # stablelm-1.6b widths
B, T, CACHE = 8, 128, 2048          # serving batch, prompt, KV capacity


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    """Lower and compile `fn` for the described chip; the kernel must be
    there as a Mosaic custom call (no XLA fallback took its place)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed(sharding, k, n, lead=()):
    """Shapes of a serving weight packed as layers.quantize_weights packs it:
    output-major int8 values with one f32 scale per 64 output rows (`lead`:
    a layer stack's leading axis)."""
    qt = jax.eval_shape(
        lambda w: quant.quantize(w, quant.QuantSpec(64, None, transpose=True)),
        jax.ShapeDtypeStruct(lead + (k, n), jnp.bfloat16))
    return (_spec(sharding, qt.values.shape, qt.values.dtype),
            _spec(sharding, qt.scales.shape, qt.scales.dtype), qt.block)


def test_prefill_gemm_compiles_at_planner_block(one_chip):
    """A 2048-token QKV GEMM at the planner's block (2048^3 under the
    64 MiB budget): compiles only because every kernel passes the budget as
    its scoped-VMEM limit."""
    m, n, k = 2048, 3 * D, D
    blk = tiling.choose_block_shape(m, n, k)
    assert blk == tiling.BlockShape(2048, 2048, 2048)
    _compile(lambda a, b: gemm.gemm(a, b, block_m=blk.bm, block_n=blk.bn,
                                    block_k=blk.bk),
             _spec(one_chip, (m, k)), _spec(one_chip, (k, n)))


def test_prefill_bgemm_swiglu_dual_b_compiles(one_chip):
    """The prefill SwiGLU: one dual-B bgemm launch with the silu gate fused
    into the flush, at the block the planner gives the fused variant."""
    blk = tiling.rank_block_shapes(T, FF, D, gate=True)[0]
    epi = epilogue.make("silu", gate=True)
    _compile(lambda a, b, b2: bgemm.bgemm(
        a, b, b2=b2, epilogue=epi, block_m=blk.bm, block_n=blk.bn,
        block_k=blk.bk),
        _spec(one_chip, (B, T, D)), _spec(one_chip, (D, FF)),
        _spec(one_chip, (D, FF)))


def test_prefill_bgemm_swiglu_int8_at_internlm2_widths_compiles(one_chip):
    """The int8 prefill SwiGLU of internlm2-20b (d=6144, d_ff=16384), an
    admission of 8 slots of 1024 rows: packed gate and up weights, each
    dequantized in-kernel, at the planner's first block, which has to fit
    the 64 MiB scoped-VMEM budget once the dequantized tiles are charged."""
    b, t, d, ff = 8, 1024, 6144, 16384
    blk = tiling.rank_block_shapes(t, ff, d, gate=True, b_dtype_bytes=1)[0]
    epi = epilogue.make("silu", gate=True)
    w, ws, q_block = _packed(one_chip, d, ff)
    _compile(lambda a, w, ws, w2, w2s: bgemm.bgemm(
        a, w, b2=w2, scales=ws, b2_scales=w2s, q_block=q_block,
        b_layout="nk", epilogue=epi, block_m=blk.bm, block_n=blk.bn,
        block_k=blk.bk),
        _spec(one_chip, (b, t, d)), w, ws, w, ws)


def _planned_bgemv(a_dtype, m, n, *, gate=False, residual=False):
    """The (block_b, block_m, block_n) keywords `tiling.plan_bgemv` gives a
    broadcast-weight decode projection of B slots."""
    bb, bm, bn = tiling.plan_bgemv(B, m, n, a_dtype, jnp.bfloat16, gate=gate,
                                   residual=residual)
    assert bb == B  # the whole batch in one block: each tile read once
    return dict(block_b=bb, block_m=bm, block_n=bn)


def test_decode_bgemv_transpose_a_compiles(one_chip):
    """The bf16 decode projection: broadcast weight streamed in its HBM
    (d, f) layout under transpose_a, with the SwiGLU gate fused, at the
    planner's tiles."""
    epi = epilogue.make("silu", gate=True)
    blocks = _planned_bgemv(jnp.bfloat16, FF, D, gate=True)
    _compile(lambda w, w2, x: bgemv.bgemv(w, x, a2=w2, epilogue=epi,
                                          transpose_a=True, **blocks),
             _spec(one_chip, (D, FF)), _spec(one_chip, (D, FF)),
             _spec(one_chip, (B, D)))


def test_decode_bgemv_int8_packed_compiles(one_chip):
    """The int8 decode projection: packed output-major weight and its scale
    grid, the tile converted in-kernel and scaled in the flush, SwiGLU gate
    fused, at the planner's tiles."""
    epi = epilogue.make("silu", gate=True)
    w, ws, q_block = _packed(one_chip, D, FF)
    blocks = _planned_bgemv(jnp.int8, FF, D, gate=True)
    _compile(lambda w, ws, w2, w2s, x: bgemv.bgemv(
        w, x, a2=w2, scales=ws, a2_scales=w2s, q_block=q_block,
        epilogue=epi, **blocks),
        w, ws, w, ws, _spec(one_chip, (B, D)))


@pytest.mark.parametrize("proj", [
    # internlm2-20b (d=6144, d_ff=16384), int8, one scale per 64 rows
    pytest.param(("int8", 6144, 16384, "gate"), id="int8-gate-up"),
    pytest.param(("int8", 16384, 6144, "residual"), id="int8-down"),
    pytest.param(("int8", 6144, 6144, None), id="int8-wq"),
    # stablelm-1.6b, bf16 under transpose_a
    pytest.param(("bf16", D, FF, "gate"), id="bf16-gate-up"),
    pytest.param(("bf16", D, D, "bias"), id="bf16-qkv-bias"),
])
@pytest.mark.parametrize("layers", [None, 8], ids=["one", "stack"])
def test_decode_bgemv_compiles_at_serving_widths(one_chip, proj, layers):
    """Each decode projection of both benchmark models, 8 slots in one
    block, at the tiles the planner gives: the VMEM charge and the tiling
    of every block (weight tile, (B, bn) x, lane-dense (B, bm) output) are
    what the chip's compiler checks.  "stack" is how the decode step runs
    it: one layer read in place out of an 8-layer stack, the layer index
    in SMEM."""
    kind, k, n, extra = proj
    gate, residual, bias = extra == "gate", extra == "residual", extra == "bias"
    epi = epilogue.make("silu" if gate else None, gate=gate or None,
                        residual=residual or None, bias=bias or None)
    x = _spec(one_chip, (B, k))
    lead = () if layers is None else (layers,)
    layer = [] if layers is None else [_spec(one_chip, (), jnp.int32)]
    res = [_spec(one_chip, (B, n))] if residual else []
    if kind == "int8":
        w, ws, q_block = _packed(one_chip, k, n, lead)
        blocks = _planned_bgemv(jnp.int8, n, k, gate=gate, residual=residual)

        def fn(x, w, ws, *rest):
            rest = list(rest)
            kw = dict(a2=rest.pop(0), a2_scales=rest.pop(0)) if gate else {}
            if residual:
                kw["residual"] = rest.pop(0)
            if layers:
                kw["layer"] = rest.pop(0)
            return bgemv.bgemv(w, x, scales=ws, q_block=q_block, epilogue=epi,
                               **kw, **blocks)

        _compile(fn, x, w, ws, *([w, ws] if gate else []), *res, *layer)
        return
    w = _spec(one_chip, lead + (k, n))
    blocks = _planned_bgemv(jnp.bfloat16, n, k, gate=gate, residual=residual)

    def fn(x, w, *rest):
        rest = list(rest)
        kw = {}
        if gate:
            kw["a2"] = rest.pop(0)
        if bias:
            kw["bias"] = rest.pop(0)
        if residual:
            kw["residual"] = rest.pop(0)
        if layers:
            kw["layer"] = rest.pop(0)
        return bgemv.bgemv(w, x, epilogue=epi, transpose_a=True, **kw,
                           **blocks)

    _compile(fn, x, w, *([w] if gate else []),
             *([_spec(one_chip, (1, n))] if bias else []), *res, *layer)


def test_flash_cache_layout_decode_compiles(one_chip):
    """Ragged-slot decode over the dense (B, T, H, D) cache: one query per
    slot, per-slot lengths in SMEM, whole-head K/V blocks."""
    _compile(lambda q, k, v, lens: attention.attention(q, k, v, kv_lens=lens),
             _spec(one_chip, (B, 1, H, HD)),
             _spec(one_chip, (B, CACHE, H, HD)),
             _spec(one_chip, (B, CACHE, H, HD)),
             _spec(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("shape", [
    # (slots, query heads, KV heads, head size, page size, pool pages,
    #  table columns)
    pytest.param((B, H, H, HD, 1, 1 + B * CACHE, CACHE), id="1"),
    pytest.param((B, H, H, HD, 16, 1 + B * CACHE // 16, CACHE // 16),
                 id="16"),
    # the benchmark cell's pool: 777 pages of 16 x 32 x 64, 96 columns
    pytest.param((B, H, H, HD, 16, 777, 96), id="cell"),
    # GQA at internlm2-20b's widths: 48 query heads on 8 KV heads of 128
    pytest.param((B, 48, 8, 128, 16, 777, 96), id="gqa"),
])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_flash_paged_decode_compiles(one_chip, shape, kv):
    """One-query paged decode through the page table, bf16 and packed int8
    KV: the decode path, P page-sized blocks of the pool a grid step.  Page
    size 1 is the smallest a pool can have: whole-head blocks keep each
    block's last two dims at (KVH, D) whatever the page size."""
    b, h, kvh, hd, page_size, pages, cols = shape
    pool = (pages, page_size, kvh, hd)
    q = _spec(one_chip, (b, 1, h, hd))
    lens = _spec(one_chip, (b,), jnp.int32)
    table = _spec(one_chip, (b, cols), jnp.int32)
    groups = h // kvh
    if kv == "int8":
        kq = _spec(one_chip, pool, jnp.int8)
        ks = _spec(one_chip, pool[:-1] + (1,), jnp.float32)
        _compile(lambda q, k, v, ks, vs, lens, pt: attention.attention(
            q, k, v, k_scales=ks, v_scales=vs, kv_lens=lens, page_table=pt,
            kv_groups=groups),
            q, kq, kq, ks, ks, lens, table)
    else:
        kb = _spec(one_chip, pool)
        _compile(lambda q, k, v, lens, pt: attention.attention(
            q, k, v, kv_lens=lens, page_table=pt, kv_groups=groups),
            q, kb, kb, lens, table)

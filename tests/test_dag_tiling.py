"""DAG analysis (paper S4) and TPU tiling invariants."""

import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dag, tiling
from repro.core.tiling import BlockShape, choose_block_shape, plan_gemm


def test_ddot_structure():
    p = dag.ddot(8)
    assert p.max_width == 8          # all mults in parallel (paper Fig 3)
    assert p.depth == 1 + 3          # mult level + log2(8) add levels
    assert p.flops == 15


def test_dgemm_is_n2_independent_ddots():
    n = 16
    d, g = dag.ddot(n), dag.dgemm(n)
    assert g.depth == d.depth        # independent ddots: depth unchanged
    assert g.max_width == n ** 3     # all mults in parallel (paper S4.3.5)
    assert g.flops == n * n * d.flops


def test_strassen_winograd_op_counts():
    # paper Tables 2-3: 7 mults; 18 vs 15 adds; classical: 8 mults 4 adds
    assert dag.STRASSEN.block_mults == dag.WINOGRAD.block_mults == 7
    assert dag.STRASSEN.block_adds == 18 and dag.WINOGRAD.block_adds == 15
    assert dag.CLASSICAL.block_mults == 8
    # winograd always beats strassen (fewer adds); strassen only beats
    # classical asymptotically (the paper's argument for classical GEMM at
    # PE-block sizes: at n<=100 classical wins outright)
    assert dag.algo_flops(dag.WINOGRAD, 64) < dag.algo_flops(dag.STRASSEN, 64)
    assert dag.algo_flops(dag.STRASSEN, 64) > 2 * 64 ** 3  # blocking sizes: classical wins
    assert dag.algo_flops(dag.STRASSEN, 2 ** 14) < 2 * (2 ** 14) ** 3  # asymptotically loses
    assert dag.STRASSEN.exponent < dag.CLASSICAL.exponent


@settings(max_examples=30, deadline=None)
@given(m=st.integers(1, 16384), n=st.integers(1, 16384), k=st.integers(1, 16384))
def test_block_chooser_respects_vmem_and_alignment(m, n, k):
    b = choose_block_shape(m, n, k)
    # bm is MXU-aligned — except the skinny-m plan, where the one legal
    # sub-MXU extent is the SUBLANE-aligned real row count
    assert (b.bm % 128 == 0
            or b.bm == tiling.round_up(m, tiling.SUBLANE))
    assert b.bm % tiling.SUBLANE == 0
    assert b.bn % 128 == 0 and b.bk % 128 == 0
    vmem = 2 * (b.bm * b.bk + b.bk * b.bn) * 2 + b.bm * b.bn * 4 + b.bm * b.bn * 2
    assert vmem <= tiling.DEFAULT_VMEM_BUDGET


def test_skinny_m_plans_sublane_block():
    """Speculative verify windows run (k+1)-row member GEMMs (k+1 <= 8):
    the planner must pick the SUBLANE-aligned bm — a 128-row tile would be
    >90% padding — and spend the freed VMEM on wide bn/bk, where the
    arithmetic intensity actually lives when m is tiny."""
    for m in (1, 4, 5, 8):
        top = tiling.rank_block_shapes(m, 4096, 4096)[0]
        assert top.bm == 8, (m, top)
        assert top.bn >= 1024 and top.bk >= 1024, (m, top)
    # one row past the sublane: pads to 16, still beats a 128-row tile
    assert tiling.rank_block_shapes(9, 4096, 4096)[0].bm == 16
    # at or past one MXU tile nothing changes
    assert tiling.rank_block_shapes(128, 4096, 4096)[0].bm % 128 == 0
    assert choose_block_shape(8192, 8192, 8192).bm % 128 == 0


def test_autotune_cache_key_quantized_is_distinct():
    """A winner measured with packed int8 B tiles must never be served to
    the full-precision op: the :q1 suffix keys quantized plans separately,
    composing with the fused-epilogue flags."""
    base = dict(op="bgemm", m=8, n=4096, k=4096, dtype_bytes=2,
                backend="cpu")
    plain = tiling.autotune_cache_key(**base)
    quant = tiling.autotune_cache_key(**base, quantized=True)
    fused_q = tiling.autotune_cache_key(**base, gate=True, quantized=True)
    assert plain != quant and quant.endswith(":q1")
    assert len({plain, quant, fused_q}) == 3


def test_vmem_bytes_matches_selection_budget_formula():
    """BlockShape.vmem_bytes(dtype_bytes) must BE the budget formula the
    chooser enforces (regression: it hardcoded 2-byte operands while
    choose_block_shape took dtype_bytes)."""
    b = BlockShape(256, 128, 512)
    for db in (1, 2, 4, 8):
        want = 2 * (b.bm * b.bk + b.bk * b.bn) * db + b.bm * b.bn * 4 + b.bm * b.bn * db
        assert b.vmem_bytes(db) == want
    # default stays the bf16 working set the seed reported
    assert b.vmem_bytes() == b.vmem_bytes(2)


@pytest.mark.parametrize("dtype_bytes", [1, 2, 4, 8])
def test_block_chooser_budget_holds_per_dtype(dtype_bytes):
    """The selected block's working set — measured at the SAME dtype the
    chooser planned for — must fit the budget for every operand width."""
    b = choose_block_shape(8192, 8192, 8192, dtype_bytes=dtype_bytes)
    assert b.vmem_bytes(dtype_bytes) <= tiling.DEFAULT_VMEM_BUDGET


def test_bigger_blocks_win_when_they_fit():
    """The AE4 argument: arithmetic intensity grows with block size, so the
    chooser takes the largest VMEM-feasible tile."""
    small = BlockShape(128, 128, 128)
    big = choose_block_shape(8192, 8192, 8192)
    assert big.arithmetic_intensity() > small.arithmetic_intensity()


@settings(max_examples=30, deadline=None)
@given(m=st.integers(1, 8192), n=st.integers(1, 8192), k=st.integers(1, 8192))
def test_grid_plan_covers_problem(m, n, k):
    plan = plan_gemm(m, n, k)
    pm, pn, pk = plan.padded
    assert pm >= m and pn >= n and pk >= k
    g = plan.grid
    assert g[0] * plan.block.bm == pm
    assert 0.0 <= plan.pad_waste_fraction() < 1.0


def test_pad_dim_roundtrip():
    import jax.numpy as jnp
    x = jnp.ones((5, 7))
    y, orig = tiling.pad_dim_to(x, 1, 4)
    assert y.shape == (5, 8) and orig == 7
    assert float(y[:, 7:].sum()) == 0.0


# the prefill projections of stablelm-1.6b's cell (d=2048, d_ff=5632; 1024
# rows a slot): bf16 plans, which the packed-weight charge leaves alone
@pytest.mark.parametrize("n,k,kw", [
    pytest.param(2048, 2048, {}, id="qkv"),
    pytest.param(2048, 2048, {"residual": True}, id="wo"),
    pytest.param(5632, 2048, {"gate": True}, id="gate-up"),
    pytest.param(2048, 5632, {"residual": True}, id="down"),
])
def test_bf16_plans_at_stablelm_widths_are_unchanged(n, k, kw):
    assert tiling.rank_block_shapes(1024, n, k, **kw)[0] == BlockShape(
        1024, 2048, 2048)


def test_packed_plan_charges_the_dequantized_tiles():
    """A packed-weight plan charges the B tile the kernel dequantizes to
    f32, and the gate's beside it.  At internlm2-20b's SwiGLU (d=6144,
    d_ff=16384, 1024 rows a slot) that keeps out the block a v5e refuses
    (72.77 MiB of scoped VMEM against 64) and plans the next one."""
    big = BlockShape(1024, 2048, 2048)
    assert tiling.dequant_vmem_bytes(big) == 2048 * 2048 * 4
    assert tiling.dequant_vmem_bytes(big, gate=True) == 2 * 2048 * 2048 * 4
    ranked = tiling.rank_block_shapes(1024, 16384, 6144, gate=True,
                                      b_dtype_bytes=1)
    assert big not in ranked
    assert ranked[0] == BlockShape(1024, 2048, 1024)
    for b in ranked:
        used = (2 * (b.bm * b.bk * 2 + b.bk * b.bn) + b.bm * b.bn * 4
                + b.bm * b.bn * 2 + tiling.dequant_vmem_bytes(b, gate=True)
                + tiling.epilogue_vmem_bytes(b, 1, gate=True))
        assert used <= tiling.DEFAULT_VMEM_BUDGET
    # one packed stream charges one dequantized tile
    plain = tiling.rank_block_shapes(1024, 6144, 16384, b_dtype_bytes=1)
    assert plain[0] == big


@pytest.mark.parametrize("a_dtype,m,n,kw,want", [
    # internlm2-20b int8 decode projections (output m, contraction n)
    ("int8", 16384, 6144, {"gate": True}, (8, 1024, 2048)),
    ("int8", 6144, 16384, {"residual": True}, (8, 1024, 2048)),
    ("int8", 1024, 6144, {}, (8, 1024, 2048)),
    # stablelm-1.6b bf16: d_ff 5632 = 4 x 1408 along the contraction
    ("bfloat16", 5632, 2048, {"gate": True}, (8, 512, 2048)),
    ("bfloat16", 2048, 5632, {"residual": True}, (8, 512, 1408)),
])
def test_bgemv_plans_at_serving_widths(a_dtype, m, n, kw, want):
    """The decode plan: 8 slots in one block beside ~2 MiB weight tiles
    whose extents divide the weight (no ragged fringe), under the budget
    once the converted int8 tile, the gate and the residual are charged."""
    import jax.numpy as jnp
    bb, bm, bn = tiling.plan_bgemv(8, m, n, a_dtype, jnp.bfloat16, **kw)
    assert (bb, bm, bn) == want
    assert m % bm == 0 and n % bn == 0
    used = tiling.bgemv_vmem_bytes(bb, bm, bn, a_dtype, jnp.bfloat16, **kw)
    assert used <= tiling.DEFAULT_VMEM_BUDGET
    if a_dtype == "int8" and kw.get("gate"):
        # two double-buffered int8 tiles and their bf16 copies; x, the two
        # f32 accumulators, the output and the bias row, 8 rows each
        assert used == (2 * (2 * bm * bn + bm * bn * 2) + 2 * 8 * bn * 2
                        + 2 * 8 * bm * 4 + 2 * 8 * bm * 2 + 2 * 8 * bm * 2)


@pytest.mark.parametrize("a_dtype,x_dtype,m,n", [
    ("float32", "float32", 512, 256),
    ("bfloat16", "bfloat16", 5632, 2048),
    ("int8", "bfloat16", 16384, 6144),
])
def test_bgemv_batch_cap_is_what_fits(a_dtype, x_dtype, m, n):
    """The batch cap is the most aligned rows whose charge fits the budget;
    a batch past it splits into the fewest even blocks within the cap."""
    budget = tiling.DEFAULT_VMEM_BUDGET
    _, bm, bn = tiling.plan_bgemv(1, m, n, a_dtype, x_dtype, gate=True)
    cap = tiling.bgemv_batch_cap(bm, bn, a_dtype, x_dtype, gate=True)
    assert cap % tiling.BGEMV_BATCH_ALIGN == 0 and cap > 0
    charge = lambda bb: tiling.bgemv_vmem_bytes(bb, bm, bn, a_dtype,
                                                x_dtype, gate=True)
    assert charge(cap) <= budget
    assert charge(cap + tiling.BGEMV_BATCH_ALIGN) > budget
    for batch in (cap, cap + 1, 3 * cap + 5):
        bb, _, _ = tiling.plan_bgemv(batch, m, n, a_dtype, x_dtype,
                                     gate=True)
        assert bb <= cap
        assert tiling.cdiv(batch, bb) == tiling.cdiv(batch, cap)


def test_bgemv_caller_tiles_are_charged():
    """Tiles a caller passes replace the planned ones, and the batch block
    is charged against them: wider tiles leave room for fewer rows."""
    m, n = 16384, 6144
    _, bm, bn = tiling.plan_bgemv(8, m, n, "int8", "bfloat16", gate=True)
    wide = tiling.plan_bgemv(10 ** 6, m, n, "int8", "bfloat16", gate=True,
                             block_m=2 * bm, block_n=bn)
    assert wide[1:] == (2 * bm, bn)
    cap = tiling.bgemv_batch_cap(2 * bm, bn, "int8", "bfloat16", gate=True)
    assert 0 < wide[0] <= cap < tiling.bgemv_batch_cap(bm, bn, "int8",
                                                       "bfloat16", gate=True)
    assert tiling.plan_bgemv(8, m, n, "int8", "bfloat16", gate=True,
                             block_m=128, block_n=256) == (8, 128, 256)

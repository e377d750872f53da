"""Block-scaled int8 quantization: numerics, kernels, plumbing, tiling.

The accuracy contract: every backend's quantized output stays within the
DOCUMENTED per-block error bound of the f32 oracle (quant.matvec_error_bound
— weight rounding only for the exact-dequant paths, plus the activation
terms for the host W8A8 fast path).  pallas/ref must match the
dequantization oracle exactly (same math, different engine).

The bandwidth contract is structural and backend-independent: packed weight
bytes < full/2, and the tiling planner sees the true packed width.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import blas, quant, tiling
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)
BACKENDS = ("xla", "pallas", "ref")


def _rand(shape, dtype=jnp.float32, key=KEY, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# quantize / dequantize numerics
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,block", [
    ((64, 128), (32, 64)),
    ((64, 128), (64, None)),
    ((96, 80), (48, 40)),
    ((3, 64, 128), (16, 128)),       # leading (layer/expert) dim
])
def test_dequantize_within_elementwise_bound(dtype, shape, block):
    x = _rand(shape, dtype)
    qt = quant.quantize(x, quant.QuantSpec(block_m=block[0], block_n=block[1]))
    assert qt.values.dtype == jnp.int8
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(x, np.float32))
    bound = np.asarray(qt.elementwise_bound())
    assert (err <= bound + 1e-6).all()
    assert qt.shape == tuple(shape)


def test_transpose_storage_keeps_logical_shape():
    x = _rand((48, 96))
    qt = quant.quantize(x, quant.QuantSpec(block_m=16, block_n=None, transpose=True))
    assert qt.stored_shape == (96, 48)
    assert qt.shape == (48, 96)
    np.testing.assert_allclose(
        np.asarray(qt.dequantize()), np.asarray(x), atol=float(qt.scales.max()) / 2 + 1e-6
    )


def test_zero_block_quantizes_to_exact_zero():
    x = jnp.zeros((32, 64), jnp.float32)
    qt = quant.quantize(x, quant.QuantSpec(block_m=16, block_n=32))
    assert (np.asarray(qt.dequantize()) == 0).all()


def test_block_fits_awkward_dims():
    # prime-ish dims: blocks shrink to the nearest divisor, never crash
    x = _rand((66, 130))
    qt = quant.quantize(x, quant.QuantSpec(block_m=64, block_n=64))
    qm, qn = qt.block
    assert 66 % qm == 0 and 130 % qn == 0


@pytest.mark.parametrize("shape,spec", [
    ((2, 64, 128), quant.QuantSpec(64, None, transpose=True)),
    ((3, 66, 130), quant.QuantSpec(64, 64)),             # awkward dims
    ((3, 96, 80), quant.QuantSpec(48, 40, transpose=True)),
    ((2, 3, 40, 72), quant.QuantSpec(16, None)),         # two leading dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pack_matches_whole_stack_quantize_bitwise(shape, spec, dtype):
    """Packing one matrix of a stack at a time gives the values, scales and
    block that quantizing the whole stack at once gives, bit for bit."""
    x = _rand(shape, dtype, scale=3.0)
    want = quant.quantize(x, spec)
    got = quant.pack(x, spec)
    assert (got.block, got.transposed) == (want.block, want.transposed)
    assert got.values.dtype == want.values.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got.values),
                                  np.asarray(want.values))
    np.testing.assert_array_equal(np.asarray(got.scales).view(np.uint32),
                                  np.asarray(want.scales).view(np.uint32))


def test_pack_refuses_non_finite_input():
    bad = _rand((2, 16, 32)).at[1, 3, 4].set(jnp.nan)
    with pytest.raises(ValueError, match="NaN/Inf"):
        quant.pack(bad)
    assert quant.is_quantized(quant.pack(_rand((2, 16, 32))))


def test_pack_temporaries_span_one_matrix():
    """Compiled for the CPU only (nothing is allocated): packing an
    internlm2-20b stage's SwiGLU leaf, (8, 6144, 16384) bf16, keeps its
    float32 temporaries to about one layer's matrix (0.4 GB), where the
    whole stack at once would hold several of 3.2 GB."""
    spec = quant.QuantSpec(64, None, transpose=True)
    leaf = jax.ShapeDtypeStruct((8, 6144, 16384), jnp.bfloat16)
    packed = jax.eval_shape(lambda x: quant.quantize(x, spec), leaf)
    per_layer = quant._pack_into.lower(
        packed, leaf, jax.ShapeDtypeStruct((), jnp.int32), spec).compile()
    whole = jax.jit(lambda x: quant.quantize(x, spec)).lower(leaf).compile()
    temp = per_layer.memory_analysis().temp_size_in_bytes
    assert temp < 2e9
    assert whole.memory_analysis().temp_size_in_bytes > 2 * temp


def test_quantized_tensor_is_a_pytree():
    x = _rand((32, 64))
    qt = quant.quantize(x, quant.QuantSpec(block_m=16, block_n=None))
    leaves, treedef = jax.tree.flatten(qt)
    assert len(leaves) == 2
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert rebuilt.block == qt.block and rebuilt.transposed == qt.transposed
    # jit boundary: passes through as an argument with static aux
    out = jax.jit(lambda q: q.dequantize())(qt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(qt.dequantize()))


@settings(deadline=None, max_examples=12)
@given(
    m=st.integers(min_value=1, max_value=96),
    n=st.integers(min_value=1, max_value=160),
    block_m=st.integers(min_value=1, max_value=64),
    block_n=st.integers(min_value=1, max_value=96),
    bf16=st.integers(min_value=0, max_value=1),
    transpose=st.integers(min_value=0, max_value=1),
)
def test_roundtrip_matvec_within_bound_property(m, n, block_m, block_n, bf16,
                                                transpose):
    """Property sweep: for ANY shape/block/dtype/layout, the quantize ->
    dequantize round trip applied as a matvec stays within the documented
    `matvec_error_bound` of the f32 product.  This is the bound every
    backend's exact-dequant path inherits, so it must hold unconditionally —
    including degenerate 1-sized dims, non-divisible blocks (shrunk to
    divisors) and transposed (output-major) storage."""
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    w = _rand((m, n), dtype, key=jax.random.PRNGKey(m * 1000 + n))
    spec = quant.QuantSpec(block_m=block_m, block_n=block_n,
                           transpose=bool(transpose))
    qt = quant.quantize(w, spec)
    # the bound runs over STORED rows: feed x along the stored column axis
    x = _rand((qt.values.shape[-1],), jnp.float32, key=jax.random.PRNGKey(n))
    w_stored = np.asarray(w, np.float32).T if transpose else np.asarray(w, np.float32)
    deq_stored = np.asarray(qt.dequantize())
    if transpose:
        deq_stored = deq_stored.T
    y_q = deq_stored @ np.asarray(x)
    y_f = w_stored @ np.asarray(x)
    bound = np.asarray(quant.matvec_error_bound(qt, x))
    # bf16 operands add the oracle's own representation error on top of the
    # quantization bound
    slack = 1e-5 if dtype == jnp.float32 else 0.05 * (1 + np.abs(y_f).max())
    assert (np.abs(y_q - y_f) <= bound + slack).all(), (
        (m, n, qt.block, bool(transpose), dtype),
        np.abs(y_q - y_f).max(), bound.min(),
    )


# --------------------------------------------------------------------------
# per-block error bound vs the f32 oracle, across backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemv_within_documented_bound(backend, dtype):
    m, n = 128, 256
    a = _rand((m, n), dtype)
    x = _rand((n,), dtype, key=jax.random.PRNGKey(7))
    qt = quant.quantize(a, quant.QuantSpec(block_m=32, block_n=None))
    with blas.use_backend(backend):
        y = blas.gemv(qt, x)
    f32 = np.asarray(a, np.float32) @ np.asarray(x, np.float32)
    # the host fast path quantizes the activation too; its extra terms are
    # part of the documented bound
    act = quant.activation_scale(x)[None] if backend == "xla" else None
    bound = np.asarray(quant.matvec_error_bound(qt, x, activation_scales=act))
    # bf16 operands add their own representation error on top of the
    # quantization bound (the oracle itself is only bf16-accurate)
    slack = 1e-5 if dtype == jnp.float32 else 0.05
    assert (np.abs(np.asarray(y, np.float32) - f32) <= bound + slack).all()


@pytest.mark.parametrize("backend", ("pallas", "ref"))
def test_gemv_exact_dequant_parity(backend):
    """pallas in-kernel dequant and ref must agree with the dequantization
    oracle to float tolerance (identical math)."""
    m, n = 192, 320
    a = _rand((m, n))
    x = _rand((n,), key=jax.random.PRNGKey(3))
    qt = quant.quantize(a, quant.QuantSpec(block_m=64, block_n=64))
    with blas.use_backend(backend):
        y = blas.gemv(qt, x)
    want = np.asarray(qt.dequantize()) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_decode_matmul_within_bound(backend):
    """The serving decode projection: (B, 1, d) @ quantized (d, f)."""
    d, f, B = 192, 256, 3
    w = _rand((d, f), scale=0.1)
    x = _rand((B, 1, d), key=jax.random.PRNGKey(5))
    qt = quant.quantize(w, quant.QuantSpec(block_m=64, block_n=None, transpose=True))
    with blas.use_backend(backend):
        y = blas.matmul(x, qt)
    assert y.shape == (B, 1, f)
    deq = np.asarray(qt.dequantize())
    want = np.asarray(x).reshape(B, d) @ deq
    got = np.asarray(y).reshape(B, f)
    if backend == "xla":
        # W8A8 host path: bound vs f32 via the activation-aware bound
        for b in range(B):
            xb = x[b, 0]
            bound = np.asarray(quant.matvec_error_bound(
                qt, xb, activation_scales=quant.activation_scale(xb)[None]))
            f32 = np.asarray(x[b, 0]) @ np.asarray(w)
            assert (np.abs(got[b] - f32) <= bound + 1e-5).all()
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_dual_gemv_decode(backend):
    """SwiGLU decode: quantized dual-operand matmul_fused stays one launch
    and matches the dequant oracle through the identical epilogue."""
    d, f, B = 128, 192, 2
    wg = _rand((d, f), scale=0.1)
    wu = _rand((d, f), scale=0.1, key=jax.random.PRNGKey(9))
    x = _rand((B, 1, d), key=jax.random.PRNGKey(11))
    spec = quant.QuantSpec(block_m=64, block_n=None, transpose=True)
    qg, qu = quant.quantize(wg, spec), quant.quantize(wu, spec)
    with blas.use_backend(backend):
        y = blas.matmul_fused(x, qg, w2=qu, activation="silu")
    xg = np.asarray(x).reshape(B, d)
    if backend == "xla":
        h = np.stack([np.asarray(quant.gemv_host(qg, x[b, 0])) for b in range(B)])
        h2 = np.stack([np.asarray(quant.gemv_host(qu, x[b, 0])) for b in range(B)])
    else:
        h = xg @ np.asarray(qg.dequantize())
        h2 = xg @ np.asarray(qu.dequantize())
    want = np.asarray(jax.nn.silu(h)) * h2
    np.testing.assert_allclose(
        np.asarray(y).reshape(B, f), want, rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_prefill_gemm_quantized(backend):
    """Prefill-shaped matmul with a transposed-stored (output-major) packed
    weight: the gemm kernel streams the nk layout without a transpose."""
    d, f = 128, 256
    w = _rand((d, f), scale=0.1)
    x = _rand((2, 8, d), key=jax.random.PRNGKey(13))
    qt = quant.quantize(w, quant.QuantSpec(block_m=64, block_n=None, transpose=True))
    with blas.use_backend(backend):
        y = blas.matmul(x, qt)
    want = np.asarray(x).reshape(-1, d) @ np.asarray(qt.dequantize())
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, f), want, rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_gemm_quantized_experts(backend):
    """MoE expert stacks: batched (E, d, f) packed weights through
    batched_gemm, per-expert block scales, kn layout."""
    E, c, d, f = 3, 8, 64, 128
    h = _rand((E, c, d))
    w = _rand((E, d, f), scale=0.1, key=jax.random.PRNGKey(17))
    qt = quant.quantize(w, quant.QuantSpec(block_m=32, block_n=64))
    with blas.use_backend(backend):
        y = blas.batched_gemm(h, qt)
    want = np.einsum("ecd,edf->ecf", np.asarray(h), np.asarray(qt.dequantize()))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)


def test_quantized_rejects_transpose_flags():
    qt = quant.quantize(_rand((64, 128)), quant.QuantSpec(block_m=32, block_n=None))
    with pytest.raises(ValueError, match="stored layout"):
        blas.gemm(_rand((8, 128)), qt, transpose_b=True)
    with pytest.raises(ValueError, match="stored"):
        blas.gemv(qt, _rand((64,)), trans=True)


def test_dual_gemv_spec_mismatch_raises():
    spec_a = quant.QuantSpec(block_m=32, block_n=None, transpose=True)
    spec_b = quant.QuantSpec(block_m=64, block_n=None, transpose=True)
    qa = quant.quantize(_rand((64, 128)), spec_a)
    qb = quant.quantize(_rand((64, 128)), spec_b)
    with pytest.raises(ValueError, match="share one quantization spec"):
        ops.bgemv(qa, _rand((2, 64)), a2=qb, activation="silu", transpose_a=True)


def test_kernel_tiles_smaller_than_scale_blocks():
    """Coarse scale blocks (the default whole-row serving spec) must NOT
    inflate the kernel block plan: tiles smaller than a scale block divide
    it and share its scale (kernels.gemv.scale_layout).  Regression for the
    VMEM blowup where _align_block forced block_k to the full contraction."""
    from repro.kernels import gemv as _gemv_k
    m, n = 256, 512
    a = _rand((m, n), scale=0.1)
    x = _rand((n,), key=jax.random.PRNGKey(43))
    # one scale block spanning the whole matrix width and 128 rows
    qt = quant.quantize(a, quant.QuantSpec(block_m=128, block_n=None))
    y = _gemv_k.gemv(qt.values, x, scales=qt.scales, q_block=qt.block,
                     block_m=64, block_n=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(qt.dequantize()) @ np.asarray(x),
        rtol=1e-5, atol=1e-4)
    # and through the full matmul path with tiny explicit kernel blocks:
    # the gemm nk-layout stream with whole-axis scale blocks
    w = _rand((128, 256), scale=0.1)
    qw = quant.quantize(w, quant.QuantSpec(block_m=64, block_n=None,
                                           transpose=True))
    xp = _rand((2, 8, 128), key=jax.random.PRNGKey(47))
    with blas.use_backend("pallas"):
        out = blas.matmul(xp, qw)
    want = np.asarray(xp).reshape(-1, 128) @ np.asarray(qw.dequantize())
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 256), want,
                               rtol=1e-4, atol=1e-4)


def test_fit_block_to_quant():
    from repro.kernels.gemv import fit_block_to_quant
    assert fit_block_to_quant(512, 64) == 512     # multiple of q
    assert fit_block_to_quant(500, 64) == 448     # rounded down to multiple
    assert fit_block_to_quant(128, 512) == 128    # divisor of q
    assert fit_block_to_quant(100, 512) == 64     # largest divisor <= block
    assert fit_block_to_quant(1, 7) == 1


# --------------------------------------------------------------------------
# host fast path
# --------------------------------------------------------------------------

def test_host_fast_path_eligibility():
    # per-row-block scales, short contraction: eligible
    q1 = quant.quantize(_rand((64, 128)), quant.QuantSpec(block_m=32, block_n=None))
    assert quant.host_fast_path_eligible(q1)
    # 2-D scale grid: not eligible
    q2 = quant.quantize(_rand((64, 128)), quant.QuantSpec(block_m=32, block_n=64))
    assert not quant.host_fast_path_eligible(q2)
    # contraction past the host int8 cliff: not eligible
    q3 = quant.quantize(
        _rand((8, quant.HOST_FAST_MAX_K + 128)),
        quant.QuantSpec(block_m=8, block_n=None),
    )
    assert not quant.host_fast_path_eligible(q3)


def test_gemv_host_matches_inside_jit():
    """The eager two-dispatch form and the traced fused form are the same
    math (bit-equal quantization, same dot)."""
    qt = quant.quantize(_rand((64, 256)), quant.QuantSpec(block_m=32, block_n=None))
    x = _rand((256,), key=jax.random.PRNGKey(23))
    eager = quant.gemv_host(qt, x)
    traced = jax.jit(quant.gemv_host)(qt, x)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(traced), rtol=1e-6)


# --------------------------------------------------------------------------
# masked tail handling (no ops padding on ragged shapes)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(13, 17), (127, 257), (101, 640)])
def test_gemv_prime_sizes(m, n):
    """Regression: gemv used to hard-assert divisibility; the kernel now
    masks the ragged fringe in-kernel (no caller padding)."""
    a = _rand((m, n))
    x = _rand((n,), key=jax.random.PRNGKey(29))
    with blas.use_backend("pallas"):
        y = blas.gemv(a, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(a) @ np.asarray(x), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("n", [7, 113, 2051])
def test_blas1_prime_sizes(n):
    x = _rand((n,))
    y = _rand((n,), key=jax.random.PRNGKey(31))
    with blas.use_backend("pallas"):
        d = blas.dot(x, y)
        nr = blas.nrm2(x)
        ax = blas.axpy(2.5, x, y)
    np.testing.assert_allclose(float(d), float(jnp.sum(x * y)), rtol=1e-4)
    np.testing.assert_allclose(float(nr), float(jnp.linalg.norm(x)), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ax), np.asarray(2.5 * x + y),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# tiling: packed-width plans + quantized cache keys
# --------------------------------------------------------------------------

def test_autotune_cache_key_quantized_separation():
    base = tiling.autotune_cache_key("gemm", 512, 512, 512, 4, "cpu")
    q = tiling.autotune_cache_key("gemm", 512, 512, 512, 4, "cpu", quantized=True)
    assert base != q and q.endswith(":q1")
    # and the quantized flag composes with the epilogue flags
    qg = tiling.autotune_cache_key("gemm", 512, 512, 512, 4, "cpu",
                                   gate=True, quantized=True)
    assert ":g1r0" in qg and qg.endswith(":q1")


def test_autotune_block_shape_quantized_entry_is_separate(monkeypatch):
    tiling.clear_autotune_cache()
    kw = dict(dtype_bytes=4, backend="cpu")
    full = tiling.autotune_block_shape("gemm", 4096, 4096, 4096, **kw)
    quantized = tiling.autotune_block_shape("gemm", 4096, 4096, 4096,
                                            quantized=True, **kw)
    key_f = tiling.autotune_cache_key("gemm", 4096, 4096, 4096, 4, "cpu")
    key_q = tiling.autotune_cache_key("gemm", 4096, 4096, 4096, 4, "cpu",
                                      quantized=True)
    assert key_f in tiling._autotune_cache and key_q in tiling._autotune_cache
    # the packed plan sees cheaper B tiles: its analytic AI is >= the full
    # plan's at the same budget
    ai_q = (2 * quantized.bm * quantized.bn * quantized.bk) / (
        quantized.bm * quantized.bk * 4 + quantized.bk * quantized.bn * 1
    )
    ai_f = (2 * full.bm * full.bn * full.bk) / (
        (full.bm * full.bk + full.bk * full.bn) * 4
    )
    assert ai_q >= ai_f
    tiling.clear_autotune_cache()


def test_rank_block_shapes_packed_width_grows_feasible_set():
    kw = dict(dtype_bytes=4, vmem_budget=16 * 1024 * 1024)
    full = tiling.rank_block_shapes(8192, 8192, 8192, **kw)
    packed = tiling.rank_block_shapes(8192, 8192, 8192, b_dtype_bytes=1, **kw)
    assert len(packed) >= len(full)
    # the same block is budgeted cheaper at packed width
    blk = full[0]
    mixed = (2 * (blk.bm * blk.bk * 4 + blk.bk * blk.bn * 1)
             + blk.bm * blk.bn * 4 + blk.bm * blk.bn * 4)
    assert mixed < blk.vmem_bytes(4)


def test_mlp_traffic_weight_accounting():
    plain = tiling.mlp_traffic(1, 1024, 4096, dtype_bytes=4, fused=True)
    assert plain.weight_reads == 0  # default: fusion comparison unchanged
    full = tiling.mlp_traffic(1, 1024, 4096, dtype_bytes=4, fused=True,
                              weight_bytes_per_elem=4.0)
    qb = quant.packed_weight_bytes((1024, 4096), (64, None)) / (1024 * 4096)
    packed = tiling.mlp_traffic(1, 1024, 4096, dtype_bytes=4, fused=True,
                                weight_bytes_per_elem=qb)
    assert full.weight_reads == 3 * 1024 * 4096 * 4
    assert full.weight_reads / packed.weight_reads >= 2.0
    assert packed.total_bytes < full.total_bytes


def test_weight_traffic_ratio():
    assert quant.weight_traffic_ratio((4096, 4096), full_bytes_per_elem=4) > 3.9
    assert quant.weight_traffic_ratio((4096, 4096), full_bytes_per_elem=2) > 1.9


def test_roofline_models_packed_weight_bytes():
    """The decode-cell memory term shrinks when cfg.weight_dtype='int8':
    the structural roofline claim behind serve --quantize."""
    import dataclasses
    from repro.configs.base import ShapeCell
    from repro.launch import roofline
    from repro.models.registry import get_config
    cfg = get_config("stablelm-1.6b", "smoke")
    # single-stream short-context decode: the weight read dominates the cell
    cell = ShapeCell("decode_tiny", 32, 1, "decode")
    full = roofline.analytic_hbm_bytes(cfg, cell, chips=1)
    packed = roofline.analytic_hbm_bytes(
        dataclasses.replace(cfg, weight_dtype="int8"), cell, chips=1)
    assert packed < full
    # the saved bytes are EXACTLY the projection params repriced from bf16
    # to packed width — the embedding/unembedding share (which
    # quantize_weights leaves full precision) must NOT be repriced
    p_embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    p_packed = cfg.param_count() - p_embed
    want_saving = p_packed * (2.0 - roofline.WEIGHT_INT8_BYTES)
    assert abs((full - packed) - want_saving) < 1e-6 * full
    # training bytes are untouched (quantized serving is inference-only)
    tr = ShapeCell("train_small", 256, 8, "train")
    assert roofline.analytic_hbm_bytes(cfg, tr, 1) == roofline.analytic_hbm_bytes(
        dataclasses.replace(cfg, weight_dtype="int8"), tr, 1)


# --------------------------------------------------------------------------
# KV-cache quantization frame (per-(token, head) block scales)
# --------------------------------------------------------------------------

def test_quantize_kv_shapes_and_elementwise_bound():
    """quantize_kv is the QuantizedTensor frame at block (1, hd): one scale
    per (token, head), leading (B, T) dims free, round trip within s/2."""
    x = _rand((2, 5, 3, 16), key=jax.random.PRNGKey(3))
    qt = quant.quantize_kv(x)
    assert qt.values.shape == (2, 5, 3, 16) and qt.values.dtype == jnp.int8
    assert qt.scales.shape == (2, 5, 3, 1) and qt.scales.dtype == jnp.float32
    assert qt.block == (1, 16)
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(x, np.float32))
    assert (err <= np.asarray(qt.elementwise_bound()) + 1e-6).all()
    # dequantize_kv is the same math on the raw cache leaves
    np.testing.assert_array_equal(
        np.asarray(quant.dequantize_kv(qt.values, qt.scales)),
        np.asarray(qt.dequantize()),
    )


def test_kv_traffic_ratio_structural():
    # bf16 -> int8 + one f32 scale per hd elements: ~1.9x at hd=64
    assert quant.kv_traffic_ratio(64) > 1.85
    assert quant.kv_traffic_ratio(128) > 1.9
    assert quant.kv_traffic_ratio(64, full_bytes_per_elem=4) > 3.7
    assert quant.packed_kv_bytes(100, 4, 64) == 100 * 4 * 68


@settings(deadline=None, max_examples=8)
@given(
    t=st.integers(min_value=1, max_value=48),
    h=st.integers(min_value=1, max_value=4),
    hd=st.integers(min_value=4, max_value=64),
)
def test_quantize_kv_roundtrip_property(t, h, hd):
    x = _rand((t, h, hd), key=jax.random.PRNGKey(t * 7 + h * 3 + hd))
    qt = quant.quantize_kv(x)
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(x, np.float32))
    scales = np.asarray(qt.scales)                   # (t, h, 1)
    assert (err <= np.broadcast_to(scales / 2, x.shape) + 1e-6).all()


def test_attention_error_bound_is_rigorous_and_finite():
    """The derived softmax-perturbation bound must hold for the exact
    dequant attention vs full precision, and must not be vacuous."""
    from repro.kernels import ref
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand((4, 8, 32), key=ks[0])
    k = _rand((2, 64, 32), key=ks[1])   # GQA: 2 stored heads, 4 query rows
    v = _rand((2, 64, 32), key=ks[2])
    kq, vq = quant.quantize_kv(k), quant.quantize_kv(v)
    got = ref.attention_kv_dequant(q, kq.values, kq.scales, vq.values,
                                   vq.scales, causal=True)
    want = ref.attention(q, jnp.repeat(k, 2, axis=0),
                         jnp.repeat(v, 2, axis=0), causal=True)
    bound = np.asarray(quant.attention_error_bound(
        q, kq.scales, vq.values.astype(jnp.float32) * vq.scales, vq.scales))
    err = np.abs(np.asarray(got) - np.asarray(want, np.float32))
    assert (err <= bound + 1e-5).all(), (err.max(), bound.min())
    assert np.isfinite(bound).all() and (bound > 0).all()


# --------------------------------------------------------------------------
# roofline: the combined weights+KV decode byte model (the measured cell)
# --------------------------------------------------------------------------

def test_decode_byte_terms_combined_composition():
    """Composing weight_dtype=int8 with kv_cache_dtype=int8 must shrink
    EXACTLY the two modeled byte terms it claims — weights at the PR 4
    packed width, KV at 1 + 4/hd B/elem — and their combined total on a
    long-context cell by >= 1.5x vs weights-only (the ISSUE 5 gate)."""
    import dataclasses
    from repro.configs.base import ShapeCell
    from repro.launch import roofline
    from repro.models.registry import get_config
    cfg = get_config("stablelm-1.6b", "full")
    cell = ShapeCell("decode_long", 8192, 64, "decode")
    full = roofline.decode_byte_terms(cfg, cell)
    w_only = roofline.decode_byte_terms(
        dataclasses.replace(cfg, weight_dtype="int8"), cell)
    both = roofline.decode_byte_terms(
        dataclasses.replace(cfg, weight_dtype="int8", kv_cache_dtype="int8"),
        cell)
    # weights term: repriced once, identical whether KV packs or not
    assert both["weights"] == w_only["weights"] < full["weights"]
    # KV term: repriced by exactly the packed ratio, orthogonal to weights
    assert w_only["kv"] == full["kv"]
    want_kv = full["kv"] * roofline.kv_int8_bytes(cfg.hd) / 2.0
    assert abs(both["kv"] - want_kv) < 1e-6 * full["kv"]
    # activations untouched; totals are the sum of their parts
    assert both["act"] == full["act"]
    for terms in (full, w_only, both):
        assert abs(terms["total"]
                   - (terms["weights"] + terms["kv"] + terms["act"])) < 1.0
    assert w_only["total"] / both["total"] >= 1.5
    # analytic_hbm_bytes and the terms helper agree (single source of truth)
    assert roofline.analytic_hbm_bytes(cfg, cell, 1) == full["total"]


# --------------------------------------------------------------------------
# quantize_weights pass over model params
# --------------------------------------------------------------------------

def test_quantize_weights_packs_projections_only():
    from repro.models import layers as L
    key = jax.random.PRNGKey(0)
    params = {
        "embed": {"table": _rand((128, 64))},
        "final_norm": {"scale": jnp.zeros((64,))},
        "layers": {
            "ln1": {"scale": jnp.zeros((2, 64))},
            "attn": L.init_attention(
                key, L.AttnConfig(d_model=64, n_heads=4, n_kv=2, head_dim=16),
                jnp.float32,
            ),
            "ffn": L.init_mlp(key, 64, 128, "swiglu", jnp.float32),
        },
    }
    qp = L.quantize_weights(params)
    assert quant.is_quantized(qp["layers"]["attn"]["wq"])
    assert qp["layers"]["attn"]["wq"].transposed
    assert quant.is_quantized(qp["layers"]["ffn"]["w_gate"])
    # untouched: embeddings, norms
    assert not quant.is_quantized(qp["embed"]["table"])
    assert not quant.is_quantized(qp["final_norm"]["scale"])
    assert not quant.is_quantized(qp["layers"]["ln1"]["scale"])
    # logical shapes preserved (the step functions see the same tree shape)
    assert qp["layers"]["attn"]["wq"].shape == params["layers"]["attn"]["wq"].shape


def test_quantize_weights_moe_expert_rule():
    from repro.configs.base import MoEConfig
    from repro.models import layers as L
    from repro.models import moe
    mcfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=32, n_shared_experts=1)
    params = moe.init_moe(jax.random.PRNGKey(0), 64, mcfg, "swiglu", jnp.float32)
    qp = moe.quantize_weights(params)
    # routed experts: batched (E, d, f) kn layout, NOT transposed
    assert quant.is_quantized(qp["w_gate"]) and not qp["w_gate"].transposed
    assert qp["w_gate"].shape == params["w_gate"].shape
    # router stays f32
    assert not quant.is_quantized(qp["router"])
    # shared experts follow the dense (output-major) rule
    assert quant.is_quantized(qp["shared"]["w_gate"])
    assert qp["shared"]["w_gate"].transposed


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_quantized_layer_forward_close_to_full(backend):
    """A whole dense block forward with packed weights stays close to the
    full-precision forward (random init, moderate scale)."""
    from repro.models import layers as L
    key = jax.random.PRNGKey(0)
    d, ff = 64, 128
    mlp_p = L.init_mlp(key, d, ff, "swiglu", jnp.float32)
    x = _rand((2, 4, d), key=jax.random.PRNGKey(41), scale=0.5)
    with blas.use_backend(backend):
        full = L.mlp(mlp_p, x, "swiglu")
        qmlp = L.quantize_weights({"ffn": mlp_p})["ffn"]
        packed = L.mlp(qmlp, x, "swiglu")
    # int8 block scales keep the MLP output within ~1% of full precision
    denom = np.abs(np.asarray(full)).max() + 1e-6
    rel = np.abs(np.asarray(packed) - np.asarray(full)).max() / denom
    assert rel < 0.05, rel

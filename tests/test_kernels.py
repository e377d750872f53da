"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

F32, BF16 = jnp.float32, jnp.bfloat16


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == BF16 else dict(rtol=2e-4, atol=2e-4)


def _cmp(a, b, dtype):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **_tol(dtype)
    )


# --------------------------------------------------------------------------
# GEMM
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128), (300, 200, 170), (64, 96, 32), (8, 8, 8)])
def test_gemm_sweep(m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(m * k + n), 2)
    a = jax.random.normal(ka, (m, k), F32).astype(dtype)
    b = jax.random.normal(kb, (k, n), F32).astype(dtype)
    _cmp(ops.gemm(a, b, block_m=128, block_n=128, block_k=128), ref.gemm(a, b), dtype)


def test_gemm_block_shape_invariance():
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 384), F32)
    b = jax.random.normal(jax.random.PRNGKey(1), (384, 256), F32)
    out_ref = ref.gemm(a, b)
    for bm, bn, bk in [(64, 64, 64), (128, 256, 128), (256, 128, 384)]:
        _cmp(ops.gemm(a, b, block_m=bm, block_n=bn, block_k=bk), out_ref, F32)


# --------------------------------------------------------------------------
# GEMV / Level-1
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("m,n", [(128, 128), (513, 700), (64, 2048)])
def test_gemv_sweep(m, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(m + n), 2)
    a = jax.random.normal(ka, (m, n), F32).astype(dtype)
    x = jax.random.normal(kb, (n,), F32).astype(dtype)
    _cmp(ops.gemv(a, x), ref.gemv(a, x), dtype)


@pytest.mark.parametrize("n", [128, 1000, 4096])
def test_blas1_sweep(n):
    kx, ky = jax.random.split(jax.random.PRNGKey(n), 2)
    x = jax.random.normal(kx, (n,), F32)
    y = jax.random.normal(ky, (n,), F32)
    _cmp(ops.dot(x, y), ref.dot(x, y), F32)
    _cmp(ops.nrm2(x), ref.nrm2(x), F32)
    _cmp(ops.axpy(1.7, x, y), ref.axpy(1.7, x, y), F32)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 1024), seed=st.integers(0, 2 ** 16))
def test_blas1_property(n, seed):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(kx, (n,), F32)
    y = jax.random.normal(ky, (n,), F32)
    _cmp(ops.dot(x, y), ref.dot(x, y), F32)


# --------------------------------------------------------------------------
# Batched GEMM / GEMV (fused-launch layer)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("batch,m,k,n", [(1, 128, 128, 128), (3, 37, 65, 41), (8, 8, 8, 8)])
def test_bgemm_sweep(batch, m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(batch * m + n), 2)
    a = jax.random.normal(ka, (batch, m, k), F32).astype(dtype)
    b = jax.random.normal(kb, (batch, k, n), F32).astype(dtype)
    _cmp(ops.bgemm(a, b), ref.bgemm(a, b), dtype)


@pytest.mark.parametrize("dtype", [F32, BF16])
def test_bgemm_broadcast_b(dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(5), 2)
    a = jax.random.normal(ka, (4, 33, 129), F32).astype(dtype)
    w = jax.random.normal(kb, (129, 65), F32).astype(dtype)
    _cmp(ops.bgemm(a, w), ref.bgemm(a, w), dtype)


@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("batch,m,n", [(2, 128, 128), (5, 33, 200), (16, 1, 64)])
def test_bgemv_sweep(batch, m, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(batch + m + n), 2)
    a = jax.random.normal(ka, (batch, m, n), F32).astype(dtype)
    x = jax.random.normal(kb, (batch, n), F32).astype(dtype)
    _cmp(ops.bgemv(a, x), ref.bgemv(a, x), dtype)


def test_bgemv_broadcast_a():
    ka, kb = jax.random.split(jax.random.PRNGKey(6), 2)
    a = jax.random.normal(ka, (65, 130), F32)
    x = jax.random.normal(kb, (7, 130), F32)
    _cmp(ops.bgemv(a, x), ref.bgemv(a, x), F32)


def test_bgemm_block_shape_invariance():
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 256, 192), F32)
    b = jax.random.normal(jax.random.PRNGKey(1), (3, 192, 128), F32)
    out_ref = ref.bgemm(a, b)
    for bm, bn, bk in [(64, 64, 64), (128, 128, 192), (256, 128, 64)]:
        _cmp(ops.bgemm(a, b, block_m=bm, block_n=bn, block_k=bk), out_ref, F32)


# --------------------------------------------------------------------------
# Broadcast-A bgemv: the decode kernel, the whole batch in one block
# --------------------------------------------------------------------------

#: (activation, bias, gate, residual) of the broadcast-A sweep
BGEMV_EPILOGUES = [
    (None, False, False, False),
    ("silu", False, True, False),       # the dual-GEMV SwiGLU
    (None, True, False, False),
    (None, False, False, True),
]


def _bgemv_case(layout, m, n, batch, act, use_bias, use_gate, use_res, seed=0):
    """Operands of one broadcast-A bgemv and its f32 numpy oracle.

    `layout` "bf16_t" is the bf16 weight stored (n, m) under transpose_a;
    "int8" the packed output-major weight with one scale per 64 output rows
    across the contraction (layers.quantize_weights' spec), bf16 vectors."""
    from repro.core import quant
    from repro.core.epilogue import make
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = jax.random.normal(ks[0], (n, m), F32) * 0.1
    w2 = jax.random.normal(ks[1], (n, m), F32) * 0.1
    x = jax.random.normal(ks[2], (batch, n), F32).astype(BF16)
    bias = jax.random.normal(ks[3], (m,), F32).astype(BF16) if use_bias else None
    res = (jax.random.normal(ks[4], (batch, m), F32).astype(BF16)
           if use_res else None)
    if layout == "int8":
        spec = quant.QuantSpec(block_m=64, block_n=None, transpose=True)
        a, a2 = quant.quantize(w, spec), quant.quantize(w2, spec)
        wf, w2f = np.asarray(a.dequantize()), np.asarray(a2.dequantize())
    else:
        a, a2 = w.astype(BF16), w2.astype(BF16)
        wf, w2f = np.asarray(a, np.float32), np.asarray(a2, np.float32)
    f = lambda z: None if z is None else np.asarray(z, np.float32)
    xf = f(x)
    epi = make(act, bias=bias, gate=use_gate or None, residual=res)
    want = np.asarray(epi.apply(
        jnp.asarray(xf @ wf), acc2=jnp.asarray(xf @ w2f) if use_gate else None,
        bias=f(bias), residual=f(res)))
    call = dict(a2=a2 if use_gate else None, bias=bias, residual=res,
                activation=act, transpose_a=True)
    return a, x, call, want


def _pallas_grids(fn, *args):
    """Grids of every pallas_call in fn's jaxpr."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("layout", ["bf16_t", "int8"])
@pytest.mark.parametrize("act,use_bias,use_gate,use_res", BGEMV_EPILOGUES)
@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("m,n,blocks", [
    (256, 384, {}),                                    # one tile, planned
    (320, 300, {"block_m": 128, "block_n": 128}),      # ragged m and n fringes
])
def test_bgemv_broadcast_a_decode(layout, act, use_bias, use_gate, use_res,
                                  batch, m, n, blocks):
    """The decode projection (one weight for every slot, the whole batch in
    one block, one MXU product a step) against the f32 oracle: bf16 under
    transpose_a and packed int8 output-major, each epilogue, ragged m and n
    fringes (int8 tiles align to the 64-row scale groups, so its m fringe
    reads past the scale grid and its n tiles divide n)."""
    a, x, call, want = _bgemv_case(layout, m, n, batch, act, use_bias,
                                   use_gate, use_res)
    got = ops.bgemv(a, x, **call, **blocks)
    assert got.shape == (batch, m) and got.dtype == BF16
    _cmp(got, want, BF16)


@pytest.mark.parametrize("layout", ["bf16_t", "int8"])
def test_bgemv_batch_above_block_cap_splits(layout):
    """A batch one row past what one block holds beside the weight tiles
    splits into two batch blocks, each reading the weight once, and still
    matches the oracle (its ragged last block clipped on the write)."""
    from repro.core import tiling
    m, n = 128, 256
    a_dtype = jnp.int8 if layout == "int8" else BF16
    _, bm, bn = tiling.plan_bgemv(1, m, n, a_dtype, BF16, gate=True)
    cap = tiling.bgemv_batch_cap(bm, bn, a_dtype, BF16, gate=True)
    assert cap >= 8
    a, x, call, want = _bgemv_case(layout, m, n, cap + 1, "silu", False,
                                   True, False)
    bb, _, _ = tiling.plan_bgemv(cap + 1, m, n, a_dtype, BF16, gate=True)
    assert bb < cap + 1 <= 2 * bb
    fn = lambda a, x: ops.bgemv(a, x, **call)
    assert _pallas_grids(fn, a, x) == [
        (tiling.cdiv(m, bm), 2, tiling.cdiv(n, bn))]
    _cmp(fn(a, x), want, BF16)


@pytest.mark.parametrize("layout", ["bf16_t", "int8"])
@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("layers", [None, 4])
def test_bgemv_broadcast_grid_reads_each_tile_once(layout, batch, layers):
    """A broadcast weight's grid is (m/bm, n/bn), no batch axis: every
    weight tile is one grid step, so it is read once for the whole batch.
    The same holds for one layer of a layer stack (`layer`), read in place.
    Shapes wide enough for several tiles along both axes."""
    from repro.core import tiling
    m, n = 2 * 1024, 2 * tiling.BGEMV_MAX_BN
    a_dtype = jnp.int8 if layout == "int8" else BF16
    bb, bm, bn = tiling.plan_bgemv(batch, m, n, a_dtype, BF16, gate=True)
    grid = (tiling.cdiv(m, bm), tiling.cdiv(n, bn))
    assert bb == batch and min(grid) >= 2
    shape = jax.ShapeDtypeStruct
    lead = () if layers is None else (layers,)
    if layout == "int8":
        from repro.core import quant
        spec = quant.QuantSpec(block_m=64, block_n=None, transpose=True)
        a = jax.eval_shape(lambda w: quant.quantize(w, spec),
                           shape(lead + (n, m), F32))
    else:
        a = shape(lead + (n, m), BF16)
    layer = None if layers is None else shape((), jnp.int32)
    fn = lambda a, x, i: ops.bgemv(a, x, a2=a, activation="silu",
                                   transpose_a=True, layer=i)
    assert _pallas_grids(fn, a, shape((batch, n), BF16), layer) == [grid]


@pytest.mark.parametrize("layout", ["bf16_t", "int8"])
@pytest.mark.parametrize("act,use_bias,use_gate,use_res", BGEMV_EPILOGUES)
def test_bgemv_reads_layer_from_stack(layout, act, use_bias, use_gate,
                                      use_res):
    """A layer stack and a traced `layer` give exactly the call on that
    layer's weight sliced out (the index maps pick the layer, nothing else
    changes), for bf16 under transpose_a and packed int8 with its scales,
    each epilogue; and the f32 oracle."""
    m, n, batch, layer = 256, 384, 8, 1
    cases = [_bgemv_case(layout, m, n, batch, act, use_bias, use_gate,
                         use_res, seed=s) for s in range(3)]
    stack = lambda ws: jax.tree.map(lambda *w: jnp.stack(w), *ws)
    a = stack([c[0] for c in cases])
    a2 = stack([c[2]["a2"] for c in cases]) if use_gate else None
    _, x, call, want = cases[layer]
    got = jax.jit(lambda a, a2, i: ops.bgemv(a, x, **{**call, "a2": a2},
                                             layer=i))(a, a2, jnp.int32(layer))
    sliced = ops.bgemv(cases[layer][0], x, **call)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(sliced, np.float32))
    _cmp(got, want, BF16)


def test_bgemv_per_member_a_keeps_member_grid():
    """A 3-D A (one matrix per member) has no weight to share: its grid
    keeps one member a step, (m/bm, batch, n/bn)."""
    batch, m, n = 5, 33, 200
    a = jax.ShapeDtypeStruct((batch, m, n), F32)
    x = jax.ShapeDtypeStruct((batch, n), F32)
    assert _pallas_grids(lambda a, x: ops.bgemv(a, x), a, x) == [(1, batch, 1)]


@pytest.mark.parametrize("trans", [False, True])
def test_bgemv_broadcast_a_f64(trans):
    """DGEMV proper: broadcast f64 weights contract at HIGHEST precision and
    accumulate in f64 (interpret mode), ragged fringes included."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(3)
        m, n, batch = 137, 261, 3
        a = rng.standard_normal((n, m) if trans else (m, n))
        x = rng.standard_normal((batch, n))
        got = ops.bgemv(jnp.asarray(a), jnp.asarray(x), transpose_a=trans,
                        block_m=128, block_n=128)
        assert got.dtype == jnp.float64
        want = x @ (a if trans else a.T)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12,
                                   atol=1e-12)


# --------------------------------------------------------------------------
# Flash attention
# --------------------------------------------------------------------------

def _flash_flat(q, k, v, *, kv_groups=1, kv_lens=None, k_scales=None,
                v_scales=None, **kw):
    """Flat (BH, T, D) cases through the kernel's (B, T, H, D) layout: each
    slot holds one stored K/V head and the `kv_groups` query heads that
    share it, so per-row lengths (kv_groups=1) are per-slot lengths."""
    g, slots = kv_groups, k.shape[0]
    to4 = lambda z, h: jnp.moveaxis(z.reshape(slots, h, *z.shape[1:]), 1, 2)
    sc = lambda z: None if z is None else to4(z, 1)
    out = ops.flash_attention(
        to4(q, g), to4(k, 1), to4(v, 1), k_scales=sc(k_scales),
        v_scales=sc(v_scales), kv_lens=None if kv_lens is None else kv_lens[::g],
        kv_groups=g, **kw)
    return jnp.moveaxis(out, 2, 1).reshape(q.shape)

@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("tq,tk,d,causal", [
    (256, 256, 64, True),
    (128, 256, 64, True),    # decode-style: queries at the end of kv
    (1, 256, 64, True),      # single-token decode
    (128, 128, 128, False),
])
def test_flash_attention_sweep(tq, tk, d, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(tq * tk), 3)
    q = jax.random.normal(ks[0], (3, tq, d), F32).astype(dtype)
    k = jax.random.normal(ks[1], (3, tk, d), F32).astype(dtype)
    v = jax.random.normal(ks[2], (3, tk, d), F32).astype(dtype)
    out = _flash_flat(q, k, v, causal=causal, block_q=max(1, min(64, tq)), block_k=64)
    _cmp(out, ref.attention(q, k, v, causal=causal), dtype)


@pytest.mark.parametrize("tq,tk,causal", [
    (128, 100, False),   # non-block-divisible Tk, non-causal: used to trip a
    (100, 100, False),   # bare assert; now masked explicitly in-kernel
    (100, 100, True),    # non-divisible causal: padded keys must not attend
    (1, 100, True),      # decode against a padded kv range
    (60, 200, True),     # uneven q/k padding: offset from REAL lengths
])
def test_flash_attention_padded_lengths(tq, tk, causal):
    """Regression: padded key positions are masked to -inf and the causal
    offset is computed from real (unpadded) lengths."""
    ks = jax.random.split(jax.random.PRNGKey(tq * 31 + tk), 3)
    q = jax.random.normal(ks[0], (2, tq, 64), F32)
    k = jax.random.normal(ks[1], (2, tk, 64), F32)
    v = jax.random.normal(ks[2], (2, tk, 64), F32)
    out = _flash_flat(q, k, v, causal=causal, block_q=64, block_k=64)
    assert np.isfinite(np.asarray(out)).all()
    _cmp(out, ref.attention(q, k, v, causal=causal), F32)


def test_flash_attention_block_invariance():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 64), F32) for kk in ks)
    out_ref = ref.attention(q, k, v, causal=True)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        out = _flash_flat(q, k, v, block_q=bq, block_k=bk)
        _cmp(out, out_ref, F32)


# --------------------------------------------------------------------------
# Int8-KV flash attention (packed tiles, in-kernel dequant, GQA index map)
# --------------------------------------------------------------------------

def _packed_kv(key, bh, tk, d):
    from repro.core import quant
    kk, kv_ = jax.random.split(key)
    k = jax.random.normal(kk, (bh, tk, d), F32)
    v = jax.random.normal(kv_, (bh, tk, d), F32)
    kq, vq = quant.quantize_kv(k), quant.quantize_kv(v)
    return k, v, kq.values, kq.scales, vq.values, vq.scales


@pytest.mark.parametrize("tq,tk,d,causal", [
    (128, 128, 64, True),
    (1, 256, 64, True),     # single-token decode
    (100, 100, 32, True),
    (60, 200, 32, True),    # ragged lengths: padded keys stay masked
    (64, 128, 64, False),
])
def test_flash_attention_int8_kv_matches_dequant_oracle(tq, tk, d, causal):
    """The in-kernel dequant is the SAME math as the exact dequantization
    oracle (values * per-(token, head) scale), so the packed kernel must
    match ref.attention_kv_dequant to float tolerance on every shape."""
    ks = jax.random.split(jax.random.PRNGKey(tq * 131 + tk), 2)
    q = jax.random.normal(ks[0], (3, tq, d), F32)
    _, _, k8, ksc, v8, vsc = _packed_kv(ks[1], 3, tk, d)
    out = _flash_flat(q, k8, v8, k_scales=ksc, v_scales=vsc,
                      causal=causal, block_q=64, block_k=64)
    want = ref.attention_kv_dequant(q, k8, ksc, v8, vsc, causal=causal)
    _cmp(out, want, F32)


def test_flash_attention_int8_kv_within_analytic_bound():
    """vs the FULL-PRECISION oracle the packed kernel's error must stay
    inside core.quant.attention_error_bound — the documented accuracy
    contract of the int8 KV cache."""
    from repro.core import quant
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(ks[0], (4, 32, 64), F32)
    k, v, k8, ksc, v8, vsc = _packed_kv(ks[1], 4, 128, 64)
    out = _flash_flat(q, k8, v8, k_scales=ksc, v_scales=vsc,
                      causal=True, block_q=32, block_k=64)
    want = ref.attention(q, k, v, causal=True)
    v_hat = v8.astype(F32) * vsc
    bound = np.asarray(quant.attention_error_bound(q, ksc, v_hat, vsc))
    err = np.abs(np.asarray(out) - np.asarray(want, np.float32))
    assert (err <= bound + 1e-5).all(), (err.max(), bound.min())
    assert err.max() > 0  # the bound is not trivially satisfied by equality


@pytest.mark.parametrize("quantized", [False, True])
def test_flash_attention_gqa_groups_share_kv(quantized):
    """kv_groups folds GQA head sharing into the kernel index map: the
    result equals attention over the repeat_kv-expanded cache, without the
    kernel ever seeing an expanded operand."""
    B, H, KV, tk, d = 2, 6, 2, 96, 32
    g = H // KV
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    q = jax.random.normal(ks[0], (B * H, 16, d), F32)
    k, v, k8, ksc, v8, vsc = _packed_kv(ks[1], B * KV, tk, d)
    if quantized:
        out = _flash_flat(q, k8, v8, k_scales=ksc, v_scales=vsc,
                          kv_groups=g, causal=True, block_k=64)
        want = ref.attention_kv_dequant(q, k8, ksc, v8, vsc, causal=True)
    else:
        out = _flash_flat(q, k, v, kv_groups=g, causal=True, block_k=64)
        want = ref.attention(q, jnp.repeat(k, g, axis=0),
                             jnp.repeat(v, g, axis=0), causal=True)
    _cmp(out, want, F32)


def test_flash_attention_cache_layout_gqa_lens():
    """The 4-D cache-layout path (no moveaxis/reshape of the cache) with
    GQA groups AND per-slot lens — the exact decode configuration
    layers._flash_cache_attention launches — must match the flat-layout
    dequant oracle.  Guards the query-head -> KV-head fold (hh // g) of the
    kernel's head walk, which no MHA serve config exercises."""
    from repro.core import quant
    B, H, KV, Tq, S, d = 2, 6, 2, 1, 100, 32
    g = H // KV
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q4 = jax.random.normal(ks[0], (B, Tq, H, d), F32)
    k4 = jax.random.normal(ks[1], (B, S, KV, d), F32)
    v4 = jax.random.normal(ks[2], (B, S, KV, d), F32)
    kq, vq = quant.quantize_kv(k4), quant.quantize_kv(v4)
    slot_lens = jnp.asarray([37, 100], jnp.int32)
    out = ops.flash_attention(q4, kq.values, vq.values, k_scales=kq.scales,
                              v_scales=vq.scales, kv_lens=slot_lens,
                              kv_groups=g, causal=True, block_k=64)
    lens = jnp.repeat(slot_lens, H)  # the oracle's per-(slot, head) rows
    assert out.shape == (B, Tq, H, d)
    flat = lambda z: jnp.moveaxis(z, 2, 1).reshape(z.shape[0] * z.shape[2],
                                                   z.shape[1], z.shape[3])
    want = ref.attention_kv_dequant(
        flat(q4), flat(kq.values), flat(kq.scales), flat(vq.values),
        flat(vq.scales), kv_lens=lens, causal=True)
    _cmp(flat(out), want, F32)


@pytest.mark.parametrize("quantized", [False, True])
def test_flash_attention_per_row_kv_lens(quantized):
    """kv_lens makes the real KV length (and the causal offset) a per-grid-
    row value — the continuous-batching ragged slot grid in one launch.
    Lengths cover a first block, a ragged middle, the full range and a
    single visible key."""
    bh, tq, tk, d = 6, 1, 160, 32
    ks = jax.random.split(jax.random.PRNGKey(13), 2)
    q = jax.random.normal(ks[0], (bh, tq, d), F32)
    k, v, k8, ksc, v8, vsc = _packed_kv(ks[1], bh, tk, d)
    lens = jnp.asarray([5, 37, 64, 160, 1, 97], jnp.int32)
    if quantized:
        out = _flash_flat(q, k8, v8, k_scales=ksc, v_scales=vsc,
                          kv_lens=lens, causal=True, block_k=64)
        want = ref.attention_kv_dequant(q, k8, ksc, v8, vsc, kv_lens=lens,
                                        causal=True)
    else:
        out = _flash_flat(q, k, v, kv_lens=lens, causal=True, block_k=64)
        want = ref.attention_lens(q, k, v, lens, causal=True)
    assert np.isfinite(np.asarray(out)).all()
    _cmp(out, want, F32)


# --------------------------------------------------------------------------
# Ragged (prime-size) batched shapes: in-kernel masked tails, no ops padding
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pallas", "xla", "ref"])
@pytest.mark.parametrize("batch,m,n", [(3, 257, 131), (2, 13, 89), (3, 101, 640)])
def test_bgemv_prime_sizes(backend, batch, m, n):
    """Regression: bgemv used to rely on ops-side padding; the kernel now
    masks the ragged contraction fringe in-kernel (cdiv grid) and Pallas
    clips the ragged output rows — every backend agrees on prime shapes."""
    from repro.core import blas
    ka, kb = jax.random.split(jax.random.PRNGKey(batch * m + n), 2)
    a = jax.random.normal(ka, (batch, m, n), F32)
    x = jax.random.normal(kb, (batch, n), F32)
    with blas.use_backend(backend):
        y = blas.batched_gemv(a, x)
    _cmp(y, ref.bgemv(a, x), F32)
    # broadcast weights (the serving case) hit the same masked path
    with blas.use_backend(backend):
        yb = blas.batched_gemv(a[0], x)
    _cmp(yb, ref.bgemv(a[0], x), F32)


@pytest.mark.parametrize("backend", ["pallas", "xla", "ref"])
@pytest.mark.parametrize("batch,m,n,k", [(3, 257, 131, 89), (2, 19, 67, 257)])
def test_bgemm_prime_sizes(backend, batch, m, n, k):
    from repro.core import blas
    ka, kb = jax.random.split(jax.random.PRNGKey(batch + m + n + k), 2)
    a = jax.random.normal(ka, (batch, m, k), F32)
    b = jax.random.normal(kb, (batch, k, n), F32)
    with blas.use_backend(backend):
        y = blas.batched_gemm(a, b)
    _cmp(y, ref.bgemm(a, b), F32)
    with blas.use_backend(backend):
        yb = blas.batched_gemm(a, b[0])
    _cmp(yb, ref.bgemm(a, b[0]), F32)


def test_bgemv_transpose_prime_sizes():
    """The decode projection layout (transpose_a streams W in HBM order)
    masks its swapped contraction axis too."""
    n, m, batch = 131, 257, 3
    ka, kb = jax.random.split(jax.random.PRNGKey(21), 2)
    a = jax.random.normal(ka, (n, m), F32)
    x = jax.random.normal(kb, (batch, n), F32)
    y = ops.bgemv(a, x, transpose_a=True)
    want = jnp.einsum("nm,bn->bm", a, x)
    _cmp(y, want, F32)


def test_bgemm_fused_epilogue_prime_sizes():
    """Ragged fringes must not leak through the fused epilogue either: the
    masked accumulator feeds bias/activation/gate/residual untouched."""
    batch, m, n, k = 2, 19, 131, 89
    ks = jax.random.split(jax.random.PRNGKey(23), 5)
    a = jax.random.normal(ks[0], (batch, m, k), F32)
    b = jax.random.normal(ks[1], (k, n), F32)
    b2 = jax.random.normal(ks[2], (k, n), F32)
    bias = jax.random.normal(ks[3], (n,), F32)
    res = jax.random.normal(ks[4], (batch, m, n), F32)
    out = ops.bgemm(a, b, b2=b2, bias=bias, residual=res, activation="silu")
    h = jnp.einsum("bmk,kn->bmn", a, b) + bias
    want = jax.nn.silu(h) * jnp.einsum("bmk,kn->bmn", a, b2) + res
    _cmp(out, want, F32)


# --------------------------------------------------------------------------
# RWKV6 / Mamba2 scans
# --------------------------------------------------------------------------

@pytest.mark.parametrize("t,chunk", [(64, 16), (96, 32), (100, 32), (32, 32)])
def test_rwkv6_kernel_sweep(t, chunk):
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    bh, kk, vv = 2, 32, 32
    r = jax.random.normal(ks[0], (bh, t, kk), F32) * 0.5
    k = jax.random.normal(ks[1], (bh, t, kk), F32) * 0.5
    v = jax.random.normal(ks[2], (bh, t, vv), F32) * 0.5
    w = -jnp.exp(jax.random.normal(ks[3], (bh, t, kk), F32))
    u = jax.random.normal(ks[4], (bh, kk), F32) * 0.5
    y = ops.rwkv6(r, k, v, w, u, chunk=chunk)
    y_ref, _ = ref.rwkv6(r, k, v, w, u)
    _cmp(y, y_ref, F32)


def test_rwkv6_strong_decay_stability():
    """Exponents must not overflow even with near-total per-step decay."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    bh, t, kk = 2, 64, 16
    r, k, v = (jax.random.normal(ks[i], (bh, t, kk), F32) for i in range(3))
    w = jnp.full((bh, t, kk), -15.0)  # decay ~ 3e-7 per step
    u = jnp.zeros((bh, kk))
    y = ops.rwkv6(r, k, v, w, u, chunk=16)
    y_ref, _ = ref.rwkv6(r, k, v, w, u)
    assert np.isfinite(np.asarray(y)).all()
    _cmp(y, y_ref, F32)


@pytest.mark.parametrize("t,chunk", [(64, 16), (128, 64), (100, 32)])
def test_mamba2_kernel_sweep(t, chunk):
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    bh, p, n = 2, 32, 16
    x = jax.random.normal(ks[0], (bh, t, p), F32) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (bh, t), F32)) * 0.5
    b = jax.random.normal(ks[2], (bh, t, n), F32) * 0.5
    c = jax.random.normal(ks[3], (bh, t, n), F32) * 0.5
    y = ops.mamba2_ssd(x, a, b, c, chunk=chunk)
    y_ref, _ = ref.ssd(x, a, b, c)
    _cmp(y, y_ref, F32)


# --------------------------------------------------------------------------
# Pure-JAX chunked paths must match the kernels (three-way agreement)
# --------------------------------------------------------------------------

def test_wkv6_chunked_jax_matches_kernel_and_ref():
    from repro.models.rwkv import wkv6_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    bh, t, kk = 2, 80, 16
    r, k, v = (jax.random.normal(ks[i], (bh, t, kk), F32) * 0.5 for i in range(3))
    w = -jnp.exp(jax.random.normal(ks[3], (bh, t, kk), F32))
    u = jax.random.normal(ks[4], (bh, kk), F32) * 0.5
    y_jax, s_jax = wkv6_chunked(r, k, v, w, u, chunk=16)
    y_ref, s_ref = ref.rwkv6(r, k, v, w, u)
    _cmp(y_jax, y_ref, F32)
    _cmp(s_jax, s_ref, F32)
    _cmp(ops.rwkv6(r, k, v, w, u, chunk=16), y_ref, F32)


def test_ssd_chunked_jax_matches_kernel_and_ref():
    from repro.models.mamba import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    bh, t, p, n = 2, 96, 16, 8
    x = jax.random.normal(ks[0], (bh, t, p), F32) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (bh, t), F32)) * 0.5
    b = jax.random.normal(ks[2], (bh, t, n), F32) * 0.5
    c = jax.random.normal(ks[3], (bh, t, n), F32) * 0.5
    y_jax, h_jax = ssd_chunked(x, a, b, c, chunk=32)
    y_ref, h_ref = ref.ssd(x, a, b, c)
    _cmp(y_jax, y_ref, F32)
    _cmp(h_jax, h_ref, F32)
    _cmp(ops.mamba2_ssd(x, a, b, c, chunk=32), y_ref, F32)


@pytest.mark.parametrize("dtype", [BF16])
def test_rwkv6_kernel_bf16(dtype):
    """bf16 inputs, f32 state math: the TPU production configuration."""
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    bh, t, kk = 2, 64, 16
    r = (jax.random.normal(ks[0], (bh, t, kk), F32) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (bh, t, kk), F32) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (bh, t, kk), F32) * 0.5).astype(dtype)
    w = -jnp.exp(jax.random.normal(ks[3], (bh, t, kk), F32))
    u = jax.random.normal(ks[4], (bh, kk), F32) * 0.5
    y = ops.rwkv6(r, k, v, w, u, chunk=16)
    y_ref, _ = ref.rwkv6(r, k, v, w, u)
    _cmp(y, y_ref, dtype)


@pytest.mark.parametrize("dtype", [BF16])
def test_mamba2_kernel_bf16(dtype):
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    bh, t, p, n = 2, 64, 16, 8
    x = (jax.random.normal(ks[0], (bh, t, p), F32) * 0.5).astype(dtype)
    a = -jnp.abs(jax.random.normal(ks[1], (bh, t), F32)) * 0.5
    b = (jax.random.normal(ks[2], (bh, t, n), F32) * 0.5).astype(dtype)
    c = (jax.random.normal(ks[3], (bh, t, n), F32) * 0.5).astype(dtype)
    y = ops.mamba2_ssd(x, a, b, c, chunk=16)
    y_ref, _ = ref.ssd(x, a, b, c)
    _cmp(y, y_ref, dtype)

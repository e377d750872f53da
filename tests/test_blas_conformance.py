"""Backend conformance: op x backend x dtype x ragged shapes vs kernels/ref.py.

Every `core.blas` entry point must produce the same numbers (to per-dtype
tolerance) on all three backends, including the fringe sizes (1, 7, 129)
that exercise `tiling.pad_dim_to`, and the alpha/beta/transpose parameter
paths that the per-kernel sweeps do not touch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blas
from repro.kernels import ref

F32, BF16 = jnp.float32, jnp.bfloat16
BACKENDS = ("xla", "pallas", "ref")
DTYPES = (F32, BF16)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == BF16 else dict(rtol=2e-4, atol=2e-4)


def _cmp(got, want, dtype, msg=""):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        err_msg=msg, **_tol(dtype)
    )


def _rand(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, F32).astype(dtype)


def _np(x):
    return np.asarray(x, np.float32)


# --------------------------------------------------------------------------
# Level 1
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n", [1, 7, 129])
def test_level1_conformance(backend, dtype, n):
    x, y = _rand(n, (n,), dtype), _rand(n + 1, (n,), dtype)
    with blas.use_backend(backend):
        got_dot = blas.dot(x, y)
        got_nrm = blas.nrm2(x)
        got_axpy = blas.axpy(1.7, x, y)
    _cmp(got_dot, ref.dot(x, y), dtype, f"dot[{backend}]")
    _cmp(got_nrm, ref.nrm2(x), dtype, f"nrm2[{backend}]")
    _cmp(got_axpy, ref.axpy(1.7, x, y), dtype, f"axpy[{backend}]")


# --------------------------------------------------------------------------
# GEMV: plain + alpha/beta/trans parameter paths
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("m,n", [(1, 1), (7, 129), (129, 7)])
def test_gemv_conformance(backend, dtype, m, n):
    A = _rand(m * 131 + n, (m, n), dtype)
    x = _rand(1, (n,), dtype)
    y = _rand(2, (m,), dtype)
    xt = _rand(3, (m,), dtype)
    with blas.use_backend(backend):
        got = blas.gemv(A, x)
        got_ab = blas.gemv(A, x, y, alpha=0.5, beta=1.5)
        got_t = blas.gemv(A, xt, trans=True)
    _cmp(got, ref.gemv(A, x), dtype, f"gemv[{backend}]")
    _cmp(got_ab, 0.5 * (_np(A) @ _np(x)) + 1.5 * _np(y), dtype, f"gemv-ab[{backend}]")
    _cmp(got_t, ref.gemv(A.T, xt), dtype, f"gemv-t[{backend}]")


# --------------------------------------------------------------------------
# GEMM: plain + alpha/beta/transpose parameter paths
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("m,k,n", [(1, 1, 1), (7, 129, 5), (129, 7, 33)])
def test_gemm_conformance(backend, dtype, m, k, n):
    A = _rand(m + k + n, (m, k), dtype)
    B = _rand(4, (k, n), dtype)
    C = _rand(5, (m, n), dtype)
    with blas.use_backend(backend):
        got = blas.gemm(A, B)
        got_ab = blas.gemm(A, B, C, alpha=0.5, beta=1.5)
        got_t = blas.gemm(A.T, B.T, transpose_a=True, transpose_b=True)
    _cmp(got, ref.gemm(A, B), dtype, f"gemm[{backend}]")
    _cmp(got_ab, 0.5 * (_np(A) @ _np(B)) + 1.5 * _np(C), dtype, f"gemm-ab[{backend}]")
    _cmp(got_t, ref.gemm(A, B), dtype, f"gemm-t[{backend}]")


# --------------------------------------------------------------------------
# Batched GEMM: batched-B and broadcast-B, transposes, alpha/beta
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("batch,m,k,n", [(1, 1, 1, 1), (3, 7, 129, 5), (2, 129, 7, 33)])
def test_batched_gemm_conformance(backend, dtype, batch, m, k, n):
    A = _rand(batch + m, (batch, m, k), dtype)
    B = _rand(6, (batch, k, n), dtype)
    W = _rand(7, (k, n), dtype)
    C = _rand(8, (batch, m, n), dtype)
    with blas.use_backend(backend):
        got = blas.batched_gemm(A, B)
        got_bc = blas.batched_gemm(A, W)
        got_ab = blas.batched_gemm(A, B, C, alpha=0.5, beta=1.5)
        got_t = blas.batched_gemm(
            jnp.swapaxes(A, 1, 2), jnp.swapaxes(B, 1, 2),
            transpose_a=True, transpose_b=True,
        )
    want = ref.bgemm(A, B)
    _cmp(got, want, dtype, f"bgemm[{backend}]")
    _cmp(got_bc, ref.bgemm(A, W), dtype, f"bgemm-bcast[{backend}]")
    _cmp(got_ab, 0.5 * _np(want) + 1.5 * _np(C), dtype, f"bgemm-ab[{backend}]")
    _cmp(got_t, want, dtype, f"bgemm-t[{backend}]")


# --------------------------------------------------------------------------
# Batched GEMV: batched-A and broadcast-A, trans, alpha/beta
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("batch,m,n", [(1, 1, 1), (3, 7, 129), (2, 129, 7)])
def test_batched_gemv_conformance(backend, dtype, batch, m, n):
    A = _rand(batch * 17 + m, (batch, m, n), dtype)
    W = _rand(9, (m, n), dtype)
    x = _rand(10, (batch, n), dtype)
    y = _rand(11, (batch, m), dtype)
    with blas.use_backend(backend):
        got = blas.batched_gemv(A, x)
        got_bc = blas.batched_gemv(W, x)
        got_ab = blas.batched_gemv(A, x, y, alpha=0.5, beta=1.5)
        got_t = blas.batched_gemv(jnp.swapaxes(A, 1, 2), x, trans=True)
    want = ref.bgemv(A, x)
    _cmp(got, want, dtype, f"bgemv[{backend}]")
    _cmp(got_bc, ref.bgemv(W, x), dtype, f"bgemv-bcast[{backend}]")
    _cmp(got_ab, 0.5 * _np(want) + 1.5 * _np(y), dtype, f"bgemv-ab[{backend}]")
    _cmp(got_t, want, dtype, f"bgemv-t[{backend}]")


# --------------------------------------------------------------------------
# f64: the paper's D-prefix routines must accumulate in double precision
# (regression: kernels hard-cast operands/accumulators to f32)
# --------------------------------------------------------------------------

def _f64(seed, shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape))


def _cancel(n):
    """f32 accumulation collapses 1e9 + 1 - 1e9 to 0; f64 keeps the 1."""
    v = np.zeros(n)
    v[0], v[1], v[2] = 1e9, 1.0, -1e9
    return v


@pytest.mark.parametrize("backend", BACKENDS)
def test_level1_f64_accumulation(backend):
    with jax.enable_x64(True):
        x, y = _f64(0, (131,)), _f64(1, (131,))
        cx = jnp.asarray(_cancel(131))
        with blas.use_backend(backend):
            got_dot = blas.dot(x, y)
            got_nrm = blas.nrm2(x)
            got_axpy = blas.axpy(1.7, x, y)
            got_cancel = blas.dot(cx, jnp.ones(131))
        for got in (got_dot, got_nrm, got_axpy):
            assert got.dtype == jnp.float64, backend
        np.testing.assert_allclose(np.asarray(got_dot), np.asarray(x) @ np.asarray(y),
                                   rtol=1e-12, err_msg=f"dot[{backend}]")
        np.testing.assert_allclose(np.asarray(got_nrm), np.linalg.norm(np.asarray(x)),
                                   rtol=1e-12, err_msg=f"nrm2[{backend}]")
        np.testing.assert_allclose(np.asarray(got_axpy), 1.7 * np.asarray(x) + np.asarray(y),
                                   rtol=1e-12, err_msg=f"axpy[{backend}]")
        # f32 accumulation would be off by O(100) here, not O(1e-7)
        np.testing.assert_allclose(float(got_cancel), 1.0, atol=1e-3,
                                   err_msg=f"dot-cancel[{backend}]")


@pytest.mark.parametrize("backend", BACKENDS)
def test_level23_f64_accumulation(backend):
    with jax.enable_x64(True):
        A = _f64(2, (7, 131))
        B = _f64(3, (131, 9))
        Ab = _f64(4, (3, 7, 131))
        xv = _f64(5, (131,))
        xb = _f64(6, (3, 131))
        Ac = np.random.default_rng(7).standard_normal((7, 131))
        Ac[0, :3] = (1e9, 1.0, -1e9)
        Ac[0, 3:] = 0.0
        Ac = jnp.asarray(Ac)
        with blas.use_backend(backend):
            got_gemv = blas.gemv(A, xv)
            got_gemm = blas.gemm(A, B)
            got_bgemm = blas.batched_gemm(Ab, B)
            got_bgemv = blas.batched_gemv(Ab, xb)
            got_cancel = blas.gemv(Ac, jnp.ones(131))
        nA, nB, nAb = np.asarray(A), np.asarray(B), np.asarray(Ab)
        for got in (got_gemv, got_gemm, got_bgemm, got_bgemv):
            assert got.dtype == jnp.float64, backend
        np.testing.assert_allclose(np.asarray(got_gemv), nA @ np.asarray(xv),
                                   rtol=1e-12, err_msg=f"gemv[{backend}]")
        np.testing.assert_allclose(np.asarray(got_gemm), nA @ nB,
                                   rtol=1e-12, err_msg=f"gemm[{backend}]")
        np.testing.assert_allclose(np.asarray(got_bgemm), nAb @ nB,
                                   rtol=1e-12, err_msg=f"bgemm[{backend}]")
        np.testing.assert_allclose(np.asarray(got_bgemv),
                                   np.einsum("bmn,bn->bm", nAb, np.asarray(xb)),
                                   rtol=1e-12, err_msg=f"bgemv[{backend}]")
        np.testing.assert_allclose(float(np.asarray(got_cancel)[0]), 1.0, atol=1e-3,
                                   err_msg=f"gemv-cancel[{backend}]")


# --------------------------------------------------------------------------
# ref backend must actually dispatch to the kernels/ref.py oracles
# (regression: dot/nrm2/axpy/gemv only branched on pallas-vs-default, so
# backend="ref" silently ran the XLA path)
# --------------------------------------------------------------------------

def test_level1_ref_backend_dispatches_to_oracles(monkeypatch):
    calls = []

    def _spy(name):
        real = getattr(ref, name)

        def wrapper(*a, **kw):
            calls.append(name)
            return real(*a, **kw)

        return wrapper

    for name in ("dot", "nrm2", "axpy", "gemv"):
        monkeypatch.setattr(ref, name, _spy(name))
    x, y = _rand(0, (16,), F32), _rand(1, (16,), F32)
    A = _rand(2, (8, 16), F32)
    with blas.use_backend("ref"):
        blas.dot(x, y)
        blas.nrm2(x)
        blas.axpy(0.5, x, y)
        blas.gemv(A, x)
    assert calls == ["dot", "nrm2", "axpy", "gemv"], calls
    # ...and the default backend must NOT touch the oracles
    calls.clear()
    blas.dot(x, y)
    blas.gemv(A, x)
    assert calls == [], calls


def test_bgemm_plans_blocks_for_operand_width(monkeypatch):
    """ops.bgemm's default block plan must see the real operand width —
    an f64 tile may not be budgeted as if it were bf16 (regression: the
    plan call omitted dtype_bytes, so every dtype planned at 2 bytes).
    Block defaults now route through the autotune cache front-end."""
    from repro.core import tiling
    from repro.kernels import ops

    seen = []
    real = tiling.autotune_block_shape

    def spy(*a, **kw):
        seen.append(kw.get("dtype_bytes"))
        return real(*a, **kw)

    monkeypatch.setattr(tiling, "autotune_block_shape", spy)
    with jax.enable_x64(True):
        ops.bgemm(jnp.ones((2, 9, 130), jnp.float64), jnp.ones((130, 5), jnp.float64))
    assert seen and seen[-1] == 8, seen


def test_gemm_gemv_block_defaults_use_planner(monkeypatch):
    """ops.gemm/ops.gemv defaults must come from the tiling planner at the
    real operand width (regression: they hardcoded 256/512 blocks and
    ignored the planner ops.bgemm already used)."""
    from repro.core import tiling
    from repro.kernels import ops

    seen = []
    real = tiling.autotune_block_shape

    def spy(op, *a, **kw):
        seen.append((op, kw.get("dtype_bytes")))
        return real(op, *a, **kw)

    monkeypatch.setattr(tiling, "autotune_block_shape", spy)
    ops.gemm(jnp.ones((9, 130), jnp.float32), jnp.ones((130, 5), jnp.float32))
    ops.gemv(jnp.ones((9, 130), jnp.float32), jnp.ones((130,), jnp.float32))
    assert ("gemm", 4) in seen and ("gemv", 4) in seen, seen


def test_shape_mismatch_raises_not_pads():
    """Padding must not silently absorb a contraction-dim mismatch."""
    from repro.kernels import ops

    with pytest.raises(ValueError, match="bgemm shape mismatch"):
        ops.bgemm(jnp.ones((2, 4, 8)), jnp.ones((2, 9, 5)))
    with pytest.raises(ValueError, match="bgemv shape mismatch"):
        ops.bgemv(jnp.ones((2, 4, 8)), jnp.ones((3, 8)))
    with pytest.raises(ValueError, match="gemm shape mismatch"):
        ops.gemm(jnp.ones((4, 8)), jnp.ones((9, 5)))
    with pytest.raises(ValueError, match="gemv shape mismatch"):
        ops.gemv(jnp.ones((4, 8)), jnp.ones((9,)))


# --------------------------------------------------------------------------
# matmul routing: leading batch dims keep their structure under pallas
# --------------------------------------------------------------------------

def test_matmul_3d_routes_through_bgemm_broadcast(monkeypatch):
    """blas.matmul on 3-D+ inputs must dispatch to ops.bgemm with a 2-D
    (broadcast) weight — not reshape-flatten the batch into one GEMM."""
    from repro.kernels import ops

    calls = []
    real_bgemm = ops.bgemm

    def spy(a, b, **kw):
        calls.append((a.shape, b.shape))
        return real_bgemm(a, b, **kw)

    monkeypatch.setattr(ops, "bgemm", spy)
    x = _rand(0, (4, 7, 33), F32)
    w = _rand(1, (33, 11), F32)
    with blas.use_backend("pallas"):
        out = blas.matmul(x, w)
    assert calls == [((4, 7, 33), (33, 11))], calls  # 2-D b == broadcast-B
    _cmp(out, _np(x) @ _np(w), F32)

    # 4-D input: leading dims fold into the batch axis, still broadcast-B
    calls.clear()
    x4 = _rand(2, (2, 3, 5, 33), F32)
    with blas.use_backend("pallas"):
        out4 = blas.matmul(x4, w)
    assert calls == [((6, 5, 33), (33, 11))], calls
    _cmp(out4, _np(x4) @ _np(w), F32)


def test_matmul_decode_routes_through_bgemv(monkeypatch):
    """Decode-shaped (B, 1, d) matmuls must dispatch to ops.bgemv with
    broadcast weights in their HBM layout + transpose_a=True (the
    batched-decode serving path; regression: it materialized w.T on every
    decode step)."""
    from repro.kernels import ops

    calls = []
    real_bgemv = ops.bgemv

    def spy(a, x, **kw):
        calls.append((a.shape, x.shape, kw.get("transpose_a", False)))
        return real_bgemv(a, x, **kw)

    monkeypatch.setattr(ops, "bgemv", spy)
    x = _rand(0, (4, 1, 33), F32)
    w = _rand(1, (33, 11), F32)
    with blas.use_backend("pallas"):
        out = blas.matmul(x, w)
    # 2-D a == broadcast-A, passed UNtransposed with transpose_a pushed down
    assert calls == [((33, 11), (4, 33), True)], calls
    _cmp(out, _np(x) @ _np(w), F32)

"""Epilogue fusion + autotuner coverage.

Parity: every fused (op x epilogue x dtype x backend) combination must match
the unfused oracle — the op's plain result pushed through
`core.epilogue.Epilogue.apply` in accumulator precision (f32, or f64 under
enable_x64 for the paper's D-prefix routines).  The fused pallas kernels run
in interpret mode on this CPU-only container, so the kernel bodies are
executed bit-faithfully.

Autotuner: `tiling.autotune_block_shape` must (a) return the analytic
`choose_block_shape` answer when measurement is off, (b) measure the top-K
shortlist exactly once per key and serve hits from the process cache,
(c) persist winners to the on-disk JSON and reload them in a fresh process
cache, and (d) key on (op, shape, dtype, backend) so changing any of them
re-tunes.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blas, tiling
from repro.core.epilogue import ACTIVATIONS, Epilogue, as_epilogue, make
from repro.kernels import ops

F32, BF16 = jnp.float32, jnp.bfloat16
BACKENDS = ("xla", "pallas", "ref")

#: (activation, use bias, use gate, use residual) — the epilogue sweep
EPILOGUES = [
    ("silu", False, False, False),
    ("gelu", True, False, False),
    ("relu", False, False, True),
    ("silu", False, True, False),      # dual-GEMM SwiGLU
    ("silu", True, True, True),        # everything at once
    (None, True, False, True),         # bias + residual, no activation
]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == BF16 else dict(rtol=2e-4, atol=2e-4)


def _cmp(got, want, dtype, msg=""):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        err_msg=msg, **_tol(dtype)
    )


def _rand(seed, shape, dtype=F32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, F32).astype(dtype)


def _oracle(epi: Epilogue, h, h2=None, bias=None, residual=None):
    """Unfused oracle: f32 matmul results through the shared epilogue
    semantic (the same `apply` the kernels call on VMEM tiles)."""
    return np.asarray(
        epi.apply(
            jnp.asarray(h, jnp.float32),
            acc2=None if h2 is None else jnp.asarray(h2, jnp.float32),
            bias=None if bias is None else jnp.asarray(bias, jnp.float32),
            residual=None if residual is None else jnp.asarray(residual, jnp.float32),
        )
    )


# --------------------------------------------------------------------------
# blas.gemm(..., epilogue=) conformance: op x epilogue x dtype x backend
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", (F32, BF16))
@pytest.mark.parametrize("act,use_bias,use_gate,use_res", EPILOGUES)
def test_gemm_epilogue_conformance(backend, dtype, act, use_bias, use_gate, use_res):
    m, k, n = 7, 129, 33
    A, B = _rand(0, (m, k), dtype), _rand(1, (k, n), dtype)
    B2 = _rand(2, (k, n), dtype) if use_gate else None
    bias = _rand(3, (n,), dtype) if use_bias else None
    res = _rand(4, (m, n), dtype) if use_res else None
    epi = make(act, bias=bias, gate=B2, residual=res)
    with blas.use_backend(backend):
        got = blas.gemm(A, B, B2=B2, bias=bias, residual=res, epilogue=epi)
    f = np.float32
    want = _oracle(epi, f(A) @ f(B), None if B2 is None else f(A) @ f(B2), bias, res)
    _cmp(got, want, dtype, f"gemm-epi[{backend},{act},{use_bias},{use_gate},{use_res}]")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", (F32, BF16))
@pytest.mark.parametrize("b_broadcast", (False, True))
@pytest.mark.parametrize("act,use_bias,use_gate,use_res", EPILOGUES[:5])
def test_bgemm_epilogue_conformance(backend, dtype, b_broadcast, act, use_bias,
                                    use_gate, use_res):
    batch, m, k, n = 3, 7, 65, 33
    A = _rand(0, (batch, m, k), dtype)
    bshape = (k, n) if b_broadcast else (batch, k, n)
    B = _rand(1, bshape, dtype)
    B2 = _rand(2, bshape, dtype) if use_gate else None
    bias = _rand(3, (n,), dtype) if use_bias else None
    res = _rand(4, (batch, m, n), dtype) if use_res else None
    epi = make(act, bias=bias, gate=B2, residual=res)
    with blas.use_backend(backend):
        got = blas.batched_gemm(A, B, B2=B2, bias=bias, residual=res, epilogue=epi)
    f = np.float32
    want = _oracle(epi, f(A) @ f(B), None if B2 is None else f(A) @ f(B2), bias, res)
    _cmp(got, want, dtype, f"bgemm-epi[{backend},{b_broadcast},{act}]")


@pytest.mark.parametrize("dtype", (F32, BF16))
@pytest.mark.parametrize("transpose_a", (False, True))
@pytest.mark.parametrize("a_batched", (False, True))
@pytest.mark.parametrize("act,use_bias,use_gate,use_res", EPILOGUES[:5])
def test_bgemv_epilogue_sweep(dtype, transpose_a, a_batched, act, use_bias,
                              use_gate, use_res):
    """ops.bgemv fused epilogues across layouts (broadcast/batched A, both
    orientations) vs the unfused oracle; pallas interpret kernel bodies."""
    batch, m, n = 4, 33, 129
    ashape = ((n, m) if transpose_a else (m, n))
    if a_batched:
        ashape = (batch,) + ashape
    A = _rand(0, ashape, dtype)
    A2 = _rand(1, ashape, dtype) if use_gate else None
    x = _rand(2, (batch, n), dtype)
    bias = _rand(3, (m,), dtype) if use_bias else None
    res = _rand(4, (batch, m), dtype) if use_res else None
    epi = make(act, bias=bias, gate=A2, residual=res)
    got = ops.bgemv(A, x, a2=A2, bias=bias, residual=res, activation=act,
                    transpose_a=transpose_a)
    f = np.float32
    Am = f(A) if a_batched else f(A)[None]
    A2m = None if A2 is None else (f(A2) if a_batched else f(A2)[None])
    op = (lambda M: np.swapaxes(M, -2, -1)) if transpose_a else (lambda M: M)
    h = np.einsum("bmn,bn->bm", op(Am), f(x))
    h2 = None if A2m is None else np.einsum("bmn,bn->bm", op(A2m), f(x))
    want = _oracle(epi, h, h2, bias, res)
    _cmp(got, want, dtype, f"bgemv-epi[{transpose_a},{a_batched},{act}]")


# --------------------------------------------------------------------------
# f64: fused epilogues keep double-precision accumulation (D-prefix proper)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_epilogue_f64(backend):
    with jax.enable_x64(True):
        rng = np.random.default_rng(0)
        A = jnp.asarray(rng.standard_normal((7, 131)))
        B = jnp.asarray(rng.standard_normal((131, 9)))
        B2 = jnp.asarray(rng.standard_normal((131, 9)))
        bias = jnp.asarray(rng.standard_normal((9,)))
        with blas.use_backend(backend):
            got = blas.gemm(A, B, B2=B2, bias=bias, epilogue="silu")
        assert got.dtype == jnp.float64, backend
        z = np.asarray(A) @ np.asarray(B) + np.asarray(bias)
        want = (z / (1.0 + np.exp(-z))) * (np.asarray(A) @ np.asarray(B2))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-10,
                                   err_msg=backend)


# --------------------------------------------------------------------------
# matmul_fused: the model-layer entry point, all routings x backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("shape", [(7, 33), (2, 5, 33), (4, 1, 33)])
def test_matmul_fused_swiglu_parity(backend, shape):
    """Fused SwiGLU (gemm / bgemm / decode-bgemv routing) must match the
    unfused three-op chain on every backend."""
    x = _rand(0, shape, F32)
    wg, wu = _rand(1, (33, 65), F32), _rand(2, (33, 65), F32)
    with blas.use_backend(backend):
        got = blas.matmul_fused(x, wg, w2=wu, activation="silu")
        gate = jax.nn.silu(blas.matmul(x, wg).astype(jnp.float32))
        up = blas.matmul(x, wu).astype(jnp.float32)
        want = (gate * up).astype(x.dtype)
    _cmp(got, want, F32, f"matmul_fused[{backend},{shape}]")


@pytest.mark.parametrize("backend", BACKENDS)
def test_matmul_fused_bias_residual(backend):
    x = _rand(0, (2, 5, 33), BF16)
    w = _rand(1, (33, 65), BF16)
    bias = _rand(2, (65,), BF16)
    res = _rand(3, (2, 5, 65), BF16)
    with blas.use_backend(backend):
        got = blas.matmul_fused(x, w, bias=bias, activation="gelu", residual=res)
    f = np.float32
    want = _oracle(Epilogue("gelu", bias=True, residual=True),
                   f(x).reshape(10, 33) @ f(w), None, bias, f(res).reshape(10, 65))
    _cmp(got, want.reshape(2, 5, 65), BF16, f"matmul_fused-bias-res[{backend}]")


def test_matmul_fused_decode_routes_one_launch(monkeypatch):
    """Decode-shaped fused SwiGLU must be ONE bgemv launch carrying both
    weight operands (the dual-GEMV), not two launches + elementwise."""
    calls = []
    real = ops.bgemv

    def spy(a, x, **kw):
        calls.append((a.shape, kw.get("a2") is not None, kw.get("transpose_a")))
        return real(a, x, **kw)

    monkeypatch.setattr(ops, "bgemv", spy)
    x = _rand(0, (4, 1, 33), F32)
    wg, wu = _rand(1, (33, 65), F32), _rand(2, (33, 65), F32)
    with blas.use_backend("pallas"):
        blas.matmul_fused(x, wg, w2=wu, activation="silu")
    assert calls == [((33, 65), True, True)], calls


def test_epilogue_rejects_alpha_beta_combo():
    A, B, C = _rand(0, (8, 8)), _rand(1, (8, 8)), _rand(2, (8, 8))
    with pytest.raises(ValueError, match="alpha/beta"):
        blas.gemm(A, B, C, beta=1.0, epilogue="silu")
    with pytest.raises(ValueError, match="alpha/beta"):
        blas.batched_gemm(A[None], B, alpha=2.0, epilogue="relu")


def test_epilogue_spec_coercion():
    assert as_epilogue(None).is_identity
    assert as_epilogue("silu") == Epilogue(activation="silu")
    assert as_epilogue(Epilogue("gelu", bias=True)).bias
    with pytest.raises(ValueError, match="activation"):
        Epilogue(activation="tanh")
    with pytest.raises(TypeError):
        as_epilogue(42)
    assert set(ACTIVATIONS) == {"silu", "gelu", "relu"}


# --------------------------------------------------------------------------
# Autotuner: cache hits, persistence, invalidation
# --------------------------------------------------------------------------

@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv(tiling.AUTOTUNE_CACHE_ENV, str(cache))
    monkeypatch.setenv(tiling.AUTOTUNE_ENV, "1")
    tiling.clear_autotune_cache()
    yield cache
    tiling.clear_autotune_cache()


def test_autotune_disabled_matches_analytic(tmp_path, monkeypatch):
    monkeypatch.setenv(tiling.AUTOTUNE_CACHE_ENV, str(tmp_path / "c.json"))
    monkeypatch.setenv(tiling.AUTOTUNE_ENV, "0")
    tiling.clear_autotune_cache()
    calls = []
    got = tiling.autotune_block_shape(
        "gemm", 4096, 4096, 4096, dtype_bytes=2, backend="cpu",
        bench_fn=lambda blk: calls.append(blk) or 1.0,
    )
    assert calls == [], "bench ran with tuning disabled"
    assert got == tiling.choose_block_shape(4096, 4096, 4096)
    tiling.clear_autotune_cache()


def test_autotune_measures_once_and_caches(tune_env):
    short = tiling.rank_block_shapes(512, 512, 512, dtype_bytes=4, top_k=4)
    assert short[0] == tiling.choose_block_shape(512, 512, 512, dtype_bytes=4)
    calls = []

    def bench(blk):  # pretend the LAST-ranked candidate wins empirically
        calls.append(blk)
        return 0.5 if blk == short[-1] else 1.0

    b1 = tiling.autotune_block_shape("gemm", 512, 512, 512, dtype_bytes=4,
                                     backend="cpu", bench_fn=bench, top_k=4)
    assert b1 == short[-1] != short[0], "measured winner must beat analytic"
    assert calls == short, "shortlist must be measured in rank order"
    b2 = tiling.autotune_block_shape("gemm", 512, 512, 512, dtype_bytes=4,
                                     backend="cpu", bench_fn=bench, top_k=4)
    assert b2 == b1 and len(calls) == 4, "second call must hit the cache"


def test_autotune_disk_persistence_and_reload(tune_env):
    bench = lambda blk: float(blk.bm)  # smallest row block "wins"
    b1 = tiling.autotune_block_shape("bgemm", 512, 512, 512, dtype_bytes=2,
                                     backend="cpu", bench_fn=bench, top_k=4)
    data = json.loads(tune_env.read_text())
    [key] = data.keys()
    assert key == tiling.autotune_cache_key("bgemm", 512, 512, 512, 2, "cpu")
    assert data[key]["source"] == "measured"
    # fresh process cache: the winner must come back from disk, no re-bench
    tiling.clear_autotune_cache()
    boom = lambda blk: pytest.fail("re-benchmarked despite disk cache")
    b2 = tiling.autotune_block_shape("bgemm", 512, 512, 512, dtype_bytes=2,
                                     backend="cpu", bench_fn=boom, top_k=4)
    assert b2 == b1


def test_autotune_key_invalidation(tune_env):
    counts = {"n": 0}

    def bench(blk):
        counts["n"] += 1
        return 1.0

    base = dict(dtype_bytes=2, backend="cpu", bench_fn=bench, top_k=2)
    tiling.autotune_block_shape("gemm", 512, 512, 512, **base)
    n1 = counts["n"]
    # every key component change must re-tune...
    tiling.autotune_block_shape("bgemm", 512, 512, 512, **base)
    tiling.autotune_block_shape("gemm", 1024, 512, 512, **base)
    tiling.autotune_block_shape("gemm", 512, 512, 512, dtype_bytes=4,
                                backend="cpu", bench_fn=bench, top_k=2)
    tiling.autotune_block_shape("gemm", 512, 512, 512, dtype_bytes=2,
                                backend="tpu", bench_fn=bench, top_k=2)
    assert counts["n"] == 5 * n1  # 5 distinct keys, each measured once
    # ...and the exact same key must not
    tiling.autotune_block_shape("gemm", 512, 512, 512, **base)
    assert counts["n"] == 5 * n1


def test_autotune_upgrades_analytic_entry(tune_env, monkeypatch):
    """An analytic cache entry (recorded while tuning was off) must stay off
    disk — analytic picks are recomputable, persisting them would freeze the
    heuristic — and must be re-tuned the first time measurement is
    available."""
    monkeypatch.setenv(tiling.AUTOTUNE_ENV, "0")
    a = tiling.autotune_block_shape("gemm", 512, 512, 512, dtype_bytes=2,
                                    backend="cpu")
    assert not tune_env.exists(), "analytic entries must not touch disk"
    monkeypatch.setenv(tiling.AUTOTUNE_ENV, "1")
    short = tiling.rank_block_shapes(512, 512, 512, dtype_bytes=2, top_k=4)
    bench = lambda blk: 0.0 if blk == short[-1] else 1.0
    b = tiling.autotune_block_shape("gemm", 512, 512, 512, dtype_bytes=2,
                                    backend="cpu", bench_fn=bench, top_k=4)
    assert b == short[-1] and b != a or short[-1] == a
    data = json.loads(tune_env.read_text())
    assert data and all(e["source"] == "measured" for e in data.values())


def test_autotune_fused_variant_keys_and_budget(tune_env):
    """A fused dual-GEMM (gate) variant must (a) key its cache entries
    separately from the unfused op and (b) have the gate operand's double
    buffer + second accumulator charged against the VMEM budget, so the
    fused plan can never claim the VMEM headroom the plain plan maxed out."""
    kwa = dict(dtype_bytes=2, backend="cpu")
    plain = tiling.autotune_block_shape("gemm", 8192, 8192, 8192, **kwa)
    fused = tiling.autotune_block_shape("gemm", 8192, 8192, 8192, gate=True,
                                        residual=True, **kwa)
    extra = tiling.epilogue_vmem_bytes(fused, 2, gate=True, residual=True)
    assert fused.vmem_bytes(2) + extra <= tiling.DEFAULT_VMEM_BUDGET
    # the plain winner saturates the budget, so charging the epilogue must
    # have shrunk the fused block
    assert plain.vmem_bytes(2) + tiling.epilogue_vmem_bytes(
        plain, 2, gate=True, residual=True) > tiling.DEFAULT_VMEM_BUDGET
    assert fused != plain
    k1 = tiling.autotune_cache_key("gemm", 8192, 8192, 8192, 2, "cpu")
    k2 = tiling.autotune_cache_key("gemm", 8192, 8192, 8192, 2, "cpu",
                                   gate=True, residual=True)
    assert k1 != k2


def test_ops_fused_call_plans_with_epilogue_flags(monkeypatch):
    """ops.gemm with a gate operand must plan under the fused flags."""
    seen = []
    real = tiling.autotune_block_shape

    def spy(*a, **kw):
        seen.append((kw.get("gate"), kw.get("residual")))
        return real(*a, **kw)

    monkeypatch.setattr(tiling, "autotune_block_shape", spy)
    x, w, w2 = _rand(0, (8, 16)), _rand(1, (16, 8)), _rand(2, (16, 8))
    ops.gemm(x, w, b2=w2, activation="silu")
    assert seen == [(True, False)], seen


def test_autotune_corrupt_disk_cache_tolerated(tune_env):
    tune_env.write_text("{not json")
    b = tiling.autotune_block_shape("gemm", 256, 256, 256, dtype_bytes=2,
                                    backend="cpu", bench_fn=lambda blk: 1.0)
    assert isinstance(b, tiling.BlockShape)


def test_ops_consume_autotuned_plan(tune_env, monkeypatch):
    """An eager ops.gemm call with tuning on must benchmark the shortlist
    and the chosen (measured) block must be what the kernel launches with."""
    m = k = n = 256
    a, b = _rand(0, (m, k)), _rand(1, (k, n))
    out = ops.gemm(a, b)
    data = json.loads(tune_env.read_text())
    key = tiling.autotune_cache_key("gemm", m, n, k, 4, jax.default_backend())
    assert data[key]["source"] == "measured"
    _cmp(out, np.asarray(a) @ np.asarray(b), F32)
    # the cached winner is served on subsequent calls (no further bench):
    # poison rank_block_shapes; a cache hit never consults it
    monkeypatch.setattr(tiling, "rank_block_shapes",
                        lambda *a_, **k_: pytest.fail("cache miss"))
    _ = ops.gemm(a, b)


# --------------------------------------------------------------------------
# Traffic model: fused strictly beats unfused on launches and HBM traffic
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["swiglu", "gelu"])
def test_mlp_traffic_model_fused_strictly_less(kind):
    fused = tiling.mlp_traffic(512, 1024, 4096, fused=True, kind=kind)
    unfused = tiling.mlp_traffic(512, 1024, 4096, fused=False, kind=kind)
    assert fused.kernel_launches < unfused.kernel_launches
    assert fused.hbm_writes < unfused.hbm_writes
    assert fused.hbm_reads < unfused.hbm_reads
    assert fused.round_trips < unfused.round_trips

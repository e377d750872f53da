"""Level-1/2/3 BLAS as the framework's single matmul entry point.

This module is the paper's contribution reified as the substrate of the whole
framework: every model layer routes its linear algebra through these
functions, so the co-designed blocked kernels (kernels/) are a first-class,
globally switchable feature rather than a bolt-on.

Backends
--------
- "xla":    jnp/lax ops with f32 accumulation (`preferred_element_type`).
            Used for dry-runs/rooflines so `cost_analysis()` sees the FLOPs,
            and as the fallback on non-TPU hosts.
- "pallas": the Pallas TPU kernels in repro.kernels (VMEM-blocked, MXU-
            aligned — the paper's PE mapped onto a TPU core).  On CPU these
            run in interpret mode (slow; used by tests).
- "ref":    naive pure-jnp oracles (kernels/ref.py semantics) for validation.

All functions follow BLAS semantics (alpha/beta scaling, accumulate into y/C)
but are functional: they return the result instead of mutating.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import epilogue as _epilogue
from repro.core import quant as _quant
from repro.core.epilogue import Epilogue

_state = threading.local()
_VALID = ("xla", "pallas", "ref")


def get_backend() -> str:
    return getattr(_state, "backend", "xla")


def set_backend(name: str) -> None:
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    _state.backend = name


@contextlib.contextmanager
def use_backend(name: str):
    old = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        set_backend(old)


def in_verify_window() -> bool:
    return getattr(_state, "verify_window", False)


@contextlib.contextmanager
def verify_window():
    """Mark every row of (B, t, d) inputs traced inside this block as a
    DECODE token (a speculative verify window: last committed token + k
    drafts), not prefill.

    Speculative verification runs k+1 decode tokens per slot through one
    launch, so projections that key numeric paths on shape alone would move
    those tokens onto the prefill path — on the xla host backend that is
    dequantize+f32-GEMM instead of the contiguous packed-int8 matvec, whose
    different accumulation rounding can flip a near-tied greedy argmax and
    break the bit-identical-tokens contract vs --speculate 0.  Under this
    flag the quantized host path runs each window row through the SAME
    per-token `quant.gemv_host` dot that plain decode uses, making verify
    numerics per-row identical to decode numerics by construction.  The
    pallas backend is unaffected: its bgemm tiles dequantize with the same
    in-kernel scheme as bgemv, so the skinny-GEMM intensity shift keeps
    bit-stable rows without a special case.
    """
    old = in_verify_window()
    _state.verify_window = True
    try:
        yield
    finally:
        _state.verify_window = old


def _acc_dtype(x: jnp.ndarray) -> jnp.dtype:
    # max(f32, operand dtype): low-precision inputs accumulate in f32 (MXU
    # style); f64 operands keep f64 accumulation (the D-prefix routines).
    return jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16, jnp.int8) else x.dtype


def _deq(w, dtype=jnp.float32):
    """Dequantization fallback: exact W8A16 oracle semantics (xla/ref)."""
    return w.dequantize(dtype) if _quant.is_quantized(w) else w


def _quant_matvec_host(w, xb: jnp.ndarray, decode_shaped: bool = True) -> jnp.ndarray:
    """Host (xla) packed matvec batch: y[b] = W^T x[b] -> (B, f) f32 for a
    serving-layout QuantizedTensor (stored output-major).

    DECODE-SHAPED calls (one token per member) run one contiguous int8 dot
    per member (`quant.gemv_host` — the measured bandwidth win); everything
    else falls back to exact dequantization, where the f32 GEMM's own batch
    amortization already covers the traffic.  The switch keys on the call's
    SHAPE only — never on the batch count — so the same token takes the
    same numeric path at every batch size and greedy decode stays
    bit-identical across scheduling configurations (the test_serve parity
    contract).
    """
    batch = xb.shape[0]
    if decode_shaped and w.transposed and _quant.host_fast_path_eligible(w):
        if batch == 1:
            return _quant.gemv_host(w, xb[0])[None]
        return jnp.stack([_quant.gemv_host(w, xb[i]) for i in range(batch)])
    acc = _acc_dtype(xb)
    return jnp.matmul(xb.astype(acc), _deq(w).astype(acc))


def _epi_spec(epilogue, gate, bias, residual) -> Epilogue:
    """Static spec from the user's epilogue arg (Epilogue | activation str |
    None) + operand presence; flags always track the operands actually
    passed so the spec cannot claim data that is not there."""
    return _epilogue.make(
        _epilogue.as_epilogue(epilogue).activation,
        bias=bias, gate=gate, residual=residual,
    )


def _check_no_blas_params(epi: Epilogue, alpha, beta, C, what: str) -> None:
    if not epi.is_identity and (alpha != 1.0 or beta != 0.0 or C is not None):
        raise ValueError(
            f"{what}: alpha/beta/C accumulate-scaling cannot be combined with a "
            "fused epilogue (apply one or the other)"
        )


# --------------------------------------------------------------------------
# Level 1
# --------------------------------------------------------------------------

def dot(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """ddot: x^T y (paper Fig 3 DAG: parallel mults + log-depth add tree)."""
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        return ops.dot(x, y)
    if backend == "ref":
        from repro.kernels import ref
        return ref.dot(x, y)
    acc = _acc_dtype(x)
    return jnp.sum(x.astype(acc) * y.astype(acc)).astype(x.dtype)


def nrm2(x: jnp.ndarray) -> jnp.ndarray:
    """dnrm2: sqrt(x^T x) — same DAG as ddot plus one sqrt (paper S4.1)."""
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        return ops.nrm2(x)
    if backend == "ref":
        from repro.kernels import ref
        return ref.nrm2(x)
    acc = _acc_dtype(x)
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(acc)))).astype(x.dtype)


def axpy(alpha, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """daxpy: alpha*x + y — one fully parallel DAG level."""
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        return ops.axpy(alpha, x, y)
    if backend == "ref":
        from repro.kernels import ref
        return ref.axpy(alpha, x, y)
    return (jnp.asarray(alpha, x.dtype) * x + y).astype(x.dtype)


def scal(alpha, x: jnp.ndarray) -> jnp.ndarray:
    return (jnp.asarray(alpha, x.dtype) * x).astype(x.dtype)


# --------------------------------------------------------------------------
# Level 2
# --------------------------------------------------------------------------

def gemv(
    A: jnp.ndarray,
    x: jnp.ndarray,
    y: Optional[jnp.ndarray] = None,
    *,
    alpha=1.0,
    beta=0.0,
    trans: bool = False,
) -> jnp.ndarray:
    """dgemv: y = alpha * op(A) x + beta * y (op = A or A^T).

    A may be a block-scaled `QuantizedTensor` (non-transposed storage): the
    pallas backend streams the packed int8 values with in-kernel
    dequantization; xla runs the contiguous int8 host fast path when the
    scale layout allows (per-row-block scales) and exact dequantization
    otherwise; ref always uses the dequantization oracle.
    """
    quantized = _quant.is_quantized(A)
    if quantized and (trans or A.transposed):
        raise ValueError(
            "quantized gemv streams A in its stored (m, n) layout; "
            "quantize the transpose instead of passing trans=True"
        )
    if trans:
        A = A.T
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        out = ops.gemv(A, x)
    elif backend == "ref":
        from repro.kernels import ref
        out = ref.gemv(_deq(A, x.dtype), x)
    elif quantized:
        if _quant.host_fast_path_eligible(A):
            out = _quant.gemv_host(A, x).astype(x.dtype)
        else:
            acc = _acc_dtype(x)
            out = jnp.dot(_deq(A).astype(acc), x.astype(acc)).astype(x.dtype)
    else:
        acc = _acc_dtype(A)
        out = jnp.dot(A, x, preferred_element_type=acc).astype(A.dtype)
    out = scal(alpha, out)
    if y is not None and beta != 0.0:
        out = out + scal(beta, y)
    return out


# --------------------------------------------------------------------------
# Level 3
# --------------------------------------------------------------------------

def gemm(
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: Optional[jnp.ndarray] = None,
    *,
    alpha=1.0,
    beta=0.0,
    transpose_a: bool = False,
    transpose_b: bool = False,
    B2: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
    epilogue=None,
) -> jnp.ndarray:
    """dgemm: C = alpha * op(A) op(B) + beta * C — or, with an epilogue,
    C = epilogue(op(A) op(B) [, op(A) op(B2)]) fused into the kernel flush.

    `epilogue` is an `Epilogue` spec or an activation name ("silu"/"gelu"/
    "relu"); `bias` (n,), `residual` (m, n) and the dual-GEMM gate operand
    `B2` ride along and are applied to the f32 accumulator before the
    single HBM write (pallas) or in f32 before the output cast (xla/ref).
    2-D operands only; for the model-layer entry point with leading batch
    dims use `matmul` / `matmul_fused` below.
    """
    quantized = _quant.is_quantized(B)
    if quantized and (transpose_a or transpose_b):
        raise ValueError(
            "quantized gemm streams B in its stored layout; fold the "
            "transpose into QuantSpec(transpose=...) instead"
        )
    if transpose_a:
        A = A.T
    if transpose_b:
        B = B.T
        if B2 is not None:
            B2 = B2.T
    epi = _epi_spec(epilogue, B2, bias, residual)
    _check_no_blas_params(epi, alpha, beta, C, "gemm")
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        out = ops.gemm(A, B, b2=B2, bias=bias, residual=residual,
                       activation=epi.activation,
                       out_dtype=A.dtype if quantized else None)
    elif not epi.is_identity:
        # xla/ref fused fallback: accumulate in max(f32, dtype), apply the
        # identical epilogue semantic, cast once — same math, no kernel
        # (quantized operands enter through the exact dequantization oracle)
        acc = _acc_dtype(A)
        h = jnp.dot(A, _deq(B, A.dtype), preferred_element_type=acc).astype(acc)
        h2 = (jnp.dot(A, _deq(B2, A.dtype), preferred_element_type=acc).astype(acc)
              if epi.gate else None)
        out = epi.apply(h, acc2=h2, bias=bias, residual=residual).astype(A.dtype)
    elif backend == "ref":
        from repro.kernels import ref
        out = ref.gemm(A, _deq(B, A.dtype))
    else:
        acc = _acc_dtype(A)
        out = jnp.dot(A, _deq(B, A.dtype), preferred_element_type=acc).astype(A.dtype)
    if alpha != 1.0:
        out = scal(alpha, out)
    if C is not None and beta != 0.0:
        out = out + scal(beta, C)
    return out


def batched_gemm(
    A: jnp.ndarray,  # (batch, m, k) (before transpose_a)
    B: jnp.ndarray,  # (batch, k, n) or (k, n) broadcast (before transpose_b)
    C: Optional[jnp.ndarray] = None,
    *,
    alpha=1.0,
    beta=0.0,
    transpose_a: bool = False,
    transpose_b: bool = False,
    out_dtype=None,
    B2: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
    epilogue=None,
) -> jnp.ndarray:
    """Batched dgemm: C[b] = alpha * op(A[b]) op(B[b]) + beta * C[b].

    One fused launch for the whole batch (KBLAS-style): the pallas backend
    folds the batch into the kernel grid instead of looping N tiny GEMMs.
    A 2-D B is broadcast across the batch — the shared-weight serving case,
    where the kernel fetches each B tile once and reuses it per batch member.

    The fused-epilogue args mirror `gemm`: `B2` (same layout as B) is the
    dual-GEMM gate operand — with epilogue="silu" this computes the whole
    MoE-expert SwiGLU silu(A@B) * (A@B2) in one launch; `bias` is (n,),
    `residual` (batch, m, n).
    """
    quantized = _quant.is_quantized(B)
    if quantized and (transpose_a or transpose_b):
        raise ValueError(
            "quantized batched_gemm streams B in its stored layout; fold "
            "the transpose into QuantSpec(transpose=...) instead"
        )
    if transpose_a:
        A = jnp.swapaxes(A, -2, -1)
    if transpose_b:
        B = jnp.swapaxes(B, -2, -1)
        if B2 is not None:
            B2 = jnp.swapaxes(B2, -2, -1)
    epi = _epi_spec(epilogue, B2, bias, residual)
    _check_no_blas_params(epi, alpha, beta, C, "batched_gemm")
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        out = ops.bgemm(A, B, b2=B2, bias=bias, residual=residual,
                        activation=epi.activation,
                        out_dtype=out_dtype or (A.dtype if quantized else None))
    elif not epi.is_identity:
        # quantized operands enter through the exact dequantization oracle
        acc = _acc_dtype(A)
        h = jnp.matmul(A, _deq(B, A.dtype), preferred_element_type=acc).astype(acc)
        h2 = (jnp.matmul(A, _deq(B2, A.dtype), preferred_element_type=acc).astype(acc)
              if epi.gate else None)
        out = epi.apply(h, acc2=h2, bias=bias, residual=residual).astype(
            out_dtype or A.dtype
        )
    elif backend == "ref":
        from repro.kernels import ref
        out = ref.bgemm(A, _deq(B, A.dtype), out_dtype=out_dtype)
    else:
        acc = _acc_dtype(A)
        out = jnp.matmul(A, _deq(B, A.dtype),
                         preferred_element_type=acc).astype(out_dtype or A.dtype)
    if alpha != 1.0:
        out = scal(alpha, out)
    if C is not None and beta != 0.0:
        out = out + scal(beta, C)
    return out


def batched_gemv(
    A: jnp.ndarray,  # (batch, m, n) or (m, n) broadcast (before trans)
    x: jnp.ndarray,  # (batch, n)
    y: Optional[jnp.ndarray] = None,
    *,
    alpha=1.0,
    beta=0.0,
    trans: bool = False,
) -> jnp.ndarray:
    """Batched dgemv: y[b] = alpha * op(A[b]) x[b] + beta * y[b] -> (batch, m).

    A single GEMV is bandwidth-bound (the paper's 40%-of-peak case); batching
    N of them into one launch is the classic fix.  A 2-D A is broadcast —
    the batched-decode case where every request multiplies the same weights,
    so A traffic amortizes over the batch.

    Under the pallas backend, trans=True is pushed into the kernel
    (`transpose_a`): the weight streams in its HBM layout instead of being
    materialized transposed on every call.

    A may be a block-scaled `QuantizedTensor` (broadcast serving weight):
    pallas streams the packed int8 values with in-kernel dequantization
    (the stored layout must encode the op — quantize with
    `QuantSpec(transpose=trans)`); xla uses the per-member contiguous int8
    host fast path for small batches and exact dequantization otherwise;
    ref always dequantizes.
    """
    quantized = _quant.is_quantized(A)
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        out = ops.bgemv(A, x, transpose_a=trans)
        if quantized:
            out = out.astype(x.dtype)
    elif quantized:
        if trans != A.transposed:
            raise ValueError(
                "quantized batched_gemv streams the stored layout; quantize "
                f"with QuantSpec(transpose={trans}) to request op=A^T={trans}"
            )
        if backend == "ref":
            Ad = _deq(A, x.dtype)
            if trans:
                Ad = jnp.swapaxes(Ad, -2, -1)
            from repro.kernels import ref
            out = ref.bgemv(Ad, x)
        elif trans:
            out = _quant_matvec_host(A, x).astype(x.dtype)
        elif _quant.host_fast_path_eligible(A) and A.ndim == 2:
            out = jnp.stack(
                [_quant.gemv_host(A, x[i]) for i in range(x.shape[0])]
            ).astype(x.dtype)
        else:
            out = jnp.matmul(
                _deq(A).astype(jnp.float32), x[..., None].astype(jnp.float32)
            )[..., 0].astype(x.dtype)
    else:
        if trans:
            A = jnp.swapaxes(A, -2, -1)
        if backend == "ref":
            from repro.kernels import ref
            out = ref.bgemv(A, x)
        else:
            acc = _acc_dtype(A)
            out = jnp.matmul(
                A.astype(acc), x[..., None].astype(acc)
            )[..., 0].astype(A.dtype)
    out = scal(alpha, out)
    if y is not None and beta != 0.0:
        out = out + scal(beta, y)
    return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class LayerSlice:
    """Layer `index` of a layer-stacked projection weight, left in its stack.

    The decode step's layer scan (`transformer._scan_blocks`) hands these to
    the projections in place of the per-layer slices `lax.scan` would cut:
    a pallas decode-shaped projection passes the stack and the index to
    `bgemv`, which reads the layer's tiles straight out of the stack in
    HBM, so no copy of the layer's weights is made each step.  Every other
    consumer takes `get()`, the slice the scan would have cut.
    """

    stack: Any   # (L, d, f) array, or a QuantizedTensor stacked the same way
    index: Any   # () int32

    def tree_flatten(self):
        return (self.stack, self.index), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self) -> tuple:
        return self.stack.shape[1:]

    def get(self):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, self.index, 0, False),
            self.stack)


def _layer_operands(x, w, w2=None):
    """(w, w2, layer) for a projection of x.  A `LayerSlice` stays in its
    stack (layer = its index) only where the pallas decode `bgemv` reads it
    in place: one row per member, the weight in the layout it streams.
    Elsewhere it is sliced out, as `lax.scan` would have cut it."""
    if not isinstance(w, LayerSlice):
        return w, w2, None
    in_place = (get_backend() == "pallas" and x.ndim > 2 and x.shape[-2] == 1
                and (not _quant.is_quantized(w.stack) or w.stack.transposed))
    if in_place:
        return w.stack, None if w2 is None else w2.stack, w.index
    return w.get(), None if w2 is None else w2.get(), None


def matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Model-layer entry point: x (..., d) @ w (d, f) -> (..., f).

    Every projection in the model zoo calls this, so switching the backend
    switches the whole network onto the co-designed kernels.  Inputs with
    leading batch dims keep their per-request structure: under the pallas
    backend they route through the fused batched kernels with broadcast
    weights (bgemm, or bgemv for decode-shaped (..., 1, d) blocks) instead
    of reshape-flattening the batch away.

    A `QuantizedTensor` w (layers.quantize_weights) runs the whole
    projection packed: the pallas kernels stream int8 tiles with in-kernel
    dequantization (decode-shaped inputs stay ONE broadcast-weight bgemv
    launch, now at int8 bandwidth); the xla host backend uses per-member
    contiguous int8 matvecs for small decode batches and exact
    dequantization elsewhere.

    A `LayerSlice` w is read in place by the decode bgemv, else sliced.
    """
    w, _, layer = _layer_operands(x, w)
    quantized = _quant.is_quantized(w)
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        lead = x.shape[:-1]
        if x.ndim <= 2:
            out = ops.gemm(x.reshape(-1, x.shape[-1]), w)
            return out.reshape(*lead, w.shape[-1]).astype(x.dtype)
        rows, d = x.shape[-2], x.shape[-1]
        xb = x.reshape(-1, rows, d)
        if rows == 1:
            # decode-shaped: one token per batch member -> batched GEMV with
            # broadcast weights (y[b] = w^T x[b], transpose_a pushed into the
            # kernel so w streams in its HBM layout instead of materializing
            # w.T per decode step); cast back to the activation dtype
            # (bgemv's out dtype follows its first operand, here w).
            # The continuous-batching serve scheduler keeps the slot grid at a
            # fixed batch size (inactive slots compute and are masked on the
            # host), so this path — one fused launch — holds at any occupancy.
            # Quantized weights are stored output-major (QuantSpec.transpose)
            # so the same call streams packed int8 in HBM layout.
            wq = w if not quantized or w.transposed else _deq(w, x.dtype)
            out = ops.bgemv(wq, xb[:, 0, :], transpose_a=True,
                            layer=layer).astype(x.dtype)
            return out.reshape(*lead, w.shape[-1])
        out = ops.bgemm(xb, w)
        return out.reshape(*lead, w.shape[-1]).astype(x.dtype)
    if quantized:
        lead = x.shape[:-1]
        d, f = w.shape[-2:]
        decode_shaped = x.ndim >= 3 and (x.shape[-2] == 1
                                         or in_verify_window())
        xb = x.reshape(-1, d)
        if backend == "ref":
            from repro.kernels import ref
            out = ref.bgemv(jnp.swapaxes(_deq(w, x.dtype), -2, -1), xb)
        else:
            out = _quant_matvec_host(w, xb, decode_shaped).astype(x.dtype)
        return out.reshape(*lead, f)
    acc = _acc_dtype(x)
    if acc == jnp.float32 and x.dtype == jnp.bfloat16:
        from repro.core import act_sharding
        if act_sharding.matmul_reduce_dtype() == "bfloat16":
            # TP hillclimb: round partial sums to bf16 BEFORE the cross-shard
            # all-reduce (per-shard MXU accumulation is f32 regardless)
            acc = jnp.bfloat16
    return jnp.dot(x, w, preferred_element_type=acc).astype(x.dtype)


def matmul_fused(
    x: jnp.ndarray,               # (..., d)
    w: jnp.ndarray,               # (d, f)
    *,
    w2: Optional[jnp.ndarray] = None,        # (d, f) dual-GEMM gate operand
    bias: Optional[jnp.ndarray] = None,      # (f,)
    residual: Optional[jnp.ndarray] = None,  # (..., f)
    activation: Optional[str] = None,        # "silu" | "gelu" | "relu"
) -> jnp.ndarray:
    """Model-layer projection with the epilogue fused into the kernel flush.

        y = epilogue(x @ w [, x @ w2])
          = act(x @ w + bias) [* (x @ w2)] [+ residual]

    so a SwiGLU layer is one call — `matmul_fused(x, w_gate, w2=w_up,
    activation="silu")` — and a biased QKV projection is
    `matmul_fused(x, wq, bias=bq)`.  Under the pallas backend each call is
    ONE kernel launch and ONE HBM output write (gemm / bgemm / decode-shaped
    bgemv with transpose_a, mirroring `matmul`'s routing); xla/ref apply the
    identical epilogue semantic to the f32 accumulator before the single
    output cast, so all backends agree to dtype tolerance.  `LayerSlice`
    weights route as in `matmul`.
    """
    w, w2, layer = _layer_operands(x, w, w2)
    epi = _epi_spec(activation, w2, bias, residual)
    quantized = _quant.is_quantized(w)
    lead = x.shape[:-1]
    f = w.shape[-1]
    res = None if residual is None else residual.reshape(*lead, f)
    backend = get_backend()
    if backend == "pallas":
        from repro.kernels import ops
        if x.ndim <= 2:
            x2 = x.reshape(-1, x.shape[-1])
            r2 = None if res is None else res.reshape(x2.shape[0], f)
            out = ops.gemm(x2, w, b2=w2, bias=bias, residual=r2,
                           activation=epi.activation, out_dtype=x.dtype)
            return out.reshape(*lead, f)
        rows, d = x.shape[-2], x.shape[-1]
        xb = x.reshape(-1, rows, d)
        if rows == 1:
            # decode-shaped: dual-GEMV with broadcast weights in HBM layout
            # (transpose_a) — the whole decode-step SwiGLU is one launch;
            # quantized weights (stored output-major) keep it one launch at
            # int8 bandwidth, both accumulators dequantizing on the fly
            rb = None if res is None else res.reshape(-1, f)
            wq, wq2 = w, w2
            if quantized and not w.transposed:
                wq, wq2 = _deq(w, x.dtype), _deq(w2, x.dtype)
            out = ops.bgemv(wq, xb[:, 0, :], a2=wq2, bias=bias, residual=rb,
                            transpose_a=True, activation=epi.activation,
                            layer=layer).astype(x.dtype)
            return out.reshape(*lead, f)
        rb = None if res is None else res.reshape(-1, rows, f)
        out = ops.bgemm(xb, w, b2=w2, bias=bias, residual=rb,
                        activation=epi.activation, out_dtype=x.dtype)
        return out.reshape(*lead, f)
    if quantized:
        # xla/ref: packed host matvecs (or the dequantization oracle) feed
        # the identical epilogue semantic on the f32 accumulator
        d = x.shape[-1]
        decode_shaped = x.ndim >= 3 and (x.shape[-2] == 1
                                         or in_verify_window())
        xb = x.reshape(-1, d)
        if backend == "ref":
            from repro.kernels import ref
            h = ref.bgemv(jnp.swapaxes(_deq(w), -2, -1), xb).astype(jnp.float32)
            h2 = (ref.bgemv(jnp.swapaxes(_deq(w2), -2, -1), xb).astype(jnp.float32)
                  if epi.gate else None)
        else:
            h = _quant_matvec_host(w, xb, decode_shaped)
            h2 = _quant_matvec_host(w2, xb, decode_shaped) if epi.gate else None
        r2 = None if res is None else res.reshape(xb.shape[0], f)
        out = epi.apply(h, acc2=h2, bias=bias, residual=r2).astype(x.dtype)
        return out.reshape(*lead, f)
    acc = _acc_dtype(x)
    h = jnp.dot(x, w, preferred_element_type=acc).astype(acc)
    h2 = jnp.dot(x, w2, preferred_element_type=acc).astype(acc) if epi.gate else None
    return epi.apply(h, acc2=h2, bias=bias, residual=res).astype(x.dtype)


def einsum(subscripts: str, *operands: jnp.ndarray) -> jnp.ndarray:
    """einsum with MXU-style f32 accumulation; used by attention/MoE layers.

    The pallas backend intentionally falls through to XLA here: arbitrary
    contractions are XLA's job; the co-designed kernels cover the named BLAS
    patterns (gemm/gemv/dot) plus attention/scan kernels in repro.kernels.
    """
    acc = _acc_dtype(operands[0])
    return jnp.einsum(subscripts, *operands, preferred_element_type=acc).astype(
        operands[0].dtype
    )

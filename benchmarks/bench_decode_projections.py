"""Decode projections alone on the chip: each weight read once per step.

One decode step runs every projection of every layer once, for all slots,
with weights that cycle through HBM: no layer's weight is touched again
before the whole stack has been read.  This bench reproduces that: an
8-layer stack of one projection's weights, scanned layer by layer as the
model's layer scan does, 8 slots, and times the scan (ms per layer, the
best of `--reps` jitted calls).  Per projection of internlm2-20b (int8 and
bf16 weights) and stablelm-1.6b (bf16), three ways to compute it:

  - `sliced`:   `ops.bgemv` on the layer the scan slices out of the stack
                (the decode step before the projections were read in place);
  - `in_place`: `ops.bgemv` reading the layer out of the stack through its
                index maps (`layer=`), as the decode step does now; skipped
                where `ops.bgemv` has no `layer` (an older checkout);
  - `gemm`:     `ops.gemm` on the sliced layer, the (8, d) rows as one GEMM
                operand, epilogue fused the same way.

`--sweep` times `in_place` at other (block_m, block_n) tiles for the dual
gate/up projections; `--model` keeps one model's projections.  Prints a
JSON line a projection and writes them all to `--out`.  Each variant
compiles once, ~10 s on a v5e: all of them take ~5 min.

    PYTHONPATH=src python benchmarks/bench_decode_projections.py \\
        --out decode_projections.json [--sweep] [--model M]
"""

from __future__ import annotations

import argparse
import inspect
import json
import time

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels import ops

SLOTS, LAYERS = 8, 8

#: (name, contraction n, output m, epilogue) per model
PROJECTIONS = {
    "internlm2": [("wq", 6144, 6144, None), ("wk", 6144, 1024, None),
                  ("wo+res", 6144, 6144, "residual"),
                  ("gate/up dual", 6144, 16384, "gate"),
                  ("down+res", 16384, 6144, "residual")],
    "stablelm": [("wq+bias", 2048, 2048, "bias"),
                 ("wo+res", 2048, 2048, "residual"),
                 ("gate/up dual", 2048, 5632, "gate"),
                 ("down+res", 5632, 2048, "residual")],
}
SWEEP = [(512, 1024), (512, 2048), (1024, 1024), (1024, 2048), (2048, 2048),
         (1024, 4096)]


def _stack(key, n, m, int8: bool):
    """An 8-layer stack of (n, m) weights in the layout the model keeps:
    bf16 (L, n, m), or packed int8 output-major (L, m, n) with one f32
    scale per 64 output rows."""
    if not int8:
        return (jax.random.normal(key, (LAYERS, n, m), jnp.float32)
                * 0.02).astype(jnp.bfloat16)
    kv, ks = jax.random.split(key)
    values = jax.random.randint(kv, (LAYERS, m, n), -127, 128, jnp.int8)
    scales = jax.random.uniform(ks, (LAYERS, m // 64, 1), jnp.float32,
                                1e-4, 1e-3)
    return quant.QuantizedTensor(values, scales, (64, n), transposed=True)


def _operands(n, m, epi, int8, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w = _stack(k1, n, m, int8)
    w2 = _stack(k2, n, m, int8) if epi == "gate" else None
    x = jax.random.normal(k3, (SLOTS, n), jnp.float32).astype(jnp.bfloat16)
    extra = {}
    if epi == "bias":
        extra["bias"] = jnp.ones((m,), jnp.bfloat16)
    if epi == "residual":
        extra["residual"] = jax.random.normal(
            k4, (SLOTS, m), jnp.float32).astype(jnp.bfloat16)
    if epi == "gate":
        extra["activation"] = "silu"
    return w, w2, x, extra


def _layer_of(stack, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                        stack)


def _scan(project, w, w2, x):
    """Every layer once, the output of one feeding nothing (the x of each
    layer is the same), as a jitted scan over the layer index."""
    def body(acc, i):
        y = project(w, w2, x, i)
        return acc + y[:, :1].astype(jnp.float32), None

    return jax.lax.scan(body, jnp.zeros((SLOTS, 1), jnp.float32),
                        jnp.arange(LAYERS, dtype=jnp.int32))[0]


def _time_ms(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3 / LAYERS


def _variants(extra, in_place_ok, blocks=None):
    kw = dict(extra)
    blk = blocks or {}

    def sliced(w, w2, x, i):
        return ops.bgemv(_layer_of(w, i), x,
                         a2=None if w2 is None else _layer_of(w2, i),
                         transpose_a=True, **kw, **blk)

    def in_place(w, w2, x, i):
        return ops.bgemv(w, x, a2=w2, transpose_a=True, layer=i, **kw, **blk)

    def gemm(w, w2, x, i):
        return ops.gemm(x, _layer_of(w, i),
                        b2=None if w2 is None else _layer_of(w2, i), **kw)

    out = {"sliced": sliced, "gemm": gemm}
    if in_place_ok:
        out["in_place"] = in_place
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default=None)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--model", choices=sorted(PROJECTIONS), default=None,
                    help="one model's projections (default: both)")
    args = ap.parse_args()
    in_place_ok = "layer" in inspect.signature(ops.bgemv).parameters
    rows = []
    key = jax.random.PRNGKey(0)
    for model, projs in PROJECTIONS.items():
        if args.model not in (None, model):
            continue
        for int8 in ((True, False) if model == "internlm2" else (False,)):
            for name, n, m, epi in projs:
                key, sub = jax.random.split(key)
                w, w2, x, extra = _operands(n, m, epi, int8, sub)
                nbytes = sum(a.size * a.dtype.itemsize
                             for a in jax.tree.leaves((w, w2))) / LAYERS
                row = {"model": model, "weights": "int8" if int8 else "bf16",
                       "proj": name, "n": n, "m": m,
                       "weight_bytes_per_layer": nbytes}
                for label, fn in _variants(extra, in_place_ok).items():
                    f = jax.jit(lambda w, w2, x, fn=fn: _scan(fn, w, w2, x))
                    ms = _time_ms(f, (w, w2, x), args.reps)
                    row[label + "_ms"] = ms
                    row[label + "_gb_s"] = nbytes / ms / 1e6
                if args.sweep and in_place_ok and epi == "gate":
                    row["sweep_in_place_ms"] = {}
                    for bm, bn in SWEEP:
                        fn = _variants(extra, True, dict(block_m=bm,
                                                         block_n=bn))
                        f = jax.jit(lambda w, w2, x, fn=fn["in_place"]:
                                    _scan(fn, w, w2, x))
                        row["sweep_in_place_ms"][f"{bm}x{bn}"] = _time_ms(
                            f, (w, w2, x), args.reps)
                print(json.dumps(row), flush=True)
                rows.append(row)
                del w, w2
    result = {"device": str(jax.devices()[0].device_kind),
              "in_place": in_place_ok, "rows": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({"done": len(rows), "device": result["device"]}))


if __name__ == "__main__":
    main()

#!/usr/bin/env bash
# Tier-1 CI: the exact command the roadmap pins, on CPU.
#
#   ./scripts/ci.sh            # run the full suite
#   ./scripts/ci.sh -k blas    # extra args forwarded to pytest
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m pytest -x -q "$@"

# Serve smoke: a small continuous-batching run plus the batch-at-a-time
# baseline, so the scheduler path is exercised end-to-end on every push.
for sched in continuous batch; do
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
    --variant smoke --requests 6 --batch 2 --prompt-len 8 --gen 4 \
    --scheduler "$sched"
done

# Chunked-admission smoke: a long-prompt admission split into fixed-size
# prefill chunks interleaved with decode steps (ISSUE 6) — the head-of-line
# blocking fix runs end-to-end with a prompt long enough to chunk.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
  --variant smoke --requests 6 --batch 2 --prompt-len 48 --gen 4 \
  --scheduler continuous --prefill-chunk 16

# Quantized decode smoke: block-scaled int8 serving weights through the
# continuous scheduler — the bandwidth-bound decode path runs packed end to
# end (host int8 matvecs on CPU, in-kernel dequant under pallas on TPU).
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
  --variant smoke --requests 6 --batch 2 --prompt-len 8 --gen 4 \
  --scheduler continuous --quantize int8

# Fully-quantized decode smoke: int8 weights AND the block-scaled int8 KV
# cache together (the combined cell), on both schedulers — the decode
# step's two dominant byte terms both stream packed end to end.
for sched in continuous batch; do
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
    --variant smoke --requests 6 --batch 2 --prompt-len 8 --gen 4 \
    --scheduler "$sched" --quantize int8 --kv-cache int8
done

# Paged-KV smoke: the page-pool cache (shared-prefix reuse, copy-on-write,
# free-list recycling) runs end to end through both schedulers with the
# int8 KV cache stacked on top (ISSUE 7) — the page-table indirection and
# the quantized byte path compose in one serving run.
for sched in continuous batch; do
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
    --variant smoke --requests 6 --batch 2 --prompt-len 8 --gen 4 \
    --scheduler "$sched" --kv-cache int8 --kv-page-size 4
done

# Speculative-decoding smoke (ISSUE 9): --speculate 4 on both schedulers —
# the self-drafted verify path (skinny-GEMM projections, longest-accepted-
# prefix rollback) runs end to end; greedy-token parity with --speculate 0
# is gated below on the bench's asserted spec_token_parity.
for sched in continuous batch; do
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
    --variant smoke --requests 6 --batch 2 --prompt-len 8 --gen 4 \
    --scheduler "$sched" --speculate 4
done

# Speculative + fully-quantized + paged smoke: the verify window composes
# with every byte-path lever in one run (int8 weights, int8 KV, paged pool).
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
  --variant smoke --requests 6 --batch 2 --prompt-len 8 --gen 4 \
  --scheduler continuous --speculate 4 \
  --quantize int8 --kv-cache int8 --kv-page-size 4

# Fault smoke (ISSUE 8): forced pool exhaustion on both schedulers with the
# per-round invariant sweep on — the preempt -> requeue -> recompute path
# must reproduce the unfaulted run's greedy tokens BIT-identically, finish
# every preempted request as "preempted_resumed", and conserve every pool
# page (end-of-serve leak_check).  A tiny pool (--pool-pages) additionally
# exercises REAL exhaustion + watermark backpressure, no injection needed.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - <<'PY'
import numpy as np
from repro.launch.serve import serve

rng = np.random.default_rng(0)
prompts = [rng.integers(3, 256, size=(10,), dtype=np.int32) for _ in range(6)]
gen_lens = rng.integers(4, 9, size=6).tolist()
bases = {}
for sched in ("continuous", "batch"):
    kw = dict(batch=2, prompts=prompts, gen_lens=gen_lens, eos=-1,
              verbose=False, scheduler=sched, kv_page_size=4)
    bases[sched] = serve("stablelm-1.6b", "smoke", **kw)
    fx = serve("stablelm-1.6b", "smoke", faults="exhaust@0",
               check_invariants=True, **kw)
    assert fx["outputs"] == bases[sched]["outputs"], \
        f"{sched}: preempted recompute diverged from the unfaulted run"
    assert fx["preemptions"] >= 1 and "preempted_resumed" in fx["status"]
    assert ("exhaust", 0) in fx["faults_fired"] and not fx["faults_unfired"]
    print(f"[fault-smoke] {sched}: parity OK, "
          f"{fx['preemptions']} preemptions, statuses {fx['status']}")
real = serve("stablelm-1.6b", "smoke", batch=2, prompts=prompts,
             gen_lens=gen_lens, eos=-1, verbose=False,
             scheduler="continuous", kv_page_size=4, pool_pages=7,
             check_invariants=True)
assert real["outputs"] == bases["continuous"]["outputs"], \
    "small pool: real exhaustion diverged from the default-pool run"
assert real["completed"] == 6
print(f"[fault-smoke] small pool: {real['preemptions']} preemptions, "
      f"{real['completed']} completed, statuses {real['status']}")
PY

# Forced-device mesh job (ISSUE 10): the collective-GEMM conformance matrix
# and the TP serving invariants run on an EMULATED 4-device host mesh (jax
# locks the device count at first init, so the flag must be set before any
# other jax-importing step touches the interpreter — each test re-forces it
# in a subprocess, and this job pins the harness itself under the flag).
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m pytest -x -q tests/test_mesh_conformance.py

# Tensor-parallel serve smoke: --tp 2 on a forced 2-device mesh, composed
# with every byte-path lever (int8 weights, int8 KV, paged pool, speculate)
# — packed int8 shards resident per device, one integer psum per layer
# boundary, KV heads + page pools sharded.  Greedy-token identity vs the
# 1-device run is gated below on the bench's asserted tp_token_parity.
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
  --variant smoke --requests 6 --batch 2 --prompt-len 8 --gen 4 \
  --scheduler continuous --tp 2 --speculate 4 \
  --quantize int8 --kv-cache int8 --kv-page-size 4

# Fused-MLP + quantized-streaming smoke + perf-trajectory JSON: the
# kernel/fused-epilogue/quantized benches run end-to-end and emit
# BENCH_kernels.json (GFLOP/s, GB/s + %-of-measured-bandwidth for the
# bandwidth-bound rows, fused and quantized speedups); the schema is
# validated so downstream tooling can diff the numbers across PRs.
# REPRO_AUTOTUNE_CACHE points into the workspace so --autotune runs (the
# fused variants measured at tuned blocks) never touch $HOME in CI.
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
  REPRO_AUTOTUNE_CACHE="${REPRO_AUTOTUNE_CACHE:-.autotune-ci.json}" \
  python benchmarks/run.py --autotune \
  --only kernels,fused_epilogue,quantized,serve --json BENCH_kernels.json
python - <<'PY'
import json

d = json.load(open("BENCH_kernels.json"))
assert d["schema_version"] == 1, d.get("schema_version")
assert d["rows"], "no benchmark rows emitted"
for row in d["rows"]:
    assert {"name", "us_per_call", "metrics"} <= set(row), row
s = d["summary"]
assert {"max_gflops", "pct_roofline", "fused_speedup", "min_fused_speedup",
        "fused_structural_win", "quant_speedup",
        "quant_weight_bytes_ratio", "kv_quant_speedup",
        "combined_byte_ratio", "stall_tokens_chunked",
        "stall_tokens_unchunked", "max_stall_ms", "max_stall_ms_unchunked",
        "ttft_p95", "paged_capacity_multiplier", "paged_token_parity",
        "paged_pages_live", "paged_pages_shared",
        "preempt_recompute_parity", "fault_smoke_pass",
        "spec_tokens_per_step", "spec_token_parity",
        "spec_acceptance_rate", "tp_token_parity",
        "tp_interconnect_byte_ratio"} <= set(s), s
assert s["max_gflops"] > 0 and 0 < s["pct_roofline"] <= 1, s
# the fused epilogue must win structurally (fewer launches + HBM round
# trips on every fused row) AND show no real wall-clock regression: the
# interleaved pair timing bounds container noise, so >10% slower is a
# genuine regression, not drift
assert s["fused_structural_win"], s
assert s["min_fused_speedup"] >= 0.9, s
# the packed int8 path must win where it claims to: >=1.5x wall clock on
# the bandwidth-bound GEMV/decode rows and >=2x modeled weight-byte
# reduction on every quantized row (structural, backend-independent)
assert s["quant_speedup"] >= 1.5, s
assert s["quant_weight_bytes_ratio"] >= 2.0, s
# the int8 KV cache must win the same two ways: a measured wall-clock win
# on the bandwidth-bound K-stream rows (>=1.2x leaves headroom for noisy
# neighbors; structurally it is ~4x fewer bytes) and the modeled combined
# weights+KV decode byte budget >= 1.5x below the weights-only path on the
# long-context serving cells (the ISSUE 5 acceptance gate)
assert s["kv_quant_speedup"] >= 1.2, s
assert s["combined_byte_ratio"] >= 1.5, s
# chunked admission must strictly shrink the worst inter-token stall a
# long-prompt admission inflicts on live decode slots (ISSUE 6).  The gate
# is on the DETERMINISTIC stall (prefill tokens between two consecutive
# decode steps while slots are live) — wall-clock max_stall_ms is reported
# for trend tracking but includes jit-trace noise on first-seen prefill
# shapes, so it only gets a presence check.
assert s["stall_tokens_chunked"] < s["stall_tokens_unchunked"], s
assert s["stall_tokens_chunked"] > 0 and s["max_stall_ms"] > 0, s
assert s["max_stall_ms_unchunked"] > 0, s
assert s["ttft_p95"] > 0, s
# paged KV cache with shared-prefix reuse (ISSUE 7): under a shared system
# prompt at batch 4 the pool must hold the prefix ONCE (per-slot logical
# pages / distinct physical pages > 1.5x effective capacity), and the
# shared run's greedy tokens must be bit-identical to the same run with
# prefix reuse off (the bench asserts output equality and reports parity
# as 1.0)
assert s["paged_capacity_multiplier"] > 1.5, s
assert s["paged_token_parity"] == 1.0, s
assert s["paged_pages_live"] > 0 and s["paged_pages_shared"] > 0, s
# preemptible serving (ISSUE 8): the bench injects pool exhaustion on both
# schedulers and asserts preempted requests recompute to the unfaulted
# run's exact tokens; these flags are 1.0 only when that whole gate held
assert s["preempt_recompute_parity"] == 1.0, s
assert s["fault_smoke_pass"] == 1.0, s
# speculative decoding (ISSUE 9): the verify step must commit >1.2 tokens
# per step on the repetitive-tail scenario (the weight-stream amortization
# the skinny GEMMs exist for) while the bench's parity assertion holds —
# spec_token_parity is 1.0 only when --speculate 4 emitted bit-identical
# greedy tokens to plain decode on BOTH schedulers
assert s["spec_tokens_per_step"] > 1.2, s
assert s["spec_token_parity"] == 1.0, s
assert s["spec_acceptance_rate"] > 0, s
# tensor-parallel serving (ISSUE 10): the bench runs the fully-composed
# --tp 2 cell on a forced 2-device mesh and asserts greedy-token identity
# with the 1-device run (integer psum is exact, so this is bitwise);
# the interconnect ratio is the modeled wire-byte win of circulating
# packed int8 shards instead of f32 in the weight-moving collectives
assert s["tp_token_parity"] == 1.0, s
assert s["tp_interconnect_byte_ratio"] >= 2.0, s
# bandwidth-bound rows must carry the GB/s roofline column
names = {r["name"] for r in d["rows"]}
for prefix in ("blas_gemv_", "blas_bgemv_", "blas_ddot_"):
    row = next(r for r in d["rows"] if r["name"].startswith(prefix))
    assert "pct_bw" in row["metrics"] and "gbs" in row["metrics"], row
print("BENCH_kernels.json schema OK:", json.dumps(s))
PY

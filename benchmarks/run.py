"""Benchmark harness: one module per paper table/figure + roofline/kernels.

Prints ``name,us_per_call,derived`` CSV (one row per measurement).  With
``--json PATH`` it also writes a machine-readable report (schema below) so
the perf trajectory — GFLOP/s, %-of-roofline, fused-vs-unfused speedup,
quantized-vs-f32 speedup — is tracked across PRs; CI validates the schema
on every push.

``--autotune`` sets REPRO_AUTOTUNE=1 before any benchmark module imports
jax-heavy code, so every `ops.*` call tunes its block shape empirically on
the live backend (top-K analytic candidates measured, winner cached): the
fused variants are then measured at their TUNED blocks instead of the
analytic guess.  The cache defaults to the user cache; point
REPRO_AUTOTUNE_CACHE somewhere writable in CI.

JSON schema (schema_version 1):

    {
      "schema_version": 1,
      "host_backend": "cpu" | "tpu" | ...,
      "modules": ["benchmarks.bench_kernels", ...],
      "rows": [{"name": str, "us_per_call": float,
                "metrics": {str: float | str}}, ...],
      "summary": {"max_gflops": float,          # best observed GFLOP/s
                  "pct_roofline": float,        # blockspec roofline fraction
                  "fused_speedup": float,       # best fused/unfused ratio
                  "min_fused_speedup": float,   # worst fused/unfused ratio
                  "fused_structural_win": bool, # launches+HBM strictly fewer
                  "quant_speedup": float,       # best quantized/f32 ratio
                  "quant_weight_bytes_ratio": float,  # min modeled full/packed
                  "kv_quant_speedup": float,    # best int8-KV stream ratio
                  "combined_byte_ratio": float, # min modeled weights+KV vs
                                                # weights-only decode bytes
                  "stall_tokens_chunked": float,    # worst inter-token stall
                  "stall_tokens_unchunked": float,  # (prefill tokens) under
                                                    # mixed serve traffic
                  "max_stall_ms": float,            # wall-clock stall, chunked
                  "max_stall_ms_unchunked": float,  # ... and unchunked
                  "ttft_p95": float,            # chunked-admission TTFT p95 (s)
                  "paged_capacity_multiplier": float,  # logical/physical pages
                                                       # under a shared prefix
                  "paged_token_parity": float,  # 1.0 iff shared == unshared tokens
                  "paged_pages_live": float,    # peak distinct physical pages
                  "paged_pages_shared": float,  # peak pages with refcount > 1
                  "preempt_recompute_parity": float,  # 1.0 iff preempted
                                                # requests recompute to the
                                                # unfaulted run's exact tokens
                  "fault_smoke_pass": float,    # 1.0 iff the injected
                                                # exhaustion fired, preempted,
                                                # and conserved pages
                  "spec_tokens_per_step": float,  # tokens committed per
                                                # verify step under
                                                # --speculate k (>1 = win)
                  "spec_token_parity": float,   # 1.0 iff --speculate k
                                                # emitted bit-identical
                                                # greedy tokens on both
                                                # schedulers
                  "spec_acceptance_rate": float,  # accepted/proposed drafts
                  "tp_token_parity": float,     # 1.0 iff --tp 2 emitted
                                                # bit-identical greedy tokens
                                                # to the 1-device run on the
                                                # fully-composed cell
                  "tp_interconnect_byte_ratio": float}  # modeled wire-byte
                                                # reduction of packed int8
                                                # shards vs f32 in the
                                                # weight-moving collectives
    }
"""

import argparse
import importlib
import json
import os
import sys
import traceback

from repro.launch import compile_cache

MODULES = [
    "benchmarks.bench_pe_tables",       # paper Tables 4-9
    "benchmarks.bench_fig11",           # paper Fig 11 (CPF/FPC/%peak/alpha)
    "benchmarks.bench_fig12",           # paper Fig 12 (tile scaling)
    "benchmarks.bench_fig2_offtheshelf",  # paper Fig 2 (host measurement)
    "benchmarks.bench_kernels",         # BLAS timings + BlockSpec table
    "benchmarks.bench_batched",         # fused batched BLAS vs per-item loops
    "benchmarks.bench_fused_epilogue",  # epilogue fusion vs unfused chains
    "benchmarks.bench_quantized",       # packed int8 weight streaming vs f32
    "benchmarks.bench_serve",           # continuous vs batch-at-a-time serving
    "benchmarks.bench_roofline",        # deliverable (g) roofline table
]


def _parse_metrics(derived: str) -> dict:
    """'k=v;k=v' derived strings -> {k: float|str} (floats where they parse;
    trailing x/%% markers stripped for the numeric fields)."""
    metrics = {}
    for part in str(derived).split(";"):
        if "=" not in part:
            continue
        key, val = part.split("=", 1)
        raw = val.rstrip("x%")
        try:
            metrics[key] = float(raw)
        except ValueError:
            metrics[key] = val
    return metrics


def _summarize(rows: list[dict]) -> dict:
    gflops, roofline, speedups, structural = [], [], [], []
    q_speedups, q_ratios, kv_speedups, combined = [], [], [], []
    stall = {}
    paged = {}
    robust = {}
    spec = {}
    tp = {}
    for row in rows:
        m = row["metrics"]
        if row["name"].startswith("serve_speculative_k"):
            # speculative decoding (ISSUE 9): tokens committed per verify
            # step (the amortization CI gates) + parity + acceptance — the
            # bench asserts bit-identical greedy tokens itself and emits
            # these as plain floats
            spec = {k: m[k] for k in ("spec_tokens_per_step",
                                      "spec_token_parity",
                                      "spec_acceptance_rate")
                    if isinstance(m.get(k), float)}
        if row["name"] == "serve_tp2":
            # tensor-parallel packed-weight serving (ISSUE 10): the bench
            # asserts token identity vs the 1-device run itself; the wire
            # ratio is the modeled int8-shard interconnect win CI gates
            tp = {k: m[k] for k in ("tp_token_parity",
                                    "tp_interconnect_byte_ratio")
                  if isinstance(m.get(k), float)}
        if row["name"] == "serve_preempt_recompute":
            # preemption + exact recompute under injected exhaustion
            # (ISSUE 8): the bench asserts parity itself and emits 1.0 flags
            robust = {k: m[k] for k in ("preempt_recompute_parity",
                                        "fault_smoke_pass")
                      if isinstance(m.get(k), float)}
        if row["name"] == "serve_paged_shared_prefix":
            # paged KV cache + shared-prefix reuse (ISSUE 7): effective-
            # capacity multiplier and token parity with reuse off, for the CI gate
            paged = {k: m[k] for k in ("paged_capacity_multiplier",
                                       "pages_live", "pages_shared",
                                       "token_parity")
                     if isinstance(m.get(k), float)}
        if row["name"] == "serve_mixed_chunked_vs_unchunked":
            # chunked-admission head-of-line blocking (ISSUE 6): the bench
            # emits these as plain floats so CI can gate the stall reduction
            stall = {k: m[k] for k in ("stall_tokens_chunked",
                                       "stall_tokens_unchunked",
                                       "max_stall_ms_chunked",
                                       "max_stall_ms_unchunked",
                                       "ttft_p95")
                     if isinstance(m.get(k), float)}
        for key in ("gflops", "gflops_fused"):
            if isinstance(m.get(key), float):
                gflops.append(m[key])
        if isinstance(m.get("pct_roofline"), float):
            roofline.append(m["pct_roofline"])
        if isinstance(m.get("speedup"), float) and (
            "unfused_us" in m or row["name"].startswith("fused_")
        ):
            speedups.append(m["speedup"])
            structural.append(str(m.get("structural_win", "")) == "True")
        if row["name"].startswith("quant_"):
            if isinstance(m.get("speedup"), float):
                q_speedups.append(m["speedup"])
            if isinstance(m.get("weight_bytes_ratio"), float):
                q_ratios.append(m["weight_bytes_ratio"])
            if isinstance(m.get("weight_read_reduction"), float):
                q_ratios.append(m["weight_read_reduction"])
            if isinstance(m.get("kv_speedup"), float):
                kv_speedups.append(m["kv_speedup"])
            if isinstance(m.get("combined_byte_ratio"), float):
                combined.append(m["combined_byte_ratio"])
    return {
        "max_gflops": max(gflops) if gflops else 0.0,
        "pct_roofline": max(roofline) if roofline else 0.0,
        "fused_speedup": max(speedups) if speedups else 0.0,
        "min_fused_speedup": min(speedups) if speedups else 0.0,
        "fused_structural_win": bool(structural) and all(structural),
        "quant_speedup": max(q_speedups) if q_speedups else 0.0,
        "quant_weight_bytes_ratio": min(q_ratios) if q_ratios else 0.0,
        # int8 KV cache (ISSUE 5): measured K-stream win + the modeled
        # combined (weights+KV) decode byte reduction vs weights-only
        "kv_quant_speedup": max(kv_speedups) if kv_speedups else 0.0,
        "combined_byte_ratio": min(combined) if combined else 0.0,
        # chunked admission under mixed serve traffic (ISSUE 6): worst
        # inter-token stall for live slots, chunked vs unchunked admissions
        "stall_tokens_chunked": stall.get("stall_tokens_chunked", 0.0),
        "stall_tokens_unchunked": stall.get("stall_tokens_unchunked", 0.0),
        "max_stall_ms": stall.get("max_stall_ms_chunked", 0.0),
        "max_stall_ms_unchunked": stall.get("max_stall_ms_unchunked", 0.0),
        "ttft_p95": stall.get("ttft_p95", 0.0),
        # paged KV cache with shared-prefix reuse (ISSUE 7): per-slot logical
        # pages / distinct physical pages (peak) under a shared system
        # prompt, plus greedy-token parity of the shared run vs reuse off
        "paged_capacity_multiplier": paged.get("paged_capacity_multiplier", 0.0),
        "paged_token_parity": paged.get("token_parity", 0.0),
        "paged_pages_live": paged.get("pages_live", 0.0),
        "paged_pages_shared": paged.get("pages_shared", 0.0),
        # preemptible, fault-tolerant serving (ISSUE 8): bit-exact recompute
        # of preempted requests + the fault-injection smoke, both asserted
        # inside the bench and surfaced here for the CI schema gate
        "preempt_recompute_parity": robust.get("preempt_recompute_parity", 0.0),
        "fault_smoke_pass": robust.get("fault_smoke_pass", 0.0),
        # speculative decoding (ISSUE 9): self-drafted verify windows turn
        # decode GEMVs into skinny GEMMs; tokens/step is the weight-stream
        # amortization factor, parity the correctness gate
        "spec_tokens_per_step": spec.get("spec_tokens_per_step", 0.0),
        "spec_token_parity": spec.get("spec_token_parity", 0.0),
        "spec_acceptance_rate": spec.get("spec_acceptance_rate", 0.0),
        # tensor-parallel serving (ISSUE 10): greedy-token identity of the
        # --tp 2 mesh run and the modeled packed-shard wire-byte reduction
        "tp_token_parity": tp.get("tp_token_parity", 0.0),
        "tp_interconnect_byte_ratio": tp.get("tp_interconnect_byte_ratio", 0.0),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filters on module names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the machine-readable report (e.g. "
                         "BENCH_kernels.json)")
    ap.add_argument("--autotune", action="store_true",
                    help="REPRO_AUTOTUNE=1: measure top-K analytic block-"
                         "shape candidates on the live backend so fused "
                         "variants run at tuned blocks")
    args = ap.parse_args()
    if args.autotune:
        # before the benchmark modules import and touch ops: the tuner reads
        # the env at first kernel call
        os.environ["REPRO_AUTOTUNE"] = "1"
    compile_cache.enable()
    filters = args.only.split(",") if args.only else None
    print("name,us_per_call,derived")
    failed = []
    report_rows = []
    ran = []
    for modname in MODULES:
        if filters and not any(f in modname for f in filters):
            continue
        try:
            mod = importlib.import_module(modname)
            ran.append(modname)
            for name, us, derived in mod.rows():
                print(f"{name},{us},{derived}")
                report_rows.append({
                    "name": name,
                    "us_per_call": float(us),
                    "metrics": _parse_metrics(derived),
                })
        except Exception:
            failed.append(modname)
            traceback.print_exc(file=sys.stderr)
    if args.json:
        import jax
        report = {
            "schema_version": 1,
            "host_backend": jax.default_backend(),
            "modules": ran,
            "rows": report_rows,
            "summary": _summarize(report_rows),
        }
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
        print(f"wrote {args.json} ({len(report_rows)} rows)", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark modules failed: {failed}")


if __name__ == "__main__":
    main()

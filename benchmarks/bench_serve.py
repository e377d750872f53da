"""Continuous batching vs batch-at-a-time serving, measured.

Mixed-length request distribution (gen ~ U{gen_min..gen_max}): the batch
scheduler drains every group to its longest member, so short requests finish
early and their slots idle — wasted HBM bandwidth for every decode launch
(the broadcast-A bgemv amortizes weight traffic over LIVE slots only).  The
continuous scheduler re-admits into freed slots immediately.  Both runs use
the same params, prompts, and per-request budgets, so tokens are identical
and the delta is pure scheduling: decode steps, mean live-slot occupancy,
tok/s, and TTFT percentiles.

    PYTHONPATH=src python benchmarks/bench_serve.py [--backend pallas]
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.launch.serve import serve


def rows(arch: str = "stablelm-1.6b", variant: str = "smoke", requests: int = 24,
         batch: int = 4, prompt_len: int = 16, gen_min: int = 4, gen_max: int = 64,
         seed: int = 0, backend: str = "xla"):
    rng = np.random.default_rng(seed)
    gen_lens = rng.integers(gen_min, gen_max + 1, size=requests).tolist()
    out = []
    results = {}
    for sched in ("batch", "continuous"):
        stats = serve(arch, variant, batch=batch, prompt_len=prompt_len,
                      gen_lens=gen_lens, seed=seed, eos=-1, verbose=False,
                      backend=backend, scheduler=sched)
        results[sched] = stats
        ttft = np.asarray(stats["ttft"])
        out.append((
            f"serve_{sched}_b{batch}_r{requests}_gen{gen_min}-{gen_max}",
            round(stats["tok_s"], 1),
            f"tokens={stats['tokens']};decode_steps={stats['decode_steps']};"
            f"occupancy={stats['occupancy']:.2f};prefills={stats['prefills']};"
            f"ttft_p50={np.percentile(ttft, 50):.2f}s;"
            f"ttft_p95={np.percentile(ttft, 95):.2f}s",
        ))
    c, b = results["continuous"], results["batch"]
    assert c["tokens"] == b["tokens"], "schedulers must serve identical work"
    out.append((
        "serve_continuous_vs_batch",
        round(c["tok_s"] / b["tok_s"], 2),
        f"tok_s_speedup={c['tok_s'] / b['tok_s']:.2f}x;"
        f"decode_steps={c['decode_steps']}_vs_{b['decode_steps']};"
        f"occupancy={c['occupancy']:.2f}_vs_{b['occupancy']:.2f};"
        f"ttft_p95={np.percentile(np.asarray(c['ttft']), 95):.2f}s"
        f"_vs_{np.percentile(np.asarray(b['ttft']), 95):.2f}s",
    ))
    out.extend(mixed_traffic_rows(arch, variant, seed=seed, backend=backend))
    out.extend(shared_prefix_rows(arch, variant, seed=seed, backend=backend))
    out.extend(preempt_recompute_rows(arch, variant, seed=seed, backend=backend))
    out.extend(speculative_rows(arch, variant, seed=seed, backend=backend))
    out.extend(tensor_parallel_rows(arch, variant, seed=seed, backend=backend))
    return out


def _tp_parity(arch: str, variant: str, requests: int, batch: int,
               prompt_len: int, gen: int, k: int, seed: int, tp: int) -> dict:
    """Serve the fully-composed cell at tp=1 and at `tp` on the visible
    devices, and say whether the greedy tokens match bit for bit."""
    from repro.models.registry import get_config

    cfg = get_config(arch, variant)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, cfg.vocab, size=(prompt_len,), dtype=np.int32)
               for _ in range(requests)]
    gen_lens = rng.integers(3, gen + 1, size=requests).tolist()
    kw = dict(batch=batch, prompts=prompts, gen_lens=gen_lens, seed=seed,
              eos=-1, verbose=False, scheduler="continuous",
              quantize="int8", kv_cache="int8", kv_page_size=4, speculate=k)
    one = serve(arch, variant, **kw)
    many = serve(arch, variant, tp=tp, **kw)
    return {"parity": many["outputs"] == one["outputs"],
            "completed": many["completed"],
            "tok_s_tp1": one["tok_s"], "tok_s_tp2": many["tok_s"]}


def tensor_parallel_rows(arch: str = "stablelm-1.6b", variant: str = "smoke",
                         requests: int = 3, batch: int = 2,
                         prompt_len: int = 5, gen: int = 6, k: int = 4,
                         seed: int = 0, backend: str = "xla"):
    """Tensor-parallel serving: --tp 2 shards the packed int8 weights, KV
    heads and page pools across a 2-device "model" mesh and runs the
    decode/verify boundary projections as collective packed-int8 GEMMs with
    one integer psum per layer boundary.

    The pair runs on the fully-composed cell (--quantize int8 --kv-cache
    int8 --kv-page-size 4 --speculate k).  On an accelerator it runs in this
    process, on the real devices: the chips belong to the process that first
    touched them, so a child could not reach them.  On the CPU, jax locks
    the host device count at first init, so the pair runs in a child with a
    FORCED 2-device host platform.
    `tp_token_parity` is 1.0 iff the tp=2 greedy tokens are identical to the
    1-device run's.  On the CPU's forced mesh they are, and the bench
    asserts it.  On chips the sharded program sums in another order than the
    one-device program and the tokens can part ways (PERF.md), so there the
    value is reported, not asserted.  `tp_interconnect_byte_ratio` is the modeled wire-byte
    reduction of circulating packed int8 shards instead of f32 in the
    weight-moving schedules (≈3.76x, the co-design headline); the modeled
    per-chip rows translate the sharding into decode_byte_terms(chips=2):
    resident weight/KV bytes halve while the new interconnect term — f32
    boundary reductions, independent of weight precision — is what buys it.
    """
    if backend != "xla":
        return []  # --tp shards the xla serving path only
    import jax

    args = (arch, variant, requests, batch, prompt_len, gen, k, seed, 2)
    on_cpu = jax.default_backend() == "cpu"
    if not on_cpu:
        if len(jax.devices()) < 2:
            return []  # one chip: there is no mesh to shard over
        meas = _tp_parity(*args)
    else:
        import json
        import os
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
        code = ("import json; from benchmarks.bench_serve import _tp_parity; "
                f"print(json.dumps(_tp_parity(*{args!r})))")
        res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=1200, env=env)
        assert res.returncode == 0, \
            f"tp bench subprocess failed:\n{res.stdout}\n{res.stderr[-4000:]}"
        meas = json.loads(res.stdout.strip().splitlines()[-1])
    assert meas["parity"] or not on_cpu, "--tp 2 diverged from the 1-device run"
    assert meas["completed"] == requests

    from repro.configs.base import ShapeCell
    from repro.launch import roofline
    from repro.models.registry import get_config

    cfg = get_config(arch, "full")
    cell = ShapeCell(f"decode_b{batch}_s4096", 4096, batch, "decode")
    solo = roofline.decode_byte_terms(cfg, cell)
    duo = roofline.decode_byte_terms(cfg, cell, chips=2)
    wire = roofline.tp_interconnect_byte_ratio()
    return [(
        "serve_tp2",
        round(wire, 4),
        # plain floats so run.py's summary (and the CI gate) parse them
        f"tp_token_parity={float(meas['parity'])};"
        f"tp_interconnect_byte_ratio={wire:.4f};"
        f"tp_devices=2.0;"
        f"tok_s_tp1={meas['tok_s_tp1']:.1f};"
        f"tok_s_tp2={meas['tok_s_tp2']:.1f};"
        f"modeled_per_chip_weight_bytes_ratio={solo['weights'] / duo['weights']:.4f};"
        f"modeled_interconnect_bytes={duo['interconnect']:.1f};"
        f"modeled_per_chip_total_ratio={solo['total'] / duo['total']:.4f}",
    )]


def speculative_rows(arch: str = "stablelm-1.6b", variant: str = "smoke",
                     requests: int = 4, batch: int = 4, prompt_len: int = 16,
                     gen: int = 64, k: int = 4, seed: int = 0,
                     backend: str = "xla"):
    """Speculative decoding (ISSUE 9): self-drafted verify turns the decode
    GEMVs into (k+1)-row skinny GEMMs, committing tokens/step = 1 + k*accept
    and amortizing one weight stream over all of them.

    The scenario is the regime speculation targets: prompts that drive
    greedy decode into its repetitive tail (the behaviour real models show
    on code/boilerplate; this model's greedy trajectory provably collapses
    to a repeating suffix on broad-vocab prompts within a few tokens),
    which the n-gram drafter then predicts near-perfectly — with gen=64
    the repetitive regime dominates the measurement the way long
    completions dominate real serving.  Parity is asserted, not sampled:
    the --speculate k run
    must emit BIT-IDENTICAL greedy tokens to --speculate 0 on BOTH
    schedulers (acceptance only decides how many tokens arrive per step,
    never which).  `spec_tokens_per_step` is the measured speedup knob CI
    gates (> 1.2); the modeled rows translate it into the roofline's
    per-token weight-byte reduction via
    roofline.decode_byte_terms(draft_k=k, accept_rate=measured).
    """
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 1000, size=(prompt_len,), dtype=np.int32)
               for _ in range(requests)]
    gen_lens = [gen] * requests
    results = {}
    for sched in ("continuous", "batch"):
        kw = dict(batch=batch, prompts=prompts, gen_lens=gen_lens, seed=seed,
                  eos=-1, verbose=False, backend=backend, scheduler=sched)
        base = serve(arch, variant, **kw)
        spec = serve(arch, variant, speculate=k, **kw)
        assert spec["outputs"] == base["outputs"], \
            f"{sched}: --speculate {k} diverged from plain greedy decode"
        results[sched] = spec
    spec = results["continuous"]
    tps = spec["spec_tokens_per_step"]
    acc = spec["spec_acceptance_rate"]

    from repro.configs.base import ShapeCell
    from repro.launch import roofline
    from repro.models.registry import get_config

    cfg = get_config(arch, "full")
    cell = ShapeCell(f"decode_b{batch}_s4096", 4096, batch, "decode")
    plain = roofline.decode_byte_terms(cfg, cell)
    amort = roofline.decode_byte_terms(cfg, cell, draft_k=k, accept_rate=acc)
    return [(
        f"serve_speculative_k{k}",
        round(tps, 4),
        # plain floats so run.py's summary (and the CI gate) parse them
        f"spec_tokens_per_step={tps:.4f};"
        f"spec_token_parity=1.0;"
        f"spec_acceptance_rate={acc:.4f};"
        f"spec_tokens_per_step_batch={results['batch']['spec_tokens_per_step']:.4f};"
        f"draft_k={float(k)};"
        f"modeled_weight_bytes_ratio={plain['weights'] / amort['weights']:.4f};"
        f"modeled_total_bytes_ratio={plain['total'] / amort['total']:.4f};"
        f"accept_hist={'/'.join(str(c) for c in spec['spec_accept_hist'])}",
    )]


def preempt_recompute_rows(arch: str = "stablelm-1.6b", variant: str = "smoke",
                           requests: int = 6, batch: int = 2,
                           prompt_len: int = 10, gen_max: int = 8,
                           page_size: int = 4, seed: int = 0,
                           backend: str = "xla"):
    """Preemption with exact recompute (ISSUE 8): inject a pool-exhaustion
    fault into a paged serving run on BOTH schedulers (with the per-round
    invariant sweep on) and assert the preempted requests' recomputed
    streams are bit-identical to the unfaulted run's — the fault-tolerance
    acceptance gate.  `preempt_recompute_parity` is 1.0 iff every scheduler
    reproduced the unfaulted greedy tokens exactly; `fault_smoke_pass` is
    1.0 iff the injected fault actually fired, at least one slot was
    preempted and resumed, and end-of-serve page conservation held."""
    rng = np.random.default_rng(seed)
    gen_lens = rng.integers(4, gen_max + 1, size=requests).tolist()
    prompts = [rng.integers(3, 256, size=(prompt_len,), dtype=np.int32)
               for _ in range(requests)]
    preemptions = {}
    tok_s = 0.0
    for sched in ("continuous", "batch"):
        kw = dict(batch=batch, prompts=prompts, gen_lens=gen_lens, seed=seed,
                  eos=-1, verbose=False, backend=backend, scheduler=sched,
                  kv_page_size=page_size)
        base = serve(arch, variant, **kw)
        fx = serve(arch, variant, faults="exhaust@0", check_invariants=True,
                   **kw)
        assert fx["outputs"] == base["outputs"], \
            f"{sched}: preempted recompute diverged from the unfaulted run"
        assert fx["preemptions"] >= 1, f"{sched}: exhaustion never preempted"
        assert "preempted_resumed" in fx["status"], fx["status"]
        assert ("exhaust", 0) in fx["faults_fired"], fx["faults_fired"]
        assert fx["completed"] == requests
        preemptions[sched] = fx["preemptions"]
        tok_s = fx["tok_s"]
    return [(
        "serve_preempt_recompute",
        round(tok_s, 1),
        # plain floats so run.py's summary (and the CI gate) parse them
        f"preempt_recompute_parity=1.0;"
        f"fault_smoke_pass=1.0;"
        f"preemptions_continuous={float(preemptions['continuous'])};"
        f"preemptions_batch={float(preemptions['batch'])};"
        f"kv_page_size={float(page_size)}",
    )]


def shared_prefix_rows(arch: str = "stablelm-1.6b", variant: str = "smoke",
                       requests: int = 8, batch: int = 4, sys_len: int = 48,
                       tail: int = 4, gen: int = 12, page_size: int = 8,
                       seed: int = 0, backend: str = "xla"):
    """Shared-prefix serving (ISSUE 7): every request opens with the same
    `sys_len`-token system prompt, with a short unique tail.  The control
    is the same paged run with prefix reuse off, which stores the prefix
    once PER SLOT; with reuse on the scheduler hashes it page by page at
    admission and backs all concurrent slots with the same physical pages,
    so the pool holds the prefix ONCE.  Both runs serve identical work and
    greedy tokens are asserted identical — the shared run's capacity
    multiplier (per-slot logical pages / distinct physical pages, peak over
    the run) is the effective-capacity win CI gates (> 1.5x at batch 4 with
    a prefix this long)."""
    rng = np.random.default_rng(seed)
    sysp = rng.integers(3, 256, size=(sys_len,), dtype=np.int32)
    prompts = [
        np.concatenate([sysp, rng.integers(3, 256, size=(tail,), dtype=np.int32)])
        for _ in range(requests)
    ]
    gen_lens = [gen] * requests
    kw = dict(batch=batch, prompts=prompts, gen_lens=gen_lens, seed=seed,
              eos=-1, verbose=False, backend=backend, scheduler="continuous",
              kv_page_size=page_size)
    unshared = serve(arch, variant, prefix_reuse=False, **kw)
    paged = serve(arch, variant, **kw)
    assert paged["outputs"] == unshared["outputs"], \
        "prefix reuse must be greedy-token identical to unshared pages"
    assert paged["completed"] == unshared["completed"] == requests
    return [(
        "serve_paged_shared_prefix",
        round(paged["tok_s"], 1),
        # plain floats so run.py's summary (and the CI gate) parse them
        f"paged_capacity_multiplier={paged['paged_capacity_multiplier']:.4f};"
        f"pages_live={float(paged['pages_live'])};"
        f"pages_shared={float(paged['pages_shared'])};"
        f"cow_copies={float(paged['cow_copies'])};"
        f"kv_page_size={float(page_size)};"
        f"token_parity=1.0;"
        f"tok_s_unshared={unshared['tok_s']:.1f};"
        f"tok_s_paged={paged['tok_s']:.1f}",
    )]


def mixed_traffic_rows(arch: str = "stablelm-1.6b", variant: str = "smoke",
                       batch: int = 3, long_prompt: int = 192, chunk: int = 32,
                       seed: int = 0, backend: str = "xla"):
    """Head-of-line blocking under mixed traffic: short interactive requests
    are decoding when one long-prompt request arrives.  Unchunked, the
    admission prefill processes the whole prompt between two decode steps of
    the live slots (worst inter-token stall = `long_prompt` prefill tokens);
    chunked, the same admission interleaves decode rounds at every chunk
    boundary (worst stall = `chunk` tokens).  Greedy tokens are asserted
    identical, so the delta is pure scheduling.

    `stall_tokens` (prefill tokens processed between two consecutive decode
    steps while live slots exist) is the deterministic form of the stall —
    wall-clock `max_stall_ms` is also reported but includes jit-trace noise
    on first-seen prefill shapes.
    """
    rng = np.random.default_rng(seed)
    vocab_lo, vocab_hi = 3, 256
    short = 8

    def _prompt(n):
        return rng.integers(vocab_lo, vocab_hi, size=(n,), dtype=np.int32)

    # 3 short requests fill the grid; rid 0 finishes early and frees a slot
    # for the long-prompt admission while rids 1-2 are still decoding; two
    # short tails keep the grid busy after the long request drains.
    prompts = [_prompt(short), _prompt(short), _prompt(short),
               _prompt(long_prompt), _prompt(short), _prompt(short)]
    gen_lens = [4, 48, 48, 4, 8, 8]

    results = {}
    out = []
    for mode, pchunk in (("unchunked", None), ("chunked", chunk)):
        stats = serve(arch, variant, batch=batch, prompts=prompts,
                      gen_lens=gen_lens, seed=seed, eos=-1, verbose=False,
                      backend=backend, scheduler="continuous",
                      prefill_chunk=pchunk)
        results[mode] = stats
        ttft = np.asarray(stats["ttft"])
        out.append((
            f"serve_mixed_{mode}_b{batch}_p{long_prompt}",
            round(stats["tok_s"], 1),
            f"tokens={stats['tokens']};decode_steps={stats['decode_steps']};"
            f"ttft_p50={np.percentile(ttft, 50):.2f}s;"
            f"ttft_p95={np.percentile(ttft, 95):.2f}s;"
            f"max_stall_ms={stats['max_stall_ms']:.1f};"
            f"stall_tokens={stats['max_stall_prefill_tokens']}",
        ))
    ch, un = results["chunked"], results["unchunked"]
    assert ch["outputs"] == un["outputs"], \
        "chunked admission must generate bit-identical greedy tokens"
    assert ch["max_stall_prefill_tokens"] < un["max_stall_prefill_tokens"], \
        (ch["max_stall_prefill_tokens"], un["max_stall_prefill_tokens"])
    out.append((
        "serve_mixed_chunked_vs_unchunked",
        round(un["max_stall_prefill_tokens"]
              / max(1, ch["max_stall_prefill_tokens"]), 2),
        # floats without unit suffixes so run.py's summary parses them
        f"stall_tokens_chunked={ch['max_stall_prefill_tokens']};"
        f"stall_tokens_unchunked={un['max_stall_prefill_tokens']};"
        f"max_stall_ms_chunked={ch['max_stall_ms']:.2f};"
        f"max_stall_ms_unchunked={un['max_stall_ms']:.2f};"
        f"ttft_p95={float(np.percentile(np.asarray(ch['ttft']), 95)):.4f}",
    ))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-min", type=int, default=4)
    ap.add_argument("--gen-max", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="xla", choices=("xla", "pallas", "ref"))
    args = ap.parse_args()
    for name, val, extra in rows(args.arch, args.variant, args.requests,
                                 args.batch, args.prompt_len, args.gen_min,
                                 args.gen_max, args.seed, args.backend):
        print(f"{name:48s} {val:10.1f}  {extra}")


if __name__ == "__main__":
    main()

"""CPU tests of the chip benchmark's yardstick and harness.

They run at a smoke size (two layers, d=256 and d=64) on the CPU, with the
Pallas kernels in interpret mode, and check what a CPU can: the arithmetic
of every metric, the trace reduction on a hand-built trace in the
profiler's format, discovery of configurations, traffic and metrics by
name, the reference against the program's plain-jnp backend, and that the
output check fails the control and a program whose tokens are altered where
they are produced.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import (counts, harness, reference, spec as spec_mod,  # noqa: E402
                       trace_reduce, traffic, weights)
from chipbench.window import Window, percentile  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

#: stablelm's shape at a size a CPU holds, with the published head size (64):
#: at d=64 bf16 and int8 rounding do not separate, at d=256 they do
SMOKE_CONFIG = {
    "model_type": "stablelm", "hidden_size": 256, "intermediate_size": 512,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 2048, "rope_theta": 10000,
    "partial_rotary_factor": 1.0, "layer_norm_eps": 1e-5,
    "use_qkv_bias": True, "hidden_act": "silu", "tie_word_embeddings": False,
    "serve": {"slots": 2, "weights": "bfloat16", "kv_cache": "bfloat16",
              "page_size": 4},
    "program": {"registry": "stablelm-1.6b", "variant": "smoke",
                "overrides": {"dtype": "bfloat16", "d_model": 256,
                              "d_ff": 512, "vocab": 2048, "head_dim": 64}},
    # readings at this size (CPU, seeds 1-6): program at most 0.014,
    # control (int8) at least 0.045
    "correct": {"max_logit_gap": 0.025},
}
#: the other configuration's shape at smoke size: RMSNorm, GQA, no biases
SMOKE_RMS_GQA = {
    "model_type": "internlm2", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 512, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6, "bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False,
    "serve": {"slots": 2, "weights": "int8", "kv_cache": "int8",
              "page_size": 4},
    "program": {"registry": "internlm2-20b", "variant": "smoke",
                "overrides": {"dtype": "bfloat16"}},
    # readings at this size (CPU, seeds 1-3 and 2**31 + 5): program at most
    # 0.082, control (int4) at least 1.45
    "correct": {"max_logit_gap": 0.3},
}
TINY_MIX = {"loop": "closed", "prompt_len": 8, "block": 8,
            "gen": {"dist": "lognormal", "median": 4, "sigma": 0.8,
                    "min": 2, "max": 8}}


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the persistent compilation cache, a process-wide setting, off
    in the test process."""
    from chipbench import adapter
    monkeypatch.setattr(adapter, "enable_cache", lambda path: None)


def make_root(tmp: Path, config=SMOKE_CONFIG, extra_metric=None) -> tuple:
    """A checkout-like tree: BENCHMARK.json naming one smoke cell, with its
    configuration, traffic, metric readers, peaks and kernel names."""
    import jax

    bench = tmp / "bench"
    shutil.copytree(HERE / "metrics", bench / "metrics")
    (bench / "traffic").mkdir()
    (bench / "configs").mkdir()
    shutil.copy(HERE / "kernel_names.json", bench)
    # a CPU's peak, well above what the smoke model reaches, so that the
    # queue the roofline sizes never runs dry in a second
    (bench / "peaks.json").write_text(json.dumps({
        jax.devices()[0].device_kind: {
            "source": "test", "bf16_flops": 5e10, "int8_ops": 1e11,
            "hbm_bytes_per_s": 1e9, "hbm_bytes": 1e9}}))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    (bench / "configs" / "smoke.json").write_text(json.dumps(config))
    b = json.loads(json.dumps(BENCH))
    b["configs"] = [{"name": "smoke", "source": "test",
                     "file": "bench/configs/smoke.json", "reduced": [],
                     "why": "test"}]
    b["workloads"] = [{"name": "smoke.tiny", "config": "smoke",
                       "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in b["per_layer"]:
        m["workloads"] = ["smoke.tiny"]
    if extra_metric:
        name, source = extra_metric
        (bench / "metrics" / f"{name}.py").write_text(source)
        b["end_to_end"].append({"name": name, "unit": "1", "better": "higher",
                                "bound": 0.25, "source": "host_clock"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp, bench


# --------------------------------------------------------------------------
# metric arithmetic on hand-made scheduler records
# --------------------------------------------------------------------------

def _window():
    # two slots, window of 10 s.  r0: t 1,2,3 (done at 3); r1: t 1,5 (done
    # at 5); r2 is sent when r0 finishes (3) and starts at 4; r3 is sent at
    # 5 and starts at 6, finishing after the window.
    stats = {
        "ttft": [1.0, 1.0, 4.0, 6.0, 12.0],
        "token_times": [[1.0, 2.0, 3.0], [1.0, 5.0], [4.0, 4.5],
                        [6.0, 9.0, 11.0], [12.0]],
        "status": ["ok", "ok", "ok", "timeout", "timeout"],
    }
    return Window.of(stats, 10.0, slots=2, prompt_len=8)


def test_window_tokens_and_rate():
    w = _window()
    assert w.tokens() == 3 + 2 + 2 + 2
    assert w.tokens() / w.seconds == pytest.approx(0.9)


def test_closed_loop_ttft():
    # r0, r1 from t0; r2 from r0's finish (3); r3 from the second finish (4.5)
    assert _window().closed_loop_ttft() == pytest.approx([1.0, 1.0, 1.0, 1.5])


def test_itl_gaps_inside_the_window():
    assert sorted(_window().itl_gaps()) == pytest.approx(
        sorted([1.0, 1.0, 4.0, 0.5, 3.0]))
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([], 95) is None


def test_prefill_row_use():
    w = _window()
    # admitted r0..r3 in three launches (first tokens 1, 4, 6) of 2 rows
    assert w.prefill_launches() == 3
    assert w.prefill_row_use() == pytest.approx(4 / 6)


def test_useful_flops_counts_prompts_and_decoded_tokens():
    spec, _ = spec_mod.load(HERE / "configs" / "stablelm-1.6b.json")
    w = _window()
    want = 4 * counts.prompt_flops(spec, 8) + sum(
        counts.decode_flops(spec, 8 + k) for k in (1, 2, 1, 1, 1))
    assert w.useful_flops(spec) == pytest.approx(want)
    # a request's work is its prompt plus one decode step per further token
    assert counts.request_flops(spec, 8, 3) == pytest.approx(
        counts.prompt_flops(spec, 8) + counts.decode_flops(spec, 9)
        + counts.decode_flops(spec, 10))


def test_queue_ran_dry():
    w = _window()
    assert not w.queue_ran_dry(5)
    assert w.queue_ran_dry(4)


# --------------------------------------------------------------------------
# yardstick data
# --------------------------------------------------------------------------

def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v99", HERE / "peaks.json")
    assert counts.peaks("TPU v5 lite", HERE / "peaks.json")["bf16_flops"] == 197e12


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_seeds_change_tokens_not_shapes_or_work(workload):
    cell = harness.find_cell(workload)
    spec, _ = spec_mod.load(cell.config)
    mix = traffic.load(cell.traffic)
    peak = counts.peaks("TPU v5 lite", HERE / "peaks.json")
    n = traffic.queue_len(spec, mix, 10.0, peak)
    (p1, g1), (p2, g2) = (traffic.generate(spec, mix, s, n)
                          for s in (1, 2 ** 31 + 12345))
    assert {len(p) for p in p1} == {len(p) for p in p2} == {mix.prompt_len}
    # the compiled KV capacity is the longest prompt + generation: the same
    cap = mix.prompt_len + mix.hi
    assert max(len(p) + g for p, g in zip(p1, g1)) == cap
    assert max(len(p) + g for p, g in zip(p2, g2)) == cap
    # the same lengths in the same order: a window that serves part of a
    # block does the same work for every seed; the tokens differ
    assert g1 == g2 and not np.array_equal(p1[0], p2[0])
    for a in range(0, n, mix.block):
        assert sorted(g1[a:a + mix.block]) == mix.lengths()
    # every 2**k requests from a block's start span the distribution: one
    # length from each 2**k-quantile stratum
    for k in range(mix.block.bit_length()):
        first = sorted(g1[:2 ** k])
        strata = [mix.lengths()[j * mix.block >> k:(j + 1) * mix.block >> k]
                  for j in range(2 ** k)]
        assert all(g in s for g, s in zip(first, strata))
    assert min(min(p) for p in p1) >= traffic.FIRST_ID
    assert max(max(p) for p in p1) < spec.vocab


def test_lognormal_block_matches_the_mix():
    mix = traffic.load(HERE / "traffic" / "conversation.json")
    block = mix.lengths()
    assert len(block) == mix.block and block[-1] == mix.hi
    assert min(block) >= mix.lo and max(block) == mix.hi
    assert statistics.median(block) == pytest.approx(mix.median, rel=0.05)


def test_benchmark_names_files_that_agree():
    """Every cell's configuration, traffic and metrics exist, the metric
    files say what BENCHMARK.json says, and each configuration builds the
    program's config with the numbers its file states."""
    from chipbench import adapter

    for w in BENCH["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.config.exists() and cell.traffic.exists()
        spec, raw = spec_mod.load(cell.config)
        cfg = adapter.program_config(spec, raw)
        assert cfg.n_layers == spec.layers
        for m in cell.end_to_end + cell.per_layer:
            harness.load_reader(m)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tok_s"}


# --------------------------------------------------------------------------
# trace reduction, on a trace laid out as the profiler writes a TPU's
# --------------------------------------------------------------------------

#: a trace in the profiler's own format, laid out as a TPU v5e plane is: a
#: decode execution (0-10 us) whose layer loop (`while`, 0.5-9.5) holds a
#: `bgemv` call (1-5) and a fusion that reads its output (6-9), a prefill
#: execution (20-25) holding a `bgemm` call (21-23), and a host span over
#: the idle gap 9.5-21.  Operations are named by their HLO instruction,
#: operands included, as the profiler writes them
HAND_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 21000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_decode_step_slots(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_prefill_step(8)" } }
  event_metadata { key: 3 value { id: 3 name: "%_bgemv_call.48 = bf16[8,1,2048]{2,1,0} custom-call(bf16[8,1,2048]{2,1,0} %fusion.7)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.1 = bf16[8,1,2048]{2,1,0} fusion(bf16[8,1,2048]{2,1,0} %_bgemv_call.48)" } }
  event_metadata { key: 5 value { id: 5 name: "%_bgemm_call.36 = bf16[8,1024,5632]{2,1,0} custom-call(bf16[8,1024,2048]{2,1,0} %fusion.55)" } }
  event_metadata { key: 6 value { id: 6 name: "%while.4 = (s32[]) while(%tuple.49), condition=%region_8, body=%region_1" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 14000000 } }
  event_metadata { key: 1 value { id: 1 name: "serve" } }
  event_metadata { key: 2 value { id: 2 name: "admission" } } }
"""


def test_trace_reduce_on_hand_built_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND_TRACE))
    us = 1e-6
    got = trace_reduce.reduce(
        path, trace_reduce.load_names(HERE / "kernel_names.json"), 30 * us)
    assert got["busy_s"] == pytest.approx((9 + 2) * us)
    assert got["window_s"] == 30 * us
    assert got["programs"]["decode"] == {"n": 1, "s": pytest.approx(10 * us)}
    assert got["programs"]["prefill"] == {"n": 1, "s": pytest.approx(5 * us)}
    # the fusion that reads the bgemv output is not bgemv time
    assert got["kernels"] == {"decode/bgemv": pytest.approx(4 * us),
                              "prefill/bgemm": pytest.approx(2 * us)}
    # the loop holds the others: left out of the breakdown, by kind
    assert got["device_ops"] == [["_bgemv_call", pytest.approx(4 * us)],
                                 ["fusion", pytest.approx(3 * us)],
                                 ["_bgemm_call", pytest.approx(2 * us)]]
    # the longest gap, 9.5-21 us, falls in the host's admission span
    assert got["idle_gaps"][0] == ["admission", pytest.approx(11.5 * us)]


def test_trace_reduce_on_recorded_v5e_excerpt(tmp_path):
    """`kernel_names.json` against a trace recorded on one TPU v5e while
    the cell served: the first 12 ms of one decode and of one prefill
    execution, every operation in them kept as the profiler named it."""
    from jax.profiler import ProfileData

    path = tmp_path / "excerpt.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        (HERE / "testdata" / "v5e_trace_excerpt.textproto").read_text()))
    got = trace_reduce.reduce(
        path, trace_reduce.load_names(HERE / "kernel_names.json"), 1.0)
    assert got["programs"] == {"decode": {"n": 1, "s": pytest.approx(0.012)},
                               "prefill": {"n": 1, "s": pytest.approx(0.012)}}
    # sums of the recorded events of each kernel, read from the file
    assert got["kernels"] == {"decode/bgemv": pytest.approx(664.375e-6),
                              "decode/flash": pytest.approx(6065.518e-6),
                              "prefill/bgemm": pytest.approx(1154.382e-6)}
    assert got["busy_s"] == pytest.approx(14672.173e-6)
    assert [k for k, _ in got["device_ops"][:3]] == [
        "flash_attention", "copy", "_bgemm_call"]


def test_trace_union_and_family():
    e = trace_reduce.Event
    spans = trace_reduce._union([e("a", 0, 10), e("b", 5, 20), e("c", 30, 40)])
    assert spans == [(0, 20), (30, 40)]
    table = {"bgemv": __import__("re").compile("bgemv")}
    assert trace_reduce.family("jit_x/_bgemv_kernel", table) == "bgemv"
    assert trace_reduce.family("fusion.3", table) is None


# --------------------------------------------------------------------------
# the reference against the program, and the check against faults
# --------------------------------------------------------------------------

@pytest.mark.parametrize("config", [SMOKE_CONFIG, SMOKE_RMS_GQA],
                         ids=["layernorm-mha-bias", "rmsnorm-gqa"])
def test_reference_matches_program_ref_backend(tmp_path, config):
    """At smoke size, in float32, the reference's logits after a prompt
    and after each token decoded through the cache equal the program's
    `prefill` / `decode_step` under the plain-jnp backend, on the same
    seeded weights."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core import blas
    from repro.models import transformer as tf
    from chipbench import adapter

    path = tmp_path / "smoke.json"
    path.write_text(json.dumps(config))
    spec, raw = spec_mod.load(path)
    cfg = dataclasses.replace(adapter.program_config(spec, raw),
                              dtype="float32", kv_cache_dtype="model")
    w = weights.make(spec, 5)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          adapter.program_params(spec, w))
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, spec.vocab, size=(1, 6), dtype=np.int32)
    follow = rng.integers(3, spec.vocab, size=(3,), dtype=np.int32)
    with blas.use_backend("ref"):
        cache = tf.init_cache(cfg, 1, 16)
        logits, cache = tf.prefill(params, {"tokens": jnp.asarray(prompt)},
                                   cache, cfg)
        got = [np.asarray(logits[0])]
        for tok in follow:
            logits, cache = tf.decode_step(params, jnp.asarray([[tok]]),
                                           cache, cfg)
            got.append(np.asarray(logits[0]))
    seq = np.concatenate([prompt[0], follow])
    x = reference.hidden(spec, w, seq)
    head = w["head"].astype(jnp.float32)
    h = reference._norm(spec, x, w["final_norm.w"].astype(jnp.float32),
                        w["final_norm.b"].astype(jnp.float32)
                        if spec.norm == "layer" else 0.0)
    want = np.asarray(jnp.matmul(h, head, precision="highest"))
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[len(prompt[0]) - 1 + i],
                                   rtol=2e-4, atol=2e-4)


def _run(tmp_path, seed=3, **kw):
    root, bench = make_root(tmp_path, **kw)
    return harness.run("smoke.tiny", seed, 1.0, False, root=root,
                       bench_dir=bench, require_chip=False, log=lambda m: None)


def test_discovers_config_traffic_and_metric_by_name(tmp_path, no_cache):
    source = ('"""Test metric."""\nUNIT, LAYER, MOVES = "1", None, None\n\n'
              'def read(ctx):\n    return ctx.spec.hidden + ctx.mix.prompt_len\n')
    got = _run(tmp_path, extra_metric=("throwaway", source))
    assert got["metrics"]["throwaway"]["value"] == 256 + 8
    assert got["correct"] is True
    assert got["attempted"] > 0 and got["failed"] == 0
    assert set(got["metrics"]) == {m["name"] for m in BENCH["end_to_end"]} | {
        "throwaway"}
    assert list(got)[-1] == "compared"


def test_metric_that_finds_nothing_is_an_error(tmp_path, no_cache):
    """A metric the cell lists that reads nothing (a kernel or program name
    that no longer matches) is left out of the line, which the cell's check
    refuses, and says so on standard error."""
    source = ('"""Test metric."""\nUNIT, LAYER, MOVES = "1", None, None\n\n'
              'def read(ctx):\n    return None\n')
    root, bench = make_root(tmp_path, extra_metric=("throwaway", source))
    said = []
    got = harness.run("smoke.tiny", 3, 1.0, False, root=root, bench_dir=bench,
                      require_chip=False, log=said.append)
    assert "throwaway" not in got["metrics"] and "tok_s" in got["metrics"]
    assert any("throwaway found nothing" in s for s in said)


@pytest.mark.parametrize("queues", [0, 2])
def test_adapter_refuses_a_scheduler_it_cannot_cut(tmp_path, monkeypatch,
                                                   no_cache, queues):
    """The adapter cuts the one request queue at the window's end; a
    scheduler that keeps none or two fails the run."""
    from repro.launch import serve as serve_mod

    def fake(cfg, prompts, *a, **kw):
        for _ in range(queues):
            serve_mod.collections.deque(enumerate(prompts))
        n = len(prompts)
        return {"elapsed_s": 0.0, "ttft": [None] * n, "status": [None] * n,
                "token_times": [[] for _ in range(n)], "outputs": [[]] * n}

    monkeypatch.setattr(serve_mod, "_serve_continuous", fake)
    root, bench = make_root(tmp_path)
    su = harness.set_up("smoke.tiny", root, bench, require_chip=False)
    with pytest.raises(RuntimeError, match="request queue"):
        harness.serve_window(su, 2, 1.0)


def test_warm_up_admits_groups_of_every_size(tmp_path, monkeypatch, no_cache):
    """Before the window, the warm-up admits groups of every size from the
    slot count down to 1 (each size is an eager shape of its own), grows a
    page run, and holds the window's KV capacity."""
    import collections
    from repro.launch import serve as serve_mod

    calls = []
    real = serve_mod._serve_continuous

    def spy(cfg, prompts, gen_lens, *a, **kw):
        calls.append((gen_lens, real(cfg, prompts, gen_lens, *a, **kw)))
        return calls[-1][1]

    monkeypatch.setattr(serve_mod, "_serve_continuous", spy)
    config = json.loads(json.dumps(SMOKE_CONFIG))
    config["serve"]["slots"] = 3
    root, bench = make_root(tmp_path, config=config)
    su = harness.set_up("smoke.tiny", root, bench, require_chip=False)
    harness.serve_window(su, 2, 1.0)
    (warm_gens, warm), (gens, _) = calls
    groups = collections.Counter(t for t in warm["ttft"] if t is not None)
    assert sorted(collections.Counter(groups.values())) == [1, 2, 3]
    assert max(warm_gens) == max(gens) == TINY_MIX["gen"]["max"]
    assert warm["status"].count("timeout") == 1
    assert max(map(len, warm["outputs"])) > config["serve"]["page_size"]


@pytest.mark.parametrize("waits", [False, True],
                         ids=["stops-after-an-admission", "stops-at-the-end"])
def test_scheduled_stop_waits_for_an_admission(tmp_path, no_cache, waits):
    """The traced sub-window's stop is due after its start, and is made
    only once a request has been admitted since the start, or, when none
    is before the window closes, then (with None)."""
    root, bench = make_root(tmp_path)
    su = harness.set_up("smoke.tiny", root, bench, require_chip=False)
    calls = []

    def start(admitted):
        calls.append(("start", admitted))
        return True

    def stop(admitted):
        if admitted is not None and (waits or admitted <= calls[0][1]):
            return False
        calls.append(("stop", admitted))
        return True

    harness.serve_window(su, 2, 1.0, [(0.2, start), (0.3, stop)])
    (what0, at_start), (what1, at_stop) = calls
    assert (what0, what1) == ("start", "stop") and at_start > 0
    assert at_stop is None if waits else at_stop > at_start


def test_window_end_empties_the_queue(tmp_path, no_cache):
    """Requests not admitted when the window closes are never served: at
    most one admission round straddles the window's end."""
    root, bench = make_root(tmp_path)
    su = harness.set_up("smoke.tiny", root, bench, require_chip=False)
    _, stats, _, n = harness.serve_window(su, 2, 1.0)
    admitted = [t for t in stats["ttft"] if t is not None]
    assert len(admitted) < n
    assert len([t for t in admitted if t > 1.0]) <= su.spec.slots


@pytest.mark.parametrize("config", [SMOKE_CONFIG, SMOKE_RMS_GQA],
                         ids=["bf16-int8-control", "int8-int4-control"])
def test_control_fails_the_check(tmp_path, no_cache, config):
    """The reference one integer precision below the served weights, in
    the program's place, reads above the limit the program reads below."""
    root, bench = make_root(tmp_path, config=config)
    su = harness.set_up("smoke.tiny", root, bench, require_chip=False)
    # a longer window than the other tests: more served tokens to compare
    prompts, stats, _, _ = harness.serve_window(su, 1, 3.0)
    got = harness.compare(su.spec, 1, prompts, stats, control=True)
    limit = config["correct"]["max_logit_gap"]
    assert got["program"] <= limit < got["control"]


@pytest.mark.parametrize("where", ["decode", "prefill"])
def test_altered_tokens_fail_the_check(tmp_path, monkeypatch, no_cache, where):
    """A token altered where the program produces it makes `correct`
    false."""
    from repro.launch import steps

    name = {"decode": "make_decode_step_slots",
            "prefill": "make_prefill_step"}[where]
    build = getattr(steps, name)

    def broken(cfg, **kw):
        step = build(cfg, **kw)

        def step_plus_one(*args):
            tok, cache = step(*args)
            return (tok + 1) % cfg.vocab, cache
        return step_plus_one

    monkeypatch.setattr(steps, name, broken)
    got = _run(tmp_path)
    assert got["correct"] is False
    gap = got["compared"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_step_that_keeps_its_state_fails_the_check(tmp_path, monkeypatch,
                                                    no_cache):
    """A decode step that returns the KV cache it was given, unchanged,
    makes `correct` false."""
    from repro.launch import steps

    build = steps.make_decode_step_slots

    def broken(cfg, **kw):
        step = build(cfg, **kw)

        def step_keeps_cache(params, token, cache, active):
            tok, _ = step(params, token, cache, active)
            return tok, cache
        return step_keeps_cache

    monkeypatch.setattr(steps, "make_decode_step_slots", broken)
    got = _run(tmp_path)
    assert got["correct"] is False
    gap = got["compared"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_command_without_a_chip_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "run_cell.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert proc.stdout == ""

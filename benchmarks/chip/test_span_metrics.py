"""The readers of the scheduler's program spans, on hand-built records.

Each reader takes the spans that end inside the window, and reads nothing
from a program that keeps no spans (the metric is then left out of the
line) or, for `flash_roofline`, from a trace without the kernel.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import harness, spec as spec_mod  # noqa: E402
from chipbench.window import Window  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAMES = ("round_host_ms", "admit_stall_ms", "admit_spill_ms", "flash_roofline")


def reader(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    return harness.load_reader(entry)


def _round(records, start, host_ms, wait_ms, kv, after):
    """A decode round and its read-back, which starts after half the host
    time."""
    i = len(records)
    end = start + (host_ms + wait_ms) / 1e3
    records.append({"name": "serve.round", "start": start, "end": end,
                    "parent": None, "attrs": {"step": i // 2, "live": 2,
                                              "kv_tokens": kv,
                                              "after_admit": after}})
    w0 = start + host_ms / 2e3
    records.append({"name": "serve.round.wait", "start": w0,
                    "end": w0 + wait_ms / 1e3, "parent": i, "attrs": {}})


def _admit(records, start, ms):
    records.append({"name": "serve.admit", "start": start,
                    "end": start + ms / 1e3, "parent": None,
                    "attrs": {"rids": [0], "plen": 8, "rows": 1}})


def spans(inside=True, outside=True):
    """A 10 s window: six rounds inside it (host 2-13 ms, read-backs of
    10-50 ms, 100-600 positions, three after an admission) and three
    admissions (400, 410, 430 ms); a round and an admission end after the
    window, and a round before it."""
    r = []
    if outside:
        _round(r, -3.0, 900, 900, 10_000, True)
    for k, (host, wait, kv, after) in enumerate([
            (2, 10, 100, False), (13, 30, 200, True), (5, 20, 300, False),
            (11, 40, 400, True), (3, 25, 500, False), (7, 50, 600, True)]):
        if inside and after:
            _admit(r, 1.0 * k + 0.1, [400, 410, 430][k // 2])
        if inside:
            _round(r, 1.0 * k + 0.6, host, wait, kv, after)
    if outside:
        _admit(r, 9.9, 5000)
        _round(r, 9.99, 1000, 1000, 10_000, True)
    return r


SPEC = dict(name="t", hidden=32, layers=2, heads=4, kv_heads=2, head_dim=8,
            ff=64, vocab=128, rope_theta=1e4, rotary_frac=1.0, norm="layer",
            norm_eps=1e-5, qkv_bias=True, tied=False, weights="bfloat16",
            kv_cache="bfloat16", page_size=4, slots=2)
#: four decode steps whose flash calls took 224 us each
TRACE = {"programs": {"decode": {"n": 4, "s": 0.01}},
         "kernels": {"decode/flash": 4 * 224e-6}}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def ctx(records, trace=TRACE, kv_cache="bfloat16"):
    spec = spec_mod.Spec(**{**SPEC, "kv_cache": kv_cache})
    stats = {} if records is None else {"spans": records}
    return harness.Context(spec, None, PEAK, 0.0,
                           Window(10.0, 2, 8, [], [], []), stats, trace)


@pytest.mark.parametrize("name, want", [
    # hosts 2, 3, 5, 7, 11, 13 ms
    ("round_host_ms", 6.0),
    ("admit_stall_ms", 410.0),
    # read-backs after an admission 30, 40, 50 ms; the others 10, 20, 25
    ("admit_spill_ms", 40.0 - 20.0),
    # a mean round attends 350 positions: 2 layers x K, V x 2 heads x 8
    # features x 2 bytes = 44,800 bytes, 44.8 us at 1 GB/s, of 224 us
    ("flash_roofline", 20.0),
])
def test_reads_the_hand_computed_value(name, want):
    assert reader(name).read(ctx(spans())) == pytest.approx(want)


def test_flash_roofline_counts_int8_scales():
    # 8 one-byte features and a four-byte scale per head: 33,600 bytes
    got = reader("flash_roofline").read(ctx(spans(), kv_cache="int8"))
    assert got == pytest.approx(100 * 33.6 / 224)


@pytest.mark.parametrize("name", NAMES)
def test_skips_spans_outside_the_window(name):
    assert reader(name).read(ctx(spans(outside=False))) == pytest.approx(
        reader(name).read(ctx(spans())))


@pytest.mark.parametrize("name", NAMES)
def test_reads_nothing_without_spans(name):
    assert reader(name).read(ctx(None)) is None
    assert reader(name).read(ctx([])) is None
    assert reader(name).read(ctx(spans(inside=False))) is None


@pytest.mark.parametrize("trace", [None, {"programs": {}, "kernels": {}},
                                   {"programs": TRACE["programs"],
                                    "kernels": {}}],
                         ids=["untraced", "no-decode", "no-flash"])
def test_flash_roofline_reads_nothing_without_the_kernel(trace):
    assert reader("flash_roofline").read(ctx(spans(), trace)) is None


def test_admit_spill_needs_three_rounds_of_each_kind():
    r = spans()
    last_after = max(i for i, x in enumerate(r) if x["name"] == "serve.round"
                     and x["attrs"]["after_admit"] and x["end"] <= 10.0)
    r[last_after]["attrs"]["after_admit"] = False
    assert reader("admit_spill_ms").read(ctx(r)) is None

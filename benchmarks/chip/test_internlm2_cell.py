"""CPU tests of the `internlm2-20b-l8.conversation` cell.

The cell's configuration file has to say what the program computes, and
the program's int8 path has to pass the output check at the cell's shape of
head: GQA with heads of 128, RMSNorm at the published epsilon, int8 weights
and an int8 KV cache in 16-token pages.  At a size a CPU serves in seconds
(two layers, d=768, one KV head), with the Pallas kernels in interpret mode.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import adapter, harness, spec as spec_mod  # noqa: E402
from test_chipbench import make_root, no_cache  # noqa: E402,F401

CELL = "internlm2-20b-l8.conversation"

#: internlm2-20b's head at a CPU's size: 6 query heads on one KV head of
#: the published 128, RMSNorm at the published 1e-5 (the registry's full
#: config, cut), int8 weights and KV in 16-token pages
SMALL = {
    "model_type": "internlm2", "hidden_size": 768, "intermediate_size": 2048,
    "num_hidden_layers": 2, "num_attention_heads": 6,
    "num_key_value_heads": 1, "vocab_size": 1024, "rope_theta": 1e6,
    "rms_norm_eps": 1e-5, "bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False,
    "serve": {"slots": 2, "weights": "int8", "kv_cache": "int8",
              "page_size": 16},
    "program": {"registry": "internlm2-20b", "variant": "full",
                "overrides": {"d_model": 768, "n_heads": 6, "n_kv": 1,
                              "d_ff": 2048, "vocab": 1024}},
    # readings at this size (CPU, this mix, 2-3 s windows, seeds 1-6 and
    # 2**31 + 5): program 0.045-0.113, control (int4) 2.68-3.40
    "correct": {"max_logit_gap": 0.4},
}
#: whole 16-token pages of prompt, and answers long enough that the
#: warm-up fills a page (`adapter.warm_up_lengths`)
PAGED_MIX = {"loop": "closed", "prompt_len": 16, "block": 8,
             "gen": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                     "min": 2, "max": 24}}


def test_configuration_file_is_the_program_at_eight_layers():
    """The cell's file names the registry's internlm2-20b at its published
    widths; the adapter accepts it, cut to 8 layers, and the program then
    computes with the file's RMSNorm epsilon and an int8 cache."""
    cell = harness.find_cell(CELL)
    assert cell.chips == 1
    spec, raw = spec_mod.load(cell.config)
    assert (spec.layers, spec.weights, spec.kv_cache, spec.page_size,
            spec.slots) == (8, "int8", "int8", 16, 8)
    cfg = adapter.program_config(spec, raw)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd,
            cfg.d_ff, cfg.vocab) == (8, 6144, 48, 8, 128, 16384, 92544)
    assert cfg.norm == "rms" and cfg.norm_eps == spec.norm_eps == 1e-5
    assert cfg.kv_cache_dtype == "int8"
    for entry in cell.per_layer:
        harness.load_reader(entry)
    assert {m["name"] for m in cell.per_layer} == {
        "mfu.int8", "decode_step_ms.int8", "bgemv_roofline.int8",
        "bgemm_roofline.int8", "flash_roofline.int8", "pack_s"}


def _root(tmp_path):
    root, bench = make_root(tmp_path, config=SMALL)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(PAGED_MIX))
    return root, bench


def test_int8_gqa_path_is_correct_and_its_control_is_not(tmp_path, no_cache):
    """Served through the harness under the Pallas path, the program reads
    below the limit, with its weights packed in one `serve.pack` span of
    set-up; the reference at int4, in the program's place, reads above."""
    root, bench = _root(tmp_path)
    got = harness.run("smoke.tiny", 2 ** 31 + 5, 2.0, False, root=root,
                      bench_dir=bench, require_chip=False,
                      log=lambda m: None)
    assert got["correct"] is True
    assert got["attempted"] > 0 and got["failed"] == 0
    limit = SMALL["correct"]["max_logit_gap"]
    assert got["compared"]["widest_logit_gap"]["limit"] == limit

    su = harness.set_up("smoke.tiny", root, bench, require_chip=False)
    prompts, stats, _, _ = harness.serve_window(su, 1, 2.0)
    assert [r["name"] for r in stats["spans"]].count("serve.pack") == 1
    assert stats["pack_s"] > 0
    checked = harness.compare(su.spec, 1, prompts, stats, control=True)
    assert checked["program"] <= limit < checked["control"]


@pytest.mark.parametrize("packed", [True, False])
def test_pack_s_reads_the_timed_calls_span(packed):
    """`pack_s` reads the `serve.pack` record, which ends before t0 and so
    outside every window; a program that packs nothing gives no reading."""
    entry = {"name": "pack_s", "unit": "s", "layer": "weights",
             "moves": "setup_s"}
    read = harness.load_reader(entry).read
    span = {"name": "serve.pack", "start": -7.5, "end": -1.25, "parent": None,
            "attrs": {}}
    other = {"name": "serve.round", "start": 0.5, "end": 0.6, "parent": None,
             "attrs": {}}

    class Ctx:
        stats = {"spans": [span, other] if packed else [other]}

    assert read(Ctx) == (6.25 if packed else None)

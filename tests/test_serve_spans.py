"""Program spans of the continuous scheduler (`stats["spans"]`).

Each decode round that steps a slot is one `serve.round`, holding the
read-back of its tokens (`serve.round.wait`); each admission group is one
`serve.admit`.  The records are checked against what the scheduler's other
counters and token times say, counted independently here, and against the
profiler's own trace of the same run.
"""

import numpy as np
import pytest

from repro.launch.serve import serve
from repro.models.registry import get_config

ARCH = "stablelm-1.6b"
NO_EOS = -1
#: mixed prompt lengths (two admission groups in one round), one request
#: that ends on its prefill token, and a round that frees every slot
PLENS = [6, 6, 9, 6, 9, 6]
GENS = [3, 3, 4, 1, 6, 2]


def _prompts(plens, seed=0):
    vocab = get_config(ARCH, "smoke").vocab
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, size=(n,), dtype=np.int32) for n in plens]


def _serve(prompts, gens, **kw):
    return serve(ARCH, "smoke", batch=2, prompts=prompts, gen_lens=gens,
                 eos=NO_EOS, verbose=False, scheduler="continuous", **kw)


def _named(stats, name):
    return [(i, r) for i, r in enumerate(stats["spans"]) if r["name"] == name]


@pytest.fixture(scope="module", params=[4, None], ids=["paged", "default"])
def run(request):
    prompts = _prompts(PLENS)
    return prompts, _serve(prompts, GENS, kv_page_size=request.param)


def test_one_round_per_decode_step_and_one_admit_per_prefill(run):
    _, stats = run
    rounds, admits = _named(stats, "serve.round"), _named(stats, "serve.admit")
    assert len(rounds) == stats["decode_steps"] > 0
    assert [r["attrs"]["step"] for _, r in rounds] == list(
        range(stats["decode_steps"]))
    assert len(admits) == stats["prefills"] > 1
    assert sorted(rid for _, r in admits for rid in r["attrs"]["rids"]) == \
        list(range(len(PLENS)))
    for _, r in admits:
        assert r["attrs"]["rows"] == len(r["attrs"]["rids"])
        assert {PLENS[rid] for rid in r["attrs"]["rids"]} == {r["attrs"]["plen"]}
    for r in stats["spans"]:
        assert 0.0 <= r["start"] <= r["end"]


def test_each_wait_lies_inside_its_round(run):
    _, stats = run
    spans = stats["spans"]
    waits = _named(stats, "serve.round.wait")
    assert sorted(r["parent"] for _, r in waits) == [
        i for i, _ in _named(stats, "serve.round")]
    for _, w in waits:
        rnd = spans[w["parent"]]
        assert rnd["start"] <= w["start"] <= w["end"] <= rnd["end"]


def test_kv_tokens_counts_the_positions_each_round_attends(run):
    """A request of prompt length P emits its token k >= 1 from a decode
    step that attends P + k positions; the round's tokens are stamped at
    its read-back."""
    prompts, stats = run
    for _, r in _named(stats, "serve.round"):
        want = sum(len(prompts[rid]) + k
                   for rid, ts in enumerate(stats["token_times"])
                   for k, t in enumerate(ts) if k and r["start"] <= t <= r["end"])
        live = sum(1 for ts in stats["token_times"]
                   for k, t in enumerate(ts) if k and r["start"] <= t <= r["end"])
        assert (r["attrs"]["kv_tokens"], r["attrs"]["live"]) == (want, live)


def test_first_token_lies_inside_its_admission(run):
    _, stats = run
    for _, a in _named(stats, "serve.admit"):
        for rid in a["attrs"]["rids"]:
            t = stats["token_times"][rid][0]
            assert t == stats["ttft"][rid]
            assert a["start"] <= t <= a["end"]


def test_after_admit_marks_the_first_round_after_each_admission(run):
    _, stats = run
    admit_ends = [a["end"] for _, a in _named(stats, "serve.admit")]
    prev_end = float("-inf")
    marked = 0
    for _, r in _named(stats, "serve.round"):
        want = any(prev_end < e <= r["start"] for e in admit_ends)
        assert r["attrs"]["after_admit"] is want
        marked += want
        prev_end = r["end"]
    assert marked > 0


def test_max_stall_is_the_widest_gap_between_read_backs_of_a_busy_grid(run):
    """Between two consecutive read-backs, unless every slot was free at an
    admission between them."""
    _, stats = run
    times = stats["token_times"]

    def idle(t):
        return not any(ts and ts[0] < t < ts[-1] for ts in times)

    idle_at = [a["start"] for _, a in _named(stats, "serve.admit")
               if idle(a["start"])]
    ends = [w["end"] for _, w in _named(stats, "serve.round.wait")]
    gaps = [b - a for a, b in zip(ends, ends[1:])
            if not any(a < t < b for t in idle_at)]
    assert gaps
    assert stats["max_stall_ms"] == pytest.approx(max(gaps) * 1e3)


def test_round_that_steps_no_slot_leaves_no_record():
    """Deadlines of 0 cut every request at its first round boundary, so no
    round steps a slot."""
    stats = _serve(_prompts(PLENS), GENS, kv_page_size=4, deadline_ms=0)
    assert stats["decode_steps"] == 0 and stats["timeouts"] > 0
    assert _named(stats, "serve.round") == _named(stats, "serve.round.wait") == []
    assert len(_named(stats, "serve.admit")) == stats["prefills"]


def test_chunked_admission_nests_rounds_under_it():
    """Under --prefill-chunk the rounds between an admission's chunks are
    its children."""
    prompts = _prompts([6, 6, 48], seed=41)
    stats = _serve(prompts, [3, 16, 4], prefill_chunk=8)
    spans = stats["spans"]
    rounds = _named(stats, "serve.round")
    assert len(rounds) == stats["decode_steps"]
    nested = [r for _, r in rounds if r["parent"] is not None]
    assert len(nested) == 48 // 8 - 1
    for r in nested:
        adm = spans[r["parent"]]
        assert adm["name"] == "serve.admit" and adm["attrs"]["plen"] == 48
        assert adm["start"] <= r["start"] <= r["end"] <= adm["end"]


def test_profiler_trace_holds_every_round(tmp_path):
    """Each span is also a profiler annotation on the host plane."""
    import jax
    from jax.profiler import ProfileData

    prompts = _prompts(PLENS)
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats = _serve(prompts, GENS, kv_page_size=4)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [e for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host:") for ln in p.lines
              for e in ln.events]
    for name in ("serve.round", "serve.round.wait", "serve.admit"):
        got = [e for e in events if e.name == name]
        assert len(got) == len(_named(stats, name)) > 0, name
    kv = sorted(dict(e.stats)["kv_tokens"] for e in events
                if e.name == "serve.round")
    assert kv == sorted(r["attrs"]["kv_tokens"]
                        for _, r in _named(stats, "serve.round"))


@pytest.mark.parametrize("quantize", ["int8", "none"])
def test_weight_packing_is_one_span_of_set_up(quantize):
    """Packing the weights (quantize="int8") is one `serve.pack` span per
    call, recorded in set-up, so it ends before the scheduler's t0, with
    the projections it packed and their bytes before and after; `pack_s`
    is its length.  Unpacked weights leave no such span."""
    import jax
    from repro.core import quant
    from repro.models import layers, transformer as tf

    stats = _serve(_prompts(PLENS[:3]), GENS[:3], quantize=quantize)
    packs = _named(stats, "serve.pack")
    if quantize == "none":
        assert packs == [] and stats["pack_s"] == 0.0
        return
    ((_, pack),) = packs
    assert pack["parent"] is None and pack["start"] <= pack["end"] < 0.0
    assert stats["pack_s"] == pytest.approx(pack["end"] - pack["start"])
    params = jax.eval_shape(lambda: tf.init_params(
        jax.random.PRNGKey(0), get_config(ARCH, "smoke")))
    packed = jax.eval_shape(layers.quantize_weights, params)
    qts = [q for q in jax.tree.leaves(packed, is_leaf=quant.is_quantized)
           if quant.is_quantized(q)]
    assert pack["attrs"]["leaves"] == len(qts) == 7
    layer = {**params["layers"]["attn"], **params["layers"]["ffn"]}
    assert pack["attrs"]["bytes_in"] == sum(
        np.prod(layer[k].shape) * layer[k].dtype.itemsize
        for k in layers.QUANT_WEIGHT_KEYS)
    assert pack["attrs"]["bytes_out"] == sum(
        np.prod(q.values.shape) + 4 * np.prod(q.scales.shape) for q in qts)

"""One run of one cell: set up, serve the window, read the metrics, check.

The cell comes from `BENCHMARK.json` by name; its configuration file, its
traffic mix and every metric's reader are found by the names given there.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from chipbench import (counts, reference, spec as spec_mod, trace_reduce,
                       traffic, weights)
from chipbench.window import Window

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]

#: JAX's persistent compilation cache, at this fixed place in the checkout
CACHE_DIR = ".jax_cache"

#: the traced sub-window: it opens this share of the way into the window
#: and lasts TRACE_SECONDS or until an admission (and its prefill) has come
#: inside it, whichever is later, and at most until the window's end
TRACE_AT = 0.2
TRACE_SECONDS = 4.0

#: the output check reads at least this many finished requests, the longest
#: among them, and more until this many served tokens are covered
CHECK_REQUESTS = 4
CHECK_TOKENS = 512
CHECK_MAX_REQUESTS = 32


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Path
    traffic: Path
    end_to_end: list
    per_layer: list


def find_cell(workload: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in e2e_names]
    return Cell(workload, int(w["chips"]), root / conf["file"],
                bench_dir / "traffic" / f"{w['traffic']}.json", e2e, per_layer)


def load_reader(entry: dict, bench_dir: Path = BENCH_DIR):
    """The metric's reader, `metrics/<name>.py`; its UNIT, LAYER and MOVES
    must say what `BENCHMARK.json` says."""
    path = bench_dir / "metrics" / f"{entry['name']}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + entry["name"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    said = (mod.UNIT, mod.LAYER, mod.MOVES)
    want = (entry["unit"], entry.get("layer"), entry.get("moves"))
    if said != want:
        raise ValueError(f"{path}: says (unit, layer, moves) {said}, "
                         f"BENCHMARK.json says {want}")
    return mod


@dataclasses.dataclass
class Context:
    """What a metric's reader may read."""
    spec: spec_mod.Spec
    mix: traffic.Mix
    peak: dict
    setup_s: float
    window: Window
    stats: dict                  # the scheduler's counters
    trace: dict | None           # trace_reduce.reduce, in a traced run


def device(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoChip(f"{len(devs)} {devs[0].platform} device(s); the cell "
                     f"needs {chips} accelerator chip(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileLog:
    """Times of the persistent compilation cache's misses (a compile) and
    hits (a load), from JAX's monitoring events; one listener per
    process."""
    _events: list = []
    _listening = False

    @classmethod
    def listen(cls) -> None:
        if cls._listening:
            return
        import jax

        def on_event(name, **_):
            if name in ("/jax/compilation_cache/cache_misses",
                        "/jax/compilation_cache/cache_hits"):
                cls._events.append((time.time(), name.rsplit("_", 1)[-1]))

        jax.monitoring.register_event_listener(on_event)
        cls._listening = True

    @classmethod
    def between(cls, a: float, b: float) -> dict:
        out = {"misses": 0, "hits": 0}
        for t, kind in cls._events:
            if a <= t <= b:
                out[kind] += 1
        return out


class Tracer:
    """A profiler session over a sub-window, written to a scratch
    directory that is removed after reading; `seconds` is the session's
    length on the host's clock.  `start` and `stop_after_admission` are
    the adapter's scheduled calls (`adapter.serve`)."""

    def __init__(self):
        self.dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_"))
        self.on = False
        self.seconds = 0.0
        self.admitted = 0

    def start(self, admitted: int | None = 0) -> bool:
        import jax

        if admitted is None:       # the window closed first
            return True
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.on = True
        self.admitted = admitted
        self.seconds = -time.perf_counter()
        return True

    def stop_after_admission(self, admitted: int | None) -> bool:
        """Stop once a request has been admitted inside the session, so
        that it holds an admission prefill, or when the window closes."""
        if admitted is not None and admitted <= self.admitted:
            return False
        self.stop()
        return True

    def stop(self):
        import jax

        if self.on:
            self.seconds += time.perf_counter()
            jax.profiler.stop_trace()
            self.on = False

    def file(self) -> Path:
        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return found[-1]

    def close(self):
        self.stop()
        shutil.rmtree(self.dir, ignore_errors=True)


def check_sample(stats: dict, seed: int) -> list[int]:
    """Finished requests to compare: the longest, then others drawn from the
    seed, until CHECK_REQUESTS and CHECK_TOKENS are both reached."""
    done = [i for i, st in enumerate(stats["status"]) if st == "ok"]
    if not done:
        return []
    outs = stats["outputs"]
    longest = max(done, key=lambda i: (len(outs[i]), -i))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([seed, 1])
    picks, served = [longest], len(outs[longest])
    for i in rng.permutation(rest):
        if (len(picks) >= CHECK_REQUESTS and served >= CHECK_TOKENS
                or len(picks) >= CHECK_MAX_REQUESTS):
            break
        picks.append(int(i))
        served += len(outs[i])
    return picks


#: the control's integer width, by the served weight type: the next
#: precision below the one the configuration states
CONTROL_BITS = {"bfloat16": 8, "int8": 4}


def compare(spec, seed: int, prompts, stats: dict, control=False) -> dict:
    """The output check: the widest reference-logit gap of the served
    tokens over the sample, and with `control` the control's."""
    picks = check_sample(stats, seed)
    w = weights.make(spec, seed)
    seqs = [(prompts[i], stats["outputs"][i]) for i in picks]
    got = reference.gaps(spec, w, seqs,
                         CONTROL_BITS[spec.weights] if control else None)
    got["requests"] = len(picks)
    got["tokens"] = sum(len(stats["outputs"][i]) for i in picks)
    del w
    return got


@dataclasses.dataclass
class Setup:
    cell: Cell
    spec: spec_mod.Spec
    raw: dict
    mix: traffic.Mix
    device: dict
    peak: dict


def set_up(workload: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR,
           require_chip: bool = True) -> Setup:
    from chipbench import adapter

    cell = find_cell(workload, root, bench_dir)
    spec, raw = spec_mod.load(cell.config)
    mix = traffic.load(cell.traffic)
    dev = device(cell.chips, require_chip)
    adapter.enable_cache(root / CACHE_DIR)
    return Setup(cell, spec, raw, mix, dev,
                 counts.peaks(dev["kind"], bench_dir / "peaks.json"))


def serve_window(su: Setup, seed: int, seconds: float, schedule=()):
    """Generate the queue from the seed and serve it for `seconds`;
    returns (prompts, stats, t0, queue length)."""
    from chipbench import adapter

    n = traffic.queue_len(su.spec, su.mix, seconds, su.peak)
    prompts, gen_lens = traffic.generate(su.spec, su.mix, seed, n)
    stats, t0 = adapter.serve(su.spec, su.raw, prompts, gen_lens, seconds,
                              seed, lambda: weights.make(su.spec, seed),
                              schedule)
    return prompts, stats, t0, n


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, bench_dir: Path = BENCH_DIR,
        require_chip: bool = True, t_start: float | None = None,
        log=None) -> dict:
    """One run; returns the result object the command prints last."""
    t_start = time.time() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    su = set_up(workload, root, bench_dir, require_chip)
    readers = [(m, load_reader(m, bench_dir))
               for m in (su.cell.per_layer if trace else su.cell.end_to_end)]
    dev = dict(su.device)
    CompileLog.listen()
    tracer = Tracer() if trace else None
    schedule = []
    if tracer:
        at = TRACE_AT * seconds
        schedule = [(at, tracer.start),
                    (at + TRACE_SECONDS, tracer.stop_after_admission)]
    try:
        prompts, stats, t0, n = serve_window(su, seed, seconds, schedule)
        t_served = time.time()
        dev["memory_peak_bytes"] = _memory_peak()
        summary = None
        if tracer:
            tracer.stop()
            summary = trace_reduce.reduce(tracer.file(), trace_reduce.load_names(
                bench_dir / "kernel_names.json"), tracer.seconds)
    finally:
        if tracer:
            tracer.close()
    gc.collect()
    win = Window.of(stats, seconds, su.spec.slots, su.mix.prompt_len)
    if win.queue_ran_dry(n):
        raise RuntimeError(f"the queue of {n} requests ran dry inside the "
                           f"window: the load was not held")
    ctx = Context(su.spec, su.mix, su.peak, t0 - t_start, win, stats, summary)
    metrics = {}
    for entry, mod in readers:
        value = mod.read(ctx)
        if value is None:
            # left out of the line, which the cell's check then refuses: a
            # program or kernel name in kernel_names.json that no longer
            # matches, or a traced sub-window without that work
            log(f"[chipbench] {entry['name']} found nothing to read in this "
                f"run of {workload}: left out")
            continue
        metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    if summary:
        log(f"[chipbench] traced {summary['window_s']:.3f}s, device busy "
            f"{summary['busy_s']:.3f}s; programs {summary['programs']}; "
            f"kernels {summary['kernels']}")
    inside = CompileLog.between(t0, t0 + seconds)
    gaps = np.asarray(win.itl_gaps() or [0.0]) * 1e3
    log(f"[chipbench] {workload} seed {seed}: {n} queued, "
        f"{len(win.admitted())} admitted and {win.tokens()} tokens in the "
        f"{seconds:g}s window, {len(win.itl_gaps())} gaps (ms: p50 "
        f"{np.percentile(gaps, 50):.1f}, p90 {np.percentile(gaps, 90):.1f}, "
        f"p95 {np.percentile(gaps, 95):.1f}, p99 {np.percentile(gaps, 99):.1f}, "
        f"max {gaps.max():.1f}), set-up {t0 - t_start:.1f}s, drain "
        f"{t_served - t0 - seconds:.1f}s; inside the window "
        f"{inside['misses']} compiles and {inside['hits']} compile-cache "
        f"loads")
    got = compare(su.spec, seed, prompts, stats)
    limit = float(su.raw["correct"]["max_logit_gap"])
    correct = got["requests"] > 0 and got["program"] <= limit
    compared = {"widest_logit_gap": {"value": got["program"], "limit": limit},
                "requests_checked": {"value": got["requests"], "limit": 1}}
    log(f"[chipbench] compared {got['tokens']} served tokens of "
        f"{got['requests']} requests against the float32 reference")
    attempted = win.admitted()
    failed = [i for i in attempted if stats["status"][i] in (None, "rejected")]
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": dev}
    if summary:
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["compared"] = compared
    return result

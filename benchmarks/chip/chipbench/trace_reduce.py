"""Device time from a `jax.profiler` trace (`*.xplane.pb`).

On each device plane the profiler keeps one line of program executions
(`XLA Modules`) and one of the operations inside them (`XLA Ops`).  An
operation's event is named by its HLO instruction (`%_bgemv_call.48 = bf16[...]
custom-call(...)` on a TPU v5e): its name is the text before ` = `, without
the `%`, and a Pallas kernel's instruction is named after the function that
calls it.  A loop (`while`) is an operation too, which holds the operations
of its body.  Busy time is the union of the operations' intervals, and the
window is the profiler session's length on the host's clock, given by the
caller.  `kernel_names.json` maps program and kernel names to the families
the metrics ask for, so that a renamed kernel is one edit of data.  Each
operation is charged to the program execution that contains it.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import re
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int     # ns
    end: int       # ns

    @classmethod
    def of(cls, e) -> "Event":
        """An operation's event, named by its instruction alone: the
        operands that follow the name may name other kernels."""
        return cls(e.name.split(" = ", 1)[0].lstrip("%"), int(e.start_ns),
                   int(e.end_ns))


def kind(name: str) -> str:
    """An instruction's name without its numeric suffix (`copy.3` ->
    `copy`), under which the breakdown adds operations up."""
    return re.sub(r"(\.[0-9]+)+$", "", name)


def leaves(ops: list) -> list:
    """The operations that hold no other: a loop's own event spans its
    body's, which are counted for themselves.  `ops` in time order."""
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start >= op.end]


def load_names(path: Path) -> dict:
    raw = json.loads(Path(path).read_text())
    return {
        "device_plane": re.compile(raw["device_plane"]),
        "modules_line": raw["modules_line"],
        "ops_line": raw["ops_line"],
        "programs": {k: re.compile(v) for k, v in raw["programs"].items()},
        "kernels": {k: re.compile(v) for k, v in raw["kernels"].items()},
    }


def family(name: str, table: dict) -> str | None:
    for fam, pat in table.items():
        if pat.search(name):
            return fam
    return None


def _union(events) -> list[tuple[int, int]]:
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e.end))
        else:
            out.append((e.start, e.end))
    return out


def reduce(path, names: dict, window_s: float, top: int = 10) -> dict:
    """Busy seconds (averaged over the device planes), per
    program family its executions and device seconds, per (program, kernel)
    family the device seconds of the kernel inside those executions, and
    the kinds of operation that took the most time (loops left out, their
    bodies counted)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = [p for p in pd.planes if names["device_plane"].search(p.name)]
    if not planes:
        raise ValueError(f"{path}: no device plane matches "
                         f"{names['device_plane'].pattern}")
    host = [Event(e.name, int(e.start_ns), int(e.end_ns))
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    busy = 0.0
    programs = collections.defaultdict(lambda: {"n": 0, "s": 0.0})
    kernels = collections.defaultdict(float)
    op_time = collections.Counter()
    gaps = []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        ops = [Event.of(e) for e in lines[names["ops_line"]].events]
        mods = [Event(e.name, int(e.start_ns), int(e.end_ns))
                for e in lines[names["modules_line"]].events]
        if not ops:
            continue
        spans = _union(ops)
        busy += sum(b - a for a, b in spans) / 1e9
        gaps.extend((b[0] - a[1], a[1], b[0]) for a, b in zip(spans, spans[1:]))
        # ops and modules are each in time order: walk them together
        mods.sort(key=lambda e: e.start)
        ops.sort(key=lambda e: (e.start, -e.end))
        for op in leaves(ops):
            op_time[kind(op.name)] += (op.end - op.start) / 1e9
        j = 0
        for m in mods:
            fam = family(m.name, names["programs"])
            if fam is None:
                continue
            programs[fam]["n"] += 1
            programs[fam]["s"] += (m.end - m.start) / 1e9
            while j < len(ops) and ops[j].start < m.start:
                j += 1
            k = j
            while k < len(ops) and ops[k].start < m.end:
                kfam = family(ops[k].name, names["kernels"])
                if kfam is not None:
                    kernels[(fam, kfam)] += (ops[k].end - ops[k].start) / 1e9
                k += 1
    n = len(planes)
    gaps.sort(reverse=True)
    return {
        "busy_s": busy / n,
        "window_s": window_s,
        "programs": {k: dict(v) for k, v in programs.items()},
        "kernels": {f"{p}/{k}": v for (p, k), v in kernels.items()},
        "device_ops": [[name, s] for name, s in op_time.most_common(top)],
        "idle_gaps": [[_host_label(host, a, b), g / 1e9]
                      for g, a, b in gaps[:top]],
    }


def _host_label(host: list, a: int, b: int) -> str:
    """What the host was doing in the gap [a, b]: the innermost (shortest)
    host event that covers at least half of it, else the one that covers
    most of it."""
    best, key = "no host event", (False, 0)
    for e in host:
        cover = min(e.end, b) - max(e.start, a)
        if cover > 0:
            half = 2 * cover >= b - a
            k = (half, -(e.end - e.start) if half else cover)
            if k > key:
                best, key = e.name, k
    return best

"""Share of its roofline that paged flash attention reaches in a decode
step: the least time for a mean round's attention (the keys and values of
every position the stepped slots attend, in every layer, read once at the
HBM peak, or its operations at the bf16 peak, whichever is longer) over
the kernel's device time per decode step.  The positions attended come from
the scheduler's rounds over the whole window (`kv_tokens` of `serve.round`),
the kernel's time from the traced sub-window."""

import statistics

from chipbench import counts
from chipbench.spans import rounds

UNIT, LAYER, MOVES = "%", "kernels", "itl_p50_ms"

#: bytes of one cached element, and of the f32 scale an int8 cache keeps
#: per position and key-value head
KV_BYTES = {"bfloat16": 2, "int8": 1}
SCALE_BYTES = 4


def kv_bytes(spec, positions: float) -> float:
    """Keys and values of `positions` cached positions, every layer."""
    per_head = spec.head_dim * KV_BYTES[spec.kv_cache]
    if spec.kv_cache == "int8":
        per_head += SCALE_BYTES
    return positions * spec.layers * 2 * spec.kv_heads * per_head


def read(ctx):
    prog = ctx.trace["programs"].get("decode") if ctx.trace else None
    t = ctx.trace["kernels"].get("decode/flash") if ctx.trace else None
    kv = [r["attrs"]["kv_tokens"] for r, _ in rounds(ctx)]
    if not prog or not prog["n"] or not t or not kv:
        return None
    s = ctx.spec
    positions = statistics.mean(kv)
    flops = 4.0 * s.layers * s.heads * s.head_dim * positions
    least = counts.least_time(flops, kv_bytes(s, positions), ctx.peak)
    return 100.0 * least / (t / prog["n"])

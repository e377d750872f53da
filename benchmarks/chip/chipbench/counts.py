"""Operations and bytes of the serving path, from the configuration's shapes.

The arithmetic follows the program's roofline model (`decode_byte_terms`,
`model_flops_for` in `launch/roofline.py`) and is kept here, apart from the
program, so that a change to the program cannot move the yardstick.  Peaks
come from `peaks.json`, keyed by the device kind JAX reports; a device that
is not in the table is an error.
"""

from __future__ import annotations

import json
from pathlib import Path

from chipbench.spec import Spec

#: output features that share one f32 scale in the served int8 weights
#: (64-row blocks of the output-major stored layout, each spanning the row)
INT8_SCALE_ROWS = 64


def peaks(device_kind: str, path: Path) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _matrices(s: Spec) -> list[tuple[int, int]]:
    """(in, out) of every projection of one layer."""
    d, q, kv = s.hidden, s.heads * s.head_dim, s.kv_heads * s.head_dim
    return [(d, q), (d, kv), (d, kv), (q, d), (d, s.ff), (d, s.ff), (s.ff, d)]


def proj_params(s: Spec) -> int:
    """Projection weights of one layer (attention and SwiGLU MLP)."""
    return sum(i * o for i, o in _matrices(s))


def proj_weight_bytes(s: Spec) -> float:
    """Bytes of every layer's projection weights as served: what one decode
    step streams through `bgemv`, and one prefill through `bgemm`."""
    if s.weights == "int8":
        scales = sum(-(-o // INT8_SCALE_ROWS) for _, o in _matrices(s))
        return s.layers * (proj_params(s) + 4.0 * scales)
    return s.layers * proj_params(s) * 2.0


def proj_act_bytes(s: Spec, rows: int) -> float:
    """bf16 activations in and out of every projection for `rows` tokens."""
    return s.layers * rows * 2.0 * sum(i + o for i, o in _matrices(s))


def head_flops(s: Spec) -> float:
    return 2.0 * s.hidden * s.vocab


def token_flops(s: Spec, ctx: int) -> float:
    """One token through every layer, attending `ctx` keys (itself
    included): projections plus the two attention products."""
    return s.layers * (2.0 * proj_params(s) + 4.0 * s.heads * s.head_dim * ctx)


def prompt_flops(s: Spec, plen: int) -> float:
    """A causal prefill of `plen` real tokens, with logits at the last."""
    return (s.layers * (2.0 * proj_params(s) * plen
                        + 2.0 * s.heads * s.head_dim * plen * (plen + 1))
            + head_flops(s))


def decode_flops(s: Spec, ctx: int) -> float:
    """One decode step of one request whose new token attends `ctx` keys."""
    return token_flops(s, ctx) + head_flops(s)


def request_flops(s: Spec, plen: int, gen: int) -> float:
    """Useful work of a whole request: its prompt, then `gen - 1` decode
    steps (the first token comes from the prefill)."""
    n = gen - 1
    decode = n * (s.layers * 2.0 * proj_params(s) + head_flops(s))
    # sum of ctx = plen+1 .. plen+n
    decode += s.layers * 4.0 * s.heads * s.head_dim * (n * plen + n * (n + 1) / 2)
    return prompt_flops(s, plen) + decode


def bgemv_step(s: Spec) -> tuple[float, float]:
    """(flops, bytes) of one decode step's projections over the slot grid."""
    return (2.0 * s.slots * s.layers * proj_params(s),
            proj_weight_bytes(s) + proj_act_bytes(s, s.slots))


def bgemm_prefill(s: Spec, plen: int) -> tuple[float, float]:
    """(flops, bytes) of one admission prefill's projections: the whole
    (slots, plen) grid, padding rows included, since the kernel does that
    work."""
    rows = s.slots * plen
    return (2.0 * rows * s.layers * proj_params(s),
            proj_weight_bytes(s) + proj_act_bytes(s, rows))


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

"""A configuration file, read into the numbers the yardstick needs.

The file holds the published `config.json` keys of the model, as run, plus
three groups of the benchmark's own: `serve` (the deployment on one chip),
`program` (how the adapter builds the program's config) and `correct` (the
limit of the output comparison, with the readings it was set from).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

DTYPES = ("bfloat16", "int8")


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    rotary_frac: float
    norm: str            # "layer" | "rms"
    norm_eps: float
    qkv_bias: bool
    tied: bool
    weights: str         # served weight type: "bfloat16" | "int8"
    kv_cache: str        # served KV cache type: "bfloat16" | "int8"
    page_size: int
    slots: int

    @property
    def rotary_dim(self) -> int:
        return int(round(self.head_dim * self.rotary_frac))


def _need(raw: dict, key: str, path: Path):
    if key not in raw:
        raise ValueError(f"{path}: missing key {key!r}")
    return raw[key]


def load(path: Path) -> tuple[Spec, dict]:
    """Read a configuration file; returns (spec, raw JSON)."""
    path = Path(path)
    raw = json.loads(path.read_text())
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{path}: only SwiGLU (hidden_act silu) is modelled")
    if "layer_norm_eps" in raw:
        norm, eps = "layer", float(raw["layer_norm_eps"])
    else:
        norm, eps = "rms", float(_need(raw, "rms_norm_eps", path))
    hidden = int(_need(raw, "hidden_size", path))
    heads = int(_need(raw, "num_attention_heads", path))
    serve = _need(raw, "serve", path)
    spec = Spec(
        name=path.stem,
        hidden=hidden,
        layers=int(_need(raw, "num_hidden_layers", path)),
        heads=heads,
        kv_heads=int(raw.get("num_key_value_heads", heads)),
        head_dim=int(raw.get("head_dim", hidden // heads)),
        ff=int(_need(raw, "intermediate_size", path)),
        vocab=int(_need(raw, "vocab_size", path)),
        rope_theta=float(raw.get("rope_theta", 10000.0)),
        rotary_frac=float(raw.get("partial_rotary_factor", 1.0)),
        norm=norm,
        norm_eps=eps,
        qkv_bias=bool(raw.get("use_qkv_bias", raw.get("bias", False))),
        tied=bool(raw.get("tie_word_embeddings", False)),
        weights=serve["weights"],
        kv_cache=serve["kv_cache"],
        page_size=int(serve["page_size"]),
        slots=int(serve["slots"]),
    )
    for what in (spec.weights, spec.kv_cache):
        if what not in DTYPES:
            raise ValueError(f"{path}: served type {what!r} not in {DTYPES}")
    return spec, raw

"""Where the decode step's projection weights live, from the compiled HLO.

Compiles the paged continuous-batching decode step (8 slots, 16-token
pages, 1536 positions a slot, under the pallas backend) for a DESCRIBED
TPU v5e, with no chip attached, and prints every `_bgemv_call` of the layer
scan with the shape and memory space of each operand: `S(1)` is VMEM, no
`S(...)` HBM.  An operand produced by a `dynamic-slice` fusion is a
per-layer copy of a weight cut out of its stack.

    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/decode_step_hlo.py \\
        stablelm|internlm2 [--hlo out.txt]

`stablelm` is stablelm-1.6b whole (bf16); `internlm2` internlm2-20b at 8
layers with int8 weights and an int8 KV cache, as the benchmark cells run
them.
"""

from __future__ import annotations

import argparse
import dataclasses
import re

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from repro.core import blas
from repro.kernels import ops
from repro.launch import steps
from repro.models import layers, transformer as tf
from repro.models.registry import get_config

MODELS = {"stablelm": ("stablelm-1.6b", 24, False),
          "internlm2": ("internlm2-20b", 8, True)}


def compiled_decode_step(model: str) -> str:
    name, n_layers, int8 = MODELS[model]
    cfg = dataclasses.replace(get_config(name, "full"), n_layers=n_layers)
    if int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    ops._interpret = lambda: False          # compile the kernels, not run them
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def params():
        p = tf.init_params(jax.random.PRNGKey(0), cfg)
        return layers.quantize_weights(p) if int8 else p

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    p = on_chip(jax.eval_shape(params))
    cache = on_chip(jax.eval_shape(lambda: tf.init_cache(
        cfg, 8, 1536, per_slot=True, page_size=16)))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=dev)
    live = jax.ShapeDtypeStruct((8,), jnp.bool_, sharding=dev)
    with blas.use_backend("pallas"):
        step = jax.jit(steps.make_decode_step_slots(cfg), donate_argnums=(2,))
        return step.lower(p, tok, cache, live).compile().as_text()


def bgemv_operands(hlo: str) -> list[str]:
    """One line per `_bgemv_call`: each operand's name, shape and space."""
    defs = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+\[[^\]]*\]\{[^}]*\})", line)
        if m:
            defs[m.group(1)] = m.group(2)
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*%(_bgemv_call\.\d+) = \S+ custom-call\(([^)]*)\)",
                     line)
        if not m:
            continue
        args = [a.strip().split("*/")[-1].lstrip("%")
                for a in m.group(2).split(",")]
        desc = []
        for a in args:
            shape = defs.get(a, "?")
            space = re.search(r"S\((\d)\)", shape)
            where = {"1": "VMEM"}.get(space.group(1), "S" + space.group(1)) \
                if space else "HBM"
            desc.append(f"{a} {shape.split('{')[0]} {where}")
        out.append(f"{m.group(1)}: " + "; ".join(desc))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", choices=sorted(MODELS))
    ap.add_argument("--hlo", default=None, help="also write the whole HLO")
    args = ap.parse_args()
    hlo = compiled_decode_step(args.model)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    for line in bgemv_operands(hlo):
        print(line)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax ---------------------------------------
import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax                       # noqa: E402
import numpy as np               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import SHAPES, shape_applicable     # noqa: E402
from repro.core import act_sharding                         # noqa: E402
from repro.launch import roofline as rl                     # noqa: E402
from repro.launch import sharding as shd                    # noqa: E402
from repro.launch import specs, steps                       # noqa: E402
from repro.launch.mesh import dp_axes, make_production_mesh  # noqa: E402
from repro.models.registry import ARCH_IDS, get_config      # noqa: E402
from repro.optim import adamw                               # noqa: E402

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell this driver runs THREE
lowerings on the production mesh (16x16 single pod / 2x16x16 multi-pod,
built from 512 forced host devices — the XLA_FLAGS line above MUST precede
any jax import):

  1. MEMORY lowering — the full config, scans intact, microbatched exactly
     as production would run it.  Its compile success is the sharding-
     coherence proof and its memory_analysis() the fits-on-chip proof.
  2/3. COST lowerings — XLA's cost_analysis counts a while-loop body ONCE,
     so flops/bytes/collectives inside lax.scan are invisible.  These two
     lowerings unroll every scan at reduced depth L0 and 2*L0 and the cell's
     true per-step cost is the exact linear extrapolation
         c(L) = c(L0) + (c(2*L0) - c(L0)) / L0 * (L - L0).
     Attention runs its single-block path in cost mode (identical flops to
     the chunked path, which computes every masked block anyway).

Nothing allocates device memory: inputs are ShapeDtypeStructs and compile()
only builds executables.  Results: one JSON per cell in results/dryrun/.
"""


def _rep(mesh):
    return NamedSharding(mesh, P())


def _cost_cfg(cfg, layers: int, cell):
    """Reduced-depth, fully-unrolled variant for cost lowerings."""
    kw = dict(n_layers=layers, scan_unroll=True, attn_full_scores=True, remat=cfg.remat)
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=layers)
    if cfg.ssm is not None and cfg.ssm.shared_attn_every:
        pass  # layers chosen as a multiple of shared_attn_every by caller
    # ssm/rwkv chunk scans unroll too; cap the unrolled step count (the
    # chunk is an implementation parameter — intra-chunk flops grow O(C),
    # noted in EXPERIMENTS.md; production would tune it per sequence length)
    if cell.kind != "decode":
        if cfg.rwkv is not None:
            c = 128 if cell.seq_len >= 32768 else 64
            kw["rwkv"] = dataclasses.replace(cfg.rwkv, chunk=c)
        if cfg.ssm is not None:
            c = 512 if cell.seq_len >= 32768 else 128
            kw["ssm"] = dataclasses.replace(cfg.ssm, chunk=c)
    return dataclasses.replace(cfg, **kw)


def _unit_counts(cfg, cell):
    """(L0, L_full) in 'layer units' for linear extrapolation."""
    if cfg.family == "hybrid" and cfg.ssm.shared_attn_every:
        every = cfg.ssm.shared_attn_every
        return every, cfg.n_layers
    return 4, cfg.n_layers


def optimizer_profile(cfg) -> adamw.AdamWConfig:
    """100B+ archs use the lean profile (bf16 moments, no separate master —
    the blockwise-8bit-Adam stand-in) so optimizer state fits a single pod;
    see EXPERIMENTS.md §Dry-run notes."""
    if cfg.param_count() > 50e9:
        return adamw.AdamWConfig(
            use_master=False, state_dtype="bfloat16", accum_dtype="bfloat16"
        )
    return adamw.AdamWConfig()


def build_cell(arch: str, shape: str, mesh, cfg=None, microbatches: int = 1):
    """Returns (fn, args_sds, in_shardings, out_shardings, cfg, cell, donate)."""
    cfg = cfg or get_config(arch, "full")
    cell = SHAPES[shape]
    batch_sds = specs.input_specs(cfg, cell)
    bspecs = shd.batch_specs(cfg, cell, mesh)
    bspecs = {k: bspecs[k] for k in batch_sds}
    bshard = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}

    if cell.kind == "train":
        state_sds = specs.state_spec(cfg, optimizer_profile(cfg))
        pspecs = shd.param_specs(state_sds["params"], cfg, mesh)
        ospecs = shd.opt_state_specs(state_sds["params"], cfg, mesh)
        as_sh = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
        opt_shard = {
            "m": as_sh(ospecs["m"]),
            "v": as_sh(ospecs["v"]),
            "count": _rep(mesh),
        }
        if "master" in state_sds["opt"]:
            opt_shard["master"] = as_sh(ospecs["master"])
        state_shard = {"params": as_sh(pspecs), "opt": opt_shard}
        optcfg = optimizer_profile(cfg)
        fn = steps.make_train_step(cfg, optcfg, microbatches=microbatches)
        metrics_shard = {"loss": _rep(mesh), "lr": _rep(mesh), "grad_norm": _rep(mesh)}
        return fn, (state_sds, batch_sds), (state_shard, bshard), (state_shard, metrics_shard), cfg, cell, (0,)

    params_sds = specs.params_spec(cfg)
    pshard = jax.tree.map(
        lambda s: NamedSharding(mesh, s), shd.param_specs_serve(params_sds, cfg, mesh)
    )
    cache_sds = specs.cache_spec(cfg, cell)
    cshard = jax.tree.map(lambda s: NamedSharding(mesh, s), shd.cache_specs(cache_sds, cfg, cell, mesh))
    tok_shard = NamedSharding(mesh, shd.batch_specs(cfg, cell, mesh)["tokens"])
    if cell.kind == "prefill":
        fn = steps.make_prefill_step(cfg)
        return fn, (params_sds, batch_sds, cache_sds), (pshard, bshard, cshard), (tok_shard, cshard), cfg, cell, (2,)
    fn = steps.make_serve_step(cfg)
    return (
        fn,
        (params_sds, batch_sds["tokens"], cache_sds),
        (pshard, tok_shard, cshard),
        (tok_shard, cshard),
        cfg, cell, (2,),
    )


REDUCE_DTYPE = {"value": None}  # set by --reduce-bf16 (hillclimb variant)


def _compile(arch, shape, mesh, cfg, microbatches):
    fn, args, in_sh, out_sh, cfg, cell, donate = build_cell(
        arch, shape, mesh, cfg=cfg, microbatches=microbatches
    )
    dp = shd.data_axes_for(cfg, mesh)
    dpsz = int(np.prod([mesh.shape[a] for a in dp]))
    batch_ok = cell.global_batch % dpsz == 0
    tp = None if cfg.mesh_strategy == "dp" else "model"
    seqres = None
    if (cfg.mesh_strategy == "2d" and cell.kind in ("train", "prefill")
            and cell.seq_len % mesh.shape["model"] == 0):
        seqres = "model"
    with mesh:
        act_sharding.set_policy(
            mesh, dp=dp if batch_ok else (), tp=tp,
            sp=None if batch_ok else "data", seqres=seqres,
            reduce_dtype=REDUCE_DTYPE["value"],
        )
        try:
            lowered = jax.jit(
                fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=donate
            ).lower(*args)
            compiled = lowered.compile()
        finally:
            act_sharding.clear_policy()
    return compiled, cfg, cell


def cost_analysis_dict(compiled) -> dict:
    """compiled.cost_analysis(), or {} when the backend reports none."""
    return compiled.cost_analysis() or {}


def _cost_dict(compiled):
    cost = cost_analysis_dict(compiled)
    coll = rl.parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": coll.wire_bytes,
        "counts": coll.counts,
        "raw_bytes": coll.raw_bytes,
    }


def _extrapolate(c1: dict, c2: dict, l0: int, l_full: int) -> dict:
    out = {}
    for k in ("flops", "bytes", "wire"):
        slope = (c2[k] - c1[k]) / l0
        out[k] = max(0.0, c1[k] + slope * (l_full - l0))
    # counts extrapolate the same way (informational)
    out["counts"] = {
        k: round(c1["counts"].get(k, 0) + (c2["counts"].get(k, 0) - c1["counts"].get(k, 0)) / l0 * (l_full - l0))
        for k in set(c1["counts"]) | set(c2["counts"])
    }
    out["raw_bytes"] = {
        k: c1["raw_bytes"].get(k, 0) + (c2["raw_bytes"].get(k, 0) - c1["raw_bytes"].get(k, 0)) / l0 * (l_full - l0)
        for k in set(c1["raw_bytes"]) | set(c2["raw_bytes"])
    }
    return out


def default_microbatches(cell, mesh, cfg=None) -> int:
    if cell.kind != "train":
        return 1
    axes = shd.data_axes_for(cfg, mesh) if cfg is not None else dp_axes(mesh)
    dpsz = int(np.prod([mesh.shape[a] for a in axes]))
    b_local = max(1, cell.global_batch // dpsz)
    return max(1, b_local // 2)  # ~2 rows per device per microbatch


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             cfg_overrides=None, tag: str = "", verbose: bool = True,
             microbatches: int = 0, skip_cost: bool = False):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    base_cfg = get_config(arch, "full")
    if cfg_overrides:
        base_cfg = dataclasses.replace(base_cfg, **cfg_overrides)
    if not shape_applicable(arch, base_cfg.family, shape):
        res = {"arch": arch, "shape": shape, "mesh": mesh_name, "status": "skipped",
               "reason": "long_500k requires sub-quadratic attention (DESIGN.md S4)"}
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{arch}__{shape}__{mesh_name}.json").write_text(json.dumps(res, indent=1))
        if verbose:
            print(f"[dryrun] {arch} {shape} {mesh_name}: SKIP (full attention @500k)", flush=True)
        return res

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cell = SHAPES[shape]
    mb = microbatches or default_microbatches(cell, mesh, base_cfg)

    # 1) memory lowering: full config, production microbatching
    t0 = time.time()
    compiled_mem, cfg, cell = _compile(arch, shape, mesh, base_cfg, mb)
    t_mem_compile = time.time() - t0
    ma = compiled_mem.memory_analysis()
    # donated inputs alias outputs: count them once
    mem_bytes = (
        ma.temp_size_in_bytes + ma.argument_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )
    mem_repr = str(ma)

    # 2/3) cost lowerings at L0 and 2*L0, fully unrolled
    if skip_cost:
        cost_full = _cost_dict(compiled_mem)
        l0 = None
        t_cost_compile = 0.0
    else:
        l0, l_full = _unit_counts(cfg, cell)
        t0 = time.time()
        c1, _, _ = _compile(arch, shape, mesh, _cost_cfg(base_cfg, l0, cell), 1)
        c2, _, _ = _compile(arch, shape, mesh, _cost_cfg(base_cfg, 2 * l0, cell), 1)
        t_cost_compile = time.time() - t0
        cost_full = _extrapolate(_cost_dict(c1), _cost_dict(c2), l0, l_full)

    lean = cfg.param_count() > 50e9
    roof = rl.Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=cost_full["flops"], hlo_bytes=cost_full["bytes"],
        wire_bytes=cost_full["wire"],
        model_flops=rl.model_flops_for(cfg, cell),
        peak_mem_bytes=mem_bytes,
        collectives={"counts": cost_full["counts"], "raw_bytes": cost_full["raw_bytes"]},
        analytic_bytes=rl.analytic_hbm_bytes(cfg, cell, chips, mb, lean),
    )
    result = {
        "status": "ok",
        "compile_s": {"memory": round(t_mem_compile, 1), "cost": round(t_cost_compile, 1)},
        "microbatches": mb,
        "cost_l0": l0,
        "memory_analysis": mem_repr,
        "fits_16g": bool(mem_bytes <= rl.HBM_PER_CHIP),
        **roof.to_dict(),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch}__{shape}__{mesh_name}{('__' + tag) if tag else ''}.json"
    (out_dir / fname).write_text(json.dumps(result, indent=1))
    if verbose:
        print(
            f"[dryrun] {arch} {shape} {mesh_name}{' ' + tag if tag else ''}: "
            f"compile {t_mem_compile:.0f}+{t_cost_compile:.0f}s  mem {mem_bytes/2**30:.1f}GiB"
            f"{' FITS' if mem_bytes <= rl.HBM_PER_CHIP else ' OVER'}  "
            f"t_comp {roof.t_compute*1e3:.2f}ms t_mem {roof.t_memory*1e3:.2f}ms "
            f"t_coll {roof.t_collective*1e3:.2f}ms -> {roof.bottleneck}  "
            f"useful {roof.useful_flops_ratio:.2f} roofline {roof.roofline_fraction:.1%}",
            flush=True,
        )
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--microbatches", type=int, default=0, help="0 = auto")
    ap.add_argument("--skip-cost", action="store_true",
                    help="memory lowering only (no unrolled cost lowerings)")
    ap.add_argument("--remat", default=None, choices=["none", "full"])
    ap.add_argument("--moe-dispatch", default=None, choices=["einsum", "gather"])
    ap.add_argument("--mesh-strategy", default=None, choices=["2d", "dp"])
    ap.add_argument("--reduce-bf16", action="store_true",
                    help="bf16 TP partial-sum reductions (hillclimb variant)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache (decode hillclimb variant)")
    args = ap.parse_args()

    if args.reduce_bf16:
        REDUCE_DTYPE["value"] = "bfloat16"
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out = Path(args.out)

    failures = []
    for arch in archs:
        for shape in shapes:
            ov = {}
            if args.remat:
                ov["remat"] = args.remat
            if args.mesh_strategy:
                ov["mesh_strategy"] = args.mesh_strategy
            if args.kv_int8:
                ov["kv_cache_dtype"] = "int8"
            if args.moe_dispatch:
                cfgm = get_config(arch, "full").moe
                if cfgm is not None:
                    ov["moe"] = dataclasses.replace(cfgm, dispatch=args.moe_dispatch)
            for mp in meshes:
                try:
                    run_cell(arch, shape, mp, out, cfg_overrides=ov or None,
                             tag=args.tag, microbatches=args.microbatches,
                             skip_cost=args.skip_cost)
                except Exception:
                    failures.append((arch, shape, mp))
                    print(f"[dryrun] FAIL {arch} {shape} multi_pod={mp}", flush=True)
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print("[dryrun] all requested cells passed", flush=True)


if __name__ == "__main__":
    main()

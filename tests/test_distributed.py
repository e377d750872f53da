"""Multi-device tests (8 forced host devices, run in subprocesses — jax locks
the device count at first init, so each scenario gets a fresh interpreter)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run8(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-4000:]}"
    return res.stdout


def test_distributed_gemm_schedules():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import distributed as D
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((8,), ("model",))
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (128, 96), jnp.float32)
    ref = np.asarray(a @ b)
    for fn in (D.all_gather_gemm, D.ring_gemm, D.psum_gemm):
        np.testing.assert_allclose(np.asarray(fn(a, b, mesh, axis="model")), ref, rtol=1e-4, atol=1e-4)
    mesh2 = make_test_mesh((2, 2), ("data", "model"))
    np.testing.assert_allclose(np.asarray(D.block_parallel_gemm(a, b, mesh2)), ref, rtol=1e-4, atol=1e-4)
    """)


def test_ring_gemm_uses_collective_permute():
    """The ring schedule must lower to collective-permute (overlappable),
    not all-gather — the paper's AE5 overlap at mesh scale."""
    run8("""
    import jax, jax.numpy as jnp
    from repro.core import distributed as D
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((8,), ("model",))
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 96), jnp.float32)
    txt = jax.jit(lambda a, b: D.ring_gemm(a, b, mesh)).lower(a, b).compile().as_text()
    assert "collective-permute" in txt, "ring gemm lost its permute"
    assert "all-gather" not in txt, "ring gemm degenerated to all-gather"
    """)


def test_pipeline_parallel_matches_sequential():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.pipeline import pipeline_apply
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((4,), ("stage",))
    L, d = 8, 16
    keys = jax.random.split(jax.random.PRNGKey(0), L)
    params = {"w": jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in keys])}
    def block(lp, x):
        return jnp.tanh(x @ lp["w"])
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 4, d))  # (M, mb, T, d)
    out = pipeline_apply(params, x, block, mesh, axis="stage")
    # sequential reference
    def seq(x):
        def body(c, lp):
            return block(lp, c), None
        out, _ = jax.lax.scan(body, x, params)
        return out
    ref = jax.vmap(seq)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
    """)


def test_sharded_train_step_matches_single_device():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding
    from repro.configs.base import ShapeCell
    from repro.core import act_sharding
    from repro.launch import sharding as shd, steps as steps_lib
    from repro.launch.mesh import make_test_mesh
    from repro.models import transformer as tf
    from repro.models.registry import get_config
    from repro.optim import adamw

    cfg = get_config("internlm2-20b", "smoke")
    cell = ShapeCell("t", 32, 8, "train")
    optcfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    state = {"params": params, "opt": adamw.init(params, optcfg)}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    step = steps_lib.make_train_step(cfg, optcfg)

    # single device reference
    s_ref, m_ref = jax.jit(step)(state, batch)

    # sharded: 2x4 mesh with full 2D sharding rules + activation policy
    mesh = make_test_mesh((2, 4), ("data", "model"))
    pspecs = shd.param_specs(state["params"], cfg, mesh)
    ospecs = shd.opt_state_specs(state["params"], cfg, mesh)
    as_sh = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
    st_sh = {"params": as_sh(pspecs), "opt": {"m": as_sh(ospecs["m"]), "v": as_sh(ospecs["v"]),
             "master": as_sh(ospecs["master"]), "count": NamedSharding(mesh, jax.sharding.PartitionSpec())}}
    bspecs = shd.batch_specs(cfg, cell, mesh)
    b_sh = {k: NamedSharding(mesh, bspecs[k]) for k in batch}
    with mesh:
        act_sharding.set_policy(mesh, dp=("data",), tp="model")
        try:
            s_sh, m_sh = jax.jit(step, in_shardings=(st_sh, b_sh))(state, batch)
        finally:
            act_sharding.clear_policy()
    assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3
    for a, b in zip(jax.tree.leaves(s_ref["params"]), jax.tree.leaves(s_sh["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-3)
    print("sharded == single-device OK")
    """)


def test_compressed_psum_grads():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    from repro.optim import compression

    mesh = make_test_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))  # one grad row per replica
    ef = jnp.zeros((8, 4096))

    def body(g_loc, ef_loc):
        tree, new_ef = compression.compressed_psum({"g": g_loc[0]}, {"g": ef_loc[0]}, "data", 8)
        return tree["g"][None], new_ef["g"][None]

    reduced, new_ef = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                                    out_specs=(P("data"), P("data")), check_vma=False)(g, ef)
    exact = np.asarray(g).mean(0)
    got = np.asarray(reduced)[0]
    # quantization error bounded by ~|g|_max/127
    bound = np.abs(np.asarray(g)).max() / 127.0 + 1e-6
    assert np.abs(got - exact).max() <= bound
    # all replicas agree
    assert np.allclose(np.asarray(reduced)[0], np.asarray(reduced)[7])
    print("compressed psum OK")
    """)


def test_moe_dispatch_equivalence_sharded():
    run8("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import MoEConfig
    from repro.models import moe
    mcfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=16, capacity_factor=4.0)
    params = moe.init_moe(jax.random.PRNGKey(0), 16, mcfg, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 16), jnp.float32)
    y1, _ = moe.moe_einsum(params, x, mcfg, "swiglu")
    y2, _ = moe.moe_gather(params, x, mcfg, "swiglu")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    print("dispatch equivalence OK")
    """)


def test_elastic_checkpoint_reshard():
    run8("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import checkpoint
    from repro.launch.mesh import make_test_mesh

    mesh8 = make_test_mesh((8,), ("data",))
    x = jnp.arange(64.0).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh8, P("data", None)))
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, 1, {"x": xs})
        # "restart" onto a different logical mesh (4x2)
        mesh42 = make_test_mesh((4, 2), ("data", "model"))
        sh = {"x": NamedSharding(mesh42, P("data", "model"))}
        template = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        restored = checkpoint.restore(d, 1, template, shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))
        assert restored["x"].sharding.spec == P("data", "model")
    print("elastic reshard OK")
    """)


def test_small_mesh_dryrun_cell():
    """The dry-run machinery itself, on an 8-device mesh (fast CI analog of
    the 512-device run)."""
    run8("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.configs.base import ShapeCell
    from repro.core import act_sharding
    from repro.launch import roofline as rl, sharding as shd, steps as steps_lib, specs
    from repro.launch.mesh import make_test_mesh
    from repro.models.registry import get_config
    from repro.optim import adamw

    cfg = get_config("stablelm-1.6b", "smoke")
    cell = ShapeCell("t", 64, 8, "train")
    mesh = make_test_mesh((2, 4), ("data", "model"))
    state_sds = specs.state_spec(cfg)
    pspecs = shd.param_specs(state_sds["params"], cfg, mesh)
    batch_sds = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    fn = steps_lib.make_train_step(cfg, adamw.AdamWConfig())
    with mesh:
        act_sharding.set_policy(mesh, dp=("data",), tp="model")
        try:
            lowered = jax.jit(fn).lower(state_sds, batch_sds)
            compiled = lowered.compile()
        finally:
            act_sharding.clear_policy()
    from repro.launch.dryrun import cost_analysis_dict
    cost = cost_analysis_dict(compiled)
    assert cost.get("flops", 0) > 0
    stats = rl.parse_collectives(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    print("dryrun cell OK; collectives:", stats.counts)
    """)

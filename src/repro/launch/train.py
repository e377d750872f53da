"""End-to-end training driver with checkpoint/restart fault tolerance.

Runs for real on this CPU host with --variant smoke (reduced configs); the
full configs are exercised by the dry-run (launch/dryrun.py).  Fault
tolerance is demonstrable here: --fail-at-step crashes mid-run, and
re-launching with the same --ckpt-dir resumes bit-exactly (asserted in
tests/test_train_driver.py).

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --variant smoke --steps 50 --ckpt-dir /tmp/ckpt --ckpt-every 10
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.configs.base import SHAPES, ShapeCell
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch import compile_cache
from repro.launch import steps as steps_lib
from repro.models import transformer as tf
from repro.models.registry import get_config
from repro.optim import adamw


class StragglerWatchdog:
    """Step-time EMA watchdog: flags steps slower than `factor` x EMA.

    On a real cluster this feeds the control plane (preempt + re-form from
    the last checkpoint — see README 'Failure handling'); here it logs.
    """

    def __init__(self, factor: float = 2.5, alpha: float = 0.2):
        self.ema = None
        self.factor = factor
        self.alpha = alpha
        self.flags = 0

    def observe(self, dt: float) -> bool:
        slow = self.ema is not None and dt > self.factor * self.ema
        self.ema = dt if self.ema is None else (1 - self.alpha) * self.ema + self.alpha * dt
        if slow:
            self.flags += 1
        return slow


def train(arch: str, variant: str = "smoke", steps: int = 20, seq: int = 64,
          batch: int = 8, ckpt_dir: str | None = None, ckpt_every: int = 10,
          fail_at_step: int = -1, microbatches: int = 1, log_every: int = 5,
          lr: float = 3e-4, seed: int = 0, keep: int = 3):
    cfg = get_config(arch, variant)
    cell = ShapeCell("custom", seq, batch, "train")
    optcfg = adamw.AdamWConfig(lr=lr, warmup_steps=max(2, steps // 10), total_steps=steps)

    # init or resume
    start_step = 0
    state = None
    if ckpt_dir:
        last = checkpoint.latest_step(ckpt_dir)
        if last is not None:
            def _template():
                p = tf.init_params(jax.random.PRNGKey(seed), cfg)
                return {"params": p, "opt": adamw.init(p)}

            template = jax.eval_shape(_template)
            state = checkpoint.restore(ckpt_dir, last, template)
            start_step = last
            print(f"[train] resumed from step {last}", flush=True)
    if state is None:
        params = tf.init_params(jax.random.PRNGKey(seed), cfg)
        state = {"params": params, "opt": adamw.init(params)}

    step_fn = jax.jit(
        steps_lib.make_train_step(cfg, optcfg, microbatches=microbatches),
        donate_argnums=(0,),
    )
    source = SyntheticLM(cfg, cell, seed=seed)
    prefetch = Prefetcher(source, start_step)
    watchdog = StragglerWatchdog()

    losses = []
    try:
        for step in range(start_step, steps):
            t0 = time.time()
            got_step, batch_data = prefetch.next()
            assert got_step == step, (got_step, step)
            batch_jnp = {k: jnp.asarray(v) for k, v in batch_data.items()}
            state, metrics = step_fn(state, batch_jnp)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            if watchdog.observe(dt):
                print(f"[train] WARN straggler: step {step} took {dt:.2f}s "
                      f"(ema {watchdog.ema:.2f}s)", flush=True)
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step}  loss {loss:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  {dt*1e3:.0f}ms", flush=True)
            done = step + 1
            if ckpt_dir and (done % ckpt_every == 0 or done == steps):
                checkpoint.save(ckpt_dir, done, state)
                checkpoint.retain(ckpt_dir, keep=keep)
            if fail_at_step >= 0 and done == fail_at_step:
                print(f"[train] FAULT INJECTION: crashing after step {step}", flush=True)
                raise SystemExit(17)
    finally:
        prefetch.stop()
    return state, losses


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at-step", type=int, default=-1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()
    train(
        arch=args.arch, variant=args.variant, steps=args.steps, seq=args.seq,
        batch=args.batch, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        fail_at_step=args.fail_at_step, microbatches=args.microbatches,
        lr=args.lr, seed=args.seed,
    )


if __name__ == "__main__":
    main()

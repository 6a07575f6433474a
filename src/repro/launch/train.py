"""Fault-tolerant training driver.

Checkpoint/restart, async saves, straggler detection, deterministic resume
(index-based data cursor), elastic restart (mesh re-derived from the live
device fleet), optional failure injection for testing the recovery path.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 200 --ckpt-dir /tmp/ckpt [--simulate-failure 57]
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ck
from repro.configs import EngineConfig, get_config
from repro.data.pipeline import DataConfig, DataIterator, make_source
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_from_devices
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


class StragglerMonitor:
    """EMA step-time monitor: flags slow steps (at fleet scale this signal
    feeds re-meshing / hot-spare swap; here it logs)."""

    def __init__(self, factor: float = 2.0, alpha: float = 0.1):
        self.ema = None
        self.factor = factor
        self.alpha = alpha
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        slow = self.ema is not None and dt > self.factor * self.ema
        self.ema = dt if self.ema is None else \
            (1 - self.alpha) * self.ema + self.alpha * dt
        if slow:
            self.flagged += 1
        return slow


def train(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "block", "full"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="crash (exit 17) once at this step, pre-restore")
    ap.add_argument("--data-seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rt = Runtime()
    model = Model(cfg, rt)
    acfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                       total_steps=args.steps)
    eng = EngineConfig(remat=args.remat, microbatches=args.microbatches)

    mesh = mesh_from_devices()
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}")

    params = model.init(jax.random.PRNGKey(0))
    state = init_train_state(params, acfg)
    dcfg = DataConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                      vocab_size=cfg.vocab_size, seed=args.data_seed)
    it = DataIterator(make_source(dcfg))
    start_step = 0

    ckpt = None
    if args.ckpt_dir:
        ckpt = ck.AsyncCheckpointer(args.ckpt_dir, keep=3)
        latest = ck.latest_step(args.ckpt_dir)
        if latest is not None:
            state, extra = ck.restore_checkpoint(args.ckpt_dir, latest,
                                                 state)
            it.restore(extra.get("data_index", latest))
            start_step = latest
            print(f"[train] restored step {latest} "
                  f"(data cursor {it.state()})")

    step_fn = jax.jit(make_train_step(cfg, rt, acfg, eng),
                      donate_argnums=(0,))
    monitor = StragglerMonitor()

    def save_and_exit(signum, frame):   # graceful preemption
        if ckpt:
            ckpt.save(step, jax.device_get(state),
                      extra={"data_index": it.state()})
            ckpt.wait()
        print(f"[train] preempted at step {step}; checkpoint saved")
        sys.exit(0)

    signal.signal(signal.SIGTERM, save_and_exit)

    losses = []
    step = start_step
    with jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            if args.simulate_failure and step == args.simulate_failure \
                    and start_step < args.simulate_failure:
                print(f"[train] SIMULATED FAILURE at step {step}")
                os._exit(17)
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            if monitor.observe(dt):
                print(f"[train] straggler: step {step} took {dt:.2f}s "
                      f"(ema {monitor.ema:.2f}s)")
            if step % args.log_every == 0:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms",
                      flush=True)
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save(step, state, extra={"data_index": it.state()})
    if ckpt:
        ckpt.save(args.steps, state, extra={"data_index": it.state()})
        ckpt.wait()
    print(f"[train] done: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({monitor.flagged} straggler steps)")
    return losses


if __name__ == "__main__":
    train()

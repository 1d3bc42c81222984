"""End-to-end training driver.

Runs a real training loop on whatever devices exist (CPU smoke -> TPU pod;
the sharding specs are the same ones the dry-run validates at 512 chips).
Fault tolerance: periodic async checkpoints, restart-from-latest, optional
injected failures to exercise the supervisor.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --seq-len 256 --batch 8
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 30 --simulate-failures 2
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs import get_config, get_smoke_config
from repro.data import PackedStream
from repro.jax_setup import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import init_train_state, make_train_step
from repro.runtime import (RestartPolicy, StragglerDetector, WorkerFailure)
from repro.sharding import ShardingCtx, use_sharding
from repro.sharding import specs as sp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--simulate-failures", type=int, default=0,
                    help="inject N worker failures to exercise restart")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh()
    rules = sp.activation_rules(cfg, mesh, "train")
    ctx = ShardingCtx(mesh, rules)

    train_step = make_train_step(cfg, base_lr=args.lr, warmup=10,
                                 total_steps=args.steps)

    def wrapped(params, opt_state, batch):
        with use_sharding(ctx):
            return train_step(params, opt_state, batch)

    step_fn = jax.jit(wrapped, donate_argnums=(0, 1))

    params, opt_state = init_train_state(cfg, jax.random.PRNGKey(args.seed))
    n_params = models.count_params(params)
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M devices={len(jax.devices())}")

    stream = PackedStream(cfg.vocab_size, args.seq_len, seed=args.seed)
    ckpt = AsyncCheckpointer(args.ckpt_dir)
    policy = RestartPolicy()
    detector = StragglerDetector(n_workers=1)

    start = 0
    if latest_step(args.ckpt_dir) is not None:
        (params, opt_state), extras = restore(
            args.ckpt_dir, (params, opt_state))
        start = extras.get("step", 0)
        stream.restore(extras["data_state"]) if "data_state" in extras else None
        print(f"restored from checkpoint at step {start}")

    failures_left = args.simulate_failures
    step = start
    losses = []
    while step < args.steps:
        batch_np = stream.next_batch(args.batch)
        if cfg.n_prefix_tokens:
            batch_np["prefix_embeds"] = np.zeros(
                (args.batch, cfg.n_prefix_tokens, cfg.d_model), np.float32)
        if cfg.is_encoder_decoder:
            batch_np["encoder_embeds"] = np.random.default_rng(step).normal(
                size=(args.batch, cfg.encoder_seq, cfg.d_model)).astype(np.float32) * 0.02
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        t0 = time.time()
        try:
            if failures_left and step == start + 5:
                failures_left -= 1
                raise WorkerFailure(0, "(injected)")
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
        except WorkerFailure:
            now = time.time()
            policy.record_failure(now)
            if not policy.should_restart(now):
                raise
            ckpt.wait()
            ls = latest_step(args.ckpt_dir)
            if ls is not None:
                (params, opt_state), extras = restore(
                    args.ckpt_dir, jax.tree_util.tree_map(np.asarray,
                                                          (params, opt_state)))
                step = extras.get("step", 0)
                if "data_state" in extras:
                    stream.restore(extras["data_state"])
                print(f"[ft] restarted from step {step}")
            else:
                params, opt_state = init_train_state(
                    cfg, jax.random.PRNGKey(args.seed))
                step = 0
                print("[ft] no checkpoint; restarted from scratch")
            continue
        detector.record(0, time.time() - t0)
        step += 1
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"dt {time.time()-t0:.2f}s")
        if step % args.save_every == 0:
            ckpt.save(step, (params, opt_state),
                      {"step": step, "data_state": stream.snapshot()})
    ckpt.wait()
    assert losses and losses[-1] < losses[0], \
        f"loss did not improve: {losses[0]:.3f} -> {losses[-1]:.3f}"
    print(f"done: loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return losses


if __name__ == "__main__":
    main()

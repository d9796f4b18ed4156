"""End-to-end training driver with the full fault-tolerance loop:
sharded train step, periodic checkpoints, auto-resume, straggler
monitoring, elastic re-mesh on failure.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ck

On a real pod the same driver runs under ``jax.distributed.initialize``;
here it runs on however many devices the process sees.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLM
from repro.distributed import sharding as shd
from repro.distributed.elastic import StragglerMonitor
from repro.models.config import get_config, reduced
from repro.training import optim
from repro.training.optim import AdamWState
from repro.training.train_step import (TrainConfig, TrainState,
                                       build_train_step, init_train_state)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--wsd", action="store_true",
                    help="MiniCPM WSD schedule instead of cosine")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    lr = (optim.wsd_schedule(args.lr, warmup=10, stable=args.steps // 2,
                             decay=args.steps // 3) if args.wsd
          else optim.cosine_schedule(args.lr, warmup=10, total=args.steps))
    tcfg = TrainConfig(
        adamw=optim.AdamWConfig(lr=lr),
        microbatches=args.microbatches,
        compress_grads=args.compress_grads)
    step_fn = jax.jit(build_train_step(cfg, tcfg), donate_argnums=(0,))

    # data + state
    ds = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))

    # multi-device: shard params/opt over available devices
    n_dev = jax.device_count()
    if n_dev > 1:
        mesh = jax.make_mesh((1, n_dev), ("data", "model"), axis_types=(
            jax.sharding.AxisType.Auto,) * 2)
        pspecs = shd.param_specs(cfg, mesh)
        ospecs = shd.opt_state_specs(cfg, mesh)

        def put(tree, specs):
            return jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                tree, specs, is_leaf=lambda x: isinstance(x, P))
        state = TrainState(
            params=put(state.params, pspecs),
            opt=AdamWState(step=state.opt.step,
                           mu=put(state.opt.mu, ospecs),
                           nu=put(state.opt.nu, ospecs)),
            error_feedback=state.error_feedback)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr is not None:
        latest, restored = mgr.restore_latest(state)
        if latest is not None:
            print(f"[resume] from step {latest}")
            state, start = restored, latest

    mon = StragglerMonitor()
    t_all = time.time()
    for s in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(s).items()}
        if args.microbatches > 1:
            batch = {k: v.reshape((args.microbatches,
                                   v.shape[0] // args.microbatches)
                                  + v.shape[1:]) for k, v in batch.items()}
        t0 = time.time()
        state, m = step_fn(state, batch)
        dt = time.time() - t0
        mon.record(jax.process_index(), dt)
        if s % 10 == 0 or s == args.steps - 1:
            print(f"step {s:5d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} {dt*1e3:.0f}ms",
                  flush=True)
        if mgr is not None and (s + 1) % args.ckpt_every == 0:
            mgr.save(s + 1, state)
            print(f"[ckpt] step {s+1}")
    tok_s = (args.steps - start) * args.batch * args.seq / (
        time.time() - t_all)
    print(f"done: {tok_s:.0f} tok/s")


if __name__ == "__main__":
    main()

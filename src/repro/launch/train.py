"""End-to-end training driver.

Runs a REAL training loop (reduced arch configs by default, the published
ones with ``--published``; the same code path scales to the production
meshes) with: deterministic data, checkpoint/resume
(fault tolerance), async checkpointing, and a communication report from the
monitor at the end — the paper's workflow folded into the trainer.

  PYTHONPATH=src python -m repro.launch.train --arch granite_3_2b --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_8b --resume ...
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.compat import enable_compile_cache
from repro.core import monitor_fn
from repro.data import SyntheticLMData, host_transfer_log
from repro.launch.mesh import make_test_mesh
from repro.models import build_model
from repro.optim import OptConfig
from repro.parallel import Sharder
from repro.train import TrainConfig, init_train_state
from repro.train.train import (batch_shardings, make_train_step,
                               train_state_shardings)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b")
    ap.add_argument("--published", action="store_true",
                    help="the published config (default: the reduced one)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="2x2")
    ap.add_argument("--report", default="", help="write CommReport JSON here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    shape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_test_mesh(shape, ("data", "model")[:len(shape)])
    shd = Sharder(mesh)

    cfg = configs.config(args.arch, reduced=not args.published)
    model = build_model(cfg)
    ocfg = OptConfig(peak_lr=args.lr, warmup_steps=10,
                     decay_steps=max(100, args.steps))
    tcfg = TrainConfig(microbatches=args.microbatches)

    state = init_train_state(model, ocfg, jax.random.PRNGKey(args.seed))
    state_sh = train_state_shardings(model, ocfg, shd)
    state = jax.device_put(state, state_sh)

    data = SyntheticLMData(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                           global_batch=args.global_batch, seed=args.seed)
    batch0 = data.batch_at(0)
    b_sh = batch_shardings(jax.eval_shape(lambda: batch0), shd)

    step_fn = jax.jit(make_train_step(model, ocfg, tcfg, shd),
                      in_shardings=(state_sh, b_sh),
                      out_shardings=(state_sh, None))

    start = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir)
        if args.resume:
            last = latest_step(args.ckpt_dir)
            if last is not None:
                state = restore_checkpoint(args.ckpt_dir, last, state,
                                           shardings=state_sh)
                start = last
                print(f"[train] resumed from step {last}")

    t0 = time.perf_counter()
    losses = []
    for step in range(start, args.steps):
        batch = jax.device_put(data.batch_at(step), b_sh)
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % 10 == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f} ({dt:.1f}s)")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state)
    if ckpt:
        ckpt.wait()

    if args.report:
        rep = monitor_fn(make_train_step(model, ocfg, tcfg, shd),
                         jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                             x.shape, x.dtype), state),
                         jax.eval_shape(lambda: batch0),
                         mesh=mesh, name=f"train[{args.arch}]",
                         in_shardings=(state_sh, b_sh),
                         host_transfers=host_transfer_log())
        print(rep.render())
        rep.save(args.report)
    if losses:
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    else:
        print(f"[train] nothing to do (resumed at step {start})")
    return losses


if __name__ == "__main__":
    enable_compile_cache()
    main()

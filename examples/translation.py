"""Paper §4.1: machine-translation model (GNMT-style), data-parallel +
monitored **per phase**, with per-primitive communication matrices (paper
Fig. 3) and the Table-2 breakdown split fwd / bwd / optim.

Trains the seq2seq model on a synthetic copy-reverse task (AdamW + bucketed
DDP AllReduce inside shard_map) until it learns, then monitors the step as a
three-phase :class:`~repro.core.session.MonitorSession`:

* ``fwd``   -- loss forward pass (+ the ``pmean`` loss all-reduce),
* ``bwd``   -- backward pass with the paper's bucketed gradient AllReduce,
* ``optim`` -- the AdamW update (local math: zero collectives -- visible as
  an empty row in the per-phase table, the point the paper's Table 2 cannot
  make because NCCL interception sees the whole step as one blob).

Run:  PYTHONPATH=src python examples/translation.py [--steps 150]
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import argparse
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import MonitorSession
from repro.data import SyntheticSeq2Seq
from repro.models.gnmt import GNMT
from repro.optim import OptConfig, apply_updates, init_opt_state
from repro.train import ddp
from repro.compat import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()

    mesh = make_mesh((8,), ("data",))
    model = GNMT(vocab=64, d=128, layers=2)
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticSeq2Seq(vocab_size=64, src_len=12, tgt_len=12,
                            global_batch=32)
    ocfg = OptConfig(peak_lr=3e-3, warmup_steps=10,
                     decay_steps=max(500, args.steps))
    opt = init_opt_state(params, ocfg)

    def step(params, opt, i, batch):
        (loss, _), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        # the paper's DDP pattern: bucketed AllReduce of every gradient
        grads, _ = ddp.allreduce_bucketed(grads, "data", bucket_mb=1.0)
        loss = jax.lax.pmean(loss, "data")
        params, opt, _ = apply_updates(params, grads, opt, ocfg, i)
        return params, opt, loss

    sharded_step = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P(), P("data")),
        out_specs=(P(), P(), P()), check_vma=False))

    l0 = None
    for i in range(args.steps):
        params, opt, loss = sharded_step(params, opt, jnp.asarray(i),
                                         data.batch_at(i))
        l0 = l0 if l0 is not None else float(loss)
        if i % 25 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f}", flush=True)
    assert float(loss) < l0 * 0.7, "translation model failed to learn"

    # ------------------------------------------------------------------
    # one monitored step, split into its phases: fwd / bwd / optim
    # ------------------------------------------------------------------
    def fwd(params, batch):
        loss, _ = model.loss_fn(params, batch)
        return jax.lax.pmean(loss, "data")

    def bwd(params, batch):
        (_, _), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        grads, _ = ddp.allreduce_bucketed(grads, "data", bucket_mb=1.0)
        return grads

    def optim(params, grads, opt, i):
        params, opt, _ = apply_updates(params, grads, opt, ocfg, i)
        return params, opt

    def dp(fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    batch = data.batch_at(0)
    grads_like = params                    # same pytree shapes as the grads
    session = MonitorSession(mesh=mesh, name="GNMT-MT")
    with session:
        with session.phase("fwd"):
            session.capture(dp(fwd, (P(), P("data")), P()), params, batch)
        with session.phase("bwd"):
            session.capture(dp(bwd, (P(), P("data")), P()), params, batch)
        with session.phase("optim"):
            session.capture(
                dp(optim, (P(), P(), P(), P()), (P(), P())),
                params, grads_like, opt, jnp.asarray(0))

    rep = session.report()
    print()
    print(rep.phase_table())               # Table 2, per phase
    print()
    print(rep.phase_diff("fwd", "bwd"))    # where the bytes come from
    for phase in rep.phase_names():
        view = rep.view(phase=phase)
        if view.total_wire_bytes() == 0:
            print(f"\nphase {phase}: no collective communication "
                  "(local math only)")
            continue
        print()
        print(rep.heatmap(phase=phase))
    rep.save("artifacts/translation_report.json")
    print(f"\ntranslation example OK (loss {l0:.3f} -> {float(loss):.3f})")


if __name__ == "__main__":
    main()

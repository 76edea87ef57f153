"""Irregular collectives: a skewed MoE all-to-all, monitored per phase.

Expert-parallel MoE routes token buffers between ranks with an all-to-all;
when the router runs hot (one expert drawing most of the tokens), the
per-rank byte counts become *irregular* -- and a scalar per-op byte model
flattens the hot expert into the group mean.  This walkthrough monitors a
small expert-parallel dispatch/combine program, injects the measured
routing skew through the capture's ``op_transform`` hook, and shows every
artifact that consumes the per-rank byte vector:

* the comm-matrix heatmap (the hot expert's row glows),
* the Table-2 summary (new skew column),
* the timed schedule (the collective finishes at the hot rank's pace),
* the ``skewed-a2a`` lint finding (priced vs a load-balanced routing).

Run:  PYTHONPATH=src python examples/moe_skew.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import dataclasses
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.core import MonitorSession
from repro.core.reporter import (ascii_heatmap, lint_table,
                                 primitive_usage_table)

N_EXPERTS = 8          # one expert per rank
CAP = 64               # tokens per (source, expert) capacity slot
D = 128                # token width
HOT_FRAC = 0.6         # expert 0 handles 60% of all tokens


def build_program(mesh):
    """Dispatch + expert MLP + combine, one expert per data-axis rank."""
    n = N_EXPERTS

    def step(tokens, wi, wo):
        # tokens local: (n, CAP, D) -- row e holds this rank's tokens
        # bound for expert e (capacity-padded dense dispatch buffers)
        recv = jax.lax.all_to_all(tokens, "data", 0, 0)        # dispatch
        h = jax.nn.silu(recv.reshape(n * CAP, D) @ wi) @ wo    # expert MLP
        return jax.lax.all_to_all(h.reshape(n, CAP, D),
                                  "data", 0, 0)                # combine

    return jax.shard_map(step, mesh=mesh,
                         in_specs=(P("data"), P(), P()),
                         out_specs=P("data"), check_vma=False)


def hot_expert_transform(op):
    """Attach the measured routing: 60% of the bytes live on rank 0.

    The compiled HLO sizes the a2a for the *capacity* -- the worst case --
    because XLA cannot know the routing.  At runtime the router decides,
    and this hook is where that knowledge enters the model: a per-rank
    byte vector whose sum is the op's payload, with ``HOT_FRAC`` of it on
    the hot expert's rank.
    """
    if op.kind not in ("all-to-all", "ragged-all-to-all"):
        return op
    m = op.group_size
    total = float(op.payload_bytes)
    vec = [total * (1.0 - HOT_FRAC) / (m - 1)] * m
    vec[0] = total * HOT_FRAC
    return dataclasses.replace(op, bytes_per_rank_vec=vec)


def main():
    mesh = make_mesh((N_EXPERTS,), ("data",))
    prog = build_program(mesh)
    f32 = jnp.float32
    tokens = jax.ShapeDtypeStruct((N_EXPERTS * N_EXPERTS, CAP, D), f32)
    wi = jax.ShapeDtypeStruct((D, 2 * D), f32)
    wo = jax.ShapeDtypeStruct((2 * D, D), f32)

    # --- phase 1: the balanced baseline (no transform: scalar bytes) ----
    with MonitorSession(mesh=mesh, name="moe") as sess:
        with sess.phase("balanced"):
            sess.capture(prog, tokens, wi, wo, name="moe_balanced")
        # --- phase 2: the same program with the measured hot routing ----
        with sess.phase("skewed"):
            sess.capture(prog, tokens, wi, wo, name="moe_skewed",
                         op_transform=hot_expert_transform)

    for phase in ("balanced", "skewed"):
        view = sess.view(phase=phase)
        print()
        print(primitive_usage_table(view.summary, title=f"{phase} dispatch"))
        print()
        print(ascii_heatmap(view.matrix, title=f"{phase} comm matrix"))

    # the skewed phase's a2a finishes when rank 0 does; the balanced one
    # spreads the same bytes evenly
    bal = sess.view(phase="balanced").collective_seconds()
    skw = sess.view(phase="skewed").collective_seconds()
    print(f"\nmodeled collective time: balanced {bal * 1e6:.2f} us, "
          f"skewed {skw * 1e6:.2f} us "
          f"({skw / bal:.2f}x -- the hot rank is the straggler)")

    # the lint pass prices exactly that gap as the rebalancing savings
    findings = [f for f in sess.view().lint() if f.rule_id == "skewed-a2a"]
    print()
    print(lint_table(findings, title="skewed-a2a findings"))


if __name__ == "__main__":
    main()

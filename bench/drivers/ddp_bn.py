"""Data-parallel training of a model with BatchNorm: steps of the program's
stateful DDP train step (each chip normalises its own shard, then chip 0's
running statistics are broadcast to every chip), and the monitor's capture
of it.  The structure, the job segment and the comparison of losses,
gradients and parameters are ``ddp.py``'s; this driver adds the running
statistics to the step, to the capture and to the check.

The check, once the window has closed: the plain reference takes the same
three steps on the host's CPU (float32 as XLA:CPU computes it, which
compiles in seconds where the TPU takes minutes at HIGHEST), and each
step's loss, the first gradient, the parameters' change after three and,
on every chip, the running statistics' change after three are compared
with it; the last report's bytes by kind are compared with the
configuration's count (a ring all-reduce of every gradient and the loss,
and the buffers forwarded from chip 0 over n - 1 hops), and its matrices
with the bytes each chip sends under them.
"""
from __future__ import annotations

import gc
import os

import jax
import numpy as np

import harness
import seeding

ddp = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ddp.py"),
    "bench_driver_ddp")
FIRST_STEPS = ddp.FIRST_STEPS


class Driver(ddp.Driver):
    def __init__(self, cell, seed: int, devices):
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.train import ddp as program_ddp

        self.cell, self.seed = cell, seed
        self.root = seeding.root_key(seed)
        self.cfg, self.mod, t = cell.config, cell.config_module, cell.traffic
        self.per_cycle = t["units_per_cycle"]
        if len(devices) != self.cfg["data_parallel"]:
            raise ValueError(f"{self.cfg['name']} runs on "
                             f"{self.cfg['data_parallel']} chips")
        self.devices = devices
        self.mesh = Mesh(np.array(devices), ("data",),
                         axis_types=(AxisType.Auto,))
        self.model = self.mod.build_model(self.cfg)
        self.step = program_ddp.make_ddp_train_step(
            self.model.stateful_loss_fn, self.mesh,
            mode=self.cfg["ddp_mode"], bucket_mb=self.cfg["bucket_mb"],
            lr=self.cfg["lr"], stateful=True)
        params, state = self.mod.make_params(self.cfg, self.root,
                                             NamedSharding(self.mesh, P()))
        if (jax.tree.structure(params)
                != jax.tree.structure(self.model.shapes())
                or jax.tree.structure(state)
                != jax.tree.structure(self.model.init_state())):
            raise ValueError("the weights' or the state's tree is not the "
                             "program's")
        self.ef = program_ddp.init_error_feedback(params)
        n = t["feed_batches"]
        split = NamedSharding(self.mesh, P("data"))
        self.feed = jax.jit(
            lambda root: [self.mod.make_batch(self.cfg, root, i)
                          for i in range(n)], out_shardings=split)(self.root)
        self.params, self.state = params, state
        self.sent, self.report = 0, None
        # the first steps, through the window's own call and feed
        self.first = {"p0": params, "s0": state}
        losses = []
        for i in range(FIRST_STEPS):
            losses.append(self._step())
            if i == 0:
                self.first["p1"] = self.params
        self.first["p3"], self.first["s3"] = self.params, self.state
        self.first["losses"] = losses

    def _step(self):
        batch = self.feed[self.sent % len(self.feed)]
        with jax.profiler.TraceAnnotation("ddp_step"):
            self.params, self.state, self.ef, loss = self.step(
                self.params, self.state, self.ef, batch)
        self.sent += 1
        return loss

    def monitor(self, out_dir: str, time) -> dict:
        from repro.core import MonitorSession, export
        t0 = time()
        sess = MonitorSession(mesh=self.mesh, name=f"ddp[{self.cfg['name']}]")
        cap = sess.capture(self.step, self.params, self.state, self.ef,
                           self.feed[self.sent % len(self.feed)], name="step")
        t1 = time()
        rep = sess.report()
        t2 = time()
        rep.save(os.path.join(out_dir, "report.json"))
        export.export_report(rep, "html", os.path.join(out_dir, "report.html"))
        t3 = time()
        self.report = rep
        return {"capture_s": t1 - t0, "lower_s": cap.trace_seconds,
                "compile_s": cap.compile_seconds, "build_s": t2 - t1,
                "export_s": t3 - t2}

    def release(self) -> None:
        """Copy what the check reads to the host, every chip's running
        statistics apart, and free the rest."""
        first = {k: jax.device_get(self.first[k]) for k in ("p0", "p1", "p3")}
        first["losses"] = [float(x) for x in jax.device_get(
            self.first["losses"])]
        first["s0"] = jax.device_get(self.first["s0"])
        first["s3"] = per_chip(self.first["s3"], self.devices)
        self.first = first
        self.params = self.state = self.ef = self.feed = self.step = None
        gc.collect()

    # -- the check ---------------------------------------------------------
    def report_checks(self) -> dict:
        """Bytes per step by kind against the configuration's count, and
        each kind's matrix against the bytes each chip sends under the
        report's algorithm (ring): the all-reduce's row and column sums
        2 (n - 1) / n of its payload on every chip, the buffers S from chip
        k to chip k + 1 for k < n - 1 and nothing else (exact, limit 0)."""
        rep = self.report
        n = len(self.devices)
        payload = self.mod.allreduce_payload_bytes(self.cfg)
        buffers = self.mod.broadcast_payload_bytes(self.cfg)
        by_kind: dict[str, float] = {}
        for op in rep.compiled_ops:
            by_kind[op.kind] = (by_kind.get(op.kind, 0.0)
                                + float(op.payload_bytes * op.weight))
        want = {"all-reduce": float(payload),
                "collective-permute": float((n - 1) * buffers)}
        kind_gap = max(abs(by_kind.get(k, 0.0) - want.get(k, 0.0))
                       for k in set(by_kind) | set(want))
        if rep.algorithm != "ring" or set(rep.per_primitive) != set(want):
            return {"collective_bytes": (kind_gap, 0.0),
                    "matrix_bytes": (float("inf"), 0.0)}
        ar = np.asarray(rep.per_primitive["all-reduce"], np.float64)
        per_chip_ar = 2.0 * (n - 1) / n * payload
        chain = np.zeros((n + 1, n + 1))
        for k in range(1, n):
            chain[k, k + 1] = buffers
        gaps = [np.abs(ar[1:, 1:].sum(axis=1) - per_chip_ar).max(),
                np.abs(ar[1:, 1:].sum(axis=0) - per_chip_ar).max(),
                np.abs(ar[0]).sum() + np.abs(ar[:, 0]).sum(),
                np.abs(np.asarray(rep.per_primitive["collective-permute"])
                       - chain).max(),
                np.abs(np.asarray(rep.matrix) - ar - chain).max()]
        return {"collective_bytes": (kind_gap, 0.0),
                "matrix_bytes": (float(max(gaps)), 0.0)}

    def program_readings(self) -> dict:
        """ddp.py's readings, and every chip's change of the running
        statistics over the three steps."""
        out = super().program_readings()
        s0 = self.first["s0"]
        out["buffer_change"] = [change(s0, s3) for s3 in self.first["s3"]]
        return out

    def reference_readings(self, precision: str = "f32", rows=None,
                           buffers: str = "first") -> dict:
        return reference_readings(self.cell, self.root, precision, rows,
                                  buffers)

    def check(self) -> dict:
        gaps = compare(self.program_readings(), self.reference_readings())
        out = {k: (v, self.cell.limits[k]) for k, v in gaps.items()}
        out.update(self.report_checks())
        return out


def reference_readings(cell, root, precision: str = "f32", rows=None,
                       buffers: str = "first") -> dict:
    """The plain reference's readings of the first steps on the host's
    CPU, in the form :func:`compare` takes (``reference_steps``'
    arguments)."""
    losses, grads, p0, p3, s0, s3 = cell.config_module.reference_steps(
        cell.config, root, FIRST_STEPS, jax.devices("cpu")[0], precision,
        rows, buffers)
    return {"losses": [float(x) for x in losses], "grads": f64(grads),
            "change": change(p0, p3), "buffer_change": [change(s0, s3)]}


def f64(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float64), tree)


def change(before, after):
    return jax.tree.map(lambda a, b: b - a, f64(before), f64(after))


def per_chip(tree, devices) -> list:
    """One host copy of ``tree`` per chip, in ``devices`` order, each leaf
    read from that chip's own shard."""
    def shard(x, d):
        return np.asarray(next(s.data for s in x.addressable_shards
                               if s.device == d))
    return [jax.tree.map(lambda x: shard(x, d), tree) for d in devices]


def compare(got: dict, ref: dict) -> dict:
    """``ddp.compare``'s three numbers and ``buffer_gap``: over every
    chip and every running-statistics leaf, the norm of the gap between
    the chip's change over three steps and the reference's, over the
    larger of the reference change's norm of that leaf and of the median
    leaf."""
    out = ddp.compare(got, ref)
    norm = lambda x: float(np.linalg.norm(x))  # noqa: E731
    r = jax.tree.leaves(ref["buffer_change"][0])
    floor = np.median([norm(x) for x in r])
    out["buffer_gap"] = float(max(
        norm(g - x) / max(norm(x), floor)
        for chip in got["buffer_change"]
        for g, x in zip(jax.tree.leaves(chip), r)))
    return out

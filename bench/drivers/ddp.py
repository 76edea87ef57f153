"""Data-parallel training job: steps of the program's bucketed DDP train
step on a data mesh over every chip, and the monitor's capture of it.

Set-up makes the weights and the feed from the seed and drives the step
through its first three steps with the window's own call and feed, on
batches that all differ; the window then goes on from that state.  Job
segment: ``units_per_cycle`` steps, ended by ``block_until_ready``.
Monitor segment: ``MonitorSession.capture`` of the step on its live
arguments, ``report()``, then the report written as JSON and HTML.

The check, once the window has closed: the plain reference takes the same
three steps on one chip, and each step's loss, the first gradient (read
back from the parameters after one step) and the parameters' change after
three are compared leaf by leaf; the last report's all-reduce bytes and
its matrix are compared with a ring all-reduce of every gradient and the
loss.
"""
from __future__ import annotations

import gc
import os

import jax
import numpy as np

import seeding

FIRST_STEPS = 3


class Driver:
    def __init__(self, cell, seed: int, devices):
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.train import ddp

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
        self.step = ddp.make_ddp_train_step(
            self.model.loss_fn, self.mesh, mode=self.cfg["ddp_mode"],
            bucket_mb=self.cfg["bucket_mb"], lr=self.cfg["lr"])
        params = self.mod.make_params(self.cfg, self.root,
                                      NamedSharding(self.mesh, P()))
        if (jax.tree.structure(params)
                != jax.tree.structure(self.model.shapes())):
            raise ValueError("the weights' tree is not the program's")
        self.ef = ddp.init_error_feedback(params)
        n = t["feed_batches"]
        split = NamedSharding(self.mesh, P("data"))
        self.feed = jax.jit(lambda root: [self.mod.make_batch(self.cfg, root, i)
                                          for i in range(n)],
                            out_shardings=split)(self.root)
        self.params, self.sent, self.report = params, 0, None
        # the first steps, through the window's own call and feed
        self.first = {"p0": params}
        losses = []
        for i in range(FIRST_STEPS):
            losses.append(self._step())
            if i == 0:
                self.first["p1"] = self.params
        self.first["p3"] = self.params
        self.first["losses"] = losses

    def _step(self):
        batch = self.feed[self.sent % len(self.feed)]
        with jax.profiler.TraceAnnotation("ddp_step"):
            self.params, self.ef, loss = self.step(self.params, self.ef,
                                                   batch)
        self.sent += 1
        return loss

    # -- the window's two segments ---------------------------------------
    def counts_per_unit(self) -> dict:
        b = self.cfg["global_batch"]
        return {"attempted": 1, "steps": 1, "samples": b,
                "train_flops": b * self.mod.train_flops_per_sample(self.cfg)}

    def job(self, units: int | None = None) -> dict:
        n = self.per_cycle if units is None else units
        for _ in range(n):
            loss = self._step()
        jax.block_until_ready((self.params, loss))
        return {k: v * n for k, v in self.counts_per_unit().items()}

    def monitor(self, out_dir: str, time) -> dict:
        from repro.core import MonitorSession, export
        t0 = time()
        sess = MonitorSession(mesh=self.mesh, name=f"ddp[{self.cfg['name']}]")
        cap = sess.capture(self.step, self.params, self.ef,
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
        """Copy what the check reads to the host and free the rest."""
        first = jax.device_get(self.first)
        self.first = {k: first[k] for k in ("p0", "p1", "p3")}
        self.first["losses"] = [float(x) for x in first["losses"]]
        self.params = self.ef = self.feed = self.step = None
        gc.collect()

    # -- the check ---------------------------------------------------------
    def report_checks(self) -> dict:
        """All-reduce bytes per step, and each chip's bytes sent and
        received, against a ring all-reduce of every gradient and the loss
        (exact, limit 0)."""
        rep = self.report
        n = len(self.devices)
        payload = self.mod.allreduce_payload_bytes(self.cfg)
        by_kind: dict[str, float] = {}
        for op in rep.compiled_ops:
            by_kind[op.kind] = (by_kind.get(op.kind, 0.0)
                                + float(op.payload_bytes * op.weight))
        want = {"all-reduce": float(payload)}
        kind_gap = max(abs(by_kind.get(k, 0.0) - want.get(k, 0.0))
                       for k in set(by_kind) | set(want))
        matrix = np.asarray(rep.matrix, np.float64)
        per_chip = 2.0 * (n - 1) / n * payload
        dev = matrix[1:, 1:]
        matrix_gap = max(np.abs(dev.sum(axis=1) - per_chip).max(),
                         np.abs(dev.sum(axis=0) - per_chip).max(),
                         np.abs(matrix[0]).sum() + np.abs(matrix[:, 0]).sum())
        return {"collective_bytes": (kind_gap, 0.0),
                "matrix_bytes": (float(matrix_gap), 0.0)}

    def program_readings(self) -> dict:
        """The program's losses, first gradient and change after three
        steps, float64 leaves."""
        lr = self.cfg["lr"]
        f64 = lambda t: jax.tree.map(lambda x: np.asarray(x, np.float64), t)  # noqa: E731
        p0, p1, p3 = (f64(self.first[k]) for k in ("p0", "p1", "p3"))
        return {"losses": self.first["losses"],
                "grads": jax.tree.map(lambda a, b: (a - b) / lr, p0, p1),
                "change": jax.tree.map(lambda a, b: b - a, p0, p3)}

    def reference_readings(self, precision: str = "f32", rows=None) -> dict:
        losses, grads, p0, p3 = self.mod.reference_steps(
            self.cfg, self.root, FIRST_STEPS, self.devices[0], precision,
            rows)
        f64 = lambda t: jax.tree.map(lambda x: np.asarray(x, np.float64), t)  # noqa: E731
        return {"losses": [float(x) for x in losses], "grads": f64(grads),
                "change": jax.tree.map(lambda a, b: b - a, f64(p0),
                                       f64(p3))}

    def check(self) -> dict:
        gaps = compare(self.program_readings(), self.reference_readings())
        limits = self.cell.limits
        out = {k: (v, limits[k]) for k, v in gaps.items()}
        out.update(self.report_checks())
        return out


def compare(got: dict, ref: dict) -> dict:
    """The three numbers compared with the reference.

    ``loss_gap``: the largest relative gap of a step's loss.
    ``grad_gap`` and ``change_gap``: by the worst leaf, the gap between the
    program's norm and the reference's, over the larger of the reference's
    norm of that leaf and of the median leaf.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the change.
    """
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                        ref["losses"]))
    norms = lambda t: np.array([np.linalg.norm(x) for x in jax.tree.leaves(t)])  # noqa: E731
    g_ref, g_got = norms(ref["grads"]), norms(got["grads"])
    c_ref, c_got = norms(ref["change"]), norms(got["change"])
    g_med = np.median(g_ref)
    grad_gap = np.max(np.abs(g_got - g_ref) / np.maximum(g_ref, g_med))
    moved = g_ref >= 1e-3 * g_med
    c_med = np.median(c_ref[moved])
    change_gap = np.max((np.abs(c_got - c_ref)
                         / np.maximum(c_ref, c_med))[moved])
    return {"loss_gap": float(loss_gap), "grad_gap": float(grad_gap),
            "change_gap": float(change_gap)}

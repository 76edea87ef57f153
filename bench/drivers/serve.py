"""Serving job: closed-loop batches through the serving launcher's own
entries, and the monitor's capture of the very prefill and decode it runs.

Job segment: ``units_per_cycle`` batches, each ``repro.launch.serve.serve``
(``serve.generate``) over one batch of the feed with the jitted pair from
``serve_steps``; a batch's tokens are delivered (ready on the host side)
before the next is sent.  Monitor segment: ``launch.serve.monitor`` over
those objects, ``report()``, then the report written as JSON and HTML.

The check, once the window has closed: a sample of the finished requests,
drawn from the seed, goes through the configuration's plain reference;
the widest gap by which a served token's reference logit lies below the
reference's best is compared with its limit.  The last report's bytes per
collective kind and its matrix are compared with what one chip exchanges:
nothing.
"""
from __future__ import annotations

import gc
import os
import types

import jax
import jax.numpy as jnp
import numpy as np

import seeding
from harness import log, now


class Driver:
    def __init__(self, cell, seed: int, devices):
        from jax.sharding import AxisType, Mesh

        from repro.launch import serve
        from repro.parallel import Sharder

        self.serve = serve
        self.cell, self.seed = cell, seed
        self.root = seeding.root_key(seed)
        self.cfg, self.mod, t = cell.config, cell.config_module, cell.traffic
        self.batch, self.prompt_len = t["batch"], t["prompt_len"]
        self.gen_tokens, self.per_cycle = t["gen_tokens"], t["units_per_cycle"]
        if len(devices) != 1:
            raise ValueError("the serving driver runs on one chip")
        self.mesh = Mesh(np.array(devices), ("data",),
                         axis_types=(AxisType.Auto,))
        self.shd = Sharder(self.mesh)
        self.model = self.mod.build_model(self.cfg)
        shardings = self.shd.tree_shardings(self.model.shapes(),
                                            self.model.axes())
        t0 = now()
        self.params = jax.block_until_ready(
            self.mod.make_params(self.cfg, self.root, shardings))
        log(f"[bench] weights made in {now() - t0:.3f} s")
        if (jax.tree.structure(self.params)
                != jax.tree.structure(self.model.shapes())):
            raise ValueError("the weights' tree is not the program's")
        self.steps = serve.serve_steps(self.model, self.shd,
                                       types.SimpleNamespace(
                                           batch=self.batch,
                                           prompt_len=self.prompt_len,
                                           tokens=self.gen_tokens))
        t0 = now()
        self.feed = jax.block_until_ready(self._make_feed(t["feed_batches"]))
        log(f"[bench] feed made in {now() - t0:.3f} s")
        self.served: list[tuple[int, jax.Array]] = []
        self.sent = 0
        self.report = None

    def _make_feed(self, n: int):
        """``n`` distinct batches of prompts, uniform over the vocabulary,
        made on the device in one call and sent in a fixed order."""
        shape = (self.batch, self.prompt_len)
        vocab = self.cfg["vocab_size"]
        make = jax.jit(lambda root: jnp.stack([
            jax.random.randint(seeding.leaf_key(root, "feed/prompts", i),
                               shape, 0, vocab) for i in range(n)]))
        stacked = make(self.root)
        return [stacked[i] for i in range(n)]

    # -- the window's two segments ---------------------------------------
    def counts_per_unit(self) -> dict:
        """What one batch completes, and the operations and least bytes
        the algorithm needs for it."""
        b, p, t, c, m = (self.batch, self.prompt_len, self.gen_tokens,
                         self.cfg, self.mod)
        positions = range(p, p + t - 1)          # decode steps' positions
        return {
            "attempted": b, "requests": b, "tokens": b * (p + t),
            "prefill_flops": m.prefill_flops(c, b, p),
            "decode_flops": sum(m.decode_flops(c, b, q) for q in positions),
            "decode_bytes": sum(m.decode_bytes(c, b, q) for q in positions),
            "decode_steps": t - 1,
            "attention_flops": m.attention_flops(c, b, p),
            "attention_bytes": m.attention_bytes(c, b, p),
        }

    def job(self, units: int | None = None) -> dict:
        per_unit = self.counts_per_unit()
        n = self.per_cycle if units is None else units
        for _ in range(n):
            idx = self.sent % len(self.feed)
            with jax.profiler.TraceAnnotation("serve_batch"):
                tokens, logits = self.serve.serve(
                    self.model, self.params, self.shd, self.feed[idx],
                    self.gen_tokens, self.steps)
                jax.block_until_ready((tokens, logits))
            del logits
            self.served.append((idx, tokens))
            self.sent += 1
        return {k: v * n for k, v in per_unit.items()}

    def monitor(self, out_dir: str, time) -> dict:
        from repro.core import export
        t0 = time()
        sess = self.serve.monitor(self.model, self.steps, self.mesh,
                                  self.batch, self.prompt_len,
                                  name=f"serve[{self.cfg['name']}]")
        t1 = time()
        rep = sess.report()
        t2 = time()
        rep.save(os.path.join(out_dir, "report.json"))
        export.export_report(rep, "html", os.path.join(out_dir, "report.html"))
        t3 = time()
        self.report = rep
        return {"capture_s": t1 - t0,
                "lower_s": sum(c.trace_seconds for c in sess.captures),
                "compile_s": sum(c.compile_seconds for c in sess.captures),
                "build_s": t2 - t1, "export_s": t3 - t2}

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.params = self.steps = self.model = None
        self.served = [(i, np.asarray(t)) for i, t in self.served]
        gc.collect()

    # -- the check ---------------------------------------------------------
    def report_checks(self) -> dict:
        """One chip exchanges nothing: any collective byte in the last
        report, or any byte in its matrix, is wrong (exact, limit 0)."""
        rep = self.report
        moved = sum(float(op.payload_bytes * op.weight)
                    for op in rep.compiled_ops)
        matrix = np.asarray(rep.matrix, np.float64)
        return {"collective_bytes": (moved, 0.0),
                "matrix_bytes": (float(np.abs(matrix).sum()), 0.0)}

    def sample(self) -> list[tuple[int, int]]:
        """``check_requests`` distinct finished requests (unit, row), drawn
        from the seed; every request of the mix has the same length, so
        each sample holds one of the longest."""
        total = len(self.served) * self.batch
        k = min(self.cell.traffic["check_requests"], total)
        rng = np.random.default_rng(self.seed % (1 << 63))
        picks = sorted(rng.choice(total, size=k, replace=False).tolist())
        return [(i // self.batch, i % self.batch) for i in picks]

    def sequences(self, picks):
        """(prompt + served tokens) of each pick, and the served tokens."""
        feed = [np.asarray(f) for f in self.feed]
        prompts = np.stack([feed[self.served[u][0]][r] for u, r in picks])
        served = np.stack([self.served[u][1][r] for u, r in picks])
        return np.concatenate([prompts, served], axis=1), served

    def check(self) -> dict:
        """``{name: (value, limit)}`` of the program's served tokens."""
        picks = self.sample()
        seqs, served = self.sequences(picks)
        t0 = now()
        ref = jax.block_until_ready(self.mod.reference_logits(
            self.cfg, self.root, jnp.asarray(seqs[:, :-1]),
            self.prompt_len - 1))
        log(f"[bench] reference over {len(picks)} requests in "
            f"{now() - t0:.3f} s")
        gaps = self.mod.logit_gaps(ref, served)
        limits = self.cell.limits
        out = {"logit_gap": (float(gaps.max()), limits["logit_gap"])}
        out.update(self.report_checks())
        return out

    def control_gap(self) -> tuple[float, float]:
        """The program's widest gap and the control's over the same sample:
        the control is the reference in the next precision down, and reads
        the gap of the token it puts first at each position."""
        picks = self.sample()
        seqs, served = self.sequences(picks)
        toks = jnp.asarray(seqs[:, :-1])
        ref = self.mod.reference_logits(self.cfg, self.root, toks,
                                        self.prompt_len - 1)
        low = self.mod.reference_logits(self.cfg, self.root, toks,
                                        self.prompt_len - 1, precision="fp8")
        ctrl_tokens = np.asarray(low).argmax(-1)
        return (float(self.mod.logit_gaps(ref, served).max()),
                float(self.mod.logit_gaps(ref, ctrl_tokens).max()))

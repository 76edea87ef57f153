"""Regenerate the committed lint- and compare-CI fixtures.

Two saved multi-phase session reports mirroring the examples --
``examples/translation.py`` (GNMT fwd/bwd/optim on an 8-way data mesh) and
``examples/serve_lm.py`` (qwen3 reduced prefill/decode on a 4x2 mesh) --
written with ``include_hlo=True`` (so the def-use lint rules can re-run
offline) and ``include_lint=True`` (so ``python -m repro lint <file>``
serves the v7 findings as saved).  The CI fast job gates on
``--fail-on error`` over both files.

On top of the reports, two trace fixtures for the ingestion subsystem
(:mod:`repro.core.trace`), derived from the COMMITTED report JSONs so
regenerating them never needs XLA:

* ``translation_trace.json`` -- our own Perfetto export of
  ``translation_report.json``; importing it must reproduce the report's
  comm matrix bitwise (the round-trip CI gate);
* ``serve_trace.csv`` -- a synthesized ComScribe-style nvprof GPU-trace
  CSV of ``serve_report.json``'s collectives, one kernel row per
  participating device, with deterministic measured durations
  ``modeled * (1 + delta_i)`` (|delta| <= 0.08) so ``repro compare``
  sees finite errors below the pinned CI bound (0.15).

Run:  PYTHONPATH=src python tests/fixtures/make_fixtures.py
      PYTHONPATH=src python tests/fixtures/make_fixtures.py --traces-only
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.core import MonitorSession

HERE = os.path.dirname(os.path.abspath(__file__))


def translation_report():
    from repro.data import SyntheticSeq2Seq
    from repro.models.gnmt import GNMT
    from repro.optim import OptConfig, init_opt_state, apply_updates
    from repro.train import ddp

    mesh = make_mesh((8,), ("data",))
    model = GNMT(vocab=64, d=128, layers=2)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    data = SyntheticSeq2Seq(vocab_size=64, src_len=12, tgt_len=12,
                            global_batch=32)
    ocfg = OptConfig(peak_lr=3e-3, warmup_steps=10, decay_steps=500)
    opt = jax.eval_shape(lambda: init_opt_state(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params), ocfg))

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

    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         data.batch_at(0))
    session = MonitorSession(mesh=mesh, name="GNMT-MT")
    with session:
        with session.phase("fwd"):
            session.capture(dp(fwd, (P(), P("data")), P()), params, batch)
        with session.phase("bwd"):
            session.capture(dp(bwd, (P(), P("data")), P()), params, batch)
        with session.phase("optim"):
            session.capture(
                dp(optim, (P(), P(), P(), P()), (P(), P())),
                params, params, opt,
                jax.ShapeDtypeStruct((), jnp.int32))
    return session.report()


def serve_report():
    from repro import configs
    from repro.models import build_model
    from repro.parallel import Sharder
    from repro.serve import ServeConfig, cache_shardings

    mesh = make_mesh((4, 2), ("data", "model"))
    shd = Sharder(mesh)
    cfg = configs.config("qwen3_8b", reduced=True)
    model = build_model(cfg)
    batch, prompt_len, max_len = 8, 32, 56
    scfg = ServeConfig(max_len=max_len, batch=batch)
    cache_sh = cache_shardings(model, scfg, shd)
    sess = MonitorSession(mesh=mesh, name=f"serve[{cfg.name}]")
    with sess:
        with sess.phase("prefill"):
            sess.capture(
                lambda p, b: model.prefill(p, b, shd, max_len=max_len),
                model.shapes(),
                {"tokens": jax.ShapeDtypeStruct((batch, prompt_len),
                                                jnp.int32)},
                name="prefill", out_shardings=(None, cache_sh))
        with sess.phase("decode"):
            sess.capture(
                lambda p, c, b: model.decode_step(p, c, b, shd),
                model.shapes(), model.cache_shapes(batch, max_len),
                {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)},
                name="decode", in_shardings=(None, cache_sh, None),
                out_shardings=(None, cache_sh))
    return sess.report()


# deterministic measured-vs-modeled skew per op index (|delta| <= 0.08,
# cycling): keeps every fixture rel err finite and below the CI bound
_DELTAS = (0.05, -0.03, 0.07, -0.06, 0.02, -0.08, 0.04, -0.01)

_NCCL_NAMES = {
    "all-reduce": "ncclAllReduceRingLLKernel_sum_f32",
    "all-gather": "ncclAllGatherRingLLKernel_f32",
    "reduce-scatter": "ncclReduceScatterRingLLKernel_sum_f32",
    "all-to-all": "ncclAllToAllRingKernel_f32",
    "collective-broadcast": "ncclBroadcastRingLLKernel_f32",
}


def make_translation_trace():
    """Perfetto export of the committed translation report (the bitwise
    round-trip fixture)."""
    from repro.core import CommReport
    from repro.core.export.perfetto import export_perfetto

    rep = CommReport.load(os.path.join(HERE, "translation_report.json"))
    path = os.path.join(HERE, "translation_trace.json")
    export_perfetto(rep, path)
    print(f"translation_trace: {len(rep.compiled_ops)} collectives "
          f"-> {path}")
    return path


def make_serve_trace():
    """Synthesized nvprof GPU-trace CSV of the committed serve report:
    one kernel row per device per collective (PtoP memcpy rows for the
    permutes), durations = modeled * (1 + delta_i)."""
    from repro.core import CommReport

    rep = CommReport.load(os.path.join(HERE, "serve_report.json"))
    view = rep.view()
    secs = view.op_seconds()
    mb = 1024.0 ** 2
    dev = "Tesla V100-SXM2-16GB ({})"
    lines = [
        "==12345== NVPROF is profiling process 12345, "
        "command: serve_lm",
        "==12345== Profiling result:",
        '"Start","Duration","Size","SrcDev","DstDev","Device","Name",'
        '"Correlation_ID"',
        "s,ms,MB,,,,,",
    ]
    start = 0.0
    for i, (op, modeled) in enumerate(zip(rep.compiled_ops, secs)):
        measured_ms = modeled * (1.0 + _DELTAS[i % len(_DELTAS)]) * 1e3
        corr = 100 + i
        if op.kind == "collective-permute":
            size_mb = op.result_bytes / mb
            for src, dst in op.source_target_pairs:
                lines.append(
                    f"{start:.6f},{measured_ms:.9f},{size_mb:.9f},"
                    f'"{dev.format(src)}","{dev.format(dst)}",,'
                    f'"[CUDA memcpy PtoP]",{corr}')
        else:
            kname = _NCCL_NAMES[op.kind]
            size_mb = op.payload_bytes / mb
            group = (op.replica_groups[0] if op.replica_groups
                     else range(rep.num_devices))
            for d in group:
                lines.append(
                    f"{start:.6f},{measured_ms:.9f},{size_mb:.9f},,,"
                    f'"{dev.format(d)}","{kname}(...)",{corr}')
        start += measured_ms * 1e-3
    # one host transfer each way so row/col 0 of the matrix is exercised
    lines.append(f'{start:.6f},0.100000000,1.000000000,,,'
                 f'"{dev.format(0)}","[CUDA memcpy HtoD]",900')
    lines.append(f'{start + 0.001:.6f},0.100000000,1.000000000,,,'
                 f'"{dev.format(0)}","[CUDA memcpy DtoH]",901')
    path = os.path.join(HERE, "serve_trace.csv")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"serve_trace: {len(rep.compiled_ops)} collectives -> {path}")
    return path


def make_traces():
    make_translation_trace()
    make_serve_trace()


def main():
    if "--traces-only" not in sys.argv:
        for stem, build in (("translation_report", translation_report),
                            ("serve_report", serve_report)):
            rep = build()
            path = os.path.join(HERE, f"{stem}.json")
            rep.save(path, include_hlo=True, include_lint=True)
            findings = rep.lint()
            print(f"{stem}: {len(rep.compiled_ops)} collectives, "
                  f"{len(findings)} lint findings -> {path}")
            for f in findings:
                print(f"  [{f.severity}] {f.rule_id}: {f.op_names}")
    make_traces()


if __name__ == "__main__":
    main()

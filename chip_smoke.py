#!/usr/bin/env python3
"""Smoke test of the monitor's main path on a TPU, in one process.

  python3 chip_smoke.py             # one chip: granite-3-2b serving
  python3 chip_smoke.py --chips 4   # four chips: the paper's DDP ResNet-18

One chip: the serving launcher (``repro.launch.serve``) builds granite-3-2b
at its published widths with random weights from ``--seed``, answers 4
requests of 512 prompt tokens with 32 greedy tokens each, and a
``MonitorSession`` captures the very prefill and decode objects that
served them, compiled for the chip.  The Pallas attention kernel is
checked against ``attention_ref``.

Four chips: ResNet-18 (200 classes, 64x64 images, global batch 64) takes 3
bucketed-DDP steps on a 4-chip data mesh and the same 3 steps on a one-chip
mesh; losses and parameters must agree, the compiled all-reduce bytes must
equal the traced psum bytes, and each device's matrix row must equal the
cost model's send bytes.

Every check raises on failure, so any failed phase exits non-zero.  With
no TPU the script exits non-zero before running anything.  The last line
of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compat import enable_compile_cache, make_mesh  # noqa: E402

OUT_DIR = os.path.join(HERE, "artifacts", "chip_smoke")

# Pallas kernel vs attention_ref at the prefill's shape, f32 inputs.  On
# the TPU an f32 matmul at default precision may round its operands to
# bf16 (8-bit mantissa).  Emulating that rounding on both of the kernel's
# matmuls (q.k and p.v) against an exact f32 reference gives, at this shape
# and seed, a largest error of 0.0117 and a mean of 3.2e-4 (unit-normal
# q, k, v); the limits below leave 4-6x room.  A masking or blocking fault
# misplaces whole softmax rows, which moves outputs by O(1).
KERNEL_MAX_ABS_ERR = 5e-2
KERNEL_MEAN_ABS_ERR = 2e-3
# 4-chip DDP vs one chip, both at "highest" matmul precision so the only
# difference is f32 summation order: on 4 forced CPU devices the losses
# differ by 1.3e-7 (relative) and the parameter updates by 9.7e-5 (norm of
# the difference over the norm of the update; cancelling per-example
# gradient sums amplify the reordering).  A missing, doubled or unaveraged
# gradient all-reduce is off by O(1) on both.
DDP_LOSS_REL_TOL = 1e-4
DDP_UPDATE_REL_TOL = 1e-2
DDP_STEPS = 3
DDP_PSUM_BYTES = 45_078_564          # 11,269,640 f32 parameters + the loss


def require(cond, what: str):
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def log(msg: str):
    print(msg, flush=True)


def serve_phase(seed: int) -> None:
    """granite-3-2b serving on one chip, and its two-phase capture."""
    from repro.core import export, roofline_of
    from repro.kernels.flash_attention import ops
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.launch import serve

    args = serve.parse_args([
        "--arch", "granite_3_2b", "--published", "--mesh", "1",
        "--batch", "4", "--prompt-len", "512", "--tokens", "32",
        "--seed", str(seed)])
    t0 = time.perf_counter()
    mesh, shd, model, params = serve.build(args)
    cfg = model.cfg
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads ({cfg.n_kv_heads} kv), d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}; {n_params:,} parameters, {n_bytes:,} bytes "
        f"({cfg.param_dtype}), built in {time.perf_counter() - t0:.1f}s")

    dev = jax.devices()[0]
    in_use = dev.memory_stats()["bytes_in_use"]
    prompts = serve.prompts_for(args, cfg.vocab_size)
    steps = serve.serve_steps(model, shd, args)
    t0 = time.perf_counter()
    tokens, logits = serve.serve(model, params, shd, prompts, args.tokens,
                                 steps)
    tokens, logits = np.asarray(tokens), np.asarray(logits)
    log(f"[serve] {args.batch} requests x {args.prompt_len} prompt tokens -> "
        f"{tokens.shape[1]} greedy tokens each in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    require(tokens.shape == (args.batch, args.tokens), "token shape")
    require(((tokens >= 0) & (tokens < cfg.vocab_size)).all(),
            "tokens within the vocab")
    require(np.isfinite(logits).all(), "finite logits")
    require((tokens == logits.argmax(-1)).all(),
            "each token is the argmax of its logits")
    log(f"[serve] tokens in [0, {cfg.vocab_size}): min {tokens.min()} max "
        f"{tokens.max()}; logits {logits.shape} finite, |max| "
        f"{np.abs(logits).max():.4f}")
    log(f"[serve] request 0 tokens: {tokens[0].tolist()}")
    stats = dev.memory_stats()
    log(f"[device] bytes in use before serving {in_use:,}; peak while "
        f"serving {stats['peak_bytes_in_use']:,} of limit "
        f"{stats['bytes_limit']:,}")
    # the served prefill's own executable, compiled from the live arguments
    served_prefill = steps[0].lower(params, {"tokens": prompts}).compile()
    served_mem = served_prefill.memory_analysis()
    del params, served_prefill

    t0 = time.perf_counter()
    sess = serve.monitor(model, steps, mesh, args.batch, args.prompt_len,
                         name="serve[granite_3_2b]")
    log(f"[monitor] two captures in {time.perf_counter() - t0:.1f}s")
    for cap in sess.captures:
        mem = cap.memory_stats
        log(f"[monitor] capture {cap.name}: trace {cap.trace_seconds:.2f}s, "
            f"compile {cap.compile_seconds:.2f}s; compiler memory: argument "
            f"{mem['argument_bytes']:,} B, temp {mem['temp_bytes']:,} B")
    captured = sess.captures[0].memory_stats
    log(f"[monitor] served prefill compiled from its live arguments: "
        f"argument {served_mem.argument_size_in_bytes:,} B, temp "
        f"{served_mem.temp_size_in_bytes:,} B")
    require((captured["argument_bytes"], captured["temp_bytes"])
            == (served_mem.argument_size_in_bytes,
                served_mem.temp_size_in_bytes),
            "the prefill capture describes the served prefill")
    prefill_hlo = sess.captures[0].hlo_text
    require("tpu_custom_call" in prefill_hlo,
            "the Pallas kernel in the prefill HLO")
    log(f"[monitor] prefill HLO holds the Pallas kernel: "
        f"{prefill_hlo.count('tpu_custom_call')} tpu_custom_call line(s)")

    shape = (args.batch, args.prompt_len, cfg.n_heads, cfg.dh)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    q, k, v = (jax.random.normal(key, shape, jnp.float32) for key in keys)
    out = jax.jit(ops.attend)(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(attention_ref)(q, k, v)
    err = np.abs(np.asarray(out) - np.asarray(ref))
    log(f"[kernel] Pallas flash attention vs attention_ref at {shape} f32: "
        f"max abs err {err.max():.6f} (limit {KERNEL_MAX_ABS_ERR}), mean "
        f"{err.mean():.2e} (limit {KERNEL_MEAN_ABS_ERR})")
    require(err.max() <= KERNEL_MAX_ABS_ERR, "kernel max abs error")
    require(err.mean() <= KERNEL_MEAN_ABS_ERR, "kernel mean abs error")

    rep = sess.report()
    matrix = np.asarray(rep.matrix)
    log(f"[report] {rep.num_devices} device(s), {len(rep.compiled_ops)} "
        f"collective op(s), matrix {matrix.shape} sum {matrix.sum():.0f}; "
        f"hardware spec {sess.topo.hw.name}")
    require(rep.num_devices == 1, "report device count")
    require(not rep.compiled_ops and not matrix.any(),
            "no inter-chip traffic on one chip")
    roof = roofline_of(rep)
    mem = rep.memory_stats
    log(f"[report] roofline: {roof.flops_per_device:.4e} FLOP/device, "
        f"{roof.bytes_per_device:.4e} HBM bytes/device, dominant "
        f"{roof.dominant}; compiler memory stats summed over the "
        f"{len(sess.captures)} captures: "
        f"argument {mem['argument_bytes']:,} B, temp {mem['temp_bytes']:,} B, "
        f"total {mem['total_bytes']:,} B")
    require(roof.flops_per_device > 0 and roof.bytes_per_device > 0,
            "non-empty roofline")
    require(mem["total_bytes"] > 0, "memory stats from the compiler")

    os.makedirs(OUT_DIR, exist_ok=True)
    json_path = os.path.join(OUT_DIR, "serve_granite_3_2b.json")
    html_path = os.path.join(OUT_DIR, "serve_granite_3_2b.html")
    rep.save(json_path)
    export.export_report(rep, "html", html_path)
    log(f"[report] wrote {json_path} and {html_path}")


def ddp_phase(seed: int) -> None:
    """The paper's DDP ResNet-18 on a 4-chip data mesh vs one chip."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import paper
    from repro.core import MonitorSession, cost_models
    from repro.train import ddp

    devices = jax.devices()
    require(len(devices) == 4, f"4 chips (found {len(devices)})")
    mesh4 = make_mesh((4,), ("data",))
    mesh1 = Mesh(np.array(devices[:1]), ("data",),
                 axis_types=(AxisType.Auto,))
    ids4 = [d.id for d in mesh4.devices.flat]
    log(f"[ddp] data mesh device ids {ids4}; one-chip reference mesh "
        f"{[d.id for d in mesh1.devices.flat]}")
    require(sorted(ids4) == sorted(d.id for d in devices),
            "the data mesh spans every chip")

    model = paper.resnet18_model(paper.RESNET_DATA["num_classes"])
    b, side = paper.RESNET_DATA["global_batch"], paper.RESNET_DATA["image_size"]
    repl4 = NamedSharding(mesh4, P())
    params0 = jax.jit(model.init, out_shardings=repl4)(
        jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params0))
    log(f"[ddp] ResNet-18: {n_params:,} parameters, "
        f"{paper.RESNET_DATA['num_classes']} classes, {side}x{side} images, "
        f"global batch {b}")

    def batch_at(step: int, mesh):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), step)
        ki, kl = jax.random.split(key)
        split = NamedSharding(mesh, P("data"))
        make = jax.jit(lambda: {
            "images": jax.random.normal(ki, (b, side, side, 3), jnp.float32),
            "labels": jax.random.randint(kl, (b,), 0,
                                         paper.RESNET_DATA["num_classes"])},
            out_shardings=split)
        return make()

    def run(mesh, params):
        step = ddp.make_ddp_train_step(model.loss_fn, mesh, mode="bucketed",
                                       bucket_mb=25.0)
        ef = ddp.init_error_feedback(params)
        losses = []
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            for i in range(DDP_STEPS):
                params, ef, loss = step(params, ef, batch_at(i, mesh))
                losses.append(float(loss))
        log(f"[ddp] {mesh.devices.size} chip(s): losses "
            f"{[f'{x:.6f}' for x in losses]} in "
            f"{time.perf_counter() - t0:.1f}s (compile included); loss on "
            f"devices {sorted(d.id for d in loss.sharding.device_set)}")
        return params, losses, loss, step

    params4, losses4, loss4, step4 = run(mesh4, params0)
    require(len(loss4.sharding.device_set) == 4, "the step ran on 4 chips")
    params1, losses1, _, _ = run(mesh1, jax.device_put(
        params0, NamedSharding(mesh1, P())))

    loss_rel = max(abs(a - c) / abs(c) for a, c in zip(losses4, losses1))
    flat = lambda t: np.concatenate(  # noqa: E731
        [np.asarray(x, np.float64).ravel() for x in jax.tree.leaves(t)])
    p0, p4, p1 = flat(params0), flat(params4), flat(params1)
    update_rel = np.linalg.norm(p4 - p1) / np.linalg.norm(p1 - p0)
    log(f"[ddp] 4 chips vs 1: max loss rel diff {loss_rel:.3e} (limit "
        f"{DDP_LOSS_REL_TOL}); parameter update rel diff "
        f"{update_rel:.3e} (limit {DDP_UPDATE_REL_TOL}); max abs param "
        f"diff {np.abs(p4 - p1).max():.3e}")
    require(loss_rel <= DDP_LOSS_REL_TOL, "4-chip loss matches one chip")
    require(update_rel <= DDP_UPDATE_REL_TOL,
            "4-chip parameters match one chip")

    # the capture is of the very step that ran on the 4 chips, traced at
    # the precision it ran at
    sess = MonitorSession(mesh=mesh4, name="ddp-resnet18")
    with jax.default_matmul_precision("highest"):
        cap = sess.capture(step4, params0, ddp.init_error_feedback(params0),
                           batch_at(0, mesh4), name="step")
    log(f"[monitor] capture {cap.name}: trace {cap.trace_seconds:.2f}s, "
        f"compile {cap.compile_seconds:.2f}s; hardware spec "
        f"{sess.topo.hw.name}")
    all_reduces = [op for op in cap.ops if op.kind == "all-reduce"]
    compiled = sum(op.payload_bytes * op.weight for op in all_reduces)
    traced = sum(ev.payload_bytes for ev in cap.traced
                 if ev.primitive == "psum")
    log(f"[monitor] {len(all_reduces)} compiled all-reduce(s) "
        f"{[str(s) for op in all_reduces for s in op.result_shapes]}: "
        f"{compiled:,.0f} bytes/step; {len(cap.traced)} traced psum(s): "
        f"{traced:,} bytes/step")
    require(compiled == traced == DDP_PSUM_BYTES,
            f"compiled all-reduce bytes == traced psum bytes == "
            f"{DDP_PSUM_BYTES:,}")

    rep = sess.report()
    matrix = np.asarray(rep.matrix)
    expected = np.zeros(rep.num_devices)
    for op in rep.compiled_ops:
        for group in op.replica_groups:
            sent = cost_models.device_send_bytes(
                op.kind, op.payload_bytes, group, rep.algorithm,
                topo=sess.topo)
            for d, nbytes in sent.items():
                expected[d] += nbytes * op.weight
    rows = matrix[1:, 1:].sum(axis=1)
    log(f"[monitor] matrix row sums {rows.tolist()}; device_send_bytes "
        f"{expected.tolist()}")
    require(np.array_equal(rows, expected),
            "matrix row sums == device_send_bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: granite-3-2b serving; 4: DDP ResNet-18 only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: no TPU found (jax's default backend is "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    enable_compile_cache()
    log(f"[device] platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(devices)}; jax {jax.__version__}")
    if args.chips == 4:
        ddp_phase(args.seed)
    else:
        serve_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

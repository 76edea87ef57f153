"""Multi-pod dry-run launcher.

For every (architecture x input-shape x mesh) cell this lowers + compiles the
real step function (train_step / prefill / decode) against ShapeDtypeStruct
stand-ins — no device memory is allocated — and records:

* ``compiled.memory_analysis()``  (bytes per device: proves it fits),
* ``compiled.cost_analysis()``    (FLOPs / bytes for the roofline),
* the collective schedule parsed from the compiled HLO (the paper's
  contribution makes this visible), and
* the three-term roofline row (EXPERIMENTS.md §Roofline).

Usage (the CLI forwards `python -m repro dryrun ...` here):
  python -m repro dryrun --arch grok_1_314b --shape train_4k --mesh single
  python -m repro dryrun --all --mesh both --skip-existing

The 512-host-device XLA flag is applied inside :func:`main` (not at import
time) so importing this module for its cell builders -- as the sweep engine
does -- never clobbers the caller's device configuration.
"""
import argparse
import gzip
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import hlo_parser, roofline
from repro.core.topology import MeshTopology
from repro.launch.mesh import make_production_mesh, mesh_name
from repro.models import SHAPES_BY_NAME, build_model
from repro.models.common import ShapeConfig
from repro.optim import OptConfig
from repro.parallel import Sharder
from repro.serve import ServeConfig, make_decode_step, make_prefill_step
from repro.train.train import (batch_shardings, jit_train_step,
                               train_state_shapes, train_state_shardings)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _memory_stats(compiled):
    m = compiled.memory_analysis()
    return {
        "argument_bytes": int(m.argument_size_in_bytes),
        "output_bytes": int(m.output_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "alias_bytes": int(m.alias_size_in_bytes),
        "total_bytes": int(m.argument_size_in_bytes + m.output_size_in_bytes
                           + m.temp_size_in_bytes - m.alias_size_in_bytes),
    }


def _cost(compiled):
    c = compiled.cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return {k: float(v) for k, v in dict(c).items()
            if isinstance(v, (int, float))}


def lower_cell(arch: str, shape_name: str, mesh, *, opt_name=None,
               sp: bool = False, train_overrides=None):
    """Build and lower one cell.  Returns (lowered, aux dict)."""
    cfg = configs.config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    model = build_model(cfg)
    shd = Sharder(mesh, enable_sp=sp)
    batch = configs.input_specs(cfg, shape)

    if shape.kind == "train":
        tcfg = configs.train_config(arch)
        if train_overrides:
            import dataclasses
            tcfg = dataclasses.replace(tcfg, **train_overrides)
        ocfg = OptConfig(name=opt_name or cfg.optimizer,
                         state_dtype=cfg.opt_state_dtype)
        from repro.train.train import make_train_step, train_state_shardings
        step_fn = make_train_step(model, ocfg, tcfg, shd)
        state_sh = train_state_shardings(model, ocfg, shd)
        state_shapes = train_state_shapes(model, ocfg)
        b_sh = batch_shardings(batch, shd)
        step = jax.jit(step_fn,
                       in_shardings=(state_sh, b_sh),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,))
        lowered = step.lower(state_shapes, batch)
        n_tokens = shape.global_batch * shape.seq_len
        model_flops = roofline.train_model_flops(cfg.n_params_active, n_tokens)
    elif shape.kind == "prefill":
        scfg = ServeConfig(max_len=shape.seq_len, batch=shape.global_batch)
        params_sh = shd.tree_shardings(model.shapes(), model.axes())
        step, _ = make_prefill_step(model, shd, scfg, params_sh=params_sh)
        b_sh = batch_shardings(batch, shd)
        lowered = step.lower(model.shapes(), batch)
        model_flops = roofline.forward_model_flops(
            cfg.n_params_active, shape.global_batch * shape.seq_len)
    else:  # decode
        scfg = ServeConfig(max_len=shape.seq_len, batch=shape.global_batch)
        params_sh = shd.tree_shardings(model.shapes(), model.axes())
        step, cache_sh = make_decode_step(model, shd, scfg,
                                          params_sh=params_sh)
        cache_shapes = model.cache_shapes(shape.global_batch, shape.seq_len)
        lowered = step.lower(model.shapes(), cache_shapes, batch)
        model_flops = roofline.forward_model_flops(
            cfg.n_params_active, shape.global_batch)
    return lowered, {"cfg": cfg, "shape": shape, "model_flops": model_flops}


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, save_hlo=False,
             out_dir=ARTIFACT_DIR, sp: bool = False, tag: str = "",
             train_overrides=None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mname = "multi" if multi_pod else "single"
    t0 = time.perf_counter()
    lowered, aux = lower_cell(arch, shape_name, mesh, sp=sp,
                              train_overrides=train_overrides)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()

    hlo = compiled.as_text()
    topo = MeshTopology.from_mesh(mesh)
    cost = _cost(compiled)
    rl = roofline.analyze(
        arch=arch, mesh_name=mname, cost=cost, hlo_text=hlo, topo=topo,
        model_flops=aux["model_flops"], memory_stats=_memory_stats(compiled))

    result = {
        "arch": arch, "shape": shape_name, "mesh": mname,
        "devices": topo.num_devices,
        "ok": True,
        "trace_s": t1 - t0, "compile_s": t2 - t1,
        "memory": _memory_stats(compiled),
        "cost": {k: cost.get(k, 0.0) for k in ("flops", "bytes accessed")},
        "collectives": rl.collective_breakdown,
        "roofline": roofline.to_row(rl),
        "tag": tag,
    }
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch}_{shape_name}_{mname}" + (f"_{tag}" if tag else "")
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    if save_hlo:
        with gzip.open(os.path.join(out_dir, stem + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    return result


def main(argv=None) -> int:
    from repro.compat import ensure_host_devices
    ensure_host_devices(512)
    ap = argparse.ArgumentParser(prog="python -m repro dryrun")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--sp", action="store_true", help="sequence parallelism")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args(argv)

    todo = configs.cells() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape in todo:
        for mp in meshes:
            mname = "multi" if mp else "single"
            stem = f"{arch}_{shape}_{mname}" + \
                (f"_{args.tag}" if args.tag else "")
            path = os.path.join(args.out, stem + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {stem}")
                continue
            print(f"[dryrun] {arch} x {shape} @ {mname} ...", flush=True)
            try:
                r = run_cell(arch, shape, mp, save_hlo=args.save_hlo,
                             out_dir=args.out, sp=args.sp, tag=args.tag)
                mem = r["memory"]["total_bytes"] / 2**30
                rl = r["roofline"]
                print(f"  ok: mem/dev={mem:.2f} GiB "
                      f"compute={rl['compute_s']:.3e}s "
                      f"memory={rl['memory_s']:.3e}s "
                      f"collective={rl['collective_s']:.3e}s "
                      f"dominant={rl['dominant']} "
                      f"(trace {r['trace_s']:.1f}s compile {r['compile_s']:.1f}s)",
                      flush=True)
            except Exception as e:
                failures.append((arch, shape, mname, repr(e)))
                print(f"  FAIL: {e}\n{traceback.format_exc()}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nall dry-run cells passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

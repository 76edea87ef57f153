"""Config-sweep engine: monitor many (config x mesh x algorithm) cells.

The paper renders one program's communication; comparing behavior *across*
algorithms, topologies and workloads is where monitoring earns its keep
("Demystifying NCCL", "The Landscape of GPU-Centric Communication").  This
module runs :func:`repro.core.monitor.monitor_fn` over a registry of
sweepable configs -- the paper's own applications (GNMT, ResNet-18, the DDP
microbenchmark) plus every architecture in :mod:`repro.configs` at reduced
scale -- crossed with mesh shapes and collective algorithms, and emits the
comparative artifact set (JSON / CSV / HTML dashboard / Perfetto timeline)
through :mod:`repro.core.export`.

Three properties keep iteration fast:

* **dry-run**: every cell lowers against ``jax.ShapeDtypeStruct`` stand-ins
  (model ``.shapes()`` trees), so no device memory is ever allocated;
* **cache**: finished reports land in the on-disk
  :class:`~repro.core.report_cache.ReportCache` keyed by ``(config, mesh,
  algorithm, jax version)`` -- a second sweep run recompiles nothing, and a
  cell keyed with ``phase=`` reuses the cached whole-session snapshot
  instead of recapturing (per-phase rows are lazy ``view(phase=...)``
  bindings over it);
* **algorithm derivation**: compilation is algorithm-independent, so extra
  algorithms for an already-compiled cell are derived in milliseconds from
  a sibling report's lazy ``view(algorithm)`` binding
  (``CommReport.rebound``).

Multi-phase workloads sweep natively: a config's builder may return
``{"captures": [{"phase", "fn", "args", ...}, ...]}`` instead of a single
``{"fn", "args"}``, and the cell is monitored as one
:class:`~repro.core.session.MonitorSession` (one compile per capture, one
snapshot per cell) -- see the ``serve`` config's prefill/decode cells and
``sweep --by-phase``.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import time
from typing import Callable, Optional

from repro.core import monitor_fn
from repro.core.cost_models import ALGORITHMS, validate_algorithm
from repro.core.report_cache import ReportCache, cache_key
from repro.core.reporter import format_table, human_bytes
DEFAULT_MESHES = ("4x2",)


# ---------------------------------------------------------------------------
# mesh specs
# ---------------------------------------------------------------------------
_MESH_AXES = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}


def parse_mesh(spec: str):
    """``"8"`` -> (8,) data  |  ``"4x2"`` -> (4,2) data,model  |
    ``"2x2x2"`` -> (2,2,2) pod,data,model."""
    shape = tuple(int(p) for p in spec.lower().split("x"))
    if len(shape) not in _MESH_AXES:
        raise ValueError(f"mesh spec {spec!r}: want 1-3 'x'-separated ints")
    return shape, _MESH_AXES[len(shape)]


def mesh_id(spec: str) -> str:
    shape, axes = parse_mesh(spec)
    return "x".join(map(str, shape)) + ":" + ",".join(axes)


def check_mesh_fits(spec: str):
    """Raise ``ValueError`` when ``spec`` needs more devices than the
    default backend has.  The backend is never swapped for forced CPU
    devices behind the user's back: on a chip host a larger mesh is
    modelled by running under ``JAX_PLATFORMS=cpu``."""
    import math

    import jax
    shape, _ = parse_mesh(spec)
    need, have = math.prod(shape), jax.device_count()
    if need > have:
        dev = jax.devices()[0]
        raise ValueError(
            f"mesh {spec} needs {need} devices but this host has {have} "
            f"{dev.platform} device(s) ({dev.device_kind}); CPU modelling "
            f"of larger meshes runs under JAX_PLATFORMS=cpu")


def build_mesh(spec: str):
    from repro.compat import make_mesh
    shape, axes = parse_mesh(spec)
    return make_mesh(shape, axes)


# ---------------------------------------------------------------------------
# sweepable-config registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One sweepable workload: a builder from mesh -> monitorable program.

    ``build(mesh)`` returns either ``dict(fn=, args=, kwargs=)`` (a single
    captured function) or ``dict(captures=[dict(phase=, fn=, args=,
    kwargs=, name=), ...])`` -- a multi-phase session monitored as one
    cell.
    """

    name: str
    description: str
    version: str                 # part of the cache key: bump to invalidate
    build: Callable              # (mesh) -> dict(fn=...) | dict(captures=...)

    @property
    def config_id(self) -> str:
        return f"{self.name}/{self.version}"


def _sds_like(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), tree)


def _data_axis_size(mesh) -> int:
    if "data" not in mesh.axis_names:
        raise ValueError(
            f"config needs a 'data' mesh axis; got {tuple(mesh.axis_names)}")
    return dict(zip(mesh.axis_names, mesh.devices.shape))["data"]


def _build_paper(mesh):
    """Paper §4 microbenchmark: DDP 2-layer MLP, bucketed AllReduce.

    On a 3-axis (pod,data,model) mesh the replica axis spans ``("pod",
    "data")`` so the gradient AllReduce crosses the DCN boundary -- the
    multi-pod shape the lint pass's flat-ring rule prices.
    """
    import jax
    import jax.numpy as jnp
    from repro.train import ddp

    d = 256
    n_data = _data_axis_size(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axis = ("pod", "data") if "pod" in mesh.axis_names else "data"
    n_repl = n_data * sizes.get("pod", 1)
    b = 4 * n_repl

    def loss_fn(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
        pred = h @ params["w2"]
        return ((pred - batch["y"]) ** 2).mean(), {}

    step = ddp.make_ddp_train_step(loss_fn, mesh, axis_name=axis,
                                   mode="bucketed", bucket_mb=1.0)
    f32 = jnp.float32
    params = {"w1": jax.ShapeDtypeStruct((d, 4 * d), f32),
              "b1": jax.ShapeDtypeStruct((4 * d,), f32),
              "w2": jax.ShapeDtypeStruct((4 * d, d), f32)}
    batch = {"x": jax.ShapeDtypeStruct((b, d), f32),
             "y": jax.ShapeDtypeStruct((b, d), f32)}
    return {"fn": step, "args": (params, _sds_like(params), batch)}


def _build_gnmt(mesh):
    """Paper §4.1 app: data-parallel GNMT epoch (broadcast + DDP steps +
    metrics AllGather), lowered against ShapeDtypeStructs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.models.gnmt import GNMT
    from repro.train import ddp

    n_data = _data_axis_size(mesh)
    steps, seq = 4, 16
    b = 2 * n_data
    model = GNMT(vocab=1024, d=64, layers=2)

    def epoch(params, batches):
        # startup Broadcast modeled as AllGather + take rank-0 (DESIGN.md §8)
        params = jax.tree.map(
            lambda p: jax.lax.all_gather(p, "data")[0], params)

        def one(params, batch):
            (loss, _), grads = jax.value_and_grad(
                model.loss_fn, has_aux=True)(params, batch)
            grads, _ = ddp.allreduce_bucketed(grads, "data", bucket_mb=1.0)
            params = jax.tree.map(
                lambda p, g: p - 1e-2 * g.astype(p.dtype), params, grads)
            return params, loss

        params, losses = jax.lax.scan(one, params, batches)
        metrics = jax.lax.all_gather(losses, "data")
        return params, metrics

    prog = jax.shard_map(epoch, mesh=mesh,
                         in_specs=(P(), P(None, "data")),
                         out_specs=(P(), P()), check_vma=False)
    i32 = jnp.int32
    batches = {k: jax.ShapeDtypeStruct((steps, b, seq), i32)
               for k in ("src", "tgt", "labels")}
    return {"fn": prog, "args": (model.shapes(), batches)}


def _build_resnet(mesh):
    """Paper §4.2 app: ResNet-18 DDP step with PyTorch-style bucketing."""
    import jax
    import jax.numpy as jnp
    from repro.models.resnet import ResNet18
    from repro.train import ddp

    n_data = _data_axis_size(mesh)
    b = 2 * n_data
    model = ResNet18(num_classes=100)
    step = ddp.make_ddp_train_step(model.loss_fn, mesh, mode="bucketed",
                                   bucket_mb=1.0)
    params = model.shapes()
    batch = {"images": jax.ShapeDtypeStruct((b, 32, 32, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((b,), jnp.int32)}
    return {"fn": step, "args": (params, _sds_like(params), batch)}


def _build_serve(mesh):
    """Prefill/decode serve cells: one multi-phase session per sweep cell.

    Monitors the qwen3-family reduced config's prefill (full prompt, fills
    the KV cache) and decode (one token against the cache) as TWO named
    phases of one :class:`~repro.core.session.MonitorSession`, so
    ``sweep --by-phase`` shows the prefill all-gather-heavy profile next
    to the decode TP-psum profile without a separate compile per row.
    """
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.models import build_model
    from repro.parallel import Sharder
    from repro.serve import ServeConfig, cache_shardings

    n_data = _data_axis_size(mesh)
    batch = 2 * n_data
    prompt_len, max_len = 32, 48
    cfg = configs.config("qwen3_8b", reduced=True)
    model = build_model(cfg)
    shd = Sharder(mesh)
    scfg = ServeConfig(max_len=max_len, batch=batch)
    cache_sh = cache_shardings(model, scfg, shd)
    params = model.shapes()
    i32 = jnp.int32

    def prefill(params, batch_):
        return model.prefill(params, batch_, shd, max_len=max_len)

    def decode(params, cache, batch_):
        return model.decode_step(params, cache, batch_, shd)

    return {"captures": [
        {"phase": "prefill", "name": "prefill", "fn": prefill,
         "args": (params,
                  {"tokens": jax.ShapeDtypeStruct((batch, prompt_len),
                                                  i32)}),
         "kwargs": {"out_shardings": (None, cache_sh)}},
        {"phase": "decode", "name": "decode", "fn": decode,
         "args": (params, model.cache_shapes(batch, max_len),
                  {"tokens": jax.ShapeDtypeStruct((batch, 1), i32)}),
         "kwargs": {"in_shardings": (None, cache_sh, None),
                    "out_shardings": (None, cache_sh)}},
    ]}


def _build_moe_skew(mesh):
    """Skewed MoE dispatch/combine: expert-parallel ``all_to_all`` with an
    irregular per-rank byte vector.

    The einsum MoE block (:mod:`repro.models.moe`) dispatches via matmuls
    and emits no all-to-all, so this cell uses the NCCL-style formulation
    instead: ``shard_map`` over the data axis, one expert per rank, one
    ``jax.lax.all_to_all`` to dispatch token buffers to their experts and
    one to combine the results back.  Expert capacity comes from the MoE
    block's own :func:`~repro.models.moe.group_capacity`.

    Static HLO cannot know the routing, so the cell injects the measured
    skew through the capture's ``op_transform`` hook: expert 0 is hot,
    handling 60% of all tokens, and every a2a gets a per-rank byte vector
    (``bytes_per_rank_vec``) with 60% of the bytes on rank 0 -- the hot
    row in the comm-matrix heatmap, the straggler in the timed schedule,
    and the ``skewed-a2a`` lint finding.
    """
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.models.common import ModelConfig
    from repro.models.moe import group_capacity

    n = _data_axis_size(mesh)
    d, f = 128, 256
    cfg = ModelConfig(name="moe_skew", family="moe", n_layers=1, d_model=d,
                      n_heads=4, n_kv_heads=4, d_ff=f, vocab_size=256,
                      n_experts=n, top_k=1)
    cap = group_capacity(cfg, group=n * 32)   # tokens per (src, expert) slot

    def step(tokens, wi, wo):
        # tokens local: (n, cap, d) -- row e holds the tokens this rank
        # routes to expert e (capacity-padded dense dispatch buffers)
        recv = jax.lax.all_to_all(tokens, "data", 0, 0)           # dispatch
        h = jax.nn.silu(recv.reshape(n * cap, d) @ wi) @ wo       # expert MLP
        back = jax.lax.all_to_all(h.reshape(n, cap, d), "data", 0, 0)
        return back                                               # combine

    prog = jax.shard_map(step, mesh=mesh,
                         in_specs=(P("data"), P(), P()),
                         out_specs=P("data"), check_vma=False)
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((n * n, cap, d), f32),
            jax.ShapeDtypeStruct((d, f), f32),
            jax.ShapeDtypeStruct((f, d), f32))

    hot_frac = 0.6

    def hot_expert(op):
        if op.kind not in ("all-to-all", "ragged-all-to-all"):
            return op
        m = op.group_size
        if m < 2:
            return op
        total = float(op.payload_bytes)
        vec = [total * (1.0 - hot_frac) / (m - 1)] * m
        vec[0] = total * hot_frac
        return dc.replace(op, bytes_per_rank_vec=vec)

    return {"fn": prog, "args": args, "op_transform": hot_expert}


def _arch_builder(arch: str):
    """Reduced-scale train step for one :mod:`repro.configs` architecture,
    sharded by the production Sharder over the given mesh (needs data+model
    axes)."""

    def build(mesh):
        import dataclasses as dc

        import jax
        from repro import configs
        from repro.models import build_model
        from repro.models.common import ShapeConfig
        from repro.optim import OptConfig
        from repro.parallel import Sharder
        from repro.train import TrainConfig
        from repro.train.train import (batch_shardings, make_train_step,
                                       train_state_shapes,
                                       train_state_shardings)

        n_data = _data_axis_size(mesh)
        cfg = configs.config(arch, reduced=True)
        shape = ShapeConfig("sweep_small", seq_len=64,
                            global_batch=2 * n_data, kind="train")
        model = build_model(cfg)
        shd = Sharder(mesh)
        ocfg = OptConfig(name=cfg.optimizer, state_dtype=cfg.opt_state_dtype)
        tcfg = TrainConfig()
        step = make_train_step(model, ocfg, tcfg, shd)
        state_sh = train_state_shardings(model, ocfg, shd)
        state_shapes = train_state_shapes(model, ocfg)
        batch = configs.input_specs(cfg, shape)
        b_sh = batch_shardings(batch, shd)
        return {"fn": step, "args": (state_shapes, batch),
                "kwargs": {"in_shardings": (state_sh, b_sh)}}

    return build


def _registry() -> dict[str, SweepSpec]:
    from repro import configs as _configs

    specs = [
        SweepSpec("paper", "paper §4 DDP microbenchmark (2-layer MLP, "
                  "bucketed AllReduce)", "v2:d=256,bucket=1,pod-dp",
                  _build_paper),
        SweepSpec("gnmt", "paper §4.1 GNMT machine translation, DDP epoch "
                  "(broadcast + AllReduce + AllGather)",
                  "v1:d=64,layers=2,steps=4", _build_gnmt),
        SweepSpec("resnet", "paper §4.2 ResNet-18 image classification, DDP "
                  "step (PyTorch-style bucketing)",
                  "v1:classes=100,bucket=1", _build_resnet),
        SweepSpec("serve", "prefill/decode serve cells: one multi-phase "
                  "session per cell (qwen3_8b reduced; use --by-phase)",
                  "v1:qwen3,prompt=32,max=48", _build_serve),
        SweepSpec("moe-skew", "skewed MoE expert dispatch: expert-parallel "
                  "all-to-all with a 60%-hot expert 0 (irregular per-rank "
                  "byte vectors via op_transform)",
                  "v1:d=128,hot=0.6,topk=1", _build_moe_skew),
    ]
    for arch in _configs.ARCH_IDS:
        specs.append(SweepSpec(
            arch, f"reduced-scale {arch} train step (Sharder-sharded)",
            "v1:reduced,seq=64", _arch_builder(arch)))
    return {s.name: s for s in specs}


def available_configs() -> dict[str, SweepSpec]:
    """Name -> spec for every sweepable config (paper apps + architectures)."""
    return _registry()


# ---------------------------------------------------------------------------
# the sweep itself
# ---------------------------------------------------------------------------
def _monitor_cell(built: dict, mesh, name: str, algorithm: str):
    """Monitor one built cell: a single function via ``monitor_fn``, or a
    ``captures`` list as one multi-phase :class:`MonitorSession`."""
    if "captures" not in built:
        return monitor_fn(
            built["fn"], *built.get("args", ()),
            mesh=mesh, name=name, algorithm=algorithm,
            op_transform=built.get("op_transform"),
            **built.get("kwargs", {}))
    from repro.core import MonitorSession

    with MonitorSession(mesh=mesh, name=name, algorithm=algorithm) as sess:
        for cap in built["captures"]:
            with sess.phase(cap["phase"]):
                sess.capture(cap["fn"], *cap.get("args", ()),
                             name=cap.get("name"),
                             op_transform=cap.get("op_transform",
                                                  built.get("op_transform")),
                             **cap.get("kwargs", {}))
    return sess.report()


@dataclasses.dataclass
class SweepResult:
    reports: list                        # CommReport, one per finished cell
    failures: list[dict]                 # {config, mesh, error}
    cache_hits: int
    compiles: int
    artifacts: dict[str, str] = dataclasses.field(default_factory=dict)

    def summary_table(self, by_link: bool = False,
                      by_phase: bool = False,
                      lint: bool = False) -> str:
        """One row per cell; ``by_link=True`` adds the physical-link view
        (busiest link, its contention-aware bottleneck ms, and the
        tier-overlapped communication time ici ∥ dcn -- the ``--by-link``
        CLI columns).  ``by_phase=True`` expands each cell into one row per
        session phase (single-phase reports keep one row, labelled with
        their phase), with all statistics computed from that phase's
        :class:`~repro.core.views.CommView`.  ``lint=True`` appends the
        static-analysis columns: finding count (worst severity) and the
        total modeled savings across findings (the ``--lint`` CLI
        columns)."""
        from repro.core.lint import max_severity
        rows = []
        for rep in self.reports:
            targets = [(None, rep.view())]
            if by_phase and rep.phase_names():
                targets = [(ph, rep.view(phase=ph))
                           for ph in rep.phase_names()]
            for ph, view in targets:
                summary = view.summary
                total_wire = sum(r.get("wire_bytes", 0.0)
                                 for r in summary.values())
                calls = sum(r.get("calls", 0) for r in summary.values())
                dominant = max(
                    summary,
                    key=lambda k: summary[k].get("wire_bytes", 0.0),
                ) if summary else "-"
                row = [
                    rep.meta.get("config", rep.name),
                    rep.meta.get("mesh", f"{rep.num_devices}dev"),
                    rep.algorithm,
                ]
                if by_phase:
                    row.append(ph or "-")
                row += [
                    f"{rep.num_devices}",
                    f"{calls:,}",
                    human_bytes(total_wire),
                    f"{view.collective_seconds() * 1e3:.3f}",
                    dominant,
                    rep.meta.get("source", "?"),
                ]
                if by_link:
                    lu = view.link_utilization()
                    bn = lu.bottleneck() if lu is not None else None
                    overlap = view.collective_overlap_seconds()
                    row[-1:-1] = ([bn[0].name, f"{bn[1] * 1e3:.3f}",
                                   f"{overlap * 1e3:.3f}"]
                                  if bn else ["-", "-", "-"])
                if lint:
                    findings = rep.lint(phase=ph)
                    sev = max_severity(findings)
                    row[-1:-1] = [
                        f"{len(findings)}" + (f" ({sev})" if sev else ""),
                        f"{sum(f.est_savings_s for f in findings) * 1e3:.3f}",
                    ]
                rows.append(row)
        header = ["config", "mesh", "algorithm"] \
            + (["phase"] if by_phase else []) \
            + ["devices", "collective calls", "wire bytes", "collective ms",
               "dominant primitive", "source"]
        if by_link:
            header[-1:-1] = ["busiest link", "link ms", "overlap ms"]
        if lint:
            header[-1:-1] = ["lint findings", "lint savings ms"]
        return format_table(rows, header)


def run_scale_curve(
    config_names: list[str],
    mesh_specs: list[str] = DEFAULT_MESHES,
    algorithms: list[str] = ("ring",),
    *,
    device_counts: Optional[list[int]] = None,
    cache: Optional[ReportCache] = None,
    use_cache: bool = True,
    jobs: int = 1,
    log: Callable[[str], None] = print,
):
    """``sweep --scale-curve``: monitor each cell once at its (small) base
    mesh -- cache rules identical to :func:`run_sweep` (including the
    ``jobs`` thread pool) -- then project the compiled ops onto synthetic
    fleet topologies per device count (:mod:`repro.scale`), all sparse, no
    recompilation.

    Returns ``(SweepResult, list[ScalePoint])``.
    """
    from repro import scale

    result = run_sweep(config_names, mesh_specs, algorithms,
                       cache=cache, use_cache=use_cache, jobs=jobs, log=log)
    points = scale.scale_curve(
        result.reports,
        device_counts if device_counts else scale.DEFAULT_SCALE_POINTS,
        log=log)
    return result, points


def resolve_jobs(jobs) -> int:
    """Normalize a ``--jobs`` value: int-like, or ``"auto"`` -> cpu count."""
    if isinstance(jobs, str):
        if jobs.strip().lower() == "auto":
            return max(1, os.cpu_count() or 1)
        jobs = int(jobs)
    return max(1, int(jobs))


def run_sweep(
    config_names: list[str],
    mesh_specs: list[str] = DEFAULT_MESHES,
    algorithms: list[str] = ("ring",),
    *,
    cache: Optional[ReportCache] = None,
    use_cache: bool = True,
    jobs: int = 1,
    log: Callable[[str], None] = print,
) -> SweepResult:
    """Monitor every (config, mesh) cell, derive every algorithm, cache all.

    Per cell: try the cache for each requested algorithm; if at least one
    entry exists, derive the missing algorithms from it (compile-free); only
    a fully-cold cell compiles, once, regardless of algorithm count.

    ``jobs > 1`` evaluates independent cells on a thread pool (cells are
    jax compiles -- most of the wall clock releases the GIL).  Workers only
    *read* the shared :class:`ReportCache`; all writes (``cache.put``,
    report/failure assembly, counters) happen afterwards on the calling
    thread in the serial iteration order, so the result -- reports order,
    failures, CSV output -- is identical to ``jobs=1``.
    """
    registry = _registry()
    unknown = [c for c in config_names if c not in registry]
    if unknown:
        raise KeyError(
            f"unknown config(s) {unknown}; known: {sorted(registry)}")
    for alg in algorithms:
        validate_algorithm(alg)
    for mspec in mesh_specs:
        check_mesh_fits(mspec)
    cache = cache or ReportCache()
    result = SweepResult(reports=[], failures=[], cache_hits=0, compiles=0)
    jobs = resolve_jobs(jobs)

    def eval_cell(cname: str, mspec: str):
        """One (config, mesh) cell: probe cache, compile if cold, derive
        missing algorithms.  Pure w.r.t. shared state -- returns
        ``(cell, keys, failure, cache_hits, compiles)`` for the caller to
        merge deterministically."""
        spec = registry[cname]
        mid = mesh_id(mspec)
        keys = {alg: cache_key(spec.config_id, mid, alg)
                for alg in algorithms}
        cell: dict[str, object] = {}
        hits = 0
        compiles = 0
        if use_cache:
            for alg, key in keys.items():
                rep = cache.get(key)
                if rep is not None:
                    log(f"[cache] hit config={cname} mesh={mspec} "
                        f"algorithm={alg} key={key}")
                    rep.meta["source"] = "cache"
                    cell[alg] = rep
                    hits += 1
        missing = [a for a in algorithms if a not in cell]
        sibling = None
        if missing and not cell and use_cache:
            # an entry for an UNrequested algorithm still spares the
            # compile: everything derives from the same compiled ops
            for alg in ALGORITHMS:
                if alg in keys:
                    continue            # already probed above
                rep = cache.get(cache_key(spec.config_id, mid, alg))
                if rep is not None:
                    log(f"[cache] sibling hit config={cname} "
                        f"mesh={mspec} algorithm={alg} -- deriving "
                        "requested algorithms without recompiling")
                    rep.meta["source"] = "cache"
                    sibling = rep
                    break
        if missing and not cell and sibling is None:
            # fully cold: compile once for the first missing algorithm
            alg0 = missing[0]
            log(f"[sweep] compile config={cname} mesh={mspec} "
                f"algorithm={alg0} ...")
            t0 = time.perf_counter()
            try:
                mesh = build_mesh(mspec)
                built = spec.build(mesh)
                rep = _monitor_cell(built, mesh, f"{cname}@{mspec}",
                                    alg0)
            except Exception as e:  # noqa: BLE001 -- keep sweeping
                log(f"[sweep] FAIL config={cname} mesh={mspec}: {e!r}")
                failure = {"config": cname, "mesh": mspec,
                           "error": repr(e)}
                return cell, keys, failure, hits, compiles
            compiles += 1
            log(f"[sweep] compiled config={cname} mesh={mspec} in "
                f"{time.perf_counter() - t0:.1f}s "
                f"({len(rep.compiled_ops)} collectives)")
            rep.meta.update(config=cname, mesh=mspec, source="compiled")
            cell[alg0] = rep
            missing = [a for a in algorithms if a not in cell]
        if missing and (cell or sibling):
            # warm: derive remaining algorithms without recompiling --
            # a lazy view(alg) binding over the sibling's compiled ops,
            # snapshotted so the cache gets one report per algorithm
            base = next(iter(cell.values())) if cell else sibling
            for alg in missing:
                rep = base.rebound(alg)
                rep.meta = dict(base.meta, source="derived",
                                algorithm=alg)
                log(f"[sweep] derive config={cname} mesh={mspec} "
                    f"algorithm={alg} (no recompile)")
                cell[alg] = rep
        return cell, keys, None, hits, compiles

    cells = [(cname, mspec) for cname in config_names
             for mspec in mesh_specs]
    if jobs > 1 and len(cells) > 1:
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=min(jobs, len(cells))) as pool:
            futures = [pool.submit(eval_cell, cn, ms) for cn, ms in cells]
            outcomes = [f.result() for f in futures]
    else:
        outcomes = [eval_cell(cn, ms) for cn, ms in cells]

    for (cname, mspec), (cell, keys, failure, hits, compiles) in zip(
            cells, outcomes):
        result.cache_hits += hits
        result.compiles += compiles
        if failure is not None:
            result.failures.append(failure)
            continue
        for alg in algorithms:
            if alg not in cell:
                continue
            rep = cell[alg]
            rep.meta.update(config=cname, mesh=mspec, algorithm=alg)
            result.reports.append(rep)
            if use_cache and rep.meta.get("source") != "cache":
                cache.put(keys[alg], rep, meta=rep.meta)
    return result

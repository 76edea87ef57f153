"""Three-term roofline analysis from a compiled (dry-run) artifact.

For a compiled SPMD program the module is per-device, so
``compiled.cost_analysis()`` reports *per-device* FLOPs and bytes; dividing
by per-chip peaks yields the same seconds as the global formulation
(``HLO_FLOPs_global / (chips x peak)``):

    compute_s    = flops_per_device        / peak_flops_per_chip
    memory_s     = bytes_accessed_per_dev  / hbm_bw_per_chip
    collective_s = wire_bytes_per_device   / link_bw  (spec formula), and a
                   topology-aware estimate (ring/DCN) as a refinement.

``wire_bytes_per_device`` is NOT in cost_analysis — it is summed from the
collective ops parsed out of the compiled HLO (the paper's contribution makes
exactly this visible).

**Link-level overlap model.**  ``collective_s_topo`` serializes every
collective; real schedules overlap compute with communication and the ICI
torus with the DCN fabric (independent wires).  The overlap-aware bound is

    bound_overlap_s = max(compute_s, memory_s,
                          collective_ici_s, collective_dcn_s)

where ``collective_ici_s`` / ``collective_dcn_s`` are the per-tier
serialized sums from ``cost_models.total_time_split`` -- bandwidth plus
the per-phase latency hops of each op's decomposition schedule
(:mod:`repro.core.decompose`), summed per phase per tier -- so
``collective_overlap_s = max(ici, dcn) <= collective_s_topo``, with
equality exactly when a single tier carries all the traffic.  The
per-link busy times from ``LinkUtilization.busy_seconds`` ride along as
the contention-aware refinement per tier (``ici_busy_s`` / ``dcn_busy_s``:
the busiest physical link of each fabric, including multi-hop transit --
pure bandwidth, since links carry bytes, not hop latencies).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from . import cost_models, hlo_parser
from .events import CollectiveOp
from .topology import HardwareSpec, MeshTopology, V5E


@dataclasses.dataclass
class RooflineReport:
    arch: str
    mesh: str
    num_devices: int
    # raw inputs
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    # three terms, in seconds
    compute_s: float
    memory_s: float
    collective_s: float
    collective_s_topo: float        # topology-aware (serialized, bw+latency)
    # link-level overlap terms (tiers are independent fabrics)
    collective_ici_s: float = 0.0   # serialized ICI share of collective_s_topo
    collective_dcn_s: float = 0.0   # serialized DCN share of collective_s_topo
    ici_busy_s: float = 0.0         # busiest physical ICI link (w/ transit)
    dcn_busy_s: float = 0.0         # busiest DCN up/downlink
    # analysis
    model_flops: float = 0.0        # 6*N*D (dense) / 6*N_active*D (MoE), global
    useful_flops_ratio: float = 0.0 # MODEL_FLOPS / (flops_per_device*chips)
    peak_fraction: float = 0.0      # compute_s / max(all terms)
    dominant: str = ""
    memory_bytes_per_device: Optional[dict] = None  # memory_analysis summary
    collective_breakdown: Optional[dict] = None

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def collective_overlap_s(self) -> float:
        """Overlapped communication time: ICI and DCN are independent
        fabrics, so their serialized per-tier sums run concurrently.
        Always <= ``collective_s_topo`` (their sum); equal exactly when a
        single tier carries all the traffic."""
        return max(self.collective_ici_s, self.collective_dcn_s)

    @property
    def bound_overlap_s(self) -> float:
        """Overlap-aware roofline bound: compute ∥ ICI ∥ DCN (and the HBM
        stream), instead of summing serialized collective times."""
        return max(self.compute_s, self.memory_s,
                   self.collective_ici_s, self.collective_dcn_s)

    def one_liner(self) -> str:
        hints = {
            "compute": "increase arithmetic efficiency (less remat recompute, "
                       "larger fused matmuls, avoid redundant einsums)",
            "memory": "reduce HBM traffic (fuse elementwise chains, better remat "
                      "policy, bf16 activations, larger per-op tiles)",
            "collective": "cut wire bytes (overlapped/hierarchical collectives, "
                          "bf16/compressed gradients, resharding to remove "
                          "redundant all-gathers)",
        }
        val = getattr(self, "collective_s" if self.dominant == "collective"
                      else self.dominant + "_s")
        return (f"{self.arch}@{self.mesh}: dominant={self.dominant} "
                f"({val:.3e}s); {hints[self.dominant]}")


def _sum_wire_bytes_per_device(ops: list[CollectiveOp], num_devices: int,
                               algorithm: str = "ring") -> float:
    """Average per-device bytes *sent* over all collective ops in one step."""
    total = 0.0
    for op in ops:
        total += op.wire_bytes_total(algorithm)
    return total / max(1, num_devices)


def analyze(
    *,
    arch: str,
    mesh_name: str,
    cost: dict,
    hlo_text,
    topo: MeshTopology,
    hw: HardwareSpec = V5E,
    model_flops: float = 0.0,
    memory_stats: dict,
    algorithm: str = "ring",
    link_utilization=None,
) -> RooflineReport:
    """Build the roofline report for one (arch x mesh) dry-run cell.

    FLOPs/bytes/collectives come from the loop-aware HLO walk
    (:mod:`repro.core.hlo_cost`) — ``cost_analysis`` counts while bodies once
    and is kept only as the ``cost_analysis_*`` reference fields.

    ``hlo_text`` is one compiled module, or a list of modules (a
    multi-capture session): each module is analyzed **separately** —
    computation names are only unique within a module, so concatenating
    them would clobber same-named computations and drop loop trip counts
    — and the per-module FLOPs / bytes / collectives are summed.

    ``link_utilization`` lets a caller that already projected the program
    onto physical links (e.g. ``CommReport.link_utilization()``) reuse it
    for the per-tier busy diagnostics instead of re-routing the placed
    edges here (cost is proportional to placed edges x route hops).
    """
    from . import hlo_cost as hc_mod
    texts = [hlo_text] if isinstance(hlo_text, str) else list(hlo_text)
    hcs = [hc_mod.analyze_hlo(t) for t in texts]
    ops = [op for hc in hcs for op in hc.collectives]
    flops = sum(hc.flops for hc in hcs)
    byts = sum(hc.bytes_hbm for hc in hcs)
    bytes_logical = sum(hc.bytes_logical for hc in hcs)
    wire = _sum_wire_bytes_per_device(ops, topo.num_devices, algorithm)

    compute_s = flops / hw.peak_flops_bf16
    memory_s = byts / hw.hbm_bw
    # spec formula: collective_bytes / (chips x link_bw); per-device wire bytes
    # over one link's bandwidth (conservative: a ring uses 2 links per axis,
    # captured in the topology-aware estimate below).
    collective_s = wire / hw.ici_bw
    ici_s, dcn_s = cost_models.total_time_split(ops, topo, algorithm)
    collective_s_topo = ici_s + dcn_s
    lu = link_utilization
    if lu is None and ops:
        from . import comm_matrix
        lu = comm_matrix.link_utilization_for_ops(ops, topo, algorithm)

    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    total_flops = flops * topo.num_devices
    mem = dict(memory_stats)
    mem["cost_analysis_flops"] = float(cost.get("flops", 0.0))
    mem["cost_analysis_bytes"] = float(cost.get("bytes accessed", 0.0))
    mem["hlo_bytes_logical"] = bytes_logical
    memory_stats = mem
    report = RooflineReport(
        arch=arch,
        mesh=mesh_name,
        num_devices=topo.num_devices,
        flops_per_device=flops,
        bytes_per_device=byts,
        wire_bytes_per_device=wire,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        collective_s_topo=collective_s_topo,
        collective_ici_s=ici_s,
        collective_dcn_s=dcn_s,
        ici_busy_s=lu.busy_seconds("ici") if lu is not None else 0.0,
        dcn_busy_s=lu.busy_seconds("dcn") if lu is not None else 0.0,
        model_flops=model_flops,
        useful_flops_ratio=(model_flops / total_flops) if total_flops else 0.0,
        peak_fraction=(compute_s / max(terms.values())) if max(terms.values()) else 0.0,
        dominant=dominant,
        memory_bytes_per_device=memory_stats,
        collective_breakdown=hlo_parser.summarize(ops, algorithm),
    )
    return report


# ---------------------------------------------------------------------------
# MODEL_FLOPS helpers — 6*N*D for training, 2*N*D for a forward/decode token
# ---------------------------------------------------------------------------
def train_model_flops(n_params_active: float, tokens: float) -> float:
    return 6.0 * n_params_active * tokens

def forward_model_flops(n_params_active: float, tokens: float) -> float:
    return 2.0 * n_params_active * tokens


def to_row(r: RooflineReport) -> dict:
    return {
        "arch": r.arch,
        "mesh": r.mesh,
        "devices": r.num_devices,
        "flops/dev": r.flops_per_device,
        "bytes/dev": r.bytes_per_device,
        "wire_bytes/dev": r.wire_bytes_per_device,
        "compute_s": r.compute_s,
        "memory_s": r.memory_s,
        "collective_s": r.collective_s,
        "collective_s_topo": r.collective_s_topo,
        "collective_ici_s": r.collective_ici_s,
        "collective_dcn_s": r.collective_dcn_s,
        "collective_overlap_s": r.collective_overlap_s,
        "bound_overlap_s": r.bound_overlap_s,
        "ici_busy_s": r.ici_busy_s,
        "dcn_busy_s": r.dcn_busy_s,
        "dominant": r.dominant,
        "model_flops": r.model_flops,
        "useful_flops_ratio": r.useful_flops_ratio,
    }

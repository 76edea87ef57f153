"""The legacy placement oracle: the pre-schedule, per-kind, per-edge matrix
builder.

Before the phase-schedule IR (``repro.core.decompose``) existed, matrices
were built by placing each collective kind by hand: flattened rings
everywhere, binary trees for ``tree``, flattened intra-pod rings plus a
cross-pod ring for ``hierarchical``.  This module keeps that loop as a
readable oracle: on single-axis replica groups (where per-axis
decomposition cannot apply) schedule-derived matrices must equal it
exactly, and on multi-axis groups it is the flattened baseline whose
transit inflation per-axis placement removes.
"""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np

from repro.core import cost_models, decompose as decompose_mod
from repro.core.events import CollectiveOp
from repro.core.topology import MeshTopology

_TREE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-broadcast")


def _ring_edges(group, per_rank: float) -> list[tuple[int, int, float]]:
    """Bidirectional ring: each member streams half its per-rank bytes to
    each ring neighbour (the torus ring algorithm uses both directions of
    the axis links -- the bandwidth ``ring_bw_per_chip`` credits).  On a
    2-member ring both halves reach the same peer and accumulate."""
    group = list(group)
    n = len(group)
    half = 0.5 * per_rank
    out: list[tuple[int, int, float]] = []
    for i in range(n):
        out.append((group[i], group[(i + 1) % n], half))
        out.append((group[i], group[(i - 1) % n], half))
    return out


def _tree_placement(group, kind: str,
                    s: float) -> list[tuple[int, int, float]]:
    """Per-edge bytes on the implicit binary tree (heap layout), resolved
    from the shared :func:`repro.core.decompose.tree_edge_profile`."""
    group = list(group)
    n = len(group)
    up, down = decompose_mod.tree_edge_profile(kind, s, n)
    edges: list[tuple[int, int, float]] = []
    for i in range(1, n):
        parent, child = group[(i - 1) // 2], group[i]
        if up[i - 1]:
            edges.append((child, parent, float(up[i - 1])))
        if down[i - 1]:
            edges.append((parent, child, float(down[i - 1])))
    return edges


def _legacy_hierarchical_placement(group, kind: str, s: float,
                                   topo: MeshTopology):
    """Pre-schedule hierarchical placement: flattened intra-pod rings +
    cross-pod exchange (no per-axis decomposition)."""
    dec = cost_models.hierarchical_decomposition(kind, list(group), topo)
    if dec is None:
        return None
    p, m, subs = dec
    phases = cost_models.hier_phases(kind)
    edges: list[tuple[int, int, float]] = []
    if m > 1:
        intra_per_rank = phases * (m - 1) * s / m
        for sub in subs:
            edges.extend(_ring_edges(sub, intra_per_rank))
    cross_per_rank = phases * (p - 1) * s / len(group)
    for j in range(m):
        ring = [subs[k][j] for k in range(p)]
        edges.extend(_ring_edges(ring, cross_per_rank))
    return edges


def _legacy_op_edges(op: CollectiveOp, algorithm: str = "ring",
                     topo: Optional[MeshTopology] = None):
    """The pre-schedule scalar placement (flattened rings everywhere)."""
    edges: list[tuple[int, int, float]] = []
    if op.kind == "collective-permute":
        nbytes = float(op.result_bytes) * op.num_groups
        return [(src, dst, nbytes) for src, dst in op.source_target_pairs]
    for group in op.replica_groups or [[]]:
        n = len(group)
        if n <= 1:
            continue
        s = float(op.payload_bytes)
        if op.kind in ("all-to-all", "ragged-all-to-all"):
            block = s / (n * n)
            edges.extend((a, b, block)
                         for a in group for b in group if a != b)
            continue
        if algorithm == "tree" and op.kind in _TREE_KINDS:
            edges.extend(_tree_placement(group, op.kind, s))
            continue
        if algorithm == "hierarchical" and topo is not None:
            placed = _legacy_hierarchical_placement(group, op.kind, s, topo)
            if placed is not None:
                edges.extend(placed)
                continue
            if op.kind in cost_models.HIERARCHICAL_KINDS \
                    and topo.group_crosses_dcn(group):
                decompose_mod.warn_fallback_once(
                    op.kind, n,
                    f"hierarchical {op.kind} over cross-pod group of {n} "
                    "cannot decompose (uneven pod split); placing flat "
                    "ring edges and billing the same fallback",
                    stacklevel=1)
        per_rank = cost_models.wire_bytes_per_rank(
            op.kind, s, n, algorithm, pods=1)
        edges.extend(_ring_edges(group, per_rank))
    return edges


def matrix_for_ops_reference(
    ops: Iterable[CollectiveOp],
    num_devices: int,
    algorithm: str = "ring",
    kinds: Optional[set[str]] = None,
    topo: Optional[MeshTopology] = None,
) -> np.ndarray:
    """The pre-schedule builder: per-op, per-edge Python accumulation over
    the legacy per-kind placement.  On single-axis replica groups (where
    per-axis decomposition does not apply) schedule-derived matrices must
    equal this loop exactly."""
    cost_models.validate_algorithm(algorithm)
    mat = np.zeros((num_devices + 1, num_devices + 1), dtype=np.float64)
    for op in ops:
        if kinds is not None and op.kind not in kinds:
            continue
        w = getattr(op, "weight", 1.0)
        for src, dst, nbytes in _legacy_op_edges(op, algorithm, topo):
            if src < num_devices and dst < num_devices:
                mat[src + 1, dst + 1] += nbytes * w
    return mat

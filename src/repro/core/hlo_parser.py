"""Extract collective-communication ops from compiled HLO text.

This is the TPU/XLA analogue of the paper's NCCL interception: on TPU the
*compiler* decides the communication schedule, so the compiled (SPMD
partitioned, per-device) module is the ground truth.  We parse
``compiled.as_text()`` for every collective op, its result shape(s),
replica groups (explicit or iota form) and metadata.

The parser is line-oriented and regex-based; HLO prints one instruction per
line.  Async pairs (``all-gather-start``/``-done``) are counted once at the
``-start``.

A malformed replica-group list (ragged explicit groups, an iota form whose
group shape does not tile its source) raises :class:`HLOParseError` carrying
the offending instruction text -- silently dropping groups would make every
downstream byte count quietly wrong.
"""
from __future__ import annotations

import re
from typing import Iterable

import numpy as np

from .events import COLLECTIVE_KINDS, CollectiveOp, Shape


class HLOParseError(ValueError):
    """An HLO instruction the parser recognizes but cannot interpret
    (malformed replica groups, ...).  Carries the op text in the message."""

# ----------------------------------------------------------------------------
# Shape parsing
# ----------------------------------------------------------------------------
_SHAPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|s4|s8|s16|s32|s64|u4|u8|u16|u32|u64|c64|c128"
    r"|f8e4m3fn|f8e4m3b11fnuz|f8e4m3fnuz|f8e5m2fnuz|f8e5m2|f8e3m4|f8e4m3)"
    r"\[([0-9,]*)\]"
)


def _parse_shapes(text: str) -> list[Shape]:
    out = []
    for m in _SHAPE_RE.finditer(text):
        dims = tuple(int(d) for d in m.group(2).split(",") if d != "")
        out.append(Shape(dtype=m.group(1), dims=dims))
    return out


# ----------------------------------------------------------------------------
# Replica-group parsing: explicit {{0,1},{2,3}} and iota [4,2]<=[8] or
# [2,4]<=[4,2]T(1,0) forms.
# ----------------------------------------------------------------------------
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[0-9,{}\s]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)


def parse_replica_groups(line: str) -> list[list[int]]:
    """Replica groups of one instruction line ([] when the attribute is
    absent).  Raises :class:`HLOParseError` (with the op text) on malformed
    lists: ragged explicit groups, or an iota form whose group shape does
    not hold exactly the source's elements / whose permutation does not
    match the source rank."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        group_shape = [int(x) for x in m.group(1).split(",")]
        src_dims = [int(x) for x in m.group(2).split(",")]
        if int(np.prod(group_shape)) != int(np.prod(src_dims)):
            raise HLOParseError(
                f"iota replica_groups [{m.group(1)}]<=[{m.group(2)}] do not "
                f"tile: {np.prod(group_shape)} != {np.prod(src_dims)} "
                f"elements in op: {line.strip()}")
        v = np.arange(int(np.prod(src_dims))).reshape(src_dims)
        if m.group(3):
            perm = [int(x) for x in m.group(3).split(",")]
            if sorted(perm) != list(range(len(src_dims))):
                raise HLOParseError(
                    f"iota replica_groups transpose T({m.group(3)}) is not "
                    f"a permutation of the {len(src_dims)}-d source in op: "
                    f"{line.strip()}")
            v = v.transpose(perm)
        v = v.reshape(group_shape)
        return [list(map(int, row)) for row in v]
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        inner = m.group(1)
        groups = [
            [int(x) for x in g.replace(" ", "").split(",") if x != ""]
            for g in re.findall(r"\{([0-9,\s]*)\}", inner)
        ]
        sizes = {len(g) for g in groups}
        if len(sizes) > 1:
            raise HLOParseError(
                f"ragged replica_groups (sizes {sorted(sizes)}) in op: "
                f"{line.strip()}")
        return groups
    return []


_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GLOBAL_IDS_RE = re.compile(r"use_global_device_ids=true")
_DIMS_RE = re.compile(r"dimensions=\{([0-9,]*)\}")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# Per-rank byte vector riding in frontend_attributes (irregular
# collectives: allgatherv / skewed MoE all-to-all).  Runtimes that know the
# true per-rank sizes stamp them as a comma-separated list, e.g.
# ``frontend_attributes={repro.bytes_per_rank_vec="4096,1024,1024,1024"}``.
_VEC_RE = re.compile(r'repro\.bytes_per_rank_vec="([0-9eE+\-.,\s]+)"')


def _parse_byte_vector(line: str):
    """``bytes_per_rank_vec`` list from a frontend attribute, or ``None``
    (malformed vectors are dropped here; length/kind validation happens in
    :meth:`~repro.core.events.CollectiveOp.byte_vector`)."""
    m = _VEC_RE.search(line)
    if not m:
        return None
    try:
        vec = [float(x) for x in m.group(1).split(",") if x.strip()]
    except ValueError:
        return None
    return vec or None


# ----------------------------------------------------------------------------
# Operand parsing that survives both HLO spellings.  New jax prints
# ``all-reduce(%a, %b)``; jax 0.4.x prints typed operands
# ``all-reduce(f32[8,8]{1,0} %a, (s32[], f32[4]) %b)`` whose layouts and
# tuple-shaped types contain commas and parens, so naive ``split(",")``
# parsing silently yields garbage names.  These helpers are shared with
# :mod:`repro.core.hlo_cost` (which re-imports them).
# ----------------------------------------------------------------------------
def _split_top_level(text: str) -> list[str]:
    """Split on commas at bracket depth 0 (wrt ``()[]{}``)."""
    parts: list[str] = []
    cur: list[str] = []
    depth = 0
    for ch in text:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def _operand_names(args_text: str) -> list[str]:
    """Operand names from a call's argument text (last token per operand,
    ``%`` stripped -- drops any inline type annotation)."""
    return [p.split()[-1].lstrip("%") for p in _split_top_level(args_text)]


def _call_args(line: str, opcode: str) -> str:
    """Balanced-paren argument text of ``opcode(...)`` in ``line``
    ('' when absent)."""
    idx = line.find(opcode + "(")
    if idx < 0:
        return ""
    start = idx + len(opcode) + 1
    depth = 1
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return line[start:i]
    return line[start:]

# instruction: [ROOT] %name = <result-type> opcode(
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)"
    r"(-start)?\s*\("
)


_PROMOTED_RE = re.compile(r"to_apply=%?\S*promoted")
_CONTEXT = Shape("u32", ())


def parse_hlo_collectives(hlo_text: str) -> list[CollectiveOp]:
    """Parse all collective ops from HLO text (one per async pair).

    XLA:CPU *promotes* bf16 all-reduces to f32 (convert -> AR(f32) ->
    convert, reduction computation named ``*_promoted``); TPU reduces bf16
    natively.  Promoted ops are accounted at their pre-promotion width.
    """
    ops: list[CollectiveOp] = []
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if not line or "=" not in line:
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name, result_text, kind, _start = m.group(1), m.group(2), m.group(3), m.group(4)
        # skip fusions that merely *consume* a collective: opcode must follow '='
        result_shapes = _parse_shapes(result_text)
        if _PROMOTED_RE.search(line):
            result_shapes = [
                Shape("bf16", s.dims) if s.dtype == "f32" else s
                for s in result_shapes]
        # async-start results repeat operand + result; dedupe: the final shape
        # tuple of a start op is ((operands), results, ...) -- keep the result
        # entries only.  A TPU's collective-permute-start ends in two u32[]
        # context scalars, (op, result, u32[], u32[]): drop them first.
        if _start and len(result_shapes) >= 2:
            # all-gather-start: (op, result); all-reduce-start: same shape
            if (kind == "collective-permute" and len(result_shapes) == 4
                    and result_shapes[2:] == [_CONTEXT, _CONTEXT]):
                result_shapes = result_shapes[:2]
            half = len(result_shapes) // 2
            result_shapes = result_shapes[half:] or result_shapes
        groups = parse_replica_groups(line)
        pairs = []
        pm = _PAIRS_RE.search(line)
        if pm:
            pairs = [
                tuple(int(x) for x in p.split(","))
                for p in re.findall(r"\{(\d+,\d+)\}", pm.group(1))
            ]
        cm = _CHANNEL_RE.search(line)
        dm = _DIMS_RE.search(line)
        om = _OPNAME_RE.search(line)
        # operand names via the balanced-paren walk: tuple-shaped operands
        # (async starts, variadic all-reduces) contain depth-1 commas that
        # a naive split would shred
        args = _call_args(line, kind + ("-start" if _start else ""))
        operands = _operand_names(args) if args.strip() else []
        ops.append(
            CollectiveOp(
                kind=kind,
                name=name,
                result_shapes=result_shapes,
                replica_groups=groups,
                channel_id=int(cm.group(1)) if cm else None,
                dimensions=tuple(int(x) for x in dm.group(1).split(",") if x)
                if dm
                else (),
                source_target_pairs=pairs,
                op_name=om.group(1) if om else "",
                operand_names=operands,
                use_global_device_ids=bool(_GLOBAL_IDS_RE.search(line)),
                bytes_per_rank_vec=_parse_byte_vector(line),
            )
        )
    return ops


# ----------------------------------------------------------------------------
# Summaries
# ----------------------------------------------------------------------------
def _op_wire_bytes(op: CollectiveOp, algorithm: str, topo) -> float:
    """Execution-weighted wire bytes for one op, decided **per replica
    group** with the shared hierarchical predicate -- so summaries
    degenerate to ring exactly where the placement and the cost model do
    (one predicate, no divergence), even when groups differ in how they
    straddle pods."""
    from . import cost_models

    if op.kind == "collective-permute":
        if algorithm == "hierarchical" and topo is not None \
                and topo.num_pods > 1 and op.source_target_pairs:
            # the pod-leader relay adds ICI hops the flat pair count
            # misses; read the total off the same schedule the matrix
            # places so summary == matrix
            from . import decompose as _dec
            return _dec.decompose(op, algorithm, topo,
                                  warn=False).total_bytes() * op.weight
        return op.wire_bytes_total(algorithm)
    if topo is None or not op.replica_groups:
        return op.wire_bytes_total(algorithm)
    total = 0.0
    for g in op.replica_groups:
        total += cost_models.wire_bytes_group_total(
            op.kind, op.payload_bytes, len(g), algorithm,
            pods=cost_models.effective_pods(op.kind, g, topo),
            vec=op.byte_vector())
    return total * op.weight


def summarize(ops: Iterable[CollectiveOp], algorithm: str = "ring",
              topo=None) -> dict:
    """Paper Table-2/3-style summary: per-kind call counts and byte totals.

    Counts are execution-weighted: an op inside a while body with trip count
    64 contributes 64 calls (loop-aware, see hlo_cost.py).  ``topo`` (a
    :class:`~repro.core.topology.MeshTopology`) makes the hierarchical
    algorithm's byte totals pod-aware.
    """
    table: dict[str, dict] = {}
    for op in ops:
        row = table.setdefault(
            op.kind,
            {"calls": 0, "payload_bytes": 0, "wire_bytes": 0.0},
        )
        row["calls"] += int(op.weight)
        row["payload_bytes"] += int(op.payload_bytes * op.num_groups * op.weight)
        row["wire_bytes"] += _op_wire_bytes(op, algorithm, topo)
        skew = op.skew()
        if skew > 1.0:
            # irregular ops surface their worst max/mean per-rank skew
            # (absent for regular kinds, so fixed-column consumers keep
            # their layout)
            row["max_skew"] = max(row.get("max_skew", 1.0), skew)
        if op.measured_s is not None:
            # trace-imported ops carry measured wall time (schema v9);
            # absent for purely modeled captures, so fixed-column
            # consumers keep their layout
            row["measured_s"] = (row.get("measured_s", 0.0)
                                 + float(op.measured_s))
    return table


def total_wire_bytes(ops: Iterable[CollectiveOp], algorithm: str = "ring",
                     topo=None) -> float:
    """Global bytes-on-the-wire across all devices (roofline numerator)."""
    return float(sum(_op_wire_bytes(op, algorithm, topo) for op in ops))


def count_by_opname(ops: Iterable[CollectiveOp]) -> dict[str, int]:
    out: dict[str, int] = {}
    for op in ops:
        key = op.op_name or "<unattributed>"
        out[key] = out.get(key, 0) + 1
    return out

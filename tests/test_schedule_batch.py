"""Schedule evaluation: bitwise parity with the per-op oracle.

The evaluation path (signature-memoized ``cached_decompose``, deduping
``schedules_for_ops``, edge arrays and ``time_split`` once per distinct
schedule) promises every consumer **bitwise-identical** artifacts to
decomposing and placing each op on its own -- matrices (dense and
sparse), per-op and total per-tier timing, link projection -- while
decomposing once per distinct op shape.
This suite pins that promise on a deterministic grid (all op kinds x all
algorithms x 1/2/4-pod meshes x uniform/skewed byte vectors), exercises
the cache's correctness edges (topology/algorithm in the signature, weight
out of it; no collisions between equal-device-count meshes), and checks
the bounded-LRU mechanics plus fallback-warning replay through cache hits.
A hypothesis-widened generator rides along when the library is available.
"""
import dataclasses
import warnings

import numpy as np
import pytest

from repro.core import comm_matrix, cost_models
from repro.core.decompose import (BoundedCache, HierarchicalFallbackWarning,
                                  cached_decompose,
                                  clear_schedule_cache, decompose,
                                  op_signature, reset_fallback_warnings,
                                  schedule_cache, schedules_for_ops,
                                  topo_signature)
from repro.core.cost_models import clear_billing_caches
from repro.core.events import CollectiveOp, Shape
from repro.core.topology import MeshTopology
from repro.core.views import CommView

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

KINDS = ("all-reduce", "all-gather", "reduce-scatter",
         "collective-broadcast", "all-to-all", "collective-permute")
ALGS = ("ring", "tree", "hierarchical")

MESHES = {
    "1pod": MeshTopology(axis_names=("data", "model"), axis_sizes=(4, 2)),
    "2pod": MeshTopology(axis_names=("pod", "data", "model"),
                         axis_sizes=(2, 4, 2)),
    "4pod": MeshTopology(axis_names=("pod", "data", "model"),
                         axis_sizes=(4, 4, 2)),
}


def make_stream(mesh_key: str, seed: int, num_ops: int = 6,
                skewed: bool = False) -> list[CollectiveOp]:
    """Mixed-kind op stream with repeated shapes: every op is emitted
    twice (fresh name/weight), so the dedupe path is always exercised."""
    topo = MESHES[mesh_key]
    d = int(np.prod(topo.axis_sizes))
    rng = np.random.default_rng(seed)
    protos = []
    for i in range(num_ops):
        kind = KINDS[int(rng.integers(len(KINDS)))]
        elems = int(rng.integers(1, 1 << 10))
        if kind == "collective-permute":
            perm = rng.permutation(d)
            pairs = [(int(perm[j]), int(perm[(j + 1) % d]))
                     for j in range(d)]
            protos.append(CollectiveOp(
                kind=kind, name=f"p{i}",
                result_shapes=[Shape("f32", (elems,))],
                replica_groups=[], source_target_pairs=pairs))
            continue
        gsize = int(rng.choice([s for s in (2, 4, 8, d) if s <= d]))
        devs = rng.permutation(d)
        groups = [sorted(int(x) for x in devs[k:k + gsize])
                  for k in range(0, d, gsize)]
        extra = {}
        if skewed and kind == "all-to-all":
            vec = rng.random(gsize) + 0.1
            vec[int(rng.integers(gsize))] *= 7.0
            vec = vec / vec.sum() * float(rng.integers(1 << 8, 1 << 16))
            extra["bytes_per_rank_vec"] = [float(x) for x in vec]
        protos.append(CollectiveOp(
            kind=kind, name=f"p{i}",
            result_shapes=[Shape("f32", (elems,))],
            replica_groups=groups, **extra))
    ops = []
    for rep in range(2):
        for i, p in enumerate(protos):
            ops.append(dataclasses.replace(
                p, name=f"op{rep}_{i}",
                weight=float(rng.integers(1, 17))))
    return ops


def per_op_matrix(ops, d, alg, topo):
    """The per-op oracle: decompose and place every op individually,
    per-op ``np.add.at`` in op order."""
    mat = np.zeros((d + 1, d + 1), dtype=np.float64)
    for op in ops:
        sched = decompose(op, alg, topo, warn=False)
        src, dst, val = comm_matrix.schedule_edge_arrays(sched)
        if src.size:
            keep = (src < d) & (dst < d)
            w = max(1.0, op.weight)
            np.add.at(mat, (src[keep] + 1, dst[keep] + 1), val[keep] * w)
    return mat


def per_op_seconds(ops, alg, topo):
    """The per-op oracle for :meth:`CommView.op_seconds`: each op's own
    schedule time, ``sum(time_split)`` times its execution weight."""
    out = []
    for op in ops:
        ici, dcn = decompose(op, alg, topo, warn=False).time_split(topo)
        out.append((ici + dcn) * max(1.0, op.weight))
    return out


def view_op_seconds(ops, d, alg, topo):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", HierarchicalFallbackWarning)
        return CommView(ops, d, algorithm=alg, topo=topo).op_seconds()


GRID = [(mk, alg, skewed) for mk in MESHES for alg in ALGS
        for skewed in (False, True)]


@pytest.mark.parametrize("mesh_key,alg,skewed", GRID)
class TestBitwiseParity:
    """evaluation path == per-op oracle, bit for bit, across the full
    grid."""

    def _setup(self, mesh_key, alg, skewed):
        clear_schedule_cache()
        clear_billing_caches()
        topo = MESHES[mesh_key]
        d = int(np.prod(topo.axis_sizes))
        ops = make_stream(mesh_key, seed=hash((mesh_key, alg)) % 997,
                          skewed=skewed)
        return topo, d, ops

    def test_dense_matrix(self, mesh_key, alg, skewed):
        topo, d, ops = self._setup(mesh_key, alg, skewed)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", HierarchicalFallbackWarning)
            got = comm_matrix.matrix_for_ops(ops, d, alg, topo=topo)
        assert np.array_equal(got, per_op_matrix(ops, d, alg, topo))

    def test_sparse_matrix(self, mesh_key, alg, skewed):
        topo, d, ops = self._setup(mesh_key, alg, skewed)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", HierarchicalFallbackWarning)
            sp = comm_matrix.matrix_for_ops(ops, d, alg, topo=topo,
                                            sparse=True)
        assert np.array_equal(sp.to_dense(),
                              per_op_matrix(ops, d, alg, topo))

    def test_time_split_per_op(self, mesh_key, alg, skewed):
        topo, d, ops = self._setup(mesh_key, alg, skewed)
        assert view_op_seconds(ops, d, alg, topo) == per_op_seconds(
            ops, alg, topo)

    def test_total_time_split(self, mesh_key, alg, skewed):
        topo, d, ops = self._setup(mesh_key, alg, skewed)
        got = cost_models.total_time_split(ops, topo, alg)
        ici = dcn = 0.0
        for op in ops:
            i, dd = decompose(op, alg, topo, warn=False).time_split(topo)
            w = max(1.0, op.weight)
            ici += i * w
            dcn += dd * w
        assert got == (ici, dcn)

    def test_project_links(self, mesh_key, alg, skewed):
        topo, d, ops = self._setup(mesh_key, alg, skewed)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", HierarchicalFallbackWarning)
            got = comm_matrix.project_links(
                comm_matrix.matrix_for_ops(ops, d, alg, topo=topo), topo)
        ref = comm_matrix.project_links(
            per_op_matrix(ops, d, alg, topo), topo)
        assert got.bytes_by_link == ref.bytes_by_link


class TestBillingCaches:
    """The bounded signature-keyed caches behind ``wire_bytes_*``."""

    @pytest.mark.parametrize("kind", KINDS)
    def test_cached_equals_fresh(self, kind):
        for n in (2, 4, 8):
            clear_billing_caches()
            cold_pr = cost_models.wire_bytes_per_rank(kind, 4096.0, n,
                                                      "ring")
            cold_gt = cost_models.wire_bytes_group_total(kind, 4096.0, n,
                                                         "ring")
            warm_pr = cost_models.wire_bytes_per_rank(kind, 4096.0, n,
                                                      "ring")
            warm_gt = cost_models.wire_bytes_group_total(kind, 4096.0, n,
                                                         "ring")
            assert cold_pr == warm_pr and cold_gt == warm_gt

    def test_vector_ops_do_not_contaminate_the_scalar_cache(self):
        """Interleaving vector and scalar calls with identical (kind,
        payload, n, algorithm) must each keep returning their own fresh
        value -- a vec call can never be served a scalar cache entry or
        poison one."""
        vec = np.asarray([1000.0, 10.0, 10.0, 10.0])
        clear_billing_caches()
        v1 = cost_models.wire_bytes_group_total("all-to-all",
                                                float(vec.sum()), 4,
                                                "ring", vec=vec)
        s1 = cost_models.wire_bytes_group_total("all-to-all",
                                                float(vec.sum()), 4, "ring")
        v2 = cost_models.wire_bytes_group_total("all-to-all",
                                                float(vec.sum()), 4,
                                                "ring", vec=vec)
        clear_billing_caches()
        assert v1 == v2 == cost_models.wire_bytes_group_total(
            "all-to-all", float(vec.sum()), 4, "ring", vec=vec)
        assert s1 == cost_models.wire_bytes_group_total(
            "all-to-all", float(vec.sum()), 4, "ring")


class TestBoundedCache:
    def test_eviction_order_is_lru(self):
        c = BoundedCache(maxsize=2)
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1            # refreshes "a"
        c.put("c", 3)                     # evicts "b", the stalest
        assert "b" not in c and "a" in c and "c" in c
        assert len(c) == 2

    def test_hit_miss_counters_and_clear(self):
        c = BoundedCache(maxsize=4)
        assert c.get("x") is None and c.misses == 1
        c.put("x", 7)
        assert c.get("x") == 7 and c.hits == 1
        c.clear()
        assert len(c) == 0 and c.hits == 0 and c.misses == 0


class TestSignature:
    def test_equal_device_count_topologies_do_not_collide(self):
        """(4,2) and (2,4) meshes have 8 devices each but different ring
        neighbourhoods -- their signatures must differ."""
        t42 = MeshTopology(axis_names=("data", "model"), axis_sizes=(4, 2))
        t24 = MeshTopology(axis_names=("data", "model"), axis_sizes=(2, 4))
        assert topo_signature(t42) != topo_signature(t24)
        op = CollectiveOp(kind="all-reduce", name="ar",
                          result_shapes=[Shape("f32", (64,))],
                          replica_groups=[list(range(8))])
        assert op_signature(op, "ring", t42) != op_signature(op, "ring", t24)

    def test_weight_and_name_not_in_signature(self):
        op = CollectiveOp(kind="all-reduce", name="a", weight=1.0,
                          result_shapes=[Shape("f32", (64,))],
                          replica_groups=[list(range(8))])
        twin = dataclasses.replace(op, name="b", weight=64.0)
        assert op_signature(op) == op_signature(twin)

    def test_algorithm_in_signature(self):
        op = CollectiveOp(kind="all-reduce", name="a",
                          result_shapes=[Shape("f32", (64,))],
                          replica_groups=[list(range(8))])
        assert op_signature(op, "ring") != op_signature(op, "tree")

    def test_byte_vector_in_signature(self):
        base = dict(kind="all-to-all", name="a",
                    result_shapes=[Shape("f32", (1,))],
                    replica_groups=[[0, 1, 2, 3]])
        flat = CollectiveOp(bytes_per_rank_vec=[4.0] * 4, **base)
        skew = CollectiveOp(bytes_per_rank_vec=[13.0, 1.0, 1.0, 1.0],
                            **base)
        assert op_signature(flat) != op_signature(skew)

    def test_cached_decompose_shares_schedule_objects(self):
        clear_schedule_cache()
        topo = MESHES["1pod"]
        op = CollectiveOp(kind="all-gather", name="a",
                          result_shapes=[Shape("f32", (64,))],
                          replica_groups=[list(range(8))])
        twin = dataclasses.replace(op, name="b", weight=3.0)
        s1 = cached_decompose(op, "ring", topo, warn=False)
        s2 = cached_decompose(twin, "ring", topo, warn=False)
        assert s1 is s2
        scheds = schedules_for_ops([op, twin, op], "ring", topo)
        assert scheds[0] is scheds[1] is scheds[2]
        assert schedule_cache().hits >= 1

    def test_fallback_warning_replays_through_cache_hits(self):
        """A hierarchical refusal recorded at miss time must re-warn on a
        later cache hit (after the once-per-session dedup is reset)."""
        clear_schedule_cache()
        topo = MESHES["2pod"]
        # a cross-pod group that is NOT pod-aligned: 3 devices spanning
        # pods -> the hierarchical predicate refuses and falls back
        op = CollectiveOp(kind="all-reduce", name="odd",
                          result_shapes=[Shape("f32", (64,))],
                          replica_groups=[[0, 1, 8]])
        reset_fallback_warnings()
        with pytest.warns(HierarchicalFallbackWarning):
            cached_decompose(op, "hierarchical", topo)     # miss: records
        reset_fallback_warnings()
        with pytest.warns(HierarchicalFallbackWarning):
            cached_decompose(op, "hierarchical", topo)     # hit: replays
        reset_fallback_warnings()


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(mesh_key=st.sampled_from(sorted(MESHES)),
           alg=st.sampled_from(ALGS),
           seed=st.integers(0, 2**16),
           skewed=st.booleans())
    def test_hypothesis_bitwise_matrix_and_timing(mesh_key, alg, seed,
                                                  skewed):
        clear_schedule_cache()
        topo = MESHES[mesh_key]
        d = int(np.prod(topo.axis_sizes))
        ops = make_stream(mesh_key, seed=seed, skewed=skewed)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", HierarchicalFallbackWarning)
            got = comm_matrix.matrix_for_ops(ops, d, alg, topo=topo)
            sp = comm_matrix.matrix_for_ops(ops, d, alg, topo=topo,
                                            sparse=True)
        ref = per_op_matrix(ops, d, alg, topo)
        assert np.array_equal(got, ref)
        assert np.array_equal(sp.to_dense(), ref)
        assert view_op_seconds(ops, d, alg, topo) == per_op_seconds(
            ops, alg, topo)

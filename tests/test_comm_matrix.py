"""Communication-matrix invariants (paper Figs. 2-3), property-based.

``hypothesis`` is an optional [test] extra: without it this module degrades
to a skip instead of a collection error (the tier-1 suite must stay green on
a bare interpreter).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import comm_matrix
from repro.core.decompose import cached_decompose
from repro.core.events import CollectiveOp, HostTransfer, Shape
from oracles import matrix_for_ops_reference


def mk_op(kind, dims, groups, dtype="f32", pairs=None):
    return CollectiveOp(kind=kind, name="t", result_shapes=[Shape(dtype, dims)],
                        replica_groups=groups,
                        source_target_pairs=pairs or [])


class TestMatrixInvariants:
    @given(n=st.sampled_from([2, 4, 8]), elems=st.integers(1, 4096))
    @settings(max_examples=60, deadline=None)
    def test_matrix_sum_equals_wire_total_ring(self, n, elems):
        op = mk_op("all-reduce", (elems,), [list(range(n))])
        mat = comm_matrix.matrix_for_ops([op], n)
        assert mat.sum() == pytest.approx(op.wire_bytes_total("ring"))

    @given(n=st.sampled_from([2, 4, 8]), elems=st.integers(1, 1024))
    @settings(max_examples=40, deadline=None)
    def test_ring_traffic_only_on_ring_edges(self, n, elems):
        """Bidirectional ring: both neighbours get half, nothing else."""
        op = mk_op("all-gather", (elems * n,), [list(range(n))])
        mat = comm_matrix.matrix_for_ops([op], n)[1:, 1:]
        for i in range(n):
            for j in range(n):
                if j in ((i + 1) % n, (i - 1) % n):
                    assert mat[i, j] > 0
                    assert mat[i, j] == pytest.approx(mat[i, (i + 1) % n])
                else:
                    assert mat[i, j] == 0

    def test_host_row_and_column(self):
        mat = np.zeros((5, 5))
        comm_matrix.add_host_transfers(mat, [
            HostTransfer("h2d", 0, 100), HostTransfer("h2d", 3, 50),
            HostTransfer("d2h", 1, 25)])
        assert mat[0, 1] == 100 and mat[0, 4] == 50 and mat[2, 0] == 25
        assert mat[1:, 1:].sum() == 0

    def test_permute_matrix_matches_pairs(self):
        op = mk_op("collective-permute", (8,), [],
                   pairs=[(0, 1), (1, 2), (2, 0)])
        mat = comm_matrix.matrix_for_ops([op], 4)
        nb = 8 * 4
        assert mat[1, 2] == nb and mat[2, 3] == nb and mat[3, 1] == nb
        assert mat.sum() == 3 * nb

    def test_all_to_all_uniform(self):
        n, elems = 4, 64
        op = mk_op("all-to-all", (elems,), [list(range(n))])
        mat = comm_matrix.matrix_for_ops([op], n)[1:, 1:]
        off_diag = mat[~np.eye(n, dtype=bool)]
        assert np.all(off_diag == off_diag[0]) and off_diag[0] > 0
        assert np.all(np.diag(mat) == 0)

    def test_multiple_groups_disjoint(self):
        op = mk_op("all-reduce", (16,), [[0, 1], [2, 3]])
        mat = comm_matrix.matrix_for_ops([op], 4)[1:, 1:]
        # no traffic between groups
        assert mat[0, 2] == mat[0, 3] == mat[1, 2] == mat[1, 3] == 0
        assert mat[2, 0] == mat[3, 0] == mat[2, 1] == mat[3, 1] == 0

    def test_per_primitive_split_sums_to_total(self):
        ops = [mk_op("all-reduce", (64,), [[0, 1, 2, 3]]),
               mk_op("all-gather", (64,), [[0, 1, 2, 3]])]
        total = comm_matrix.matrix_for_ops(ops, 4)
        per = comm_matrix.per_primitive_matrices(ops, 4)
        assert set(per) == {"all-reduce", "all-gather"}
        np.testing.assert_allclose(sum(per.values()), total)

    def test_tree_algorithm_uses_tree_edges(self):
        op = mk_op("all-reduce", (64,), [[0, 1, 2, 3, 4, 5, 6, 7]])
        ring = comm_matrix.matrix_for_ops([op], 8, algorithm="ring")
        tree = comm_matrix.matrix_for_ops([op], 8, algorithm="tree")
        assert not np.allclose(ring, tree)
        # tree root (rank 0) exchanges with children 1,2 only
        assert tree[1, 2] > 0 and tree[1, 3] > 0 and tree[1, 4] == 0


KINDS = ("all-reduce", "all-gather", "reduce-scatter",
         "collective-broadcast", "all-to-all", "collective-permute")


@st.composite
def op_streams(draw):
    """Randomized op streams over 8 devices: mixed kinds, group partitions
    of every dividing size, permute pair schedules, loop-trip weights."""
    num_devices = 8
    ops = []
    for _ in range(draw(st.integers(1, 8))):
        kind = draw(st.sampled_from(KINDS))
        elems = draw(st.integers(1, 2048))
        weight = float(draw(st.integers(1, 64)))
        if kind == "collective-permute":
            perm = draw(st.permutations(range(num_devices)))
            k = draw(st.integers(1, num_devices))
            pairs = [(perm[i], perm[(i + 1) % num_devices])
                     for i in range(k)]
            op = mk_op(kind, (elems,), [], pairs=pairs)
        else:
            gsize = draw(st.sampled_from([2, 4, 8]))
            devs = draw(st.permutations(range(num_devices)))
            groups = [sorted(devs[i:i + gsize])
                      for i in range(0, num_devices, gsize)]
            op = mk_op(kind, (elems,), groups)
        op.weight = weight
        ops.append(op)
    return ops


class TestVectorizedBuilder:
    """The COO-batched ``matrix_for_ops`` (rendered from decomposition
    schedules) must match the legacy per-op/per-edge reference loop on
    randomized op streams wherever the legacy placement is still the
    contract: no topology, or single-axis replica groups.  (Multi-axis
    single-pod groups intentionally diverge -- per-axis ring phases --
    pinned in tests/test_decompose.py.)"""

    @given(ops=op_streams(),
           algorithm=st.sampled_from(["ring", "tree", "hierarchical"]))
    @settings(max_examples=80, deadline=None)
    def test_coo_matches_loop(self, ops, algorithm):
        import warnings
        from repro.core.topology import MeshTopology
        # single-axis pods: every intra-pod group lies along ONE torus
        # axis, so per-axis decomposition never applies and the schedule
        # path must reproduce the legacy loop byte-for-byte
        topo = MeshTopology(axis_names=("pod", "data"), axis_sizes=(2, 4))
        for t in (None, topo):
            check_ops = ops
            if t is not None and algorithm == "hierarchical":
                # hierarchical a2a / cross-pod permute on a multi-pod
                # topology now genuinely decompose (intra-pod a2a +
                # pod-leader DCN exchange; leader relay); the legacy loop
                # keeps the flat placement -- the new paths' conservation
                # laws are pinned in test_decompose /
                # test_link_consistency instead
                check_ops = [op for op in ops if op.kind not in
                             ("all-to-all", "ragged-all-to-all",
                              "collective-permute")]
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                vec = comm_matrix.matrix_for_ops(check_ops, 8, algorithm,
                                                 topo=t)
                ref = matrix_for_ops_reference(
                    check_ops, 8, algorithm, topo=t)
            np.testing.assert_allclose(vec, ref, rtol=1e-12)

    @given(ops=op_streams(),
           algorithm=st.sampled_from(["ring", "tree", "hierarchical"]))
    @settings(max_examples=30, deadline=None)
    def test_edge_arrays_match_edge_tuples(self, ops, algorithm):
        """Each schedule phase's edge arrays, summed by source, equal the
        phase's own per-device send bytes (``CommPhase.send_bytes``) --
        including multi-axis per-axis placements and the pod phases."""
        import warnings
        from repro.core.topology import MeshTopology
        topo = MeshTopology(axis_names=("pod", "data", "model"),
                            axis_sizes=(2, 2, 2))
        for t in (None, topo):
            for op in ops:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    sched = cached_decompose(op, algorithm, t)
                for ph in sched.phases:
                    src, _, val = comm_matrix._phase_edge_arrays(ph)
                    agg: dict = {}
                    for s, b in zip(src.tolist(), val.tolist()):
                        agg[s] = agg.get(s, 0.0) + b
                    want = {d: b for d, b in ph.send_bytes().items() if b}
                    assert set(agg) == set(want)
                    for d in want:
                        assert agg[d] == pytest.approx(want[d])

    def test_flush_batching_boundary(self):
        """A long stream of small ops followed by one op with tens of
        thousands of edges (a 192-wide all-to-all) accumulates like the
        legacy per-edge loop."""
        d = 192
        big = mk_op("all-to-all", (4096,), [list(range(d))])
        ops = [mk_op("all-reduce", (256,), [[0, 1, 2, 3]])] * 5000 + [big]
        vec = comm_matrix.matrix_for_ops(ops, d)
        ref = matrix_for_ops_reference(ops, d)
        np.testing.assert_allclose(vec, ref)


class TestReporter:
    def test_heatmap_renders(self):
        from repro.core import reporter
        mat = np.random.default_rng(0).random((9, 9)) * 1e9
        txt = reporter.ascii_heatmap(mat, title="test")
        assert "test" in txt and len(txt.splitlines()) >= 10

    def test_heatmap_coarsens_large(self):
        from repro.core import reporter
        mat = np.ones((257, 257))
        txt = reporter.ascii_heatmap(mat, max_devices=32)
        assert "blocks of" in txt

    def test_csv(self):
        from repro.core import reporter
        mat = np.arange(9).reshape(3, 3).astype(float)
        csv = reporter.matrix_to_csv(mat)
        assert csv.splitlines()[0] == ",host,gpu0,gpu1"
        assert csv.splitlines()[1] == "host,0,1,2"

    def test_human_bytes(self):
        from repro.core.reporter import human_bytes
        assert human_bytes(0) == "0 B"
        assert human_bytes(1024) == "1.00 KiB"
        assert human_bytes(3.5 * 2**30) == "3.50 GiB"

"""The trace reduction, on a trace recorded on a TPU v5e (one serve of
granite-3-2b at batch 4 x 3,584 prompt tokens, 8 greedy tokens: one
prefill and 7 decode steps) and on intervals made by hand."""
import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "prefill_b4.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_dir(os.path.dirname(TRACE), window_s=1.5)


@pytest.fixture(scope="module")
def raw_ops():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TRACE)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {line.name: list(line.events) for line in plane.lines}
    return lines


def test_union_and_self_time_by_hand():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]
    ops = [("%while = x", 0, 10), ("%fusion.1 = y", 1, 4),
           ("%fusion.2 = z", 5, 6), ("%fusion.1 = y", 12, 13)]
    assert trace_reduce._self_times(ops) == {"while": 6, "fusion.1": 4,
                                             "fusion.2": 1}


def test_one_chip_and_its_programs(reduced):
    assert reduced["chips"] == 1
    steps = {n: c for n, (c, _) in reduced["modules"].items()
             if n.startswith("jit_step(")}
    assert sorted(steps.values()) == [1, 7]     # prefill, 7 decode steps
    decode = trace_reduce.module_time(reduced, 7, "jit_step(")
    prefill = trace_reduce.module_time(reduced, 1, "jit_step(")
    assert decode == pytest.approx(0.086806, rel=1e-3)
    assert prefill == pytest.approx(1.350419, rel=1e-3)


def test_kernel_time_is_the_sum_of_its_events(reduced, raw_ops):
    direct = sum(e.duration_ns for e in raw_ops["XLA Ops"]
                 if e.name.startswith("%flash_attention")) * 1e-9
    assert direct > 0.8
    assert trace_reduce.ops_time(reduced, "flash_attention") == (
        pytest.approx(direct, rel=1e-9))


def test_busy_is_within_the_modules_span(reduced, raw_ops):
    mods = raw_ops["XLA Modules"]
    span = (mods[-1].end_ns - mods[0].start_ns) * 1e-9
    in_modules = sum(e.duration_ns for e in mods) * 1e-9
    assert 0.95 * in_modules <= reduced["busy_s"] <= span
    assert reduced["window_s"] == 1.5


def test_breakdown_is_bounded_and_ranked(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert ops[0][0].startswith("flash_attention")
    assert all(s >= 0 for _, s in gaps)

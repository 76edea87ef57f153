"""serve.cache_donated_share over a synthetic span log: a warm-up batch
and a window of two, two decode steps each."""
import pytest

from repro.core import spans
from test_spanlog import Log, metric, run_of

READER = "serve.cache_donated_share"


def batches(log, donated):
    """Three batches from 0 ms, 200 ms and 400 ms; ``donated(batch,
    step)`` says whether that decode step counted its cache consumed."""
    for b, t in enumerate((0, 200, 400)):
        g = spans.new_group()
        log.add("serve.prefill", t, t + 10, g)
        for i in range(2):
            counts = {"serve.decode_steps": 1}
            if donated(b, i):
                counts["serve.cache_donated"] = 1
            s = t + 10 + 4 * i
            log.add("serve.decode", s, s + 3, g, counts=counts)
            log.add("serve.sample", s + 3, s + 4, g)


@pytest.mark.parametrize("donated,want", [
    (lambda b, i: True, 100.0),
    # the window's last step kept its cache; the warm-up's do not count
    (lambda b, i: (b, i) != (2, 1), 75.0),
    (lambda b, i: b == 0 or i == 0, 50.0),
])
def test_share_of_the_window_steps(monkeypatch, donated, want):
    batches(Log(monkeypatch), donated)
    assert metric(READER).read(run_of()) == pytest.approx(want, rel=1e-12)


def test_nothing_where_the_counter_is_absent(monkeypatch):
    batches(Log(monkeypatch), lambda b, i: False)
    assert metric(READER).read(run_of()) is None


def test_nothing_without_a_window(monkeypatch):
    batches(Log(monkeypatch), lambda b, i: True)
    assert metric(READER).read(run_of(reports=0, cycles=0,
                                      requests=0)) is None


def test_a_program_without_the_log(monkeypatch):
    import sys

    import repro.core
    batches(Log(monkeypatch), lambda b, i: True)
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert metric(READER).read(run_of()) is None

"""The reduction from a profiler trace to busy time, idle share, time by
program, exposed collectives and idle gaps; on hand-made intervals and on
a small trace recorded on a TPU v5e (the paged decode kernel and a bf16
matmul, three times, inside host spans)."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

CHIP_TRACE = Path(__file__).parent / "data" / "v5e_paged_attention.xplane.pb"


def test_interval_algebra():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.length([(0, 2.5), (3, 4)]) == 3.5
    assert tr.subtract([(0, 10)], [(1, 2), (5, 6), (9, 12)]) == [
        (0, 1), (2, 5), (6, 9)]
    assert tr.subtract([(0, 1), (4, 5)], [(0.5, 4.5)]) == [(0, 0.5), (4.5, 5)]


def test_collective_exposed_and_idle():
    dev = tr.Device("/device:TPU:0", ops=[
        ("fusion.1", 0.0, 2.0), ("all-reduce.3", 1.0, 4.0),
        ("fusion.2", 5.0, 6.0), ("all-gather-start.1", 5.5, 7.0)])
    s = tr.Summary([dev], spans=[("step", 0.0, 10.0), ("decode", 6.5, 9.0)],
                   window=(0.0, 10.0))
    assert dev.collective_exposed() == pytest.approx(2.0 + 1.0)
    assert s.busy_s() == pytest.approx(6.0)
    assert s.idle_share() == pytest.approx(0.4)
    assert s.collective_exposed_share() == pytest.approx(0.3)
    gaps = dict(map(tuple, s.idle_gaps()))
    assert gaps == pytest.approx({"step": 1.0, "decode": 3.0})


def test_a_trace_recorded_on_the_chip():
    s = tr.reduce_file(str(CHIP_TRACE))
    assert len(s.devices) == 1 and s.devices[0].name == "/device:TPU:0"
    win = [x for x in s.spans if x[0] == "window"]
    assert len(win) == 1 and len([x for x in s.spans if x[0] == "step"]) == 3
    s.window = win[0][1:]
    kernel = s.matching("paged_attention")
    mm = s.matching("lambda")
    assert kernel and mm and kernel < mm
    assert 0.0 < s.busy_s() < s.window_s
    assert 0.0 < s.idle_share() < 1.0
    assert s.collective_exposed_share() == 0.0
    assert s.matching("no-such-program") is None
    top = s.top("modules")
    assert {n for n, _ in top} == {"jit_paged_attention", "jit__lambda"}
    assert top[0][1] >= top[-1][1] > 0
    assert any(n.startswith("paged_attention") for n, _ in s.top("ops"))
    assert sum(v for _, v in s.idle_gaps()) == pytest.approx(
        s.window_s - s.busy_s())

"""The reduction from trace intervals to shares, on hand-made intervals
with known answers and on a short trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from benchmarks.chip.trace import Intervals, Op, Reduced, Span, union

SAMPLE = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / \
    "testdata" / "trace_sample.json.gz"


def _toy():
    """Window [0, 10] s; device 0 busy 1-3 (two overlapping ops) and 6-7;
    device 1 busy 0-5.  The host stepped from 0 to 6.2 and from 6.3 to
    9.5, admitting from 3 to 6 and decoding from 6.5 to 8."""
    ops = {0: [Op("fusion.1", 1.0, 2.5), Op("decode_attention.3", 2.0, 3.0),
               Op("fusion.2", 6.0, 7.0)],
           1: [Op("fusion.1", -1.0, 5.0)]}
    spans = [Span("bench.traced", 0.0, 10.0), Span("bench.step", 0.0, 6.2),
             Span("bench.step", 6.3, 9.5),
             Span("bench.admit", 3.0, 6.0), Span("bench.decode", 6.5, 8.0)]
    return Intervals(ops, spans)


def test_union_merges_overlaps():
    assert union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)]) == [(1, 2.5), (3, 5)]


def test_busy_and_idle_share():
    red = Reduced.for_span(_toy(), "bench.traced")
    assert red.window_s == 10.0
    assert red.busy_s(0) == pytest.approx(3.0)
    assert red.busy_s(1) == pytest.approx(5.0)      # clipped at the window
    assert red.idle_share() == pytest.approx(1 - 4.0 / 10)
    assert red.idle_share([0]) == pytest.approx(0.7)


def test_kernel_time_and_top_ops():
    red = Reduced.for_span(_toy(), "bench.traced", devices=[0])
    assert red.op_time_s("decode_attention") == pytest.approx(1.0)
    top = red.top_ops(2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(1.5)


def test_gaps_are_labelled_by_the_innermost_span():
    red = Reduced.for_span(_toy(), "bench.traced", devices=[0])
    gaps = dict(red.idle_gaps(0))
    # idle: 0-1 (step), 3-6 (admit), 7-10 (midpoint 8.5: step)
    assert gaps["admit"] == pytest.approx(3.0)
    assert gaps["step"] == pytest.approx(4.0)
    assert red.span_time_s("bench.admit") == pytest.approx(3.0)


def test_recorded_trace_against_a_sweep():
    """On one engine step traced on the chip, busy time by interval union
    agrees with a sweep over the operations' start and end points."""
    iv = Intervals.load(str(SAMPLE))
    red = Reduced.for_span(iv, "bench.traced")
    assert red.devices == [0] and 0.5 < red.window_s < 2.0
    edges = sorted([(max(o.start, red.t0), 1) for o in iv.ops[0]]
                   + [(min(o.end, red.t1), -1) for o in iv.ops[0]])
    busy, depth, since = 0.0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0 and since is not None:
            busy += max(0.0, t - since)
            since = None
    assert red.busy_s(0) == pytest.approx(busy, rel=1e-9)
    assert 0.9 < red.idle_share() < 1.0
    # one decode step: the attention kernel ran once, for a few ms
    assert 1e-3 < red.op_time_s("decode_attention") < 0.05
    labels = dict(red.idle_gaps(0))
    assert set(labels) <= {"step", "admit", "decode", "retire", "client"}
    assert sum(labels.values()) == pytest.approx(red.window_s - busy)

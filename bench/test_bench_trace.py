"""The trace reduction, on a trace recorded on one v5e chip and on
hand-made events.  The recorded one (``bench/fixtures``) is one batch of
the card cell at its full size, 271 systems x 10^6 trials, cut down to
what the reduction reads: the device's ``XLA Ops`` and ``XLA Modules``
lines and the benchmark's host spans."""
import os

import pytest

from harness import cells, trace

BENCH = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(BENCH, "fixtures", "score_small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(FIXTURE)


def test_recorded_trace_has_one_chip_and_its_spans(recorded):
    assert recorded.devices == [0]
    assert recorded.n_batches >= 1
    assert recorded.window_s > 0
    names = {s[0] for s in recorded.spans}
    assert {"bench.batch", "bench.score_systems"} <= names


def test_busy_and_idle_partition_the_window(recorded):
    busy = recorded.busy_s(0)
    idle = sum(e - s for s, e in recorded.idle_gaps(0)) * 1e-9
    assert 0 < busy < recorded.window_s
    assert busy + idle == pytest.approx(recorded.window_s, rel=1e-9)
    gaps = recorded.top_gaps()
    assert sum(v for _, v in gaps) == pytest.approx(idle, rel=1e-9)
    assert {n for n, _ in gaps} <= {"bench.batch", "bench.score_systems",
                                    "outside_spans"}


def test_per_layer_readers_on_the_recorded_trace(recorded):
    run = {"batches": recorded.n_batches}
    idle = cells.load_reader("idle_share.score")(recorded, run)
    assert idle == pytest.approx(60.7506, abs=1e-3)    # as recorded
    assert idle == pytest.approx(
        100 * (1 - recorded.busy_s(0) / recorded.window_s))
    stream = cells.load_reader("stream_device_ms.score")(recorded, run)
    assert 0 < stream <= 1e3 * recorded.window_s / recorded.n_batches
    assert cells.load_reader("merge_ms.score")(recorded, run) is None


def test_top_ops_leave_loops_out(recorded):
    ops = recorded.top_ops()
    assert 0 < len(ops) <= 10
    assert all(" while" not in name for name, _ in ops)
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert sum(v for _, v in ops) <= recorded.busy_s(0) * 1.0001


def _hand_made():
    t = trace.Trace()
    t.spans = [("bench.batch", 0.0, 100.0), ("bench.score_systems", 0.0,
                                             90.0)]
    t.ops = {0: [("%a = f32[2]{0} fusion(x)", 10.0, 30.0),
                 ("%b = f32[2]{0} fusion(x)", 20.0, 40.0),
                 ("%w = (f32[2]{0}) while(x)", 10.0, 40.0),
                 ("%c = f32[2]{0} all-reduce(x)", 95.0, 120.0)],
             1: [("%a = f32[2]{0} fusion(x)", 0.0, 50.0)]}
    t.modules = {0: [("jit__stream(1)", 10.0, 40.0)],
                 1: [("jit__stream(1)", 0.0, 50.0)]}
    return t


def test_union_clips_to_the_window_and_merges_overlaps():
    t = _hand_made()
    assert t.busy_intervals(0) == [(10.0, 40.0), (95.0, 100.0)]
    assert t.idle_gaps(0) == [(0.0, 10.0), (40.0, 95.0)]
    assert t.mean_busy_s() == pytest.approx((35.0 + 50.0) / 2 * 1e-9)


def test_gaps_go_to_the_innermost_open_span():
    t = _hand_made()
    assert t.span_at(50.0) == "bench.score_systems"
    assert t.span_at(95.0) == "bench.batch"
    assert t.span_at(150.0) == "outside_spans"


def test_time_by_name_averages_over_devices():
    t = _hand_made()
    assert t.time_s("XLA Modules", "_stream") == pytest.approx(40e-9)
    assert t.time_s("XLA Ops", "all-reduce") == pytest.approx(2.5e-9)
    assert trace.short_op_name("%a = f32[2]{0} fusion(x)") == "%a fusion f32[2]"

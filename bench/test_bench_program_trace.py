"""The program's spans and device scopes (``harness/program_trace.py``) and
the six readers built on them, on two traces recorded on one v5e chip and
on hand-made events.

``score_small.xplane.pb`` is a batch of a program without spans: the new
readers read nothing there, and the readers that were there read what
they read before.  ``score_spans.xplane.pb`` is one batch of the card
cell at its full size with the spans in the program, cut down like the
first to what the reductions read: the device's ``XLA Ops`` and ``XLA
Modules`` lines, with each op's ``tf_op`` name stack, and the host's
``bench.*`` and ``repro.*`` spans and program launch events."""
import os

import pytest

from harness import cells, program_trace, trace

BENCH = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(BENCH, "fixtures")
NEW = ("table_build_ms.score", "table_launches.score",
       "frontier_host_ms.score", "sample_device_ms.score",
       "decide_device_ms.score", "sketch_device_ms.score")


def _read(tr, name):
    return cells.load_reader(name)(tr, {"batches": tr.n_batches})


@pytest.fixture(scope="module")
def unspanned():
    return program_trace.load_both(os.path.join(FIXTURES,
                                                "score_small.xplane.pb"))


def test_a_trace_without_spans_reads_as_before(unspanned):
    assert unspanned.program.spans == []
    assert all(sc is None for ops in unspanned.program.ops.values()
               for *_, sc in ops)
    assert _read(unspanned, "idle_share.score") == pytest.approx(
        60.750589, abs=1e-5)
    assert _read(unspanned, "stream_device_ms.score") == pytest.approx(
        947.345475, abs=1e-5)
    assert _read(unspanned, "merge_ms.score") is None
    for name in NEW:
        assert _read(unspanned, name) is None, name
    assert program_trace.top_gaps(unspanned) == unspanned.top_gaps()


@pytest.fixture(scope="module")
def spanned():
    return program_trace.load_both(os.path.join(FIXTURES,
                                                "score_spans.xplane.pb"))


def test_the_recorded_batch_splits_by_layer(spanned):
    assert spanned.devices == [0] and spanned.n_batches == 1
    got = {name: _read(spanned, name) for name in NEW}
    assert got == pytest.approx({                       # as recorded
        "table_build_ms.score": 978.776063,
        "table_launches.score": 1741,
        "frontier_host_ms.score": 7.434079,
        "sample_device_ms.score": 15.851722,
        "decide_device_ms.score": 306.90048,
        "sketch_device_ms.score": 610.326356}, abs=1e-5)
    assert _read(spanned, "idle_share.score") == pytest.approx(
        51.546515, abs=1e-5)
    stream = _read(spanned, "stream_device_ms.score")
    assert stream == pytest.approx(947.414769, abs=1e-5)
    scoped = sum(got[f"{sc}_device_ms.score"]
                 for sc in ("sample", "decide", "sketch"))
    assert 0.95 * stream <= scoped <= stream


def test_program_spans_nest_inside_the_benchmark_span(spanned):
    (_, s0, e0), = [s for s in spanned.spans
                    if s[0] == "bench.score_systems"]
    names = {n for n, _, _ in spanned.program.spans}
    assert names == {"repro.score", "repro.score.table",
                     "repro.score.masks", "repro.stream.fast_path",
                     "repro.stream.race", "repro.score.readback",
                     "repro.score.frontier"}
    assert all(s0 <= s and e <= e0 for _, s, e in spanned.program.spans)


def test_idle_time_goes_to_the_program_steps(spanned):
    gaps = program_trace.top_gaps(spanned)
    idle = sum(v for _, v in gaps)
    assert idle == pytest.approx(
        sum(e - s for s, e in spanned.idle_gaps(0)) * 1e-9)
    assert sum(v for n, v in gaps if n.startswith("repro.")) >= 0.9 * idle
    assert gaps[0][0] == "repro.score.table"
    # the benchmark's own attribution is what it was
    assert [n for n, _ in spanned.top_gaps()] == ["bench.score_systems"]


def _hand_made():
    t = trace.Trace()
    t.spans = [("bench.batch", 0.0, 100.0),
               ("bench.score_systems", 0.0, 90.0)]
    t.modules = {0: [("jit_broadcast_in_dim(1)", 5.0, 6.0),
                     ("jit_broadcast_in_dim(1)", 12.0, 13.0),
                     ("jit__stream(2)", 30.0, 80.0),
                     ("jit_concatenate(3)", 200.0, 201.0)]}
    t.ops = {0: [("%a = f32[2]{0} fusion(x)", 5.0, 6.0),
                 ("%b = f32[2]{0} fusion(x)", 12.0, 13.0),
                 ("%w = (f32[2]{0}) while(x)", 30.0, 80.0),
                 ("%c = f32[2]{0} sort(x)", 30.0, 50.0),
                 ("%d = s32[8]{0} fusion(x)", 50.0, 80.0)]}
    t.program = program_trace.ProgramTrace(
        spans=[("repro.score", 1.0, 89.0),
               ("repro.score.table", 2.0, 20.0),
               ("repro.score.masks", 2.0, 10.0),
               ("repro.score.frontier", 84.0, 88.0)],
        launches=[1.5, 4.0, 11.0, 19.9, 20.0, 25.0],
        ops={0: [(n, s, e, sc) for (n, s, e), sc in zip(
            t.ops[0], [None, None, "sketch", "decide", "sketch"])]})
    return t


def test_host_spans_per_batch_and_the_programs_they_launch():
    t = _hand_made()
    assert program_trace.span_ms_per_batch(t, "repro.score.table") == \
        pytest.approx(18e-6)
    assert program_trace.span_ms_per_batch(t, "repro.stream.race") is None
    # launched in [2, 20): 4.0, 11.0, 19.9; in [2, 10): 4.0
    assert program_trace.launches_in(t, "repro.score.table") == 3
    assert program_trace.launches_in(t, "repro.score.masks") == 1
    assert program_trace.launches_in(t, "repro.stream.race") is None


def test_scope_time_leaves_loops_out():
    t = _hand_made()
    assert program_trace.scope_ms_per_batch(t, "sketch") == \
        pytest.approx(30e-6)
    assert program_trace.scope_ms_per_batch(t, "decide") == \
        pytest.approx(20e-6)
    assert program_trace.scope_ms_per_batch(t, "sample") is None


def test_idle_gaps_go_to_the_innermost_span_of_either_set():
    t = _hand_made()
    gaps = dict(program_trace.top_gaps(t))
    # idle 0-5 and 6-12 (midpoints in masks), 13-30 (in repro.score, after
    # the table), 80-100 (midpoint 90: the benchmark's own span)
    assert gaps == pytest.approx({"repro.score.masks": 11e-9,
                                  "repro.score": 17e-9,
                                  "bench.score_systems": 20e-9})
    assert sum(gaps.values()) == pytest.approx(
        sum(e - s for s, e in t.idle_gaps(0)) * 1e-9)


def test_scope_of_reads_the_innermost_layer():
    stack = "jit(_stream)/while/body/closed_call/repro.decide/jit(sort)/sort:"
    assert program_trace.scope_of(stack) == "decide"
    assert program_trace.scope_of("a/repro.decide/b/repro.sample/c") == \
        "sample"
    assert program_trace.scope_of("jit(f)/while/repro.sketch:") == "sketch"
    assert program_trace.scope_of("jit(f)/repro.decider/x") is None
    assert program_trace.scope_of(None) is None


def _field(num, value):
    """One protobuf field: a varint for an int, length-delimited else."""
    def varint(x):
        out = bytearray()
        while True:
            out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
            x >>= 7
            if not x:
                return bytes(out)
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def test_name_stacks_come_from_the_event_metadata():
    def event_md(i, name, *stats):
        body = _field(1, i) + _field(2, name) + b"".join(
            _field(5, st) for st in stats)
        return _field(4, _field(1, i) + _field(2, body))

    def stat_md(i, name):
        return _field(5, _field(1, i) + _field(2, _field(1, i)
                                              + _field(2, name)))
    device = (_field(2, "/device:TPU:0") + stat_md(7, "tf_op")
              + stat_md(8, "jit(f)/repro.sketch/scatter-add:")
              + stat_md(9, "hlo_category")
              + event_md(1, "%sort.1 = sort()",
                         _field(1, 9) + _field(5, "sort"),
                         _field(1, 7) + _field(5, "jit(f)/repro.decide/"
                                                  "sort:"))
              + event_md(2, "%fusion.2 = fusion()",
                         _field(1, 7) + _field(7, 8))
              + event_md(3, "%copy.3 = copy()"))
    host = _field(2, "/host:CPU") + stat_md(7, "tf_op") + event_md(
        1, "repro.score", _field(1, 7) + _field(5, "jit(g)/repro.sample/"))
    space = _field(1, device) + _field(1, host)
    assert program_trace._op_stacks(space) == {
        "%sort.1 = sort()": "jit(f)/repro.decide/sort:",
        "%fusion.2 = fusion()": "jit(f)/repro.sketch/scatter-add:"}

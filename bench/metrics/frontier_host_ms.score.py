"""Host milliseconds per batch in the program's frontier step: the
``repro.score.frontier`` span of ``score_systems`` (fault tolerance, the
Pareto mask, the result)."""
from harness import program_trace


def read(trace, run):
    return program_trace.span_ms_per_batch(trace, "repro.score.frontier")

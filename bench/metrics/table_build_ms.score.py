"""Host milliseconds per batch in the program's mask-table build: the
``repro.score.table`` span of ``score_systems`` (systems to masks on the
host, then ``engine.build_mask_table``)."""
from harness import program_trace


def read(trace, run):
    return program_trace.span_ms_per_batch(trace, "repro.score.table")

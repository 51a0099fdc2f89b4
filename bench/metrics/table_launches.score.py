"""Device programs per batch launched from inside the program's mask-table
build (``repro.score.table``): one small program per array the table is
assembled from, so a count that is exact run to run."""
from harness import program_trace


def read(trace, run):
    return program_trace.launches_in(trace, "repro.score.table")

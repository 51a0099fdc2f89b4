"""Device milliseconds per batch of the operations the program runs under
``jax.named_scope("repro.sketch")`` (sketch bucketing, histogram scatters,
sums and maxima, the summary update), averaged over the cell's chips."""
from harness import program_trace


def read(trace, run):
    return program_trace.scope_ms_per_batch(trace, "sketch")

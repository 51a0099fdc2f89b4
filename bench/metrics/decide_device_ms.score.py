"""Device milliseconds per batch of the operations the program runs under
``jax.named_scope("repro.decide")`` (tallies, presorts and top-k, the
winner, quorum saturations), averaged over the cell's chips."""
from harness import program_trace


def read(trace, run):
    return program_trace.scope_ms_per_batch(trace, "decide")

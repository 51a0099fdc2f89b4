"""Device milliseconds per batch spent in the streamed driver's jitted
programs (``_stream``: chunk scan, draws, decide, sketch), found by
module name in the trace and averaged over the cell's chips."""


def read(trace, run):
    if not trace.devices or not trace.n_batches:
        return None
    s = trace.time_s("XLA Modules", r"_stream")
    return 1e3 * s / trace.n_batches if s > 0 else None

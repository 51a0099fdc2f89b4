"""Device milliseconds per batch in the cross-chip merge: the all-reduce
operations (psum of counts and sketches, pmax of maxima) of a sharded
stream, averaged over the cell's chips.  Nothing to read on one chip."""


def read(trace, run):
    if len(trace.devices) < 2 or not trace.n_batches:
        return None
    s = trace.time_s("XLA Ops", r"(?i)all-reduce|all_reduce")
    return 1e3 * s / trace.n_batches if s > 0 else None

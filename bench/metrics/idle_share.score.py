"""Device idle share of a scoring window, in percent: 1 - the union of
device-op intervals over the window, averaged over the cell's chips."""


def read(trace, run):
    if not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s() / trace.window_s)

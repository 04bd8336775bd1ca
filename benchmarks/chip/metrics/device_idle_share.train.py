"""Share of the traced training window in which no operation runs on the
device, averaged over the cell's chips, in %."""


def read(run):
    if not run.trace.ops:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_ns() / run.trace.window_ns)

"""Device: share of the traced sub-window in which no operation ran on the
device, averaged over the cell's devices, in %."""


def read(run):
    tr = run.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["mean_busy_s"] / tr["window_s"])

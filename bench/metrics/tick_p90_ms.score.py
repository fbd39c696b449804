"""Tick tail: the 90th percentile of the time from the call to
``Castor.tick`` to its return with every forecast persisted, in ms, over
the window's ticks after the profiled ones. Each reading is one boundary,
shorter than the 250 ms a host-clock timing has to span to stand as an
end-to-end latency; so it is read here, without a bound, beside the
``jobs_per_s`` that carries the mean."""
import numpy as np


def read(run):
    ticks = run.ticks[run.traced_ticks:]
    if len(ticks) < 10:
        return None
    return float(np.percentile([t["tick_s"] * 1e3 for t in ticks], 90))

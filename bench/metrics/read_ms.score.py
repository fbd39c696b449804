"""Store reads: ``store.read_many`` spans per window tick, in ms."""


def read(run):
    spans = [s for s in run.spans if s.name == "store.read_many"]
    if not spans or not run.ticks:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(run.ticks)

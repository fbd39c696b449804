"""Ingest: the benchmark's ``bench.ingest`` step (``Castor.ingest`` of
every reading stamped since the last boundary) per window tick, in ms."""


def read(run):
    if not run.ticks:
        return None
    return 1e3 * sum(t["ingest_s"] for t in run.ticks) / len(run.ticks)

"""Executor: ``exec.prepare`` spans (each bin's version lookups and
model instances, up to the call into ``fleet_score``) per window tick,
in ms."""
import host_spans


def read(run):
    return host_spans.ms_per_tick(run, "exec.prepare")

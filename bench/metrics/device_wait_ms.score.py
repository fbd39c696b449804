"""Score step: ``device.wait`` spans (the host blocked on the rollout's
result) per window tick, in ms."""
import host_spans


def read(run):
    return host_spans.ms_per_tick(run, "device.wait")

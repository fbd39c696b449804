"""Whole score step: the operations of one score tick of the real
deployments, as the configuration's ``bench/models/<reference>.py``
``score_flops`` counts them, times the traced ticks, over the traced
sub-window, as a share of the cell's chips' peak, in %. Nothing where the
model counts none."""


def read(run):
    tr = run.trace
    if not tr or not run.traced_ticks:
        return None
    flops = run.cell["model"].score_flops(run.config, run.site.n)
    if flops is None:
        return None
    flops *= run.traced_ticks
    chips = len(tr["busy_s"])
    return 100.0 * flops / tr["window_s"] / (chips * run.peaks["flops_per_s"])

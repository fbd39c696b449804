"""Whole score step: the ANN rollout's operations for the real
deployments (2 x parameters x deployments x horizon per tick) over the
traced sub-window, as a share of the cell's chips' peak, in %."""
import work


def read(run):
    tr, cfg = run.trace, run.config
    if not tr or cfg["forecaster"] != "ANNForecaster" or not run.traced_ticks:
        return None
    up = cfg["user_params"]
    n_features = int(up["target_lags"]) + 1 + int(up["weather_lags"]) + 5
    flops = work.ann_score_flops(run.site.n, n_features, int(up["hidden"]),
                                 int(cfg["hidden_layers"]),
                                 int(up["horizon"])) * run.traced_ticks
    chips = len(tr["busy_s"])
    return 100.0 * flops / tr["window_s"] / (chips * run.peaks["flops_per_s"])

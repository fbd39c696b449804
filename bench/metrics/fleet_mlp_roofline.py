"""Kernel: ``fleet_mlp``'s share of its roofline, in %.

The least time of one call is the larger of its operations over the peak
rate and its bytes (every real deployment's float32 weights and biases,
its input row and its output) over the peak HBM bandwidth; padded
instances and padded lanes earn nothing. The share is the least time of
all calls in the traced sub-window over the device time of the kernel's
events there."""
import work

#: the kernel's events in the device trace are custom calls named after
#: the op (``%_fleet_mlp.5 = ... custom-call(...)`` on a TPU v5e)
KERNEL = "fleet_mlp"


def read(run):
    tr, cfg = run.trace, run.config
    if not tr or cfg["forecaster"] != "ANNForecaster" or not run.traced_ticks:
        return None
    spent = sum(s for name, s in tr["ops_s"].items()
                if KERNEL in name and "custom-call" in name)
    if spent <= 0:
        return None
    up = cfg["user_params"]
    n_features = int(up["target_lags"]) + 1 + int(up["weather_lags"]) + 5
    shape = (run.site.n, n_features, int(up["hidden"]),
             int(cfg["hidden_layers"]))
    least = max(work.fleet_mlp_flops(*shape) / run.peaks["flops_per_s"],
                work.fleet_mlp_bytes(*shape) / run.peaks["hbm_bytes_per_s"])
    calls = run.traced_ticks * int(up["horizon"])
    return 100.0 * least * calls / spent

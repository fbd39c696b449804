"""Operations and bytes the algorithms need, counted from the shapes.

These count what the forecasting algorithm requires for the real
deployments, whatever implements it: padded instances, padded lanes and
recomputation are not credited. A later change that fuses or replaces a
kernel is held to the same counts.
"""
from __future__ import annotations

F32 = 4


def ann_sizes(n_features: int, width: int, hidden_layers: int) -> list:
    """Layer widths from input to the single output."""
    return [n_features] + [width] * hidden_layers + [1]


def ann_params(n_features: int, width: int, hidden_layers: int) -> int:
    """Weights and biases of one network."""
    s = ann_sizes(n_features, width, hidden_layers)
    return sum(a * b + b for a, b in zip(s[:-1], s[1:]))


def ann_score_flops(n: int, n_features: int, width: int, hidden_layers: int,
                    horizon: int) -> float:
    """One score tick of ``n`` deployments: a forward pass of one row per
    deployment at each horizon step, at 2 operations per parameter."""
    return 2.0 * ann_params(n_features, width, hidden_layers) * n * horizon


def ann_fit_flops(n: int, n_features: int, width: int, hidden_layers: int,
                  rows: int, epochs: int) -> float:
    """Full-batch training of ``n`` deployments: forward and backward at 6
    operations per parameter and row, every epoch."""
    return 6.0 * ann_params(n_features, width, hidden_layers) * rows \
        * epochs * n


def fleet_mlp_flops(n: int, n_features: int, width: int,
                    hidden_layers: int, rows: int = 1) -> float:
    """One call of the per-deployment MLP: ``rows`` rows through each of
    ``n`` networks."""
    return 2.0 * ann_params(n_features, width, hidden_layers) * n * rows


def fleet_mlp_bytes(n: int, n_features: int, width: int, hidden_layers: int,
                    rows: int = 1) -> float:
    """HBM bytes one call must move at least: every deployment's float32
    weights and biases once, its input rows and its outputs."""
    per = ann_params(n_features, width, hidden_layers) + rows * n_features \
        + rows
    return float(F32 * per * n)

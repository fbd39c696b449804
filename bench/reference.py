"""Plain float64 references for what the timed path persists.

Independent of the program: the inputs come from the benchmark's own
traffic (``traffic.Site``) and weather copy, and the arithmetic is written
out here from the paper's Table-1 features and the forecasters'
definitions (copied from ``chip_smoke.py``'s references and the feature
rules in ``forecast/features.py`` / ``timeseries/transforms.py``):

* hourly alignment: the mean of the readings stamped in each bin of
  ``[t0, t1)``, forward-filled inside the window, 0 before its first
  reading;
* the design: target lags 1..L, the temperature at t and its lags 1..Lw,
  five calendar features, the first ``max(L, Lw)`` rows dropped,
  standardised per deployment by the training window's mean and
  standard deviation (+1e-8);
* LR: ridge on the standardised design with an intercept column,
  lambda 1e-2 on every coefficient;
* ANN: ReLU layers, sigmoid output times the model's ``y_scale``;
* scoring: the recursive 24-step rollout over the forecast temperatures
  issued at the boundary.

Every function takes ``xp`` (numpy by default) so that the lower-precision
control can run the same arithmetic through ``jax.numpy``.
"""
from __future__ import annotations

import numpy as np

from traffic import DAY, HOUR


class Spec:
    """The feature settings a configuration's ``user_params`` state."""

    def __init__(self, up: dict):
        self.target_lags = int(up["target_lags"])
        self.weather_lags = int(up["weather_lags"])
        self.window_days = float(up["train_window_days"])
        self.horizon = int(up["horizon"])
        self.warm = max(self.target_lags, self.weather_lags)

    @property
    def n_features(self) -> int:
        return self.target_lags + 1 + self.weather_lags + 5


def calendar(times) -> np.ndarray:
    t = np.asarray(times, np.float64)
    hod, dow = (t % DAY) / HOUR, (t // DAY) % 7
    return np.stack([np.sin(2 * np.pi * hod / 24),
                     np.cos(2 * np.pi * hod / 24),
                     np.sin(2 * np.pi * dow / 7), np.cos(2 * np.pi * dow / 7),
                     (dow >= 5).astype(np.float64)], axis=-1)


def binned(site, t0: float, t1: float, sensors=None):
    """Per-bin sums and counts of the readings stamped in ``[t0, t1)`` on
    its hourly grid: ``(sums, cnts)``, each ``(N, T)``."""
    T = int(round((t1 - t0) / HOUR))
    t, v, ok = site.stamped(t0, t1, sensors)
    n = t.shape[0]
    idx = np.floor((t - t0) / HOUR).astype(np.int64)
    flat = (np.arange(n)[:, None] * T + idx)[ok]
    sums = np.bincount(flat, weights=v[ok], minlength=n * T).reshape(n, T)
    cnts = np.bincount(flat, minlength=n * T).reshape(n, T)
    return sums, cnts


def aligned(sums, cnts):
    """Bin means, forward-filled, 0 before the first filled bin."""
    T = sums.shape[-1]
    last = np.maximum.accumulate(np.where(cnts > 0, np.arange(T), -1), axis=-1)
    vals = np.take_along_axis(sums / np.maximum(cnts, 1),
                              np.maximum(last, 0), axis=-1)
    return np.where(last >= 0, vals, 0.0)


def hourly(site, t0: float, t1: float, sensors=None):
    """``(grid (T,), targets (N, T))``: the site's readings aligned onto the
    hourly grid of ``[t0, t1)``."""
    T = int(round((t1 - t0) / HOUR))
    return t0 + HOUR * np.arange(T), aligned(*binned(site, t0, t1, sensors))


def design(spec: Spec, grid, targets, temps):
    """``(X (N, R, F), y (N, R))`` over rows ``warm..T-1``."""
    T, w = grid.size, spec.warm
    cols = [targets[:, w - L: T - L] for L in range(1, spec.target_lags + 1)]
    cols.append(temps[:, w:])
    cols += [temps[:, w - L: T - L] for L in range(1, spec.weather_lags + 1)]
    X = np.stack(cols, axis=-1)
    cal = np.broadcast_to(calendar(grid[w:]), X.shape[:2] + (5,))
    return np.concatenate([X, cal], axis=-1), targets[:, w:]


def training_set(site, spec: Spec, now: float, sensors=None):
    """What a fit at ``now`` learns from: the standardised design, the
    targets, and the standardisation."""
    grid, targets = hourly(site, now - spec.window_days * DAY, now, sensors)
    X, y = design(spec, grid, targets,
                  site.weather.temperature(grid, sensors))
    mu = X.mean(axis=1)
    sd = X.std(axis=1) + 1e-8
    return (X - mu[:, None]) / sd[:, None], y, mu, sd


def ridge(Xs, y, lam: float = 1e-2, xp=np, matmul=None):
    """Per-deployment ridge solve with an intercept column."""
    mm = matmul or xp.matmul
    Xb = xp.concatenate([Xs, xp.ones(Xs.shape[:-1] + (1,), Xs.dtype)], -1)
    Xt = xp.swapaxes(Xb, -1, -2)
    A = mm(Xt, Xb) + lam * xp.eye(Xb.shape[-1], dtype=Xs.dtype)
    return xp.linalg.solve(A, mm(Xt, y[..., None]))[..., 0]


def lr_predict(theta, xs, xp=np):
    """``theta (N, F+1)``, standardised ``xs (N, ..., F)``."""
    th = theta.reshape(theta.shape[:1] + (1,) * (xs.ndim - 2)
                       + theta.shape[1:])
    return (xs * th[..., :-1]).sum(-1) + th[..., -1]


def ann_predict(layers, y_scale, xs, xp=np, matmul=None):
    """``layers``: ``[(w (N, in, out), b (N, out)), ...]``, standardised
    ``xs (N, S, F)``."""
    mm = matmul or xp.matmul
    h = xs
    for i, (w, b) in enumerate(layers):
        h = mm(h, w) + b[:, None, :]
        if i < len(layers) - 1:
            h = xp.maximum(h, 0.0)
    return y_scale[:, None] / (1.0 + xp.exp(-h[..., 0]))


def matmul_bf16x3(a, b):
    """float32 matmul at ``high`` precision on any backend: each operand
    split into a bfloat16 head and a bfloat16 tail, and the three products
    head x head, head x tail and tail x head (exact in float32) summed in
    float32; the tail x tail term is dropped. This is what a TPU computes
    for ``Precision.HIGH``."""
    import jax
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    mm = lambda x, y: jnp.matmul(x, y,  # noqa: E731
                                 precision=jax.lax.Precision.HIGHEST)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def rollout(predict, spec: Spec, y_hist, t_hist, temps_future, t_start,
            xp=np):
    """Recursive forecast of ``spec.horizon`` steps.

    ``y_hist (N, S, >=L)`` and ``t_hist (N, S, >=Lw+1)`` end at the last
    hour before the boundary, ``temps_future (N, S, H)`` are the
    forecasts issued at it and ``t_start (S,)`` the boundaries;
    ``predict`` maps raw features ``(N, S, F)`` to ``(N, S)``."""
    L, Lw = spec.target_lags, spec.weather_lags
    y = y_hist[..., y_hist.shape[-1] - L:]
    tw = t_hist[..., t_hist.shape[-1] - Lw:] if Lw else t_hist[..., :0]
    out = []
    for h in range(spec.horizon):
        cal = xp.asarray(calendar(np.asarray(t_start) + h * HOUR),
                         y.dtype)
        cal = xp.broadcast_to(cal, y.shape[:-1] + (5,))
        tf = temps_future[..., h:h + 1]
        x = xp.concatenate([y[..., ::-1], tf, tw[..., ::-1], cal], axis=-1)
        yh = predict(x)
        out.append(yh)
        y = xp.concatenate([y[..., 1:], yh[..., None]], axis=-1)
        if Lw:
            tw = xp.concatenate([tw[..., 1:], tf], axis=-1)
    return xp.stack(out, axis=-1)


def score_inputs(site, spec: Spec, boundaries, sensors=None):
    """The histories and horizon weather of every boundary:
    ``(y_hist, t_hist, temps_future)``, each ``(N, S, .)``."""
    b = np.asarray(boundaries, np.float64)
    k, win = spec.warm + 1, spec.window_days * DAY
    lo = float(b.min()) - win
    sums, cnts = binned(site, lo, float(b.max()), sensors)
    ys, ts, fs = [], [], []
    for t in b:
        i1 = int(round((t - lo) / HOUR))
        i0 = i1 - int(round(win / HOUR))
        ys.append(aligned(sums[:, i0:i1], cnts[:, i0:i1])[:, -k:])
        ts.append(site.weather.temperature(t - HOUR * np.arange(k, 0, -1),
                                           sensors))
        fs.append(site.weather.forecast(
            t, t + HOUR * np.arange(spec.horizon), sensors))
    return np.stack(ys, 1), np.stack(ts, 1), np.stack(fs, 1)


def ann_layers(models):
    """Per-deployment weights stacked: ``[(w, b), ...]`` and ``y_scale``."""
    n_layers = sum(1 for k in models[0] if k.startswith("w"))
    layers = [(np.stack([m[f"w{i}"] for m in models]).astype(np.float64),
               np.stack([m[f"b{i}"] for m in models]).astype(np.float64))
              for i in range(n_layers)]
    return layers, np.asarray([m["y_scale"] for m in models], np.float64)

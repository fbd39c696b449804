"""The comparison that decides ``correct``.

``collect`` copies out of the system under test what its timed window
persisted: every boundary's forecasts are looked up (a missing one, or one
stamped with other times, counts under ``unpersisted``), and the
forecasts of a sample of boundaries drawn from the seed, the last one
always among them, are kept with the model versions that made them.
``compare`` then runs the float64 reference over the same traffic and
returns each number with its limit from ``bench/limits/<cell>.json``:

* ``unpersisted``: boundaries x deployments without their forecast, or
  jobs that failed (limit 0);
* ``forecast_gap``: the largest absolute gap, in kWh, between a
  persisted forecast value and the reference's;
* LR ``theta_gap``: the largest absolute gap between a persisted ridge
  coefficient and the reference's own float64 solve.

``decide`` turns them into ``correct``: every number at or under its
limit. The LR reference recomputes everything from the traffic, the fit
included. The ANN reference scores with the networks themselves: the
benchmark's own copy where it made them from the seed, the persisted ones
otherwise.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

import reference as ref
from traffic import HOUR

#: deployments per block of the LR reference (bounds its memory)
BLOCK = 512


def sample_ticks(n: int, k: int, seed: int) -> list:
    """``k`` of ``n`` window ticks drawn from the seed, the last included."""
    rng = np.random.default_rng(seed)
    rest = rng.choice(n - 1, size=min(k - 1, n - 1), replace=False) \
        if n > 1 else []
    return sorted(int(i) for i in rest) + [n - 1]


def collect(run) -> dict:
    c, spec = run.castor, ref.Spec(run.config["user_params"])
    bounds = [t["boundary"] for t in run.ticks]
    picked = sample_ticks(len(bounds), int(run.traffic["check_ticks"]),
                          run.seed)
    want_t = np.arange(spec.horizon) * HOUR
    missing = 0
    got = np.full((len(run.names), len(picked), spec.horizon), np.nan)
    pick_of = {bounds[j]: i for i, j in enumerate(picked)}
    for d, name in enumerate(run.names):
        by_t = {fc.created_at: fc for fc in c.predictions.history(name)}
        for b in bounds:
            fc = by_t.get(b)
            if fc is None or not np.array_equal(fc.times, b + want_t):
                missing += 1
            elif b in pick_of:
                got[d, pick_of[b]] = fc.values
    if run.seeded is None:
        versions = [[c.versions.get(name, at=bounds[j]) for j in picked]
                    for name in run.names]
    else:               # the benchmark's own copy, not the store's
        versions = [[SimpleNamespace(trained_at=run.now0, params=m)]
                    * len(picked) for m in run.seeded]
    return {"site": run.site, "spec": spec, "config": run.config,
            "seed": run.seed, "boundaries": [bounds[j] for j in picked],
            "got": got, "missing": missing,
            "failed": sum(t["failed"] for t in run.ticks),
            "versions": versions}


def compare(found: dict, cell: dict) -> dict:
    """``{name: (value, limit)}`` of the program's run."""
    kind = found["config"]["reference"]
    numbers = {"unpersisted": float(found["missing"] + found["failed"])}
    numbers.update(COMPARE[kind](found))
    return with_limits(numbers, cell)


def with_limits(numbers: dict, cell: dict) -> dict:
    """``{name: (value, limit)}`` from ``{name: value}`` and the cell's
    ``bench/limits/<cell>.json``."""
    limits = cell["limits"]
    return {k: (float(v), float(limits[k])) for k, v in numbers.items()}


def decide(numbers: dict) -> bool:
    """``correct``: every number at or under its limit."""
    return all(v <= lim for v, lim in numbers.values())


def _scored(found):
    """Sampled (deployment, boundary) pairs that have a forecast."""
    return ~np.isnan(found["got"][..., 0])


def compare_lr(found: dict) -> dict:
    site, spec, b = found["site"], found["spec"], found["boundaries"]
    lam = float(found["config"]["ridge_lambda"])
    versions, scored = found["versions"], _scored(found)
    trained = sorted({v.trained_at for vs in versions for v in vs
                      if v is not None})
    theta_gap, fc_gap = 0.0, 0.0
    for lo in range(0, site.n, BLOCK):
        rows = np.arange(lo, min(site.n, lo + BLOCK))
        ys, ts, fs = ref.score_inputs(site, spec, b, rows)
        for at in trained:
            Xs, y, mu, sd = ref.training_set(site, spec, at, rows)
            theta = ref.ridge(Xs, y, lam)
            want = ref.rollout(
                lambda x: ref.lr_predict(theta, (x - mu[:, None])
                                         / sd[:, None]),
                spec, ys, ts, fs, b)
            for i, d in enumerate(rows):
                use = [s for s, v in enumerate(versions[d])
                       if v is not None and v.trained_at == at]
                if not use:
                    continue
                th = np.asarray(versions[d][use[0]].params["params"]
                                ["theta"], np.float64)
                theta_gap = max(theta_gap, float(np.abs(th - theta[i]).max()))
                use = [s for s in use if scored[d, s]]
                if use:
                    fc_gap = max(fc_gap, float(np.abs(
                        found["got"][d, use] - want[i, use]).max()))
    return {"forecast_gap": fc_gap, "theta_gap": theta_gap}


def compare_ann(found: dict) -> dict:
    site, spec, b = found["site"], found["spec"], found["boundaries"]
    scored = _scored(found)
    ys, ts, fs = ref.score_inputs(site, spec, b)
    fc_gap = 0.0
    mus = {}
    for d in range(site.n):
        vs = found["versions"][d]
        for v in {id(v): v for v in vs if v is not None}.values():
            cols = [s for s in range(len(b))
                    if vs[s] is v and scored[d, s]]
            if not cols:
                continue
            key = (v.trained_at, d)
            if key not in mus:
                mus.update(_standardisation(site, spec, v.trained_at))
            mu, sd = mus[key]
            layers, y_scale = ref.ann_layers([v.params["params"]])
            want = ref.rollout(
                lambda x: ref.ann_predict(layers, y_scale, (x - mu) / sd),
                spec, ys[d:d + 1, cols], ts[d:d + 1, cols],
                fs[d:d + 1, cols], [b[s] for s in cols])
            fc_gap = max(fc_gap, float(
                np.abs(found["got"][d, cols] - want[0]).max()))
    return {"forecast_gap": fc_gap}


def _standardisation(site, spec, at: float) -> dict:
    """``{(at, d): (mu, sd)}`` of every deployment's fit at ``at``."""
    _, _, mu, sd = ref.training_set(site, spec, at)
    return {(at, d): (mu[d], sd[d]) for d in range(site.n)}


COMPARE = {"lr": compare_lr, "ann": compare_ann}

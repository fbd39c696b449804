"""The comparison that decides ``correct``.

``collect`` copies out of the system under test what its timed window
persisted: every boundary's forecasts are looked up (a missing one, or one
stamped with other times, counts under ``unpersisted``), and the
forecasts of a sample of boundaries drawn from the seed, the last one
always among them, are kept with the model versions that made them.
``compare`` then runs the float64 reference over the same traffic and
returns each number with its limit from ``bench/limits/<cell>.json``:
``unpersisted``, boundaries x deployments without their forecast or jobs
that failed (limit 0), and the numbers of the configuration's model,
which ``bench/models/<reference>.py`` ``compare`` computes from what
``collect`` kept.

``decide`` turns them into ``correct``: every number at or under its
limit.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from traffic import HOUR


def sample_ticks(n: int, k: int, seed: int) -> list:
    """``k`` of ``n`` window ticks drawn from the seed, the last included."""
    rng = np.random.default_rng(seed)
    rest = rng.choice(n - 1, size=min(k - 1, n - 1), replace=False) \
        if n > 1 else []
    return sorted(int(i) for i in rest) + [n - 1]


def collect(run) -> dict:
    c, horizon = run.castor, int(run.config["user_params"]["horizon"])
    bounds = [t["boundary"] for t in run.ticks]
    picked = sample_ticks(len(bounds), int(run.traffic["check_ticks"]),
                          run.seed)
    want_t = np.arange(horizon) * HOUR
    missing = 0
    got = np.full((len(run.names), len(picked), horizon), np.nan)
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
    return {"site": run.site, "config": run.config,
            "seed": run.seed, "boundaries": [bounds[j] for j in picked],
            "got": got, "missing": missing,
            "failed": sum(t["failed"] for t in run.ticks),
            "versions": versions}


def compare(found: dict, cell: dict) -> dict:
    """``{name: (value, limit)}`` of the program's run."""
    numbers = {"unpersisted": float(found["missing"] + found["failed"])}
    numbers.update(cell["model"].compare(found))
    return with_limits(numbers, cell)


def with_limits(numbers: dict, cell: dict) -> dict:
    """``{name: (value, limit)}`` from ``{name: value}`` and the cell's
    ``bench/limits/<cell>.json``."""
    limits = cell["limits"]
    return {k: (float(v), float(limits[k])) for k, v in numbers.items()}


def decide(numbers: dict) -> bool:
    """``correct``: every number at or under its limit."""
    return all(v <= lim for v, lim in numbers.values())


def scored(found: dict) -> np.ndarray:
    """Sampled (deployment, boundary) pairs that have a forecast."""
    return ~np.isnan(found["got"][..., 0])

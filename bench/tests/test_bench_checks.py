"""The comparison that decides ``correct``: a whole run of each cell's
harness on the CPU at a tiny size, first sound, then with the timed path
broken underneath. Every fault must turn ``correct`` false."""
from pathlib import Path

import numpy as np
import pytest

import run as bench

ROOT = Path(__file__).resolve().parents[2]
CELLS = {"ann": "ann.cyprus531_score", "lr": "lr.hourly_score_4096"}


def tiny(kind: str, hidden: int = 32, n: int = 6) -> dict:
    """The cell at ``n`` prosumers; ANN networks ``hidden`` wide."""
    cell = bench.load_cell(ROOT, CELLS[kind])
    cell["traffic"]["site"].update(n_prosumers=n, n_feeders=2)
    if kind == "ann":
        cell["config"]["user_params"].update(hidden=hidden)
    return cell


def run_cell(kind: str, seed: int = 2 ** 31 + 7) -> dict:
    return bench.execute(ROOT, tiny(kind), seed, 0.4, 0, {},
                         require_chip=False)


def answer_altered(mp):
    """One forecast value off by 0.05 kWh where the rollout returns it."""
    from repro.core.runtime import FleetRuntime
    orig = FleetRuntime.fleet_score

    def score(self, *a, **kw):
        out = orig(self, *a, **kw)
        if out:
            t, v = out[0]
            v = np.array(v)
            v[3] += 0.05
            out[0] = (t, v)
        return out
    mp.setattr(FleetRuntime, "fleet_score", score)


def state_unchanged(mp):
    """The ring update moves the watermark but returns the old windows."""
    from repro.core.runtime import FleetRuntime
    orig = FleetRuntime._advance
    fields = ("ring", "filled", "ring_t", "y_win", "y_tail", "t_tail")

    def advance(self, state, *a):
        keep = [getattr(state, f) for f in fields]
        got = orig(self, state, *a)
        for f, v in zip(fields, keep):
            setattr(state, f, v)
        return got
    mp.setattr(FleetRuntime, "_advance", advance)


def half_persisted(mp):
    """Half of every bin's forecasts left out of the store."""
    from repro.core.lineage import PredictionStore
    orig = PredictionStore.save_many
    mp.setattr(PredictionStore, "save_many",
               lambda self, fcs: orig(self, fcs[:len(fcs) // 2]))


def fit_half_rows(mp):
    """The ridge fit sees every other row of the design."""
    import repro.forecast.linear as lin
    orig = lin._ridge_fleet
    mp.setattr(lin, "_ridge_fleet",
               lambda X, y, lam=1e-2, mesh=None: orig(
                   X[:, ::2], y[:, ::2], lam, mesh=mesh))


@pytest.mark.parametrize("kind", ["ann", "lr"])
def test_sound_run_is_correct(kind):
    out = run_cell(kind)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 6 * 8
    assert out["checks"]["unpersisted"]["value"] == 0.0


@pytest.mark.parametrize("kind,fault", [
    ("ann", answer_altered), ("ann", state_unchanged),
    ("ann", half_persisted),
    ("lr", answer_altered), ("lr", state_unchanged),
    ("lr", half_persisted), ("lr", fit_half_rows)])
def test_fault_is_not_correct(kind, fault, monkeypatch):
    fault(monkeypatch)
    out = run_cell(kind)
    assert not out["correct"], out["checks"]


def test_sample_of_ticks_is_drawn_from_the_seed_and_keeps_the_last():
    import checks
    a = checks.sample_ticks(100, 8, 5)
    assert a == checks.sample_ticks(100, 8, 5)
    assert a != checks.sample_ticks(100, 8, 6)
    assert len(set(a)) == 8 and a[-1] == 99
    assert checks.sample_ticks(3, 8, 1) == [0, 1, 2]


def readings(kind: str, seed: int) -> tuple:
    """The program's numbers and the control's, under the cell's limits,
    after 8 window ticks; ANN at the configuration's own width. The
    limits hold the widest gap over the cell's whole fleet, so the fleet
    here is 24 deployments: at 6 the control's widest gap can fall
    under the limit (1.0e-4 against 1.2e-4 on one seed)."""
    import calibrate
    import checks
    cell = tiny(kind, hidden=512, n=24)
    run = bench.Run(cell, seed, require_chip=False)
    run.setup()
    for _ in range(8):
        run.ticks.append(run.step())
    return calibrate.readings(checks.collect(run), cell)


@pytest.fixture(scope="module")
def calibration():
    return {(kind, seed): readings(kind, seed)
            for kind in CELLS for seed in (11, 12, 2 ** 31 + 13)}


@pytest.mark.parametrize("kind,number", [("ann", "forecast_gap"),
                                         ("lr", "theta_gap")])
def test_control_reads_far_above_the_program(kind, number, calibration):
    """The control (the reference at ``high`` matmul precision in the
    program's place) reads at least three times what the program does."""
    for (k, seed), (program, control) in calibration.items():
        if k == kind:
            assert control[number][0] >= 3 * program[number][0], seed


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
def test_control_is_not_correct_under_the_cells_limits(kind, seed,
                                                       calibration):
    """The decision ``bench/run.py`` makes, on the cell's own limits:
    the program comes out correct, its control not."""
    import checks
    program, control = calibration[(kind, seed)]
    assert checks.decide(program), program
    assert not checks.decide(control), control


def test_history_leaves_the_tails_spread_over_the_flush_cycle():
    """Set-up leaves each series its own fill of unflushed hourly chunks,
    and no read of tick 0 or the warm ticks consolidates them."""
    run = bench.Run(tiny("lr"), 5, require_chip=False)
    store = run.castor.store
    tails = [store._data[i].tail_n for i in run.site.ts_ids]
    assert store.compaction_count == run.site.n
    assert sorted(tails) == tails and tails[0] == 0
    assert tails[-1] > store.tail_max * 3 // 4
    run.setup()
    assert store.compaction_count == run.site.n
    assert all(store._data[i].tail_n > t
               for i, t in zip(run.site.ts_ids, tails))

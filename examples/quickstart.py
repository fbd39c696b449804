"""Quickstart: the paper's full workflow in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py

Ingest a sensor -> attach semantics -> publish a model implementation ->
deploy it against the semantic context -> let the scheduler execute it ->
retrieve the forecast by semantics.
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import Castor, ModelDeployment, Schedule, DAY, HOUR
from repro.forecast import LinearForecaster
from repro.timeseries.transforms import mape


def main():
    enable_compile_cache()
    castor = Castor()

    # (1) ingest an irregular energy time-series for 35 days
    rng = np.random.default_rng(0)
    t = np.arange(0, 35 * DAY, HOUR) + rng.uniform(-60, 60, 35 * 24)
    hod = (t % DAY) / HOUR
    load = 3 + 2 * np.exp(-0.5 * ((hod - 19) / 2.5) ** 2) \
        + rng.normal(0, 0.08, t.size)
    castor.ingest("sensor-001", t, load)

    # (2) contextualise: what quantity, where
    castor.add_signal("ENERGY_LOAD", unit="kWh")
    castor.add_entity("SUBSTATION_S1", kind="SUBSTATION", lat=35.1, lon=33.4)
    castor.link("sensor-001", "ENERGY_LOAD", "SUBSTATION_S1")

    # (3)/(4) publish a model implementation (the paper's PyPI step)
    castor.publish("energy-lr", "1.0", LinearForecaster)

    # (5)/(6) deploy it against the context with train/score schedules
    castor.deploy(ModelDeployment(
        name="lr-s1", package="energy-lr",
        signal="ENERGY_LOAD", entity="SUBSTATION_S1",
        train=Schedule(start=30 * DAY, every=7 * DAY),     # weekly training
        score=Schedule(start=30 * DAY, every=HOUR),        # hourly scoring
        user_params={"train_window_days": 21, "horizon": 24}))

    # (7)-(10) one scheduler tick trains + scores; forecasts are persisted
    results = castor.tick(now=30 * DAY)
    print(f"executed {len(results)} jobs: "
          f"{[f'{r.job.task}:{r.ok}' for r in results]}")

    # retrieval is semantic: consumers never know which model served it
    fc = castor.best_forecast("ENERGY_LOAD", "SUBSTATION_S1")
    print(f"forecast by {fc.deployment_name} (model v{fc.model_version}): "
          f"{fc.values[:6].round(2)} ...")

    tt, actual = castor.read("ENERGY_LOAD", "SUBSTATION_S1",
                             fc.times[0] - 1, fc.times[-1] + 1)
    n = min(len(actual), len(fc.values))
    print(f"24h MAPE vs actuals: {mape(actual[:n], fc.values[:n]):.2f}%")
    print("system stats:", castor.stats())


if __name__ == "__main__":
    main()

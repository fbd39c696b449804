"""End-to-end smart-grid deployment (paper §4): a full site with topology,
IoT ingestion, a data-transformation model (Fig. 4), all four AI models
deployed against the substation (Figs. 5/6), programmatic fleet deployment
to every prosumer, rolling-horizon scoring over several cycles (Fig. 7),
and the model-ranking retrieval.

    PYTHONPATH=src python examples/smartgrid_forecasting.py \
        [--executor fleet|serverless|local]

``--executor serverless`` routes the cycles through the serverless
invocation pipeline (stateless payloads, aggregated actions, warm
sticky workers — repro/serverless/) and prints its invocation telemetry.
"""
import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import Castor, ModelDeployment, Schedule, DAY, HOUR
from repro.forecast import (PAPER_MODELS, EnergyFromCurrentModel)
from repro.timeseries.ingest import SiteSpec, build_site, ingest_current_feed
from repro.timeseries.transforms import mape


def main(executor: str = "fleet"):
    enable_compile_cache()
    castor = Castor()
    t_end = 50 * DAY
    site = build_site(castor, SiteSpec("CY", n_prosumers=8, n_feeders=2,
                                       n_substations=1, seed=5),
                      t0=0.0, t1=t_end)
    print(f"[site] {castor.stats()} ({site['readings']:,} readings)")

    # ---- data-transformation model (Fig. 4): current -> 15-min energy ----
    ingest_current_feed(castor, "CY_SUB_0", t0=40 * DAY, t1=45 * DAY)
    castor.publish("castor-xform", "1.0", EnergyFromCurrentModel)
    castor.add_signal("ENERGY_LOAD_15MIN", unit="kWh")
    castor.deploy(ModelDeployment(
        name="xform-sub", package="castor-xform",
        signal="ENERGY_LOAD_15MIN", entity="CY_SUB_0",
        train=Schedule(45 * DAY, 1e12), score=Schedule(45 * DAY, DAY),
        user_params={"window_days": 5}))

    # ---- the paper's four AI models on the substation (Figs. 5/6) ----
    hp = {"ANN": {"epochs": 150, "hidden": 32},
          "LSTM": {"epochs": 150, "hidden": 16}}
    for rank, (kind, cls) in enumerate(PAPER_MODELS.items()):
        castor.publish(f"castor-{kind.lower()}", "1.0", cls)
        castor.deploy(ModelDeployment(
            name=f"{kind}-sub", package=f"castor-{kind.lower()}",
            signal="ENERGY_LOAD", entity="CY_SUB_0",
            train=Schedule(45 * DAY, 7 * DAY), score=Schedule(45 * DAY, HOUR),
            user_params={"train_window_days": 28, **hp.get(kind, {})},
            rank=rank))

    # ---- programmatic fleet: LR for every prosumer with the signal ----
    fleet = castor.deploy_for_all(
        package="castor-lr", signal="ENERGY_LOAD", name_prefix="fleet-lr",
        kind="PROSUMER", train=Schedule(45 * DAY, 7 * DAY),
        score=Schedule(45 * DAY, HOUR),
        user_params={"train_window_days": 21})
    print(f"[deploy] {len(castor.deployments)} deployments "
          f"({len(fleet)} from one semantic rule)")

    # ---- run 3 hourly scheduler cycles (rolling horizons, Fig. 7) ----
    t0 = time.time()
    for i in range(3):
        res = castor.tick(45 * DAY + i * HOUR, executor=executor)
        ok = sum(r.ok for r in res)
        print(f"[tick {i}] {ok}/{len(res)} jobs ok")
        bad = [r for r in res if not r.ok]
        for r in bad[:3]:
            print("   FAIL", r.job.deployment_name, r.error[:100])
    print(f"[exec] 3 cycles in {time.time()-t0:.1f}s wall "
          f"(executor={executor})")
    if executor == "serverless":
        s = castor.stats()["serverless"]
        print(f"[serverless] {s['invocations']} invocations "
              f"({s['cold_starts']} cold / {s['warm_starts']} warm), "
              f"mean aggregation {s['mean_aggregation']:.1f} jobs/action, "
              f"p50 exec {s['exec_s_p50'] * 1e3:.0f}ms")

    # ---- Fig. 6: compare the four substation models against actuals ----
    print("\nvalidation MAPE over the first scored day (paper: LR 3.92, "
          "GAM 2.86, ANN 2.76, LSTM 6.37):")
    for kind in PAPER_MODELS:
        fc = castor.predictions.history(f"{kind}-sub")[0]
        t, actual = castor.read("ENERGY_LOAD", "CY_SUB_0",
                                fc.times[0] - 1, fc.times[-1] + 1)
        n = min(len(actual), len(fc.values))
        print(f"  {kind:5s} MAPE = {mape(actual[:n], fc.values[:n]):5.2f}%")

    # ---- Fig. 7: one target hour seen from multiple forecast horizons ----
    first = castor.predictions.history("GAM-sub")[0]
    target = float(first.times[4])
    hz = castor.predictions.horizons("GAM-sub", target)
    print(f"\nFig.7 view — target hour t={target/3600:.0f}h predicted from "
          f"{len(hz)} horizons: {[(round(c/3600., 1), round(v, 2)) for c, v in hz]}")

    # ---- ranking: consumers just ask for the context ----
    best = castor.best_forecast("ENERGY_LOAD", "CY_SUB_0")
    print(f"\nranked retrieval serves: {best.deployment_name}")
    print(f"[lineage] {castor.versions.count()} model versions, "
          f"{castor.predictions.count()} persisted forecasts")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--executor", default="fleet",
                    choices=("fleet", "serverless", "local"))
    main(ap.parse_args().executor)

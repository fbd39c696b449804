"""Chip smoke test: Castor's fleet tick on one TPU, at the paper's ANN width.

Builds a seeded smart-grid site of 256 prosumers with hourly readings and
deploys two fleets on it through ``Castor.deploy_for_all``: an ANN fleet at
the paper's width (4 hidden layers of 512, paper §4.2, default 300 epochs)
and an LR fleet. Then it drives ``Castor.tick(executor="fleet")``:

* tick 0 trains and scores every deployment (4 x 256 jobs, cold);
* ticks 1 and 2 are hourly score-only polls (2 x 256 jobs each) that must
  take the warm ``FleetRuntime`` path.

Every job must succeed. The results are checked against references that
share no code with the device path: LR ``theta`` against a float64 numpy
ridge solve on the host design matrix, and tick 2's LR and ANN forecasts
against the host ``recursive_forecast`` with a float64 numpy predictor
over the persisted parameters. The compiled ANN rollout must contain the
Pallas kernel (``tpu_custom_call``).

    python chip_smoke.py [--seed S]          # one chip
    python chip_smoke.py --chips 4           # sharded tick 0 vs one chip

With ``--chips 4`` only tick 0 runs: once sharded over the four chips
(``FleetExecutor``'s automatic mesh) and once with ``mesh="off"`` on one
chip. The LR forecasts of the two must agree, and each run's forecasts
must match the float64 reference. The two ANN trainings are not compared
value by value: Adam over 300 epochs amplifies float32 rounding, which
differs between a sharded and an unsharded batch.

The script needs a TPU. Without one it exits non-zero and prints no result.
Timings it prints are smoke timings, not benchmark numbers. The last line
of its output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

N_PROSUMERS = 256
ANN_HP = {"hidden": 512}             # 4 hidden layers, 300 epochs: defaults
TICKS = 3
DAYS = 38                            # ingested history (FLEET_NOW is day 35)
RIDGE_LAM = 1e-2                     # LinearForecaster's ridge penalty
#: theta tolerance of the fleet == single contract (tests/test_forecast.py)
THETA_RTOL, THETA_ATOL = 1e-3, 3e-4


class SmokeFailure(RuntimeError):
    pass


def build(n: int, seed: int, ann_hp: dict):
    """Castor with one seeded site of ``n`` prosumers and two fleets: ANN
    (``ann-*``) and LR (``lr-*``), trained once at FLEET_NOW and scored
    hourly from FLEET_NOW on."""
    from repro.core import Castor, Schedule
    from repro.forecast import ANNForecaster, LinearForecaster
    from repro.testing import DAY, FLEET_NOW, HOUR
    from repro.timeseries.ingest import SiteSpec, build_site
    c = Castor()
    build_site(c, SiteSpec("S", n_prosumers=n, n_feeders=1,
                           n_substations=1, seed=seed),
               t0=0.0, t1=DAYS * DAY)
    c.publish("ann", "1.0", ANNForecaster)
    c.publish("lr", "1.0", LinearForecaster)
    for pkg, hp in (("ann", ann_hp), ("lr", {})):
        c.deploy_for_all(package=pkg, signal="ENERGY_LOAD", name_prefix=pkg,
                         kind="PROSUMER", train=Schedule(FLEET_NOW, 1e12),
                         score=Schedule(FLEET_NOW, HOUR),
                         user_params=dict(hp))
    return c


def _require_ok(res, expected: int, label: str) -> None:
    bad = [r for r in res if not r.ok]
    if bad:
        errs = "\n  ".join(f"{r.job.deployment_name} {r.job.task}: {r.error}"
                           for r in bad[:5])
        raise SmokeFailure(f"{label}: {len(bad)}/{len(res)} jobs failed:\n"
                           f"  {errs}")
    if len(res) != expected:
        raise SmokeFailure(f"{label}: {len(res)} jobs, expected {expected}")


def run_ticks(c, n: int, ticks: int = TICKS) -> list:
    """Tick 0 trains and scores all 2n deployments; every later tick is an
    hourly score poll on the warm runtime. The first warm poll compiles
    only the one-step ring update; from the second on nothing compiles.
    Returns one summary dict per tick."""
    from repro.obs.metrics import get_metrics
    from repro.testing import FLEET_NOW, HOUR
    ring = get_metrics().counter("jit.retrace.ring_update")
    out = []
    for k in range(ticks):
        r0 = ring.value
        t0 = time.perf_counter()
        res = c.tick(FLEET_NOW + k * HOUR, executor="fleet")
        seconds = time.perf_counter() - t0
        _require_ok(res, 4 * n if k == 0 else 2 * n, f"tick {k}")
        stats = c.fleet_executor().last_bin_stats
        retraces = sum(b["retraces"] for b in stats)
        if k > 0:
            cold = [b["bin"] for b in stats if b["runtime"] != "warm"]
            if cold:
                raise SmokeFailure(f"tick {k}: bins off the warm path: {cold}")
            allowed = ring.value - r0 if k == 1 else 0
            if retraces != allowed:
                raise SmokeFailure(
                    f"tick {k}: {retraces} retraces, expected {allowed}: "
                    f"{[(b['bin'], b['retraces']) for b in stats]}")
        out.append({"tick": k, "jobs": len(res), "seconds": seconds,
                    "retraces": retraces,
                    "runtime": sorted({b["runtime"] for b in stats})})
    return out


def _host_inputs(c, deps, cls, now: float):
    """What a cold host poll at ``now`` sees, computed on the host in
    float64: the aligned target rows and observed temperatures over the
    training window, plus the horizon weather forecast."""
    import numpy as np
    from repro.forecast.features import FeatureSpec, fleet_hourly_series
    from repro.timeseries.transforms import DAY
    up = {**cls.DEFAULTS, **deps[0].user_params}
    spec = FeatureSpec.from_params(up)
    ctxs = [c.graph.context(d.signal, d.entity) for d in deps]
    ents = [x.entity for x in ctxs]
    lats, lons = [e.lat for e in ents], [e.lon for e in ents]
    t0 = now - float(up["train_window_days"]) * DAY
    grid, targets = fleet_hourly_series(c, ctxs, t0, now, spec.step)
    temps = c.weather.temperature_many(lats, lons, grid) \
        if spec.use_weather else np.zeros_like(targets)
    fut_t = now + spec.step * np.arange(int(up["horizon"]))
    temps_fut = c.weather.forecast_many(lats, lons, now, fut_t)
    return spec, grid, targets, temps, fut_t, temps_fut


def check_lr_theta(c) -> float:
    """LR ``theta`` of every deployment against a float64 ridge solve on
    the host design matrix. Returns the largest absolute deviation."""
    import numpy as np
    from repro.forecast import LinearForecaster
    from repro.forecast.features import design_matrix
    from repro.testing import FLEET_NOW
    deps = c.deployments.for_package("lr")
    spec, grid, targets, temps, _, _ = _host_inputs(
        c, deps, LinearForecaster, FLEET_NOW)
    worst = 0.0
    for i, d in enumerate(deps):
        X, y = design_matrix(spec, grid, targets[i], temps[i])
        Xs = (X - X.mean(0)) / (X.std(0) + 1e-8)
        Xb = np.concatenate([Xs, np.ones((len(Xs), 1))], axis=1)
        want = np.linalg.solve(Xb.T @ Xb + RIDGE_LAM * np.eye(Xb.shape[1]),
                               Xb.T @ y)
        got = np.asarray(c.versions.get(d.name).params["params"]["theta"],
                         np.float64)
        worst = max(worst, float(np.abs(got - want).max()))
        np.testing.assert_allclose(got, want, rtol=THETA_RTOL,
                                   atol=THETA_ATOL, err_msg=d.name)
    return worst


def _lr_predict(models):
    import numpy as np
    th = np.stack([m["params"]["theta"] for m in models]).astype(np.float64)
    return lambda x: np.einsum("nf,nf->n", x, th[:, :-1]) + th[:, -1]


def _ann_predict(models):
    import numpy as np
    from repro.forecast.ann import N_HIDDEN_LAYERS
    p = [m["params"] for m in models]
    layers = [(np.stack([q[f"w{i}"] for q in p]).astype(np.float64),
               np.stack([q[f"b{i}"] for q in p]).astype(np.float64))
              for i in range(N_HIDDEN_LAYERS + 1)]
    ys = np.asarray([q["y_scale"] for q in p], np.float64)

    def predict(x):
        h = x
        for i, (w, b) in enumerate(layers):
            h = np.einsum("nf,nfh->nh", h, w) + b
            if i < N_HIDDEN_LAYERS:
                h = np.maximum(h, 0.0)
        return ys / (1.0 + np.exp(-h[:, 0]))

    return predict


def check_forecasts(c, now: float) -> dict:
    """The forecasts persisted at ``now`` against ``recursive_forecast``
    driven by a float64 numpy predictor over the persisted parameters.
    Returns the largest absolute deviation per fleet."""
    import numpy as np
    from repro.forecast import ANNForecaster, LinearForecaster
    from repro.forecast.features import recursive_forecast
    from repro.testing import FLEET_ATOL, FLEET_RTOL
    worst = {}
    for pkg, cls, make in (("lr", LinearForecaster, _lr_predict),
                           ("ann", ANNForecaster, _ann_predict)):
        deps = c.deployments.for_package(pkg)
        spec, _, targets, temps, fut_t, temps_fut = _host_inputs(
            c, deps, cls, now)
        models = [c.versions.get(d.name, at=now).params for d in deps]
        mu = np.stack([m["mu"] for m in models])
        sd = np.stack([m["sd"] for m in models])
        predict = make(models)
        warm = max(spec.target_lags, spec.weather_lags) + 1
        want = recursive_forecast(lambda x: predict((x - mu) / sd), spec,
                                  targets[:, -warm:], temps[:, -warm:],
                                  temps_fut, float(fut_t[0]), fut_t.size)
        got = []
        for d in deps:
            fc = c.predictions.history(d.name)[-1]
            if fc.created_at != now:
                raise SmokeFailure(f"{d.name}: no forecast at {now}")
            got.append(fc.values)
        got = np.asarray(got, np.float64)
        worst[pkg] = float(np.abs(got - want).max())
        np.testing.assert_allclose(got, want, rtol=FLEET_RTOL,
                                   atol=FLEET_ATOL, err_msg=pkg)
    return worst


def check_kernel(c, *, require_custom_call: bool) -> bool:
    """Build the ANN scoring rollout the fleet path builds, at this
    fleet's bin shapes, and check that the Pallas kernel is in it: a
    ``pallas_call`` in its jaxpr and, compiled for a TPU, a
    ``tpu_custom_call`` in the executable."""
    import jax
    import jax.numpy as jnp
    from repro.forecast import ANNForecaster
    from repro.forecast.ann import N_HIDDEN_LAYERS
    from repro.forecast.features import (FeatureSpec, bucket_n,
                                         make_device_rollout)
    deps = c.deployments.for_package("ann")
    up = {**ANNForecaster.DEFAULTS, **deps[0].user_params}
    spec = FeatureSpec.from_params(up)
    n, H, width = bucket_n(len(deps)), int(up["horizon"]), int(up["hidden"])
    F = spec.n_features
    sizes = [F] + [width] * N_HIDDEN_LAYERS + [1]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    stacked = {f"w{i}": s(n, sizes[i], sizes[i + 1])
               for i in range(len(sizes) - 1)}
    stacked.update({f"b{i}": s(n, sizes[i + 1])
                    for i in range(len(sizes) - 1)})
    stacked["y_scale"] = s(n)
    tw = spec.weather_lags + 1 if spec.use_weather else 1
    args = (stacked, s(n, F), s(n, F), s(n, spec.target_lags), s(n, tw),
            s(n, H), s(H), s(H))
    run = make_device_rollout(
        ANNForecaster._device_predict_factory(spec, ()), spec, H)
    if "pallas_call" not in str(jax.make_jaxpr(run)(*args)):
        raise SmokeFailure("the ANN rollout does not call the Pallas kernel")
    if require_custom_call and \
            "tpu_custom_call" not in run.lower(*args).compile().as_text():
        raise SmokeFailure("the compiled ANN rollout has no tpu_custom_call")
    return True


def _forecast_values(c, pkg: str) -> dict:
    import numpy as np
    return {d.name: np.asarray(c.predictions.history(d.name)[-1].values)
            for d in c.deployments.for_package(pkg)}


def device_busy_seconds(trace_dir) -> dict:
    """Seconds each TPU spent running XLA ops in a profiler trace: the
    summed event durations of each device plane's "XLA Ops" line, keyed
    by plane name ("/device:TPU:0", ...)."""
    from jax.profiler import ProfileData
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    planes = list(ProfileData.from_file(str(path)).planes)
    busy = {p.name: sum(e.duration_ns for ln in p.lines
                        if ln.name == "XLA Ops" for e in ln.events) / 1e9
            for p in planes if p.name.startswith("/device:TPU:")}
    if not busy:
        raise SmokeFailure("no TPU planes in the trace: " + str(
            [(p.name, [ln.name for ln in p.lines]) for p in planes]))
    return busy


def compare_sharded(n: int, seed: int, ann_hp: dict, ndev: int) -> dict:
    """Tick 0 twice: through the castor's fleet executor, which shards
    every bin over all ``ndev`` local devices, and through a
    ``FleetExecutor(mesh="off")`` on one device. The profiler trace of the
    sharded tick must show every device busy (not all the work landing on
    device 0), the LR forecasts of both must agree at
    FLEET_RTOL/FLEET_ATOL, and both ANN fleets must match the float64
    rollout reference over their own parameters."""
    import tempfile

    import jax
    import numpy as np
    from repro.core.executor import FleetExecutor
    from repro.testing import FLEET_ATOL, FLEET_NOW, FLEET_RTOL
    sharded = build(n, seed, ann_hp)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            res = sharded.tick(FLEET_NOW, executor="fleet")
        t_sharded = time.perf_counter() - t0
        busy = device_busy_seconds(trace_dir)
    _require_ok(res, 4 * n, "sharded")
    stats = sharded.fleet_executor().last_bin_stats
    off_mesh = [b["bin"] for b in stats
                if not b["sharded"] or b["mesh_devices"] != ndev]
    if off_mesh:
        raise SmokeFailure(f"bins not sharded over {ndev} devices: {off_mesh}")
    if len(busy) != ndev or min(busy.values()) < max(busy.values()) / ndev:
        raise SmokeFailure(f"sharded work piled onto one device: {busy}")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]

    single = build(n, seed, ann_hp)
    t0 = time.perf_counter()
    _require_ok(FleetExecutor(single, mesh="off").run(
        single.scheduler.poll(FLEET_NOW)), 4 * n, "one device")
    t_single = time.perf_counter() - t0

    # LR is a closed-form solve: both fleets must agree. ANN training is
    # not comparable value by value: 300 Adam epochs amplify float32
    # rounding (a 1e-7 relative input perturbation moves the forecasts by
    # percents), and a TPU rounds a 64-instance batch differently from a
    # 256-instance one. Each ANN fleet's forecasts are held instead to the
    # float64 reference over its own parameters.
    lr_dev = 0.0
    got, want = _forecast_values(sharded, "lr"), _forecast_values(single, "lr")
    for name in sorted(want):
        lr_dev = max(lr_dev, float(np.abs(got[name] - want[name]).max()))
        np.testing.assert_allclose(got[name], want[name], rtol=FLEET_RTOL,
                                   atol=FLEET_ATOL, err_msg=name)
    got, want = (_forecast_values(sharded, "ann"),
                 _forecast_values(single, "ann"))
    ann_dev = max(float(np.abs(got[k] - want[k]).max()) for k in want)
    return {"sharded_seconds": t_sharded, "one_device_seconds": t_single,
            "busy_seconds": busy, "peak_bytes_in_use": peaks,
            "lr_dev": lr_dev, "ann_training_dev": ann_dev,
            "theta_dev": check_lr_theta(sharded),
            "reference_dev": {"sharded": check_forecasts(sharded, FLEET_NOW),
                              "one_device": check_forecasts(single,
                                                            FLEET_NOW)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"jax {jax.__version__}; {len(devices)} x {devices[0].device_kind};"
          f" compile cache {enable_compile_cache()}")

    try:
        if args.chips > 1:
            r = compare_sharded(N_PROSUMERS, args.seed, ANN_HP, args.chips)
            print(f"tick 0 sharded over {args.chips} chips: "
                  f"{r['sharded_seconds']:.1f}s, one chip: "
                  f"{r['one_device_seconds']:.1f}s (smoke timings, not "
                  "benchmark numbers)")
            print(f"device busy seconds in the sharded tick (profiled): "
                  f"{r['busy_seconds']}")
            print(f"peak_bytes_in_use per device: {r['peak_bytes_in_use']}")
            print(f"LR sharded vs one chip, largest forecast deviation: "
                  f"{r['lr_dev']:.3e}; LR theta vs float64 ridge: "
                  f"{r['theta_dev']:.3e}")
            print(f"largest deviation from the float64 rollout reference: "
                  f"{r['reference_dev']}")
            print(f"ANN sharded vs one chip, separately trained (not "
                  f"compared): {r['ann_training_dev']:.3e}")
        else:
            t0 = time.perf_counter()
            c = build(N_PROSUMERS, args.seed, ANN_HP)
            print(f"built {N_PROSUMERS} prosumers x 2 fleets in "
                  f"{time.perf_counter() - t0:.1f}s")
            for t in run_ticks(c, N_PROSUMERS):
                print(f"tick {t['tick']}: {t['jobs']} jobs ok in "
                      f"{t['seconds']:.2f}s, runtime {t['runtime']}, "
                      f"retraces {t['retraces']}")
            print("(tick times are smoke timings, not benchmark numbers; "
                  "tick 0 includes compilation)")
            from repro.testing import FLEET_NOW, HOUR
            theta = check_lr_theta(c)
            fc = check_forecasts(c, FLEET_NOW + (TICKS - 1) * HOUR)
            print(f"largest deviation: LR theta {theta:.3e}, LR forecast "
                  f"{fc['lr']:.3e}, ANN forecast {fc['ann']:.3e}")
            check_kernel(c, require_custom_call=True)
            print("ANN rollout compiles to a tpu_custom_call (Pallas)")
            print(f"peak_bytes_in_use: "
                  f"{devices[0].memory_stats()['peak_bytes_in_use']}")
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

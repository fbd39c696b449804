"""Shared load/transform/score plumbing for the paper's four forecasters.

Each concrete model supplies:
    _fit(X, y, rng) -> params-dict          (train on standardized features)
    _predict(params, X) -> yhat             (one-step prediction)
and optionally the fleet hooks (stacked across instances).

user_params (Listing 2): train_window_days, horizon, frequency, target_lags,
weather_lags, plus model-specific extras (hidden, epochs, lr, ...).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.registry import ModelInterface
from ..obs.trace import get_tracer
from ..timeseries.transforms import DAY, HOUR, calendar_phases
from .features import (FeatureSpec, bucket_n, design_matrix, edge_pad,
                       fleet_hourly_series, make_device_rollout,
                       recursive_forecast)


class _LRUCache:
    """Bounded LRU for compiled program caches, with hit/miss counters.

    The rollout cache used to grow without limit across (class, spec,
    horizon, statics, mesh) configurations — a long-lived server cycling
    through many deployment configs would pin every compilation forever.
    Eviction drops our reference; jax's own executable cache is keyed by
    the function object, so the next use of an evicted config recompiles.
    """

    def __init__(self, cap: int = 32):
        self.cap = int(cap)
        self._d: "OrderedDict[tuple, Callable]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key):
        fn = self._d.get(key)
        if fn is None:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return fn

    def put(self, key, fn):
        self._d[key] = fn
        self._d.move_to_end(key)
        while len(self._d) > self.cap:
            self._d.popitem(last=False)
        return fn

    def __len__(self):
        return len(self._d)

    def stats(self) -> dict:
        return {"size": len(self._d), "cap": self.cap,
                "hits": self.hits, "misses": self.misses}


#: compiled whole-horizon rollouts, keyed by
#: (model class, FeatureSpec, horizon, class-specific statics, mesh) — one
#: trace per configuration, reused across every score bin of that shape
#: bucket. mesh=None is the single-device jit; a fleet mesh gets its own
#: sharded compilation (jax Mesh objects hash by devices+axes). LRU-bounded
#: (see _LRUCache); hit/miss counters surface per bin via
#: ``FleetExecutor.last_bin_stats``.
_ROLLOUT_CACHE = _LRUCache(cap=32)


def rollout_cache_stats() -> dict:
    return _ROLLOUT_CACHE.stats()


#: prediction-interval quantiles (lower, upper) for every forecaster's
#: residual band — q10..q90, the band the detection flow compares against
BAND_QUANTILES = (0.1, 0.9)


def prediction_bands(model_object, values):
    """(lower, upper) quantile bands around a rolled-out point forecast.

    Bands come from the TRAINING residual quantiles persisted in the model
    object (``resid_q``, one-step-ahead errors), widened by sqrt(h+1) per
    horizon step — the standard recursive-forecast error growth heuristic:
    step 0 is the raw one-step band, later steps widen as accumulated
    prediction error compounds. Works per instance (``resid_q`` shape
    ``(2,)``, values ``(H,)``) and per fleet bin (``(N, 2)`` / ``(N, H)``).
    Returns ``(None, None)`` for model objects without residual quantiles
    (third-party implementations, versions trained before bands existed) —
    callers persist band-less forecasts rather than failing.
    """
    rq = model_object.get("resid_q") if isinstance(model_object, dict) \
        else None
    if rq is None:
        return None, None
    values = np.asarray(values, np.float64)
    rq = np.asarray(rq, np.float64)
    widen = np.sqrt(1.0 + np.arange(values.shape[-1], dtype=np.float64))
    return (values + rq[..., 0, None] * widen,
            values + rq[..., 1, None] * widen)


class ForecastModelBase(ModelInterface):
    DEFAULTS = {"train_window_days": 28, "horizon": 24}
    #: the fleet hooks accept a ``runtime=`` kwarg (FleetRuntime): the
    #: executor only threads its runtime through classes advertising this,
    #: so third-party SUPPORTS_FLEET implementations with the old
    #: signature keep working
    SUPPORTS_RUNTIME = True

    # ------------- paper 4-function workflow -------------
    def load(self):
        """Single-instance case of ``fleet_load``: one shared pipeline is
        what makes LocalPool and Fleet execution structurally equivalent."""
        self.fleet_load([self])
        return self._loaded

    def transform(self):
        spec, times, target, temps, now = self._loaded
        X, y = design_matrix(spec, times, target, temps)
        mu, sd = X.mean(0), X.std(0) + 1e-8
        self._xy = ((X - mu) / sd, y, mu, sd)
        return self._xy

    def train(self) -> dict:
        self.load()
        X, y, mu, sd = self.transform()
        import zlib                      # stable across processes (hash() is salted)
        rng = np.random.default_rng(zlib.crc32(self.model_id.encode()))
        params = self._fit(X, y, rng)
        # one-step residuals over the training window feed the q10/q90
        # prediction band persisted with every forecast (X is standardized)
        resid = y - np.asarray(self._predict(params, X), np.float64)
        return {"kind": self.KIND, "params": params, "mu": mu, "sd": sd,
                "y_scale": float(np.abs(y).max() + 1e-6),
                "resid_q": np.quantile(resid, BAND_QUANTILES)}

    def score(self, model_object):
        self.load()
        spec, times, target, temps, now = self._loaded
        up = {**self.DEFAULTS, **self.user_params}
        H = int(up["horizon"])
        warm = max(spec.target_lags, spec.weather_lags) + 1
        ent = self.context.entity
        # history grid ends at now-step; the first unknown interval is AT now
        fut_t = now + spec.step * np.arange(0, H)
        temps_future = self.system.weather.forecast(ent.lat, ent.lon, now, fut_t)
        mu, sd = model_object["mu"], model_object["sd"]

        def predict(x):
            return self._predict(model_object["params"], (x - mu) / sd)

        vals = recursive_forecast(predict, spec, target[-warm:], temps[-warm:],
                                  temps_future, now, H)
        lower, upper = prediction_bands(model_object, vals)
        return fut_t, vals, lower, upper

    # ------------- fleet plumbing (stacked across instances) -------------
    @classmethod
    def fleet_load(cls, instances: List[ModelInterface]) -> None:
        """Batched ``load()`` for a fleet bin: ONE ``store.read_many`` per
        shared (window, step) group instead of one ``read()`` per instance.

        Jobs in a bin share user_params and ``now``, so normally this is a
        single group — the whole bin's history arrives in one store call.
        Sets each instance's ``_loaded`` to exactly what ``load()`` would,
        keeping LocalPool and Fleet observationally equivalent.
        """
        groups: dict = {}
        for inst in instances:
            up = {**cls.DEFAULTS, **inst.user_params}
            spec = FeatureSpec.from_params(up)
            now = float(up.get("now", 0.0))
            t0 = now - float(up["train_window_days"]) * DAY
            groups.setdefault((t0, now, spec.step), []).append(
                (inst, spec, now))
        for (t0, t1, step), members in groups.items():
            ctxs = [m[0].context for m in members]
            system = members[0][0].system
            grid, targets = fleet_hourly_series(system, ctxs, t0, t1, step)
            # ONE vectorized weather call per bin group, not O(N) python
            # calls on the hot path (temperature_many rows are bitwise the
            # per-instance calls, so nothing downstream can tell). History
            # weather is the OBSERVED temperature (paper §4.2 trains on
            # observed weather); only the scoring horizon uses forecasts,
            # issued at scoring time. Observed history is also what makes
            # the steady-state runtime O(delta): a forecast issued at the
            # sliding window start would change EVERY value each poll.
            widx = [i for i, m in enumerate(members) if m[1].use_weather]
            if widx:
                ents = [members[i][0].context.entity for i in widx]
                wtemps = system.weather.temperature_many(
                    [e.lat for e in ents], [e.lon for e in ents], grid)
            temps_rows: Dict[int, np.ndarray] = {
                i: wtemps[j] for j, i in enumerate(widx)}
            for i, ((inst, spec, now), target) in enumerate(
                    zip(members, targets)):
                temps = temps_rows.get(i)
                if temps is None:
                    temps = np.zeros_like(grid)
                inst._loaded = (spec, grid, target, temps, now)

    @classmethod
    def _require_one_window(cls, instances) -> None:
        """Batched *scoring* rolls one recursive forecast with a single
        shared time axis, so a bin mixing execution times ('now') would
        silently compute wrong calendar features for all but the first
        instance — fail loudly instead. Training is per-instance after
        stacking and tolerates mixed windows, so only fleet_score guards.
        (Scheduler polls stamp every job in a cycle with the same time, so
        this only trips when jobs from different polls are mixed into one
        run.)"""
        nows = {inst._loaded[4] for inst in instances}
        if len(nows) > 1:
            raise RuntimeError(
                f"fleet bin mixes execution times {sorted(nows)[:3]}...; "
                "run each poll's jobs separately")

    @classmethod
    def _fleet_xy(cls, instances) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        cls.fleet_load(instances)
        Xs, ys, mus, sds = [], [], [], []
        for inst in instances:
            X, y, mu, sd = inst.transform()
            Xs.append(X), ys.append(y), mus.append(mu), sds.append(sd)
        return (np.stack(Xs), np.stack(ys), np.stack(mus), np.stack(sds))

    @classmethod
    def fleet_train(cls, instances: List[ModelInterface], *, mesh=None,
                    runtime=None):
        state = loaded = None
        if runtime is not None:
            loaded = runtime.fleet_xy(cls, instances)
        if loaded is None:               # cold / runtime opted out
            X, y, mu, sd = cls._fleet_xy(instances)
        else:                            # device-resident incremental path
            X, y, mu, sd, state = loaded
        rng = np.random.default_rng(12345)
        # jobs in a bin share user_params_key, so the first instance's
        # merged params speak for the whole bin (hardcoding defaults here
        # is the fleet/local divergence bug this signature prevents)
        up = {**cls.DEFAULTS, **instances[0].user_params}
        params = cls._fleet_fit(X, y, rng, up, mesh=mesh)   # stacked params
        # ONE host transfer per parameter (persistence needs numpy); the
        # train->score handoff below keeps the stacked DEVICE params so a
        # same-poll score bin never re-uploads what training just computed
        host = {k: np.asarray(v) for k, v in params.items()}
        mu_h, sd_h = np.asarray(mu), np.asarray(sd)
        ymax = np.asarray(np.abs(np.asarray(y)).max(axis=1))
        out = []
        yhat = cls._fleet_window_predict(
            [{"params": {k: v[i] for k, v in host.items()}}
             for i in range(len(instances))], np.asarray(X, np.float64))
        resid = np.asarray(y, np.float64) - np.asarray(yhat, np.float64)
        rq = np.quantile(resid, BAND_QUANTILES, axis=1).T      # (N, 2)
        for i, inst in enumerate(instances):
            pi = {k: v[i] for k, v in host.items()}
            out.append({"kind": cls.KIND, "params": pi, "mu": mu_h[i],
                        "sd": sd_h[i], "y_scale": float(ymax[i] + 1e-6),
                        "resid_q": rq[i]})
        if state is not None:
            runtime.note_trained(state, params, mu, sd, out)
        return out

    @classmethod
    def _fleet_window_predict(cls, model_objects, X: np.ndarray) -> np.ndarray:
        """One-step predictions over each instance's full standardized
        training design: ``X (N, T, F) -> (N, T)``. Feeds the per-instance
        training-residual quantiles behind prediction bands. The default
        loops instances through ``_predict`` (none of the built-in
        predictors touch ``self``); each forecaster overrides with a
        batched path."""
        return np.stack([
            np.asarray(cls._predict(cls, m["params"], X[i]), np.float64)
            for i, m in enumerate(model_objects)])

    @classmethod
    def _attach_bands(cls, model_objects, results):
        """Zip per-instance quantile bands onto ``(times, values)`` fleet
        results — shared by the device-runtime and cold scoring paths so
        both return the same 4-tuple shape."""
        with get_tracer().span("score.bands"):
            return [(t, v, *prediction_bands(m, v))
                    for m, (t, v) in zip(model_objects, results)]

    @classmethod
    def fleet_score(cls, instances: List[ModelInterface], model_objects, *,
                    mesh=None, runtime=None):
        if runtime is not None:
            res = runtime.fleet_score(cls, instances, model_objects,
                                      mesh=mesh)
            if res is not None:
                return cls._attach_bands(model_objects, res)
        cls.fleet_load(instances)
        cls._require_one_window(instances)
        # jobs in a bin share user_params_key: one merge speaks for all
        up = {**cls.DEFAULTS, **instances[0].user_params}
        H = int(up["horizon"])
        spec = None
        y_hists, temp_hists, fut_ts = [], [], []
        for inst in instances:
            spec, times, target, temps, now = inst._loaded
            warm = max(spec.target_lags, spec.weather_lags) + 1
            fut_t = now + spec.step * np.arange(0, H)
            y_hists.append(target[-warm:])
            temp_hists.append(temps[-warm:])
            fut_ts.append(fut_t)
        # one vectorized weather call per bin (bitwise == per-instance)
        ents = [inst.context.entity for inst in instances]
        temps_futs = instances[0].system.weather.forecast_many(
            [e.lat for e in ents], [e.lon for e in ents],
            instances[0]._loaded[4], fut_ts[0])
        mu = np.stack([m["mu"] for m in model_objects])
        sd = np.stack([m["sd"] for m in model_objects])
        stacked = {k: np.stack([m["params"][k] for m in model_objects])
                   for k in model_objects[0]["params"]}
        t_start = fut_ts[0][0]
        y_hist = np.stack(y_hists)
        temp_hist = np.stack(temp_hists)
        temps_fut = np.stack(temps_futs)

        vals = None
        if up.get("rollout", "device") != "host":
            vals = cls._device_rollout(spec, up, stacked, mu, sd, y_hist,
                                       temp_hist, temps_fut, t_start, H,
                                       mesh=mesh)
        if vals is None:                 # reference path / no device hook
            def predict(x):                              # x: (N, F)
                return cls._fleet_predict(stacked, (x - mu) / sd)

            vals = recursive_forecast(predict, spec, y_hist, temp_hist,
                                      temps_fut, t_start, H)
        return cls._attach_bands(
            model_objects, [(fut_ts[i], vals[i]) for i in range(len(instances))])

    # ------------- device-resident scoring rollout -------------
    @classmethod
    def _rollout_statics(cls, up: dict, stacked: dict) -> tuple:
        """Hashable per-class trace statics derived from the bin's shared
        user_params / stacked model params (e.g. GAM's spline column
        indices). Part of the compiled-rollout cache key."""
        return ()

    @classmethod
    def _device_predict_factory(cls, spec: FeatureSpec,
                                statics: tuple) -> Optional[Callable]:
        """Return a traceable ``(stacked_params, x) -> (N,)`` one-step
        predictor, or None to keep scoring on the numpy reference path
        (``recursive_forecast``)."""
        return None

    @classmethod
    def _device_rollout(cls, spec: FeatureSpec, up: dict, stacked, mu, sd,
                        y_hist, temp_hist, temps_future, t_start: float,
                        H: int, mesh=None) -> Optional[np.ndarray]:
        """Score a whole bin with ONE device program (jitted lax.scan over
        the horizon) instead of H host-loop steps; with ``mesh`` the bin's
        instance axis is shard_map-partitioned across the mesh's devices
        (still one dispatch). Returns None when the model has no traceable
        predictor — callers then fall back to the numpy reference path,
        preserving the executor equivalence contract for models that
        cannot run device-resident."""
        import jax.numpy as jnp
        statics = cls._rollout_statics(up, stacked)
        key = (cls, spec, H, statics, mesh)
        fn = _ROLLOUT_CACHE.get(key)
        if fn is None:
            predict = cls._device_predict_factory(spec, statics)
            if predict is None:
                return None
            fn = _ROLLOUT_CACHE.put(
                key, make_device_rollout(predict, spec, H, mesh=mesh))
        tracer = get_tracer()
        # the dispatch with its input staging; device.wait is the host
        # blocked on the chip
        with tracer.span("score.rollout"):
            tl, wl = spec.target_lags, spec.weather_lags
            f32 = jnp.float32
            y0 = jnp.asarray(y_hist, f32)[..., -tl:]
            if spec.use_weather:
                tw0 = jnp.asarray(temp_hist, f32)[..., -(wl + 1):]
            else:                        # unused carry, keep it minimal
                tw0 = jnp.zeros(y0.shape[:-1] + (1,), f32)
            hod, dow = calendar_phases(t_start + spec.step * np.arange(H))
            # shape-bucketed dispatch: pad the instance axis to its bucket
            # so nearby bin sizes hit ONE compilation (per-instance
            # recursion => padded lanes cannot perturb real ones); slice
            # the pad back off
            n = y0.shape[0] if y0.ndim > 1 else 0
            pad = bucket_n(n) - n if n else 0
            stacked = {k: edge_pad(jnp.asarray(v), pad)
                       for k, v in stacked.items()}
            args = [edge_pad(jnp.asarray(a, f32), pad)
                    for a in (mu, sd, y0, tw0, temps_future)]
            out = fn(stacked, *args, jnp.asarray(hod, f32),
                     jnp.asarray(dow, f32))
            with tracer.span("device.wait"):
                out = np.asarray(out, np.float64)
        return out[:n] if n else out

"""FleetRuntime: persistent device-resident per-bin state for the
steady-state poll hot path.

The paper's workload is rolling-horizon operation: thousands of deployed
models re-scored every cycle against a window that slides by a handful of
rows per poll. The cold fleet path re-reads the whole train window from
the store, realigns it, rebuilds lag/design matrices row-by-row in host
numpy and re-uploads everything — O(history) work for O(1) new data.

``FleetRuntime`` makes the warm poll O(delta) with three coordinated
layers (one object per ``FleetExecutor``; opt out per deployment with
``user_params["runtime"] = "off"`` or executor-wide with
``FleetExecutor(system, runtime="off")``):

* **Watermark-delta loads.** Per bin, the aligned target history lives in
  a device ring buffer ``(N_bucket, cap)`` next to a boolean *filled*
  mask. A poll reads only ``[watermark, now)`` from the store
  (``read_many(since=..., prior_counts=True)`` — O(log n + delta), no
  consolidation pass) and rolls the new rows in with ONE jitted update
  (ring buffers donated, so the update is in-place off-CPU). The
  ``prior_counts`` handshake proves no out-of-order append landed behind
  the watermark; if one did, the bin cold-rebuilds.
* **On-device feature assembly.** Warm train polls assemble the
  lag/weather/calendar design matrix, per-instance standardization
  included, in one jitted program over the ring — the host numpy
  row-stacking of ``design_matrix``/``transform`` disappears from the
  loop. The numpy path remains the cold/reference path, same contract as
  the scoring rollout's host fallback.
* **Shape-bucketed programs.** The ring's instance axis is padded to its
  power-of-two bucket (edge replication), so the update/assembly/rollout
  programs are shared by nearby bin sizes: a bin that loses a job (failed
  deployment, removed sensor) re-uses every warm compilation.

Window-relative fill semantics are preserved EXACTLY: the cold aligner
forward-fills gaps only from inside ``[t0, now)`` and zero-fills before
the first in-window point, while the ring's fill chain may reach back
before ``t0``. The *filled* mask restores cold semantics at read time
(``y = where(any fill in window so far, ring, 0)``), so a sensor going
silent across the window boundary cannot diverge the two paths.

A cached bin is invalidated (cold-rebuilt) when: the deployment set /
spec / window length changes (different state key), ``now`` regresses or
is not a whole number of steps past the watermark, a late append lands
behind the watermark, or the delta spans the whole window.

History weather rides in a third ring: history features use OBSERVED
temperatures (deterministic per site/time — see the fleet_load note in
forecast/base.py), so a warm poll computes only the ``d`` new columns
with one vectorized ``temperature_many`` call. Horizon weather is a
forecast issued at scoring time and is the single per-poll weather call
that cannot be cached (``forecast_many``, one call per bin).
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..forecast.features import (FeatureSpec, align_delta, bucket_n,
                                 edge_pad, fleet_window, note_trace)
from ..obs.trace import get_tracer
from ..timeseries.transforms import DAY, calendar_features, regular_grid

#: jitted ring updates / assemblies, keyed by static config (shapes key
#: the underlying jit cache); LRU-bounded like the rollout cache — a
#: long-lived server cycling many specs must not pin every compilation
from ..forecast.base import _LRUCache

_UPDATE_FNS = _LRUCache(cap=64)
_ASSEMBLE_FNS = _LRUCache(cap=64)


def _cached_program(cache: _LRUCache, key, build):
    fn = cache.get(key)
    if fn is None:
        fn = cache.put(key, build())
    return fn


def _make_update(d: int, T: int, warm_s: int):
    """One jitted program per (delta steps, window length, score warmup):
    roll the target/filled/temperature rings left by ``d``, forward-fill
    the new target columns from the previous ring column (the value the
    cold aligner would have propagated), and emit the window-masked
    target matrix plus the trailing score windows — a warm score poll
    reads the update's outputs directly, with no further device ops
    before the rollout dispatch. Ring buffers are donated — the
    steady-state poll updates in place instead of doubling residency."""
    import jax
    import jax.numpy as jnp

    def upd(ring, filled, ring_t, vals, mask, tvals):
        note_trace("ring_update")    # Python body runs only while tracing

        def ff(prev, xs):
            v, m = xs
            cur = jnp.where(m, v, prev)
            return cur, cur

        _, new = jax.lax.scan(ff, ring[:, -1], (vals.T, mask.T))
        ring = jnp.concatenate([ring[:, d:], new.T], axis=1)
        filled = jnp.concatenate([filled[:, d:], mask], axis=1)
        ring_t = jnp.concatenate([ring_t[:, d:], tvals], axis=1)
        win_f = filled[:, -T:]
        seen = jnp.cumsum(win_f, axis=1) > 0
        y_win = jnp.where(seen, ring[:, -T:], jnp.float32(0.0))
        return (ring, filled, ring_t, y_win,
                y_win[:, -warm_s:], ring_t[:, -warm_s:])

    donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
    return jax.jit(upd, donate_argnums=donate)


def _make_assemble(spec: FeatureSpec, T: int):
    """Jitted twin of ``design_matrix`` + ``transform`` over a whole bin:
    lag stacking is pure gathering (bitwise the host values), calendar
    features arrive precomputed on the host (float64 reduction, then one
    f32 cast — the same cast point as the cold path), and per-instance
    standardization runs in f32 on device (the one place warm and cold
    differ, at f32 epsilon)."""
    import jax
    import jax.numpy as jnp

    tl, wl = spec.target_lags, spec.weather_lags
    warm = max(tl, wl if spec.use_weather else 0)

    def asm(y_win, temps, cal):      # (N,T) f32, (N,T) f32, (T,5) f32
        note_trace("assemble")
        cols = [y_win[:, warm - L: T - L] for L in range(1, tl + 1)]
        if spec.use_weather:
            cols.append(temps[:, warm:])
            cols.extend(temps[:, warm - L: T - L] for L in range(1, wl + 1))
        parts = [jnp.stack(cols, axis=-1)]
        if spec.use_calendar:
            parts.append(jnp.broadcast_to(
                cal[warm:], (y_win.shape[0], T - warm, 5)))
        X = jnp.concatenate(parts, axis=-1)
        y = y_win[:, warm:]
        mu = X.mean(axis=1)
        sd = X.std(axis=1) + 1e-8
        Xs = (X - mu[:, None, :]) / sd[:, None, :]
        return Xs, y, mu, sd

    return jax.jit(asm)


@dataclass
class _BinState:
    key: tuple
    ids: Tuple[str, ...]
    sites: Any                       # weather SiteBatch (fixed per bin)
    spec: FeatureSpec
    T: int                           # window length in steps
    cap: int                         # ring capacity (bucketed >= T)
    n: int
    n_pad: int
    t0: float                        # window start (now - train_window)
    t_hi: float                      # watermark: end of aligned history
    prior: np.ndarray                # per-series store count < t_hi
    ring: Any = None                 # device (n_pad, cap) f32 targets
    filled: Any = None               # device (n_pad, cap) bool
    ring_t: Any = None               # device (n_pad, cap) f32 temperatures
    y_win: Any = None                # device (n_pad, T) f32, window-masked
    y_tail: Any = None               # device (n_pad, warm_s) score window
    t_tail: Any = None               # device (n_pad, warm_s) temp window
    targets_host: Optional[np.ndarray] = None   # f64 rows (cold train path)
    temps_host: Optional[np.ndarray] = None     # f64 rows (cold train path)
    #: (ids(mo), stacked_dev, mu_dev, sd_dev, refs) — refs keep the
    #: matched dicts alive so the id tuple cannot alias recycled objects
    trained: Optional[tuple] = None
    param_cache: Optional[tuple] = None


class FleetRuntime:
    """Owns per-bin device state across polls; created by ``FleetExecutor``
    and threaded into ``fleet_train`` / ``fleet_score`` of models that set
    ``SUPPORTS_RUNTIME``. Every public entry returns None to send the
    caller down the unchanged cold path."""

    def __init__(self, system, *, max_states: int = 32,
                 max_delta_steps: int = 512):
        self.system = system
        self.max_states = int(max_states)
        self.max_delta_steps = int(max_delta_steps)
        self._states: "OrderedDict[tuple, _BinState]" = OrderedDict()
        self._no_rollout: set = set()    # (cls, spec) with no device predictor
        self.last_stats: Dict[str, Any] = {}
        # lifetime counters (benchmarks/tests)
        self.cold_loads = 0
        self.warm_loads = 0
        self.invalidations = 0

    # ------------- telemetry -------------
    def _note(self, mode: str, delta_rows: int, reason: str = "") -> None:
        self.last_stats = {"runtime": mode, "cache_hit": mode == "warm",
                           "delta_rows": delta_rows}
        if reason:
            self.last_stats["runtime_reason"] = reason

    def pop_stats(self) -> Dict[str, Any]:
        out, self.last_stats = self.last_stats, {}
        return out

    # ------------- bin loading -------------
    @staticmethod
    def _merged(cls, instances) -> dict:
        return {**cls.DEFAULTS, **instances[0].user_params}

    def _load(self, cls, instances, up) -> Optional[_BinState]:
        if str(up.get("runtime", "on")).lower() == "off":
            self._note("off", 0)
            return None
        spec = FeatureSpec.from_params(up)
        now = float(up.get("now", 0.0))
        # a bin shares ONE window (executor bins share user_params_key, so
        # the dicts are equal); direct callers mixing nows/params fall
        # back to the cold path (which groups / fails loudly as designed)
        first = instances[0].user_params
        for inst in instances[1:]:
            if inst.user_params != first:
                self._note("cold", 0, "mixed bin params")
                return None
        step = spec.step
        t0 = now - float(up["train_window_days"]) * DAY
        T = regular_grid(t0, now, step).size
        if abs(T * step - (now - t0)) > 1e-6 * step:
            # a window that is not a whole number of steps makes the cold
            # grid origin and the ring watermark live on different bin
            # lattices — stay on the cold path rather than risk off-by-eps
            # bin assignment for boundary points
            self._note("cold", 0, "fractional window")
            return None
        ids = tuple(inst.context.ts_id for inst in instances)
        key = (ids, spec, T)
        state = self._states.get(key)
        if state is not None:
            self._states.move_to_end(key)
            if now == state.t_hi:                       # same-poll re-use
                self._note("warm", 0)
                return state
            if now > state.t_hi:
                k = (now - state.t_hi) / step
                d = int(round(k))
                aligned = d >= 1 and abs(k - d) < 1e-9 * max(1.0, abs(k))
                if aligned and d < min(T, self.max_delta_steps):
                    got = self._advance(state, d, t0, now)
                    if got is not None:
                        self._note("warm", d)
                        return got
                    reason = "late data behind watermark"
                elif aligned:
                    reason = "delta spans window"
                else:
                    reason = "misaligned now"
            else:
                reason = "now regression"
            self.invalidations += 1
            del self._states[key]
        else:
            reason = "first load"
        state = self._build(key, ids, instances, spec, t0, now, T)
        self._note("cold", T, reason)
        return state

    def _advance(self, state: _BinState, d: int, t0: float, now: float
                 ) -> Optional[_BinState]:
        """Watermark-delta poll: one O(log n + delta) store read, one
        jitted ring update. Returns None when a late append invalidates."""
        with get_tracer().span("runtime.advance"):
            raw, prior = self.system.store.read_many(
                state.ids, end=now, since=state.t_hi, prior_counts=True)
            if not np.array_equal(prior, state.prior):
                return None         # out-of-order append behind watermark
            vals, mask = align_delta(raw, state.t_hi, now, state.spec.step)
            pad = state.n_pad - state.n
            vals32 = edge_pad(vals.astype(np.float32), pad)
            mask_p = edge_pad(mask, pad)
            if state.spec.use_weather:      # observed temps at the d new steps
                tnew = state.sites.temperature(
                    state.t_hi + state.spec.step * np.arange(d))
                tnew = edge_pad(tnew.astype(np.float32), pad)
            else:
                tnew = np.zeros((state.n_pad, d), np.float32)
            warm_s = max(state.spec.target_lags, state.spec.weather_lags) + 1
            upd = _cached_program(_UPDATE_FNS, (d, state.T, warm_s),
                                  partial(_make_update, d, state.T, warm_s))
            (state.ring, state.filled, state.ring_t, state.y_win,
             state.y_tail, state.t_tail) = upd(
                state.ring, state.filled, state.ring_t, vals32, mask_p, tnew)
            state.prior = prior + np.asarray([t.size for t, _ in raw],
                                             np.int64)
            state.t0, state.t_hi = t0, now
            state.targets_host = state.temps_host = None   # cold-build only
            self.warm_loads += 1
            return state

    def _build(self, key, ids, instances, spec: FeatureSpec, t0: float,
               now: float, T: int) -> _BinState:
        """Cold build: one full-window batched read (the same one the cold
        path issues) plus one vectorized observed-temperature call;
        host-aligned rows kept in f64 for the cold train path, rings
        uploaded once."""
        with get_tracer().span("runtime.build", n=len(ids)):
            return self._build_inner(key, ids, instances, spec, t0, now, T)

    def _build_inner(self, key, ids, instances, spec: FeatureSpec,
                     t0: float, now: float, T: int) -> _BinState:
        import jax.numpy as jnp
        ctxs = [inst.context for inst in instances]
        grid, targets, mask, prior = fleet_window(
            self.system, ctxs, t0, now, spec.step)
        ents = [c.entity for c in ctxs]
        sites = self.system.weather.sites([e.lat for e in ents],
                                          [e.lon for e in ents])
        n = len(ids)
        temps = sites.temperature(grid) if spec.use_weather \
            else np.zeros((n, T))
        n_pad = bucket_n(n)
        cap = bucket_n(T)
        ring_h = np.zeros((n, cap), np.float32)
        fill_h = np.zeros((n, cap), bool)
        temp_h = np.zeros((n, cap), np.float32)
        ring_h[:, cap - T:] = targets.astype(np.float32)
        fill_h[:, cap - T:] = mask
        temp_h[:, cap - T:] = temps.astype(np.float32)
        ring = jnp.asarray(edge_pad(ring_h, n_pad - n))
        filled = jnp.asarray(edge_pad(fill_h, n_pad - n))
        ring_t = jnp.asarray(edge_pad(temp_h, n_pad - n))
        warm_s = max(spec.target_lags, spec.weather_lags) + 1
        state = _BinState(key=key, ids=ids, sites=sites, spec=spec, T=T,
                          cap=cap, n=n, n_pad=n_pad, t0=t0, t_hi=now,
                          prior=prior, ring=ring, filled=filled,
                          ring_t=ring_t, y_win=ring[:, cap - T:],
                          y_tail=ring[:, cap - warm_s:],
                          t_tail=ring_t[:, cap - warm_s:],
                          targets_host=targets, temps_host=temps)
        self._states[key] = state
        while len(self._states) > self.max_states:
            self._states.popitem(last=False)
        self.cold_loads += 1
        return state

    # ------------- training -------------
    def fleet_xy(self, cls, instances) -> Optional[tuple]:
        """Replacement for ``ForecastModelBase._fleet_xy``: returns
        ``(X, y, mu, sd, state)`` or None (cold path). A freshly built
        state answers with the EXACT host-f64 design-matrix path (single
        polls stay bitwise-identical to the pre-runtime executor); warm
        states assemble on device from the ring."""
        up = self._merged(cls, instances)
        state = self._load(cls, instances, up)
        if state is None:
            return None
        spec, T, n = state.spec, state.T, state.n
        if state.targets_host is not None:      # cold build this poll
            from ..forecast.features import design_matrix
            grid = regular_grid(state.t0, state.t_hi, spec.step)
            Xs, ys, mus, sds = [], [], [], []
            for i in range(n):
                X, y = design_matrix(spec, grid, state.targets_host[i],
                                     state.temps_host[i])
                mu, sd = X.mean(0), X.std(0) + 1e-8
                Xs.append((X - mu) / sd)
                ys.append(y), mus.append(mu), sds.append(sd)
            return (np.stack(Xs), np.stack(ys), np.stack(mus),
                    np.stack(sds), state)
        import jax.numpy as jnp
        grid = regular_grid(state.t0, state.t_hi, spec.step)
        cal = calendar_features(grid).astype(np.float32) \
            if spec.use_calendar else np.zeros((T, 5), np.float32)
        asm = _cached_program(_ASSEMBLE_FNS, (spec, T),
                              partial(_make_assemble, spec, T))
        X, y, mu, sd = asm(state.y_win, state.ring_t[:, state.cap - T:],
                           jnp.asarray(cal))
        return X[:n], y[:n], mu[:n], sd[:n], state

    def note_trained(self, state: _BinState, params, mu, sd, out) -> None:
        """Train->score handoff: remember the stacked DEVICE params against
        the identity of the per-instance model objects just persisted, so
        a same-cycle (or any later) score poll of those versions never
        re-uploads or re-stacks them. The dicts themselves ride along in
        the tuple: identity matching is only sound while the matched
        objects are provably alive (a deduplicated retrain discards the
        fresh dicts, and a recycled address must never alias them)."""
        state.trained = (tuple(id(mo) for mo in out), params, mu, sd, out)
        state.param_cache = None

    # ------------- scoring -------------
    def _stacked(self, state: _BinState, model_objects) -> tuple:
        import jax.numpy as jnp
        key = tuple(id(mo) for mo in model_objects)
        # id-tuple matching is sound because both caches hold the matched
        # dicts alive (last element), so an id cannot be recycled to a
        # different live object
        if state.param_cache is not None and state.param_cache[0] == key:
            _, stacked, mu, sd, _ = state.param_cache
            return stacked, mu, sd
        if state.trained is not None and state.trained[0] == key:
            _, stacked, mu, sd, _ = state.trained
        else:                            # stack once, then cache
            stacked = {k: np.stack([m["params"][k] for m in model_objects])
                       for k in model_objects[0]["params"]}
            mu = np.stack([m["mu"] for m in model_objects])
            sd = np.stack([m["sd"] for m in model_objects])
        # device-resident AND bucket-padded from here on: later warm polls
        # dispatch the rollout without re-uploading or re-padding a single
        # parameter
        pad = state.n_pad - state.n
        stacked = {k: edge_pad(jnp.asarray(v), pad)
                   for k, v in stacked.items()}
        mu = edge_pad(jnp.asarray(mu, jnp.float32), pad)
        sd = edge_pad(jnp.asarray(sd, jnp.float32), pad)
        state.param_cache = (key, stacked, mu, sd, list(model_objects))
        return stacked, mu, sd

    def fleet_score(self, cls, instances, model_objects, *,
                    mesh=None) -> Optional[list]:
        """Device-resident scoring: trailing windows come from the ring
        (no store read, no host stacking), params from the train handoff
        or a once-per-version stacking. Returns None to fall back to the
        cold path (runtime off, host rollout requested, no traceable
        predictor, or a bin the runtime cannot key)."""
        up = self._merged(cls, instances)
        if up.get("rollout", "device") == "host":
            self._note("off", 0, "host rollout requested")
            return None
        if len(model_objects) != len(instances):
            return None
        spec0 = FeatureSpec.from_params(up)
        if (cls, spec0) in self._no_rollout:
            # a host-only model (no traceable predictor) must not pay ring
            # maintenance AND the cold path every poll
            self._note("off", 0, "no device predictor")
            return None
        state = self._load(cls, instances, up)
        if state is None:
            return None
        spec, n = state.spec, state.n
        H = int(up["horizon"])
        now = state.t_hi
        stacked, mu, sd = self._stacked(state, model_objects)
        # all inputs pre-padded to the shape bucket: the rollout's own
        # bucketing becomes a no-op and the only per-poll host work left
        # is the horizon weather
        fut_t = now + spec.step * np.arange(0, H)
        temps_future = edge_pad(state.sites.forecast(now, fut_t),
                                state.n_pad - n)
        vals = cls._device_rollout(spec, up, stacked, mu, sd, state.y_tail,
                                   state.t_tail, temps_future,
                                   float(fut_t[0]), H, mesh=mesh)
        if vals is None:                 # no traceable predictor: remember
            self._no_rollout.add((cls, spec0))
            return None
        return [(fut_t, vals[i]) for i in range(n)]

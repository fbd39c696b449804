"""The benchmark's traffic: one smart-grid site and its irregular meter feed,
made from ``--seed`` alone.

Modelled on the program's site generator (``timeseries/ingest.py``:
``build_site`` and ``demand_profile``): prosumers under feeders under one
substation, hourly energy readings with a daily and weekly shape and a
temperature response, each timestamp jittered by up to ``jitter_h`` hours
and about ``drop`` of the readings lost. Unlike that generator, every draw
here is addressed by ``(seed, sensor, hour)`` through a counter-based hash,
so the readings of any simulated hour are a pure function of those three
and the window can ingest hour after hour without replaying a generator.

The weather is the program's input too: ``Castor(weather_seed=seed)``
serves it, and :class:`Weather` below is an independent copy of that
service's arithmetic for the reference (``timeseries/weather.py``).
"""
from __future__ import annotations

import numpy as np

HOUR = 3600.0
DAY = 24 * HOUR
YEAR = 365.0 * DAY

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)
_MASK64 = (1 << 64) - 1

# salts of the independent draws
_S_BASE, _S_MORNING, _S_EVENING, _S_WEEKEND = 1, 2, 3, 4
_S_DROP, _S_JITTER, _S_NOISE = 5, 6, 7


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer (wrapping uint64)."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * _M1
        x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


def uniforms(seed: int, salt: int, sensors, hours) -> np.ndarray:
    """Uniforms in [0, 1) addressed by (seed, salt, sensor, hour); the
    result broadcasts ``sensors`` against ``hours``."""
    s = np.asarray(sensors, np.uint64)
    h = np.asarray(hours, np.int64).astype(np.uint64)
    key = np.uint64(((seed & _MASK64) * 0x2545F4914F6CDD1D + salt) & _MASK64)
    with np.errstate(over="ignore"):
        x = _mix64(_mix64(key ^ (s * _GOLD)) + h * _M2)
    return (x >> np.uint64(11)).astype(np.float64) / 2.0 ** 53


def normals(seed: int, salt: int, sensors, hours) -> np.ndarray:
    u1 = 1.0 - uniforms(seed, salt, sensors, hours)            # (0, 1]
    u2 = uniforms(seed, salt + 1000, sensors, hours)
    return np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)


# ------------------------------------------------------------------ weather

def _counter_normals(keys, salt: int, idx) -> np.ndarray:
    with np.errstate(over="ignore"):
        c = (keys[:, None] * _GOLD + np.uint64(salt & _MASK64)
             + idx.astype(np.uint64) * _M2)
        h1 = _mix64(c * np.uint64(2))
        h2 = _mix64(c * np.uint64(2) + np.uint64(1))
    u1 = ((h1 >> np.uint64(11)).astype(np.float64) + 1.0) / 2.0 ** 53
    u2 = (h2 >> np.uint64(11)).astype(np.float64) / 2.0 ** 53
    return np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)


class Weather:
    """Observed and forecast temperatures at a fixed set of sites: the
    arithmetic of the program's ``WeatherService(seed)``, copied so that
    the reference depends on nothing the program computes."""

    def __init__(self, seed: int, lats, lons):
        keys = [(seed * 1_000_003 + int(la * 1e4) * 7919
                 + int(lo * 1e4) * 104729) % (2 ** 31 - 1)
                for la, lo in zip(lats, lons)]
        self.keys = np.asarray(keys, np.uint64)
        p = np.empty((len(keys), 4))
        for i, k in enumerate(keys):
            rng = np.random.default_rng(k)
            p[i] = (rng.uniform(0, 2 * np.pi), rng.uniform(4, 8),
                    rng.uniform(8, 14), rng.uniform(8, 18))
        self.phase, self.amp_d, self.amp_y, self.base = (
            p[:, j:j + 1] for j in range(4))

    def temperature(self, times, rows=None) -> np.ndarray:
        """(N, T) observed temperatures at ``times`` (of the sites
        ``rows``, all by default)."""
        r = slice(None) if rows is None else np.asarray(rows)
        t = np.asarray(times, np.float64)
        phase = self.phase[r]
        seasonal = self.amp_y[r] * np.sin(2 * np.pi * t / YEAR + phase)
        diurnal = self.amp_d[r] * np.sin(2 * np.pi * t / DAY - np.pi / 2)
        slow = 2.0 * np.sin(2 * np.pi * t / (11 * DAY) + phase * 0.7)
        obs = 0.3 * _counter_normals(self.keys[r], 0x5DEECE66D,
                                     np.round(t).astype(np.int64))
        return self.base[r] + seasonal + diurnal + slow + obs

    def forecast(self, issued_at: float, times, rows=None) -> np.ndarray:
        """(N, H) forecast issued at ``issued_at`` for ``times``."""
        r = slice(None) if rows is None else np.asarray(rows)
        t = np.asarray(times, np.float64)
        lead_days = np.maximum(t - issued_at, 0.0) / DAY
        err = 0.2 * _counter_normals(self.keys[r], int(issued_at) % 65521,
                                     np.arange(t.size))
        return self.temperature(t, rows) + err * np.sqrt(1.0 + lead_days)


# ------------------------------------------------------------------ the site

class Site:
    """One substation, ``n_feeders`` feeders and ``n_prosumers`` metered
    prosumers. Prosumer ``p`` hangs under feeder ``p % n_feeders``."""

    def __init__(self, traffic: dict, seed: int):
        s = traffic["site"]
        self.seed = int(seed)
        self.name = str(s.get("name", "S"))
        self.n = int(s["n_prosumers"])
        self.n_feeders = int(s["n_feeders"])
        self.jitter_h = float(traffic["jitter_h"])
        self.drop = float(traffic["drop"])
        self.sub_lat, self.sub_lon = 35.0, 33.0
        p = np.arange(self.n)
        f = p % self.n_feeders
        self.lats = self.sub_lat + 0.001 * f + 0.0001 * p
        self.lons = np.full(self.n, self.sub_lon)
        self.names = [f"{self.name}_PRO_{i:05d}" for i in range(self.n)]
        self.ts_ids = [f"raw::{nm}::load" for nm in self.names]
        # per-prosumer demand shape (demand_profile's draws)
        self.base = 1.0 + 5.0 * uniforms(seed, _S_BASE, p, 0)
        self.morning = 7.0 + 2.0 * uniforms(seed, _S_MORNING, p, 0)
        self.evening = 18.0 + 2.0 * uniforms(seed, _S_EVENING, p, 0)
        self.weekend = 0.7 + 0.2 * uniforms(seed, _S_WEEKEND, p, 0)
        self.weather = Weather(seed, self.lats, self.lons)

    def readings(self, k0: int, k1: int, sensors=None):
        """Readings of nominal hours ``[k0, k1)``: ``(times, values, kept)``,
        each ``(n_sensors, k1 - k0)``; ``kept`` is False where the reading
        was lost."""
        p = np.arange(self.n) if sensors is None else np.asarray(sensors)
        k = np.arange(k0, k1)
        t = k * HOUR
        temp = self.weather.temperature(t, p)
        hod = (t % DAY) / HOUR
        dow = (t // DAY) % 7
        morning = np.exp(-0.5 * ((hod - self.morning[p, None]) / 1.5) ** 2)
        evening = np.exp(-0.5 * ((hod - self.evening[p, None]) / 2.0) ** 2)
        weekend = np.where(dow >= 5, self.weekend[p, None], 1.0)
        resp = 0.08 * np.maximum(temp - 22.0, 0) \
            + 0.05 * np.maximum(16.0 - temp, 0)
        noise = 0.05 * normals(self.seed, _S_NOISE, p[:, None], k)
        load = np.maximum(self.base[p, None] * (0.4 + morning + 1.2 * evening)
                          * weekend + resp + noise, 0.01)
        kept = uniforms(self.seed, _S_DROP, p[:, None], k) >= self.drop
        jit = (2.0 * uniforms(self.seed, _S_JITTER, p[:, None], k) - 1.0) \
            * self.jitter_h * HOUR
        return t + jit, load, kept

    def stamped(self, t_lo: float, t_hi: float, sensors=None):
        """``(times, values, ok)`` of the nominal hours around
        ``[t_lo, t_hi)``, each ``(n_sensors, hours)``: ``ok`` marks the kept
        readings stamped inside the range."""
        pad = int(np.ceil(self.jitter_h)) + 1
        k0 = int(np.floor(t_lo / HOUR)) - pad
        k1 = int(np.ceil(t_hi / HOUR)) + pad
        t, v, kept = self.readings(k0, k1, sensors)
        ok = kept & (t >= t_lo) & (t < t_hi)
        return t, v, ok

    def build(self, castor) -> None:
        """Topology and links in the castor (paper Fig. 1 steps 1-2)."""
        castor.add_signal("ENERGY_LOAD", "kWh", "energy demand per interval")
        sub = castor.add_entity(f"{self.name}_SUB_0", "SUBSTATION",
                                lat=self.sub_lat, lon=self.sub_lon)
        feeders = [castor.add_entity(f"{self.name}_FD_{f}", "FEEDER",
                                     lat=self.sub_lat + 0.001 * f,
                                     lon=self.sub_lon, parent=sub.name)
                   for f in range(self.n_feeders)]
        for i, nm in enumerate(self.names):
            castor.add_entity(nm, "PROSUMER", lat=float(self.lats[i]),
                              lon=float(self.lons[i]),
                              parent=feeders[i % self.n_feeders].name)
            castor.link(self.ts_ids[i], "ENERGY_LOAD", nm)

    def ingest_history(self, castor, t_end: float, fills,
                       block: int = 256) -> None:
        """Every reading stamped in ``[0, t_end)``, left in the store as
        hourly ingest leaves it some ``fills[i]`` readings after sensor
        ``i``'s last flush: the older readings in one sorted segment
        (``TimeSeriesStore.compact``), those of the hours that hold the
        newest ``fills[i]`` in its unflushed tail, appended hour by hour
        across the sensors by :meth:`ingest`, as the window appends them."""
        hours = np.zeros(self.n, np.int64)
        for lo in range(0, self.n, block):
            rows = np.arange(lo, min(self.n, lo + block))
            t, v, ok = self.stamped(0.0, t_end, rows)
            for r, i in enumerate(rows):
                order = np.argsort(t[r][ok[r]], kind="stable")
                ti, vi = t[r][ok[r]][order], v[r][ok[r]][order]
                f = min(int(fills[i]), ti.size)
                if f:
                    hours[i] = np.ceil((t_end - ti[ti.size - f]) / HOUR)
                old = ti < t_end - hours[i] * HOUR
                castor.ingest(self.ts_ids[i], ti[old], vi[old])
                castor.store.compact(self.ts_ids[i])
        for h in range(int(hours.max()), 0, -1):
            self.ingest(castor, t_end - h * HOUR, t_end - (h - 1) * HOUR,
                        np.flatnonzero(hours >= h))

    def ingest(self, castor, t_lo: float, t_hi: float,
               sensors=None) -> int:
        """Append every reading stamped in ``[t_lo, t_hi)`` through
        ``Castor.ingest``, one call per sensor (of ``sensors``, all by
        default). Returns the count."""
        rows = np.arange(self.n) if sensors is None else np.asarray(sensors)
        t, v, ok = self.stamped(t_lo, t_hi, rows)
        order = np.argsort(np.where(ok, t, np.inf), axis=1, kind="stable")
        t = np.take_along_axis(t, order, 1)
        v = np.take_along_axis(v, order, 1)
        cnt = ok.sum(1)
        total = 0
        for i, row in enumerate(rows.tolist()):
            c = int(cnt[i])
            if c:
                total += castor.ingest(self.ts_ids[row], t[i, :c], v[i, :c])
        return total

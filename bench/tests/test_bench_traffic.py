"""The traffic generator is a pure function of the seed."""
import numpy as np
import pytest

from traffic import HOUR, Site

MIX = {"site": {"n_prosumers": 16, "n_feeders": 4}, "jitter_h": 0.1,
       "drop": 0.02}


def test_same_seed_same_readings():
    a = Site(MIX, 2 ** 33 + 5).readings(100, 400)
    b = Site(MIX, 2 ** 33 + 5).readings(100, 400)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_other_seed_other_readings_same_amount_of_work():
    t1, v1, k1 = Site(MIX, 1).readings(0, 2000)
    t2, v2, k2 = Site(MIX, 2).readings(0, 2000)
    assert not np.array_equal(v1, v2)
    assert v1.shape == v2.shape
    assert abs(k1.mean() - k2.mean()) < 0.01


def test_readings_of_an_hour_do_not_depend_on_the_range_asked():
    s = Site(MIX, 9)
    whole = s.readings(0, 50)
    part = s.readings(20, 30)
    for x, y in zip(whole, part):
        np.testing.assert_array_equal(x[:, 20:30], y)


def test_jitter_drop_and_stamped_range():
    s = Site(MIX, 4)
    t, v, kept = s.readings(0, 5000)
    nominal = np.arange(5000) * HOUR
    assert np.abs(t - nominal).max() <= 0.1 * HOUR
    assert 0.015 < 1 - kept.mean() < 0.025
    assert (v > 0).all()
    t, v, ok = s.stamped(100 * HOUR, 200 * HOUR)
    assert ((t[ok] >= 100 * HOUR) & (t[ok] < 200 * HOUR)).all()
    assert ok.sum(1).min() >= 90


def test_weather_copy_matches_the_programs_service():
    from repro.timeseries.weather import WeatherService
    s = Site(MIX, 123456789)
    w = WeatherService(seed=123456789)
    times = 35 * 86400.0 + HOUR * np.arange(-30, 30)
    np.testing.assert_array_equal(
        s.weather.temperature(times),
        w.temperature_many(list(s.lats), list(s.lons), times))
    np.testing.assert_array_equal(
        s.weather.forecast(times[30], times[30:]),
        w.forecast_many(list(s.lats), list(s.lons), times[30], times[30:]))


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11, 2 ** 40])
def test_large_seeds(seed):
    t, v, k = Site(MIX, seed).readings(0, 10)
    assert np.isfinite(v).all()

"""The reduction from trace events to busy time, idle gaps and kernels."""
import pytest

import trace_reduce as tr


def test_union_merges_overlap_and_nesting():
    got = tr.union([(5, 6), (0, 2), (1, 3), (1.5, 1.7), (7, 7), (3, 4)])
    assert got == [(0, 4), (5, 6)]


def test_covered_clips_to_the_window():
    ivs = [(0, 2), (1, 3), (5, 9)]
    assert tr.covered(ivs, 1, 6) == pytest.approx(3.0)


def test_gaps_between_busy_intervals():
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def test_reduce_events_busy_idle_ops_and_labels():
    devices = {
        "/device:TPU:0": [("fusion", 1.0, 2.0), ("fleet_mlp", 1.5, 3.0),
                          ("copy", 9.0, 11.0)],
        "/device:TPU:1": [("fusion", 0.0, 10.0)],
    }
    marks = [(0.5, 4.0, "bench.tick"), (4.0, 10.0, "bench.ingest")]
    r = tr.reduce_events(devices, marks)
    assert r["window_s"] == pytest.approx(9.5)
    assert r["busy_s"]["/device:TPU:0"] == pytest.approx(2.0 + 1.0)
    assert r["busy_s"]["/device:TPU:1"] == pytest.approx(9.5)
    assert r["mean_busy_s"] == pytest.approx(6.25)
    # op time is clipped to the window and summed over devices
    assert r["ops_s"]["fusion"] == pytest.approx(1.0 + 9.5)
    assert r["ops_n"]["fleet_mlp"] == 1
    assert r["device_ops"][0][0] == "fusion"
    # TPU:0 idles in [0.5, 1) during a tick and [3, 9) mostly in ingest
    assert r["idle_gaps"][0] == ["bench.ingest (/device:TPU:0)",
                                 pytest.approx(6.0)]
    assert ["bench.tick (/device:TPU:0)", pytest.approx(0.5)] \
        in r["idle_gaps"]


def test_reduce_refuses_a_trace_without_devices_or_marks():
    with pytest.raises(ValueError):
        tr.reduce_events({}, [(0, 1, "bench.tick")])
    with pytest.raises(ValueError):
        tr.reduce_events({"/device:TPU:0": []}, [])


def test_recorded_chip_trace():
    """Three ticks of an 8-prosumer LR fleet traced on one TPU v5e."""
    from pathlib import Path
    d = Path(__file__).parent / "data"
    devices, marks = tr.read_xplane(d)
    assert list(devices) == ["/device:TPU:0"]
    assert sorted(n for _, _, n in marks) == ["bench.ingest"] * 3 \
        + ["bench.tick"] * 3
    r = tr.reduce_trace(d)
    busy = r["busy_s"]["/device:TPU:0"]
    assert 0 < busy < r["window_s"] < 1.0
    # the rollout's while loop holds most of the device time; its body's
    # ops nest inside it and count once in the busy union
    assert r["device_ops"][0][0].startswith("%while")
    assert busy <= sum(r["ops_s"].values())
    idle = r["window_s"] - busy
    assert sum(d for _, d in r["idle_gaps"]) <= idle * (1 + 1e-9)

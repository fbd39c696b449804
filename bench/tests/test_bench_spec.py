"""BENCHMARK.json against its format rules, and a cell, a configuration, a
traffic mix and a per-layer metric added by files alone."""
import json
import re
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import run as bench

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_cells_configs_and_chips():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in configs.values():
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]


def test_bounds_and_metric_sources():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = bench.load_cell(ROOT, cell)
    assert c["config"]["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == cell)
    assert "unpersisted" in c["limits"]
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in c["per_layer"]:
        assert callable(bench.load_reader(m["name"]))


def test_a_new_cell_takes_new_files_only(tmp_path, monkeypatch):
    """Copy the benchmark's files, add a configuration, a mix, a cell with
    its limits and a per-layer metric, and drive the new cell on the CPU
    without touching any existing file."""
    b = tmp_path / "bench"
    for d in ("configs", "mixes", "limits", "metrics", "models"):
        shutil.copytree(ROOT / "bench" / d, b / d)
    cfg = json.loads((b / "configs" / "lr_ridge.json").read_text())
    cfg.update(name="lr_short", user_params={
        **cfg["user_params"], "train_window_days": 7})
    (b / "configs" / "lr_short.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "mixes" / "hourly_score_4096.json").read_text())
    mix["site"].update(n_prosumers=5, n_feeders=2)
    mix.update(history_days=9, check_ticks=3)
    (b / "mixes" / "tiny.json").write_text(json.dumps(mix))
    (b / "limits" / "lr_short.tiny.json").write_text(json.dumps(
        json.loads((b / "limits" / "lr.hourly_score_4096.json").read_text())))
    (b / "metrics" / "ticks_seen.py").write_text(
        "def read(run):\n    return float(len(run.ticks))\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "lr_short", "source": "x",
                            "file": "bench/configs/lr_short.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "lr_short.tiny", "config": "lr_short",
                              "traffic": "tiny", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "ticks_seen", "unit": "ticks",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "jobs_per_s",
                              "workloads": ["lr_short.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = bench.load_cell(tmp_path, "lr_short.tiny", bench=b)
    assert cell["config"]["user_params"]["train_window_days"] == 7
    assert cell["traffic"]["site"]["n_prosumers"] == 5
    assert [m["name"] for m in cell["per_layer"]] == ["ticks_seen"]
    out = bench.execute(tmp_path, cell, 3, 0.3, 0, {}, require_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in SPEC["end_to_end"]
                                   if "workloads" not in m}
    run = type("R", (), {"ticks": [1, 2, 3]})()
    assert bench.load_reader("ticks_seen", b)(run) == 3.0


#: a model of a new reference kind: one ridge model made from the seed and
#: shared by every deployment, compared with its float64 rollout
SHARED_LR = '''
import numpy as np

import checks
import reference as ref

CALLS = []


def _want(found, theta):
    site, b = found["site"], found["boundaries"]
    spec = ref.Spec(found["config"]["user_params"])
    ys, ts, fs = ref.score_inputs(site, spec, b)
    th = np.broadcast_to(theta, (site.n, theta.size))
    return ref.rollout(lambda x: ref.lr_predict(th, x), spec, ys, ts, fs, b)


def _theta(found):
    return np.asarray(found["versions"][0][0].params["params"]["theta"],
                      np.float64)


def compare(found):
    gap = np.abs(found["got"] - _want(found, _theta(found)))
    return {"forecast_gap": float(gap[checks.scored(found)].max())}


def control(found):
    theta = _theta(found).astype(np.float16).astype(np.float64)
    gap = np.abs(found["got"] - _want(found, theta))
    return {"forecast_gap": float(gap[checks.scored(found)].max())}


def score_flops(config, n):
    spec = ref.Spec(config["user_params"])
    return 2.0 * (spec.n_features + 1) * n * spec.horizon


def seed_versions(run):
    CALLS.append(run.seed)
    f = ref.Spec(run.config["user_params"]).n_features
    theta = np.random.default_rng(run.seed).normal(0.0, 0.01, f + 1)
    theta[0], theta[-1] = 0.5, 1.0
    model = {"kind": "LR", "params": {"theta": theta.astype(np.float32)},
             "mu": np.zeros(f), "sd": np.ones(f), "y_scale": 10.0,
             "resid_q": np.array([-0.1, 0.1])}
    for name in run.names:
        run.castor.versions.save(name, model, trained_at=run.now0)
    return [model] * len(run.names)
'''


def test_a_new_model_takes_new_files_only(tmp_path, monkeypatch):
    """A configuration of a new reference kind brings its own
    ``bench/models/<kind>.py`` (comparison, control, one seeded version
    shared by every deployment, operations): the harness drives its cell
    on the CPU without touching any existing file."""
    import calibrate
    import checks
    b = tmp_path / "bench"
    for d in ("configs", "mixes", "limits", "metrics", "models"):
        shutil.copytree(ROOT / "bench" / d, b / d)
    (b / "models" / "shared_lr.py").write_text(SHARED_LR)
    cfg = json.loads((b / "configs" / "lr_ridge.json").read_text())
    cfg.update(name="lr_shared", reference="shared_lr", user_params={
        **cfg["user_params"], "train_window_days": 7})
    (b / "configs" / "lr_shared.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "mixes" / "hourly_score_4096.json").read_text())
    mix["site"].update(n_prosumers=5, n_feeders=2)
    mix.update(history_days=9, check_ticks=3, weights="seed")
    del mix["train_every_h"]
    (b / "mixes" / "tiny_seeded.json").write_text(json.dumps(mix))
    (b / "limits" / "lr_shared.tiny_seeded.json").write_text(json.dumps(
        {"unpersisted": 0, "forecast_gap": 1e-3}))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "lr_shared", "source": "x",
                            "file": "bench/configs/lr_shared.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "lr_shared.tiny_seeded",
                              "config": "lr_shared", "traffic": "tiny_seeded",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "score_mfu.shared", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "x", "moves": "jobs_per_s",
                              "workloads": ["lr_shared.tiny_seeded"]})
    shutil.copy(b / "metrics" / "score_mfu.py",
                b / "metrics" / "score_mfu.shared.py")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = bench.load_cell(tmp_path, "lr_shared.tiny_seeded", bench=b)
    found = []
    collect = checks.collect
    monkeypatch.setattr(checks, "collect",
                        lambda run: found.append(collect(run)) or found[-1])
    out = bench.execute(tmp_path, cell, 3, 0.3, 0, {}, require_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {"unpersisted", "forecast_gap"}
    assert cell["model"].CALLS == [3]
    versions = found[0]["versions"]
    assert len({id(v.params) for vs in versions for v in vs}) == 1
    program, control = calibrate.readings(found[0], cell)
    assert checks.decide(program) and set(control) == {"forecast_gap"}

    run = SimpleNamespace(trace={"busy_s": {"/device:TPU:0": 0.5},
                                 "window_s": 2.0},
                          traced_ticks=4, config=cell["config"],
                          site=SimpleNamespace(n=5), cell=cell,
                          peaks={"flops_per_s": 1e9})
    want = 100.0 * (2.0 * 55 * 5 * 24) * 4 / 2.0 / 1e9
    got = bench.load_reader("score_mfu.shared", b)(run)
    assert got == pytest.approx(want, rel=1e-12)


def test_a_seeded_mix_needs_the_models_seed_versions():
    cell = bench.load_cell(ROOT, "lr.hourly_score_4096")
    cell["traffic"]["weights"] = "seed"
    with pytest.raises(SystemExit, match=r"lr\.py has no seed_versions"):
        bench.Run(cell, 1, require_chip=False)


@pytest.mark.parametrize("cell", ["ann.cyprus531_score",
                                  "lr.hourly_score_4096"])
def test_score_mfu_takes_the_models_operations(cell):
    """The ANN's whole score step is ``work.ann_score_flops`` a tick; LR
    counts none, and the reader reads nothing there."""
    import work
    c = bench.load_cell(ROOT, cell)
    run = SimpleNamespace(trace={"busy_s": {"/device:TPU:0": 1.5},
                                 "window_s": 3.0},
                          traced_ticks=40, config=c["config"],
                          site=SimpleNamespace(n=531), cell=c,
                          peaks=bench.peak_of("TPU v5 lite"))
    got = bench.load_reader("score_mfu")(run)
    if c["config"]["reference"] == "lr":
        assert got is None
        return
    want = work.ann_score_flops(531, 54, 512, 4, 24) * 40 / 3.0 \
        / 197e12 * 100.0
    assert got == pytest.approx(want, rel=1e-12)


def test_tick_tail_reads_the_ticks_after_the_profiled_ones():
    read = bench.load_reader("tick_p90_ms.score")
    ticks = [{"tick_s": 9.0}] * 5 + [{"tick_s": 0.1 + 0.001 * i}
                                     for i in range(40)]
    run = SimpleNamespace(ticks=ticks, traced_ticks=5)
    assert read(run) == pytest.approx(
        np.percentile([100.0 + i for i in range(40)], 90))
    assert read(SimpleNamespace(ticks=ticks[:12], traced_ticks=5)) is None

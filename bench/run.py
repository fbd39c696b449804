"""Castor's chip benchmark: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/<config>.json``) and traffic
(``bench/mixes/<traffic>.json``); the per-layer metrics are readers in
``bench/metrics/<metric>.py``, the limits of the correctness check are
in ``bench/limits/<cell>.json``, and what depends on the configuration's
model (its comparison, its control, its versions made from the seed and
its operations) is in ``bench/models/<reference>.py``. Nothing here names
a cell or a model.

Set-up builds the site from the seed and its history, each sensor's
unflushed store tail at its own point of the flush cycle (so the window
starts, and stays, in the state of a site long in service), deploys one
forecaster per prosumer through ``Castor.deploy_for_all``, runs tick 0
(train and score through the normal path) and a few warm ticks, so that
nothing compiles in the window. The window then runs schedule boundaries back to back: the
readings stamped before the next boundary go in through ``Castor.ingest``,
then ``Castor.tick(boundary, executor="fleet")`` runs and returns with
every forecast persisted. After the window, a sample of the persisted
forecasts drawn from the seed is compared with the float64 reference.

The last line of standard output is one JSON object; the numbers compared
and their limits are the last lines of standard error. Without a TPU, or
with another number of chips than the cell asks for, the run exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import checks  # noqa: E402
from traffic import DAY, HOUR, Site  # noqa: E402

GIB = float(1 << 30)


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str, bench: Path = BENCH) -> dict:
    """The cell ``name`` with its configuration, its model's module,
    traffic, limits and the per-layer metrics that read it, all found by
    name from ``BENCHMARK.json`` and the files under ``bench``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    kind = config["reference"]
    traffic = load_json(bench / "mixes" / f"{cell['traffic']}.json")
    limits = load_json(bench / "limits" / f"{name}.json")
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in spec["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"name": name, "bench": bench, "chips": int(cell["chips"]),
            "config": config,
            "model": load_module(bench / "models" / f"{kind}.py",
                                 "bench_model_" + kind),
            "traffic": traffic, "limits": limits, "per_layer": per_layer,
            "end_to_end": end_to_end}


def load_module(path: Path, name: str):
    """The Python file ``path``, imported as ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench: Path = BENCH):
    """``read(run) -> float | None`` from ``bench/metrics/<metric>.py``."""
    return load_module(bench / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_")).read


def peak_of(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def devices_for(chips: int):
    """The accelerator devices, or NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) != chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


def enable_cache() -> str:
    import jax
    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class Run:
    """One run of a cell: the system under test, its traffic and clock.

    ``require_chip=False`` lets the tests drive a run on the CPU; the
    benchmark itself never sets it."""

    def __init__(self, cell: dict, seed: int, *, require_chip: bool = True):
        self.cell, self.seed = cell, int(seed)
        self.config, self.traffic = cell["config"], cell["traffic"]
        # a mix with "weights": "seed" scores versions the benchmark made
        # from the seed, and schedules no training
        seeded = self.traffic.get("weights") == "seed"
        if seeded and not hasattr(cell["model"], "seed_versions"):
            raise SystemExit(
                f"bench: the mix {cell['name']!r} asks for seeded weights, "
                f"and bench/models/{self.config['reference']}.py has no "
                f"seed_versions(run)")
        if require_chip:
            devices_for(cell["chips"])
        from repro.core import Castor
        from repro.core.scheduler import Schedule
        import repro.forecast as forecasters
        tr = self.traffic
        self.site = Site(tr, self.seed)
        self.now0 = float(tr["history_days"]) * DAY
        self.tick_s = float(tr["tick_h"]) * HOUR
        self.castor = c = Castor(weather_seed=self.seed)
        self.site.build(c)
        # sensors joined at spread-out times, so their flush cycles are
        # spread evenly: every boundary finds the tails' fills spread over
        # the store's whole cycle, as in a deployment long in service
        m = c.store.tail_max
        self.site.ingest_history(c, self.now0,
                                 (np.arange(self.site.n) * m) // self.site.n)
        cfg = self.config
        pkg = cfg["package"]
        c.publish(pkg, "1.0", getattr(forecasters, cfg["forecaster"]))

        def every(key):
            h = tr.get(key)
            return 1e12 if h is None else float(h) * HOUR

        c.deploy_for_all(package=pkg, signal="ENERGY_LOAD", name_prefix=pkg,
                         kind="PROSUMER",
                         train=None if seeded else Schedule(
                             self.now0, every("train_every_h")),
                         score=Schedule(self.now0, every("score_every_h")),
                         user_params=dict(cfg["user_params"]))
        self.names = [f"{pkg}-{nm}" for nm in self.site.names]
        # the benchmark's own copy of each deployment's seeded version
        self.seeded = cell["model"].seed_versions(self) if seeded else None
        self.k = 0                      # boundaries ticked so far
        self.ticks = []                 # window ticks: dicts

    def boundary(self, k: int) -> float:
        return self.now0 + k * self.tick_s

    def step(self, annotate: bool = False) -> dict:
        """Ingest up to the next boundary, then tick it."""
        import contextlib
        b = self.boundary(self.k)
        ann = contextlib.nullcontext
        if annotate:
            from jax.profiler import TraceAnnotation as ann
        t0 = time.perf_counter()
        with ann("bench.ingest"):
            if self.k:
                self.site.ingest(self.castor, b - self.tick_s, b)
        t1 = time.perf_counter()
        with ann("bench.tick"):
            res = self.castor.tick(b, executor="fleet")
        t2 = time.perf_counter()
        self.k += 1
        bad = [r for r in res if not r.ok]
        return {"boundary": b, "ingest_s": t1 - t0, "tick_s": t2 - t1,
                "t_end": t2, "jobs": len(res), "failed": len(bad),
                "errors": [f"{r.job.deployment_name} {r.job.task}: {r.error}"
                           for r in bad[:3]]}

    def setup(self) -> None:
        """Tick 0 and the warm ticks; every job must succeed."""
        for _ in range(1 + int(self.traffic["warm_ticks"])):
            t = self.step()
            if t["failed"] or not t["jobs"]:
                raise RuntimeError(f"set-up tick at {t['boundary']}: "
                                   f"{t['failed']}/{t['jobs']} jobs failed: "
                                   f"{t['errors']}")

    def window(self, seconds: float, trace_dir=None) -> None:
        """Closed loop of boundaries until ``seconds`` have passed; the
        profiler, when given a directory, covers the first
        ``trace_ticks`` of them."""
        from repro.obs.trace import get_tracer
        from repro.forecast.features import trace_count
        tracer = get_tracer()
        self.span_mark = tracer.mark()
        self.compiles0 = trace_count()
        n_trace = int(self.traffic["trace_ticks"]) if trace_dir else 0
        if n_trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans only: no per-call
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        self.w0 = time.perf_counter()
        while True:
            t = self.step(annotate=len(self.ticks) < n_trace)
            self.ticks.append(t)
            if len(self.ticks) == n_trace:
                import jax
                jax.profiler.stop_trace()
            if t["t_end"] - self.w0 >= seconds:
                break
        self.window_s = self.ticks[-1]["t_end"] - self.w0
        if 0 < len(self.ticks) < n_trace:
            import jax
            jax.profiler.stop_trace()
        self.traced_ticks = min(n_trace, len(self.ticks))
        self.compiles = trace_count() - self.compiles0
        self.spans = [s for s in tracer.spans() if s.seq > self.span_mark]

    def peak_bytes(self) -> int:
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices())


def end_to_end(run: Run, setup_s: float, peak: int, wanted) -> dict:
    lat = [t["tick_s"] * 1e3 for t in run.ticks]
    ok_jobs = sum(t["jobs"] - t["failed"] for t in run.ticks)
    vals = {"jobs_per_s": ok_jobs / run.window_s,
            "setup_s": setup_s,
            "peak_hbm_gib": peak / GIB,
            "latency_p50_ms": statistics.median(lat),
            "latency_p90_ms": float(np.percentile(lat, 90))}
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in wanted}


def per_layer(run: Run, trace: dict, peaks: dict, wanted) -> dict:
    run.trace, run.peaks = trace, peaks
    out = {}
    for m in wanted:
        v = load_reader(m["name"], run.cell["bench"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = Path.cwd()
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cell = load_cell(root, args.workload)
    try:
        devs = devices_for(cell["chips"])
        peaks = peak_of(devs[0].device_kind)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    enable_cache()

    out = execute(root, cell, args.seed, args.seconds, args.trace, peaks)
    print(json.dumps(out))
    return 0


def execute(root: Path, cell: dict, seed: int, seconds: float, trace: int,
            peaks: dict, *, require_chip: bool = True) -> dict:
    """Set-up, window, metrics and the correctness check of one run:
    the result line as a dict. The numbers compared go to standard error
    last."""
    run = Run(cell, seed, require_chip=require_chip)
    run.setup()
    setup_s = time.perf_counter() - T_PROCESS
    trace_dir = None
    if trace:
        import shutil
        trace_dir = root / ".bench_out" / "trace" / f"{cell['name']}.{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.window(seconds, trace_dir)
    peak = run.peak_bytes()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {}
    if trace:
        from trace_reduce import reduce_trace
        tr = reduce_trace(trace_dir)
        device["busy_s"] = tr["mean_busy_s"]
        device["window_s"] = tr["window_s"]
        metrics = per_layer(run, tr, peaks, cell["per_layer"])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr["device_ops"]],
            "idle_gaps": tr["idle_gaps"]}
    else:
        metrics = end_to_end(run, setup_s, peak, cell["end_to_end"])

    attempted = sum(t["jobs"] for t in run.ticks)
    failed = sum(t["failed"] for t in run.ticks)
    for t in run.ticks:
        for e in t["errors"]:
            print(f"bench: failed job at {t['boundary']}: {e}",
                  file=sys.stderr)
    print(f"bench: {len(run.ticks)} ticks in {run.window_s:.3f} s, "
          f"{run.compiles} compilations in the window, "
          f"set-up {setup_s:.3f} s", file=sys.stderr)

    found = checks.collect(run)
    del run
    gc.collect()
    numbers = checks.compare(found, cell)
    correct = checks.decide(numbers)
    for name, (v, lim) in numbers.items():
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **result,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}}


if __name__ == "__main__":
    sys.exit(main())

"""Read the correctness numbers of a cell's program and of its control, on
the chip, at the cell's own size: the readings the limits in
``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --ticks <k> --seeds s1 s2 ...

For each seed, in one process: the cell's set-up, ``k`` window ticks at
the cell's own load, then the numbers ``bench/run.py`` compares (the
program's readings) and the same numbers for the control: the float64
reference itself, put in the program's place and computed in float32 with
its matmuls at ``high`` precision (three bfloat16 passes,
``reference.matmul_bf16x3``), the step below what the configuration
states (float32, with the forecasting matmuls at ``highest``). Both sets
of numbers go through the decision ``bench/run.py`` makes
(``checks.with_limits`` against ``bench/limits/<cell>.json``, then
``checks.decide``): a sound program comes out correct, the control not.
One JSON line per seed, each number beside its limit; the benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import checks  # noqa: E402
import reference as ref  # noqa: E402
import run as bench  # noqa: E402


def control_lr(found: dict) -> dict:
    """The LR reference in float32 at ``high``: its own ridge fit and
    rollout, held to the float64 reference."""
    import jax.numpy as jnp
    site, spec, b = found["site"], found["spec"], found["boundaries"]
    lam = float(found["config"]["ridge_lambda"])
    at = found["versions"][0][0].trained_at
    Xs, y, mu, sd = ref.training_set(site, spec, at)
    theta64 = ref.ridge(Xs, y, lam)
    ys, ts, fs = ref.score_inputs(site, spec, b)
    want = ref.rollout(
        lambda x: ref.lr_predict(theta64, (x - mu[:, None]) / sd[:, None]),
        spec, ys, ts, fs, b)
    f32 = jnp.float32
    theta = ref.ridge(jnp.asarray(Xs, f32), jnp.asarray(y, f32), lam,
                      xp=jnp, matmul=ref.matmul_bf16x3)
    m, s = jnp.asarray(mu, f32)[:, None], jnp.asarray(sd, f32)[:, None]
    got = ref.rollout(lambda x: ref.lr_predict(theta, (x - m) / s, xp=jnp),
                      spec, jnp.asarray(ys, f32), jnp.asarray(ts, f32),
                      jnp.asarray(fs, f32), b, xp=jnp)
    return {"forecast_gap": float(np.abs(np.asarray(got, np.float64)
                                         - want).max()),
            "theta_gap": float(np.abs(np.asarray(theta, np.float64)
                                      - theta64).max())}


def control_ann(found: dict) -> dict:
    """The ANN scoring reference in float32 at ``high`` over the persisted
    networks, held to the float64 reference over the same networks."""
    import jax.numpy as jnp
    site, spec, b = found["site"], found["spec"], found["boundaries"]
    models = [vs[-1].params["params"] for vs in found["versions"]]
    at = found["versions"][0][-1].trained_at
    _, _, mu, sd = ref.training_set(site, spec, at)
    ys, ts, fs = ref.score_inputs(site, spec, b)
    layers, y_scale = ref.ann_layers(models)
    mu3, sd3 = mu[:, None], sd[:, None]
    want = ref.rollout(
        lambda x: ref.ann_predict(layers, y_scale, (x - mu3) / sd3),
        spec, ys, ts, fs, b)
    f32 = jnp.float32
    lj = [(jnp.asarray(w, f32), jnp.asarray(c, f32)) for w, c in layers]
    yj = jnp.asarray(y_scale, f32)
    mj, sj = jnp.asarray(mu3, f32), jnp.asarray(sd3, f32)
    got = ref.rollout(
        lambda x: ref.ann_predict(lj, yj, (x - mj) / sj, xp=jnp,
                                  matmul=ref.matmul_bf16x3),
        spec, jnp.asarray(ys, f32), jnp.asarray(ts, f32),
        jnp.asarray(fs, f32), b, xp=jnp)
    return {"forecast_gap": float(np.abs(np.asarray(got, np.float64)
                                         - want).max())}


CONTROL = {"lr": control_lr, "ann": control_ann}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ticks", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = Path.cwd()
    sys.path.insert(0, str(root / "src"))
    cell = bench.load_cell(root, args.workload)
    try:
        bench.devices_for(cell["chips"])
    except bench.NoChip as e:
        print(f"calibrate: {e}; nothing was run", file=sys.stderr)
        return 2
    bench.enable_cache()
    for seed in args.seeds:
        run = bench.Run(cell, seed)
        run.setup()
        for _ in range(args.ticks):
            run.ticks.append(run.step())
        found = checks.collect(run)
        del run
        gc.collect()
        program, control = readings(found, cell)
        out = {"seed": seed,
               "program_correct": checks.decide(program),
               "control_correct": checks.decide(control),
               "program": {k: {"value": v, "limit": lim}
                           for k, (v, lim) in program.items()},
               "control": {k: {"value": v, "limit": lim}
                           for k, (v, lim) in control.items()}}
        print(json.dumps(out), flush=True)
    return 0


def readings(found: dict, cell: dict) -> tuple:
    """The program's numbers and the control's, each ``{name: (value,
    limit)}`` under the cell's limits."""
    control = CONTROL[cell["config"]["reference"]](found)
    return (checks.compare(found, cell), checks.with_limits(control, cell))


if __name__ == "__main__":
    sys.exit(main())

"""Read the correctness numbers of a cell's program and of its control, on
the chip, at the cell's own size: the readings the limits in
``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --ticks <k> --seeds s1 s2 ...

For each seed, in one process: the cell's set-up, ``k`` window ticks at
the cell's own load, then the numbers ``bench/run.py`` compares (the
program's readings) and the same numbers for the control, which the
configuration's ``bench/models/<reference>.py`` ``control`` computes: the
float64 reference itself, put in the program's place and computed in
float32 with its matmuls at ``high`` precision (three bfloat16 passes,
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

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import checks  # noqa: E402
import run as bench  # noqa: E402


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
    control = cell["model"].control(found)
    return (checks.compare(found, cell), checks.with_limits(control, cell))


if __name__ == "__main__":
    sys.exit(main())

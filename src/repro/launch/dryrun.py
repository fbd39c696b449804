import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell
on placeholder devices; record memory/cost/collective analysis to JSON.

The XLA_FLAGS assignment above MUST run before any jax import (device count
locks on first init) — keep it the first statement of this module.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from ..configs import SHAPES, get_config, list_archs, shape_applicable  # noqa: E402
from . import hlo_cost  # noqa: E402
from .cells import build_cell, lower_cell  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

# TPU v5e hardware model (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s per link (we count per-device wire bytes)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Path, keep_hlo: bool = False,
             optimized: bool = False, **cell_kw) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    if optimized:
        # §Perf configuration: weight-stationary serving + distributed
        # flash-decode for serve cells; sequence parallelism for train cells
        from ..distributed.sharding import serve_rules
        kind = SHAPES[shape_name].kind
        if kind in ("decode", "prefill"):
            cell_kw.setdefault("rules", serve_rules(multi_pod))
            if kind == "decode":
                cell_kw.setdefault("dist_decode", True)
        # train: sequence parallelism (sp_rules) is a per-cell lever — it
        # halves llama4's memory term but regresses internlm2's collectives
        # (§Perf); pass rules=sp_rules(...) explicitly where it wins.
    cell = build_cell(arch, shape_name, mesh, **cell_kw)
    lowered = lower_cell(cell)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    text = compiled.as_text()
    cost = hlo_cost.analyze(text, n_dev)

    result = {
        "arch": arch, "shape": shape_name, "kind": cell.kind,
        "mesh": dict(zip(mesh.axis_names, (mesh.shape[a] for a in mesh.axis_names))),
        "n_devices": n_dev,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_bytes": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "xla_cost_analysis": {"flops_per_iter": ca.get("flops", 0.0),
                              "bytes_per_iter": ca.get("bytes accessed", 0.0)},
        "hlo_cost": {
            "flops": cost.flops,
            "bytes": cost.bytes,
            "collective_wire_bytes": cost.collective_wire_bytes,
            "collectives": dict(cost.collectives),
            "collective_counts": dict(cost.collective_counts),
        },
        "roofline": {
            "compute_s": cost.flops / PEAK_FLOPS,
            "memory_s": cost.bytes / HBM_BW,
            "collective_s": cost.collective_wire_bytes / ICI_BW,
        },
    }
    rl = result["roofline"]
    result["roofline"]["dominant"] = max(rl, key=lambda k: rl[k])
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{shape_name}__{'multipod' if multi_pod else 'pod'}"
    with open(out_dir / f"{tag}.json", "w") as f:
        json.dump(result, f, indent=1)
    if keep_hlo:
        (out_dir / f"{tag}.hlo.txt").write_text(text)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf optimized layouts (serve_rules + "
                         "distributed flash-decode + SP)")
    args = ap.parse_args()
    out_dir = Path(args.out)

    cells = []
    if args.all:
        for a in list_archs():
            cfg = get_config(a)
            for s in SHAPES.values():
                ok, why = shape_applicable(cfg, s)
                if ok:
                    cells.append((a, s.name))
                else:
                    print(f"SKIP {a} x {s.name}: {why}")
    else:
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}"
            if args.skip_existing and (out_dir / f"{tag}.json").exists():
                print(f"skip existing {tag}")
                continue
            try:
                r = run_cell(arch, shape, multi_pod=mp, out_dir=out_dir,
                             keep_hlo=args.keep_hlo, optimized=args.optimized)
                rl = r["roofline"]
                print(f"OK  {tag}: compile={r['t_compile_s']}s "
                      f"mem/dev={r['memory']['peak_per_device_bytes']/2**30:.2f}GiB "
                      f"compute={rl['compute_s']*1e3:.2f}ms "
                      f"memory={rl['memory_s']*1e3:.2f}ms "
                      f"coll={rl['collective_s']*1e3:.2f}ms "
                      f"dom={rl['dominant']}", flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()

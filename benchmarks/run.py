"""Benchmark harness: one module per paper table/figure (+ kernels +
roofline). Prints ``name,us_per_call,derived`` CSV.

``--trace PATH`` keeps the observability tracer on across every bench
group and dumps the accumulated spans as Chrome trace-event JSON
(default ``artifacts/bench_run.perfetto-trace.json``; open at
ui.perfetto.dev) — one flamegraph over the whole suite.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--trace", nargs="?", metavar="PATH",
        const="artifacts/bench_run.perfetto-trace.json", default=None,
        help="dump a Perfetto/Chrome trace of the whole run to PATH")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_control_plane, bench_detection, bench_durability,
                   bench_fig2_ingestion, bench_fig4_transform,
                   bench_kernels, bench_observability, bench_roofline,
                   bench_steady_state, bench_table1_models,
                   bench_table2_sites, bench_table3_invocations,
                   bench_table3_scalability)
    benches = [
        ("fig2", bench_fig2_ingestion),
        ("fig4", bench_fig4_transform),
        ("table1", bench_table1_models),
        ("table2", bench_table2_sites),
        ("table3", bench_table3_scalability),
        ("table3_invoke", bench_table3_invocations),
        ("steady", bench_steady_state),
        ("control_plane", bench_control_plane),
        ("detection", bench_detection),
        ("durability", bench_durability),
        ("observability", bench_observability),
        ("kernels", bench_kernels),
        ("roofline", bench_roofline),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for tag, mod in benches:
        t0 = time.time()
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{tag}_FAILED,0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        else:
            print(f"# {tag} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if args.trace:
        from repro.obs.export import write_chrome_trace
        path = write_chrome_trace(args.trace)
        print(f"# trace written to {path}", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} bench group(s) failed")


if __name__ == "__main__":
    main()

"""Integration test of the dry-run cell machinery on 8 placeholder devices
(subprocess: the device-count override must precede jax init, and the main
test process must keep its single real device)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.testing import subprocess_env

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.launch.cells import build_cell, lower_cell
    from repro.launch.mesh import make_mesh
    from repro.launch import hlo_cost
    from repro.distributed.sharding import serve_rules

    mesh = make_mesh((2, 4), ("data", "model"))
    out = {}
    for tag, kw in [("baseline", {}),
                    ("optimized", dict(rules=serve_rules(False),
                                       dist_decode=True))]:
        cell = build_cell("qwen3-1.7b", "decode_32k", mesh, **kw)
        compiled = lower_cell(cell).compile()
        cost = hlo_cost.analyze(compiled.as_text(), 8)
        mem = compiled.memory_analysis()
        out[tag] = {"flops": cost.flops, "bytes": cost.bytes,
                    "wire": cost.collective_wire_bytes,
                    "temp": mem.temp_size_in_bytes}
    # train cell lowers too (microbatching + FSDP path)
    cell = build_cell("qwen3-1.7b", "train_4k", mesh)
    compiled = lower_cell(cell).compile()
    cost = hlo_cost.analyze(compiled.as_text(), 8)
    out["train"] = {"flops": cost.flops, "wire": cost.collective_wire_bytes}
    print(json.dumps(out))
""")


def test_cells_compile_and_analyze_on_8_devices():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=520,
        # the child inherits this test's JAX_PLATFORMS (cpu, from
        # conftest.py): the forced 8-device override is a CPU feature
        env=subprocess_env(Path(__file__).parent.parent / "src"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # decode cells: optimized layout must slash collective wire bytes
    assert out["optimized"]["wire"] < out["baseline"]["wire"] * 0.5, out
    # train flops per device at 8 devices: 6*N*D/8 within remat factor bounds
    n, d = 1.72e9, 256 * 4096
    model = 6 * n * d / 8
    assert 0.8 * model < out["train"]["flops"] < 2.0 * model, out["train"]

"""Elastic restart: a checkpoint saved under one mesh restores onto a
DIFFERENT (shrunken) mesh with new shardings — the node-failure recovery
path claimed in DESIGN.md. Subprocess (needs 8 placeholder devices)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.testing import subprocess_env

SCRIPT = textwrap.dedent("""
    import os, tempfile, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.checkpoint import save, restore
    from repro.distributed.fault import elastic_remesh, largest_mesh_shape
    from repro.launch.mesh import make_mesh

    mesh8 = make_mesh((2, 4), ("data", "model"))
    tree = {"w": jnp.arange(64.0).reshape(8, 8), "step": jnp.asarray(5)}
    sh8 = {"w": NamedSharding(mesh8, P("data", "model")),
           "step": NamedSharding(mesh8, P())}
    placed = jax.tree_util.tree_map(jax.device_put, tree, sh8)
    d = tempfile.mkdtemp()
    save(d + "/ck", placed, step=5)

    # a node died: rebuild the largest mesh from 7 surviving devices
    surv = jax.devices()[:7]
    assert largest_mesh_shape(7, model_axis=4) == (1, 4)
    mesh4 = elastic_remesh(surv, model_axis=4)
    assert mesh4.devices.size == 4
    sh4 = {"w": NamedSharding(mesh4, P("data", "model")),
           "step": NamedSharding(mesh4, P())}
    got, man = restore(d + "/ck", tree, shardings=sh4)
    ok = bool(np.allclose(np.asarray(got["w"]), np.asarray(tree["w"])))
    ok = ok and man["step"] == 5
    ok = ok and got["w"].sharding.mesh.devices.size == 4
    # and training math continues on the new mesh
    y = jax.jit(lambda w: (w @ w.T).sum())(got["w"])
    ok = ok and bool(np.isfinite(float(y)))
    print(json.dumps({"ok": ok}))
""")


def test_checkpoint_restores_onto_shrunken_mesh():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=300,
        # the child inherits this test's JAX_PLATFORMS (cpu, from
        # conftest.py): the forced 8-device override is a CPU feature
        env=subprocess_env(Path(__file__).parent.parent / "src"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"]

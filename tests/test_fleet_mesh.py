"""Mesh-sharded fleet execution: sharded == unsharded equivalence, padding
of uneven bins, telemetry, and the opt-out.

Two layers of coverage:
  * in-process tests run whenever the suite sees >1 jax device (the CI
    matrix entry sets XLA_FLAGS=--xla_force_host_platform_device_count=8);
    on a single device they skip and the always-on subprocess smoke below
    still exercises the sharded path.
  * single-device behaviors (auto-select declines, opt-out) always run.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.executor import FleetExecutor
from repro.forecast import (ANNForecaster, GAMForecaster, LSTMForecaster,
                            LinearForecaster)
from repro.testing import (FLEET_ATOL, FLEET_NOW as NOW, FLEET_RTOL,
                           build_fleet_castor, subprocess_env)

MODELS = {
    "lr": (LinearForecaster, {}),
    "gam": (GAMForecaster, {}),
    "ann": (ANNForecaster, {"hidden": 8, "epochs": 20}),
    "lstm": (LSTMForecaster, {"hidden": 8, "epochs": 20}),
}

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count=N)")


def _fleet_castor(kind, mesh_opt, n=6):
    cls, hp = MODELS[kind]
    return build_fleet_castor(kind, cls, hp, mesh_opt, n=n)


@multi_device
@pytest.mark.parametrize("kind", list(MODELS))
def test_sharded_equals_unsharded_fleet(kind):
    """The mesh-sharded fleet path persists the same model versions and
    forecasts as the single-device vmap (tolerance-pinned: float32 batched
    solves/matmuls reassociate across shard boundaries)."""
    ca, fa = _fleet_castor(kind, "auto")
    cb, fb = _fleet_castor(kind, "off")
    mdev = min(jax.device_count(), 6)           # mesh sized to the bin
    for b in fa.last_bin_stats:
        assert b["sharded"] and b["mesh_devices"] == mdev
        assert b["pad"] == (-6) % mdev          # uneven bins padded+masked
        assert b["dispatches"] == 1             # still ONE dispatch per bin
    assert all(not b["sharded"] and b["mesh_devices"] == 1
               for b in fb.last_bin_stats)
    for i in range(6):
        name = f"s-Z_PRO_0_{i}"
        pa = ca.versions.get(name).params["params"]
        pb = cb.versions.get(name).params["params"]
        assert pa.keys() == pb.keys()
        for k in pa:
            np.testing.assert_allclose(pa[k], pb[k], rtol=5e-2, atol=5e-3,
                                       err_msg=f"{kind} params[{k}]")
        fca = ca.predictions.history(name)
        fcb = cb.predictions.history(name)
        assert len(fca) == len(fcb) == 1
        np.testing.assert_allclose(fca[0].times, fcb[0].times)
        np.testing.assert_allclose(fca[0].values, fcb[0].values,
                                   rtol=FLEET_RTOL, atol=FLEET_ATOL,
                                   err_msg=kind)


@multi_device
def test_fleet_sharded_helper_pads_and_replicates():
    """Unit contract of distributed.sharding.fleet_sharded: uneven leading
    axes are padded to a shard multiple and sliced back; replicated args
    broadcast; results equal the unsharded function."""
    from repro.distributed.sharding import fleet_sharded
    from repro.launch.mesh import make_fleet_mesh
    mesh = make_fleet_mesh()
    assert mesh is not None

    def fn(x, scale):                     # x sharded (N, F), scale replicated
        return {"out": x * scale, "sum": x.sum(axis=-1)}

    ndev = jax.device_count()
    n = ndev + 1                          # deliberately uneven
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    scale = np.asarray(2.0, np.float32)
    got = fleet_sharded(fn, mesh, replicated_argnums=(1,))(x, scale)
    np.testing.assert_array_equal(np.asarray(got["out"]), x * 2.0)
    np.testing.assert_array_equal(np.asarray(got["sum"]), x.sum(-1))


def test_single_device_auto_declines_mesh():
    """mesh='auto' on one device (or an opted-out deployment) runs the
    plain vmap path and says so in telemetry."""
    if jax.device_count() > 1:
        pytest.skip("needs exactly 1 device")
    _, fx = _fleet_castor("lr", "auto", n=3)
    assert all(not b["sharded"] and b["mesh_devices"] == 1 and b["pad"] == 0
               for b in fx.last_bin_stats)


def test_mesh_off_opt_out_via_user_params():
    _, fx = _fleet_castor("lr", "off", n=3)
    assert all(not b["sharded"] for b in fx.last_bin_stats)


def test_executor_level_mesh_off():
    c, _ = _fleet_castor("lr", "auto", n=3)
    fx = FleetExecutor(c, mesh="off")
    res = fx.run(c.scheduler.poll(NOW + 1e12))
    assert res and all(r.ok for r in res)
    assert all(not b["sharded"] for b in fx.last_bin_stats)


_SMOKE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    from repro.forecast import ANNForecaster, LinearForecaster
    from repro.testing import FLEET_ATOL, FLEET_RTOL, build_fleet_castor

    assert jax.device_count() == 8
    out = {}
    for kind, cls, hp in [("lr", LinearForecaster, {}),
                          ("ann", ANNForecaster, {"hidden": 8, "epochs": 20})]:
        ca, fa = build_fleet_castor(kind, cls, hp, "auto")
        cb, fb = build_fleet_castor(kind, cls, hp, "off")
        # mesh sized to the 6-job bin (not all 8 devices), so pad == 0
        assert all(b["sharded"] and b["mesh_devices"] == 6 and b["pad"] == 0
                   for b in fa.last_bin_stats), fa.last_bin_stats
        assert all(not b["sharded"] for b in fb.last_bin_stats)
        dev = 0.0
        for i in range(6):
            name = f"s-Z_PRO_0_{i}"
            va = ca.predictions.history(name)[0].values
            vb = cb.predictions.history(name)[0].values
            assert np.allclose(va, vb, rtol=FLEET_RTOL, atol=FLEET_ATOL), \\
                (kind, name)
            dev = max(dev, float(np.max(np.abs(va - vb))))
        out[kind] = dev
    print(json.dumps(out))
""")


def test_sharded_fleet_subprocess_smoke():
    """Always-on sharded coverage: even a single-device test host verifies
    the 8-device mesh path in a subprocess (the device-count override must
    precede jax init)."""
    proc = subprocess.run(
        [sys.executable, "-c", _SMOKE], capture_output=True, text=True,
        timeout=520,
        env=subprocess_env(Path(__file__).parent.parent / "src"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    devs = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(devs) == {"lr", "ann"}
    assert all(d < 1e-3 for d in devs.values()), devs


def test_subprocess_env_forwards_platform_only_when_set(monkeypatch):
    """A child runs on its parent's JAX_PLATFORMS, and on JAX's default
    when none is set: it is never sent to the CPU behind the caller's
    back."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert subprocess_env("src")["JAX_PLATFORMS"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "JAX_PLATFORMS" not in subprocess_env("src")


def test_subprocess_env_refuses_when_parent_holds_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        subprocess_env("src")

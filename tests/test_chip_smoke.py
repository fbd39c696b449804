"""chip_smoke.py's phases at a tiny size on the CPU: the same ticks and
reference checks the chip runs at 256 prosumers and width 512, with the
Pallas kernel in interpret mode."""
import importlib.util
from pathlib import Path

import pytest

from repro.forecast import base
from repro.kernels import common
from repro.testing import FLEET_NOW, HOUR

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_at_tiny_size(smoke, monkeypatch):
    # the kernel through the Pallas interpreter, and a rollout cache of
    # this test's own so no rollout traced with the jnp reference is reused
    monkeypatch.setattr(common, "default_impl", lambda: "pallas_interpret")
    monkeypatch.setattr(base, "_ROLLOUT_CACHE", base._LRUCache(cap=32))
    n = 4
    c = smoke.build(n, 0, {"hidden": 16, "epochs": 5})
    ticks = smoke.run_ticks(c, n)
    assert [t["jobs"] for t in ticks] == [4 * n, 2 * n, 2 * n]
    assert ticks[1]["runtime"] == ticks[2]["runtime"] == ["warm"]
    assert ticks[2]["retraces"] == 0
    assert smoke.check_lr_theta(c) < smoke.THETA_ATOL
    dev = smoke.check_forecasts(c, FLEET_NOW + 2 * HOUR)
    assert set(dev) == {"lr", "ann"}
    assert smoke.check_kernel(c, require_custom_call=False)


def test_smoke_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out

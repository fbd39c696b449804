"""Compile the main path's device programs for a TPU v5e without one.

The TPU compiler is installed with libtpu and compiles for a described,
unattached chip. That catches what interpret mode and the CPU backend
cannot: Pallas blocks that break the TPU tiling, kernels that need more
VMEM than Mosaic allows, and programs that do not fit in HBM. Nothing runs,
so these tests say nothing about results or times.

Only one process may load libtpu, so the topology is described inside a
fixture (never at import) and every check lives in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.forecast import ANNForecaster
from repro.forecast.ann import N_HIDDEN_LAYERS, _fit_fleet
from repro.forecast.features import FeatureSpec, make_device_rollout
from repro.forecast.linear import _ridge_fit_fleet
from repro.kernels import common
from repro.kernels.fleet_mlp.ops import fleet_mlp

#: the paper's ANN width (§4.2) and the ANN design: 48 target lags, the
#: forecast temperature and 5 calendar features over a 28-day hourly window
WIDTH = 512
ANN_SPEC = FeatureSpec(target_lags=48, weather_lags=0)
ANN_T = 28 * 24 - 48
#: LR's default design: 24 target and 24 temperature lags
LR_SPEC = FeatureSpec()
LR_T = 28 * 24 - 24
HBM_BYTES = 16 << 30                 # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    """The backend here is the CPU, so the kernel dispatch would pick the
    jnp reference; steer it to the real Pallas lowering."""
    monkeypatch.setattr(common, "default_impl", lambda: "pallas")


def _is_shape(s):
    return isinstance(s, tuple) and all(isinstance(d, int) for d in s)


def _shapes(sharding, tree):
    """float32 ShapeDtypeStructs on ``sharding`` for every shape tuple in
    ``tree``."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding),
        tree, is_leaf=_is_shape)


def _mlp_shapes(n, F, width):
    sizes = [F] + [width] * N_HIDDEN_LAYERS + [1]
    return ([(n, sizes[i], sizes[i + 1]) for i in range(len(sizes) - 1)],
            [(n, sizes[i + 1]) for i in range(len(sizes) - 1)])


@pytest.mark.parametrize("N,width", [(16, WIDTH), (256, WIDTH),
                                     (1024, WIDTH), (1024, 64),
                                     (16, 1024)])   # raises the VMEM limit
def test_fleet_mlp_compiles(one_chip, pallas, N, width):
    ws, bs = _mlp_shapes(N, ANN_SPEC.n_features, width)
    x, ws, bs = _shapes(one_chip, ((N, 1, ANN_SPEC.n_features), ws, bs))
    compiled = jax.jit(fleet_mlp).lower(x, ws, bs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ann_rollout_compiles(one_chip, pallas):
    N, H, F = 256, 24, ANN_SPEC.n_features
    ws, bs = _mlp_shapes(N, F, WIDTH)
    stacked = {f"w{i}": w for i, w in enumerate(ws)}
    stacked.update({f"b{i}": b for i, b in enumerate(bs)})
    stacked["y_scale"] = (N,)
    args = _shapes(one_chip, (stacked, (N, F), (N, F),
                              (N, ANN_SPEC.target_lags), (N, 1), (N, H),
                              (H,), (H,)))
    run = make_device_rollout(
        ANNForecaster._device_predict_factory(ANN_SPEC, ()), ANN_SPEC, H)
    compiled = run.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lr_fleet_ridge_compiles(one_chip):
    N, F = 256, LR_SPEC.n_features
    X, y, lam = _shapes(one_chip, ((N, LR_T, F), (N, LR_T), ()))
    compiled = _ridge_fit_fleet.lower(X, y, lam).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_ann_fleet_fit_fits_one_chip(one_chip):
    N, F = 256, ANN_SPEC.n_features
    X, y, ys = _shapes(one_chip, ((N, ANN_T, F), (N, ANN_T), (N,)))
    keys = jax.ShapeDtypeStruct((N, 2), jnp.uint32, sharding=one_chip)
    compiled = _fit_fleet.lower(keys, X, y, ys, epochs=300, width=WIDTH,
                                lr=1e-3).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES, total

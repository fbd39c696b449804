"""Model versions made by the benchmark from the seed, for cells that
measure scoring without the program's own fit.

The ANN networks are drawn on the device in one jitted call, in float32
(the type they are served in), with the program's initialisation law
(He-normal weights, zero biases); the standardisation and output scale
come from each deployment's training window as a fit would compute them
(float64 mean and standard deviation of the design, 1.2 x the largest
target). Each deployment gets one version, trained at ``now``, saved
through the castor's ``ModelVersionStore`` as an imported model would be.
"""
from __future__ import annotations

import numpy as np

import reference as ref


def ann_models(config: dict, site, seed: int, now: float) -> list:
    """One ANN model object per prosumer of ``site``."""
    import jax
    import jax.numpy as jnp
    up = config["user_params"]
    spec = ref.Spec(up)
    sizes = [spec.n_features] + [int(up["hidden"])] \
        * int(config["hidden_layers"]) + [1]
    n = site.n

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(sizes) - 1)
        return [jax.random.normal(k, (n, a, b), jnp.float32)
                * jnp.sqrt(2.0 / a)
                for k, a, b in zip(keys, sizes[:-1], sizes[1:])]

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    ws = [np.asarray(w) for w in draw(key)]
    Xs, y, mu, sd = ref.training_set(site, spec, now)
    y_scale = np.abs(y).max(axis=1) * 1.2 + 1e-6
    spread = y.std(axis=1)
    models = []
    for d in range(n):
        params = {f"w{i}": w[d] for i, w in enumerate(ws)}
        params.update({f"b{i}": np.zeros(b, np.float32)
                       for i, b in enumerate(sizes[1:])})
        params["y_scale"] = float(y_scale[d])
        models.append({"kind": "ANN", "params": params, "mu": mu[d],
                       "sd": sd[d], "y_scale": float(np.abs(y[d]).max()
                                                     + 1e-6),
                       "resid_q": np.array([-0.1, 0.1]) * spread[d]})
    return models


MODELS = {"ann": ann_models}

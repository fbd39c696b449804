"""The ANN forecaster of the paper (section 4.2): its comparison, its
control, its versions made from the seed and its operations.

The reference scores with the networks themselves: the benchmark's own
copy where it made them from the seed, the persisted ones otherwise. The
standardisation comes from each deployment's training window, as a fit
would compute it.
"""
from __future__ import annotations

import copy

import numpy as np

import checks
import reference as ref
import work


def compare(found: dict) -> dict:
    """``forecast_gap``: the largest absolute gap, in kWh, between a
    persisted forecast value and the float64 reference's rollout of the
    network that made it."""
    site, b = found["site"], found["boundaries"]
    spec = ref.Spec(found["config"]["user_params"])
    scored = checks.scored(found)
    ys, ts, fs = ref.score_inputs(site, spec, b)
    fc_gap = 0.0
    mus = {}
    for d in range(site.n):
        vs = found["versions"][d]
        for v in {id(v): v for v in vs if v is not None}.values():
            cols = [s for s in range(len(b))
                    if vs[s] is v and scored[d, s]]
            if not cols:
                continue
            key = (v.trained_at, d)
            if key not in mus:
                mus.update(_standardisation(site, spec, v.trained_at))
            mu, sd = mus[key]
            layers, y_scale = ref.ann_layers([v.params["params"]])
            want = ref.rollout(
                lambda x: ref.ann_predict(layers, y_scale, (x - mu) / sd),
                spec, ys[d:d + 1, cols], ts[d:d + 1, cols],
                fs[d:d + 1, cols], [b[s] for s in cols])
            fc_gap = max(fc_gap, float(
                np.abs(found["got"][d, cols] - want[0]).max()))
    return {"forecast_gap": fc_gap}


def _standardisation(site, spec, at: float) -> dict:
    """``{(at, d): (mu, sd)}`` of every deployment's fit at ``at``."""
    _, _, mu, sd = ref.training_set(site, spec, at)
    return {(at, d): (mu[d], sd[d]) for d in range(site.n)}


def control(found: dict) -> dict:
    """The ANN scoring reference in float32 at ``high`` over the persisted
    networks, held to the float64 reference over the same networks."""
    import jax.numpy as jnp
    site, b = found["site"], found["boundaries"]
    spec = ref.Spec(found["config"]["user_params"])
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


def score_flops(config: dict, n: int) -> float:
    """One score tick of ``n`` deployments: the 24-step rollout, a forward
    pass of one row per deployment and step (``work.ann_score_flops``)."""
    up = config["user_params"]
    return work.ann_score_flops(n, ref.Spec(up).n_features, int(up["hidden"]),
                                int(config["hidden_layers"]),
                                int(up["horizon"]))


def seed_versions(run) -> list:
    """One network per prosumer, made from the seed and saved through the
    castor's ``ModelVersionStore`` as an imported model would be; the
    program gets a copy, the benchmark keeps its own.

    The networks are drawn on the device in one jitted call, in float32
    (the type they are served in), with the program's initialisation law
    (He-normal weights, zero biases); the standardisation and output scale
    come from each deployment's training window as a fit would compute
    them (float64 mean and standard deviation of the design, 1.2 x the
    largest target). Each deployment gets one version, trained at the
    window's start."""
    import jax
    import jax.numpy as jnp
    config, site, seed, now = run.config, run.site, run.seed, run.now0
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
    for name, m in zip(run.names, models):
        run.castor.versions.save(name, copy.deepcopy(m), trained_at=now,
                                 metadata={"source": "benchmark seed"})
    return models

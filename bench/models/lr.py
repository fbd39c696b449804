"""The LR forecaster of the paper (Table 1): its comparison and its
control. The reference recomputes everything from the traffic, the ridge
fit included; the mix fits through the program, so there are no seeded
versions, and the rollout's operations are too few to set a share of the
chip's peak by.
"""
from __future__ import annotations

import numpy as np

import checks
import reference as ref

#: deployments per block of the reference (bounds its memory)
BLOCK = 512


def compare(found: dict) -> dict:
    """``theta_gap``: the largest absolute gap between a persisted ridge
    coefficient and the reference's own float64 solve; ``forecast_gap``:
    the largest absolute gap, in kWh, between a persisted forecast value
    and the reference's rollout."""
    site, b = found["site"], found["boundaries"]
    spec = ref.Spec(found["config"]["user_params"])
    lam = float(found["config"]["ridge_lambda"])
    versions, scored = found["versions"], checks.scored(found)
    trained = sorted({v.trained_at for vs in versions for v in vs
                      if v is not None})
    theta_gap, fc_gap = 0.0, 0.0
    for lo in range(0, site.n, BLOCK):
        rows = np.arange(lo, min(site.n, lo + BLOCK))
        ys, ts, fs = ref.score_inputs(site, spec, b, rows)
        for at in trained:
            Xs, y, mu, sd = ref.training_set(site, spec, at, rows)
            theta = ref.ridge(Xs, y, lam)
            want = ref.rollout(
                lambda x: ref.lr_predict(theta, (x - mu[:, None])
                                         / sd[:, None]),
                spec, ys, ts, fs, b)
            for i, d in enumerate(rows):
                use = [s for s, v in enumerate(versions[d])
                       if v is not None and v.trained_at == at]
                if not use:
                    continue
                th = np.asarray(versions[d][use[0]].params["params"]
                                ["theta"], np.float64)
                theta_gap = max(theta_gap, float(np.abs(th - theta[i]).max()))
                use = [s for s in use if scored[d, s]]
                if use:
                    fc_gap = max(fc_gap, float(np.abs(
                        found["got"][d, use] - want[i, use]).max()))
    return {"forecast_gap": fc_gap, "theta_gap": theta_gap}


def control(found: dict) -> dict:
    """The LR reference in float32 at ``high``: its own ridge fit and
    rollout, held to the float64 reference."""
    import jax.numpy as jnp
    site, b = found["site"], found["boundaries"]
    spec = ref.Spec(found["config"]["user_params"])
    lam = float(found["config"]["ridge_lambda"])
    at = found["versions"][0][0].trained_at
    Xs, y, mu, sd = ref.training_set(site, spec, at)
    theta64 = ref.ridge(Xs, y, lam)
    ys, ts, fs = ref.score_inputs(site, spec, b)
    want = ref.rollout(
        lambda x: ref.lr_predict(theta64, (x - mu[:, None]) / sd[:, None]),
        spec, ys, ts, fs, b)
    f32 = jnp.float32
    theta = ref.ridge(jnp.asarray(Xs, f32), jnp.asarray(y, f32), lam,
                      xp=jnp, matmul=ref.matmul_bf16x3)
    m, s = jnp.asarray(mu, f32)[:, None], jnp.asarray(sd, f32)[:, None]
    got = ref.rollout(lambda x: ref.lr_predict(theta, (x - m) / s, xp=jnp),
                      spec, jnp.asarray(ys, f32), jnp.asarray(ts, f32),
                      jnp.asarray(fs, f32), b, xp=jnp)
    return {"forecast_gap": float(np.abs(np.asarray(got, np.float64)
                                         - want).max()),
            "theta_gap": float(np.abs(np.asarray(theta, np.float64)
                                      - theta64).max())}


def score_flops(config: dict, n: int) -> None:
    """No share of the peak: the rollout is about 110 operations a
    deployment and step, and the tick is host work."""
    return None

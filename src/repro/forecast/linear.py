"""LR forecaster (paper Table 1): ridge regression on weather + lag +
calendar features. Closed-form fit; fleet path is a vmapped solve."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import cho_factor, cho_solve

from .base import ForecastModelBase
from .features import bucket_n, edge_pad, note_trace


_HIGHEST = jax.lax.Precision.HIGHEST

#: iterative-refinement steps after the normal-equations solve
_REFINE_STEPS = 2


def _ridge_fit(X, y, lam=1e-2):
    """Ridge solve in float32 that matches a float64 solve.

    The normal equations square the design's condition number, so solving
    them once in float32 is off by about cond(Xb)^2 * eps (measured up to
    3e-3 on hourly smart-grid designs). Each refinement step re-solves for
    the residual taken from the design itself, which brings the error down
    to about cond(Xb) * eps. Every matmul is pinned to HIGHEST: a TPU's
    default float32 matmul is a single bfloat16 pass."""
    Xb = jnp.concatenate([X, jnp.ones(X.shape[:-1] + (1,))], -1)
    A = jnp.matmul(Xb.T, Xb, precision=_HIGHEST) + lam * jnp.eye(Xb.shape[-1])
    chol = cho_factor(A)
    theta = cho_solve(chol, jnp.matmul(Xb.T, y, precision=_HIGHEST))
    for _ in range(_REFINE_STEPS):
        resid = y - jnp.matmul(Xb, theta, precision=_HIGHEST)
        r = jnp.matmul(Xb.T, resid, precision=_HIGHEST) - lam * theta
        theta = theta + cho_solve(chol, r)
    return theta


def _ridge_fit_counted(X, y, lam=1e-2):
    # shared by LR and GAM (single + fleet), hence the neutral name
    note_trace("ridge_fit")          # Python body runs only while tracing
    return _ridge_fit(X, y, lam)


_ridge_fit_j = jax.jit(_ridge_fit_counted)
_ridge_fit_fleet = jax.jit(jax.vmap(_ridge_fit_counted, in_axes=(0, 0, None)),
                           static_argnums=())


def _ridge_fleet(X, y, lam=1e-2, mesh=None):
    """Vmapped per-instance ridge solve; with ``mesh`` the instance axis is
    shard_map-partitioned (one sharded dispatch, no collectives). Shared by
    the LR and GAM fleet fits.

    The instance axis is padded up to its power-of-two bucket (edge
    replication, pad lanes sliced off the solution) so nearby bin sizes
    share ONE compilation — the vmapped solve is per-lane independent, so
    real lanes are unaffected."""
    X, y = jnp.asarray(X), jnp.asarray(y)
    n = X.shape[0]
    pad = bucket_n(n) - n
    X, y = edge_pad(X, pad), edge_pad(y, pad)
    if mesh is None:
        return _ridge_fit_fleet(X, y, lam)[:n]
    from ..distributed.sharding import fleet_sharded
    fit = fleet_sharded(lambda xx, yy: jax.vmap(_ridge_fit_counted,
                                                (0, 0, None))(xx, yy, lam),
                        mesh, key=("ridge_fleet", lam))
    return fit(X, y)[:n]


class LinearForecaster(ForecastModelBase):
    KIND = "LR"
    SUPPORTS_FLEET = True

    def _fit(self, X, y, rng):
        theta = np.asarray(_ridge_fit_j(jnp.asarray(X), jnp.asarray(y)))
        return {"theta": theta}

    def _predict(self, params, X):
        th = params["theta"]
        return np.asarray(X) @ th[:-1] + th[-1]

    @classmethod
    def _fleet_fit(cls, X, y, rng, up, mesh=None):
        # stays device-resident: base.fleet_train converts ONCE for
        # persistence and hands the device copy to the runtime for scoring
        return {"theta": _ridge_fleet(jnp.asarray(X), jnp.asarray(y),
                                      1e-2, mesh=mesh)}

    @classmethod
    def _fleet_predict(cls, stacked, X):
        th = stacked["theta"]                        # (N, F+1)
        return np.einsum("nf,nf->n", np.asarray(X), th[:, :-1]) + th[:, -1]

    @classmethod
    def _fleet_window_predict(cls, model_objects, X):
        # (N, T, F) design against per-instance theta in one einsum
        th = np.stack([m["params"]["theta"] for m in model_objects])
        return (np.einsum("ntf,nf->nt", np.asarray(X), th[:, :-1])
                + th[:, -1][:, None])

    @classmethod
    def _fleet_predict_traced(cls, stacked, x):
        th = jnp.asarray(stacked["theta"], jnp.float32)
        return jnp.einsum("nf,nf->n", x, th[:, :-1],
                          precision=_HIGHEST) + th[:, -1]

    @classmethod
    def _device_predict_factory(cls, spec, statics):
        return cls._fleet_predict_traced

"""Pure-jnp oracle for the fleet-batched MLP — the paper's many-small-models
hot-spot (Castor scoring megabatch): N independent model instances, each with
its OWN weights, scored in one fused computation.

    x:       (N, b, F)                per-instance feature batch
    weights: [ (N, F, H1), (N, H1, H2), ..., (N, Hk, O) ]
    biases:  [ (N, H1), ..., (N, O) ]
ReLU between layers, final layer linear. float32 accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fleet_mlp_reference(x, weights, biases):
    h = x.astype(jnp.float32)
    n = len(weights)
    for i, (w, b) in enumerate(zip(weights, biases)):
        h = jnp.einsum("nbf,nfh->nbh", h, w.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        h = h + b.astype(jnp.float32)[:, None, :]
        if i < n - 1:
            h = jnp.maximum(h, 0.0)
    return h.astype(x.dtype)

"""Distributed flash-decode: the KV cache stays SHARD-RESIDENT along S
(model axis); each shard computes a partial (unnormalised out, running max,
denominator) over its local cache chunk and the shards combine with a tiny
psum of exp-corrected statistics — (B, H, D+2) per layer instead of gathering
the (B, S, KV, D) cache.

This is the beyond-paper serving optimization of §Perf: XLA's auto-partition
of a softmax over a sharded axis chooses to all-gather the cache; expressing
the combine explicitly via shard_map removes ~all decode collective volume.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _partial(q, k, v, lengths, offset):
    """Local unnormalised attention over one S-chunk.
    q: (B,H,D), k/v: (B,S_loc,KV,D), positions offset..offset+S_loc.
    Returns o_unnorm (B,H,D) f32, m (B,H) f32, l (B,H) f32."""
    B, H, D = q.shape
    S_loc, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    # einsum directly on the (B,S,KV,D) layout: no materialised transpose
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    pos = offset + jnp.arange(S_loc)
    valid = pos[None, :] < lengths[:, None]                  # (B, S_loc)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # (B,KV,G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))


def decode_attention_distributed(q, k_cache, v_cache, lengths, *, mesh,
                                 seq_axis: str = "model",
                                 batch_axes=("data",)):
    """q (B,H,D); caches (B,S,KV,D) with S sharded on ``seq_axis`` and B on
    ``batch_axes``. Returns (B,H,D)."""
    import math
    b_ax = tuple(a for a in batch_axes if a in mesh.axis_names)
    if b_ax and q.shape[0] % math.prod(mesh.shape[a] for a in b_ax) != 0:
        b_ax = ()                      # e.g. B=1 long-context: replicate B
    bspec = b_ax if len(b_ax) > 1 else (b_ax[0] if b_ax else None)

    def local(q, k, v, lens):
        i = jax.lax.axis_index(seq_axis)
        o, m, l = _partial(q, k, v, lens, i * k.shape[1])
        m_max = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_max)
        o = jax.lax.psum(o * corr[..., None], seq_axis)
        l = jax.lax.psum(l * corr, seq_axis)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, seq_axis, None, None),
                  P(bspec, seq_axis, None, None), P(bspec)),
        out_specs=P(bspec, None, None), check_vma=False,
    )(q, k_cache, v_cache, lengths)

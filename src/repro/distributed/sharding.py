"""Logical-axis sharding rules: DP/FSDP/TP/EP/SP over the (pod, data, model)
production mesh.

Parameters declare LOGICAL axes (see arch/params.py); a ``Rules`` object maps
them to mesh axes. Activations use a parallel set of rules applied through the
``shard(x, names)`` hook threaded into the model.

Divisibility guard: a mapping is dropped (replicated) when the dim size does
not divide the mesh-axis extent (jit in_shardings require exact division).
Attention projections avoid the issue structurally: they are stored fused
over (H*hd) — see arch/layers.attention_specs.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..arch.params import is_spec

Axes = Union[None, str, Tuple[str, ...]]

PAD_OK: set = set()         # logical axes where uneven sharding would be allowed


# ---------------------------------------------------------------------------
# Fleet-bin sharding: partition a megabatch's INSTANCE axis over devices.
# ---------------------------------------------------------------------------

#: compiled sharded dispatchers, keyed by (caller key, mesh, replicated set,
#: arg count) — one shard_map trace per configuration, like _ROLLOUT_CACHE.
_FLEET_SHARDED_CACHE: Dict[tuple, object] = {}


def _pad_leading(tree, pad: int):
    """Pad every array leaf's leading (instance) axis by repeating its last
    row ``pad`` times. Edge replication — never zeros — so padded instances
    run the same numerics as a real one (e.g. GAM knot rows must stay
    strictly increasing); their outputs are sliced off before anyone reads
    them."""
    import jax.numpy as jnp

    def one(a):
        a = jnp.asarray(a)
        last = jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])
        return jnp.concatenate([a, last], axis=0)

    return jax.tree_util.tree_map(one, tree)


def fleet_sharded(fn, mesh, *, replicated_argnums: Tuple[int, ...] = (),
                  key=None):
    """Wrap ``fn`` — traceable, vmapped/independent over every sharded
    argument's LEADING instance axis, collective-free — so it executes as
    ONE ``shard_map`` dispatch over ``mesh``'s single fleet axis: each
    device computes its N/ndev slice of the bin.

    The wrapper pads the instance axis up to a multiple of the shard count
    (edge-replicated rows, masked back off the outputs), so uneven bins
    just work. Arguments listed in ``replicated_argnums`` are broadcast to
    every device unsharded. With ``key`` the shard_map trace + jit are
    cached across calls (keyed additionally by mesh and arity), mirroring
    the rollout cache in forecast/base.py.
    """
    axis = mesh.axis_names[0]
    nshard = math.prod(mesh.shape.values())
    repl = frozenset(replicated_argnums)

    def build(nargs: int):
        in_specs = tuple(P() if i in repl else P(axis) for i in range(nargs))
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=P(axis), check_vma=False))

    def wrapper(*args):
        cache_k = None if key is None else (key, mesh, repl, len(args))
        inner = _FLEET_SHARDED_CACHE.get(cache_k) if cache_k else None
        if inner is None:
            inner = build(len(args))
            if cache_k is not None:
                _FLEET_SHARDED_CACHE[cache_k] = inner
        first = next(a for i, a in enumerate(args) if i not in repl)
        n = jax.tree_util.tree_leaves(first)[0].shape[0]
        pad = (-n) % nshard
        if pad:
            args = tuple(a if i in repl else _pad_leading(a, pad)
                         for i, a in enumerate(args))
        out = inner(*args)
        if pad:
            out = jax.tree_util.tree_map(lambda x: x[:n], out)
        return out

    return wrapper


@dataclass(frozen=True)
class Rules:
    params: Dict[str, Axes]
    acts: Dict[str, Axes]
    name: str = "baseline"


def baseline_rules(multi_pod: bool = False) -> Rules:
    dp: Axes = ("pod", "data") if multi_pod else ("data",)
    return Rules(
        name="baseline",
        params={
            "embed": dp,            # FSDP (ZeRO-3): shard d_model dim of weights
            "vocab": ("model",),
            "heads": ("model",),    # TP
            "kv_heads": None,       # few KV heads: replicate (baseline)
            "head": None,
            "mlp": ("model",),      # TP
            "expert": ("model",),   # EP
            "expert_mlp": ("model",),   # collapses onto EP axis (dropped)
            "mamba_proj": ("model",),
            "ssm_inner": ("model",),
            "ssm_heads": ("model",),
            "rwkv_heads": ("model",),
            "rwkv_hidden": ("model",),
            "layers": None,
        },
        acts={
            "batch": dp,
            # MoE dispatch groups shard over dp ONLY so the (B,S,d)->(G,Sg,d)
            # reshape is layout-aligned (free); the expert einsum's all-to-all
            # covers the model axis.
            "tokens": dp,
            "expert": ("model",),
            "capacity": ("data",),
            "seq": None,            # "model" under sequence parallelism
            "kv_seq": ("model",),   # decode KV caches: shard S over model
            "kv_heads": None,
            "heads": ("model",),
        })


def serve_rules(multi_pod: bool = False) -> Rules:
    """Weight-STATIONARY serving layout (beyond-paper optimization, §Perf):
    no FSDP at decode — dense weights live TP-sharded (model axis) and are
    never gathered; MoE expert weights are 2D-sharded (expert@model x
    ffn@data) so a 400B MoE fits without per-token weight movement. The KV
    cache stays (B@data, S@model); attention combines S-shards with the
    distributed flash-decode (partial-softmax psum) instead of gathering."""
    base = baseline_rules(multi_pod)
    dp: Axes = ("pod", "data") if multi_pod else ("data",)
    params = dict(base.params)
    params.update({
        "embed": None,               # NO FSDP: weights stationary
        "expert": ("model",),
        "expert_mlp": dp,            # 2D expert sharding
    })
    acts = dict(base.acts)
    return Rules(name="serve_stationary", params=params, acts=acts)


def sp_rules(multi_pod: bool = False) -> Rules:
    """Sequence-parallel training layout: the residual stream (and the remat
    residual stack) shards its SEQUENCE dim over the model axis between
    blocks; GSPMD converts the TP all-reduces into reduce-scatter +
    all-gather pairs and the saved activations shrink 16x."""
    base = baseline_rules(multi_pod)
    acts = dict(base.acts)
    acts["seq"] = ("model",)
    return Rules(name="sp", params=dict(base.params), acts=acts)


def _norm(a: Axes) -> Tuple[str, ...]:
    if a is None:
        return ()
    return (a,) if isinstance(a, str) else tuple(a)


def _mesh_extent(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def spec_for(mesh: Mesh, rules: Dict[str, Axes], logical: Tuple[Optional[str], ...],
             shape: Tuple[int, ...]) -> P:
    """PartitionSpec for one tensor given its logical axes + shape."""
    out, used = [], set()
    for dim, name in zip(shape, logical):
        axes = tuple(a for a in _norm(rules.get(name)) if name is not None
                     and a in mesh.axis_names and a not in used)
        if not axes:
            out.append(None)
            continue
        ext = _mesh_extent(mesh, axes)
        if dim % ext != 0 and name not in PAD_OK:
            out.append(None)
            continue
        used.update(axes)
        out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def param_shardings(mesh: Mesh, rules: Rules, spec_tree):
    """ParamSpec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_for(mesh, rules.params, s.axes, s.shape)),
        spec_tree, is_leaf=is_spec)


def make_shard_fn(mesh: Mesh, rules: Rules):
    """The ``shard(x, logical_names)`` hook threaded through model code."""
    def shard(x, names):
        spec = spec_for(mesh, rules.acts, names, x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return shard


def batch_shardings(mesh: Mesh, rules: Rules, batch_specs):
    """Input-batch shardings: leading dim is batch (or dim 1 for (3,B,S))."""
    def one(s):
        if s.shape and s.shape[0] == 3 and len(s.shape) == 3:   # mrope positions
            logical = (None, "batch", None)
        else:
            logical = ("batch",) + (None,) * (len(s.shape) - 1)
        return NamedSharding(mesh, spec_for(mesh, rules.acts, logical, s.shape))
    return jax.tree_util.tree_map(one, batch_specs)


def decode_state_shardings(mesh: Mesh, rules: Rules, cfg, state_specs):
    """Decode state: caches (periods, B, S, KV, hd) -> B on dp, S on model;
    SSM/RWKV states -> B on dp, heads on model."""
    def one(path, s):
        names = [p.key for p in path if hasattr(p, "key")]
        leaf = names[-1] if names else ""
        nd = len(s.shape)
        if leaf in ("k", "v"):
            logical = (None, "batch", "kv_seq", "kv_heads", None)
        elif leaf == "ssd":                       # (periods,B,H,P,N)
            logical = (None, "batch", "heads", None, None)
        elif leaf == "wkv":                       # (periods,B,H,K,V)
            logical = (None, "batch", "heads", None, None)
        elif leaf == "conv":                      # (periods,B,w-1,ch)
            logical = (None, "batch", None, None)
        elif leaf in ("x_tm", "x_cm"):            # (periods,B,d)
            logical = (None, "batch", None)
        elif leaf == "lengths":
            logical = ("batch",)
        else:
            logical = (None,) * nd
        logical = tuple(logical[:nd]) + (None,) * max(0, nd - len(logical))
        return NamedSharding(mesh, spec_for(mesh, rules.acts, logical, s.shape))
    return jax.tree_util.tree_map_with_path(one, state_specs)

"""Pallas TPU fleet-batched MLP: N independent model instances with
per-instance weights in one kernel — the Castor scoring-megabatch hot-spot.

Grid: (N / block_n,). Each block holds ``block_n`` instances' weights AND
their feature batches in VMEM and runs the whole depth as batched matmuls,
turning the paper's "N containers x tiny GEMM" into MXU-dense batched GEMMs.

``block_n`` is derived from the shapes: the double-buffered per-instance
bytes of every block (x, weights, biases, output) are fitted into Mosaic's
default scoped VMEM. At the paper's width of 512 one instance's weights
alone take ~3.6 MB, so a fixed block of 8 instances cannot fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES, _LANES = 8, 128

#: Mosaic's default scoped-VMEM limit on v4 and v5e, the tightest of the
#: TPU generations (v6e defaults to 32 MiB)
_SCOPED_VMEM_BYTES = 16 << 20

#: share of the scoped limit the pipelined blocks may take; the rest is
#: left to Mosaic's internal scratch
_BLOCK_SHARE = 0.75


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a (rows, cols) slab once padded to the TPU tiling
    (8 x 128 for 32-bit types, 16 x 128 for 16-bit)."""
    size = jnp.dtype(dtype).itemsize
    sub = _SUBLANES * max(1, 4 // size)
    return _round_up(rows, sub) * _round_up(cols, _LANES) * size


def vmem_plan(N: int, b: int, F: int, widths, dtype) -> tuple:
    """``(block_n, vmem_limit_bytes)`` for ``N`` instances of an MLP with
    layer output ``widths``. ``block_n`` is the largest divisor of N whose
    double-buffered blocks fit the default scoped VMEM, so the grid tiles N
    exactly and no instance is padded. ``vmem_limit_bytes`` is None unless
    a single instance does not fit, in which case the limit is raised to
    what that instance needs."""
    fan_in = [F] + list(widths[:-1])
    per = _tile_bytes(b, F, dtype) + _tile_bytes(b, widths[-1], dtype)
    per += sum(_tile_bytes(i, o, dtype) + _tile_bytes(1, o, dtype)
               for i, o in zip(fan_in, widths))
    # pipelined inputs/outputs are double-buffered; the f32 activations
    # (layer input and output) live once
    need = 2 * per + 2 * _tile_bytes(b, max(widths), jnp.float32)
    fit = int(_SCOPED_VMEM_BYTES * _BLOCK_SHARE) // need
    if fit < 1:
        return 1, _round_up(int(need / _BLOCK_SHARE), 1 << 20)
    block_n = max(d for d in range(1, min(fit, N) + 1) if N % d == 0)
    return block_n, None


def _kernel(*refs, depth: int):
    x_ref = refs[0]
    w_refs = refs[1:1 + depth]
    b_refs = refs[1 + depth:1 + 2 * depth]
    o_ref = refs[1 + 2 * depth]

    h = x_ref[...].astype(jnp.float32)                     # (bn, b, F)
    for i in range(depth):
        w = w_refs[i][...].astype(jnp.float32)             # (bn, F, H)
        b = b_refs[i][...].astype(jnp.float32)             # (bn, 1, H)
        h = jax.lax.dot_general(h, w, (((2,), (1,)), ((0,), (0,))),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        h = h + b
        if i < depth - 1:
            h = jnp.maximum(h, 0.0)
    o_ref[...] = h.astype(o_ref.dtype)


def fleet_mlp_pallas(x, weights, biases, *, block_n: int | None = None,
                     interpret: bool = False):
    """x: (N, b, F); weights[i]: (N, in, out); biases[i]: (N, out).
    ``block_n`` (a divisor of N) defaults to :func:`vmem_plan`'s."""
    N, b, F = x.shape
    depth = len(weights)
    widths = [w.shape[-1] for w in weights]
    planned, vmem_limit = vmem_plan(N, b, F, widths, x.dtype)
    block_n = planned if block_n is None else block_n
    if N % block_n:
        raise ValueError(f"block_n={block_n} does not divide N={N}")
    # biases ride as (N, 1, H): a 3-D block's last two dims are (1, H) —
    # the full array extent — so any block_n tiles legally
    biases = [bb[:, None, :] for bb in biases]

    in_specs = [pl.BlockSpec((block_n, b, F), lambda i: (i, 0, 0))]
    for w in weights:
        in_specs.append(pl.BlockSpec((block_n,) + w.shape[1:],
                                     lambda i: (i, 0, 0)))
    for bb in biases:
        in_specs.append(pl.BlockSpec((block_n,) + bb.shape[1:],
                                     lambda i: (i, 0, 0)))
    O = widths[-1]

    return pl.pallas_call(
        functools.partial(_kernel, depth=depth),
        grid=(N // block_n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_n, b, O), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, b, O), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(x, *weights, *biases)

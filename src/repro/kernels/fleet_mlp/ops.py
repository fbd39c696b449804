"""jit'd public entry point for the fleet-batched per-instance-weights MLP."""
from __future__ import annotations

from functools import partial

import jax

from ..common import resolve
from .ref import fleet_mlp_reference

#: Python-level dispatch counter. Inside a jitted caller (the device
#: scoring rollout) the count rises only while TRACING — once per compiled
#: bin shape — whereas the host-loop reference path dispatches once per
#: horizon step. Benchmarks/tests read it via ``invocation_count()``.
_invocations = 0


def invocation_count() -> int:
    return _invocations


@partial(jax.jit, static_argnames=("impl",))
def _fleet_mlp(x, weights, biases, *, impl: str):
    if impl == "xla":
        return fleet_mlp_reference(x, weights, biases)
    from .kernel import fleet_mlp_pallas
    # the block size divides N (see kernel.vmem_plan), so a mesh-sharded
    # bin's per-device slice of any size runs without padding
    return fleet_mlp_pallas(x, weights, biases,
                            interpret=(impl == "pallas_interpret"))


def fleet_mlp(x, weights, biases, *, impl: str | None = None):
    """x: (N,b,F); weights/biases: per-layer stacks with leading N.
    Returns (N,b,O). ReLU between layers; final layer linear."""
    global _invocations
    _invocations += 1
    # resolved before the jit, so the implementation keys its cache
    return _fleet_mlp(x, weights, biases, impl=resolve(impl))

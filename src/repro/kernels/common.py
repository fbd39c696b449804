"""Shared kernel-dispatch policy.

Every kernel exposes ``op(..., impl=None)`` where impl is one of
    "xla"               pure-jnp (chunked where applicable) — CPU default
    "pallas"            real Pallas lowering — TPU default
    "pallas_interpret"  Pallas interpret=True — CPU validation of kernel bodies
``None`` resolves via :func:`default_impl`: the backend chooses. Tests that
want another path pass ``impl=`` explicitly.
"""
from __future__ import annotations

import jax

VALID = ("xla", "pallas", "pallas_interpret")


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve(impl: str | None) -> str:
    impl = impl or default_impl()
    if impl not in VALID:
        raise ValueError(f"unknown kernel impl {impl!r}; expected {VALID}")
    return impl

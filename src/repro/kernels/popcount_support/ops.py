"""Dispatching wrapper: Pallas kernel on TPU, jnp oracle elsewhere.

``repro.core.eclat`` routes its pair batches through here so the hot loop is
kernel-backed on real hardware while remaining exact (and fast enough) on the
CPU host used for tests (``tests/test_kernels.py``).
"""
from __future__ import annotations

import jax

from .popcount_support import popcount_support
from .ref import popcount_support_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def intersect_support(a: jax.Array, b: jax.Array, *, interpret: bool | None = None):
    """Batched tidset AND + support.  See kernel docstring for tiling."""
    if interpret is None:
        if _on_tpu():
            return popcount_support(a, b)
        return popcount_support_ref(a, b)
    return popcount_support(a, b, interpret=interpret)

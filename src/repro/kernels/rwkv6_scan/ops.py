"""jit'd public wrapper for the WKV recurrence."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import wkv_chunked
from .ref import wkv_ref

__all__ = ["wkv"]


def wkv(r, k, v, w, u, state, *, use_pallas: bool = True,
        interpret: bool = False, chunk: int = 64):
    if use_pallas:
        return wkv_chunked(r, k, v, w, u, state, chunk=chunk,
                           interpret=interpret)
    return wkv_ref(r, k, v, w, u, state)

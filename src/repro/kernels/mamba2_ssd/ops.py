"""jit'd public wrapper for the SSD scan."""

from __future__ import annotations

from .kernel import ssd_chunked
from .ref import ssd_ref

__all__ = ["ssd"]


def ssd(x, dt, A, B, C, D, state, *, use_pallas: bool = True,
        interpret: bool = False, chunk: int = 64):
    if use_pallas:
        return ssd_chunked(x, dt, A, B, C, D, state, chunk=chunk,
                           interpret=interpret)
    return ssd_ref(x, dt, A, B, C, D, state)

"""jit'd public wrapper around the pivot kernel."""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from .kernel import pivot_tiled
from .ref import pivot_ref

__all__ = ["pivot", "pivot_columns"]


def pivot(rows: jnp.ndarray, *, use_pallas: bool = True,
          interpret: bool = False) -> jnp.ndarray:
    """[N, W] row-major words -> [W, N] column-major words."""
    if use_pallas:
        return pivot_tiled(rows, interpret=interpret)
    return rows.T


def pivot_columns(rows: jnp.ndarray, widths: Sequence[int], *,
                  use_pallas: bool = True,
                  interpret: bool = False) -> List[jnp.ndarray]:
    """[N, W] + per-column word widths -> list of [N, w_i] column tensors
    (each contiguous; i.e. the arrowcol layout on device)."""
    colmajor = pivot(rows, use_pallas=use_pallas, interpret=interpret)
    out = []
    off = 0
    for w in widths:
        out.append(colmajor[off: off + w].T)
        off += w
    return out

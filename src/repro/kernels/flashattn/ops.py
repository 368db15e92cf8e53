"""jit'd public wrapper for blockwise attention."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention
from .ref import attention_ref

__all__ = ["attention"]


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, use_pallas: bool = True,
              interpret: bool = False, blk_q: int = 128,
              blk_k: int = 128) -> jnp.ndarray:
    """Drop-in blockwise GQA attention; falls back to the jnp oracle."""
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, blk_q=blk_q,
                               blk_k=blk_k, interpret=interpret)
    return attention_ref(q, k, v, causal=causal)

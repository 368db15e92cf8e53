"""jit'd public wrapper for decode attention."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import decode_attention
from .ref import decode_attention_ref

__all__ = ["decode_attn"]


def decode_attn(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                length, *, use_pallas: bool = True,
                interpret: bool = False, blk_s: int = 512) -> jnp.ndarray:
    if use_pallas:
        return decode_attention(q, k_cache, v_cache, length,
                                blk_s=blk_s, interpret=interpret)
    return decode_attention_ref(q, k_cache, v_cache, length)

"""Blockwise (flash) GQA attention with its backward pass, for train and
prefill: JAX's splash attention kernels (forward, dq and dkv), which skip
the blocks a causal mask hides and never write the [Sq, Sk] score plane.

K and V keep their KV heads: each q head reads the K/V head of its group.
q and k enter the MXU as the values they are, with float32 accumulation;
the softmax's running max and sum and the output accumulator are float32.
The softmax scale (``scale``, 1/sqrt(hd) by default) is applied to q
before the kernel, in float32 and cast back to q's dtype: exact where it is
a power of two (1/sqrt(hd) for hd 64 or 256), one rounding of q's dtype
otherwise.

The blocks are a function of the sequence length: ``block_for``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

__all__ = ["attention", "block_for", "supports"]

# The largest block: the fastest of 256, 512 and 1024 for q and kv on a
# TPU v5e at S = 2048 (forward and backward; PERF.md, section 6).
BLOCK = 1024
_LANES = 128


def block_for(seq: int) -> Optional[int]:
    """The block of a sequence of ``seq``: the largest power of two from
    BLOCK down to 128 that divides it, or None where none does."""
    b = BLOCK
    while b >= _LANES:
        if seq % b == 0:
            return b
        b //= 2
    return None


def supports(q_shape, k_shape) -> bool:
    """Whether the kernel takes q [B,Sq,H,hd] and k [B,Sk,KV,hd]."""
    _, sq, h, hd = q_shape
    _, sk, kv, hd_k = k_shape
    return (hd == hd_k and h % kv == 0 and block_for(sq) is not None
            and block_for(sk) is not None)


@functools.lru_cache(maxsize=16)
def _kernel(heads: int, sq: int, sk: int, causal: bool, interpret: bool):
    if causal:
        head_mask = splash.CausalMask((sq, sk), offset=sk - sq)
    else:
        head_mask = splash.FullMask((sq, sk))
    bq, bkv = block_for(sq), block_for(sk)
    blocks = splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=bq, block_kv_dq=bkv)
    with jax.ensure_compile_time_eval():     # concrete mask tables, cached
        return splash.make_splash_mha(
            splash.MultiHeadMask([head_mask] * heads), block_sizes=blocks,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, scale: Optional[float] = None,
              interpret: bool = False) -> jnp.ndarray:
    """q: [B,Sq,H,hd]; k, v: [B,Sk,KV,hd], KV dividing H; causal masks
    aligned at the end (query i sees keys up to i + Sk - Sq); the scores
    are scaled by ``scale`` (None: 1/sqrt(hd)).  Returns [B,Sq,H,hd] in q's
    dtype.  Differentiable in q, k and v."""
    if not supports(q.shape, k.shape):
        raise ValueError(f"flash attention does not take q {q.shape}, "
                         f"k {k.shape}")
    _, sq, h, hd = q.shape
    kernel = _kernel(h, sq, k.shape[1], causal, interpret)
    qf = q.astype(jnp.float32)
    qf = qf / math.sqrt(hd) if scale is None else qf * scale
    q = qf.astype(q.dtype)
    heads_first = lambda t: t.transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)

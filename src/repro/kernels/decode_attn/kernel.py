"""Pallas TPU kernel: one-token decode attention over a paged/long KV cache.

Decode attention is memory-bound: the whole valid KV prefix streams from
HBM once per token while compute is a [H, hd] x [hd, BLK_S] matvec-like
contraction.  The kernel tiles the cache sequence dim into VMEM blocks
(BLK_S x hd per KV head), keeps the online-softmax state in VMEM scratch,
and masks the tail beyond ``length`` with the running-max trick — so HBM
traffic is exactly one pass over K and V (the roofline floor for decode).

Grid: (batch, s_blocks); innermost s visits the cache sequentially.  The
cache is viewed as [B, S, KV*hd] (a free reshape), so one block holds all
KV heads of BLK_S positions with full-extent trailing dims: that tiles on
the TPU for any KV and hd, where a [.., 1, hd] per-head block does not.
The kernel walks the KV heads statically; each head's group queries
[g, hd] ride in VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "BLK_S"]

BLK_S = 512
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, blk_s: int, kv: int, hd: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0]
    # skip blocks entirely past the valid prefix
    @pl.when(si * blk_s < length)
    def _body():
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)                     # [g, hd]
            k = k_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)  # [BLK_S, hd]
            v = v_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [g, BLK_S]
            pos = si * blk_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(si == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_s", "interpret"))
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, length: jnp.ndarray,
                     blk_s: int = BLK_S, interpret: bool = False) -> jnp.ndarray:
    """q: [B,H,hd]; k/v_cache: [B,S,KV,hd]; length: [] int32."""
    B, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    blk_s = min(blk_s, S)
    qg = q.reshape(B, KV, g, hd)
    kf = k_cache.reshape(B, S, KV * hd)
    vf = v_cache.reshape(B, S, KV * hd)
    grid = (B, S // blk_s)
    kernel = functools.partial(_decode_kernel, scale=scale, blk_s=blk_s,
                               kv=KV, hd=hd)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, KV, g, hd), lambda b, s: (b, 0, 0, 0)),
            pl.BlockSpec((1, blk_s, KV * hd), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, blk_s, KV * hd), lambda b, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, g, hd), lambda b, s: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((KV, g, 1), jnp.float32),
            pltpu.VMEM((KV, g, 1), jnp.float32),
            pltpu.VMEM((KV, g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(length, jnp.int32).reshape(1), qg, kf, vf)
    return out.reshape(B, H, hd)

"""Pallas TPU kernel: Mamba-2 SSD scan, chunk-parallel within a tile.

The SSD dual form: within a chunk, outputs decompose into an *intra-chunk*
part (a lower-triangular decay-weighted attention-like matmul — MXU work)
plus an *inter-chunk* part (the carried state applied through cumulative
decays).  Only the [hd, N] state carries across chunks, held in VMEM
scratch along the sequential chunk grid axis.

Grid: (B*H, S/CHUNK).  Per chunk, with hd=64, N=64, CHUNK=64: tiles are
64x64 f32 — MXU-shaped — and the whole working set is ~100 KiB of VMEM.

Layout: every block has full-extent or (8, 128)-aligned trailing dims, as
the TPU requires.  ``dt`` is viewed as [B*H, S/CHUNK, CHUNK] and held whole
per head (a chunk is one row of it); the per-head scalars A and D sit in
SMEM; B and C are read from their [B, S, N] arrays by batch index, never
broadcast over heads in HBM.  The cumulative decay is a masked row sum, so
it needs no scan inside the kernel.

The intra-chunk math here follows the SSD paper's scalar-decay-per-head
structure:  decay(i<-j) = exp(cum[i] - cum[j]) with cum = cumsum(dt*A).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_chunked", "CHUNK"]

CHUNK = 64


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
                y_ref, sout_ref, state_ref, *, chunk: int, heads: int):
    bh = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    x = x_ref[0].astype(jnp.float32)               # [C, hd]
    dt = dt_ref[0, pl.ds(ci, 1), :].astype(jnp.float32)   # [1, C]
    A = a_ref[bh % heads]                          # scalar (per head)
    Bm = b_ref[0].astype(jnp.float32)              # [C, N]
    Cm = c_ref[0].astype(jnp.float32)              # [C, N]
    D = d_ref[bh % heads]                          # scalar

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = jj <= ii
    da = dt * A                                    # [1, C] (negative)
    cum_c = jnp.sum(jnp.where(causal, da, 0.0), axis=1, keepdims=True)  # [C, 1]
    cum_r = cum_c.T                                # [1, C]
    cum_last = cum_c[chunk - 1:chunk, :]           # [1, 1]
    # inter-chunk: y_inter[i] = exp(cum[i]) * C_i . state
    carry = state_ref[...]                         # [hd, N]
    y_inter = jnp.exp(cum_c) * jax.lax.dot_general(
        Cm, carry, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # [C, hd]
    # intra-chunk: G[i,j] = exp(cum[i]-cum[j]) * (C_i . B_j) * dt[j], j<=i
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [C, C]
    decay = jnp.exp(cum_c - cum_r)
    G = jnp.where(causal, cb * decay * dt, 0.0)
    y_intra = jax.lax.dot_general(G, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0] = (y_inter + y_intra + D * x).astype(y_ref.dtype)
    # state update: S' = exp(cum[-1]) * S + sum_j exp(cum[-1]-cum[j]) dt_j x_j B_j^T
    wts = jnp.exp(cum_last - cum_c) * dt.T         # [C, 1]
    sx = jax.lax.dot_general(x * wts, Bm,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [hd, N]
    state_ref[...] = jnp.exp(cum_last) * carry + sx

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit():
        sout_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(x, dt, A, B, C, D, state, chunk: int = CHUNK,
                interpret: bool = False):
    """x: [B,S,H,hd]; dt: [B,S,H]; A,D: [H]; B,C: [B,S,N];
    state: [B,H,hd,N].  Returns (y [B,S,H,hd] f32, final state f32)."""
    Bb, S, H, hd = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    BH = Bb * H
    n_chunks = S // chunk
    xf = x.transpose(0, 2, 1, 3).reshape(BH, S, hd)
    dtf = dt.transpose(0, 2, 1).reshape(BH, n_chunks, chunk)
    sf = state.reshape(BH, hd, N).astype(jnp.float32)

    grid = (BH, n_chunks)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, heads=H)
    y, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n_chunks, chunk), lambda b, c: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b // H, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b // H, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, hd, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, hd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, A.astype(jnp.float32), B, C, D.astype(jnp.float32), sf)
    y = y.reshape(Bb, H, S, hd).transpose(0, 2, 1, 3)
    return y, s_out.reshape(Bb, H, hd, N)

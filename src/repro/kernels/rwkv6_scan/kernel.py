"""Pallas TPU kernel: RWKV-6 WKV recurrence, chunk-tiled.

TPU adaptation: the recurrence is sequential in t, but only the [hd, hd]
state matrix carries between steps.  The kernel tiles time into CHUNK-sized
VMEM blocks — per grid step it streams r/k/v/w chunks from HBM once, runs
the recurrence in-register/VMEM (fori_loop over the chunk), and carries the
state in VMEM scratch across the (innermost, sequential) chunk axis.  HBM
traffic is one pass over the inputs — the memory-bound floor — versus a
naive lax.scan which round-trips the state every step.

Grid: (B*H, S/CHUNK).  hd is 64 for rwkv6 heads: the state tile is
64x64xf32 = 16 KiB, so state + 4 input chunks fit VMEM comfortably.  The
per-head bonus ``u`` is read as a [1, 1, hd] block of the [H, 1, hd] array
(head = program b mod H), so its trailing dims are full-extent and it is
never broadcast to B*H in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv_chunked", "CHUNK"]

CHUNK = 64


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sout_ref, state_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    # rows are loaded as [1, hd] and turned into [hd, 1] columns by a 2-D
    # transpose: the key axis of the state runs down the sublanes
    u = u_ref[0].astype(jnp.float32).T             # [hd, 1]

    def step(t, _):
        rt = r_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # [1, hd]
        kt = k_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        vt = v_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        wt = w_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        s = state_ref[...]                         # [hd, hd] key-major
        kv = kt.T * vt                             # outer product
        y = jnp.sum(rt.T * (s + u * kv), axis=0, keepdims=True)  # [1, hd]
        y_ref[0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        state_ref[...] = wt.T * s + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit_state():
        sout_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_chunked(r, k, v, w, u, state, chunk: int = CHUNK,
                interpret: bool = False):
    """r,k,v,w: [B,S,H,hd]; u: [H,hd]; state: [B,H,hd,hd].
    Returns (y [B,S,H,hd] f32, final state [B,H,hd,hd] f32)."""
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    BH = B * H

    def flat(t):
        return t.transpose(0, 2, 1, 3).reshape(BH, S, hd)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    uf = u.reshape(H, 1, hd)
    sf = state.reshape(BH, hd, hd).astype(jnp.float32)

    grid = (BH, S // chunk)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    y, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b % H, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, sf)
    y = y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    return y, s_out.reshape(B, H, hd, hd)

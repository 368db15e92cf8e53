"""Mamba-2 (SSD) mixer: the zamba2 backbone layer and Granite 4.0-H's
recurrent mixer.

Pure-JAX reference: selective state-space recurrence as ``lax.scan`` over
time (the Pallas chunked kernel in ``repro.kernels.mamba2_ssd`` implements
the chunk-parallel SSD form for TPU).  The mixer, as published
(``norm_before_gate=False``): in_proj -> z, xBC, dt; a depthwise causal
conv with silu over xBC; the scan with a D skip; the gated RMSNorm
``rmsnorm(y * silu(z))`` over all inner channels; out_proj.

State per layer (decode): (conv_state [B, K-1, d_conv_in], ssm_state
[B, nheads, hd, N]).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import _dense_init, dtype_of, rmsnorm, rmsnorm_init

Params = Dict[str, Any]

CONV_K = 4   # depthwise causal conv window
NGROUPS = 1  # B/C groups


def _dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    d_in = cfg.expand * cfg.d_model
    hd = cfg.ssm_head_dim
    nheads = d_in // hd
    N = cfg.ssm_state
    return d_in, hd, nheads, N


def _conv_dim(cfg: ModelConfig) -> int:
    d_in, _, _, N = _dims(cfg)
    return d_in + 2 * NGROUPS * N


def mamba2_mixer_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    d_in, hd, nheads, N = _dims(cfg)
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 6)
    conv_dim = _conv_dim(cfg)
    return {
        # in_proj: x -> [z (d_in), xBC (conv_dim), dt (nheads)]
        "w_in": _dense_init(ks[0], (d, d_in + conv_dim + nheads), dt),
        "conv_w": _dense_init(ks[1], (CONV_K, conv_dim), dt, scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.zeros((nheads,), jnp.float32),       # A = -exp(A_log)
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.full((nheads,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "ln_out": rmsnorm_init(cfg, d_in),
        "w_out": _dense_init(ks[2], (d_in, d), dt),
    }


def mamba2_block_init(key, cfg: ModelConfig) -> Params:
    """zamba2's layer: the mixer with its own pre-norm."""
    return {"ln": rmsnorm_init(cfg), **mamba2_mixer_init(key, cfg)}


def _split_in(cfg: ModelConfig, proj: jnp.ndarray):
    d_in = _dims(cfg)[0]
    end = d_in + _conv_dim(cfg)
    return proj[..., :d_in], proj[..., d_in:end], proj[..., end:]


def _causal_conv(xBC: jnp.ndarray, conv_state: jnp.ndarray,
                 w: jnp.ndarray, b: jnp.ndarray):
    """Depthwise causal conv (window CONV_K) via shifted adds.

    xBC: [B,S,C]; conv_state: [B,K-1,C] (inputs before position 0).
    Returns (out [B,S,C], new_conv_state [B,K-1,C])."""
    full = jnp.concatenate([conv_state.astype(xBC.dtype), xBC], axis=1)
    S = xBC.shape[1]
    out = b
    for i in range(CONV_K):
        out = out + full[:, i: i + S, :] * w[i]
    new_state = full[:, S:, :]  # last K-1 inputs
    return jax.nn.silu(out.astype(jnp.float32)).astype(xBC.dtype), new_state


def _ssd_scan(x, dt, A, B, C, D, state):
    """Selective scan.

    x: [B,S,H,hd]; dt: [B,S,H] (post-softplus); A: [H] (negative);
    B,C: [B,S,N] (ngroups=1, shared across heads); D: [H];
    state: [B,H,hd,N].  Returns (y [B,S,H,hd], new state).
    """
    def step(s, inp):
        xt, dtt, Bt, Ct = inp          # [B,H,hd], [B,H], [B,N], [B,N]
        da = jnp.exp(dtt * A)          # [B,H]
        # products and sums in float32 on the vector units, not the MXU
        dBx = (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        s = da[..., None, None] * s + dBx
        yt = jnp.sum(s * Ct[:, None, None, :], axis=-1) \
            + D[None, :, None] * xt
        return s, yt

    xs = jnp.moveaxis(x, 1, 0)
    dts = jnp.moveaxis(dt, 1, 0)
    Bs = jnp.moveaxis(B, 1, 0)
    Cs = jnp.moveaxis(C, 1, 0)
    state, ys = jax.lax.scan(step, state, (xs, dts, Bs, Cs))
    return jnp.moveaxis(ys, 0, 1), state


def mamba2_mixer(p: Params, cfg: ModelConfig, h: jnp.ndarray, state: Tuple):
    """The mixer alone, on normed inputs h: [B,S,d]; state: (conv_state,
    ssm_state).  Returns (out [B,S,d], new state)."""
    conv_state, ssm_state = state
    B_, S, d = h.shape
    d_in, hd, nheads, N = _dims(cfg)
    proj = jnp.einsum("bsd,de->bse", h, p["w_in"])
    z, xBC, dt_raw = _split_in(cfg, proj)
    xBC, conv_state = _causal_conv(xBC, conv_state, p["conv_w"], p["conv_b"])
    xin = xBC[..., :d_in].reshape(B_, S, nheads, hd)
    Bmat = xBC[..., d_in: d_in + NGROUPS * N].astype(jnp.float32)
    Cmat = xBC[..., d_in + NGROUPS * N:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,S,H]
    A = -jnp.exp(p["A_log"])
    y, ssm_state = _ssd_scan(
        xin.astype(jnp.float32), dt, A, Bmat, Cmat, p["D"], ssm_state
    )
    y = y.reshape(B_, S, d_in) * jax.nn.silu(z.astype(jnp.float32))
    y = rmsnorm(p["ln_out"], y, cfg.norm_eps).astype(h.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, (conv_state, ssm_state)


def mamba2_block(p: Params, cfg: ModelConfig, x: jnp.ndarray, state: Tuple):
    """zamba2's layer, x + mixer(rmsnorm(x)); x: [B,S,d]; state: (conv_state,
    ssm_state)."""
    h = rmsnorm(p["ln"], x, cfg.norm_eps)
    out, state = mamba2_mixer(p, cfg, h, state)
    return x + out, state


def mamba2_init_state(cfg: ModelConfig, batch: int, dtype=None):
    d_in, hd, nheads, N = _dims(cfg)
    dt = dtype or jnp.dtype(cfg.dtype)
    return (
        jnp.zeros((batch, CONV_K - 1, _conv_dim(cfg)), dt),
        jnp.zeros((batch, nheads, hd, N), jnp.float32),
    )

"""Shared model layers (pure-JAX, functional): norms, rotary embeddings,
GQA attention with KV cache, SwiGLU MLP, and capacity-based MoE.

Conventions
-----------
* Params are nested dicts of ``jnp.ndarray``; per-layer params are stacked on
  a leading ``L`` axis by the model assemblers and consumed via
  ``jax.lax.scan`` (compact HLO — essential for the 512-device dry-run).
* Activations flow in ``cfg.dtype`` (bf16 by default); norms/softmax/router
  run in f32.
* The MoE block is expert-parallel via ``shard_map`` over the ``model`` mesh
  axis: activations are replicated over that axis between blocks (standard
  TP layout), so each shard simply *selects* the tokens routed to its local
  experts and the combine is the same ``psum`` a TP FFN needs anyway — no
  explicit all-to-all, balanced compute, capacity-factor drop policy.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import telemetry
from ..kernels.flashattn.ops import attention as flash_attention
from ..kernels.flashattn.ops import supports as flash_supports
from .config import ModelConfig

Params = Dict[str, Any]


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------- #
# initializers
# --------------------------------------------------------------------------- #

def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #

def rmsnorm_init(cfg: ModelConfig, width: Optional[int] = None) -> Params:
    return {"scale": jnp.ones((width or cfg.d_model,), jnp.float32)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


def layernorm_init(cfg: ModelConfig, width: Optional[int] = None) -> Params:
    w = width or cfg.d_model
    return {"scale": jnp.ones((w,), jnp.float32), "bias": jnp.zeros((w,), jnp.float32)}


def layernorm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# rotary embeddings (RoPE and M-RoPE)
# --------------------------------------------------------------------------- #

def _rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, hd]; pos: [B, S] (int)."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                       # [hd/2]
    ang = pos[..., None].astype(jnp.float32) * freqs     # [B, S, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, pos: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int] = (1, 1, 2)) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL): the head dim is split into
    temporal/height/width sections, each rotated by its own position id.

    x: [B, S, H, hd]; pos: [3, B, S] (t/h/w ids; for pure text all equal).
    ``sections`` are relative weights over the hd/2 frequency slots.
    """
    hd = x.shape[-1]
    half = hd // 2
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections[:-1]:
        acc += (half * s) // total
        bounds.append(acc)
    freqs = _rope_freqs(hd, theta)                       # [half]
    # per-frequency-slot section id: 0,1,2
    slot = jnp.zeros((half,), jnp.int32)
    slot = jnp.where(jnp.arange(half) >= bounds[0], 1, slot)
    slot = jnp.where(jnp.arange(half) >= bounds[1], 2, slot)
    # gather per-slot positions: pos_sel [B, S, half]
    pos_f = pos.astype(jnp.float32)                      # [3, B, S]
    pos_sel = jnp.take(pos_f, slot, axis=0)              # [half, B, S]
    pos_sel = jnp.moveaxis(pos_sel, 0, -1)               # [B, S, half]
    ang = pos_sel * freqs                                # [B, S, half]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# attention (GQA, optional QKV bias, KV cache)
# --------------------------------------------------------------------------- #

def attention_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = dtype_of(cfg)
    k = jax.random.split(key, 4)
    p: Params = {
        "wq": _dense_init(k[0], (d, H, hd), dt),
        "wk": _dense_init(k[1], (d, KV, hd), dt),
        "wv": _dense_init(k[2], (d, KV, hd), dt),
        "wo": _dense_init(k[3], (H, hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((KV, hd), dt)
        p["bv"] = jnp.zeros((KV, hd), dt)
    return p


def _project_qkv(params: Params, xq: jnp.ndarray, xkv: jnp.ndarray):
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray,
                scale: Optional[float] = None) -> jnp.ndarray:
    """q: [B,Sq,H,hd], k: [B,Sk,KV,hd] -> scores [B,H,Sq,Sk] (f32), times
    ``scale`` (None: over sqrt(hd))."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, Sq, KV, g, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s.reshape(B, KV * g, Sq, k.shape[1])
    return s / math.sqrt(hd) if scale is None else s * scale


def _gqa_out(w: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """w: [B,H,Sq,Sk] (f32), v: [B,Sk,KV,hd] -> [B,Sq,H,hd]."""
    B, H, Sq, Sk = w.shape
    KV = v.shape[2]
    g = H // KV
    wg = w.reshape(B, KV, g, Sq, Sk)
    o = jnp.einsum("bkgqs,bskh->bqkgh", wg, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[3])


def _constrain_seq(t: jnp.ndarray, mesh, seq_dim: int) -> jnp.ndarray:
    """Context-parallel constraint: shard a sequence dim over `model` (head-
    count independent — works for 15/28/40-head models on a 16-way axis)."""
    if mesh is None or "model" not in mesh.axis_names:
        return t
    if t.shape[seq_dim] % mesh.shape["model"] != 0:
        return t
    baxes = tuple(a for a in mesh.axis_names if a != "model")
    dims: list = [None] * t.ndim
    dims[0] = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    dims[seq_dim] = "model"
    return jax.lax.with_sharding_constraint(
        t, jax.sharding.NamedSharding(mesh, P(*dims)))


def _on_tpu(mesh) -> bool:
    """Whether the arrays of a call under ``mesh`` (or, without one, of the
    default backend) live on TPU."""
    if mesh is None:
        return jax.default_backend() == "tpu"
    return mesh.devices.flat[0].platform == "tpu"


def _batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def _takes_flash(q: jnp.ndarray, k: jnp.ndarray, causal: bool,
                 self_attn: bool, mesh) -> bool:
    """Causal self-attention on TPU that the flash kernel takes, with heads
    and sequence whole on every device (no `model` axis above 1) and the
    batch split evenly over the other axes."""
    if not (causal and self_attn and flash_supports(q.shape, k.shape)
            and _on_tpu(mesh)):
        return False
    if mesh is None:
        return True
    split = math.prod(mesh.shape[a] for a in _batch_axes(mesh))
    return mesh.shape.get("model", 1) == 1 and q.shape[0] % split == 0


def _flash(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mesh,
           scale: Optional[float]) -> jnp.ndarray:
    """The flash kernel, under shard_map over the batch axes where a mesh
    is given, so that each device runs it on its own rows."""
    kernel = partial(flash_attention, scale=scale)
    if mesh is None:
        return kernel(q, k, v)
    spec = P(_batch_axes(mesh) or None, None, None, None)
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


def attention(params: Params, cfg: ModelConfig, x: jnp.ndarray,
              pos: jnp.ndarray, *, causal: bool = True,
              x_kv: Optional[jnp.ndarray] = None, mesh=None) -> jnp.ndarray:
    """Full-sequence attention (train / prefill). pos: [B,S] or [3,B,S].

    Causal self-attention on TPU runs the flash kernel (``_takes_flash``),
    which never writes the [Sq, Sk] score plane.  Every other call
    computes the plane with einsums; with a mesh, the query sequence dim is
    then sharded over `model` (context parallelism): score/softmax compute
    and memory scale 1/|model| for any head count; K/V stay gathered (they
    are KV-head sized, GQA-small).  ``model.attention_path`` counts the
    path each call site takes, once per trace."""
    xkv = x if x_kv is None else x_kv
    q, k, v = _project_qkv(params, x, xkv)
    if cfg.rope == "mrope":
        q, k = apply_mrope(q, pos, cfg.rope_theta), apply_mrope(k, pos, cfg.rope_theta)
    elif cfg.rope == "rope":
        q, k = apply_rope(q, pos, cfg.rope_theta), apply_rope(k, pos, cfg.rope_theta)
    flash = _takes_flash(q, k, causal, x_kv is None, mesh)
    telemetry.counter("model.attention_path",
                      path="kernel" if flash else "einsum").inc()
    if flash:
        o = _flash(q, k, v, mesh, cfg.attn_scale)
    else:
        o = _attend_einsum(q, k, v, causal and x_kv is None, mesh,
                           cfg.attn_scale)
    return jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), params["wo"])


def _attend_einsum(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   causal: bool, mesh,
                   scale: Optional[float] = None) -> jnp.ndarray:
    q = _constrain_seq(q, mesh, 1)
    scores = _gqa_scores(q, k, scale)
    scores = _constrain_seq(scores, mesh, 2)
    if causal:
        Sq, Sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), Sk - Sq)
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = _gqa_out(w, v)
    return _constrain_seq(o, mesh, 1)


def attention_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     index: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode over a KV cache.

    x: [B, 1, d]; cache_k/v: [B, S, KV, hd]; index: [B] position of the new
    token in each row, so every row is its own sequence.
    Returns (out [B,1,d], new_cache_k, new_cache_v).
    """
    B, S, KV, hd = cache_k.shape
    q, k, v = _project_qkv(params, x, x)
    pos = index[:, None].astype(jnp.int32)               # [B, 1]
    if cfg.rope == "mrope":
        pos3 = jnp.broadcast_to(pos, (3,) + pos.shape)
        q, k = apply_mrope(q, pos3, cfg.rope_theta), apply_mrope(k, pos3, cfg.rope_theta)
    elif cfg.rope == "rope":
        q, k = apply_rope(q, pos, cfg.rope_theta), apply_rope(k, pos, cfg.rope_theta)
    rows = jnp.arange(B)
    cache_k = cache_k.at[rows, index].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[rows, index].set(v[:, 0].astype(cache_v.dtype))
    scores = _gqa_scores(q, cache_k, cfg.attn_scale)     # [B,H,1,S]
    valid = jnp.arange(S)[None, :] <= index[:, None]     # [B,S]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = _gqa_out(w, cache_v)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), params["wo"])
    return out, cache_k, cache_v


def cross_attention_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                           enc_k: jnp.ndarray, enc_v: jnp.ndarray) -> jnp.ndarray:
    """Decode-side cross attention over precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    scores = _gqa_scores(q, enc_k)
    w = jax.nn.softmax(scores, axis=-1)
    o = _gqa_out(w, enc_v)
    return jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), params["wo"])


# --------------------------------------------------------------------------- #
# MLP (SwiGLU) and MoE
# --------------------------------------------------------------------------- #

def mlp_init(key, cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    dt = dtype_of(cfg)
    k = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k[0], (d, f), dt),
        "w_up": _dense_init(k[1], (d, f), dt),
        "w_down": _dense_init(k[2], (f, d), dt),
    }


def mlp(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, params["w_gate"]).astype(jnp.float32))
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    return jnp.einsum("bsf,fd->bsd", (g.astype(x.dtype) * u), params["w_down"])


def moe_init(key, cfg: ModelConfig) -> Params:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
    dt = dtype_of(cfg)
    k = jax.random.split(key, 4)
    return {
        "router": _dense_init(k[0], (d, E), jnp.float32),
        "w_gate": _dense_init(k[1], (E, d, f), dt),
        "w_up": _dense_init(k[2], (E, d, f), dt),
        "w_down": _dense_init(k[3], (E, f, d), dt),
    }


def _moe_local(x: jnp.ndarray, router: jnp.ndarray, w_gate: jnp.ndarray,
               w_up: jnp.ndarray, w_down: jnp.ndarray, *,
               cfg: ModelConfig, n_shards: int, shard_index: jnp.ndarray,
               fparts: int = 1):
    """Per-shard MoE body (runs under shard_map over the `model` axis).

    x: [B_loc, S, d] (replicated over the model axis);
    w_*: [E_loc, ...] local expert slices.  Each shard routes all tokens,
    keeps those destined to its local experts (fixed capacity), computes
    them, scatters results back, and the caller psums over the model axis.

    When the mesh axis is larger than the expert count (e.g. grok-1: 8
    experts on a 16-way model axis), each expert is split over ``fparts``
    consecutive shards along d_ff (EPxTP): those shards process the *same*
    dispatched tokens on complementary d_ff slices and the final psum sums
    the partial FFN outputs — the same combine that merges experts.
    """
    E, k_top = cfg.moe_experts, cfg.moe_top_k
    E_loc = w_gate.shape[0]
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = (xt.astype(jnp.float32) @ router)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k_top)          # [T, k]
    # capacity per *local* expert; never below all-tokens at tiny T (decode
    # batches must not drop tokens)
    cap = int(math.ceil(T * k_top / E * cfg.capacity_factor))
    cap = min(T, max(cap, 8))
    lo = (shard_index // fparts) * E_loc
    y = jnp.zeros((T, d), jnp.float32)
    for slot in range(k_top):
        e_glob = top_e[:, slot]                          # [T]
        gate = top_p[:, slot]                            # [T]
        e_loc = e_glob - lo
        mine = (e_loc >= 0) & (e_loc < E_loc)
        e_loc = jnp.where(mine, e_loc, 0)
        # position of each token within its expert's capacity buffer
        onehot = jax.nn.one_hot(e_loc, E_loc, dtype=jnp.int32) * mine[:, None]
        pos = jnp.cumsum(onehot, axis=0) - 1             # [T, E_loc]
        pos_t = jnp.take_along_axis(pos, e_loc[:, None], axis=1)[:, 0]
        keep = mine & (pos_t < cap)
        slot_idx = jnp.where(keep, e_loc * cap + pos_t, E_loc * cap)  # drop bin
        # dispatch: [E_loc*cap+1, d]
        buf = jnp.zeros((E_loc * cap + 1, d), xt.dtype)
        buf = buf.at[slot_idx].add(jnp.where(keep[:, None], xt, 0))
        h = buf[:-1].reshape(E_loc, cap, d)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, w_gate).astype(jnp.float32))
        u = jnp.einsum("ecd,edf->ecf", h, w_up)
        o = jnp.einsum("ecf,efd->ecd", g.astype(h.dtype) * u, w_down)
        o = o.reshape(E_loc * cap, d)
        got = jnp.where(keep[:, None], o[jnp.where(keep, slot_idx, 0)], 0)
        y = y + got.astype(jnp.float32) * (gate * keep)[:, None]
    return y.reshape(B, S, d)


def moe(params: Params, cfg: ModelConfig, x: jnp.ndarray,
        mesh: Optional[jax.sharding.Mesh] = None,
        model_axis: str = "model") -> jnp.ndarray:
    """Expert-parallel MoE FFN.

    With a mesh: shard_map over the `model` axis — experts sharded
    (E >= axis) or expert-split over d_ff (E < axis, EPxTP), tokens
    replicated over the axis, psum combine.  Without a mesh (CPU smoke
    tests): single local shard.
    """
    E, f = cfg.moe_experts, cfg.d_ff
    usable = (
        mesh is not None
        and model_axis in mesh.axis_names
        and (E % mesh.shape[model_axis] == 0
             or (mesh.shape[model_axis] % E == 0
                 and f % (mesh.shape[model_axis] // E) == 0))
    )
    if not usable:
        y = _moe_local(
            x, params["router"], params["w_gate"], params["w_up"],
            params["w_down"], cfg=cfg, n_shards=1,
            shard_index=jnp.array(0, jnp.int32),
        )
        return y.astype(x.dtype)

    M = mesh.shape[model_axis]
    fparts = 1 if E % M == 0 else M // E
    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    if fparts > 1:
        fpf = f // fparts
        # slot s = e*fparts + j  <->  expert e, d_ff slice j
        wg = jnp.moveaxis(wg.reshape(E, cfg.d_model, fparts, fpf), 2, 1)
        wg = wg.reshape(E * fparts, cfg.d_model, fpf)
        wu = jnp.moveaxis(wu.reshape(E, cfg.d_model, fparts, fpf), 2, 1)
        wu = wu.reshape(E * fparts, cfg.d_model, fpf)
        wd = wd.reshape(E, fparts, fpf, cfg.d_model).reshape(E * fparts, fpf, cfg.d_model)

    other = tuple(a for a in mesh.axis_names if a != model_axis)
    # batch sharded over the non-model axes, replicated over model
    xspec = P(other if other else None, None, None)

    def body(xl, router, wgl, wul, wdl):
        idx = jax.lax.axis_index(model_axis)
        y = _moe_local(xl, router, wgl, wul, wdl, cfg=cfg,
                       n_shards=M, shard_index=idx, fparts=fparts)
        return jax.lax.psum(y, model_axis).astype(xl.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=xspec,
        check_vma=False,
    )(x, params["router"], wg, wu, wd)


# --------------------------------------------------------------------------- #
# embeddings / head
# --------------------------------------------------------------------------- #

def embedding_init(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    k = jax.random.split(key, 2)
    return {
        "embed": _dense_init(k[0], (cfg.vocab, cfg.d_model), dt, scale=0.02),
        "unembed": _dense_init(k[1], (cfg.d_model, cfg.vocab), dt),
    }


def embed(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["embed"], tokens, axis=0)


def unembed(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("bsd,dv->bsv", x, params["unembed"])

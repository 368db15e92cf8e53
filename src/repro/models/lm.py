"""Decoder-only LM assembly for the dense / moe / vlm / ssm / hybrid families.

Everything is scan-over-layers (stacked [L, ...] params) so the lowered HLO
stays compact for the 512-device dry-run, and functional:

    params = init(rng, cfg)
    logits = forward(params, cfg, batch, mesh)          # train / prefill
    loss, metrics = loss_fn(params, cfg, batch, mesh)
    cache  = init_cache(cfg, batch_size, seq_len)
    logits, cache = decode_step(params, cfg, cache, tok, mesh)  # serving
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    Params,
    attention,
    attention_decode,
    attention_init,
    dtype_of,
    embed,
    embedding_init,
    mlp,
    mlp_init,
    moe,
    moe_init,
    rmsnorm,
    rmsnorm_init,
    unembed,
)
from .mamba2 import (
    CONV_K,
    NGROUPS,
    _dims as _mamba_dims,
    mamba2_block,
    mamba2_block_init,
    mamba2_init_state,
)
from .rwkv6 import rwkv6_block, rwkv6_block_init, rwkv6_init_state


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #

def _layer_init(key, cfg: ModelConfig) -> Params:
    if cfg.family == "ssm":
        return rwkv6_block_init(key, cfg)
    if cfg.family == "hybrid":
        return mamba2_block_init(key, cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln_attn": rmsnorm_init(cfg),
        "attn": attention_init(k1, cfg),
        "ln_mlp": rmsnorm_init(cfg),
    }
    if cfg.moe_experts:
        p["moe"] = moe_init(k2, cfg)
    else:
        p["mlp"] = mlp_init(k2, cfg)
    return p


def init(rng, cfg: ModelConfig) -> Params:
    k_emb, k_layers, k_shared, k_ln = jax.random.split(rng, 4)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params: Params = {
        "embedding": embedding_init(k_emb, cfg),
        "layers": jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys),
        "ln_final": rmsnorm_init(cfg),
    }
    if cfg.family == "hybrid":
        params["shared_attn"] = {
            "ln": rmsnorm_init(cfg),
            "attn": attention_init(k_shared, cfg),
        }
    return params


# --------------------------------------------------------------------------- #
# forward (train / prefill)
# --------------------------------------------------------------------------- #

def _attn_block(lp: Params, cfg: ModelConfig, x, pos, mesh):
    h = rmsnorm(lp["ln_attn"], x, cfg.norm_eps)
    x = x + attention(lp["attn"], cfg, h, pos, mesh=mesh)
    h = rmsnorm(lp["ln_mlp"], x, cfg.norm_eps)
    if cfg.moe_experts:
        x = x + moe(lp["moe"], cfg, h, mesh)
    else:
        x = x + mlp(lp["mlp"], h)
    return x


def _hidden_forward(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                    pos: jnp.ndarray, mesh) -> jnp.ndarray:
    """Run the layer stack over embedded inputs x: [B,S,d]."""
    B, S, _ = x.shape
    if cfg.family == "ssm":
        state0 = rwkv6_init_state(cfg, B)

        def body(carry, lp):
            h, st = rwkv6_block(lp, cfg, carry, state0, mesh=mesh)
            return h, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"],
                            unroll=cfg.scan_unroll)
    elif cfg.family == "hybrid":
        st0 = mamba2_init_state(cfg, B)
        shared = params["shared_attn"]

        def body(carry, inp):
            lp, idx = inp
            h, _ = mamba2_block(lp, cfg, carry, st0)

            def with_attn(hh):
                a = rmsnorm(shared["ln"], hh, cfg.norm_eps)
                return hh + attention(shared["attn"], cfg, a, pos, mesh=mesh)

            h = jax.lax.cond(idx % cfg.shared_attn_every == 0,
                             with_attn, lambda hh: hh, h)
            return h, None

        idxs = jnp.arange(cfg.n_layers)
        x, _ = jax.lax.scan(jax.checkpoint(body), x, (params["layers"], idxs),
                            unroll=cfg.scan_unroll)
    else:
        def body(carry, lp):
            return _attn_block(lp, cfg, carry, pos, mesh), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"],
                            unroll=cfg.scan_unroll)
    return rmsnorm(params["ln_final"], x, cfg.norm_eps)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            mesh=None) -> jnp.ndarray:
    """Returns logits [B,S,V]."""
    if cfg.family == "vlm":
        x = batch["embeds"].astype(dtype_of(cfg))
        pos = batch["positions"]                       # [3,B,S] (M-RoPE ids)
        B, S = x.shape[0], x.shape[1]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed(params["embedding"], tokens)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _hidden_forward(params, cfg, x, pos, mesh)
    return unembed(params["embedding"], x)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            mesh=None) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits = forward(params, cfg, batch, mesh).astype(jnp.float32)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    loss = jnp.mean(nll)
    return loss, {"loss": loss, "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}


# --------------------------------------------------------------------------- #
# serving: KV / state caches + single-token decode
# --------------------------------------------------------------------------- #

def n_shared_apps(cfg: ModelConfig) -> int:
    k = cfg.shared_attn_every
    return (cfg.n_layers + k - 1) // k if k else 0


def init_cache(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    """Decode-time cache sized for a context of ``seq`` tokens.

    ``index`` [batch] is each row's next position; every other leaf holds
    the batch on axis 1.  An empty row is all zeros."""
    dt = dtype_of(cfg)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    if cfg.family == "ssm":
        xa, xf, wkv = rwkv6_init_state(cfg, batch)
        stack = lambda t: jnp.broadcast_to(t, (L,) + t.shape)
        return {"xp_att": stack(xa), "xp_ffn": stack(xf),
                "wkv": stack(wkv), "index": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "hybrid":
        conv, ssm = mamba2_init_state(cfg, batch)
        stack = lambda t: jnp.broadcast_to(t, (L,) + t.shape)
        apps = n_shared_apps(cfg)
        return {
            "conv": stack(conv), "ssm": stack(ssm),
            "shared_k": jnp.zeros((apps, batch, seq, KV, hd), dt),
            "shared_v": jnp.zeros((apps, batch, seq, KV, hd), dt),
            "index": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((L, batch, seq, KV, hd), dt),
        "v": jnp.zeros((L, batch, seq, KV, hd), dt),
        "index": jnp.zeros((batch,), jnp.int32),
    }


def decode_step(params: Params, cfg: ModelConfig, cache: Dict[str, Any],
                batch: Dict[str, jnp.ndarray], mesh=None):
    """One new token against the cache.  batch: {"token": [B,1]} (vlm:
    {"embed": [B,1,d]}).  Returns (logits [B,1,V], new cache)."""
    if cfg.family == "vlm":
        x = batch["embed"].astype(dtype_of(cfg))
    else:
        x = embed(params["embedding"], batch["token"])
    index = cache["index"]

    if cfg.family == "ssm":
        def body(carry, inp):
            h = carry
            lp, xa, xf, wkv = inp
            h, (xa, xf, wkv) = rwkv6_block(lp, cfg, h, (xa, xf, wkv),
                                           mesh=mesh)
            return h, (xa, xf, wkv)

        x, (xa, xf, wkv) = jax.lax.scan(
            body, x, (params["layers"], cache["xp_att"], cache["xp_ffn"],
                      cache["wkv"]))
        new_cache = dict(cache, xp_att=xa, xp_ffn=xf, wkv=wkv, index=index + 1)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        apps = n_shared_apps(cfg)

        def body(carry, inp):
            h, sk, sv = carry
            lp, conv, ssm, idx = inp
            h, (conv, ssm) = mamba2_block(lp, cfg, h, (conv, ssm))
            app = idx // cfg.shared_attn_every

            def with_attn(op):
                hh, sk, sv = op
                a = rmsnorm(shared["ln"], hh, cfg.norm_eps)
                o, k_l, v_l = attention_decode(
                    shared["attn"], cfg, a, sk[app], sv[app], index)
                sk = jax.lax.dynamic_update_index_in_dim(sk, k_l, app, 0)
                sv = jax.lax.dynamic_update_index_in_dim(sv, v_l, app, 0)
                return hh + o, sk, sv

            h, sk, sv = jax.lax.cond(
                idx % cfg.shared_attn_every == 0, with_attn,
                lambda op: op, (h, sk, sv))
            return (h, sk, sv), (conv, ssm)

        idxs = jnp.arange(cfg.n_layers)
        (x, sk, sv), (conv, ssm) = jax.lax.scan(
            body, (x, cache["shared_k"], cache["shared_v"]),
            (params["layers"], cache["conv"], cache["ssm"], idxs))
        new_cache = dict(cache, conv=conv, ssm=ssm, shared_k=sk, shared_v=sv,
                         index=index + 1)
    else:
        def body(carry, inp):
            h = carry
            lp, k_l, v_l = inp
            a = rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
            o, k_l, v_l = attention_decode(lp["attn"], cfg, a, k_l, v_l, index)
            h = h + o
            a = rmsnorm(lp["ln_mlp"], h, cfg.norm_eps)
            if cfg.moe_experts:
                h = h + moe(lp["moe"], cfg, a, mesh)
            else:
                h = h + mlp(lp["mlp"], a)
            return h, (k_l, v_l)

        x, (k, v) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                           cache["v"]))
        new_cache = dict(cache, k=k, v=v, index=index + 1)

    x = rmsnorm(params["ln_final"], x, cfg.norm_eps)
    return unembed(params["embedding"], x), new_cache

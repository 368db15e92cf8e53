"""Decoder-only LM assembly for the dense / moe / vlm / ssm / hybrid families,
and for stacks that follow a layer list (``cfg.layer_types``).

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

from ..core import telemetry
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
    mamba2_block,
    mamba2_block_init,
    mamba2_init_state,
    mamba2_mixer,
    mamba2_mixer_init,
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


def _listed_layer_init(key, cfg: ModelConfig, kind: str) -> Params:
    k1, k2 = jax.random.split(key)
    mixer = (mamba2_mixer_init(k1, cfg) if kind == "mamba"
             else attention_init(k1, cfg))
    return {"ln_mixer": rmsnorm_init(cfg), "mixer": mixer,
            "ln_mlp": rmsnorm_init(cfg), "mlp": mlp_init(k2, cfg)}


def _layers_init(key, cfg: ModelConfig) -> Params:
    """Stacked layer params: [L, ...]; for a layer list, one stack per kind
    of layer, [layers of that kind, ...], in the list's order."""
    if not cfg.layer_types:
        keys = jax.random.split(key, cfg.n_layers)
        return jax.vmap(lambda k: _layer_init(k, cfg))(keys)
    stacks = {}
    for i, kind in enumerate(LAYER_KINDS):
        n = cfg.layer_types.count(kind)
        if n:
            keys = jax.random.split(jax.random.fold_in(key, i), n)
            stacks[kind] = jax.vmap(
                lambda k: _listed_layer_init(k, cfg, kind))(keys)
    return stacks


def init(rng, cfg: ModelConfig) -> Params:
    k_emb, k_layers, k_shared, k_ln = jax.random.split(rng, 4)
    params: Params = {
        "embedding": embedding_init(k_emb, cfg),
        "layers": _layers_init(k_layers, cfg),
        "ln_final": rmsnorm_init(cfg),
    }
    if cfg.family == "hybrid" and not cfg.layer_types:
        params["shared_attn"] = {
            "ln": rmsnorm_init(cfg),
            "attn": attention_init(k_shared, cfg),
        }
    return params


# --------------------------------------------------------------------------- #
# a stack by layer list (Granite 4.0-H): layer i is
#     x += m_r * mixer_i(rmsnorm(x));  x += m_r * mlp_i(rmsnorm(x))
# with a Mamba-2 or an attention mixer, as ``cfg.layer_types`` says.  The list
# repeats a period; the stack is a scan over periods, each running its layers
# in order from the stack of its kind.
# --------------------------------------------------------------------------- #

LAYER_KINDS = ("mamba", "attention")
_MIXER = {"mamba": "mamba2", "attention": "attention"}


def _period(cfg: ModelConfig) -> Tuple[str, ...]:
    """The shortest prefix of the layer list that the list repeats."""
    kinds, n = cfg.layer_types, cfg.n_layers
    return next(kinds[:p] for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def _residual(cfg: ModelConfig, x, branch):
    m = cfg.residual_multiplier
    return x + (branch if m == 1.0 else branch * m)


def _listed_stack(params: Params, cfg: ModelConfig, x, states, mix,
                  remat: bool = False):
    """Run the layer list over x: [B,S,d].  ``states`` maps a kind to a
    tuple of per-layer states stacked [layers of that kind, ...] (kinds
    without state are left out); they ride in the scan's carry.  Each layer
    indexes its own weights in the stack of its kind, and reads and rewrites
    its own slice of the states in place.  ``mix(kind, p, h, state)`` runs
    one mixer on normed h and returns (out, new state).  Returns (x, new
    states)."""
    per = _period(cfg)
    count = {k: per.count(k) for k in params["layers"]}

    def body(carry, period):
        h, sts = carry
        seen = dict.fromkeys(count, 0)
        for kind in per:
            at = period * count[kind] + seen[kind]
            seen[kind] += 1
            index = lambda tree: jax.tree_util.tree_map(
                lambda t: jax.lax.dynamic_index_in_dim(t, at, 0, False), tree)
            lp = index(params["layers"][kind])
            telemetry.counter("model.mixer", kind=_MIXER[kind]).inc()
            o, st = mix(kind, lp["mixer"],
                        rmsnorm(lp["ln_mixer"], h, cfg.norm_eps),
                        index(sts[kind]) if kind in sts else None)
            if kind in sts:
                sts = dict(sts, **{kind: jax.tree_util.tree_map(
                    lambda t, u: jax.lax.dynamic_update_index_in_dim(
                        t, u, at, 0), sts[kind], st)})
            h = _residual(cfg, h, o)
            a = rmsnorm(lp["ln_mlp"], h, cfg.norm_eps)
            h = _residual(cfg, h, mlp(lp["mlp"], a))
        return (h, sts), None

    periods = jnp.arange(cfg.n_layers // len(per))
    (x, states), _ = jax.lax.scan(
        jax.checkpoint(body) if remat else body, (x, states), periods,
        unroll=cfg.scan_unroll)
    return x, states


def _embed_in(params: Params, cfg: ModelConfig, tokens):
    x = embed(params["embedding"], tokens)
    m = cfg.embedding_multiplier
    return x if m == 1.0 else x * m


def _logits_out(params: Params, cfg: ModelConfig, x):
    logits = unembed(params["embedding"], x)
    s = cfg.logits_scaling
    return logits if s == 1.0 else logits / s


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
    if cfg.layer_types:
        st0 = mamba2_init_state(cfg, B)

        def mix(kind, p, h, _):
            if kind == "mamba":
                return mamba2_mixer(p, cfg, h, st0)[0], None
            return attention(p, cfg, h, pos, mesh=mesh), None

        x, _ = _listed_stack(params, cfg, x, {}, mix, remat=True)
    elif cfg.family == "ssm":
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
        x = _embed_in(params, cfg, tokens)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _hidden_forward(params, cfg, x, pos, mesh)
    return _logits_out(params, cfg, x)


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
    the batch on axis 1.  An empty row is all zeros.  A layer list holds the
    Mamba-2 layers' conv and SSM state (``conv``, ``ssm``) and the attention
    layers' keys and values (``k``, ``v``), each stacked by layer."""
    dt = dtype_of(cfg)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    if cfg.layer_types:
        n_mamba = cfg.layer_types.count("mamba")
        n_attn = cfg.layer_types.count("attention")
        cache = {"index": jnp.zeros((batch,), jnp.int32)}
        if n_mamba:
            conv, ssm = mamba2_init_state(cfg, batch)
            cache["conv"] = jnp.zeros((n_mamba,) + conv.shape, conv.dtype)
            cache["ssm"] = jnp.zeros((n_mamba,) + ssm.shape, ssm.dtype)
        if n_attn:
            cache["k"] = jnp.zeros((n_attn, batch, seq, KV, hd), dt)
            cache["v"] = jnp.zeros((n_attn, batch, seq, KV, hd), dt)
        return cache
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
        x = _embed_in(params, cfg, batch["token"])
    index = cache["index"]

    if cfg.layer_types:
        def mix(kind, p, h, st):
            if kind == "mamba":
                return mamba2_mixer(p, cfg, h, st)
            o, k_l, v_l = attention_decode(p, cfg, h, *st, index)
            return o, (k_l, v_l)

        states = {}
        if "conv" in cache:
            states["mamba"] = (cache["conv"], cache["ssm"])
        if "k" in cache:
            states["attention"] = (cache["k"], cache["v"])
        x, states = _listed_stack(params, cfg, x, states, mix)
        new_cache = dict(cache, index=index + 1)
        if "mamba" in states:
            new_cache["conv"], new_cache["ssm"] = states["mamba"]
        if "attention" in states:
            new_cache["k"], new_cache["v"] = states["attention"]
    elif cfg.family == "ssm":
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
    return _logits_out(params, cfg, x), new_cache

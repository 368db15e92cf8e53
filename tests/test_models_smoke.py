"""Per-architecture smoke tests: reduced config, one forward + train step on
CPU, output shapes + no NaNs (the assignment's required smoke surface)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ARCHS, build_model, get_config
from repro.models import encdec
from repro.train.optimizer import adamw_init, adamw_update

RNG = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    if cfg.family == "vlm":
        return {
            "embeds": jax.random.normal(RNG, (B, S, cfg.d_model)),
            "positions": jnp.zeros((3, B, S), jnp.int32)
            + jnp.arange(S)[None, None, :],
            "labels": jnp.zeros((B, S), jnp.int32),
        }
    if cfg.is_encdec:
        return {
            "frames": jax.random.normal(RNG, (B, S, cfg.d_model)),
            "tokens": jnp.zeros((B, S), jnp.int32),
            "labels": jnp.zeros((B, S), jnp.int32),
        }
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_forward_and_train_step(name):
    cfg = get_config(name).reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    batch = _batch(cfg)
    B, S = 2, 16

    logits = jax.jit(lambda p, b: model.forward(p, b))(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())

    # one full train step (grad + adamw) must keep everything finite
    def loss_of(p):
        return model.loss_fn(p, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_of))(params)
    assert np.isfinite(float(loss))
    opt = adamw_init(params)
    new_params, opt, metrics = adamw_update(params, grads, opt,
                                            jnp.asarray(1e-3))
    assert np.isfinite(float(metrics["grad_norm"]))
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert not bool(jnp.isnan(leaf).any())


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_decode_step(name):
    cfg = get_config(name).reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 2, 16
    if cfg.is_encdec:
        cache = model.init_cache(B, 32, enc_len=S)
        enc = encdec.encode(params, cfg, jax.random.normal(RNG, (B, S, cfg.d_model)))
        cache = model.precompute_cross(params, enc, cache)
        dbatch = {"token": jnp.zeros((B, 1), jnp.int32)}
    elif cfg.family == "vlm":
        cache = model.init_cache(B, 32)
        dbatch = {"embed": jax.random.normal(RNG, (B, 1, cfg.d_model))}
    else:
        cache = model.init_cache(B, 32)
        dbatch = {"token": jnp.zeros((B, 1), jnp.int32)}
    step = jax.jit(lambda p, c, b: model.decode_step(p, c, b))
    lg, cache = step(params, cache, dbatch)
    lg2, cache = step(params, cache, dbatch)
    assert lg.shape == (B, 1, cfg.vocab)
    assert not bool(jnp.isnan(lg2).any())
    assert np.asarray(cache["index"]).tolist() == [2] * B


def test_decode_matches_forward_dense():
    """Teacher-forced decode must reproduce full-forward logits (dense)."""
    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    full = model.forward(params, {"tokens": toks,
                                  "labels": jnp.zeros((B, S), jnp.int32)})
    cache = model.init_cache(B, S)
    step = jax.jit(lambda p, c, b: model.decode_step(p, c, b))
    outs = []
    for t in range(S):
        lg, cache = step(params, cache, {"token": toks[:, t:t + 1]})
        outs.append(np.asarray(lg[:, 0], np.float32))
    got = np.stack(outs, axis=1)
    np.testing.assert_allclose(got, np.asarray(full, np.float32),
                               rtol=2e-4, atol=2e-4)


def test_decode_matches_forward_rwkv():
    """Stateful decode equals the scan-over-time forward (rwkv6)."""
    cfg = get_config("rwkv6-3b").reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab)
    full = model.forward(params, {"tokens": toks,
                                  "labels": jnp.zeros((B, S), jnp.int32)})
    cache = model.init_cache(B, S)
    step = jax.jit(lambda p, c, b: model.decode_step(p, c, b))
    outs = []
    for t in range(S):
        lg, cache = step(params, cache, {"token": toks[:, t:t + 1]})
        outs.append(np.asarray(lg[:, 0], np.float32))
    got = np.stack(outs, axis=1)
    np.testing.assert_allclose(got, np.asarray(full, np.float32),
                               rtol=5e-4, atol=5e-4)


def test_param_counts_are_plausible():
    for name, cfg in ARCHS.items():
        n = cfg.param_count()
        assert n > 1e8, (name, n)
        if cfg.moe_experts:
            assert cfg.active_param_count() < n

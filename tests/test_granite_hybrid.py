"""Granite 4.0-H's layer-list stack against the benchmark's plain reference,
at a tiny size on the CPU with seeded weights and a list that holds both
kinds of layer: the forward, prefill by decode through the cache, a reused
serving row, the gated norm's order, and the dense programs at the new
knobs' defaults."""

import importlib.util
import json
import math
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry
from repro.models import ModelConfig, build_model, get_config
from repro.models import mamba2
from repro.serve import ServeEngine

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "references" / "granite_hybrid.py", "granite_ref")
PUBLISHED = json.loads((BENCH / "configs" / "granite-4.0-h-micro.json")
                       .read_text())
# two periods of [mamba, attention, mamba]; every width cut, no key dropped
TINY = dict(PUBLISHED, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=6,
            layer_types=["mamba", "attention", "mamba"] * 2, vocab_size=256,
            shared_intermediate_size=128, mamba_d_head=16, mamba_d_state=16,
            torch_dtype="float32")
SEED = 2 ** 31 + 17
# the program runs the reference's float32 arithmetic in another order (the
# scan's products on the vector units, XLA's own fusion), so the two agree
# to float32 rounding, carried through 12 residual branches and the scan
TOL = 2e-4


def _model(cfg=TINY):
    return build_model(ModelConfig(**REF.program_kwargs(cfg)))


def _tokens(B, S, seed=3):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                         TINY["vocab_size"]), np.int32)


def _ref_logits(params, tokens):
    return np.asarray(REF.logits(REF.dims(TINY), False, params,
                                 jnp.asarray(tokens)))


def _close(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=TOL,
                               atol=TOL * np.abs(want).max())


def test_program_layout_and_count_match_the_reference():
    model = _model()
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: REF.init_params(TINY, 1))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(want))
    assert REF.param_count(TINY) == n


def test_published_sizes():
    assert REF.param_count(PUBLISHED) == 3_191_396_096 + 100_352 * 2048
    cfg = get_config("granite-4.0-h-micro")
    assert ModelConfig(**REF.program_kwargs(PUBLISHED)).layer_types == \
        cfg.layer_types
    ssm, conv = REF._state_bytes(REF.dims(PUBLISHED))
    assert 32 * ssm == 2_415_919_104 and 32 * conv == 30_081_024


def test_forward_matches_the_reference():
    model = _model()
    params = REF.init_params(TINY, SEED)
    tokens = _tokens(2, 12)
    got = jax.jit(lambda p, t: model.forward(p, {"tokens": t}))(params, tokens)
    _close(got, _ref_logits(params, tokens))


def test_prefill_by_decode_matches_the_reference_at_every_position():
    model = _model()
    params = REF.init_params(TINY, SEED)
    B, S = 2, 12
    tokens = _tokens(B, S, seed=5)
    cache = model.init_cache(B, 16)
    assert set(cache) == {"conv", "ssm", "k", "v", "index"}
    assert cache["ssm"].shape[:2] == (4, B) and cache["k"].shape[:2] == (2, B)
    step = jax.jit(model.decode_step)
    got = []
    for t in range(S):
        lg, cache = step(params, cache, {"token": jnp.asarray(tokens[:, t:t + 1])})
        got.append(np.asarray(lg[:, 0]))
    _close(np.stack(got, 1), _ref_logits(params, tokens))


def _counter(name, **labels):
    key = name + ("{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
                  + "}" if labels else "")
    return telemetry.registry().snapshot()["counters"].get(key, 0)


def _recording(model):
    """``model`` with a decode step that keeps every logit it computes, in
    the order its steps run: the engine's compiled step chooses the token on
    the device and hands the host no logits."""
    seen = []

    def decode_step(params, cache, batch, mesh=None):
        logits, cache = model.decode_step(params, cache, batch, mesh)
        jax.debug.callback(lambda x: seen.append(np.array(x)), logits,
                           ordered=True)
        return logits, cache

    return replace(model, decode_step=decode_step), seen


def test_refilled_row_serves_as_a_fresh_engine():
    """A row reused after a finished request starts from zero recurrent
    state: every logit of the second request equals, bit for bit, that of an
    engine that never served the first."""
    model, seen = _recording(_model())
    params = REF.init_params(TINY, SEED)
    first, second = _tokens(1, 10, 7)[0].tolist(), _tokens(1, 6, 9)[0].tolist()
    reused = ServeEngine(model, params, batch_size=1, max_context=48,
                         eos_token=-1)
    resets = _counter("serve.state_rows_reset")
    reused.submit(first, max_new_tokens=12)
    reused.submit(second, max_new_tokens=24)
    out = {r.request_id: r.tokens for r in reused.run(max_steps=100)}
    assert _counter("serve.state_rows_reset") - resets == 2
    jax.effects_barrier()
    n = len(seen)
    fresh = ServeEngine(model, params, batch_size=1, max_context=48,
                        eos_token=-1)
    fresh.submit(second, max_new_tokens=24)
    assert out[1] == fresh.run(max_steps=100)[0].tokens
    jax.effects_barrier()
    want = seen[n:]
    assert n == 21 + len(want) and len(want) == 6 + 24 - 1
    assert all(a.shape == (1, 1, TINY["vocab_size"]) for a in seen)
    assert all(np.array_equal(a, b) for a, b in zip(seen[21:n], want))
    ssm, conv = REF._state_bytes(REF.dims(TINY))
    gauges = telemetry.registry().snapshot()["gauges"]
    assert gauges["serve.cache_bytes{kind=ssm_state}"] == ssm
    assert gauges["serve.cache_bytes{kind=conv_state}"] == conv
    assert gauges["serve.cache_bytes{kind=kv}"] == 2 * 2 * 48 * 2 * 16 * 4


def test_mixers_are_counted_once_per_traced_call_site():
    model = _model()
    before = {k: _counter("model.mixer", kind=k) for k in ("mamba2", "attention")}
    cache = jax.eval_shape(lambda: model.init_cache(1, 8))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    jax.eval_shape(model.decode_step, params, cache,
                   {"token": jax.ShapeDtypeStruct((1, 1), jnp.int32)})
    assert {k: _counter("model.mixer", kind=k) - v
            for k, v in before.items()} == {"mamba2": 2, "attention": 1}


def test_gated_norm_is_rmsnorm_of_y_times_silu_z():
    """One token from zero state, by hand in numpy: the mixer normalises
    y * silu(z), not rmsnorm(y) * silu(z)."""
    cfg = ModelConfig(**REF.program_kwargs(TINY))
    p = jax.tree_util.tree_map(
        lambda t: t[0], REF.init_params(TINY, SEED)["layers"]["mamba"])["mixer"]
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 1, 64)),
                   np.float32)
    conv, ssm = mamba2.mamba2_init_state(cfg, 1)
    got, _ = mamba2.mamba2_mixer(p, cfg, jnp.asarray(h), (conv, ssm))

    n = {k: np.asarray(v, np.float64) for k, v in p.items() if k != "ln_out"}
    silu = lambda v: v / (1 + np.exp(-v))
    d_in, N, H, hd = 128, 16, 8, 16
    proj = h[0, 0].astype(np.float64) @ n["w_in"]
    z, xbc, dt = proj[:d_in], proj[d_in:d_in + d_in + 2 * N], proj[-H:]
    xbc = silu(xbc * n["conv_w"][-1] + n["conv_b"])   # earlier inputs are 0
    x, B, C = xbc[:d_in].reshape(H, hd), xbc[d_in:d_in + N], xbc[-N:]
    dt = np.log1p(np.exp(dt + n["dt_bias"]))
    y = (dt[:, None] * x) * (B @ C) + n["D"][:, None] * x
    g = y.reshape(d_in) * silu(z)
    g = g / np.sqrt(np.mean(g * g) + cfg.norm_eps) \
        * np.asarray(p["ln_out"]["scale"], np.float64)
    want = g @ n["w_out"]
    np.testing.assert_allclose(np.asarray(got)[0, 0], want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    old = y.reshape(d_in) / np.sqrt(np.mean(y.reshape(d_in) ** 2)
                                    + cfg.norm_eps) * silu(z)
    assert np.abs(old @ n["w_out"] - want).max() > 100 * 1e-4 * \
        np.abs(want).max()


@pytest.mark.parametrize("name", ["qwen2-1.5b", "smollm-360m"])
def test_dense_decode_is_bit_identical_at_the_defaults(name):
    """The new knobs at their defaults, or spelled out as the identity they
    are (a head size of 16 makes 1/sqrt(hd) exact), leave a dense decode
    step's program and outputs as they were."""
    cfg = get_config(name).reduced()
    spelled = replace(cfg, attn_scale=1 / math.sqrt(cfg.hd),
                      embedding_multiplier=1.0, residual_multiplier=1.0,
                      logits_scaling=1.0, layer_types=())
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    tokens = _tokens(2, 5, seed=11)
    outs = []
    for c in (cfg, spelled):
        model = build_model(c)
        cache = model.init_cache(2, 8)
        step = jax.jit(model.decode_step)
        for t in range(tokens.shape[1]):
            lg, cache = step(params, cache,
                             {"token": jnp.asarray(tokens[:, t:t + 1])})
        outs.append((np.asarray(lg), np.asarray(cache["k"])))
    assert all(np.array_equal(a, b) for a, b in zip(*outs))


def test_serve_control_reads_above_the_limit():
    """The fp8 control (the reference with both operands of every matrix
    product rounded to float8 e4m3) at the published widths, over one period
    of the layer list and a vocabulary cut to 16,384, reads above the serve
    cell's ``served_gap`` limit.  The readings at full size, program and
    control, are from the chip (PERF.md)."""
    cfg = dict(PUBLISHED, num_hidden_layers=10, vocab_size=16_384)
    rng = np.random.default_rng(3)
    served = [(rng.integers(0, 16_384, 24).tolist(),
               rng.integers(0, 16_384, 40).tolist()) for _ in range(2)]
    _, gaps = REF.served_gaps(cfg, 11, served, 64, control=True)
    limits = json.loads((BENCH / "limits" / "serve.granite-4.0-h-micro.chat.json")
                        .read_text())
    assert gaps.max() > limits["served_gap"]

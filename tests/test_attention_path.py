"""The flash kernel against the einsum path of ``layers.attention`` (forward
and gradients, interpret mode on CPU), and which path a call takes."""

import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.core import telemetry
from repro.kernels.flashattn.ops import attention as flash_attention
from repro.models import get_config, layers

TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _qkvo(B, S, H, KV, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, H, hd), dtype),
            jax.random.normal(ks[1], (B, S, KV, hd), dtype),
            jax.random.normal(ks[2], (B, S, KV, hd), dtype),
            jax.random.normal(ks[3], (B, S, H, hd), dtype))


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S", [256, 512])
@pytest.mark.parametrize("H,KV,hd", [(15, 5, 64), (12, 2, 128)])
def test_flash_matches_einsum_path(H, KV, hd, S, dtype):
    q, k, v, do = _qkvo(2, S, H, KV, hd, dtype)
    kernel = functools.partial(flash_attention, interpret=True)

    def einsum(q, k, v):
        return layers._attend_einsum(q, k, v, True, None).astype(q.dtype)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * do)

    tol = TOL[jnp.dtype(dtype).name]
    _close(kernel(q, k, v), einsum(q, k, v), tol)
    got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(einsum), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == dtype
        _close(g, w, tol)


def _count(path):
    return telemetry.registry().snapshot()["counters"].get(
        f"model.attention_path{{path={path}}}", 0)


@pytest.mark.parametrize("case,want", [
    ("cpu", "einsum"),
    ("cross", "einsum"),
    ("non_causal", "einsum"),
    ("model_axis", "einsum"),
    ("eligible", "kernel"),
])
def test_attention_path(monkeypatch, case, want):
    cfg = get_config("smollm-360m").reduced()
    B, S = 2, 256
    params = layers.attention_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kw, mesh = {}, None
    if case != "cpu":
        monkeypatch.setattr(layers, "_on_tpu", lambda mesh: True)
        monkeypatch.setattr(layers, "flash_attention", functools.partial(
            flash_attention, interpret=True))
    if case == "cross":
        kw["x_kv"] = x[:, ::-1]
    elif case == "non_causal":
        kw["causal"] = False
    elif case == "model_axis":
        mesh = AbstractMesh((1, 2), ("data", "model"))
    before = {p: _count(p) for p in ("kernel", "einsum")}
    out = jax.eval_shape(lambda x: layers.attention(
        params, cfg, x, pos, mesh=mesh, **kw), x)
    assert out.shape == x.shape
    counted = {p: _count(p) - before[p] for p in before}
    assert counted == {p: int(p == want) for p in before}
    if case == "eligible":
        got = layers.attention(params, cfg, x, pos)
        monkeypatch.setattr(layers, "_on_tpu", lambda mesh: False)
        _close(got, layers.attention(params, cfg, x, pos), TOL[cfg.dtype])


BATCH_MESH = textwrap.dedent(r"""
    import functools, sys
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.kernels.flashattn.ops import attention as flash_attention
    from repro.models import get_config, layers

    cfg = get_config("smollm-360m").reduced()
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("data", "model"))
    params = layers.attention_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(256)[None], (4, 256))
    def run():
        def f(x):
            return layers.attention(params, cfg, x, pos, mesh=mesh)
        g = jax.grad(lambda x: jnp.sum(f(x).astype(jnp.float32) ** 2))
        return jax.jit(lambda x: (f(x), g(x)))

    want = run()(x)
    layers._on_tpu = lambda mesh: True
    layers.flash_attention = functools.partial(flash_attention,
                                               interpret=True)
    text = run().lower(x).as_text()
    got = run()(x)
    assert "sdy.manual_computation" in text, text[:2000]
    for g, w in zip(got, want):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        print("ERR", float(jnp.max(jnp.abs(g - w))),
              float(jnp.max(jnp.abs(w))), cfg.dtype)
""")


def test_flash_runs_per_device_on_a_batch_mesh(tmp_path):
    """On a (4, 1) data mesh the kernel runs under shard_map over `data`,
    each device on its own rows, and agrees with the einsum path in its
    output and its gradient."""
    script = tmp_path / "batch_mesh.py"
    script.write_text(BATCH_MESH)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    lines = [l.split()[1:] for l in r.stdout.splitlines()
             if l.startswith("ERR")]
    assert len(lines) == 2                      # output and gradient
    for err, scale, dtype in lines:
        assert float(err) <= TOL[dtype] * max(1.0, float(scale))

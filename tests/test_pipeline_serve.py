"""Input pipeline (pipe-fed) and serving engine."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry
from repro.core.datapipe import PipeConfig
from repro.models import build_model, get_config
from repro.pipeline import PipeFeeder, SyntheticSource
from repro.serve import ServeEngine


@pytest.fixture
def fresh_jax():
    """Isolate the jax PRNG/compile-cache interaction: drop every cached
    executable left behind by earlier tests so both runs inside the test
    compile (and autotune) from the same clean slate, and hand each test
    its own key instead of a module-level one."""
    jax.clear_caches()
    yield jax.random.PRNGKey(0)


def test_pipe_feeder_delivers_batches():
    seq, bsz, vocab = 8, 4, 100
    name = "db://feed?query=f1"
    feeder = PipeFeeder([name], batch_size=bsz, seq_len=seq).start()
    src = SyntheticSource(vocab, seq, seed=1)
    t = threading.Thread(target=src.serve, args=(name, 20),
                         kwargs={"config": PipeConfig(block_rows=8)})
    t.start()
    batches = list(feeder.batches())
    t.join(20)
    assert len(batches) == 5  # 20 rows / 4
    for b in batches:
        assert b.data["tokens"].shape == (bsz, seq)
        assert b.data["tokens"].max() < vocab
        np.testing.assert_array_equal(
            b.data["labels"][:, :-1], b.data["tokens"][:, 1:])
    assert [b.batch_id for b in batches] == [0, 1, 2, 3, 4]


def test_pipe_feeder_skip_until_restart():
    """Deterministic restart: skip_until fast-forwards past done batches."""
    seq, bsz, vocab = 8, 2, 50
    name = "db://feed2?query=f1"
    feeder = PipeFeeder([name], batch_size=bsz, seq_len=seq,
                        skip_until=3).start()
    src = SyntheticSource(vocab, seq, seed=2)
    t = threading.Thread(target=src.serve, args=(name, 10))
    t.start()
    batches = list(feeder.batches())
    t.join(20)
    assert [b.batch_id for b in batches] == [3, 4]


def test_feeder_merges_multiple_sources():
    seq, bsz = 8, 4
    names = ["db://multi?query=a", "db://multi2?query=b"]
    feeder = PipeFeeder(names, batch_size=bsz, seq_len=seq).start()
    threads = [
        threading.Thread(target=SyntheticSource(64, seq, seed=i).serve,
                         args=(n, 6))
        for i, n in enumerate(names)
    ]
    for t in threads:
        t.start()
    batches = list(feeder.batches())
    for t in threads:
        t.join(20)
    assert sum(b.data["tokens"].shape[0] for b in batches) == 12


def test_serve_engine_continuous_batching():
    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_size=2, max_context=64,
                      eos_token=-1)  # never hit eos
    rids = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(5)]
    results = eng.run(max_steps=200)
    assert len(results) == 5
    by_id = {r.request_id: r for r in results}
    assert set(by_id) == set(rids)
    for r in results:
        assert len(r.tokens) == 4
        assert all(0 <= t < cfg.vocab for t in r.tokens)


def test_serve_engine_greedy_deterministic(fresh_jax):
    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg)
    params = model.init(fresh_jax)

    def run_once():
        # regression guard for the token-buffer aliasing race: ServeEngine
        # must copy _tokens at dispatch (jnp.array), or the async step
        # reads the buffer while the loop mutates it and this diverges
        eng = ServeEngine(model, params, batch_size=1, max_context=32)
        eng.submit([5, 6], max_new_tokens=6)
        return eng.run(max_steps=50)[0].tokens

    assert run_once() == run_once()


def test_serve_engine_rows_are_independent():
    """Continuous batching must not leak context between requests: each
    answer equals the model's own greedy continuation of its prompt,
    whatever shares the batch or used its row before."""
    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[5, 6, 7], [9], [1, 2, 3, 4, 5], [8, 8], [3, 1, 4, 1]]
    eng = ServeEngine(model, params, batch_size=2, max_context=16,
                      eos_token=-1)
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    by_id = {r.request_id: r for r in eng.run(max_steps=200)}
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t}))
    for rid, prompt in zip(rids, prompts):
        seq = list(prompt)
        for _ in range(4):
            logits = fwd(params, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert by_id[rid].tokens == seq[len(prompt):], rid


@pytest.mark.parametrize("name, donated", [("granite-4.0-h-micro", True),
                                           ("qwen2-1.5b", True),
                                           ("grok-1-314b", True),
                                           ("rwkv6-3b", False)])
def test_serve_engine_decode_step_donates_the_cache(name, donated):
    """A step that carries its cache through the layer scan (a layer list,
    the dense scan and its moe body) rewrites it in place: the cache it was
    handed is consumed, so the engine never holds two whole caches at once.
    rwkv6 reads its state as scan inputs and writes it as outputs, which
    cannot alias, so its cache stays undonated."""
    model = build_model(get_config(name).reduced())
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                      batch_size=2, max_context=16, eos_token=-1)
    eng.submit([1, 2], max_new_tokens=1)
    eng._fill_slots()
    before = eng.cache
    eng._decode_one_step([])
    assert all(leaf.is_deleted() == donated
               for leaf in jax.tree_util.tree_leaves(before))
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(eng.cache))


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_serve_engine_step_returns_token_ids(temperature):
    """The compiled step chooses the next token: its first output is the
    [B] int32 ids, and no [B, 1, V] logits leave it."""
    model = build_model(get_config("qwen2-1.5b").reduced())
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                      batch_size=3, max_context=16, temperature=temperature)
    args = (eng._rng, eng._temperature) if temperature else ()
    ids, *rest = eng._step(eng.params, eng.cache,
                           {"token": jnp.ones((3, 1), jnp.int32)}, *args)
    assert ids.shape == (3,) and ids.dtype == jnp.int32
    # the rest is the cache and, when sampling, the next key
    assert jax.tree_util.tree_structure(rest) == \
        jax.tree_util.tree_structure([eng.cache, *args[:1]])


def test_serve_engine_counts_host_pull_bytes():
    """A step pulls B int32 ids once a row samples, and nothing while every
    row is still being fed its prompt."""
    model = build_model(get_config("qwen2-1.5b").reduced())
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                      batch_size=4, max_context=16, eos_token=-1)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng._fill_slots()

    def pulled():
        counter = telemetry.counter("serve.host_pull_bytes")
        before = counter.value
        eng._decode_one_step([])
        return counter.value - before

    assert [pulled() for _ in range(4)] == [0, 0, 4 * 4, 4 * 4]


def test_serve_engine_sampling_is_seeded():
    """Sampling is Gumbel-max on the device: one seed gives one stream of
    tokens, and at a high temperature that stream is not the greedy one."""
    model = build_model(get_config("qwen2-1.5b").reduced())
    params = model.init(jax.random.PRNGKey(0))

    def tokens(temperature, seed=7):
        eng = ServeEngine(model, params, batch_size=2, max_context=32,
                          eos_token=-1, temperature=temperature, seed=seed)
        for prompt in ([5, 6], [9, 1, 4]):
            eng.submit(prompt, max_new_tokens=12)
        return [r.tokens for r in sorted(eng.run(max_steps=50),
                                         key=lambda r: r.request_id)]

    sampled = tokens(1.0)
    assert sampled == tokens(1.0)
    assert tokens(100.0) != tokens(0.0)
    assert all(0 <= t < model.cfg.vocab for row in sampled for t in row)


def test_serve_engine_rejects_overlong_request():
    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                      batch_size=1, max_context=8)
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3, 4], max_new_tokens=5)

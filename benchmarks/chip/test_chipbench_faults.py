"""A run with the timed path broken underneath reads ``correct`` false.

Each test skips the look for a chip, plants one fault in the program (not
in the benchmark) and drives the rest of a run at a small size:

* a step that returns its state unchanged;
* half of the batch left out, the mean taken over the rest;
* a token altered where it is produced (in the pipe's batches; in the
  served answers).

There is no exchange between chips to leave out: every cell runs on one.
"""

import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402
from chipbench import testkit  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.pipeline import feeder  # noqa: E402
from repro.train import step as train_step  # noqa: E402


def _run(tmp_path, cell):
    root = testkit.small_copy(tmp_path)
    return run.run_cell(root, cell, 12345, 1.0, False, require_tpu=False)


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(train_step, "adamw_update",
                        lambda params, grads, state, lr: (
                            params, state, {"grad_norm": jnp.float32(0)}))


def _half_batch(monkeypatch):
    real = lm.loss_fn

    def half(params, cfg, batch, mesh=None):
        n = batch["tokens"].shape[0] // 2
        return real(params, cfg, {k: v[:n] for k, v in batch.items()}, mesh)

    monkeypatch.setattr(lm, "loss_fn", half)


def _pipe_token(monkeypatch):
    real = feeder.PipeFeeder.batches

    def altered(self):
        for b in real(self):
            b.data["tokens"][0, 0] = (b.data["tokens"][0, 0] + 1) % 256
            yield b

    monkeypatch.setattr(feeder.PipeFeeder, "batches", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
@pytest.mark.parametrize("cell", ["train.smollm-360m.pipe",
                                  "train.smollm-360m.hostfed"])
def test_train_fault_reads_incorrect(tmp_path, monkeypatch, cell, fault):
    fault(monkeypatch)
    result = _run(tmp_path, cell)
    assert result["correct"] is False, result["compared"]


def test_pipe_token_altered_reads_incorrect(tmp_path, monkeypatch):
    _pipe_token(monkeypatch)
    result = _run(tmp_path, "train.smollm-360m.pipe")
    assert result["correct"] is False
    assert result["compared"]["window_rows_wrong"]["value"] > 0


def _cache_unchanged(monkeypatch):
    real = lm.decode_step

    def frozen(params, cfg, cache, batch, mesh=None):
        logits, _ = real(params, cfg, cache, batch, mesh)
        return logits, cache

    monkeypatch.setattr(lm, "decode_step", frozen)


def _served_token(monkeypatch):
    real = lm.decode_step

    def shifted(params, cfg, cache, batch, mesh=None):
        logits, cache = real(params, cfg, cache, batch, mesh)
        return jnp.roll(logits, 1, axis=-1), cache

    monkeypatch.setattr(lm, "decode_step", shifted)


@pytest.mark.parametrize("fault", [_cache_unchanged, _served_token])
def test_serve_fault_reads_incorrect(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    result = _run(tmp_path, "serve.qwen2-1.5b.chat")
    assert result["correct"] is False, result["compared"]
    assert result["compared"]["served_gap"]["value"] > \
        result["compared"]["served_gap"]["limit"]

"""Batched serving engine: continuous-batching decode over a shared KV/state
cache, with PipeGen pipes as the request/response transport option.

Small but real: requests are queued, packed into the fixed batch, decoded
step-by-step with the model's ``decode_step`` (greedy or temperature
sampling), and finished sequences are swapped out for queued requests
between steps (continuous batching).  Every cache row is its own sequence
(per-row positions), and a new request starts from an emptied row, so a
request's tokens do not depend on what else shares the batch.  Prompts are
fed one token per step alongside the other rows' decoding.  On CPU this
serves the reduced configs; the same code lowers for the production mesh.
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..models import Model

__all__ = ["FeatureView", "ServeEngine", "GenerationResult"]


class FeatureView:
    """A continuously-fresh relation the serving path reads without ever
    reloading it: epochs arrive through a :class:`repro.core.subscribe.
    Subscription` and fold into one ColumnBlock on ``refresh()``.

    The serving loop calls ``refresh()`` between decode steps (cheap:
    non-blocking poll, usually empty), so feature freshness is bounded by
    the publisher's commit cadence, not by any re-export schedule.  When
    the publisher dies the view keeps serving its last image and flags
    ``ended`` — the owner resubscribes at ``watermark`` once the
    publisher is back (the crash-heal path the fault tests exercise).
    """

    def __init__(self, subscription: Any):
        self._sub = subscription
        self.block: Optional[Any] = None    # latest folded ColumnBlock
        self.epoch = 0                      # epoch of that image
        self.refreshes = 0                  # polls that brought new epochs
        self.ended = False

    @property
    def watermark(self) -> int:
        return self._sub.watermark

    def refresh(self) -> int:
        """Drain pending epochs into the view; returns how many applied."""
        if self.ended:
            return 0
        try:
            deltas = self._sub.poll(timeout=0.0)
        except BrokenPipeError:
            self.ended = True
            return 0
        for delta in deltas:
            if delta.kind == "snapshot" or self.block is None:
                self.block = delta.block
            else:
                from ..core.types import ColumnBlock
                self.block = ColumnBlock.concat([self.block, delta.block])
            self.epoch = delta.epoch
        if deltas:
            self.refreshes += 1
        return len(deltas)

    def close(self) -> None:
        self._sub.close()


# One jitted decode step per (model, mesh): engines over the same model reuse
# one compiled executable (one greedy, one sampled) instead of re-jitting a
# fresh function each time.  Besides skipping the recompile, this pins
# determinism — two executables compiled from identical HLO may still
# autotune differently, and a low-order-bit logit difference is enough to
# flip a greedy argmax tie (the test_serve_engine_greedy_deterministic flake).
_STEP_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_STEP_LOCK = threading.Lock()


def _shared_decode_step(model: Model, mesh):
    """jit(params, cache, batch) -> (next token [B] int32, cache), greedy;
    jit(params, cache, batch, key, temperature) -> (next token, cache, next
    key), sampled.  The token is chosen on the device, so the host pulls B
    ids and never the [B, 1, V] logits."""
    with _STEP_LOCK:
        per_model = _STEP_CACHE.setdefault(model, {})
        key = id(mesh)  # mesh stays alive via the jitted closure below
        fn = per_model.get(key)
        if fn is None:
            # close over a weakref, not the model: a strong ref from the
            # cached value would pin the weak key forever and leak every
            # model/executable pair for the process lifetime.  Callers of
            # fn (engines) hold the model, so the deref cannot dangle.
            model_ref = weakref.ref(model)

            def step(params, cache, batch, rng=None, temperature=None):
                logits, cache = model_ref().decode_step(params, cache, batch,
                                                        mesh)
                logits = logits[:, 0, :]
                if rng is None:  # greedy: the argmax, lowest index on ties
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
                # Gumbel-max: a sample of softmax(logits / temperature)
                rng, sub = jax.random.split(rng)
                noise = jax.random.gumbel(sub, logits.shape)
                scores = logits.astype(jnp.float32) / temperature + noise
                return jnp.argmax(scores, axis=-1).astype(jnp.int32), cache, rng

            # A layer-list step carries its state through the layer scan
            # and rewrites it in place, so its cache is donated: undonated,
            # each step allocated a second whole cache, and near a full chip
            # the allocator stalled steps for 0.1-1 s.  The dense scan reads
            # the cache as scan inputs and writes it as outputs; donated,
            # the compiler copies it whole twice more and the step is slower.
            fn = jax.jit(step,
                         donate_argnums=(1,) if model.cfg.layer_types else ())
            per_model[key] = fn
        return fn


@dataclass
class GenerationResult:
    request_id: int
    prompt: List[int]
    tokens: List[int] = field(default_factory=list)
    finished: bool = False
    latency_s: float = 0.0


def _row_reset(model: Model, max_context: int):
    """jit(cache, rows [B] bool) -> cache with those rows emptied."""
    def reset(cache, rows):
        empty = model.init_cache(rows.shape[0], max_context)

        def pick(old, new):
            shape = [1] * old.ndim
            axis = 0 if old.ndim == 1 else 1   # ``index`` [B]; others [L,B,..]
            shape[axis] = rows.shape[0]
            return jnp.where(rows.reshape(shape), new, old)

        return jax.tree_util.tree_map(pick, cache, empty)

    return jax.jit(reset, donate_argnums=(0,))


# What each cache leaf holds, for the ``serve.cache_bytes`` gauge: keys and
# values that grow with position, or Mamba-2 state that a row carries.
_CACHE_KIND = {"k": "kv", "v": "kv", "ssm": "ssm_state", "conv": "conv_state"}


def _record_cache(cache: Dict[str, Any]) -> None:
    """Set ``serve.cache_bytes{kind}`` from the cache's leaves."""
    held: Dict[str, int] = {}
    for name, kind in _CACHE_KIND.items():
        if name in cache:
            held[kind] = held.get(kind, 0) + cache[name].nbytes
    for kind, nbytes in held.items():
        telemetry.gauge("serve.cache_bytes", kind=kind).set(nbytes)


@dataclass
class _Slot:
    request: Optional[GenerationResult] = None
    pending: List[int] = field(default_factory=list)  # prompt not yet fed
    remaining: int = 0
    t0: float = 0.0


class ServeEngine:
    """Continuous-batching greedy/sampled decoding."""

    def __init__(self, model: Model, params: Any, *, batch_size: int = 4,
                 max_context: int = 256, eos_token: int = 0,
                 temperature: float = 0.0, seed: int = 0, mesh=None):
        self.model = model
        self.params = params
        self.batch_size = batch_size
        self.max_context = max_context
        self.eos = eos_token
        self.temperature = temperature
        self.mesh = mesh
        self._rng = jax.random.PRNGKey(seed)
        self._temperature = jnp.float32(temperature)
        self._queue: "queue.Queue[GenerationResult]" = queue.Queue()
        self._next_id = 0
        self._slots = [_Slot() for _ in range(batch_size)]
        self.cache = model.init_cache(batch_size, max_context)
        _record_cache(self.cache)
        self._tokens = np.zeros((batch_size, 1), np.int32)
        self._step = _shared_decode_step(model, mesh)
        self._reset = _row_reset(model, max_context)
        self.steps_run = 0
        self.features: Optional[FeatureView] = None

    def attach_feature_source(self, subscription: Any) -> FeatureView:
        """Serve against a continuously-updated feature relation: wrap the
        subscription in a :class:`FeatureView` refreshed at the top of
        every :meth:`run` iteration (instead of reloading the relation
        per batch).  Returns the view; ``self.features.block`` is the
        current image."""
        self.features = FeatureView(subscription)
        self.features.refresh()
        return self.features

    # -- client API -------------------------------------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int = 16) -> int:
        if len(prompt) + max_new_tokens > self.max_context:
            raise ValueError(
                f"prompt of {len(prompt)} + {max_new_tokens} new tokens "
                f"exceeds max_context={self.max_context}")
        rid = self._next_id
        self._next_id += 1
        req = GenerationResult(rid, list(prompt))
        req._max_new = max_new_tokens  # type: ignore[attr-defined]
        self._queue.put(req)
        return rid

    def run(self, max_steps: int = 512) -> List[GenerationResult]:
        """Decode until queue + slots drain (or max_steps)."""
        done: List[GenerationResult] = []
        for _ in range(max_steps):
            if self.features is not None:
                self.features.refresh()
            self._fill_slots()
            if not any(s.request for s in self._slots):
                break
            self._decode_one_step(done)
            self.steps_run += 1
        # flush still-running sequences
        for slot in self._slots:
            if slot.request:
                slot.request.finished = False
                done.append(slot.request)
                slot.request = None
        return done

    # -- internals -----------------------------------------------------------------
    def _fill_slots(self) -> None:
        fresh = np.zeros(self.batch_size, bool)
        for i, slot in enumerate(self._slots):
            if slot.request is None and not self._queue.empty():
                req = self._queue.get()
                slot.request = req
                slot.pending = list(req.prompt) or [self.eos]
                slot.remaining = req._max_new  # type: ignore[attr-defined]
                slot.t0 = time.perf_counter()
                fresh[i] = True
        if fresh.any():
            self.cache = self._reset(self.cache, jnp.asarray(fresh))
            if "ssm" in self.cache or "conv" in self.cache:
                telemetry.counter("serve.state_rows_reset").inc(
                    int(fresh.sum()))

    def _decode_one_step(self, done: List[GenerationResult]) -> None:
        # a row samples once its last prompt token is fed; until then the
        # step only fills its cache (prefill-by-decode)
        sampling = np.zeros(self.batch_size, bool)
        for i, slot in enumerate(self._slots):
            if slot.request is None:
                continue
            if slot.pending:
                self._tokens[i, 0] = slot.pending.pop(0)
            sampling[i] = not slot.pending
        # hand JAX a private copy: the transfer of a host buffer may still
        # be reading it after dispatch returns, and _tokens is rewritten
        # before the next step (jnp.array does not copy a numpy input first)
        batch = {"token": jnp.asarray(self._tokens.copy())}
        if self.temperature > 0:
            nxt, self.cache, self._rng = self._step(
                self.params, self.cache, batch, self._rng, self._temperature)
        else:
            nxt, self.cache = self._step(self.params, self.cache, batch)
        if not sampling.any():
            return
        nxt = np.asarray(nxt)
        telemetry.counter("serve.host_pull_bytes").inc(nxt.nbytes)
        for i, slot in enumerate(self._slots):
            if not sampling[i]:
                continue
            tok = int(nxt[i])
            slot.request.tokens.append(tok)
            slot.remaining -= 1
            self._tokens[i, 0] = tok
            if tok == self.eos or slot.remaining <= 0:
                slot.request.finished = True
                slot.request.latency_s = time.perf_counter() - slot.t0
                done.append(slot.request)
                slot.request = None

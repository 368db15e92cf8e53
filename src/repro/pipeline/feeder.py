"""Training input pipeline fed through PipeGen data pipes.

The paper's scenario — engine A computes something, engine B consumes it,
no file-system materialization in between — is exactly the
tokenizer/feature-store -> trainer hand-off.  Here the *source* side is a
data engine (or synthetic generator) exporting token blocks, the *consumer*
side is the JAX training loop importing them through a pipe:

    source engine --[DataPipe, arrowcol]--> PipeFeeder --> BatchQueue --> step

Properties the 1000-node posture needs:

* pull-based with a bounded queue: a slow feeder degrades to backpressure,
  never unbounded memory;
* double-buffering: the queue depth (>=2) lets host->device transfer of
  batch N+1 overlap step N;
* straggler hedging: with several sources, a stalled source is dropped
  after ``hedge_timeout`` and its share re-requested from the others;
* deterministic restart: batches carry a monotonically increasing id, and
  ``skip_until`` fast-forwards a restarted trainer to the checkpointed step.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

from ..core import telemetry
from ..core.datapipe import DataPipeInput, DataPipeOutput, PipeConfig

__all__ = ["SyntheticSource", "EngineSource", "PipeFeeder", "BatchQueue"]


@dataclass
class Batch:
    batch_id: int
    data: Dict[str, np.ndarray]


class SyntheticSource:
    """Deterministic token stream (seeded); stands in for the tokenizer."""

    def __init__(self, vocab: int, seq_len: int, seed: int = 0):
        self.vocab = vocab
        self.seq_len = seq_len
        self.seed = seed

    def rows(self, n_rows: int) -> Iterator[np.ndarray]:
        """The ``n_rows`` token rows ``serve`` exports, regenerated from the
        seed (what a consumer checks a delivered batch against)."""
        rng = np.random.default_rng(self.seed)
        for _ in range(n_rows):
            yield rng.integers(0, self.vocab, self.seq_len)

    def serve(self, pipe_name: str, n_rows: int,
              config: Optional[PipeConfig] = None) -> None:
        """Export ``n_rows`` sequences through a data pipe (blocking)."""
        out = DataPipeOutput(pipe_name, config=config or PipeConfig())
        # feed the pipe the way a decorated engine would: typed rows
        from ..core.astring import AString

        for toks in self.rows(n_rows):
            parts: List[Any] = []
            for j, t in enumerate(toks):
                if j:
                    parts.append(",")
                parts.append(int(t))
            parts.append("\n")
            out.write(AString(parts))
        out.close()


class EngineSource:
    """Serve batches from a table in one of the mini-DBMS engines."""

    def __init__(self, engine: Any, table: str):
        self.engine = engine
        self.table = table

    def serve(self, pipe_name: str, config: Optional[PipeConfig] = None) -> None:
        from ..core import PipeEnabledEngine, adapter_for
        from ..core.ioredirect import PipeOpenContext

        gp = adapter_for(self.engine)
        with PipeEnabledEngine(gp), PipeOpenContext(config or PipeConfig()):
            self.engine.export_csv(self.table, pipe_name)


class BatchQueue:
    """Bounded prefetch queue (double buffering + backpressure).

    Every get's wait is observed in the registry histogram
    ``feeder.get_wait_s``, traced or not: the consumer's input wait."""

    def __init__(self, depth: int = 2):
        self._q: "queue.Queue[Optional[Batch]]" = queue.Queue(maxsize=depth)
        self._get_wait = telemetry.histogram("feeder.get_wait_s")

    def put(self, b: Optional[Batch]) -> None:
        self._q.put(b)

    def get(self, timeout: float = 60.0) -> Optional[Batch]:
        with telemetry.span("feeder.get") as sp:
            t0 = time.perf_counter()
            b = self._q.get(timeout=timeout)
            self._get_wait.observe(time.perf_counter() - t0)
            if b is not None:
                sp.set(batch=b.batch_id)
        return b


class PipeFeeder:
    """Consume token rows from one or more data pipes into batches.

    ``sources`` are pipe names to read from; each is drained on its own
    thread.  Rows are assembled into [batch, seq] int32 batches.  A source
    that produces nothing for ``hedge_timeout`` seconds is abandoned
    (straggler mitigation) and the remaining sources cover the demand.
    """

    def __init__(self, pipe_names: List[str], batch_size: int,
                 seq_len: int, *, queue_depth: int = 2,
                 hedge_timeout: float = 30.0, skip_until: int = 0):
        self.pipe_names = pipe_names
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.queue = BatchQueue(queue_depth)
        self.hedge_timeout = hedge_timeout
        self.skip_until = skip_until
        self.rows_dropped = 0
        self.sources_abandoned = 0
        self.errors: List[BaseException] = []  # why sources were abandoned
        self._row_q: "queue.Queue[Optional[np.ndarray]]" = queue.Queue(
            maxsize=batch_size * max(2, queue_depth) * 4)
        self._threads: List[threading.Thread] = []

    # -- source side ------------------------------------------------------------
    def _drain(self, pipe_name: str) -> None:
        try:
            pipe = DataPipeInput(pipe_name)
            last = time.perf_counter()
            for block in pipe.blocks():
                now = time.perf_counter()
                if now - last > self.hedge_timeout:
                    self.sources_abandoned += 1
                    break
                last = now
                # the put loop below waits on the consumer once the row
                # queue is full, so only the pivot is this block's work
                with telemetry.span("feeder.rows", rows=len(block)):
                    rows = np.asarray(
                        [np.asarray(c) for c in block.columns], dtype=np.int64
                    ).T  # [rows, seq]
                for r in rows:
                    self._row_q.put(r.astype(np.int32))
            pipe.close()
        except Exception as exc:  # a dead source must not stop the others
            self.sources_abandoned += 1
            self.errors.append(exc)
        finally:
            self._row_q.put(None)  # source-finished marker

    def start(self) -> "PipeFeeder":
        for name in self.pipe_names:
            t = threading.Thread(target=self._drain, args=(name,), daemon=True)
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._assemble, daemon=True)
        t.start()
        self._threads.append(t)
        return self

    def _assemble(self) -> None:
        finished = 0
        batch_id = 0
        rows: List[np.ndarray] = []
        while finished < len(self.pipe_names):
            item = self._row_q.get()
            if item is None:
                finished += 1
                continue
            if len(item) < self.seq_len:
                self.rows_dropped += 1
                continue
            rows.append(item[: self.seq_len])
            if len(rows) == self.batch_size:
                if batch_id >= self.skip_until:
                    with telemetry.span("feeder.batch", batch=batch_id):
                        tokens = np.stack(rows)
                        labels = np.roll(tokens, -1, axis=1)
                    self.queue.put(Batch(batch_id, {
                        "tokens": tokens, "labels": labels}))
                batch_id += 1
                rows = []
        self.queue.put(None)  # end of stream

    # -- consumer side -------------------------------------------------------------
    def batches(self) -> Iterator[Batch]:
        while True:
            b = self.queue.get()
            if b is None:
                return
            yield b

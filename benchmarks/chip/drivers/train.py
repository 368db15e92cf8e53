"""Train cells: the repo's compiled train step, fed through a PipeGen pipe
(``feed: pipe``) or from host arrays made during set-up (``feed: host``).

Set-up builds one object, the compiled step with its state, on weights made
from the seed, and drives it through the mix's first steps on rows that all
differ, through the same call and the same kind of feed as the window.  It
records each step's loss, the first gradient as the optimizer holds it
(first moment / (1 - b1)) and, after the last, each leaf's change.  The
window then opens with the export (or the first host batch) and runs the
same object for ``seconds``; only steps whose outputs were ready by its end
count.  Once it has closed and the state is freed, the reference follows the
set-up steps from the same seed, and every batch the pipe delivered is
compared with the rows regenerated from the seed.

The pipe path is the one ``repro.launch.train.train`` runs (SyntheticSource
exporting from a thread, PipeFeeder importing); it is driven here rather than
through ``train()``, which compiles inside the call and runs its export to
the end.
"""

from __future__ import annotations

import gc
import math
import threading
import time
from typing import Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from chipbench import traffic as tg
from chipbench.compare import leaf_gap
from chipbench.outcome import Outcome, compiles
from chipbench.seeds import sub_seed
from repro.models import ModelConfig, build_model
from repro.pipeline import PipeFeeder, SyntheticSource
from repro.train import TrainState, adamw_init, make_train_step


class PipeFeed:
    """SyntheticSource -> DataPipe -> PipeFeeder, as the launcher wires it."""

    def __init__(self, name: str, vocab: int, seq: int, batch: int,
                 rows_seed: int, n_rows: int):
        self.feeder = PipeFeeder([name], batch_size=batch, seq_len=seq)
        self.source = SyntheticSource(vocab, seq, seed=rows_seed)
        self.exporter = threading.Thread(
            target=self.source.serve, args=(name, n_rows), daemon=True)

    def start(self) -> "PipeFeed":
        self.feeder.start()
        self.exporter.start()
        self._batches = ((b.data["tokens"], b.data["labels"])
                         for b in self.feeder.batches())
        return self

    def batches(self) -> Iterator[np.ndarray]:
        return self._batches

    def close(self, timeout: float = 60.0) -> None:
        """Consume what is left so that every thread of the pipe ends."""
        deadline = time.perf_counter() + timeout
        for _ in self._batches:
            if time.perf_counter() > deadline:
                break
        for t in [self.exporter] + self.feeder._threads:
            t.join(max(0.0, deadline - time.perf_counter()))

    @property
    def healthy(self) -> bool:
        return not self.feeder.sources_abandoned and not self.feeder.errors


class HostFeed:
    """The same rows, made into host batches before the window opens."""

    def __init__(self, vocab: int, seq: int, batch: int, rows_seed: int,
                 n_rows: int):
        rows = tg.token_rows(vocab, seq, rows_seed, n_rows)
        self.rows = rows.reshape(-1, batch, seq)
        self.labels = np.roll(self.rows, -1, axis=2)

    def start(self) -> "HostFeed":
        return self

    def batches(self) -> Iterator[np.ndarray]:
        return iter(zip(self.rows, self.labels))

    def close(self) -> None:
        pass

    healthy = True


def _feed(kind: str, name: str, vocab, seq, batch, rows_seed, n_rows):
    if kind == "pipe":
        return PipeFeed(name, vocab, seq, batch, rows_seed, n_rows)
    if kind == "host":
        return HostFeed(vocab, seq, batch, rows_seed, n_rows)
    raise ValueError(f"unknown feed {kind!r}")


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _change_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


class TrainCell:
    """The compiled step of a cell, built once per process."""

    def __init__(self, cell, devices, reference):
        self.cell, self.ref = cell, reference
        self.cfg, self.tr = cell.config, cell.traffic
        self.model = build_model(ModelConfig(**reference.program_kwargs(self.cfg)))
        chips = cell.chips
        self.mesh = Mesh(np.array(devices[:chips]).reshape(chips, 1),
                         ("data", "model"))
        self.batch = self.tr["batch_per_chip"] * chips
        self.seq = self.tr["seq"]
        self.vocab = self.cfg["vocab_size"]
        self.step_mod = make_train_step(self.model, self.mesh,
                                        lr_peak=self.tr["optimizer"]["lr_peak"])
        self._adam_init = jax.jit(adamw_init,
                                  out_shardings=self.step_mod.state_shardings.opt)
        self.compiled = None

    def fresh_state(self, seed: int) -> TrainState:
        params = jax.device_put(self.ref.init_params(self.cfg, seed),
                                self.step_mod.state_shardings.params)
        return TrainState(params, self._adam_init(params))

    def compile(self, state: TrainState) -> None:
        shapes = {k: jax.ShapeDtypeStruct((self.batch, self.seq), np.int32)
                  for k in ("tokens", "labels")}
        self.compiled = self.step_mod(shapes).lower(
            jax.eval_shape(lambda: state), shapes).compile()
        self.batch_sharding = self.compiled.input_shardings[0][1]

    def step(self, state, tokens, labels):
        return self.compiled(state, jax.device_put(
            {"tokens": tokens, "labels": labels}, self.batch_sharding))

    def setup_steps(self, seed: int, state: TrainState, tag: str):
        """The mix's first steps from the seed; returns the state after them
        and the program's readings of them."""
        n = self.tr["setup_steps"]
        rows_seed = sub_seed(seed, "rows.setup")
        feed = _feed(self.tr["feed"], f"db://chipbench?query=setup-{tag}",
                     self.vocab, self.seq, self.batch, rows_seed,
                     n * self.batch).start()
        losses, batches, first = [], [], None
        for i, (tokens, labels) in zip(range(n), feed.batches()):
            batches.append(tokens)
            state, m = self.step(state, tokens, labels)
            losses.append(m["loss"])
            if i == 0:
                b1 = self.tr["optimizer"]["b1"]
                first = [float(x) / (1 - b1) for x in _leaf_norms(state.opt.m)]
        feed.close()
        p0 = self.ref.init_params(self.cfg, sub_seed(seed, "weights"))
        change = [float(x) for x in _change_norms(state.params, p0)]
        del p0
        names = self.ref.leaf_paths(state.params)
        readings = {
            "losses": [float(x) for x in losses],
            "grad_norms": dict(zip(names, first or [])),
            "change_norms": dict(zip(names, change)),
            "rows": np.stack(batches) if batches else None,
            "rows_seed": rows_seed,
            "feed_ok": feed.healthy and len(batches) == n,
        }
        return state, readings

    def reference_numbers(self, seed: int, readings) -> Dict[str, float]:
        """The numbers compared: the program's set-up steps against the
        reference's, from the same seed and the rows regenerated from it."""
        n = self.tr["setup_steps"]
        rows = tg.token_rows(self.vocab, self.seq, readings["rows_seed"],
                             n * self.batch).reshape(n, self.batch, self.seq)
        ref = self.ref.train_steps(self.cfg, self.tr["optimizer"],
                                   sub_seed(seed, "weights"), list(rows))
        return compare_steps(readings, ref, rows)


def compare_steps(prog, ref, rows) -> Dict[str, float]:
    losses = prog["losses"]
    n = len(ref["losses"])
    loss_gap = max((abs(a - b) for a, b in zip(losses, ref["losses"])),
                   default=math.inf)
    if len(losses) != n or not prog["feed_ok"]:
        loss_gap = math.inf
    delivered_wrong = n - sum(
        int(i < len(prog["rows"]) and np.array_equal(prog["rows"][i], rows[i]))
        for i in range(n)) if prog["rows"] is not None else n
    return {
        "setup_batches_wrong": float(delivered_wrong),
        "loss_gap": loss_gap,
        "grad_norm_gap": leaf_gap(prog["grad_norms"], ref["grad_norms"],
                                  ref["grad_norms"]),
        "update_norm_gap": leaf_gap(prog["change_norms"], ref["change_norms"],
                                    ref["grad_norms"]),
    }


def window_rows(cell, reference, peak_flops: float, seconds: float,
                batch: int, seq: int) -> int:
    """Rows enough that the window ends before the data does, even at the
    chip's peak: seconds * chips * peak / FLOPs per row, in whole batches."""
    per_row = reference.train_flops_per_token(cell.config, seq) * seq
    rows = seconds * cell.chips * peak_flops / per_row
    return int(math.ceil(rows / batch) + 1) * batch


def steps_in_window(sent: List[float], ready: List[float], t_open: float,
                    t_end: float) -> float:
    """Steps whose outputs were ready by the window's end, plus the share of
    the step in flight at its end that lay inside it.  A step runs from the
    later of its dispatch and the previous step's ready time (the window's
    open for the first) to its own ready time; all times are the host's."""
    full = sum(t <= t_end for t in ready)
    if full == len(ready):
        return float(full)
    start = max(sent[full], ready[full - 1] if full else t_open)
    if ready[full] <= start:
        return float(full)
    return full + min(1.0, max(0.0, (t_end - start) / (ready[full] - start)))


def run(cell, ctx) -> Outcome:
    tc = TrainCell(cell, ctx.devices, ctx.reference)
    seed = ctx.seed
    state = tc.fresh_state(sub_seed(seed, "weights"))
    tc.compile(state)
    state, prog = tc.setup_steps(seed, state, str(seed))

    kind = cell.traffic["feed"]
    n_rows = window_rows(cell, ctx.reference, ctx.peak["bf16_flops_per_s"],
                         ctx.seconds, tc.batch, tc.seq)
    rows_seed = sub_seed(seed, "rows.window")
    feed = _feed(kind, f"db://chipbench?query=window-{seed}", tc.vocab,
                 tc.seq, tc.batch, rows_seed, n_rows)
    tracer = ctx.tracer(cell.traffic.get("trace", {}))

    delivered: List[np.ndarray] = []
    losses = []
    sent: List[float] = []             # host time each step was dispatched
    ready: List[float] = []            # host time its outputs were ready
    pending = m = None
    first_batch_s = math.nan
    tracer.before_window()
    compiled0 = compiles()
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_start
    t_end = t_open + ctx.seconds
    tracer.open(t_open)
    feed.start()
    it = feed.batches()
    while True:
        with jax.profiler.TraceAnnotation("bench.wait_batch"):
            b = next(it, None)
        now = time.perf_counter()
        if b is None:
            break
        if math.isnan(first_batch_s):
            first_batch_s = now - t_open
        if now >= t_end:
            break
        tokens, labels = b
        delivered.append(tokens)
        sent.append(now)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, m = tc.step(state, tokens, labels)
        losses.append(m["loss"])
        if pending is not None:
            with jax.profiler.TraceAnnotation("bench.wait_step"):
                pending.block_until_ready()
            ready.append(time.perf_counter())
            if ready[-1] > t_end:
                pending = None
                break
        pending = m["loss"]
    if pending is not None:
        pending.block_until_ready()
        ready.append(time.perf_counter())
    jax.block_until_ready(state)
    tracer.close()
    window_compiles = compiles() - compiled0
    memory = ctx.memory_peak()
    feed.close()
    window_losses = [float(x) for x in losses]
    del state, m, losses, pending
    gc.collect()

    numbers = tc.reference_numbers(seed, prog)
    if kind == "pipe":
        want = tg.token_rows(tc.vocab, tc.seq, rows_seed,
                             len(delivered) * tc.batch)
        got = np.concatenate(delivered) if delivered else want[:0]
        numbers["window_rows_wrong"] = float(
            np.sum(np.any(got != want, axis=1)))
    failed = sum(not math.isfinite(x) for x in window_losses)
    tokens_per_step = tc.batch * tc.seq
    done = steps_in_window(sent, ready, t_open, t_end)
    return Outcome(
        attempted=len(window_losses) + cell.traffic["setup_steps"],
        failed=failed + int(numbers.get("window_rows_wrong", 0) > 0),
        end_to_end={"setup_s": setup_s,
                    "train_tokens_per_s": done * tokens_per_step / ctx.seconds},
        numbers=numbers,
        counters={
            "first_batch_s": first_batch_s,
            "steps_done": done,
            "compiles_in_window": window_compiles,
            "step_flops": ctx.reference.train_flops_per_token(
                cell.config, tc.seq) * tokens_per_step,
            "chips": cell.chips,
        },
        memory_peak_bytes=memory)

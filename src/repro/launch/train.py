"""Training launcher: a PipeGen data pipe feeds the jitted train step.

A ``SyntheticSource`` (the stand-in tokenizer) exports token rows through a
data pipe from a thread of this process; ``PipeFeeder`` imports them into
[batch, seq] batches; the step that ``make_train_step`` builds for the
local mesh (state sharded and donated) consumes them.  The run exits 1 when
fewer steps ran than asked or the feeder abandoned a source.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 100 --batch 8 --seq 64 [--reduced] [--zero1] [--microbatches 2]
"""

from __future__ import annotations

import argparse
import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
import numpy as np

from ..models import Model, build_model, get_config
from ..pipeline import PipeFeeder, SyntheticSource
from ..train import CheckpointManager, TrainState, adamw_init, make_train_step
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh, make_production_mesh

SOURCE_SEED = 1     # the synthetic tokenizer's stream


@dataclass
class TrainRun:
    state: TrainState
    steps: int              # steps this run took
    losses: List[float]
    grad_norms: List[float]
    compile_s: float        # lowering + compiling the step
    feeder: PipeFeeder


def train(model: Model, mesh, state: TrainState, *, steps: int, batch: int,
          seq: int, start: int = 0, zero1: bool = False,
          microbatches: int = 1, pipe_name: str = "db://launch-train?query=t0",
          mgr: Optional[CheckpointManager] = None, ckpt_every: int = 50,
          on_batch: Optional[Callable[[int, TrainState, dict], None]] = None,
          ) -> TrainRun:
    """Run steps ``start..steps`` on pipe-delivered batches.  ``state`` is
    consumed: the step donates its buffers.

    ``on_batch(step, state, batch)`` sees each host batch and the state it
    is about to update (before the step donates that state)."""
    step_mod = make_train_step(model, mesh, zero1=zero1,
                               microbatches=microbatches,
                               lr_total=max(steps, 100))
    shapes = {k: jax.ShapeDtypeStruct((batch, seq), np.int32)
              for k in ("tokens", "labels")}
    t0 = time.perf_counter()
    compiled = step_mod(shapes).lower(
        jax.eval_shape(lambda: state), shapes).compile()
    compile_s = time.perf_counter() - t0
    batch_shardings = compiled.input_shardings[0][1]
    state = jax.device_put(state, step_mod.state_shardings)

    n_rows = (steps - start) * batch
    feeder = PipeFeeder([pipe_name], batch_size=batch, seq_len=seq).start()
    threading.Thread(
        target=SyntheticSource(model.cfg.vocab, seq, seed=SOURCE_SEED).serve,
        args=(pipe_name, n_rows), daemon=True).start()

    step = start
    metrics_seen = []
    for b in feeder.batches():
        if on_batch is not None:
            on_batch(step, state, b.data)
        state, metrics = compiled(state,
                                  jax.device_put(b.data, batch_shardings))
        metrics_seen.append(metrics)
        step += 1
        if step % 10 == 0:
            print(f"[launch.train] step {step} "
                  f"loss={float(metrics['loss']):.4f}")
        if mgr and step % ckpt_every == 0:
            mgr.save(step, state, blocking=False)
    return TrainRun(state, step - start,
                    [float(m["loss"]) for m in metrics_seen],
                    [float(m["grad_norm"]) for m in metrics_seen],
                    compile_s, feeder)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (requires 256 devices)")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())

    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, adamw_init(params))
    start = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr and args.resume:
        try:
            state, start = mgr.restore(jax.eval_shape(lambda: state))
            print(f"[launch.train] resumed at step {start}")
        except FileNotFoundError:
            pass

    t0 = time.time()
    run = train(model, mesh, state, steps=args.steps, batch=args.batch,
                seq=args.seq, start=start, zero1=args.zero1,
                microbatches=args.microbatches, mgr=mgr,
                ckpt_every=args.ckpt_every)
    if mgr:
        mgr.wait()
        mgr.save(start + run.steps, run.state)
    dt = time.time() - t0
    print(f"[launch.train] {run.steps} steps in {dt:.1f}s "
          f"(compile {run.compile_s:.1f}s)")
    want = args.steps - start
    if run.steps < want or run.feeder.sources_abandoned:
        print(f"[launch.train] FAILED: {run.steps}/{want} steps, "
              f"{run.feeder.sources_abandoned} source(s) abandoned "
              f"{run.feeder.errors!r}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

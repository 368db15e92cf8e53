#!/usr/bin/env python3
"""Smoke run of PipeGen's ML consumer on one TPU chip.

Every phase runs in this one process, the only one that touches JAX; the
pipe's exporter is a thread, as in ``repro.launch.train``.

* train   -- smollm-360m at its registered widths, batch 8 x 1024 tokens,
             5 steps fed through a PipeGen pipe (SyntheticSource ->
             PipeFeeder) by ``repro.launch.train.train``.  Every batch is
             checked bit for bit against rows regenerated from the source's
             seed; every loss is finite; the first is near ln(vocab) and
             equals ``model.loss_fn`` on the same params and batch.
* serve   -- qwen2-1.5b at its registered widths through ``ServeEngine``:
             8 requests of 16 new tokens, batch 4.  Teacher-forced
             ``decode_step`` logits match ``model.forward`` on the same
             tokens, and each greedy token is the forward argmax wherever
             the top-2 margin leaves no doubt.
* kernels -- each of the five Pallas kernels, compiled, once at the widths
             of a registered config that would use it, against its ref.py.

``--four-chips`` runs only the data-parallel train step on a (4, 1)
("data", "model") mesh and compares it with the same global batches on a
one-device mesh.

Each phase prints one JSON line of its numbers.  The last line is
``{"ok": true, "device": {...}}``; a failed phase exits 1 without it, and a
host without a TPU exits 2 before any phase.

    python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 1
TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = "smollm-360m", 8, 1024, 5
# random init gives near-uniform next-token predictions: the first loss
# sits within this many nats of ln(vocab)
LOSS_MARGIN = 1.0
# the step's loss vs model.loss_fn outside it: one bf16 model, two programs
STEP_LOSS_TOL = 2e-2
SERVE_ARCH, SERVE_BATCH, SERVE_REQUESTS, SERVE_NEW = "qwen2-1.5b", 4, 8, 16
SERVE_CONTEXT = 64
# A random-init stack amplifies rounding with depth: at qwen2-1.5b widths,
# teacher-forced decode and the forward differ by up to 2.4 logits in bf16
# at 8 layers (CPU), and by 0.84 in float32 at "highest" matmul precision
# at 8 layers (v5e).  So the registered bf16 model is served at full depth
# and checked for its answers, and the reference comparison runs the same
# widths in float32 at "highest" precision, cut to SERVE_EXACT_LAYERS.
SERVE_EXACT_LAYERS = 2
LOGIT_TOL = 2e-2
# The learning rate is 0 at step 0, so both steps take their gradients at
# the initial weights.  A third would not: once an update has moved the
# weights by rounding-level amounts, this random stack turns that into
# O(1) gradient differences (first moments 0.75 apart at step 3 on v5e).
FOUR_STEPS = 2
FOUR_LOSS_TOL = 1e-2        # per-step loss, one mesh vs the other
# per-step |grad norm difference| / grad norm.  Clipping hides the scale of
# the gradient from the moments; this catches it: a shard-reduction bug
# moves the norm by 2x or more, bf16 rounding moved it 1.7e-2 on v5e.
FOUR_GNORM_TOL = 0.1
FOUR_MOMENT_TOL = 2e-2      # max |m difference| / max |m|


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _max_abs(x) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(x, np.float32))))


def _max_err(got, want) -> float:
    import numpy as np
    return _max_abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))


def _fresh_state(model):
    import jax

    from repro.train import TrainState, adamw_init
    params = model.init(jax.random.PRNGKey(0))
    return TrainState(params, adamw_init(params))


# --------------------------------------------------------------------------- #
# train
# --------------------------------------------------------------------------- #

def phase_train(cfg, *, batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                steps: int = TRAIN_STEPS) -> dict:
    import jax
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import SOURCE_SEED, train
    from repro.models import build_model
    from repro.pipeline import SyntheticSource

    mesh = make_local_mesh()
    model = build_model(cfg)
    expected = SyntheticSource(cfg.vocab, seq,
                               seed=SOURCE_SEED).rows(steps * batch)
    loss_of = jax.jit(lambda p, b: model.loss_fn(p, b, mesh)[0])
    seen = {"batches": 0, "mismatched": [], "ref_loss": None, "ref_s": 0.0}

    def on_batch(step, st, b):
        want = np.stack([next(expected) for _ in range(batch)])
        if not (np.array_equal(b["tokens"], want)
                and np.array_equal(b["labels"], np.roll(want, -1, axis=1))):
            seen["mismatched"].append(step)
        seen["batches"] += 1
        if seen["ref_loss"] is None:    # before the step donates st
            t0 = time.perf_counter()
            seen["ref_loss"] = float(loss_of(st.params, b))
            seen["ref_s"] = time.perf_counter() - t0     # compile + run

    t0 = time.perf_counter()
    run = train(model, mesh, _fresh_state(model), steps=steps, batch=batch,
                seq=seq, pipe_name="db://chip-smoke?query=train",
                on_batch=on_batch)
    wall = time.perf_counter() - t0
    losses = run.losses
    ln_v = math.log(cfg.vocab)
    first = losses[0] if losses else float("nan")
    checks = {
        "steps": run.steps == steps and seen["batches"] == steps,
        "feeder": not run.feeder.sources_abandoned,
        "bits": not seen["mismatched"],
        "finite": bool(losses) and all(math.isfinite(x) for x in losses),
        "first_near_ln_vocab": abs(first - ln_v) <= LOSS_MARGIN,
        "first_eq_loss_fn": seen["ref_loss"] is not None
        and abs(first - seen["ref_loss"]) <= STEP_LOSS_TOL,
    }
    return {
        "arch": cfg.name, "batch": batch, "seq": seq, "steps": run.steps,
        "tokens": run.steps * batch * seq, "losses": losses,
        "ln_vocab": ln_v, "loss_margin": LOSS_MARGIN,
        "ref_loss": seen["ref_loss"], "step_loss_tol": STEP_LOSS_TOL,
        "mismatched_batches": seen["mismatched"],
        "sources_abandoned": run.feeder.sources_abandoned,
        "feeder_errors": [repr(e) for e in run.feeder.errors],
        "compile_s": run.compile_s, "ref_s": seen["ref_s"],
        "wall_s": wall, "checks": checks, "ok": all(checks.values()),
    }


# --------------------------------------------------------------------------- #
# serve
# --------------------------------------------------------------------------- #

def exact_serve_config(cfg):
    """``cfg`` at its widths in float32, cut to SERVE_EXACT_LAYERS."""
    import dataclasses
    return dataclasses.replace(cfg, dtype="float32",
                               n_layers=min(cfg.n_layers, SERVE_EXACT_LAYERS))


def phase_serve(cfg, *, exact: bool = False) -> dict:
    """Serve SERVE_REQUESTS through ServeEngine; with ``exact``, also
    compare with the reference at "highest" matmul precision."""
    import contextlib

    import jax

    with (jax.default_matmul_precision("highest") if exact
          else contextlib.nullcontext()):
        return _serve(cfg, exact)


def _serve(cfg, exact: bool) -> dict:
    import jax
    import numpy as np

    from repro.models import build_model
    from repro.serve import ServeEngine

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist()
               for n in rng.integers(4, 12, SERVE_REQUESTS)]
    eng = ServeEngine(model, params, batch_size=SERVE_BATCH,
                      max_context=SERVE_CONTEXT,
                      eos_token=-1)
    t0 = time.perf_counter()            # the first step compiles
    eng.submit(prompts[0][:1], max_new_tokens=1)
    eng.run(max_steps=4)
    compile_s = time.perf_counter() - t0
    rids = [eng.submit(p, max_new_tokens=SERVE_NEW) for p in prompts]
    steps0 = eng.steps_run
    t0 = time.perf_counter()
    results = {r.request_id: r for r in eng.run(max_steps=100_000)}
    run_s = time.perf_counter() - t0
    answered = [results[r] for r in rids if r in results]
    checks = {
        "answered": len(answered) == SERVE_REQUESTS
        and all(r.finished and len(r.tokens) == SERVE_NEW for r in answered),
        "in_vocab": all(0 <= t < cfg.vocab for r in answered
                        for t in r.tokens),
    }
    rec = {
        "arch": cfg.name, "layers": cfg.n_layers, "dtype": cfg.dtype,
        "batch": SERVE_BATCH, "requests": len(answered),
        "new_tokens": sum(len(r.tokens) for r in answered),
        "decode_steps": eng.steps_run - steps0,
        "compile_s": compile_s, "run_s": run_s,
    }
    if exact:
        ref = _serve_reference(model, params, answered)
        checks.update(ref.pop("checks"))
        rec.update(ref)
    return {**rec, "checks": checks, "ok": all(checks.values())}


def _serve_reference(model, params, answered) -> dict:
    """The engine's token streams, teacher-forced through a fresh
    decode_step, against the full forward on the same tokens (rows padded
    to one length; the forward is causal, so padding changes nothing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seqs = [r.prompt + r.tokens for r in answered]
    width = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), width), np.int32)
    valid = np.zeros(toks.shape, bool)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
        valid[i, :len(s)] = True
    t0 = time.perf_counter()
    fwd = np.asarray(jax.jit(model.forward)(
        params, {"tokens": jnp.asarray(toks)}), np.float32)
    step = jax.jit(model.decode_step)
    cache = model.init_cache(len(seqs), SERVE_CONTEXT)
    dec = []
    for t in range(width):
        lg, cache = step(params, cache, {"token": jnp.asarray(toks[:, t:t + 1])})
        dec.append(np.asarray(lg[:, 0], np.float32))
    dec = np.stack(dec, axis=1)
    ref_s = time.perf_counter() - t0
    diff = np.abs(dec - fwd)[valid]
    logit_err = float(np.max(diff))

    compared = agreed = 0
    for i, r in enumerate(answered):
        for j, tok in enumerate(r.tokens):
            row = fwd[i, len(r.prompt) - 1 + j]
            top2 = np.partition(row, -2)[-2:]
            if top2[1] - top2[0] > 2 * LOGIT_TOL:    # decode can't flip it
                compared += 1
                agreed += int(np.argmax(row) == tok)
    return {
        "logit_max_err": logit_err, "logit_mean_err": float(np.mean(diff)),
        "logit_tol": LOGIT_TOL,
        "greedy_compared": compared, "greedy_agreed": agreed, "ref_s": ref_s,
        "checks": {
            "finite": bool(np.isfinite(dec).all() and np.isfinite(fwd).all()),
            "decode_vs_forward": logit_err <= LOGIT_TOL,
            "greedy_vs_forward": compared > 0 and agreed == compared,
        },
    }


# --------------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------------- #

# max |kernel - ref| / max |ref|: bf16 attention outputs, f32 scans
KERNEL_TOL = {"pivot": 0.0, "flashattn": 2e-2, "decode_attn": 2e-2,
              "rwkv6_scan": 1e-3, "mamba2_ssd": 2e-2}


def phase_kernels(dims=None, *, interpret: bool = False) -> dict:
    """Each kernel at ``dims`` (default: the registered widths)."""
    import jax

    from repro.kernels.cases import WIDTHS, kernel_case

    out, ok = {}, True
    for name, d in (dims or WIDTHS).items():
        case = kernel_case(name, d, seed=SEED)
        t0 = time.perf_counter()
        got = jax.block_until_ready(case.kernel(*case.args,
                                                interpret=interpret))
        first_s = time.perf_counter() - t0     # compile + one call
        with jax.default_matmul_precision("highest"):
            want = case.ref(*case.args)
        pairs = (list(zip(got, want)) if isinstance(got, (tuple, list))
                 else [(got, want)])
        scale = max(_max_abs(w) for _, w in pairs) or 1.0
        rel = max(_max_err(g, w) for g, w in pairs) / scale
        good = rel <= KERNEL_TOL[name]
        ok &= good
        out[name] = {"source": case.source, "rel_max_err": rel,
                     "tol": KERNEL_TOL[name], "first_call_s": first_s,
                     "ok": good}
    return {"kernels": out, "ok": ok}


# --------------------------------------------------------------------------- #
# four chips: data-parallel train step vs one device
# --------------------------------------------------------------------------- #

def phase_four_chips(cfg, devices, *, batch: int = TRAIN_BATCH,
                     seq: int = TRAIN_SEQ) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.launch.train import train
    from repro.models import build_model
    from repro.train.optimizer import lr_schedule

    model = build_model(cfg)
    steps = FOUR_STEPS
    runs = {}
    for label, devs in (("one", devices[:1]), ("four", devices[:4])):
        mesh = Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))
        run = train(model, mesh, _fresh_state(model), steps=steps,
                    batch=batch, seq=seq,
                    pipe_name=f"db://chip-smoke?query={label}")
        runs[label] = (run, jax.tree_util.tree_map(np.asarray, run.state))
        del run.state       # free the device copy before the next mesh
        gc.collect()
    (one, s1), (four, s4) = runs["one"], runs["four"]
    leaves = lambda t: jax.tree_util.tree_leaves(t)
    param_err = max(_max_err(a, b) for a, b in zip(leaves(s1.params),
                                                   leaves(s4.params)))
    param_max = max(_max_abs(a) for a in leaves(s1.params))
    m_err = max(_max_err(a, b) for a, b in zip(leaves(s1.opt.m),
                                               leaves(s4.opt.m)))
    m_max = max(_max_abs(a) for a in leaves(s1.opt.m)) or 1.0
    # AdamW moves a weight by at most lr per step, so a flipped update
    # sign costs 2*lr; bf16 storage adds one ulp of the largest weight
    lrs = [float(lr_schedule(np.int32(i), total=max(steps, 100)))
           for i in range(steps)]
    param_tol = 2 * sum(lrs) + param_max * 2.0 ** -7
    loss_err = max(abs(a - b) for a, b in zip(one.losses, four.losses))
    gnorm_err = max(abs(a - b) / a for a, b in zip(one.grad_norms,
                                                   four.grad_norms))
    checks = {
        "steps": one.steps == steps and four.steps == steps,
        "feeder": not (one.feeder.sources_abandoned
                       or four.feeder.sources_abandoned),
        "finite": all(math.isfinite(x) for x in one.losses + four.losses),
        "loss": loss_err <= FOUR_LOSS_TOL,
        "grad_norm": gnorm_err <= FOUR_GNORM_TOL,
        "params": param_err <= param_tol,
        "moments": m_err / m_max <= FOUR_MOMENT_TOL,
    }
    return {
        "arch": cfg.name, "global_batch": batch, "seq": seq, "steps": steps,
        "mesh": {"data": len(devices[:4]), "model": 1},
        "losses_one": one.losses, "losses_four": four.losses,
        "loss_max_err": loss_err, "loss_tol": FOUR_LOSS_TOL,
        "grad_norms_one": one.grad_norms, "grad_norms_four": four.grad_norms,
        "grad_norm_rel_err": gnorm_err, "grad_norm_tol": FOUR_GNORM_TOL,
        "param_max_err": param_err, "param_tol": param_tol,
        "moment_rel_err": m_err / m_max, "moment_tol": FOUR_MOMENT_TOL,
        "compile_s_one": one.compile_s, "compile_s_four": four.compile_s,
        "checks": checks, "ok": all(checks.values()),
    }


# --------------------------------------------------------------------------- #

def _run_phase(name: str, fn, device: dict) -> bool:
    t0 = time.perf_counter()
    try:
        rec = fn()
    except Exception:    # report it and go on to the next phase
        rec = {"ok": False, "error": traceback.format_exc()}
    rec = {"phase": name, "device": device,
           "phase_s": time.perf_counter() - t0, **rec}
    emit(rec)
    gc.collect()
    return bool(rec["ok"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the data-parallel train step on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    from repro.models import get_config

    cache_dir = use_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    emit({"phase": "setup", "device": device, "jax": jax.__version__,
          "compile_cache": cache_dir})
    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(
            get_config(TRAIN_ARCH), devices))]
    else:
        phases = [
            ("train", lambda: phase_train(get_config(TRAIN_ARCH))),
            ("serve", lambda: phase_serve(get_config(SERVE_ARCH))),
            ("serve_exact", lambda: phase_serve(
                exact_serve_config(get_config(SERVE_ARCH)), exact=True)),
            ("kernels", phase_kernels),
        ]
    failed = [name for name, fn in phases
              if not _run_phase(name, fn, device)]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

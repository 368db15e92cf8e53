#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers on many seeds,
and the control's on a few, in one process on the chip.

    python3 benchmarks/chip/control.py --workload <name> \
        --seeds 11,12,... --control-seeds 21,22,23 [--seconds 8]

Train cells: for each of ``--seeds`` the program's set-up steps against the
reference (what a run compares, without its window); for each of
``--control-seeds`` the control (the reference with fp8 matrix products)
and the fault "half of the batch left out, the mean over the rest" (the
reference on half the rows), each read by the same numbers against the
float32 reference.  Serve cells: for each seed a window of ``--seconds`` at
the cell's own load, drained, and the widest gap of the served tokens; for
control seeds also the widest gap of the tokens the control puts first at
the same positions.  One JSON line per reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402
from chipbench import spec  # noqa: E402
from chipbench.outcome import WindowTracer  # noqa: E402
from chipbench.seeds import sub_seed  # noqa: E402


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def train_readings(cell, devices, reference, seeds, control_seeds):
    from chipbench import traffic as tg
    driver = cell.module("drivers", "train")
    tc = driver.TrainCell(cell, devices, reference)
    for seed in seeds:
        t0 = time.perf_counter()
        state = tc.fresh_state(sub_seed(seed, "weights"))
        if tc.compiled is None:
            tc.compile(state)
        state, prog = tc.setup_steps(seed, state, f"r{seed}")
        del state
        gc.collect()
        nums = tc.reference_numbers(seed, prog)
        emit(kind="program", seed=seed, numbers=nums, losses=prog["losses"],
             seconds=time.perf_counter() - t0)
    opt = cell.traffic["optimizer"]
    n = cell.traffic["setup_steps"]
    for seed in control_seeds:
        w = sub_seed(seed, "weights")
        rows = tg.token_rows(tc.vocab, tc.seq, sub_seed(seed, "rows.setup"),
                             n * tc.batch).reshape(n, tc.batch, tc.seq)
        ref = reference.train_steps(cell.config, opt, w, list(rows))
        for kind, kw, batches in (
                ("control", {"fp8": True}, list(rows)),
                ("fault.half_batch", {}, [r[: tc.batch // 2] for r in rows])):
            got = reference.train_steps(cell.config, opt, w, batches, **kw)
            got.update(rows=rows, feed_ok=True)
            emit(kind=kind, seed=seed,
                 numbers=driver.compare_steps(got, ref, rows),
                 losses=got["losses"], ref_losses=ref["losses"])


def serve_readings(cell, reference, seeds, control_seeds, seconds):
    import numpy as np
    driver = cell.module("drivers", "serve")
    sc = driver.ServeCell(cell, reference)
    for seed in list(seeds) + [s for s in control_seeds if s not in seeds]:
        t0 = time.perf_counter()
        eng = sc.engine(seed)
        requests = sc.requests(seed, seconds)
        t_open, t_done = driver.serve_window(eng, requests, seconds,
                                             WindowTracer(None, seconds))
        lat = driver.latencies(eng, requests)
        served = driver.sample(eng, requests, seed,
                               cell.traffic["check"]["sample"])
        del eng
        gc.collect()
        control = seed in control_seeds
        gp, gc_ = sc.served_gap(seed, served, control=control)
        emit(kind="program", seed=seed, numbers={
            "served_gap": float(np.max(gp)), "unfinished": lat["unfinished"]},
            served_tokens=int(gp.size), requests=len(requests),
            window_s=t_done - t_open, seconds=time.perf_counter() - t0)
        if control:
            emit(kind="control", seed=seed,
                 numbers={"served_gap": float(np.max(gc_))},
                 served_tokens=int(gc_.size),
                 share_differs=float(np.mean(gc_ > 0)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    root = Path.cwd()
    cell = spec.load_cell(root, args.workload)
    devices = run.devices_for(cell.chips, require_tpu=True)
    run.use_compile_cache(root)
    reference = cell.module("references", cell.config["reference"])
    if cell.traffic["driver"] == "train":
        train_readings(cell, devices, reference, seeds, controls)
    else:
        serve_readings(cell, reference, seeds, controls, args.seconds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

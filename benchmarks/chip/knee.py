#!/usr/bin/env python3
"""Sweep a serve cell's arrival rate to find the knee: the highest rate the
engine sustains without a growing backlog.  Run once when a cell's rate is
chosen; the cell then offers load at a fixed rate (about 0.8 of the knee).

    python3 benchmarks/chip/knee.py --workload <serve cell> --seed <n> \
        --seconds 20 --rates 4,6,8,10

One engine serves every rate in turn.  For each rate, one JSON line: the
requests due, output tokens per second over the window and its drain, the
median time to first token of the first and the last third of the requests
(a growing backlog shows as the last third waiting longer), the p90, and
the seconds the drain took after the window closed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402
from chipbench import spec  # noqa: E402
from chipbench import traffic as tg  # noqa: E402
from chipbench.outcome import WindowTracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = Path.cwd()
    cell = spec.load_cell(root, args.workload)
    run.devices_for(cell.chips, require_tpu=True)
    run.use_compile_cache(root)
    reference = cell.module("references", cell.config["reference"])
    driver = cell.module("drivers", "serve")
    sc = driver.ServeCell(cell, reference)
    eng = sc.engine(args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        requests = sc.requests(args.seed, args.seconds, rate)
        steps0 = eng.steps_run
        t_open, t_done = driver.serve_window(
            eng, requests, args.seconds, WindowTracer(None, args.seconds))
        ttft = [eng.stamps[rid][0] - (t_open + requests[i].due_s)
                for rid, i in sorted(eng.rid_of.items()) if eng.stamps[rid]]
        third = max(1, len(ttft) // 3)
        tokens = sum(r.max_new for r in requests)
        steps = eng.steps_run - steps0
        print(json.dumps({
            "rate_per_s": rate, "requests": len(requests),
            "tokens_per_s": tokens / (t_done - t_open),
            "ttft_p50_first_third_s": tg.percentile(ttft[:third], 50),
            "ttft_p50_last_third_s": tg.percentile(ttft[-third:], 50),
            "ttft_p90_s": tg.percentile(ttft, 90),
            "drain_s": t_done - t_open - args.seconds,
            "decode_steps": steps,
            "ms_per_step": 1e3 * (t_done - t_open) / max(steps, 1)}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python3
"""Where a pipe-fed train cell's input time goes, read from the program's
own spans on the device trace's clock.

    python3 benchmarks/chip/pipe_spans.py --workload <train cell> \
        --seeds 11,12,13 --seconds 45 --trace <0|1>

Runs the cell's window as ``run.py`` does, once per seed in one process,
with the program's span tracer (``repro.core.telemetry``) on from the
window's open to its close.  With ``--trace 1`` the window is profiled as
well, and the spans are placed on the ``.xplane.pb``'s clock by the offset
between the monotonic clock, read as ``bench.window`` was entered, and
that annotation's start in the trace (``chipbench/program_trace.py``).
Without it the quantities come from the spans on their own clock, which
gives the cost of the span tracer against ``run.py --trace 0``.

One JSON line per seed: the cell's end-to-end metrics and ``correct``;
the pipe and feeder quantities (``first_frame_s``, ``source_s``,
``export_s``, ``unpack_s``, ``input_wait_s``) and the ``parts`` they sum;
the wait summed by the ``feeder.get_wait_s`` histogram, the spans recorded
and dropped, and the harness's ``first_batch_s``; with ``--trace 1`` also
the device's busy time over the window, the idle gaps named by harness
and program span, the share of idle time program work spans cover, and
``first_step_lag_s``, from the first batch's ``feeder.get`` to the first
step on the device.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import run  # noqa: E402
from chipbench import program_trace, spec, tracing  # noqa: E402
from chipbench.outcome import WindowTracer  # noqa: E402
from repro.core import telemetry  # noqa: E402


class SpanWindow(WindowTracer):
    """The window's tracer, with the program's span tracer on while the
    window is open.  ``anchor`` is the monotonic clock read as the window's
    annotation is entered; ``span_window`` the monotonic clock at the
    window's open and close."""

    anchor = None

    def open(self, t_open: float) -> None:
        self.program = telemetry.enable_tracing()
        self.waits = telemetry.histogram("feeder.get_wait_s")
        self.wait_sum0 = self.waits.sum
        self.mono_open = time.monotonic()
        super().open(t_open)

    def _begin(self, now: float) -> None:
        self._span = jax.profiler.TraceAnnotation(tracing.WINDOW)
        self.anchor = time.monotonic()
        self._span.__enter__()
        self.started, self.t_begin = True, now

    def close(self) -> None:
        if not self.done:
            self.span_window = (self.mono_open, time.monotonic())
            self.wait_s = self.waits.sum - self.wait_sum0
            telemetry.disable_tracing()
        super().close()


class SpanCtx(run.Ctx):
    window = None

    def tracer(self, trace_cfg):
        self.window = SpanWindow(
            self.trace_dir, self.seconds, trace_cfg.get("start", 0.0),
            trace_cfg.get("length"))
        return self.window


def run_seed(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True) -> dict:
    t_start = time.perf_counter()
    cell = spec.load_cell(root, workload)
    devices = run.devices_for(cell.chips, require_tpu)
    if require_tpu:
        run.use_compile_cache(root)
    peak = spec.peaks(root, devices[0].device_kind)
    driver = cell.module("drivers", cell.traffic["driver"])
    reference = cell.module("references", cell.config["reference"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-spans-") if trace else None
    try:
        ctx = SpanCtx(seed, seconds, t_start, devices, reference, peak,
                      trace_dir)
        out = driver.run(cell, ctx)
        win = ctx.window
        recorded = win.program.spans()
        rec = {"seed": seed, "trace": int(trace),
               "end_to_end": out.end_to_end,
               "correct": all(math.isfinite(v) and v <= cell.limits[k]
                              for k, v in out.numbers.items()),
               "program_spans": len(recorded),
               "program_spans_dropped": win.program.dropped,
               "get_wait_hist_s": win.wait_s,
               "first_batch_s": out.counters.get("first_batch_s")}
        consumer = threading.get_ident()
        if not trace:
            lo, hi = win.span_window
            rec.update(program_trace.pipe_quantities(
                program_trace.from_telemetry(recorded), lo, hi, consumer))
            return rec
        program_trace.dump(trace_dir, recorded, win.anchor)
        tr = tracing.load(trace_dir)
        lo, hi = program_trace.window(tr)
        spans = program_trace.load(trace_dir, lo)
        summary = tracing.reduce(tr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec.update(program_trace.pipe_quantities(spans, lo, hi, consumer))
    rec.update(program_trace.name_gaps(tr, spans, lo, hi))
    rec.update(window_s=summary.window_s, busy_s=summary.busy_s,
               first_step_lag_s=program_trace.first_step_lag_s(
                   tr, spans, lo, hi))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            rec = run_seed(Path.cwd(), args.workload, seed, args.seconds,
                           bool(args.trace))
        except run.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 3
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

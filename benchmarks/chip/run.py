#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic mix, limits
and per-layer metric readers are files under ``benchmarks/chip`` named in
``BENCHMARK.json`` (see ``chipbench/spec.py``).  The run exits non-zero and
prints no result when JAX finds no TPU, or fewer chips than the cell asks
for.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` the
``breakdown`` of the trace, and last ``compared``: each number that decides
``correct`` with its limit.  The same numbers end standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import spec  # noqa: E402


class NoChip(RuntimeError):
    pass


@dataclass
class Ctx:
    seed: int
    seconds: float
    t_start: float
    devices: List[Any]
    reference: Any
    peak: Dict[str, float]
    trace_dir: Optional[str]

    def tracer(self, trace_cfg: Dict[str, Any]):
        from chipbench.outcome import WindowTracer
        return WindowTracer(self.trace_dir, self.seconds,
                            trace_cfg.get("start", 0.0),
                            trace_cfg.get("length"))

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip (0 where not reported)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks, default=0))


def use_compile_cache(root: Path) -> None:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``; every program is kept, however quick."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root).resolve() / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_tpu: bool) -> List[Any]:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float = T_START,
             require_tpu: bool = True) -> Dict[str, Any]:
    """Run ``workload`` from the checkout at ``root``; returns the result."""
    from chipbench import tracing

    cell = spec.load_cell(root, workload)
    devices = devices_for(cell.chips, require_tpu)
    if require_tpu:
        use_compile_cache(root)
    kind = devices[0].device_kind
    peak = spec.peaks(root, kind)
    driver = cell.module("drivers", cell.traffic["driver"])
    reference = cell.module("references", cell.config["reference"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        ctx = Ctx(seed, seconds, t_start, devices, reference, peak, trace_dir)
        out = driver.run(cell, ctx)
        summary = tracing.reduce(tracing.load(trace_dir)) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()
    print("counters " + json.dumps(out.counters), file=sys.stderr)

    compared = {}
    for name, value in out.numbers.items():
        if name not in cell.limits:
            raise KeyError(f"limits/{workload}.json has no limit for {name!r}")
        compared[name] = {"value": value, "limit": cell.limits[name]}
    correct = bool(compared) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            reader = cell.module("metrics", m["name"])
            value = reader.read(summary, out.counters, peak)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = summary.breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(Path.cwd(), args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
intervals: each device's ops and modules, and the harness's host spans
(``bench.*``).  ``reduce`` computes from those alone, so it can be checked on
a synthetic trace:

* busy: the union of the device's op intervals inside the traced window
  (``bench.window``), averaged over the chips; idle = window - busy;
* each module's (jitted program's) executions and device time;
* collective time during which no other op ran on that device;
* the breakdown: the ops that took most time, and the longest idle gaps,
  each named by the harness span that covered most of it.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float, str]          # start_s, end_s, name

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
WINDOW = "bench.window"


@dataclass
class DeviceTrace:
    ops: List[Interval] = field(default_factory=list)
    modules: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    host: List[Interval]                     # the harness's bench.* spans


@dataclass
class Summary:
    window_s: float
    busy_s: float                            # mean over devices
    modules: Dict[str, Tuple[int, float]]    # name -> (executions, seconds), mean over devices
    collective_exposed_s: float              # mean over devices
    breakdown: Dict[str, List[List]]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, DeviceTrace] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "Core" not in plane.name:
            dev = devices.setdefault(plane.name, DeviceTrace())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops.extend(_intervals(line.events))
                elif line.name == "XLA Modules":
                    dev.modules.extend(_intervals(line.events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(i for i in _intervals(line.events)
                            if i[2].startswith("bench."))
    return Trace(devices, host)


def _intervals(events) -> List[Interval]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in events]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _minus(a, b) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return _length(a) - covered


_HLO = re.compile(r"^(%[\w.\-]+) = (\w+\[[\d,]*\])?.*?\s([\w\-]+)\(")


def short_name(name: str) -> str:
    """An op's HLO text cut to its name, kind and result shape
    ("%fusion.186 fusion bf16[64,8960]")."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    return " ".join(g for g in (m.group(1), m.group(3), m.group(2)) if g)


def is_collective(name: str) -> bool:
    return name.lower().startswith(COLLECTIVES)


def reduce(trace: Trace, top: int = 10) -> Summary:
    wins = [(s, e) for s, e, n in trace.host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    window = hi - lo
    if not trace.devices:
        raise ValueError("no device plane in the trace")
    n_dev = len(trace.devices)
    busy = exposed = 0.0
    modules: Dict[str, List[float]] = {}
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in trace.devices.values():
        ops = _clip([(s, e) for s, e, _ in dev.ops], lo, hi)
        busy_iv = union(ops)
        busy += _length(busy_iv)
        coll = [(s, e) for s, e, n in dev.ops if is_collective(n)]
        other = [(s, e) for s, e, n in dev.ops if not is_collective(n)]
        exposed += _minus(_clip(coll, lo, hi), _clip(other, lo, hi))
        for s, e, n in dev.modules:
            if s >= lo and e <= hi:
                m = modules.setdefault(n, [0, 0.0])
                m[0] += 1
                m[1] += e - s
        for s, e, n in dev.ops:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                op_time[n] = op_time.get(n, 0.0) + c
        edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    spans = [(s, e, n) for s, e, n in trace.host if n != WINDOW]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    breakdown = {
        "device_ops": [[short_name(n), t / n_dev] for n, t in
                       sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[_host_doing(spans, s, e), e - s] for s, e in longest],
    }
    return Summary(
        window_s=window, busy_s=busy / n_dev,
        modules={n: (round(c / n_dev), t / n_dev)
                 for n, (c, t) in modules.items()},
        collective_exposed_s=exposed / n_dev, breakdown=breakdown)


def _host_doing(spans: List[Interval], s: float, e: float) -> str:
    """The harness span that covers most of [s, e), or 'host.other'."""
    best, name = 0.0, "host.other"
    for hs, he, n in spans:
        c = min(e, he) - max(s, hs)
        if c > best:
            best, name = c, n
    return name

"""The program's own spans (``repro.core.telemetry``) on the device trace's
clock, and what the pipe and feeder spans say about a train window.

The program times its spans on ``time.monotonic``; the profiler's
``.xplane.pb`` has a clock of its own, a fixed and unrelated offset away.
``dump`` writes the spans into the trace directory beside the monotonic
reading taken as the window's annotation (``bench.window``) was entered;
``load`` places them on the trace's clock by the one offset between that
reading and the annotation's start there.  Everything else computes from
plain intervals, so it can be checked on a synthetic trace:

* ``pipe_quantities``: the five pipe and feeder quantities of a window,
  each from spans clipped to it, None where its spans are absent;
* ``name_gaps``: the device's idle gaps in the window, each named by the
  harness span that covers most of it and, where one covers it, the
  program work span that covers most of it
  (``bench.wait_batch / export.fill``), with the share of idle time that
  program work spans cover;
* ``first_step_lag_s``: from the end of the window's first ``feeder.get``
  to the start of the first step program on the device.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import tracing

FILE = "program_spans.json"

#: spans in which the program works; every other span (``*.wait*``,
#: ``*.rendezvous``, ``feeder.get``, the whole-pipe spans) waits or contains
WORK = ("export.fill", "export.encode", "export.send", "import.decode",
        "feeder.rows", "feeder.batch")
DATA_FRAMES = ("B", "P", "T")               # block, parts and text frames


@dataclass
class Span:
    start: float
    end: float
    name: str
    tid: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)


def from_telemetry(spans: Iterable[Any]) -> List[Span]:
    """``telemetry.Span`` objects, on their own (monotonic) clock."""
    return [Span(s.t0, s.t1, s.name, s.tid, dict(s.attrs or {}))
            for s in spans]


def dump(trace_dir: str, spans: Iterable[Any], anchor_s: float) -> str:
    """Write ``spans`` (``telemetry.Span``) as a Chrome trace, with the
    monotonic reading taken as ``bench.window`` was entered."""
    from repro.core import telemetry

    doc = telemetry.chrome_trace(spans)
    doc["otherData"] = {"monotonic_anchor_s": anchor_s}
    path = os.path.join(trace_dir, FILE)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def load(trace_dir: str, window_start: float) -> List[Span]:
    """The spans ``dump`` wrote, on the trace's clock, where the window's
    annotation starts at ``window_start``."""
    with open(os.path.join(trace_dir, FILE)) as f:
        doc = json.load(f)
    offset = window_start - doc["otherData"]["monotonic_anchor_s"]
    return [Span(ev["ts"] * 1e-6 + offset,
                 (ev["ts"] + ev["dur"]) * 1e-6 + offset, ev["name"],
                 ev.get("tid", 0), ev.get("args") or {})
            for ev in doc["traceEvents"]]


def window(trace: tracing.Trace) -> Tuple[float, float]:
    wins = [(s, e) for s, e, n in trace.host if n == tracing.WINDOW]
    if not wins:
        raise ValueError(f"no {tracing.WINDOW} span in the trace")
    return min(s for s, _ in wins), max(e for _, e in wins)


def _clipped(spans: Sequence[Span], name: str, lo: float, hi: float):
    """(clipped seconds, share of the span inside, span) of each ``name``
    span that overlaps [lo, hi]."""
    out = []
    for s in spans:
        if s.name == name and s.end > lo and s.start < hi:
            inside = min(s.end, hi) - max(s.start, lo)
            whole = s.end - s.start
            out.append((inside, inside / whole if whole > 0 else 1.0, s))
    return out


def _total(spans, names, lo, hi) -> Optional[float]:
    parts = [c for n in names for c in _clipped(spans, n, lo, hi)]
    return sum(c[0] for c in parts) if parts else None


def pipe_quantities(spans: Sequence[Span], lo: float, hi: float,
                    consumer_tid: Optional[int] = None
                    ) -> Dict[str, Optional[float]]:
    """Seconds, each clipped to the window [lo, hi]:

    * ``first_frame_s``: from the start of the window's first
      ``export.rendezvous`` to the end of the first ``export.send`` of a
      data frame after it;
    * ``source_s``: ``export.fill`` less the ``write_s`` spent parsing in
      it: the exporting engine's own time;
    * ``export_s``: ``write_s`` + ``export.encode`` + ``export.send``:
      PipeGen's export work;
    * ``unpack_s``: ``import.decode`` + ``feeder.rows`` + ``feeder.batch``;
    * ``input_wait_s``: ``feeder.get`` on the consumer's thread (every
      thread where ``consumer_tid`` is None);

    and under ``parts`` the terms of the sums: ``write_s``, ``encode_s``,
    ``send_s``, ``decode_s``, ``pivot_s`` (``feeder.rows``), ``batch_s``.
    ``write_s`` of a fill that lies partly outside counts in proportion."""
    rdv = sorted((s for s in spans if s.name == "export.rendezvous"
                  and lo <= s.start < hi), key=lambda s: s.start)
    first_frame = None
    if rdv:
        sends = [s.end for s in spans if s.name == "export.send"
                 and (s.attrs.get("kind") in DATA_FRAMES)
                 and s.start >= rdv[0].start and s.end <= hi]
        if sends:
            first_frame = min(sends) - rdv[0].start
    fills = _clipped(spans, "export.fill", lo, hi)
    write_s = sum(share * s.attrs.get("write_s", 0.0)
                  for _, share, s in fills)
    source = sum(c for c, _, _ in fills) - write_s if fills else None
    work = _total(spans, ("export.encode", "export.send"), lo, hi)
    gets = [s for s in spans if s.name == "feeder.get"
            and consumer_tid in (None, s.tid)]
    parts = {"write_s": write_s if fills else None}
    for key, name in (("encode_s", "export.encode"), ("send_s", "export.send"),
                      ("decode_s", "import.decode"), ("pivot_s", "feeder.rows"),
                      ("batch_s", "feeder.batch")):
        parts[key] = _total(spans, (name,), lo, hi)
    return {
        "first_frame_s": first_frame,
        "source_s": source,
        "export_s": None if work is None else write_s + work,
        "unpack_s": _total(spans, ("import.decode", "feeder.rows",
                                   "feeder.batch"), lo, hi),
        "input_wait_s": _total(gets, ("feeder.get",), lo, hi),
        "parts": parts,
    }


def _most(intervals: Iterable[Tuple[float, float, str]], s: float,
          e: float) -> Optional[str]:
    best, name = 0.0, None
    for a, b, n in intervals:
        c = min(e, b) - max(s, a)
        if c > best:
            best, name = c, n
    return name


def idle_gaps(trace: tracing.Trace, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Every device's intervals in [lo, hi] in which no op ran on it."""
    gaps = []
    for dev in trace.devices.values():
        busy = tracing.union([(max(s, lo), min(e, hi)) for s, e, _ in dev.ops
                              if e > lo and s < hi])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return gaps


def name_gaps(trace: tracing.Trace, spans: Sequence[Span], lo: float,
              hi: float, top: int = 10) -> Dict[str, Any]:
    """The ``top`` longest idle gaps as [name, seconds], named
    ``<harness span>`` or ``<harness span> / <program work span>``, and
    ``work_cover_share``: the share of all idle time (summed over devices)
    that program work spans cover."""
    host = [(s, e, n) for s, e, n in trace.host if n != tracing.WINDOW]
    work = [(s.start, s.end, s.name) for s in spans if s.name in WORK]
    work_union = tracing.union([(s, e) for s, e, _ in work])
    gaps = idle_gaps(trace, lo, hi)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        name = _most(host, s, e) or "host.other"
        prog = _most(work, s, e)
        named.append([f"{name} / {prog}" if prog else name, e - s])
    idle = sum(e - s for s, e in gaps)
    covered = sum(max(0.0, min(e, b) - max(s, a))
                  for s, e in gaps for a, b in work_union)
    return {"idle_gaps": named,
            "work_cover_share": covered / idle if idle > 0 else None}


def first_step_lag_s(trace: tracing.Trace, spans: Sequence[Span], lo: float,
                     hi: float, step: str = "step_fn") -> Optional[float]:
    """Seconds from the end of the window's first ``feeder.get`` that
    delivered a batch to the start of the first module named ``step`` on
    a device after it: how far the two clocks agree."""
    ends = [s.end for s in spans if s.name == "feeder.get"
            and s.attrs.get("batch") is not None and lo <= s.end < hi]
    if not ends:
        return None
    got = min(ends)
    starts = [s for dev in trace.devices.values() for s, _, n in dev.modules
              if step in n and lo <= s < hi]
    if not starts:
        return None
    return min(starts) - got

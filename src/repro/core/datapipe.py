"""Data pipes (paper sections 4 and 5): the streams IORedirect substitutes
for file streams when an engine imports/exports a *reserved filename*.

``DataPipeOutput`` stands in for a file opened for writing.  Depending on
the negotiated :class:`PipeConfig` it operates at one of the fig. 11 rungs:

    text        raw characters forwarded in T frames (IORedirect only)
    parts       AString typed parts, delimiters retained, binary primitives
    binary_rows delimiters removed, row-major custom binary
    tagged      protobuf-analog (static/dynamic templates; fig. 13)
    arrowrow    Arrow-analog row-major typed buffers
    arrowcol    Arrow-analog columnar pivot (full PipeGen; default)

``DataPipeInput`` is the matching read side.  Decorated importers consume
typed blocks (:meth:`DataPipeInput.blocks`) or AString lines with typed
parts (:meth:`astring_lines`); undecorated importers read rendered
characters via the ordinary file protocol (``read``/``readline``/iter),
reproducing the engine's original text byte-for-byte from the schema frame
metadata.

Reserved filenames follow the paper's ``db://<dataset>?workers=N&query=Q``
syntax (section 4.2); :func:`parse_reserved` also accepts the
``/tmp/__reserved__<dataset>`` template used for engines that reject custom
URI schemes (section 6.1).
"""

from __future__ import annotations

import io
import json
import queue
import re
import socket
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple
from urllib.parse import parse_qs, urlparse

from . import telemetry
from .astring import AString
from .compression import Codec, get_codec
from .directory import (DirectoryLike, Endpoint, LeaseRenewer,
                        get_directory)
from .telemetry import FlightRecorder, attach_flight
from .iobuf import BufferPool, DecodeArena, SegmentList, default_pool
from .shm_ring import (
    DEFAULT_RING_CAPACITY,
    ShmRing,
    ShmRingTransport,
    acquire_ring,
    attach_ring,
)
from .formopt import (
    DelimitedAssembler,
    FormOptError,
    JsonAssembler,
    render_delimited,
    render_json,
)
from .stream import (
    DEFAULT_STREAM_WINDOW,
    FaninTransport,
    StripedReceiver,
    StripedSender,
)
from .transport import (
    FRAME_BLOCK,
    FRAME_EOF,
    FRAME_PARTS,
    FRAME_RESUME,
    FRAME_SCHEMA,
    FRAME_TEXT,
    FRAME_VERIFY,
    Channel,
    ChannelTransport,
    LinkSim,
    SocketTransport,
    Transport,
    listen_socket,
)
from .types import ColumnBlock, RowBlock, Schema
from .wire import decode_schema, encode_schema, get_wire_format
from .wire.parts_rows import PartsRowsFormat

__all__ = [
    "PipeConfig",
    "ReservedName",
    "parse_reserved",
    "is_reserved",
    "DataPipeOutput",
    "DataPipeInput",
    "open_pipe_writer",
    "open_pipe_reader",
    "PipeStats",
    "collect_stats",
    "collect_stats_by_attempt",
    "clear_resume",
]

#: data-carrying frame kinds — the only kinds counted by the resume
#: watermark (schema/verify/resume/EOF are per-attempt control frames)
_DATA_FRAME_KINDS = (FRAME_TEXT, FRAME_PARTS, FRAME_BLOCK)

RESERVED_SCHEME = "db"
RESERVED_TEMPLATE = "/tmp/__reserved__"


@dataclass(frozen=True)
class ReservedName:
    dataset: str
    workers: Optional[int] = None
    query_id: str = "0"

    def __str__(self) -> str:  # pragma: no cover - debugging aid
        return f"db://{self.dataset}?workers={self.workers}&query={self.query_id}"


def parse_reserved(filename: str) -> Optional[ReservedName]:
    """Return the ReservedName if ``filename`` activates a data pipe."""
    filename = str(filename)
    if filename.startswith(f"{RESERVED_SCHEME}://"):
        u = urlparse(filename)
        qs = parse_qs(u.query)
        workers = int(qs["workers"][0]) if "workers" in qs else None
        query_id = qs.get("query", ["0"])[0]
        return ReservedName(u.netloc or u.path.lstrip("/"), workers, query_id)
    if filename.startswith(RESERVED_TEMPLATE):
        tail = filename[len(RESERVED_TEMPLATE):]
        m = re.match(r"([^?]+)(?:\?(.*))?$", tail)
        if not m:
            return None
        qs = parse_qs(m.group(2) or "")
        workers = int(qs["workers"][0]) if "workers" in qs else None
        query_id = qs.get("query", ["0"])[0]
        return ReservedName(m.group(1), workers, query_id)
    return None


def is_reserved(filename: str) -> bool:
    return parse_reserved(filename) is not None


@dataclass
class PipeConfig:
    """Negotiated pipe behaviour; travels in the schema frame meta.

    ``pipelined``/``scatter_gather``/``pool`` are exporter-local transport
    knobs (they do not travel in the meta): ``pipelined`` runs compression
    and the vectored send on a bounded sender thread so encoding block N+1
    overlaps the send of block N (the paper's producer/consumer overlap);
    ``scatter_gather`` disables the zero-copy path when False, falling back
    to the concatenate-then-send profile (kept for the fig. 11 seed-path
    comparison); ``pool`` supplies a dedicated buffer pool (default: the
    process-wide pool).

    ``transport``/``shm_capacity``/``decode_arena`` are importer-local: the
    importer picks the rendezvous flavor (it registers the endpoint, the way
    it owns the listening socket), the exporter connects to whatever kind
    the directory hands back.  ``transport`` is one of ``socket`` (TCP,
    default), ``channel`` (in-process queue) or ``shm`` (cross-process
    shared-memory ring, zero intermediate copies); ``decode_arena`` supplies
    a dedicated :class:`~repro.core.iobuf.DecodeArena` so decode pool stats
    attribute to one pipe (default: a per-pipe arena over the process-wide
    decode pool).  ``shm_doorbell`` (importer-local, on by default) gives
    the ring a fifo/eventfd doorbell so a blocked side wakes in
    microseconds; off (or on doorbell-less platforms) it falls back to the
    exponential-backoff poll.  ``broadcast`` (importer-local, shm only)
    joins this pipe as one of N readers of a *broadcast ring*: the
    exporter encodes and publishes every frame once and all N colocated
    importers consume it from the same segment (the planner sets this on
    fan-out edges it compiles onto one export).

    Stream-fabric knobs (``repro.core.stream`` / ``repro.core.fabric``):
    ``streams`` (importer-local) stripes each pipe across N member
    connections of the chosen transport flavor — the importer registers a
    multi-endpoint group, the exporter's frames spread round-robin over the
    members and reassemble in sequence order behind a ``stream_window``-
    frame reorder window with per-stream credits.  ``partition`` (exporter-
    local) turns the transfer into an N→M shuffle: every exporter worker
    routes rows to *all* import workers by key (``hash[:col]``,
    ``range[:col]``, ``rr``); ``partition_bounds`` presets the range
    split points (the planner's global compile-time quantiles, stamped
    into every exporter so they agree); ``fanin`` (importer-local, set by
    :func:`repro.core.session.transfer` / the planner) is the number of
    exporter streams each importer merges.  ``streams`` and ``partition``
    compose: with both set, each importer registers one private *slot*
    (a striped group of ``streams`` connections) per exporter, so every
    shuffle member pipe is itself striped."""

    mode: str = "arrowcol"  # text | parts | binary_rows | tagged | arrowrow | arrowcol
    codec: str = "none"  # none | rle | zip | zstd
    block_rows: int = 65536
    text_format: str = "csv"  # csv | json  (what the engine's serializer speaks)
    delimiter: Optional[str] = None  # inferred when None (section 5.3.1)
    verify_first_n: int = 0  # probabilistic runtime check (section 4.1)
    link: Optional[LinkSim] = None
    connect_timeout: float = 30.0
    pipelined: bool = True  # double-buffered sender thread
    scatter_gather: bool = True  # zero-copy vectored send
    sender_depth: int = 2  # bounded in-flight frames (double buffering)
    block_export: bool = True  # allow exporters to hand over whole blocks
    pool: Optional[BufferPool] = None
    transport: str = "socket"  # socket | channel | shm (importer-side)
    shm_capacity: int = DEFAULT_RING_CAPACITY  # ring data-region bytes
    shm_doorbell: bool = True  # fifo/eventfd wakeups (False = backoff poll)
    broadcast: int = 0  # shm fan-out: join as one of N broadcast readers
    decode_arena: Optional[DecodeArena] = None  # importer-side decode pool
    streams: int = 1  # stripe each pipe across N member connections
    stream_window: int = DEFAULT_STREAM_WINDOW  # reorder window (frames)
    partition: Optional[str] = None  # N→M shuffle: hash[:col]|range[:col]|rr
    partition_bounds: Optional[Tuple] = None  # preset global range bounds
    fanin: int = 1  # importer-side: exporter streams to merge (shuffle)
    # robustness knobs (set by the plan executor's retry policy).  ``resume``
    # names the process-global resume ledger for this edge: stable across
    # attempts, so a retried importer replays the data frames the previous
    # attempt already received and registers its acked watermark for the
    # exporter to skip to.  ``attempt`` is the retry epoch (0 = first try),
    # echoed in the RESUME hello.  ``lease_s`` > 0 makes the importer's
    # directory registration a leased one: a renewer thread re-stamps it
    # while the importer is alive, and an expired lease is GC'd like a dead
    # pid (crashed peers stop haunting the rendezvous).
    resume: Optional[str] = None  # resume-ledger token (edge-stable)
    attempt: int = 0  # retry epoch (0 = first try)
    lease_s: float = 0.0  # directory lease TTL (0 = unleased)
    # telemetry knobs (repro.core.telemetry).  ``trace`` opts this pipe
    # into span recording (enabling the process tracer if needed);
    # ``trace_ctx`` is the propagated "trace_id:span_id" parent context,
    # stamped by the plan executor so both ends of an edge join one
    # trace; ``flight_depth`` bounds the per-pipe flight-recorder ring;
    # ``recorder`` shares the executor's per-edge FlightRecorder so pipe
    # events land in the same timeline as admission/retry events.
    trace: bool = False  # record lifecycle spans for this pipe
    trace_ctx: str = ""  # propagated parent trace context
    flight_depth: int = 64  # flight-recorder ring depth (events)
    recorder: Optional["FlightRecorder"] = None  # shared edge recorder

    def meta(self) -> dict:
        return {
            "mode": self.mode,
            "codec": self.codec,
            "text_format": self.text_format,
            "delimiter": self.delimiter,
            "verify_first_n": self.verify_first_n,
        }


@dataclass
class PipeStats:
    bytes_sent: int = 0
    frames_sent: int = 0
    rows: int = 0
    blocks: int = 0
    copies_avoided: int = 0   # segments shipped as views of live memory
    pool_hits: int = 0        # buffer acquires served without allocating
    pool_misses: int = 0
    send_overlap_s: float = 0.0  # sender-thread work hidden behind encoding
    decode_pool_hits: int = 0    # importer: arena stores served from retention
    decode_pool_misses: int = 0
    shm_spans: int = 0           # frames carried as in-place shm ring spans
    # shm ring wait attribution: how blocked sides woke up.  A doorbell
    # regression (back to polling) shows up as poll_sleeps > 0 here.
    doorbell_waits: int = 0      # waits resolved by a doorbell wakeup
    spin_wakeups: int = 0        # waits resolved during the brief spin
    poll_sleeps: int = 0         # backoff-poll sleeps (fallback path only)
    # resumable edges: how much of a retried transfer was NOT re-moved.
    # The exporter skips re-encoded frames the importer already acked
    # (resume_skipped); the importer replays its staged prefix locally
    # (resume_replayed).  Both zero on first attempts and non-resumed runs.
    resume_skipped: int = 0      # exporter: data frames dropped at the cut
    resume_replayed: int = 0     # importer: staged frames served locally
    # striped pipes: one dict per member stream ({stream, bytes, frames, ...});
    # merged views concatenate, so a shuffle's M members each contribute theirs
    per_stream: List[dict] = field(default_factory=list)

    _SUMMED = ("bytes_sent", "frames_sent", "rows", "blocks",
               "copies_avoided", "pool_hits", "pool_misses",
               "send_overlap_s", "decode_pool_hits", "decode_pool_misses",
               "shm_spans", "doorbell_waits", "spin_wakeups", "poll_sleeps",
               "resume_skipped", "resume_replayed")

    def merge(self, other: "PipeStats") -> "PipeStats":
        """Fold ``other`` into this view (counters sum, per-stream
        breakdowns concatenate).  Returns self, so
        ``PipeStats().merge(a).merge(b)`` builds an aggregate."""
        for name in self._SUMMED:
            setattr(self, name, getattr(self, name) + getattr(other, name))
        self.per_stream = self.per_stream + list(other.per_stream)
        return self


# -- per-transfer stats sink ---------------------------------------------------
# Pipes are opened deep inside engine code, so the session layer cannot reach
# them directly; closing pipes fold their PipeStats in here under the
# (dataset, query_id) of their reserved name — keyed *per attempt* inside
# the entry, so a failed attempt's counters and its successful retry's
# counters stay distinguishable — and
# :func:`repro.core.session.transfer` collects the merged views into the
# TransferResult.  Bounded so an uncollected benchmark loop cannot grow it.

_SINK_MAX = 256
_sink_lock = threading.Lock()
# (dataset, query_id) -> {role: {attempt: PipeStats}}
_stats_sink: "dict[Tuple[str, str], dict]" = {}


def _record_stats(rn: ReservedName, role: str, stats: "PipeStats",
                  attempt: int = 0) -> None:
    with _sink_lock:
        key = (rn.dataset, rn.query_id)
        if key not in _stats_sink and len(_stats_sink) >= _SINK_MAX:
            _stats_sink.pop(next(iter(_stats_sink)))
        roles = _stats_sink.setdefault(key, {})
        attempts = roles.setdefault(role, {})
        agg = attempts.setdefault(attempt, PipeStats())
        agg.merge(stats)
    reg = telemetry.registry()
    reg.counter("pipe.closes", role=role).inc()
    reg.counter("pipe.bytes", role=role).inc(stats.bytes_sent)
    reg.counter("pipe.frames", role=role).inc(stats.frames_sent)
    reg.counter("pipe.rows", role=role).inc(stats.rows)
    if stats.resume_skipped:
        reg.counter("pipe.resume_skipped").inc(stats.resume_skipped)
    if stats.resume_replayed:
        reg.counter("pipe.resume_replayed").inc(stats.resume_replayed)
    if stats.poll_sleeps:
        reg.counter("shm.poll_sleeps").inc(stats.poll_sleeps)
    if stats.doorbell_waits:
        reg.counter("shm.doorbell_waits").inc(stats.doorbell_waits)


def collect_stats(dataset: str, query_id: str = "0") -> "dict[str, PipeStats]":
    """Pop the merged per-role (``export``/``import``) stats for one
    transfer — aggregated across workers, shuffle members, streams, *and*
    attempts (the folded view; :func:`collect_stats_by_attempt` peeks the
    per-attempt breakdown before this folds it)."""
    with _sink_lock:
        roles = _stats_sink.pop((dataset, query_id), {})
    out: "dict[str, PipeStats]" = {}
    for role, attempts in roles.items():
        agg = PipeStats()
        for k in sorted(attempts):
            agg.merge(attempts[k])
        out[role] = agg
    return out


def collect_stats_by_attempt(
        dataset: str, query_id: str = "0") -> "dict[str, dict]":
    """Non-destructive per-attempt view: ``{role: {attempt: PipeStats}}``.
    Unlike :func:`collect_stats` this does not pop the entry, so both
    views of one transfer are available."""
    with _sink_lock:
        roles = _stats_sink.get((dataset, query_id), {})
        return {role: dict(attempts) for role, attempts in roles.items()}


# -- resume ledgers ------------------------------------------------------------
# A resumable edge stages every *fully received* data frame (decompressed
# payload bytes) under its ledger token.  A retry attempt opens a fresh
# importer against the same token: the staged prefix replays locally, the
# new registration carries ``resume_seq = len(staged)`` as the acked
# watermark, and the exporter's RESUME hello says where it restarts so any
# overlap (exporter behind the watermark) is deduped by count.  The plan
# executor owns the token lifecycle and clears it once the edge settles.

class _ResumeLedger:
    __slots__ = ("staged", "lock")

    def __init__(self) -> None:
        self.staged: List[Tuple[bytes, bytes]] = []  # (kind, payload)
        self.lock = threading.Lock()


_resume_lock = threading.Lock()
_RESUME_LEDGERS: "dict[str, _ResumeLedger]" = {}


def _resume_ledger(token: str) -> _ResumeLedger:
    with _resume_lock:
        led = _RESUME_LEDGERS.get(token)
        if led is None:
            led = _RESUME_LEDGERS[token] = _ResumeLedger()
        return led


def clear_resume(token: str) -> None:
    """Drop the staged frames of one edge (call when the edge settles —
    success or final failure — so the ledger cannot leak across plans)."""
    with _resume_lock:
        _RESUME_LEDGERS.pop(token, None)


class _PoolHandle:
    """Per-pipe view of a (possibly shared) BufferPool: delegates acquires
    and counts this pipe's own hits/misses exactly, so PipeStats are not
    polluted by concurrent pipes sharing the process-wide pool."""

    __slots__ = ("pool", "hits", "misses")

    def __init__(self, pool: BufferPool):
        self.pool = pool
        self.hits = 0
        self.misses = 0

    def acquire(self, nbytes: int):
        buf = self.pool.acquire(nbytes)
        if buf.was_hit:
            self.hits += 1
        else:
            self.misses += 1
        return buf


class _PipelinedSender:
    """Bounded sender thread: compress + vectored send of frame N overlap
    the encoding of frame N+1 (double buffering via ``depth``).

    Error contract: a failure in compress/send is latched; subsequent
    submissions drain (releasing pooled buffers) so the producer never
    blocks on a dead pipe, and the error is re-raised on :meth:`submit`
    or, at the latest, :meth:`close` -- the reader is unblocked by the
    owner closing the transport."""

    _DONE = object()

    def __init__(self, transport: Transport, codec: Codec, depth: int = 2):
        self._transport = transport
        self._codec = codec
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self.busy_s = 0.0   # sender-thread time spent compressing/sending
        self.wait_s = 0.0   # producer time blocked on the bounded queue
        # (start, end) spans, each list appended in time order by one
        # thread: busy by the sender, blocked by the producer.  overlap_s
        # intersects them, so sender work done while the producer ran free
        # (including the post-final-submit drain) counts exactly once.
        self._busy_iv: List[Tuple[float, float]] = []
        self._blocked_iv: List[Tuple[float, float]] = []
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="pipegen-sender", daemon=True
        )
        self._thread.start()

    def submit(self, kind: bytes, segs: SegmentList, compress: bool = True) -> None:
        if self.error is not None:
            raise self.error
        try:
            self._q.put_nowait((kind, segs, compress))
        except queue.Full:
            # only genuine backpressure counts as wait (an uncontended put
            # costs microseconds and would drown the overlap signal)
            t0 = time.perf_counter()
            self._q.put((kind, segs, compress))
            t1 = time.perf_counter()
            self.wait_s += t1 - t0
            self._blocked_iv.append((t0, t1))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is self._DONE:
                return
            kind, segs, compress = item
            if self.error is not None:
                segs.release()  # drain so the producer never blocks
                continue
            t0 = time.perf_counter()
            try:
                if compress:
                    segs = self._codec.compress_segments(segs)
                self._transport.send_frames(kind, segs)
            except BaseException as e:  # noqa: BLE001 - latched, re-raised
                self.error = e
            finally:
                segs.release()  # recycle pooled stores on success AND error
                t1 = time.perf_counter()
                self.busy_s += t1 - t0
                self._busy_iv.append((t0, t1))

    def close(self) -> None:
        """Drain, join, and surface any latched send error."""
        self._q.put(self._DONE)
        self._thread.join()
        if self.error is not None:
            raise self.error

    @property
    def overlap_s(self) -> float:
        """Sender work hidden behind the producer: total busy time minus
        the part spent while the producer sat blocked on the bounded
        queue.  Interval intersection (not ``busy - wait``): a blocked
        put also covers sender scheduling latency, which is not sender
        work, and would otherwise cancel genuine overlap down to 0."""
        busy = 0.0
        inter = 0.0
        j = 0
        blocked = self._blocked_iv
        for a, b in self._busy_iv:
            busy += b - a
            while j < len(blocked) and blocked[j][1] <= a:
                j += 1
            k = j
            while k < len(blocked) and blocked[k][0] < b:
                inter += min(b, blocked[k][1]) - max(a, blocked[k][0])
                k += 1
        return max(0.0, busy - inter)


class _PhaseSpans:
    """A traced pipe end's phase spans: recorded as each phase ends, under
    the trace ``_trace_id`` and the whole-pipe span ``_pipe_sid``."""

    _trace_id: Optional[str]
    _pipe_sid: str

    def _record(self, name: str, t0: float,
                attrs: Optional[Dict[str, Any]] = None,
                t1: Optional[float] = None) -> None:
        """Record a phase span, ending now unless ``t1`` is given."""
        tr = telemetry.tracer()
        if tr is not None:
            tr.record(name, t0, time.monotonic() if t1 is None else t1,
                      trace_id=self._trace_id, parent_id=self._pipe_sid,
                      attrs=attrs)


class DataPipeOutput(_PhaseSpans):
    """File-like write end of a data pipe (subtype-substitutable for the
    engines' text writers, per fig. 5)."""

    def __init__(
        self,
        filename: str,
        config: Optional[PipeConfig] = None,
        directory: Optional[DirectoryLike] = None,
        endpoint: Optional[Endpoint] = None,
    ):
        rn = parse_reserved(filename)
        if rn is None:
            raise ValueError(f"{filename!r} is not a reserved pipe name")
        self.reserved = rn
        self.config = config or PipeConfig()
        self.stats = PipeStats()
        self.closed = False
        self._verify_rows: List[tuple] = []
        # telemetry: the trace context is final once the rendezvous is
        # done (explicit config ctx beats the importer's registration ctx
        # beats a fresh root), so both ends of the edge land in one trace
        # no matter which side originated it, and every phase span is
        # recorded as it ends.  The flight recorder notes lifecycle events
        # for postmortem attachment (shared with the executor's edge
        # recorder when the plan passes one in).
        if self.config.trace and not telemetry.tracing_enabled():
            telemetry.enable_tracing()
        self._trace_on = self.config.trace or telemetry.tracing_enabled()
        self._trace_ctx = self.config.trace_ctx or telemetry.current_ctx()
        self._t_open = time.monotonic()
        # the block being filled: when its first row was written, and the
        # seconds spent parsing in write() since (traced pipes only)
        self._fill_t0: Optional[float] = None
        self._fill_write_s = 0.0
        self._recorder = self.config.recorder or FlightRecorder(
            self.config.flight_depth, name=f"export {rn.dataset}")
        self._recorder.note("export.open", dataset=rn.dataset,
                            query=rn.query_id, attempt=self.config.attempt)
        # validate codec/format before any rendezvous so a bad config fails
        # fast instead of leaving a half-registered peer behind
        self._codec: Codec = get_codec(self.config.codec)
        self._wire = (
            get_wire_format(self.config.mode)
            if self.config.mode not in ("text", "parts", "bytes")
            else None
        )
        directory = directory or get_directory()
        _t_rdv = time.monotonic()
        if endpoint is None:
            endpoint = directory.query(
                rn.dataset,
                rn.query_id,
                export_workers=rn.workers,
                timeout=self.config.connect_timeout,
            )
        if not self._trace_ctx:
            # adopt the importer's registration context, if it traced
            self._trace_ctx = getattr(endpoint, "trace", "") or ""
        if endpoint.is_group:
            # the importer striped its pipe: connect every member (in
            # registration order -- the importer accepts in the same order)
            # and spread frames across them (repro.core.stream)
            members = [_connect(m, self.config.link) for m in endpoint.members]
            self._transport: Transport = StripedSender(members)
        else:
            self._transport = _connect(endpoint, self.config.link)
        if self._trace_on:
            if not self._trace_ctx:
                self._trace_ctx = telemetry.new_trace_ctx()
            self._trace_id, self._trace_parent = telemetry.split_ctx(
                self._trace_ctx)
            # the span id the whole-pipe span will be recorded under at
            # close: the parent of every phase span, and carried in the
            # schema hello so importer spans parent to this exporter when
            # the trace originates here
            self._pipe_sid = telemetry.new_span_id()
            self._record("export.rendezvous", _t_rdv)
        else:
            self._pipe_sid = ""
        self._recorder.note("export.connected")
        # resumable edge: the importer's registration carries the acked
        # watermark from the previous attempt; this export skips its first
        # ``resume_seq`` data frames at the _send funnel (mode-agnostic —
        # the engine re-produces the stream, the cut point is exact) and
        # announces the restart position in a RESUME hello after the schema
        self._resume_token: Optional[str] = None
        self._resume_from = 0
        self._resume_skip_left = 0
        if (self.config.resume is not None and not endpoint.is_group
                and getattr(endpoint, "broadcast", 0) <= 1):
            self._resume_token = self.config.resume
            self._resume_from = int(getattr(endpoint, "resume_seq", 0) or 0)
            self._resume_skip_left = self._resume_from
        self._pool = _PoolHandle(self.config.pool or default_pool())
        self._sender: Optional[_PipelinedSender] = None
        if self.config.pipelined:
            self._sender = _PipelinedSender(
                self._transport, self._codec, self.config.sender_depth
            )
        self._parts_wire = PartsRowsFormat()
        self._text_buf: List[str] = []
        self._text_len = 0
        self._part_rows: List[List[Any]] = []
        self._cur_parts: List[Any] = []
        if self.config.text_format == "json":
            self._asm: Any = JsonAssembler()
        else:
            self._asm = DelimitedAssembler()
            if self.config.delimiter is not None:
                self._asm.delimiter = self.config.delimiter
                self._asm._sampling = False
        self._schema_sent = False
        self._schema: Optional[Schema] = None
        self._byte_buf: List[bytes] = []
        self._byte_len = 0
        if self.config.mode in ("text", "bytes"):
            # schema frame still opens the stream so the reader can negotiate
            self._send_schema(Schema([]))

    # -- file protocol ---------------------------------------------------------
    def write(self, s: Any) -> int:
        if self.closed:
            raise ValueError("write to closed data pipe")
        if self.config.mode == "bytes":
            b = s if isinstance(s, (bytes, bytearray, memoryview)) else str(s).encode("latin-1")
            self._byte_buf.append(bytes(b))
            self._byte_len += len(b)
            if self._byte_len >= 1 << 20:
                self._flush_bytes()
            return len(b)
        if self.config.mode == "text":
            text = str(s)
            self._text_buf.append(text)
            self._text_len += len(text)
            if self._text_len >= 1 << 20:
                self._flush_text()
            return len(text)
        if self.config.mode == "parts":
            self._write_parts(s)
            return _cheap_len(s)
        if self._trace_on:
            t0 = time.monotonic()
            if self._fill_t0 is None:
                self._fill_t0 = t0
            self._parse(s)
            self._fill_write_s += time.monotonic() - t0
        else:
            self._parse(s)
        self._maybe_flush_rows()
        return _cheap_len(s)

    def _parse(self, s: Any) -> None:
        self._asm.write(s if isinstance(s, (AString, str)) else str(s))
        if isinstance(self._asm, JsonAssembler) and len(self._asm._parts) >= 1 << 16:
            self._asm.flush()  # retains any incomplete trailing document

    def writelines(self, lines: Sequence[Any]) -> None:
        for l in lines:
            self.write(l)

    def flush(self) -> None:
        if self.config.mode == "text":
            self._flush_text()
        elif self.config.mode == "bytes":
            self._flush_bytes()

    def close(self) -> None:
        if self.closed:
            return
        sender_err: Optional[BaseException] = None
        try:
            if self.config.mode == "text":
                self._flush_text()
            elif self.config.mode == "bytes":
                self._flush_bytes()
            elif self.config.mode == "parts":
                self._flush_parts(final=True)
            else:
                self._flush_rows(final=True)
            self._send(FRAME_EOF, SegmentList([b""]), compress=False)
        finally:
            self.closed = True
            if self._sender is not None:
                try:
                    self._sender.close()
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    sender_err = e
                self.stats.send_overlap_s = self._sender.overlap_s
            # always close the transport -- a sender failure must not leave
            # the reader blocked on a half-open stream.  Close *before*
            # reading the counters: a striped sender only finishes sending
            # (drains its member queues) inside close().
            try:
                self._transport.close()
            except BaseException as e:  # noqa: BLE001 - re-raised below
                sender_err = sender_err or e
            self.stats.bytes_sent = self._transport.bytes_sent
            self.stats.frames_sent = self._transport.frames_sent
            self.stats.pool_hits = self._pool.hits
            self.stats.pool_misses = self._pool.misses
            self.stats.shm_spans = getattr(self._transport, "shm_spans", 0)
            self.stats.doorbell_waits = getattr(
                self._transport, "doorbell_waits", 0)
            self.stats.spin_wakeups = getattr(
                self._transport, "spin_wakeups", 0)
            self.stats.poll_sleeps = getattr(
                self._transport, "poll_sleeps", 0)
            per_stream = getattr(self._transport, "per_stream", None)
            if per_stream is not None:
                self.stats.per_stream = per_stream()
            _record_stats(self.reserved, "export", self.stats,
                          attempt=self.config.attempt)
            self._recorder.note(
                "export.close", bytes=self.stats.bytes_sent,
                frames=self.stats.frames_sent,
                error=type(sender_err).__name__ if sender_err else None)
            self._emit_spans()
        if sender_err is not None:
            raise attach_flight(sender_err, self._recorder)

    def _emit_spans(self) -> None:
        """Record the whole-pipe span, the parent of the phase spans
        already recorded under its pre-allocated id."""
        tr = telemetry.tracer()
        if not self._trace_on or tr is None:
            return
        rn = self.reserved
        tr.record(
            "export.pipe", self._t_open, time.monotonic(),
            trace_id=self._trace_id, parent_id=self._trace_parent,
            span_id=self._pipe_sid,
            attrs={"dataset": rn.dataset, "query": rn.query_id,
                   "attempt": self.config.attempt, "mode": self.config.mode,
                   "bytes": self.stats.bytes_sent,
                   "frames": self.stats.frames_sent,
                   "rows": self.stats.rows})

    def __enter__(self) -> "DataPipeOutput":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- frame egress (all rungs funnel through here) ------------------------------
    def _send(self, kind: bytes, segs: SegmentList, compress: bool = True) -> None:
        if self._trace_on:
            t0 = time.monotonic()
            try:
                return self._send_impl(kind, segs, compress)
            finally:
                self._record("export.send", t0,
                             {"kind": kind.decode("ascii", "replace")})
        return self._send_impl(kind, segs, compress)

    def _send_impl(self, kind: bytes, segs: SegmentList,
                   compress: bool = True) -> None:
        """Route one frame out: codec at the segment level (data frames
        only -- schema/verify/EOF travel uncompressed), then either the
        double-buffered sender thread (pipelined) or an inline vectored
        send.  ``scatter_gather=False`` re-materializes the payload first,
        reproducing the seed path's concatenate-then-send copy profile."""
        if self._resume_skip_left and kind in _DATA_FRAME_KINDS:
            # the importer acked this frame on a previous attempt
            self._resume_skip_left -= 1
            self.stats.resume_skipped += 1
            segs.release()
            return
        if not self.config.scatter_gather:
            payload = segs.join()
            segs.release()
            segs = SegmentList([payload])
        self.stats.copies_avoided += segs.copies_avoided
        if self._sender is not None:
            self._sender.submit(kind, segs, compress)
            return
        if compress:
            segs = self._codec.compress_segments(segs)
        self._transport.send_frames(kind, segs)
        segs.release()

    # -- text rung ---------------------------------------------------------------
    def _flush_text(self) -> None:
        if not self._text_buf:
            return
        payload = "".join(self._text_buf).encode("utf-8", "surrogatepass")
        self._text_buf, self._text_len = [], 0
        self._send(FRAME_TEXT, SegmentList([payload]))

    # -- bytes rung (shared-binary-format passthrough, e.g. seqfiles) --------------
    def _flush_bytes(self) -> None:
        if not self._byte_buf:
            return
        payload = b"".join(self._byte_buf)
        self._byte_buf, self._byte_len = [], 0
        self._send(FRAME_TEXT, SegmentList([payload]))

    # -- parts rung (binary primitives, delimiters retained) ----------------------
    def _write_parts(self, s: Any) -> None:
        parts = s.parts if isinstance(s, AString) else (str(s),)
        for p in parts:
            if isinstance(p, str) and p.endswith("\n"):
                if p[:-1]:
                    self._cur_parts.append(p[:-1])
                self._part_rows.append(self._cur_parts)
                self._cur_parts = []
            else:
                self._cur_parts.append(p)
        if len(self._part_rows) >= self.config.block_rows:
            self._flush_parts()

    def _flush_parts(self, final: bool = False) -> None:
        if final and self._cur_parts:
            self._part_rows.append(self._cur_parts)
            self._cur_parts = []
        if not self._part_rows:
            return
        if not self._schema_sent:
            self._send_schema(Schema([]))
        segs = self._parts_wire.encode_parts(self._part_rows, pool=self._pool)
        self.stats.rows += len(self._part_rows)
        self._part_rows = []
        self._send(FRAME_PARTS, segs)
        self.stats.blocks += 1

    # -- typed-rows rungs ----------------------------------------------------------
    def _maybe_flush_rows(self) -> None:
        if len(self._asm.rows) >= self.config.block_rows:
            self._flush_rows()

    def _flush_rows(self, final: bool = False) -> None:
        if self._trace_on:
            t_flush = time.monotonic()
        if final:
            try:
                self._asm.flush()
            except FormOptError:
                pass  # trailing partial row: nothing further to emit
        if not self._asm.rows:
            return
        rb: RowBlock = self._asm.take_rows()
        if self._schema is None:
            self._schema = rb.schema
            self._send_schema(rb.schema)
        elif rb.schema.types != self._schema.types:
            # a write_block already fixed the stream schema; text rows of a
            # different shape would decode against the wrong layout
            raise ValueError(
                f"serialized rows schema {rb.schema!r} does not match the "
                f"stream schema {self._schema!r} already negotiated"
            )
        block = rb.to_columns()  # section 5.4 pivot
        if self.config.verify_first_n and len(self._verify_rows) < self.config.verify_first_n:
            take = self.config.verify_first_n - len(self._verify_rows)
            self._verify_rows.extend(rb.rows[:take])
            self._send_verify(RowBlock(rb.schema, rb.rows[:take]))
        segs = self._wire.encode_block(block, pool=self._pool)
        if self._trace_on:
            self._end_fill(t_flush, len(block))
        self._send(FRAME_BLOCK, segs)
        self.stats.rows += len(block)
        self.stats.blocks += 1

    def _end_fill(self, t_flush: float, rows: int) -> None:
        """The block filled since ``_fill_t0`` was flushed at ``t_flush``
        and is now encoded: record both phases (the schema and verify
        frames of a stream's first block are sent inside its encode)."""
        if self._fill_t0 is not None:
            self._record("export.fill", self._fill_t0,
                         {"rows": rows, "write_s": self._fill_write_s},
                         t1=t_flush)
            self._fill_t0, self._fill_write_s = None, 0.0
        self._record("export.encode", t_flush, {"rows": rows})

    # -- typed block fast path (decorated exporters, fig. 11 'full PipeGen') ------
    def accepts_blocks(self) -> bool:
        """True when whole ColumnBlocks can bypass the text serializer."""
        return (
            self.config.block_export
            and self.config.mode not in ("text", "parts", "bytes")
            and not self.closed
        )

    def write_block(
        self,
        block: ColumnBlock,
        header: Optional[Sequence[str]] = None,
        delimiter: Optional[str] = None,
    ) -> int:
        """Export one typed ColumnBlock directly -- the exporter-side twin
        of the importer's block fast path: no text rendering, no AString
        assembly, no row pivot.  ``header``/``delimiter`` feed the schema
        frame meta so undecorated importers can still regenerate the text
        dialect byte-for-byte.

        Zero-copy ownership contract: fixed-width columns go on the wire
        as views of ``block``'s live numpy buffers, and with
        ``pipelined=True`` the send completes asynchronously -- the caller
        must not mutate the block's columns until :meth:`close` returns
        (engines hand over stored, immutable blocks, so this holds by
        construction on every generated-adapter path)."""
        if self.closed:
            raise ValueError("write to closed data pipe")
        if not self.accepts_blocks():
            raise ValueError(
                f"mode {self.config.mode!r} cannot carry typed blocks"
            )
        self._flush_rows()  # keep ordering with any interleaved text writes
        if self._schema is not None and block.schema.types != self._schema.types:
            # the stream schema traveled once, up front; a block with
            # different column types would be decoded against the wrong
            # layout on the reader (silent corruption at same width)
            raise ValueError(
                f"write_block schema {block.schema!r} does not match the "
                f"stream schema {self._schema!r} already negotiated"
            )
        if self._schema is None:
            self._schema = block.schema
            if delimiter is not None and isinstance(self._asm, DelimitedAssembler):
                self._asm.delimiter = delimiter
                self._asm._sampling = False
            self._send_schema(block.schema, header_names=header)
        n = len(block)
        rows_per_sub = self.config.block_rows
        nstreams = getattr(self._transport, "nstreams", 1)
        if nstreams > 1 and n:
            # striped pipe: a one-shot bulk export must still produce at
            # least one frame per member stream, or the stripes sit idle
            rows_per_sub = min(rows_per_sub, max(1, -(-n // nstreams)))
        for lo in range(0, n, rows_per_sub):
            sub = (
                block
                if n <= rows_per_sub
                else ColumnBlock(
                    block.schema,
                    [c[lo : lo + rows_per_sub] for c in block.columns],
                )
            )
            if (
                self.config.verify_first_n
                and len(self._verify_rows) < self.config.verify_first_n
            ):
                rb = sub.to_rows()
                take = self.config.verify_first_n - len(self._verify_rows)
                self._verify_rows.extend(rb.rows[:take])
                self._send_verify(RowBlock(rb.schema, rb.rows[:take]))
            if self._trace_on:
                t0 = time.monotonic()
                segs = self._wire.encode_block(sub, pool=self._pool)
                self._record("export.encode", t0, {"rows": len(sub)})
            else:
                segs = self._wire.encode_block(sub, pool=self._pool)
            self._send(FRAME_BLOCK, segs)
            self.stats.rows += len(sub)
            self.stats.blocks += 1
        return n

    def _send_schema(
        self, schema: Schema, header_names: Optional[Sequence[str]] = None
    ) -> None:
        meta = self.config.meta()
        if self._trace_on and self._trace_ctx:
            # cross-process propagation: the importer adopts this trace
            # and parents its spans under the exporter's pipe span
            tid, _ = telemetry.split_ctx(self._trace_ctx)
            meta["trace"] = f"{tid}:{self._pipe_sid}"
        if isinstance(self._asm, DelimitedAssembler) and self._asm.delimiter:
            meta["delimiter"] = self._asm.delimiter
        if header_names:
            meta["header"] = list(header_names)
        elif getattr(self._asm, "header_names", None):
            meta["header"] = list(self._asm.header_names)
        self._send(FRAME_SCHEMA, SegmentList([encode_schema(schema, meta)]),
                   compress=False)
        if self._resume_token is not None:
            hello = json.dumps({"epoch": self.config.attempt,
                                "from": self._resume_from}).encode("utf-8")
            self._send(FRAME_RESUME, SegmentList([hello]), compress=False)
            self._recorder.note("export.resume_hello",
                                epoch=self.config.attempt,
                                skip=self._resume_from)
        self._schema_sent = True
        self._recorder.note("export.schema")

    def _send_verify(self, rb: RowBlock) -> None:
        """Probabilistic runtime check: ship the original text rendering of
        the first n rows so the importer can compare (section 4.1)."""
        if self._resume_from:
            # resumed attempt: the verify region was checked (and staged)
            # before the crash; re-sent expectations would misalign against
            # the post-watermark blocks actually on the wire
            return
        if self.config.text_format == "json":
            text = render_json(rb)
        else:
            text = render_delimited(rb, self._asm.delimiter or ",")
        self._send(FRAME_VERIFY, SegmentList([text.encode("utf-8")]),
                   compress=False)


class DataPipeInput(_PhaseSpans):
    """File-like read end of a data pipe.

    Decorated importers use :meth:`blocks` (typed ColumnBlocks, zero text) or
    :meth:`astring_lines` (AStrings with typed parts).  Undecorated importers
    read characters; we regenerate them from blocks + schema-frame metadata.

    Both protocols consume from a *single* decoded-block queue, so a
    header-probing client may ``read`` a few characters, :meth:`unread` them
    (bounded rewind, one block deep — the HDFS sequence-file sniff of
    section 6.1), and then switch to the typed protocol without losing data.
    """

    def __init__(
        self,
        filename: str,
        directory: Optional[DirectoryLike] = None,
        link: Optional[LinkSim] = None,
        host: str = "127.0.0.1",
        channel: Optional[Channel] = None,
        import_workers: Optional[int] = None,
        transport: Optional[str] = None,
        shm_capacity: int = DEFAULT_RING_CAPACITY,
        shm_doorbell: bool = True,
        broadcast: int = 0,
        arena: Optional[DecodeArena] = None,
        streams: int = 1,
        fanin: int = 1,
        stream_window: int = DEFAULT_STREAM_WINDOW,
        resume: Optional[str] = None,
        attempt: int = 0,
        lease_s: float = 0.0,
        connect_timeout: float = 30.0,
        trace: bool = False,
        trace_ctx: str = "",
        flight_depth: int = 64,
        recorder: Optional[FlightRecorder] = None,
    ):
        rn = parse_reserved(filename)
        if rn is None:
            raise ValueError(f"{filename!r} is not a reserved pipe name")
        self.reserved = rn
        self._attempt = attempt
        if trace and not telemetry.tracing_enabled():
            telemetry.enable_tracing()
        self._trace_on = trace or telemetry.tracing_enabled()
        self._trace_ctx = trace_ctx or telemetry.current_ctx()
        self._t_open = time.monotonic()
        # phase spans parent to the whole-pipe span, recorded at close
        # under this id; the trace id is known once the schema hello has
        # been read (``_resolve_trace``)
        self._pipe_sid = telemetry.new_span_id() if self._trace_on else ""
        self._trace_id: Optional[str] = None
        self._recorder = recorder or FlightRecorder(
            flight_depth, name=f"import {rn.dataset}")
        self._recorder.note("import.open", dataset=rn.dataset,
                            query=rn.query_id, transport=transport,
                            attempt=attempt)
        # registration context: what we publish in the directory so an
        # exporter with no context of its own joins *our* trace
        self._reg_ctx = ""
        if self._trace_on:
            self._reg_ctx = self._trace_ctx or telemetry.new_trace_ctx()
        directory = directory or get_directory()
        self._connect_timeout = float(connect_timeout) or 30.0
        if transport is None:
            transport = "channel" if channel is not None else "socket"
        if transport not in ("socket", "channel", "shm"):
            raise ValueError(
                f"unknown transport {transport!r}; have socket/channel/shm")
        workers = import_workers or rn.workers
        if broadcast > 1 and (transport != "shm" or fanin > 1 or streams > 1):
            raise ValueError(
                "broadcast pipes require transport='shm' with streams=1 "
                "and fanin=1 (one ring, one writer, N reader cursors)")
        # resumable edge (plain single-stream pipes only: stripes, shuffles
        # and broadcast rings have per-member frame orders a single frame
        # watermark cannot describe): stage received data frames under the
        # ledger token and register the acked watermark for the exporter
        self._ledger: Optional[_ResumeLedger] = None
        self._replay_idx = 0
        self._resume_base = 0
        self._resume_skip = 0
        if (resume is not None and fanin == 1 and streams == 1
                and broadcast <= 1):
            self._ledger = _resume_ledger(resume)
            self._resume_base = len(self._ledger.staged)
        _reg_kw: dict = {"lease_s": lease_s} if lease_s else {}
        _res_kw: dict = (
            {"resume_seq": self._resume_base, "resume_epoch": attempt}
            if self._ledger is not None else {})
        if self._reg_ctx:
            _res_kw["trace"] = self._reg_ctx
        _t_rdv = time.monotonic()
        if fanin > 1:
            self._transport: Transport = self._rendezvous_fanin(
                rn, directory, transport, fanin, host, link, workers,
                streams=streams, window=stream_window,
                shm_capacity=shm_capacity, shm_doorbell=shm_doorbell)
        elif streams > 1:
            self._transport = self._rendezvous_striped(
                rn, directory, transport, streams, stream_window,
                host, link, shm_capacity, workers, shm_doorbell)
        elif transport == "channel":
            ch = channel if channel is not None else Channel()
            directory.register(
                rn.dataset, Endpoint(channel=ch, **_res_kw), rn.query_id,
                import_workers=workers, **_reg_kw,
            )
            self._transport = ChannelTransport(ch, link)
        elif transport == "shm" and broadcast > 1:
            self._transport = self._rendezvous_broadcast(
                rn, directory, broadcast, shm_capacity, shm_doorbell,
                link, workers)
        elif transport == "shm":
            ring = acquire_ring(shm_capacity, doorbell=shm_doorbell)
            directory.register(
                rn.dataset,
                Endpoint(shm_name=ring.name, shm_capacity=ring.capacity,
                         **_res_kw),
                rn.query_id,
                import_workers=workers, **_reg_kw,
            )
            self._transport = ShmRingTransport(ring, link)
        else:
            lsock = listen_socket(host)
            h, p = lsock.getsockname()
            directory.register(
                rn.dataset, Endpoint(h, p, **_res_kw), rn.query_id,
                import_workers=workers, **_reg_kw,
            )
            lsock.settimeout(60.0)
            conn, _ = lsock.accept()
            lsock.close()
            self._transport = SocketTransport(conn, link)
        self._t_rdv = (_t_rdv, time.monotonic())
        self._recorder.note("import.connected")
        if getattr(directory, "degraded", False):
            # the rendezvous went through the directory client's local
            # fallback: the broker is down and both ends of this pipe
            # must live in this process for the exporter to find us
            self._recorder.note("import.degraded_rendezvous",
                                dataset=rn.dataset, query=rn.query_id)
            telemetry.counter("pipe.degraded_rendezvous").inc()
        # leased registration: keep re-stamping the directory entry while
        # this importer is alive; if it dies (thread or process), renewals
        # stop and the lease expires into the directory's dead-peer GC.
        # The heartbeat is an owned LeaseRenewer joined in close() — its
        # lifetime is the *handle's*, not any single transfer's, so the
        # same machinery serves long-lived subscription rings.
        self._renewer: Optional[LeaseRenewer] = None
        self._lease_lost = threading.Event()
        self._lease_msg = (
            f"directory lease lost for {rn.dataset!r} (query "
            f"{rn.query_id!r}): the registration expired and was GC'd "
            f"before the exporter arrived — re-register (retried attempts "
            f"do this automatically)")
        renew = getattr(directory, "renew", None)
        if lease_s and renew is not None:

            def _on_lost(rn=rn):
                # renew's documented 0: the lease expired and the
                # registration was GC'd.  Heartbeating a nonexistent
                # entry forever (while the exporter can never find us)
                # helps nobody — mark the pipe lease-lost, kick any wait
                # parked in the ring, and let the executor's retry path
                # re-register under a fresh attempt.
                self._recorder.note("import.lease_lost",
                                    dataset=rn.dataset, query=rn.query_id)
                self._lease_lost.set()
                ring = getattr(self._transport, "ring", None)
                if ring is not None:
                    ring.abort(self._lease_msg)

            self._renewer = LeaseRenewer(
                lambda ls, fn=renew, rn=rn: fn(rn.dataset, rn.query_id,
                                               lease_s=ls),
                lease_s, on_lost=_on_lost).start()
        self._arena = arena or DecodeArena()
        self.stats = PipeStats()
        self.schema: Optional[Schema] = None
        self.meta: dict = {}
        self._codec: Codec = get_codec("none")
        self._eof = False
        self._started = False
        self._verify_expected: List[str] = []
        self.verify_failures: List[str] = []
        # unified consumption state
        self._raw_tail = ""          # text rung: undelivered raw characters
        self._raw_chunks: List[bytes] = []  # bytes rung (binary passthrough)
        self._head_block: Optional[ColumnBlock] = None
        self._head_astrs: Optional[List[AString]] = None  # parts-mode head frame
        self._head_text: Optional[str] = None  # head block rendered (memoized)
        self._head_off = 0           # chars of head text consumed by read()
        self._header_pending = False  # header line not yet delivered as text

    # -- fabric rendezvous -------------------------------------------------------
    @staticmethod
    def _rendezvous_broadcast(rn, directory, readers, shm_capacity,
                              shm_doorbell, link, workers) -> Transport:
        """Join the transfer's broadcast ring as one of ``readers``
        cursors.  The directory hands out slot indexes: slot 0 creates
        the ring (it owns the segment, like every shm importer) and
        publishes its endpoint — which also registers it for the single
        exporter's ``query`` — and slots 1..R-1 attach to it."""
        slot, ep = directory.join_broadcast(
            rn.dataset, rn.query_id, readers=readers)
        if ep is None:  # first joiner: create (or re-lease warm) + publish
            from .shm_ring import acquire_broadcast_ring

            ring = acquire_broadcast_ring(shm_capacity, readers,
                                          doorbell=shm_doorbell)
            directory.publish_broadcast(
                rn.dataset,
                Endpoint(shm_name=ring.name, shm_capacity=ring.capacity,
                         broadcast=readers, shared=True),
                rn.query_id,
                import_workers=workers,
            )
        else:
            ring = ShmRing.attach(ep.shm_name, role="reader", slot=slot)
        return ShmRingTransport(ring, link)

    @staticmethod
    def _rendezvous_striped(rn, directory, transport, streams, window,
                            host, link, shm_capacity, workers,
                            shm_doorbell: bool = True) -> Transport:
        """Register one multi-endpoint group and reassemble N member
        connections into one ordered stream (repro.core.stream)."""
        if transport == "channel":
            chans = [Channel() for _ in range(streams)]
            members = tuple(Endpoint(channel=c) for c in chans)
            directory.register(rn.dataset, Endpoint(members=members),
                               rn.query_id, import_workers=workers)
            parts: List[Transport] = [ChannelTransport(c, link) for c in chans]
        elif transport == "shm":
            rings = [acquire_ring(shm_capacity, doorbell=shm_doorbell)
                     for _ in range(streams)]
            members = tuple(
                Endpoint(shm_name=r.name, shm_capacity=r.capacity)
                for r in rings)
            directory.register(rn.dataset, Endpoint(members=members),
                               rn.query_id, import_workers=workers)
            parts = [ShmRingTransport(r, link) for r in rings]
        else:
            lsocks = [listen_socket(host) for _ in range(streams)]
            members = tuple(
                Endpoint(*ls.getsockname()) for ls in lsocks)
            directory.register(rn.dataset, Endpoint(members=members),
                               rn.query_id, import_workers=workers)
            parts = []
            # the exporter (or the stub path) connects to the members in
            # registration order, so sequential accepts pair up correctly;
            # the listen backlog absorbs any out-of-order connects
            for ls in lsocks:
                ls.settimeout(60.0)
                conn, _ = ls.accept()
                ls.close()
                parts.append(SocketTransport(conn, link))
        return StripedReceiver(parts, window=window)

    @staticmethod
    def _rendezvous_fanin(rn, directory, transport, fanin, host, link,
                          workers, streams: int = 1,
                          window: int = DEFAULT_STREAM_WINDOW,
                          shm_capacity: int = DEFAULT_RING_CAPACITY,
                          shm_doorbell: bool = True,
                          ) -> Transport:
        """Register the shuffle's import-side rendezvous and merge
        ``fanin`` exporter streams.

        Two wirings:

        * **shared** (``streams == 1`` over socket/channel): one listening
          socket every exporter connects to (or one multi-producer
          channel), merged by :class:`FaninTransport` — the paper-shaped
          minimal rendezvous;
        * **slotted** (``streams > 1``, or the single-producer shm ring):
          one *private* rendezvous slot per exporter — a striped group of
          ``streams`` member connections (or a single connection) —
          registered as a ``shared`` group endpoint whose members the
          exporters claim by index via
          :meth:`WorkerDirectory.next_sender`.  Each slot reassembles
          through its own :class:`StripedReceiver`, then the slots merge
          through :class:`FaninTransport` — this is how ``streams`` and
          ``partition`` compose on one pipe.
        """
        if streams <= 1 and transport != "shm":
            if transport == "channel":
                ch = Channel(maxsize=64 * max(1, fanin))
                directory.register(
                    rn.dataset, Endpoint(channel=ch, shared=True),
                    rn.query_id, import_workers=workers,
                )
                # one shared multi-producer queue: exporters must not close
                # it under each other (Endpoint.shared), termination is
                # counted from the explicit EOF frames
                return FaninTransport([ChannelTransport(ch, link)],
                                      expected_sources=fanin)
            lsock = listen_socket(host)
            h, p = lsock.getsockname()
            directory.register(
                rn.dataset, Endpoint(h, p, shared=True), rn.query_id,
                import_workers=workers,
            )
            lsock.settimeout(60.0)
            conns: List[Transport] = []
            try:
                for _ in range(fanin):
                    conn, _ = lsock.accept()
                    conns.append(SocketTransport(conn, link))
            finally:
                lsock.close()
            return FaninTransport(conns)
        # slotted wiring: everything is registered before anything blocks,
        # so the exporters' query_all returns only once every importer
        # published its full slot table
        slot_eps: List[Endpoint] = []
        slot_parts: List[List[Transport]] = []
        slot_socks: List[List[socket.socket]] = []
        for _ in range(fanin):
            if transport == "channel":
                chans = [Channel() for _ in range(streams)]
                mems = tuple(Endpoint(channel=c) for c in chans)
                slot_parts.append([ChannelTransport(c, link) for c in chans])
                slot_socks.append([])
            elif transport == "shm":
                rings = [acquire_ring(shm_capacity, doorbell=shm_doorbell)
                         for _ in range(streams)]
                mems = tuple(
                    Endpoint(shm_name=r.name, shm_capacity=r.capacity)
                    for r in rings)
                slot_parts.append([ShmRingTransport(r, link) for r in rings])
                slot_socks.append([])
            else:
                lsocks = [listen_socket(host) for _ in range(streams)]
                mems = tuple(Endpoint(*ls.getsockname()) for ls in lsocks)
                slot_parts.append([])
                slot_socks.append(lsocks)
            slot_eps.append(mems[0] if streams == 1
                            else Endpoint(members=mems))
        directory.register(
            rn.dataset, Endpoint(members=tuple(slot_eps), shared=True),
            rn.query_id, import_workers=workers,
        )
        for parts, lsocks in zip(slot_parts, slot_socks):
            for ls in lsocks:
                ls.settimeout(60.0)
                conn, _ = ls.accept()
                ls.close()
                parts.append(SocketTransport(conn, link))
        slot_tr: List[Transport] = [
            StripedReceiver(parts, window=window) if streams > 1
            else parts[0]
            for parts in slot_parts
        ]
        return FaninTransport(slot_tr, expected_sources=fanin)

    # -- negotiation -------------------------------------------------------------
    def _check_lease(self) -> None:
        if self._lease_lost.is_set():
            raise attach_flight(BrokenPipeError(self._lease_msg),
                                self._recorder)

    def _start(self) -> None:
        if self._started:
            return
        self._check_lease()
        t0 = time.monotonic()
        if isinstance(self._transport, ShmRingTransport):
            # the handshake is not done until the schema frame lands: an
            # exporter that died at (or never reached) rendezvous would
            # otherwise park this importer on the ring forever — a shm
            # ring with no writer yet attached cannot distinguish "slow"
            # from "never coming" (socket importers get the same bound
            # from their accept/read timeouts)
            try:
                kind, payload = self._transport.recv_frame(
                    timeout=self._connect_timeout)
            except TimeoutError:
                raise attach_flight(TimeoutError(
                    f"no exporter wrote to {self.reserved.dataset!r} "
                    f"(query {self.reserved.query_id!r}) within "
                    f"{self._connect_timeout:g}s of rendezvous — it died "
                    f"or abandoned the attempt"), self._recorder) from None
        else:
            kind, payload = self._transport.recv_frame()
        t1 = time.monotonic()
        if kind == FRAME_EOF:
            self._eof = True  # stub socket: orphaned importer (section 4.2)
            self._started = True
            self._recorder.note("import.orphaned_eof")
            if self._trace_on:
                self._resolve_trace((t0, t1))
            return
        if kind != FRAME_SCHEMA:
            raise IOError(f"pipe stream must begin with schema frame, got {kind!r}")
        self.schema, self.meta = decode_schema(payload)
        self._recorder.note("import.schema", mode=self.meta.get("mode"))
        if not self._trace_ctx and self.meta.get("trace"):
            # adopt the exporter's trace from the hello: our spans parent
            # under its pipe span, landing both ends in one trace
            self._trace_ctx = str(self.meta["trace"])
        if self._trace_on:
            self._resolve_trace((t0, t1))
        self._codec = get_codec(self.meta.get("codec", "none"))
        mode = self.meta.get("mode", "arrowcol")
        self._wire = (
            get_wire_format(mode) if mode not in ("text", "parts", "bytes") else None
        )
        self._parts_wire = PartsRowsFormat()
        self._header_pending = bool(self.meta.get("header"))
        self._started = True

    @property
    def mode(self) -> str:
        self._start()
        return self.meta.get("mode", "arrowcol")

    # -- frame pump (all protocols drain through here) -----------------------------
    def _recv_data_frame(self) -> Optional[Tuple[bytes, bytes]]:
        """Next (kind, decompressed payload) data frame, or None at EOF.
        VERIFY frames are absorbed into the expected-text buffer.  On a
        resumable edge the staged prefix (frames a previous attempt fully
        received) replays first — no wire reads — then wire frames are
        deduped against the watermark and staged as they arrive."""
        led = self._ledger
        if led is not None and self._replay_idx < len(led.staged):
            kind, data = led.staged[self._replay_idx]
            self._replay_idx += 1
            self.stats.resume_replayed += 1
            return kind, data
        while not self._eof:
            self._check_lease()
            if self._trace_on:
                t0 = time.monotonic()
                kind, payload = self._transport.recv_frame()
                self._record("import.wait", t0, {
                    "kind": bytes(kind).decode("ascii", "replace")})
            else:
                kind, payload = self._transport.recv_frame()
            if kind == FRAME_EOF:
                self._eof = True
                return None
            if kind == FRAME_RESUME:
                # exporter hello: it restarts at `from`; frames between
                # that and our staged watermark arrive twice — drop them
                doc = json.loads(bytes(payload).decode("utf-8"))
                self._resume_skip = max(
                    0, self._resume_base - int(doc.get("from", 0)))
                self._recorder.note("import.resume_hello",
                                    epoch=doc.get("epoch"),
                                    dup_skip=self._resume_skip)
                continue
            if kind == FRAME_VERIFY:
                if self._resume_base:
                    continue  # verified (and staged) before the crash
                self._verify_expected.extend(payload.decode("utf-8").splitlines())
                continue
            data = self._codec.decompress(payload)
            if led is not None:
                if self._resume_skip:
                    self._resume_skip -= 1
                    continue  # duplicate of a staged frame
                # copy: shm payloads are live ring spans consumed by the
                # next recv, and a staged frame must outlive this attempt
                with led.lock:
                    led.staged.append((kind, bytes(data)))
                self._replay_idx = len(led.staged)
            return kind, data
        return None

    def _next_block(self) -> Optional[ColumnBlock]:
        """Decode the next typed block (non-text modes)."""
        frame = self._recv_data_frame()
        if frame is None:
            return None
        kind, data = frame
        t0 = time.monotonic() if self._trace_on else 0.0
        try:
            if kind == FRAME_BLOCK:
                block = self._wire.decode_block(data, self.schema,
                                                arena=self._arena)
                self._check_verify(block)
                return block
            if kind == FRAME_PARTS:
                return self._parts_to_block(data)
            if kind == FRAME_TEXT:
                return self._text_to_block(
                    data.decode("utf-8", "surrogatepass"))
            raise IOError(f"unexpected frame kind {kind!r}")  # pragma: no cover
        finally:
            if self._trace_on:
                self._record("import.decode", t0)

    # -- typed fast path -----------------------------------------------------------
    def blocks(self) -> Iterator[ColumnBlock]:
        """Yield typed ColumnBlocks (the PipeGen fast path)."""
        self._start()
        if self.mode == "text":
            # text rung: raw characters; parse per line-batch (drain any
            # characters a header probe already pulled into the raw tail)
            tail, self._raw_tail = self._raw_tail, ""
            while True:
                cut = tail.rfind("\n")
                if cut >= 0:
                    blk = self._text_to_block(tail[: cut + 1])
                    tail = tail[cut + 1:]
                    if len(blk):
                        yield blk
                frame = self._recv_data_frame()
                if frame is None:
                    if tail:
                        blk = self._text_to_block(tail)
                        if len(blk):
                            yield blk
                    return
                tail += frame[1].decode("utf-8", "surrogatepass")
        # serve the (possibly partially peeked) head frame first
        head = self._take_head_typed()
        if head is not None:
            yield head
        while True:
            blk = self._next_block()
            if blk is None:
                return
            yield blk

    def astring_lines(self) -> Iterator[AString]:
        """Yield one AString per row with typed parts + delimiters restored,
        for decorated importers (AString.parse_* skips character parsing)."""
        self._start()
        mode = self.mode
        if mode == "text":
            # raw characters: one single-part AString per line (the importer
            # parses characters exactly as it would from a file); drain any
            # characters a header probe already pulled into the raw tail
            tail, self._raw_tail = self._raw_tail, ""
            while True:
                lines = tail.split("\n")
                tail = lines.pop()
                for line in lines:
                    yield AString((line,))
                frame = self._recv_data_frame()
                if frame is None:
                    if tail:
                        yield AString((tail,))
                    return
                tail += frame[1].decode("utf-8", "surrogatepass")
        if mode == "parts":
            head = self._take_head_astrs()
            if head is not None:
                for astr in head:
                    yield astr
            while True:
                frame = self._recv_data_frame()
                if frame is None:
                    return
                for astr in self._parts_wire.decode_parts(frame[1]):
                    yield astr
            return
        d = self.meta.get("delimiter") or ","
        hdr = self.meta.get("header")
        if hdr and self._header_pending:
            self._header_pending = False
            parts: List[Any] = []
            for j, nm in enumerate(hdr):
                if j:
                    parts.append(d)
                parts.append(nm)
            yield AString(parts)
        for block in self.blocks():
            rb = block.to_rows()
            for row in rb.rows:
                parts = []
                for j, v in enumerate(row):
                    if j:
                        parts.append(d)
                    parts.append(v)
                yield AString(parts)

    # -- character protocol ----------------------------------------------------------
    def _render(self, rb: RowBlock) -> str:
        if self.meta.get("text_format") == "json":
            return render_json(rb)
        return render_delimited(rb, self.meta.get("delimiter") or ",")

    def _take_head_typed(self) -> Optional[ColumnBlock]:
        """Pop the peeked head frame as a typed block (None if no head)."""
        if self._head_block is None and self._head_astrs is None:
            return None
        if self._head_off:
            raise IOError(
                "typed read after unbalanced character peek "
                f"({self._head_off} chars consumed)"
            )
        if self._head_block is not None:
            blk, self._head_block, self._head_text = self._head_block, None, None
            return blk
        astrs, self._head_astrs, self._head_text = self._head_astrs, None, None
        return self._astrs_to_block(astrs)

    def _take_head_astrs(self) -> Optional[List[AString]]:
        """Pop the peeked head frame as AStrings (parts mode)."""
        if self._head_astrs is None:
            return None
        if self._head_off:
            raise IOError(
                "typed read after unbalanced character peek "
                f"({self._head_off} chars consumed)"
            )
        astrs, self._head_astrs, self._head_text = self._head_astrs, None, None
        return astrs

    def _pop_head(self) -> None:
        self._head_block = None
        self._head_astrs = None
        self._head_text = None
        self._head_off = 0

    def _ensure_head_text(self) -> Optional[str]:
        """Rendered text of the current head frame (fetch one if needed)."""
        if self.mode == "text":
            raise AssertionError("_ensure_head_text is for typed modes")
        if self.mode == "parts":
            if self._head_astrs is None:
                frame = self._recv_data_frame()
                if frame is None:
                    return None
                self._head_astrs = list(self._parts_wire.decode_parts(frame[1]))
                self._head_text = None
            if self._head_text is None:
                self._head_text = "".join(
                    str(a) + "\n" for a in self._head_astrs
                )
            return self._head_text
        if self._head_block is None:
            self._head_block = self._next_block()
            self._head_text = None
            if self._head_block is None:
                return None
        if self._head_text is None:
            text = self._render(self._head_block.to_rows())
            if self._header_pending:
                hdr = self.meta.get("header")
                d = self.meta.get("delimiter") or ","
                text = d.join(hdr) + "\n" + text
                self._header_pending = False
            self._head_text = text
        return self._head_text

    def _pump_raw(self) -> bool:
        """Text/bytes rung: pull one frame of raw characters into the tail."""
        frame = self._recv_data_frame()
        if frame is None:
            return False
        enc = "latin-1" if self.mode == "bytes" else "utf-8"
        self._raw_tail += frame[1].decode(enc, "surrogatepass")
        return True

    def read(self, size: int = -1) -> str:
        self._start()
        if self.mode in ("text", "bytes"):
            while (size < 0 or len(self._raw_tail) < size) and self._pump_raw():
                pass
            if size < 0:
                s, self._raw_tail = self._raw_tail, ""
                return s
            s, self._raw_tail = self._raw_tail[:size], self._raw_tail[size:]
            return s
        out: List[str] = []
        got = 0
        while size < 0 or got < size:
            text = self._ensure_head_text()
            if text is None:
                break
            avail = text[self._head_off:]
            if size >= 0 and got + len(avail) > size:
                take = size - got
                out.append(avail[:take])
                self._head_off += take
                got += take
                break
            out.append(avail)
            got += len(avail)
            self._pop_head()
        return "".join(out)

    def unread(self, text: str) -> None:
        """Bounded pushback for header-probing clients (section 6.1: the
        HDFS client's read/rewind to sniff sequence-file magic).  Rewind is
        limited to characters consumed from the current head block."""
        if self.mode in ("text", "bytes"):
            self._raw_tail = text + self._raw_tail
            return
        if len(text) > self._head_off:
            raise IOError(
                f"unread({len(text)} chars) exceeds bounded rewind "
                f"({self._head_off} available)"
            )
        self._head_off -= len(text)

    def readline(self) -> str:
        self._start()
        if self.mode in ("text", "bytes"):
            while "\n" not in self._raw_tail:
                if not self._pump_raw():
                    s, self._raw_tail = self._raw_tail, ""
                    return s
            i = self._raw_tail.index("\n") + 1
            s, self._raw_tail = self._raw_tail[:i], self._raw_tail[i:]
            return s
        out: List[str] = []
        while True:
            text = self._ensure_head_text()
            if text is None:
                return "".join(out)
            nl = text.find("\n", self._head_off)
            if nl >= 0:
                out.append(text[self._head_off: nl + 1])
                self._head_off = nl + 1
                if self._head_off >= len(text):
                    self._pop_head()
                return "".join(out)
            out.append(text[self._head_off:])
            self._pop_head()

    def read_bytes(self, size: int = -1) -> bytes:
        """Binary passthrough (shared-binary-format pipes, e.g. seqfiles)."""
        self._start()
        buf = self._raw_tail.encode("latin-1", "surrogatepass") + b"".join(self._raw_chunks)
        self._raw_tail = ""
        self._raw_chunks = []
        while size < 0 or len(buf) < size:
            frame = self._recv_data_frame()
            if frame is None:
                break
            buf += frame[1]
        if size >= 0 and len(buf) > size:
            self._raw_chunks = [buf[size:]]
            buf = buf[:size]
        return buf

    def __iter__(self) -> Iterator[str]:
        while True:
            line = self.readline()
            if not line:
                return
            yield line

    def close(self) -> None:
        if self._renewer is not None:
            # join, don't fire-and-forget: a renewer outliving its pipe
            # would keep heartbeating a dead registration (the leak the
            # live_renewers() assertion in the tests guards against)
            self._renewer.stop(join=True)
        self.stats.decode_pool_hits = self._arena.hits
        self.stats.decode_pool_misses = self._arena.misses
        self.stats.shm_spans = getattr(self._transport, "shm_spans", 0)
        self.stats.doorbell_waits = getattr(
            self._transport, "doorbell_waits", 0)
        self.stats.spin_wakeups = getattr(self._transport, "spin_wakeups", 0)
        self.stats.poll_sleeps = getattr(self._transport, "poll_sleeps", 0)
        per_stream = getattr(self._transport, "per_stream", None)
        if per_stream is not None:
            self.stats.per_stream = per_stream()
        _record_stats(self.reserved, "import", self.stats,
                      attempt=self._attempt)
        self._recorder.note("import.close",
                            replayed=self.stats.resume_replayed,
                            rows=self.stats.rows)
        self._emit_spans()
        self._transport.close()

    def _resolve_trace(
            self, wait_schema: Optional[Tuple[float, float]] = None) -> None:
        """Fix the trace context (hello > own > registration) and record
        the phases timed before it was known."""
        self._trace_id, self._trace_parent = telemetry.split_ctx(
            self._trace_ctx or self._reg_ctx)
        self._record("import.rendezvous", self._t_rdv[0], t1=self._t_rdv[1])
        if wait_schema is not None:
            self._record("import.wait_schema", wait_schema[0],
                         t1=wait_schema[1])

    def _emit_spans(self) -> None:
        """Record the whole-pipe span, the parent of the phase spans
        already recorded under its pre-allocated id."""
        tr = telemetry.tracer()
        if not self._trace_on or tr is None:
            return
        if self._trace_id is None:
            self._resolve_trace()  # closed before the schema hello
        rn = self.reserved
        tr.record(
            "import.pipe", self._t_open, time.monotonic(),
            trace_id=self._trace_id, parent_id=self._trace_parent,
            span_id=self._pipe_sid,
            attrs={"dataset": rn.dataset, "query": rn.query_id,
                   "attempt": self._attempt,
                   "mode": self.meta.get("mode"),
                   "rows": self.stats.rows,
                   "replayed": self.stats.resume_replayed})

    def __enter__(self) -> "DataPipeInput":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- helpers ---------------------------------------------------------------------
    def _parts_to_block(self, data: bytes) -> ColumnBlock:
        return self._astrs_to_block(self._parts_wire.decode_parts(data))

    def _astrs_to_block(self, astrs) -> ColumnBlock:
        asm = DelimitedAssembler(sample_rows=8)
        if self.meta.get("delimiter"):
            asm.delimiter = self.meta["delimiter"]
            asm._sampling = False
        for astr in astrs:
            asm.write(astr)
            asm.write(AString(("\n",)))
        asm.flush()
        return asm.take_rows().to_columns(arena=self._arena)

    _TEXT_DELIMS = (",", "\t", ";", "|")

    def _text_to_block(self, text: str) -> ColumnBlock:
        """Text rung (IORedirect only): the payload is raw characters, so
        parse it the way the receiving engine would — split lines, sniff the
        delimiter, keep cells as strings (the importer re-parses types)."""
        lines = [l for l in text.split("\n") if l != ""]
        if not lines:
            return ColumnBlock(Schema([]), [])
        d = self.meta.get("delimiter")
        if not d:
            for cand in self._TEXT_DELIMS:
                widths = {l.count(cand) for l in lines}
                if len(widths) == 1 and widths.pop() > 0:
                    d = cand
                    break
            d = d or ","
        rows = [tuple(l.split(d)) for l in lines]
        width = max(len(r) for r in rows)
        from .types import Field, ColType
        schema = Schema([Field(f"column{i+1}", ColType.STRING) for i in range(width)])
        rows = [r + ("",) * (width - len(r)) for r in rows]
        return RowBlock(schema, rows).to_columns()

    def _check_verify(self, block: ColumnBlock) -> None:
        if not self._verify_expected:
            return
        rb = block.to_rows()
        n = min(len(self._verify_expected), len(rb.rows))
        got = self._render(RowBlock(rb.schema, rb.rows[:n])).splitlines()
        for want, have in zip(self._verify_expected[:n], got):
            if want != have:
                self.verify_failures.append(f"want {want!r} got {have!r}")
        del self._verify_expected[:n]
        if self.verify_failures:
            raise IOError(
                "data pipe verification failed: " + "; ".join(self.verify_failures)
            )


def _cheap_len(s: Any) -> int:
    """File-protocol return value without materializing the AString (the
    write() return is the number of characters a file would have taken;
    engines ignore it, so a cheap proxy suffices)."""
    if isinstance(s, AString):
        return len(s.parts)
    return len(s) if isinstance(s, str) else 1


def _connect(ep: Endpoint, link: Optional[LinkSim]) -> Transport:
    if ep.is_channel:
        # a shared channel (shuffle fan-in) is torn down by EOF counting,
        # not by any single finishing exporter
        return ChannelTransport(ep.channel, link, owns_channel=not ep.shared)
    if ep.is_shm:
        if ep.broadcast > 1:
            # broadcast ring: the single writer of an R-reader fan-out
            # (never cached — the slot table is single-use)
            return ShmRingTransport(
                ShmRing.attach(ep.shm_name, role="writer"), link)
        return ShmRingTransport(attach_ring(ep.shm_name), link)
    s = socket.create_connection((ep.host, ep.port), timeout=30.0)
    return SocketTransport(s, link)


# -- convenience API (used by engines' generated adapters) ------------------------

def open_pipe_writer(filename: str, config: Optional[PipeConfig] = None, **kw) -> DataPipeOutput:
    return DataPipeOutput(filename, config=config, **kw)


def open_pipe_reader(filename: str, **kw) -> DataPipeInput:
    return DataPipeInput(filename, **kw)

"""Declarative transfer plans: plan → compile → execute.

The paper's usage model is one A→B table move configured by hand; hybrid
analytics is chains and fan-outs across many systems.  This module splits
the user surface into three layers (the intermediate-layer argument):

* **TransferPlan** — a declarative builder for a multi-edge DAG::

      plan().move(src, "t", dst, "t2").options(partition="hash:key",
                                               streams=2)
            .then(dst, "t2", third, "t3")

  ``move`` adds an independent edge (two moves out of the same table are
  a fan-out and run concurrently), ``then`` chains the next edge after
  the previous one, ``options`` refines the last-added edge.

* **The planner** (``TransferPlan.compile``) — resolves every edge to a
  fully-specified :class:`EdgePlan` *before any data moves*: wire mode
  via the FormOpt ladder with a process-wide negotiation cache (the
  lower rung of the two engines wins), transport/streams/partition
  validation, worker pairing and shuffle fan-in, and — for range
  partitions — *global* bounds sampled once from the source relation's
  quantiles and stamped into every exporter's config, so N exporters
  agree on the split.  Dependencies are inferred from data flow (an edge
  reading a table another edge produces waits for it; an edge
  overwriting a table an earlier edge reads waits for the read), checked
  for duplicate targets and cycles, and grouped into stages of
  independent edges.

* **The executor** (``CompiledPlan.execute``) — runs each stage's edges
  concurrently over the shared worker directory, aggregates the per-edge
  :class:`~repro.core.session.TransferResult` into a :class:`PlanResult`,
  and surfaces *all* peer failures (export and import side) instead of
  the first one, chaining secondaries as ``__context__``.

``CompiledPlan.explain()`` renders the per-edge decisions for inspection
(dry-run); ``describe()`` returns them as dicts for programmatic use.
:func:`repro.core.session.transfer` and ``transfer_via_files`` are thin
back-compat shims over a one-edge plan.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field, fields as dc_fields, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import telemetry
from .broker import QOS_CLASSES, get_broker
from .codegen import PipeEnabledEngine
from .datapipe import PipeConfig, collect_stats, collect_stats_by_attempt
from .directory import DirectoryLike, set_directory
from .fabric import compute_range_bounds, parse_partition
from .ioredirect import PipeOpenContext
from .telemetry import FlightRecorder, attach_flight

__all__ = [
    "plan",
    "TransferPlan",
    "CompiledPlan",
    "EdgePlan",
    "PlanResult",
    "PlanError",
    "PlanExecutionError",
    "SubscriptionSet",
    "negotiated_config",
    "chain_exceptions",
]


class PlanError(ValueError):
    """A plan failed validation at build/compile time (nothing moved)."""


class PlanExecutionError(RuntimeError):
    """One or more edges failed; ``.result`` holds the partial PlanResult."""

    def __init__(self, message: str, result: "PlanResult"):
        super().__init__(message)
        self.result = result


# -- negotiation cache ---------------------------------------------------------
# The FormOpt ladder (session.negotiate_pipe_mode) runs the engine's own
# round-trip tests per rung -- expensive, and its outcome is a property of
# the engine class, so one process-wide probe per engine is enough.

_negotiation_lock = threading.Lock()
_negotiation_cache: Dict[str, PipeConfig] = {}


def negotiated_config(engine: Any) -> PipeConfig:
    """The engine's negotiated :class:`PipeConfig` (FormOpt ladder,
    most-optimized rung that validates), cached process-wide per engine
    name.  Returns a copy — callers mutate freely."""
    key = engine.name
    with _negotiation_lock:
        cfg = _negotiation_cache.get(key)
    if cfg is None:
        from .session import negotiate_pipe_mode

        cfg = negotiate_pipe_mode(engine)
        with _negotiation_lock:
            cfg = _negotiation_cache.setdefault(key, cfg)
    return replace(cfg)


def clear_negotiation_cache() -> None:
    with _negotiation_lock:
        _negotiation_cache.clear()


def chain_exceptions(excs: Sequence[BaseException]) -> BaseException:
    """Return ``excs[0]`` with the remaining exceptions linked onto the
    end of its ``__context__`` chain, so a single ``raise`` surfaces every
    peer failure in the traceback."""
    primary = excs[0]
    seen = {id(primary)}
    node = primary
    while node.__context__ is not None and id(node.__context__) not in seen:
        node = node.__context__
        seen.add(id(node))
    for e in excs[1:]:
        if id(e) in seen:
            continue
        node.__context__ = e
        seen.add(id(e))
        node = e
        while node.__context__ is not None and id(node.__context__) not in seen:
            node = node.__context__
            seen.add(id(node))
    return primary


# -- the declarative surface ---------------------------------------------------

#: edge options that configure the *edge*, not the pipe.  ``broadcast``
#: (default True) gates the planner's fan-out detection: shm edges reading
#: the same source compile onto ONE export over a broadcast ring unless an
#: edge opts out with ``broadcast=False``.
_EDGE_KEYS = frozenset(
    ("workers", "import_workers", "timeout", "via", "dataset", "config",
     "broadcast", "retries", "backoff", "deadline", "failover", "resume",
     "tenant", "qos"))
_PIPE_KEYS = frozenset(f.name for f in dc_fields(PipeConfig))
_VIA = ("pipe", "files")


@dataclass
class _Edge:
    src: Any
    table: str
    dst: Any
    dst_table: str
    options: Dict[str, Any]
    after_prev: bool = False


#: options a subscribe() edge accepts (continuous pipes)
_SUB_KEYS = frozenset(
    ("name", "transport", "mode", "codec", "retain_epochs", "retain_bytes",
     "lease_s", "tenant", "qos", "shm_capacity", "streams",
     "timeout", "broadcast", "schema", "watermark"))


@dataclass
class _SubEdge:
    """A long-lived edge: ``dst`` subscribes to ``src:table`` deltas."""

    src: Any
    table: str
    dst: Any
    dst_table: str
    options: Dict[str, Any]

    @property
    def name(self) -> str:
        return self.options.get("name") or f"{self.src.name}.{self.table}"

    def explain_line(self) -> str:
        o = self.options
        bits = [f"{self.name}: {self.src.name}:{self.table} ~> "
                f"{self.dst.name}:{self.dst_table}",
                f"transport={o.get('transport', 'shm')}"]
        if int(o.get("streams", 1)) > 1:
            bits.append(f"streams={o.get('streams')}")
        if o.get("retain_epochs"):
            bits.append(f"retain={o.get('retain_epochs')}ep")
        bits.append("lifecycle=start/poll/close")
        return "  ".join(bits)


@dataclass
class EdgePlan:
    """One fully-resolved hop of a compiled plan (what ``explain`` shows
    and what the executor runs)."""

    edge_id: str
    source: str                      # source engine name
    table: str
    target: str                      # destination engine name
    dst_table: str
    via: str                         # "pipe" | "files"
    mode: str
    codec: str
    transport: str
    workers: int
    import_workers: int
    streams: int
    partition: Optional[str]
    partition_bounds: Optional[Tuple]   # global range bounds (compile-time)
    bounds_deferred: bool               # source is produced upstream
    fanin: int
    dataset: str
    timeout: float
    negotiated: bool                 # mode came from the FormOpt ladder
    depends_on: Tuple[str, ...]
    # fan-out compiled onto one export (shm broadcast ring): all edges of
    # a group share one dataset/query, the leader's edge runs the single
    # export, and every edge's importer reads a cursor slot of one ring
    broadcast: int = 0               # group size (0 = ordinary edge)
    broadcast_group: Optional[str] = None
    broadcast_leader: bool = False
    # retry policy (the executor's self-healing loop): up to 1 + retries
    # attempts with exponential backoff + seeded jitter, a deadline budget
    # shared across attempts, and — on transport faults — a shm/channel →
    # socket failover ladder.  ``resume`` gates the per-edge ledger that
    # lets attempt k+1 replay locally what attempt k already received and
    # ask the exporter for only the un-acked tail (plain 1:1 edges).
    retries: int = 0
    backoff_s: float = 0.05
    deadline_s: Optional[float] = None
    failover: bool = True
    resume: bool = True
    # broker admission (no-ops unless a PipeBroker is installed): which
    # tenant budget this edge draws from and its scheduling class —
    # queued "latency" tickets are admitted before queued "bulk" ones
    tenant: str = "default"
    qos: str = "bulk"
    broadcast_allowed: bool = field(repr=False, default=True)
    dataset_explicit: bool = field(repr=False, default=False)
    config: PipeConfig = field(repr=False, default=None)
    src_engine: Any = field(repr=False, default=None)
    dst_engine: Any = field(repr=False, default=None)

    def describe(self) -> dict:
        """The declarative decision record (no runtime handles)."""
        return {
            "edge": self.edge_id,
            "source": f"{self.source}:{self.table}",
            "target": f"{self.target}:{self.dst_table}",
            "via": self.via,
            "mode": self.mode,
            "codec": self.codec,
            "transport": self.transport,
            "workers": self.workers,
            "import_workers": self.import_workers,
            "streams": self.streams,
            "partition": self.partition,
            # a deferred edge shows "deferred" until execution samples the
            # bounds, then the sampled values
            "partition_bounds": (
                self.partition_bounds if self.partition_bounds is not None
                else ("deferred" if self.bounds_deferred else None)),
            "fanin": self.fanin,
            "negotiated": self.negotiated,
            "retries": self.retries,
            "resume": self.resume,
            "depends_on": list(self.depends_on),
            "broadcast": (
                {"group": self.broadcast_group, "readers": self.broadcast,
                 "leader": self.broadcast_leader}
                if self.broadcast_group else None),
        }

    def explain_line(self) -> str:
        bits = [f"{self.edge_id}: {self.source}:{self.table} -> "
                f"{self.target}:{self.dst_table}",
                f"via={self.via}"]
        if self.via == "pipe":
            bits += [f"mode={self.mode}"
                     + ("(negotiated)" if self.negotiated else ""),
                     f"codec={self.codec}", f"transport={self.transport}",
                     f"workers={self.workers}->{self.import_workers}"]
            if self.streams > 1:
                bits.append(f"streams={self.streams}")
            if self.partition:
                bits.append(f"partition={self.partition} fanin={self.fanin}")
                if self.partition_bounds is not None:
                    bounds = ", ".join(
                        f"{b:.4g}" if isinstance(b, float) else repr(b)
                        for b in self.partition_bounds)
                    bits.append(f"bounds=[{bounds}]")
                elif self.bounds_deferred:
                    bits.append("bounds=deferred")
            if self.broadcast_group:
                bits.append(
                    f"broadcast={self.broadcast_group}"
                    f"[{'1-export' if self.broadcast_leader else 'shared'}"
                    f",{self.broadcast} readers]")
            if self.retries:
                bits.append(
                    f"retries={self.retries}"
                    + (f" deadline={self.deadline_s:g}s"
                       if self.deadline_s else "")
                    + ("" if self.resume else " resume=off")
                    + ("" if self.failover else " failover=off"))
        else:
            bits.append(f"workers={self.workers}")
        if self.depends_on:
            bits.append(f"after={','.join(self.depends_on)}")
        return "  ".join(bits)


class TransferPlan:
    """Builder for a multi-edge transfer DAG (see module docstring)."""

    def __init__(self, directory: Optional[DirectoryLike] = None,
                 negotiate: bool = True):
        self._edges: List[_Edge] = []
        self._sub_edges: List[_SubEdge] = []
        self._last_edge: Optional[Any] = None
        self._directory = directory
        self._negotiate = negotiate

    # -- building --------------------------------------------------------------
    def move(self, src: Any, table: str, dst: Any, dst_table: str,
             **options: Any) -> "TransferPlan":
        """Add one ``src:table -> dst:dst_table`` edge.  Edges with no
        data dependency run concurrently (a second ``move`` out of the
        same table is a fan-out)."""
        self._edges.append(_Edge(src, table, dst, dst_table, dict(options)))
        self._last_edge = self._edges[-1]
        return self

    def then(self, src: Any, table: str, dst: Any, dst_table: str,
             **options: Any) -> "TransferPlan":
        """Like :meth:`move`, but explicitly sequenced after the
        previously added edge (a chained hop)."""
        if not self._edges:
            raise PlanError("then() needs a preceding move()")
        self._edges.append(
            _Edge(src, table, dst, dst_table, dict(options), after_prev=True))
        self._last_edge = self._edges[-1]
        return self

    def subscribe(self, src: Any, table: str, dst: Any, dst_table: str,
                  **options: Any) -> "TransferPlan":
        """Add a *continuous* edge: ``dst`` subscribes to ``src:table``
        and keeps receiving delta epochs for as long as the handle stays
        open (:mod:`repro.core.subscribe`).  Compile as usual, then call
        :meth:`CompiledPlan.start` — subscribe edges are long-lived, so
        they get a start/poll/close lifecycle instead of ``execute()``.
        Several subscribers of the same source relation share one
        publication, and colocated shm subscribers collapse onto a
        broadcast ring (one encode + one ring write per epoch)."""
        bad = set(options) - _SUB_KEYS
        if bad:
            raise PlanError(
                f"unknown subscribe option(s): {sorted(bad)} "
                f"(allowed: {sorted(_SUB_KEYS)})")
        self._sub_edges.append(
            _SubEdge(src, table, dst, dst_table, dict(options)))
        self._last_edge = self._sub_edges[-1]
        return self

    def options(self, **options: Any) -> "TransferPlan":
        """Refine the last-added edge (``mode=``, ``streams=``,
        ``partition=``, ``workers=``, ... — any PipeConfig knob or edge
        option)."""
        if self._last_edge is None:
            raise PlanError("options() needs a preceding move()")
        if isinstance(self._last_edge, _SubEdge):
            bad = set(options) - _SUB_KEYS
            if bad:
                raise PlanError(
                    f"unknown subscribe option(s): {sorted(bad)}")
        self._last_edge.options.update(options)
        return self

    # -- compile ---------------------------------------------------------------
    def compile(self, directory: Optional[DirectoryLike] = None
                ) -> "CompiledPlan":
        """Validate the whole DAG and resolve every edge to an
        :class:`EdgePlan` — negotiation, partition bounds, worker pairing
        — before any data moves."""
        if not self._edges and not self._sub_edges:
            raise PlanError(
                "empty plan: add edges with move() or subscribe()")
        if not self._edges:
            # subscription-only plan: no batch stages to resolve
            return CompiledPlan([], [], directory or self._directory,
                                sub_edges=list(self._sub_edges))
        with telemetry.span("plan.compile", edges=len(self._edges)):
            return self._compile(directory)

    def _compile(self, directory: Optional[DirectoryLike] = None
                 ) -> "CompiledPlan":
        n = len(self._edges)
        # duplicate targets: two edges writing the same (engine, table)
        produced: Dict[Tuple[int, str], int] = {}
        for i, e in enumerate(self._edges):
            key = (id(e.dst), e.dst_table)
            if key in produced:
                raise PlanError(
                    f"duplicate target: edges e{produced[key]} and e{i} "
                    f"both write {e.dst.name}:{e.dst_table}")
            produced[key] = i
        # data-flow dependencies (+ explicit then-chaining)
        deps: List[set] = [set() for _ in range(n)]
        for i, e in enumerate(self._edges):
            if (id(e.src), e.table) == (id(e.dst), e.dst_table):
                raise PlanError(
                    f"edge e{i} reads and writes the same table "
                    f"{e.src.name}:{e.table} (a one-edge cycle)")
            if e.after_prev:
                deps[i].add(i - 1)
            for j in range(i):  # declaration order resolves hazards
                other = self._edges[j]
                # read-after-write: i consumes what j produces
                if (id(other.dst), other.dst_table) == (id(e.src), e.table):
                    deps[i].add(j)
                # write-after-read: i overwrites what j still reads
                if (id(other.src), other.table) == (id(e.dst), e.dst_table):
                    deps[i].add(j)
            has_producer = any(
                (id(o.dst), o.dst_table) == (id(e.src), e.table)
                for o in self._edges[:i])
            if not has_producer:
                tables = getattr(e.src, "tables", None)
                if tables is not None and e.table not in tables:
                    raise PlanError(
                        f"edge e{i}: source table {e.table!r} does not "
                        f"exist in {e.src.name} and no earlier edge "
                        f"produces it")
        # topological stages (Kahn levels); leftover edges form a cycle
        stages: List[List[int]] = []
        resolved: set = set()
        remaining = set(range(n))
        while remaining:
            level = sorted(i for i in remaining if deps[i] <= resolved)
            if not level:
                raise PlanError(
                    "plan has a dependency cycle among edges "
                    f"{sorted(f'e{i}' for i in remaining)}")
            stages.append(level)
            resolved |= set(level)
            remaining -= set(level)
        # per-edge resolution
        plans: List[EdgePlan] = []
        for i, e in enumerate(self._edges):
            # sample range bounds at compile only when the source relation
            # is already final (not produced/overwritten by an upstream
            # edge) -- otherwise defer sampling to just before the edge runs
            produced_upstream = any(
                (id(o.dst), o.dst_table) == (id(e.src), e.table)
                for o in self._edges[:i])
            plans.append(self._resolve_edge(
                i, e, deps[i],
                table_preexists=(
                    not produced_upstream
                    and e.table in getattr(e.src, "tables", ()))))
        self._group_broadcasts(plans)
        return CompiledPlan(plans, [[f"e{i}" for i in lvl] for lvl in stages],
                            directory or self._directory,
                            sub_edges=list(self._sub_edges))

    @staticmethod
    def _group_broadcasts(plans: List[EdgePlan]) -> None:
        """Detect fan-outs that can share one export: N shm pipe edges
        reading the same source relation to colocated importers, with
        identical wire decisions (mode/codec/block framing), dialect, and
        dependencies.  Each group compiles onto ONE export feeding one
        broadcast ring with N reader cursor slots instead of N exports
        re-encoding the same relation."""
        groups: Dict[Tuple, List[EdgePlan]] = {}
        for ep in plans:
            cfg = ep.config
            if (not ep.broadcast_allowed or ep.via != "pipe"
                    or ep.transport != "shm" or ep.workers != 1
                    or ep.import_workers != 1 or ep.streams != 1
                    or ep.partition or cfg.broadcast
                    # an explicit dataset= names the edge's rendezvous;
                    # grouping would silently rename it to the leader's
                    or ep.dataset_explicit):
                continue
            dst = ep.dst_engine
            key = (id(ep.src_engine), ep.table, ep.mode, ep.codec,
                   cfg.block_rows, cfg.text_format, cfg.delimiter,
                   cfg.verify_first_n, cfg.shm_capacity,
                   id(cfg.link), ep.depends_on,
                   bool(getattr(dst, "writes_header", False)),
                   getattr(dst, "csv_delimiter", ","))
            groups.setdefault(key, []).append(ep)
        gid = 0
        for members in groups.values():
            if len(members) < 2:
                continue
            for k, ep in enumerate(members):
                ep.broadcast = len(members)
                ep.broadcast_group = f"b{gid}"
                ep.broadcast_leader = k == 0
            gid += 1

    def _resolve_edge(self, i: int, e: _Edge, deps: set,
                      table_preexists: bool) -> EdgePlan:
        opts = dict(e.options)
        unknown = set(opts) - _EDGE_KEYS - _PIPE_KEYS
        if unknown:
            raise PlanError(
                f"edge e{i}: unknown option(s) {sorted(unknown)}; have "
                f"{sorted(_EDGE_KEYS | _PIPE_KEYS)}")
        via = opts.pop("via", "pipe")
        if via not in _VIA:
            raise PlanError(f"edge e{i}: via={via!r} not in {_VIA}")
        broadcast_allowed = opts.pop("broadcast", True)
        if not isinstance(broadcast_allowed, bool):
            # the reader count is the planner's to derive (group size);
            # a silent bool() coercion would discard a user's int
            raise PlanError(
                f"edge e{i}: broadcast takes True/False (opt in/out of "
                f"fan-out grouping — the planner derives the reader "
                f"count from the group), got {broadcast_allowed!r}")
        retries = int(opts.pop("retries", 0))
        if retries < 0:
            raise PlanError(f"edge e{i}: retries must be >= 0")
        backoff = float(opts.pop("backoff", 0.05))
        if backoff < 0:
            raise PlanError(f"edge e{i}: backoff must be >= 0")
        deadline_opt = opts.pop("deadline", None)
        deadline_s = float(deadline_opt) if deadline_opt is not None else None
        if deadline_s is not None and deadline_s <= 0:
            raise PlanError(f"edge e{i}: deadline must be > 0")
        tenant = str(opts.pop("tenant", "default"))
        qos = opts.pop("qos", "bulk")
        if qos not in QOS_CLASSES:
            raise PlanError(
                f"edge e{i}: qos={qos!r} not in {QOS_CLASSES}")
        failover = bool(opts.pop("failover", True))
        resume = opts.pop("resume", True)
        if not isinstance(resume, bool):
            raise PlanError(
                f"edge e{i}: resume takes True/False (the executor derives "
                f"the ledger token per run), got {resume!r}")
        workers = int(opts.pop("workers", 1))
        import_workers = opts.pop("import_workers", None)
        timeout = float(opts.pop("timeout", 120.0))
        dataset = opts.pop("dataset", None)
        dataset_explicit = dataset is not None
        dataset = dataset or f"{e.src.name}2{e.dst.name}"
        base = opts.pop("config", None)
        pipe_overrides = {k: v for k, v in opts.items() if k in _PIPE_KEYS}
        if via == "files" and (retries or deadline_s is not None):
            # the retry loop wraps the pipe rendezvous; the file baseline
            # has no peer to resume against
            raise PlanError(
                f"edge e{i}: via='files' does not take a retry policy")
        if via == "files" and (pipe_overrides or base is not None
                               or import_workers is not None):
            # a file edge never opens pipes: pipe knobs silently ignored
            # would be exactly the kwarg fall-through the planner exists
            # to prevent
            bad = sorted(pipe_overrides) + (
                ["config"] if base is not None else []) + (
                ["import_workers"] if import_workers is not None else [])
            raise PlanError(
                f"edge e{i}: via='files' cannot take pipe option(s) {bad}")
        import_workers = (workers if import_workers is None
                          else int(import_workers))
        negotiated = False
        if base is not None:
            cfg = replace(base)
        elif self._negotiate and via == "pipe" and "mode" not in pipe_overrides:
            cfg, negotiated = self._negotiate_pair(e.src, e.dst), True
        else:
            cfg = PipeConfig()
        if pipe_overrides:
            cfg = replace(cfg, **pipe_overrides)
        if cfg.streams < 1:
            raise PlanError(f"edge e{i}: streams must be >= 1")
        if cfg.transport not in ("socket", "channel", "shm"):
            raise PlanError(
                f"edge e{i}: unknown transport {cfg.transport!r}")
        bounds_deferred = False
        if via == "pipe" and cfg.partition:
            try:
                part = parse_partition(cfg.partition,
                                       bounds=cfg.partition_bounds)
            except ValueError as exc:
                raise PlanError(f"edge e{i}: {exc}") from None
            cfg = replace(cfg, fanin=workers)
            if (cfg.partition_bounds is None
                    and cfg.partition.split(":", 1)[0].strip().lower()
                    == "range"):
                if table_preexists:
                    bounds = compute_range_bounds(
                        e.src.get_block(e.table), part.key, import_workers)
                    cfg = replace(cfg, partition_bounds=tuple(bounds))
                else:
                    # the source relation is produced by an upstream edge;
                    # the executor samples bounds right before this edge
                    bounds_deferred = True
        elif via == "pipe":
            cfg = replace(cfg, fanin=1)
        return EdgePlan(
            edge_id=f"e{i}", source=e.src.name, table=e.table,
            target=e.dst.name, dst_table=e.dst_table, via=via,
            mode=cfg.mode if via == "pipe" else "file-csv",
            codec=cfg.codec if via == "pipe" else "none",
            transport=cfg.transport, workers=workers,
            import_workers=import_workers, streams=cfg.streams,
            partition=cfg.partition, partition_bounds=cfg.partition_bounds,
            bounds_deferred=bounds_deferred, fanin=cfg.fanin,
            dataset=dataset, timeout=timeout,
            negotiated=negotiated,
            retries=retries, backoff_s=backoff, deadline_s=deadline_s,
            failover=failover, resume=resume, tenant=tenant, qos=qos,
            depends_on=tuple(f"e{j}" for j in sorted(deps)),
            broadcast_allowed=broadcast_allowed,
            dataset_explicit=dataset_explicit,
            config=cfg, src_engine=e.src, dst_engine=e.dst,
        )

    @staticmethod
    def _negotiate_pair(src: Any, dst: Any) -> PipeConfig:
        """Both engines run the FormOpt ladder (cached); the edge takes
        the *lower* (less optimized) of the two negotiated rungs — the
        most conservative mode both sides validated."""
        from .session import MODE_LADDER

        cfg_s, cfg_d = negotiated_config(src), negotiated_config(dst)
        try:
            rung = max(MODE_LADDER.index(cfg_s.mode),
                       MODE_LADDER.index(cfg_d.mode))
        except ValueError:  # pragma: no cover - ladder always covers both
            return cfg_s
        return replace(cfg_s, mode=MODE_LADDER[rung])

    # -- conveniences ----------------------------------------------------------
    def explain(self) -> str:
        return self.compile().explain()

    def execute(self, directory: Optional[DirectoryLike] = None,
                raise_on_error: bool = True) -> "PlanResult":
        return self.compile(directory).execute(raise_on_error=raise_on_error)


def plan(directory: Optional[DirectoryLike] = None,
         negotiate: bool = True) -> TransferPlan:
    """Start a :class:`TransferPlan` (``negotiate=False`` skips the
    FormOpt ladder and defaults un-configured edges to ``arrowcol``)."""
    return TransferPlan(directory=directory, negotiate=negotiate)


# -- results -------------------------------------------------------------------


@dataclass
class PlanResult:
    """Aggregate outcome of one executed plan."""

    results: Dict[str, Any]              # edge_id -> TransferResult
    errors: List[str]                    # formatted, all edges/sides
    exceptions: List[BaseException]      # the underlying exception objects
    skipped: List[str]                   # edges not run (upstream failed)
    seconds: float = 0.0

    @property
    def ok(self) -> bool:
        return not self.errors and not self.skipped

    @property
    def rows(self) -> int:
        """Total rows landed across *all* edges — a chain counts every
        hop (two 400-row hops report 800); per-relation counts live on
        the per-edge TransferResults."""
        return sum(r.rows for r in self.results.values())

    def edge(self, edge_id: str):
        return self.results[edge_id]

    def single(self):
        """The sole TransferResult of a one-edge plan (the shims' case)."""
        if len(self.results) != 1:
            raise ValueError(f"plan has {len(self.results)} results")
        return next(iter(self.results.values()))


class CompiledPlan:
    """A validated plan: fully-resolved edges grouped into stages of
    independent edges.  ``explain()`` before, ``execute()`` when ready."""

    def __init__(self, edges: List[EdgePlan], stages: List[List[str]],
                 directory: Optional[DirectoryLike],
                 sub_edges: Optional[List[_SubEdge]] = None):
        self.edges = edges
        self.stages = stages
        self.sub_edges = sub_edges or []
        self._directory = directory
        self._by_id = {ep.edge_id: ep for ep in edges}

    def describe(self) -> List[dict]:
        """Per-edge decision dicts, in edge order."""
        return [ep.describe() for ep in self.edges]

    def explain(self) -> str:
        """Human-readable per-edge decisions (the dry-run view)."""
        lines = [f"plan: {len(self.edges)} edge(s), "
                 f"{len(self.stages)} stage(s)"]
        for s, stage in enumerate(self.stages):
            lines.append(f"stage {s}:")
            for eid in stage:
                lines.append("  " + self._by_id[eid].explain_line())
        if self.sub_edges:
            lines.append(f"continuous: {len(self.sub_edges)} "
                         f"subscription edge(s)")
            for se in self.sub_edges:
                lines.append("  " + se.explain_line())
        return "\n".join(lines)

    def execute(self, raise_on_error: bool = True) -> PlanResult:
        """Run the stages in order, each stage's edges concurrently, over
        the shared worker directory.  With ``raise_on_error`` (default) a
        failed edge raises :class:`PlanExecutionError` after the whole
        plan settles, all collected exceptions chained; edges downstream
        of a failure are skipped, independent edges still run."""
        if self.sub_edges and not self.edges:
            raise PlanError(
                "this plan has only subscribe() edges — they are "
                "long-lived; use start() (then poll()/close() the handle)")
        if self._directory is not None:
            set_directory(self._directory)
        # generate every engine's pipe adapter up front, serially: the
        # capture run patches builtins.open process-wide, so it must never
        # overlap another edge's live pipe traffic
        from .session import adapter_for

        for ep in self.edges:
            if ep.via == "pipe":
                adapter_for(ep.src_engine)
                adapter_for(ep.dst_engine)
        t0 = time.perf_counter()
        results: Dict[str, Any] = {}
        errors: List[str] = []
        exceptions: List[BaseException] = []
        skipped: List[str] = []
        failed: set = set()
        for stage in self.stages:
            runnable: List[EdgePlan] = []
            for eid in stage:
                ep = self._by_id[eid]
                bad = [d for d in ep.depends_on if d in failed]
                if bad:
                    skipped.append(eid)
                    failed.add(eid)
                    errors.append(
                        f"{eid}: skipped (upstream {','.join(bad)} failed)")
                else:
                    runnable.append(ep)
            if not runnable:
                continue
            outs: Dict[str, Tuple[Any, List[BaseException]]] = {}
            # work units: ordinary edges run alone; a broadcast group's
            # edges run as ONE unit (one export + R importers over one
            # ring), sharing a single dataset/query rendezvous
            units: List[List[EdgePlan]] = []
            by_group: Dict[str, List[EdgePlan]] = {}
            for ep in runnable:
                if ep.broadcast_group:
                    grp = by_group.setdefault(ep.broadcast_group, [])
                    grp.append(ep)
                    if len(grp) == 1:
                        units.append(grp)
                else:
                    units.append([ep])
            # fresh query ids per run: a re-executed compiled plan must
            # not collide with its previous rendezvous (the directory's
            # per-(dataset, query) state — sender slots, stats — persists)
            from .session import _query_counter

            qids = {id(unit): f"q{next(_query_counter)}" for unit in units}
            broker = get_broker()
            # captured before the worker threads spawn: thread-locals do
            # not cross threads, so each unit re-adopts the plan's trace
            # context explicitly
            plan_ctx = telemetry.current_ctx()

            def run(unit: List[EdgePlan]) -> None:
                recorder = FlightRecorder(
                    name=f"edge {unit[0].edge_id} "
                         f"({unit[0].dataset}:{qids[id(unit)]})")
                with telemetry.trace_context(plan_ctx), \
                        telemetry.span("plan.unit",
                                       edge=unit[0].edge_id,
                                       dataset=unit[0].dataset):
                    self._run_unit(unit, qids[id(unit)], broker, outs,
                                   recorder)

            if len(units) == 1:
                run(units[0])
            else:
                threads = [
                    threading.Thread(target=run, args=(unit,),
                                     name=f"pipegen-plan-{unit[0].edge_id}",
                                     daemon=True)
                    for unit in units
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            for ep in runnable:
                res, excs = outs[ep.edge_id]
                if res is not None:
                    results[ep.edge_id] = res
                if excs:
                    failed.add(ep.edge_id)
                    exceptions.extend(excs)
                    errors.extend(
                        f"{ep.edge_id}: {m}"
                        for m in (res.errors if res is not None
                                  else [repr(x) for x in excs]))
        pr = PlanResult(results=results, errors=errors, exceptions=exceptions,
                        skipped=skipped, seconds=time.perf_counter() - t0)
        if raise_on_error and exceptions:
            raise PlanExecutionError(
                f"{len(failed)} edge(s) failed: " + "; ".join(errors), pr
            ) from chain_exceptions(exceptions)
        return pr

    # -- continuous edges (subscribe() verb) -----------------------------------
    def start(self, timeout: float = 30.0) -> "SubscriptionSet":
        """Bring the plan's subscribe() edges live and return the handle.

        Per distinct (source, table, name) one :class:`~repro.core.
        subscribe.Publication` is created — seeded with a snapshot of the
        source table if it has rows — and wired to the source engine's
        ``on_append`` delta-capture hook, so every ``engine.append()``
        commits an epoch.  Each subscribe edge becomes a
        :class:`~repro.core.subscribe.Subscription` applying epochs into
        its target engine; shm subscribers of one publication share a
        broadcast ring.  The caller owns the returned handle:
        ``poll()`` to apply deltas, ``close()`` to tear everything down.
        """
        if not self.sub_edges:
            raise PlanError("no subscribe() edges in this plan — "
                            "use execute() for batch moves")
        # `from .subscribe import ...` resolves inside the module itself —
        # the package attribute `subscribe` is shadowed by the factory
        # function of the same name once repro.core finishes importing
        from .subscribe import Publication, Subscription, apply_to_engine
        from .directory import get_directory

        directory = self._directory if self._directory is not None \
            else get_directory()
        groups: Dict[Tuple[int, str, str], List[_SubEdge]] = {}
        for se in self.sub_edges:
            groups.setdefault((id(se.src), se.table, se.name),
                              []).append(se)
        pubs: Dict[str, Any] = {}
        unhooks: List[Any] = []
        subs: List[Tuple[str, Any]] = []
        try:
            for (_, table, name), edges in groups.items():
                se0 = edges[0]
                src, o = se0.src, se0.options
                initial = (src.get_block(table)
                           if table in getattr(src, "tables", ()) else None)
                schema = (initial.schema if initial is not None
                          else o.get("schema"))
                if schema is None:
                    raise PlanError(
                        f"subscribe: source table "
                        f"{src.name}:{table} is empty — pass schema=")
                pub = Publication(
                    name, schema, directory=directory,
                    mode=o.get("mode", "arrowcol"),
                    codec=o.get("codec", "none"),
                    retain_epochs=int(o.get("retain_epochs", 64)),
                    retain_bytes=int(o.get("retain_bytes", 64 << 20)),
                    lease_s=o.get("lease_s"),
                    tenant=o.get("tenant", "default"),
                    qos=o.get("qos", "bulk"))
                pubs[name] = pub
                if initial is not None and len(initial):
                    pub.commit_snapshot(initial)
                if hasattr(src, "on_append"):
                    unhooks.append(src.on_append(
                        table, lambda _t, blk, p=pub: p.append(blk)))
                # colocated shm subscribers collapse onto one broadcast
                # ring — one encode + one ring write per epoch
                shm_edges = [
                    e for e in edges
                    if e.options.get("transport", "shm") == "shm"
                    and int(e.options.get("streams", 1)) == 1
                    and e.options.get("broadcast", True)]
                bc = len(shm_edges) if len(shm_edges) > 1 else 0
                for se in edges:
                    eo = se.options
                    kw: Dict[str, Any] = {
                        "directory": directory,
                        "transport": eo.get("transport", "shm"),
                        "streams": int(eo.get("streams", 1)),
                        "watermark": int(eo.get("watermark", 0)),
                        "timeout": eo.get("timeout", timeout),
                        "apply": apply_to_engine(se.dst, se.dst_table),
                    }
                    if bc and se in shm_edges:
                        kw["broadcast"] = bc
                    for opt in ("shm_capacity", "lease_s"):
                        if opt in eo:
                            kw[opt] = eo[opt]
                    label = f"{name}->{se.dst.name}:{se.dst_table}"
                    if any(l == label for l, _ in subs):
                        label = f"{label}#{len(subs)}"
                    subs.append((label, Subscription(name, **kw)))
        except BaseException:
            for _, s in subs:
                s.close()
            for u in unhooks:
                u()
            for p in pubs.values():
                p.close()
            raise
        return SubscriptionSet(pubs, subs, unhooks)

    @staticmethod
    def _run_unit(unit: List[EdgePlan], qid: str, broker, outs: Dict,
                  recorder: FlightRecorder) -> None:
        """One work unit end to end: admission ticket (queue under the
        broker's QoS gate), then the edge / broadcast-group runner.  The
        unit's FlightRecorder accumulates admission, attempt, and pipe
        events; any terminal failure leaves with that timeline attached."""
        ticket = None
        if broker is not None:
            # hold an admission ticket for the unit's whole lifetime:
            # over-quota units queue here (in their own thread) while
            # admitted ones move data
            vec = _admission_vector(unit)
            recorder.note("admission.request", **vec)
            t0 = time.monotonic()
            try:
                with telemetry.span("plan.admit", edge=unit[0].edge_id,
                                    tenant=vec["tenant"], qos=vec["qos"]):
                    ticket = broker.admit(**vec)
            except BaseException as e:  # noqa: BLE001 - aggregated
                recorder.note("admission.rejected", error=repr(e))
                attach_flight(e, recorder)
                for ep in unit:
                    outs[ep.edge_id] = (None, [e])
                return
            if getattr(ticket, "degraded", False):
                # control plane unreachable: admission suspended (the
                # degraded ladder), the unit proceeds un-gated
                recorder.note("admission.degraded")
                telemetry.counter("plan.admit_degraded").inc()
            recorder.note("admission.granted",
                          wait_s=round(time.monotonic() - t0, 6))
        try:
            if len(unit) == 1 and not unit[0].broadcast_group:
                outs[unit[0].edge_id] = _run_edge(unit[0], qid, recorder)
                return
            try:
                outs.update(_run_broadcast_group(unit, qid, recorder))
            except BaseException as e:  # noqa: BLE001 - aggregated
                for ep in unit:
                    outs[ep.edge_id] = (None, [e])
        finally:
            if ticket is not None:
                ticket.release()


class SubscriptionSet:
    """The live handle :meth:`CompiledPlan.start` returns for a plan's
    continuous edges: per-name publications fed by the source engines'
    append hooks, plus one subscription per edge applying epochs into its
    target engine.  ``poll()`` to apply pending deltas, ``close()`` to
    tear down subscriptions → hooks → publications, in that order."""

    def __init__(self, publications: Dict[str, Any],
                 subscriptions: List[Tuple[str, Any]],
                 unhooks: List[Any]):
        self.publications = publications
        self.subscriptions = subscriptions
        self._unhooks = unhooks
        self._closed = False

    def poll(self, timeout: float = 0.0) -> Dict[str, List[Any]]:
        """Drain every subscription once (deltas apply into the target
        engines via their ``apply`` callbacks); label -> epochs."""
        out: Dict[str, List[Any]] = {}
        for label, sub in self.subscriptions:
            try:
                out[label] = sub.poll(timeout)
            except BrokenPipeError:
                out[label] = []
        return out

    @property
    def watermarks(self) -> Dict[str, int]:
        return {label: s.watermark for label, s in self.subscriptions}

    def wait_caught_up(self, timeout: float = 10.0) -> bool:
        """Poll until every subscription's watermark reaches its
        publication's head (True) or ``timeout`` elapses (False)."""
        deadline = time.monotonic() + timeout
        while True:
            heads = {n: p.head for n, p in self.publications.items()}
            behind = [
                (label, s) for label, s in self.subscriptions
                if s.watermark < heads.get(label.split("->", 1)[0], 0)]
            if not behind:
                return True
            if time.monotonic() >= deadline:
                return False
            self.poll(timeout=0.05)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for _, sub in self.subscriptions:
            sub.close()
        for unhook in self._unhooks:
            try:
                unhook()
            except Exception:
                pass
        for pub in self.publications.values():
            pub.close()

    def __enter__(self) -> "SubscriptionSet":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


# -- the edge runners ----------------------------------------------------------


def _admission_vector(unit: List[EdgePlan]) -> Dict[str, Any]:
    """The broker resource vector for one work unit.  shm edges cost
    rings (streams × shuffle fan-in, 2 doorbell fds each while live) and
    their summed ring bytes; a broadcast group costs ONE segment however
    many readers it fans out to; channel/socket/file edges cost only a
    concurrency slot.  Tenant/QoS come from the first edge — a broadcast
    group shares one export, so its edges share one ticket."""
    lead = unit[0]
    rings = segments = nbytes = 0
    for ep in unit:
        if ep.via != "pipe" or ep.transport != "shm":
            continue
        if ep.broadcast_group:
            if segments == 0:
                segments = 1
                rings += 1
                nbytes += ep.config.shm_capacity
            continue
        n = max(1, ep.streams) * max(1, ep.fanin)
        rings += n
        segments += n
        nbytes += n * ep.config.shm_capacity
    return {"tenant": lead.tenant, "qos": lead.qos, "rings": rings,
            "segments": segments, "nbytes": nbytes}


def _run_edge(ep: EdgePlan, query_id: str,
              recorder: Optional[FlightRecorder] = None):
    """Execute one edge under the executor's per-run ``query_id``;
    returns ``(TransferResult | None, exceptions)``.  Never raises: all
    failures (both sides, timeout) are collected."""
    try:
        if ep.via == "files":
            return _run_file_edge(ep)
        return _run_pipe_edge(ep, query_id, recorder)
    except BaseException as e:  # noqa: BLE001 - the executor aggregates
        return None, [e]


def _transport_fault(excs: Sequence[BaseException]) -> bool:
    """True when any failure looks like the transport (not the data or
    the engine) let the edge down — the failover ladder's trigger."""
    return any(isinstance(e, (OSError, TimeoutError)) for e in excs)


def _run_pipe_edge(ep: EdgePlan, query_id: str,
                   recorder: Optional[FlightRecorder] = None):
    """The self-healing wrapper: run :func:`_run_pipe_attempt` up to
    ``1 + ep.retries`` times.  Each retry gets a fresh query id (the
    directory's per-(dataset, query) rendezvous state is single-use), a
    bumped ``attempt`` epoch, and — on resumable edges — the shared
    resume-ledger token, so the new importer replays the staged prefix
    and the new exporter skips to the acked watermark instead of
    re-moving the whole relation.  Backoff is exponential with seeded
    jitter; ``deadline`` bounds the whole loop; on transport faults a
    shm/channel edge fails over to the socket rendezvous."""
    from .datapipe import clear_resume

    config = ep.config
    if ep.bounds_deferred:
        # the source relation now exists (its producer edge ran): sample
        # the global range bounds that compile had to defer.  The flag
        # stays set — a re-executed plan re-samples (the upstream edge
        # re-ran too); ep.partition_bounds is updated for observability.
        part = parse_partition(ep.partition)
        bounds = tuple(compute_range_bounds(
            ep.src_engine.get_block(ep.table), part.key, ep.import_workers))
        config = replace(config, partition_bounds=bounds)
        ep.partition_bounds = bounds
    max_attempts = 1 + max(0, ep.retries)
    # resume needs a single 1:1 pipe: stripes/shuffles/broadcasts have
    # per-member frame orders one watermark cannot describe
    resumable = (ep.resume and max_attempts > 1 and ep.streams == 1
                 and ep.fanin == 1 and not ep.partition
                 and not ep.broadcast_group
                 and ep.workers == 1 and ep.import_workers == 1)
    token = f"{ep.dataset}:{query_id}:{ep.edge_id}" if resumable else None
    rng = random.Random(hash((ep.dataset, query_id, ep.edge_id)) & 0x7FFFFFFF)
    deadline = (time.monotonic() + ep.deadline_s) if ep.deadline_s else None
    transport = config.transport
    recorder = recorder if recorder is not None else FlightRecorder(
        name=f"edge {ep.edge_id} ({ep.dataset}:{query_id})")
    attempts: List[dict] = []
    history: List[str] = []
    result = None
    excs: List[BaseException] = []
    try:
        for k in range(max_attempts):
            qid = query_id if k == 0 else f"{query_id}a{k}"
            # a rendezvous must not outlive its attempt: a side blocked in
            # the directory past ep.timeout is already abandoned (the
            # attempt's join gave up on it), and an orphaned exporter
            # thread still holds its open-splice registration
            cfg = replace(config, transport=transport, resume=token,
                          attempt=k, recorder=recorder,
                          trace_ctx=(config.trace_ctx
                                     or telemetry.current_ctx()),
                          connect_timeout=min(config.connect_timeout,
                                              ep.timeout))
            recorder.note("edge.attempt", attempt=k, query_id=qid,
                          transport=transport,
                          resumed=bool(token and k > 0))
            t0 = time.monotonic()
            with telemetry.span("edge.attempt", edge=ep.edge_id,
                                attempt=k, transport=transport):
                result, excs = _run_pipe_attempt(ep, cfg, qid)
            rec = {"attempt": k, "query_id": qid, "transport": transport,
                   "seconds": round(time.monotonic() - t0, 6),
                   "ok": not excs,
                   "error": repr(excs[0]) if excs else None}
            if result is not None:
                # per-attempt attribution: this attempt's own stats, not
                # the fold across earlier failed attempts
                rec["export_stats"] = result.export_stats
                rec["import_stats"] = result.import_stats
            attempts.append(rec)
            if not excs:
                break
            recorder.note("edge.attempt_failed", attempt=k,
                          error=rec["error"])
            history.append(f"attempt {k} ({transport}): {rec['error']}")
            if k + 1 >= max_attempts:
                break
            if deadline is not None and time.monotonic() >= deadline:
                history.append(
                    f"retry budget exhausted after attempt {k} "
                    f"(deadline {ep.deadline_s:g}s)")
                break
            if (ep.failover and transport in ("shm", "channel")
                    and _transport_fault(excs)):
                history.append(f"failover: {transport} -> socket")
                recorder.note("edge.failover", frm=transport, to="socket")
                transport = "socket"
            delay = ep.backoff_s * (2 ** k) * (0.5 + rng.random())
            if deadline is not None:
                delay = min(delay, max(0.0, deadline - time.monotonic()))
            if delay > 0:
                time.sleep(delay)
    finally:
        if token is not None:
            clear_resume(token)
    if result is not None:
        result.attempts = attempts
        if history:
            result.errors = history + result.errors
    if excs:
        # terminal failure: every raised error carries the edge timeline
        for e in excs:
            attach_flight(e, recorder)
    return result, excs


def _run_pipe_attempt(ep: EdgePlan, config, query_id: str):
    from .session import TransferResult, adapter_for

    src, dst = ep.src_engine, ep.dst_engine
    gp_src, gp_dst = adapter_for(src), adapter_for(dst)
    name_exp = (f"db://{ep.dataset}?workers={ep.workers}"
                f"&query={query_id}")
    name_imp = (f"db://{ep.dataset}?workers={ep.import_workers}"
                f"&query={query_id}")
    # (side, exception) in *completion order*: the first failure is the
    # root cause (a crashed peer orphans the survivor, whose secondary
    # timeout then rides along as __context__)
    errs: List[Tuple[str, BaseException]] = []
    times = {"export": 0.0, "import": 0.0}

    def run_import() -> None:
        t0 = time.perf_counter()
        try:
            with PipeEnabledEngine(gp_dst), PipeOpenContext(config):
                dst.import_csv_parallel(ep.dst_table, name_imp,
                                        workers=ep.import_workers)
        except BaseException as e:  # noqa: BLE001 - surfaced via result
            errs.append(("import", e))
        times["import"] = time.perf_counter() - t0

    def run_export() -> None:
        t0 = time.perf_counter()
        try:
            with PipeEnabledEngine(gp_src), PipeOpenContext(config):
                src.export_csv_parallel(
                    ep.table, name_exp, workers=ep.workers,
                    header=dst.writes_header, delimiter=dst.csv_delimiter,
                )
        except BaseException as e:  # noqa: BLE001
            errs.append(("export", e))
        times["export"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    # daemon: a failed peer must not pin the process on an orphaned
    # accept/recv (the surviving side times out on its own)
    ti = threading.Thread(target=run_import, daemon=True,
                          name=f"pipegen-import-{query_id}")
    te = threading.Thread(target=run_export, daemon=True,
                          name=f"pipegen-export-{query_id}")
    ti.start()
    te.start()
    ti.join(ep.timeout)
    te.join(ep.timeout)
    elapsed = time.perf_counter() - t0
    excs: List[BaseException] = []
    messages: List[str] = []
    for side, e in errs:
        excs.append(e)
        messages.append(f"{side}: {e!r}")
    if not excs and (ti.is_alive() or te.is_alive()):
        stuck = [nm for nm, th in (("import", ti), ("export", te))
                 if th.is_alive()]
        excs.append(TimeoutError(
            f"transfer {ep.dataset} did not complete within {ep.timeout}s "
            f"({'/'.join(stuck)} still running)"))
        messages.append(f"timeout: {excs[-1]}")
    try:
        rows = len(dst.get_block(ep.dst_table))
    except KeyError:
        rows = 0
    stats = collect_stats(ep.dataset, query_id)
    exp_stats = stats.get("export")
    result = TransferResult(
        source=src.name, target=dst.name, mode=config.mode,
        codec=config.codec, rows=rows, seconds=elapsed,
        export_seconds=times["export"], import_seconds=times["import"],
        bytes_moved=exp_stats.bytes_sent if exp_stats else 0,
        errors=messages,
        export_stats=exp_stats, import_stats=stats.get("import"),
    )
    return result, excs


def _run_broadcast_group(eps: List[EdgePlan], query_id: str,
                         recorder: Optional[FlightRecorder] = None,
                         ) -> Dict[str, Tuple[Any, List[BaseException]]]:
    """Run one compiled fan-out group: a SINGLE export of the shared
    source relation into a broadcast shm ring, consumed concurrently by
    every edge's importer from its own cursor slot.  All edges share the
    leader's dataset and this run's ``query_id``; the one export's stats
    land on the leader edge (the other edges carry no export stats — the
    encode genuinely happened once).  Never raises: failures are collected
    per edge (an import failure is that edge's own; an export failure
    fails the whole group)."""
    from .session import TransferResult, adapter_for

    n_readers = len(eps)
    leader = next((ep for ep in eps if ep.broadcast_leader), eps[0])
    src = leader.src_engine
    dataset = leader.dataset
    recorder = recorder if recorder is not None else FlightRecorder(
        name=f"broadcast {dataset}:{query_id}")
    recorder.note("broadcast.start", dataset=dataset, readers=n_readers)
    bcast_ctx = telemetry.current_ctx()
    name = f"db://{dataset}?workers=1&query={query_id}"
    timeout = max(ep.timeout for ep in eps)
    errs: List[Tuple[str, BaseException]] = []  # (edge_id | "export", exc)
    times: Dict[str, float] = {}

    def run_import(ep: EdgePlan) -> None:
        t0 = time.perf_counter()
        cfg = replace(ep.config, transport="shm", broadcast=n_readers,
                      partition=None, fanin=1, streams=1,
                      recorder=recorder,
                      trace_ctx=ep.config.trace_ctx or bcast_ctx)
        try:
            with PipeEnabledEngine(adapter_for(ep.dst_engine)), \
                    PipeOpenContext(cfg):
                ep.dst_engine.import_csv_parallel(ep.dst_table, name,
                                                  workers=1)
        except BaseException as e:  # noqa: BLE001 - aggregated
            errs.append((ep.edge_id, e))
        times[ep.edge_id] = time.perf_counter() - t0

    def run_export() -> None:
        t0 = time.perf_counter()
        cfg = replace(leader.config, partition=None, fanin=1,
                      recorder=recorder,
                      trace_ctx=leader.config.trace_ctx or bcast_ctx)
        try:
            with PipeEnabledEngine(adapter_for(src)), PipeOpenContext(cfg):
                src.export_csv_parallel(
                    leader.table, name, workers=1,
                    header=leader.dst_engine.writes_header,
                    delimiter=leader.dst_engine.csv_delimiter,
                )
        except BaseException as e:  # noqa: BLE001
            errs.append(("export", e))
        times["export"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    imp_threads = [
        threading.Thread(target=run_import, args=(ep,), daemon=True,
                         name=f"pipegen-bcast-{ep.edge_id}")
        for ep in eps
    ]
    te = threading.Thread(target=run_export, daemon=True,
                          name=f"pipegen-bcast-export-{query_id}")
    for t in imp_threads:
        t.start()
    te.start()
    deadline = time.monotonic() + timeout
    for t in imp_threads + [te]:
        t.join(max(0.1, deadline - time.monotonic()))
    elapsed = time.perf_counter() - t0
    stats = collect_stats(dataset, query_id)
    exp_stats = stats.get("export")
    imp_stats = stats.get("import")  # merged across all reader slots
    export_excs = [e for tag, e in errs if tag == "export"]
    out: Dict[str, Tuple[Any, List[BaseException]]] = {}
    for ep, th in zip(eps, imp_threads):
        own = [e for tag, e in errs if tag == ep.edge_id]
        excs = own + export_excs
        messages = [f"import: {e!r}" for e in own]
        messages += [f"export: {e!r}" for e in export_excs]
        if not excs and (th.is_alive() or te.is_alive()):
            stuck = [nm for nm, alive in (("import", th.is_alive()),
                                          ("export", te.is_alive()))
                     if alive]
            excs = [TimeoutError(
                f"broadcast transfer {dataset} did not complete within "
                f"{timeout}s ({'/'.join(stuck)} still running)")]
            messages = [f"timeout: {excs[0]}"]
        for e in excs:  # attach_flight is idempotent on shared excs
            attach_flight(e, recorder)
        try:
            rows = len(ep.dst_engine.get_block(ep.dst_table))
        except KeyError:
            rows = 0
        result = TransferResult(
            source=src.name, target=ep.dst_engine.name,
            mode=leader.mode, codec=leader.codec, rows=rows,
            seconds=elapsed,
            export_seconds=(times.get("export", 0.0)
                            if ep.broadcast_leader else 0.0),
            import_seconds=times.get(ep.edge_id, 0.0),
            bytes_moved=(exp_stats.bytes_sent
                         if exp_stats and ep.broadcast_leader else 0),
            errors=messages,
            export_stats=exp_stats if ep.broadcast_leader else None,
            import_stats=imp_stats if ep.broadcast_leader else None,
        )
        out[ep.edge_id] = (result, excs)
    return out


def run_file_transfer(src: Any, table: str, dst: Any, dst_table: str,
                      workers: int, td: Optional[str] = None):
    """The file-system baseline, shared by ``via='files'`` edges and the
    :func:`~repro.core.session.transfer_via_files` shim.  With ``td``
    (caller-owned spool dir) the part files are kept; otherwise a temp
    dir is created and removed."""
    import os
    import tempfile

    from .session import TransferResult

    own_tmp = td is None
    td = td or tempfile.mkdtemp(prefix="pipegen-fs-")
    base = os.path.join(td, f"{src.name}2{dst.name}.csv")
    t0 = time.perf_counter()
    src.export_csv_parallel(
        table, base, workers=workers,
        header=dst.writes_header, delimiter=dst.csv_delimiter,
    )
    t1 = time.perf_counter()
    # single-worker export writes `base` itself; parallel writes part files
    if workers <= 1:
        if not os.path.exists(base):
            raise FileNotFoundError(base)
        dst.import_csv(dst_table, base)
    else:
        dst.import_csv_parallel(dst_table, base, workers=workers)
    t2 = time.perf_counter()
    bytes_moved = 0
    for fn in os.listdir(td):
        if fn.startswith(os.path.basename(base)):
            bytes_moved += os.path.getsize(os.path.join(td, fn))
    if own_tmp:
        for fn in os.listdir(td):
            os.unlink(os.path.join(td, fn))
        os.rmdir(td)
    rows = len(dst.get_block(dst_table))
    return TransferResult(
        source=src.name, target=dst.name, mode="file-csv", codec="none",
        rows=rows, seconds=t2 - t0,
        export_seconds=t1 - t0, import_seconds=t2 - t1,
        bytes_moved=bytes_moved,
    )


def _run_file_edge(ep: EdgePlan):
    return run_file_transfer(ep.src_engine, ep.table, ep.dst_engine,
                             ep.dst_table, ep.workers), []

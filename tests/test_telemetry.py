"""Telemetry: span tracer (and its near-free disabled path), trace
context propagation across threads AND processes, Chrome-trace export,
the metrics registry, the per-attempt stats sink (eviction order at
``_SINK_MAX``), the flight recorder + ``attach_flight``, the broker
``stats`` RPC, and the ``pipetop`` renderer."""

import json
import multiprocessing
import os
import threading
import time

import pytest

from repro.core import telemetry
from repro.core.datapipe import (
    DataPipeInput,
    DataPipeOutput,
    PipeConfig,
    PipeStats,
    collect_stats,
    collect_stats_by_attempt,
)
from repro.core.datapipe import _SINK_MAX, _record_stats, parse_reserved
from repro.core.telemetry import (
    DEFAULT_BUCKETS,
    FlightRecorder,
    MetricsRegistry,
    attach_flight,
    chrome_trace,
    merge_trace_dir,
    span,
)
from repro.engines import make_engine, make_paper_block

_mp = multiprocessing.get_context("spawn")
JOIN_S = 60


@pytest.fixture(autouse=True)
def _tracing_off():
    """Tests own the tracer's lifecycle; never leak it across tests."""
    telemetry.disable_tracing()
    yield
    telemetry.disable_tracing()


# -- the disabled path --------------------------------------------------------------


def test_disabled_span_is_shared_null_singleton():
    """The off path's contract: no tracer -> span() returns ONE
    preallocated no-op object (no allocation, no clock read)."""
    assert not telemetry.tracing_enabled()
    a = span("export.encode", rows=100)
    b = span("import.decode")
    assert a is b is telemetry._NULL_SPAN
    with a as s:
        s.set(anything="ignored")  # no-op, never raises
    assert telemetry.current_ctx() == ""
    assert telemetry.tracer() is None


def test_disabled_pipes_record_nothing():
    """A full transfer with tracing off must leave the tracer untouched
    (the <2% fig11.telemetry_overhead rung measures the wall-clock side
    of this; the structural side is asserted here), and so must a
    feeder, whose ``feeder.get_wait_s`` histogram still counts every
    get."""
    from repro.pipeline import PipeFeeder, SyntheticSource

    block = make_paper_block(64, seed=2)
    name = "db://toff?query=1"
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        got["rows"] = sum(len(b) for b in pipe.blocks())
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    _pump(name, block, PipeConfig(mode="arrowcol", block_rows=32))
    t.join(20)
    assert got["rows"] == 64

    waits = telemetry.histogram("feeder.get_wait_s")
    gets0 = waits.total
    feed = "db://toffeed?query=1"
    feeder = PipeFeeder([feed], batch_size=4, seq_len=8).start()
    src = threading.Thread(target=SyntheticSource(50, 8, seed=1).serve,
                           args=(feed, 12))
    src.start()
    batches = list(feeder.batches())
    src.join(20)
    assert not src.is_alive()
    assert len(batches) == 3
    assert waits.total - gets0 == 4  # three batches and the end of stream
    assert telemetry.tracer() is None  # nothing silently enabled it


# -- live tracer --------------------------------------------------------------------


def _pump(name, block, config):
    out = DataPipeOutput(name, config=config)
    _write_rows(out, block)
    out.close()


def _write_rows(out, block):
    from repro.core.astring import AString

    for row in block.to_rows().rows:
        parts = []
        for j, v in enumerate(row):
            if j:
                parts.append(",")
            parts.append(v)
        parts.append("\n")
        out.write(AString(parts))


def test_nested_spans_share_trace_and_parent():
    tr = telemetry.enable_tracing()
    with span("outer", layer=1):
        outer_ctx = telemetry.current_ctx()
        with span("inner"):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"outer", "inner"}
    assert spans["inner"].trace_id == spans["outer"].trace_id
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert outer_ctx == (f"{spans['outer'].trace_id}:"
                        f"{spans['outer'].span_id}")
    assert spans["outer"].duration >= spans["inner"].duration >= 0
    assert spans["outer"].attrs == {"layer": 1}


def test_trace_context_adopts_foreign_ctx_on_worker_thread():
    """plan worker threads re-adopt the spawning thread's context."""
    tr = telemetry.enable_tracing()
    ctx = telemetry.new_trace_ctx()

    def work():
        with telemetry.trace_context(ctx), span("unit"):
            pass

    t = threading.Thread(target=work)
    t.start()
    t.join(10)
    (s,) = tr.spans()
    tid, sid = telemetry.split_ctx(ctx)
    assert s.trace_id == tid and s.parent_id == sid


def test_tracer_ring_is_bounded_and_counts_drops():
    tr = telemetry.enable_tracing(capacity=8)
    for i in range(12):
        with span(f"s{i}"):
            pass
    assert len(tr.spans()) == 8
    assert tr.dropped == 4
    assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(4, 12)]


def test_chrome_trace_export_roundtrips(tmp_path):
    telemetry.enable_tracing()
    with span("export.encode", frames=3):
        pass
    doc = chrome_trace()
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "export.encode"
    assert ev["dur"] >= 0 and ev["args"]["frames"] == 3
    p = telemetry.dump_chrome_trace(str(tmp_path / "trace.json"))
    loaded = json.loads(open(p).read())
    assert loaded["traceEvents"][0]["name"] == "export.encode"


def test_traced_transfer_single_trace_in_process():
    """Exporter and importer threads of one pipe land in ONE trace, with
    the lifecycle spans parented under the per-side pipe spans."""
    tr = telemetry.enable_tracing()
    block = make_paper_block(64, seed=3)
    name = "db://ttrace?query=1"

    def imp():
        pipe = DataPipeInput(name, trace=True)
        list(pipe.blocks())
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    _pump(name, block, PipeConfig(mode="arrowcol", block_rows=32,
                                  trace=True))
    t.join(20)
    spans = tr.spans()
    names = {s.name for s in spans}
    assert {"export.pipe", "import.pipe", "export.rendezvous",
            "import.rendezvous", "export.fill", "export.encode",
            "export.send", "import.wait_schema", "import.wait",
            "import.decode"} <= names
    assert len({s.trace_id for s in spans}) == 1  # ONE trace
    by_name = {s.name: s for s in spans}
    assert by_name["export.pipe"].attrs["rows"] == 64
    # every phase span parents to its side's pipe span; the importer's
    # pipe span parents to the exporter's via the hello
    exp, imp = by_name["export.pipe"], by_name["import.pipe"]
    for s in spans:
        if s.name.startswith("export.") and s is not exp:
            assert s.parent_id == exp.span_id, s.name
        elif s.name.startswith("import.") and s is not imp:
            assert s.parent_id == imp.span_id, s.name
    assert imp.parent_id == exp.span_id


def test_traced_export_records_phase_spans_before_close():
    """Phase spans are recorded as each phase ends, not at close: a
    block's fill (first row to flush, with the seconds spent parsing in
    write), its encode and its send are in the tracer while the pipe is
    still open."""
    tr = telemetry.enable_tracing()
    block = make_paper_block(64, seed=4)
    name = "db://tstream?query=1"
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        got["rows"] = sum(len(b) for b in pipe.blocks())
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=32))
    _write_rows(out, block)
    open_spans = tr.spans()
    out.close()
    t.join(20)
    assert got["rows"] == 64
    by = {}
    for s in open_spans:
        by.setdefault(s.name, []).append(s)
    assert "export.pipe" not in by  # the whole-pipe span comes at close
    fills = by["export.fill"]
    assert [f.attrs["rows"] for f in fills] == [32, 32]
    for f in fills:
        assert 0 < f.attrs["write_s"] <= f.duration
    assert [e.attrs["rows"] for e in by["export.encode"]] == [32, 32]
    # each block's encode starts where its fill ended
    assert [e.t0 for e in by["export.encode"]] == [f.t1 for f in fills]
    assert [s.attrs["kind"] for s in by["export.send"]].count("B") == 2
    # the phase spans already name the pipe span recorded at close
    pipe_sid = next(s.span_id for s in tr.spans() if s.name == "export.pipe")
    assert {s.parent_id for s in open_spans
            if s.name.startswith("export.")} == {pipe_sid}


def test_traced_write_block_records_encode_per_frame():
    tr = telemetry.enable_tracing()
    block = make_paper_block(40, seed=5)
    name = "db://tblock?query=1"
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        got["rows"] = sum(len(b) for b in pipe.blocks())
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=16))
    out.write_block(block)
    encodes = [s for s in tr.spans() if s.name == "export.encode"]
    out.close()
    t.join(20)
    assert got["rows"] == 40
    assert [e.attrs["rows"] for e in encodes] == [16, 16, 8]
    assert not any(s.name == "export.fill" for s in tr.spans())


# -- cross-process propagation -------------------------------------------------------


def _child_export(host, port, name, n_rows):
    from repro.core.directory import DirectoryClient, set_directory

    set_directory(DirectoryClient(host, port))
    block = make_paper_block(n_rows, seed=9)
    _pump(name, block, PipeConfig(mode="arrowcol", block_rows=32,
                                  trace=True))


def _child_import(host, port, name, transport):
    from repro.core.directory import DirectoryClient, set_directory

    set_directory(DirectoryClient(host, port))
    pipe = DataPipeInput(name, transport=transport, trace=True)
    n = sum(len(b) for b in pipe.blocks())
    pipe.close()
    assert n == 96, n


@pytest.mark.parametrize("transport", ["socket", "shm"])
def test_cross_process_transfer_yields_single_trace(tmp_path, transport):
    """The acceptance scenario: exporter and importer in SEPARATE
    processes, trace context propagated through the directory
    registration / schema hello, spans spilled per-process via
    PIPEGEN_TRACE_DIR — merged, they form one trace with both sides."""
    from repro.core.directory import DirectoryServer

    spill = str(tmp_path / "spans")
    name = "db://xproc?query=1"
    server = DirectoryServer().start()
    os.environ["PIPEGEN_TRACE"] = "1"
    os.environ["PIPEGEN_TRACE_DIR"] = spill
    try:
        pi = _mp.Process(target=_child_import,
                         args=(server.host, server.port, name, transport))
        pe = _mp.Process(target=_child_export,
                         args=(server.host, server.port, name, 96))
        pi.start()
        pe.start()
        pi.join(JOIN_S)
        pe.join(JOIN_S)
        assert pi.exitcode == 0 and pe.exitcode == 0
    finally:
        del os.environ["PIPEGEN_TRACE"]
        del os.environ["PIPEGEN_TRACE_DIR"]
        server.stop()
    spans = merge_trace_dir(spill)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, s)
    assert "export.pipe" in by_name and "import.pipe" in by_name
    exp, imp = by_name["export.pipe"], by_name["import.pipe"]
    assert exp.pid != imp.pid  # genuinely two processes
    assert exp.trace_id == imp.trace_id  # ONE trace across the pipe
    assert len({s.trace_id for s in spans}) == 1
    # exportable as one Chrome-trace document
    doc = chrome_trace(spans)
    assert len(doc["traceEvents"]) == len(spans) >= 4


# -- metrics registry ---------------------------------------------------------------


def test_counters_gauges_and_labels_are_get_or_create():
    reg = MetricsRegistry()
    reg.counter("pipe.bytes", role="export").inc(100)
    reg.counter("pipe.bytes", role="export").inc(28)
    reg.counter("pipe.bytes", role="import").inc(5)
    assert reg.counter("pipe.bytes", role="export").value == 128
    reg.gauge("queue_depth").set(7)
    reg.gauge("queue_depth").add(-2)
    snap = reg.snapshot()
    assert snap["counters"]["pipe.bytes{role=export}"] == 128
    assert snap["counters"]["pipe.bytes{role=import}"] == 5
    assert snap["gauges"]["queue_depth"] == 5
    json.dumps(snap)  # must be JSON-serializable verbatim


def test_histogram_buckets_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("wait_s")
    assert h.bounds == DEFAULT_BUCKETS
    for v in (0.0002, 0.0002, 0.0002, 0.0002, 0.0002, 0.0002, 0.0002,
              0.0002, 0.05, 200.0):
        h.observe(v)
    assert h.total == 10 and h.sum == pytest.approx(0.0016 + 0.05 + 200)
    assert h.quantile(0.5) == 4e-4  # upper bound of the 200us bucket
    assert h.quantile(0.95) == float("inf")  # the 200s outlier
    snap = reg.snapshot()["histograms"]["wait_s"]
    assert snap["total"] == 10 and snap["buckets"]["+Inf"] == 1


# -- per-attempt stats sink ---------------------------------------------------------


def _stats(n=1):
    st = PipeStats()
    st.bytes_sent = 10 * n
    st.frames_sent = n
    return st


def test_stats_sink_folds_attempts_and_peeks_per_attempt():
    rn = parse_reserved("db://attr?query=qa")
    _record_stats(rn, "export", _stats(1), attempt=0)
    _record_stats(rn, "export", _stats(2), attempt=1)
    _record_stats(rn, "import", _stats(5), attempt=1)
    # non-destructive per-attempt view first
    by = collect_stats_by_attempt("attr", "qa")
    assert set(by["export"]) == {0, 1}
    assert by["export"][0].bytes_sent == 10
    assert by["export"][1].bytes_sent == 20
    assert set(by["import"]) == {1}
    # the folded view pops and merges across attempts
    folded = collect_stats("attr", "qa")
    assert folded["export"].bytes_sent == 30
    assert folded["export"].frames_sent == 3
    assert folded["import"].bytes_sent == 50
    assert collect_stats("attr", "qa") == {}  # popped


def test_stats_sink_evicts_oldest_insertion_at_cap():
    """Fill the sink past _SINK_MAX and assert FIFO eviction: the oldest
    key is gone (collect returns empty), the newest are intact, and
    re-recording an EXISTING key never evicts."""
    base = f"evt{os.getpid()}"
    for i in range(_SINK_MAX + 3):
        rn = parse_reserved(f"db://{base}{i}?query=e")
        _record_stats(rn, "export", _stats(i + 1))
    # the three oldest fell off the front, in insertion order
    for i in range(3):
        assert collect_stats(f"{base}{i}", "e") == {}
    # merging into a surviving key must NOT evict anything
    rn = parse_reserved(f"db://{base}3?query=e")
    _record_stats(rn, "export", _stats(1))
    assert collect_stats(f"{base}4", "e")["export"].frames_sent == 5
    got = collect_stats(f"{base}3", "e")
    assert got["export"].frames_sent == 4 + 1  # merged, not replaced
    for i in range(5, _SINK_MAX + 3):
        assert collect_stats(f"{base}{i}", "e")["export"] is not None


# -- flight recorder ----------------------------------------------------------------


def test_flight_recorder_ring_and_render():
    fr = FlightRecorder(depth=4, name="edge e1")
    for i in range(6):
        fr.note("frame", seq=i)
    assert len(fr) == 4
    assert [kv["seq"] for _, _, kv in fr.events()] == [2, 3, 4, 5]
    text = fr.render()
    assert "flight recorder [edge e1]" in text
    assert "seq=5" in text and "seq=0" not in text
    assert FlightRecorder().render() == "(flight recorder empty)"


def test_attach_flight_staples_timeline_and_is_idempotent():
    fr = FlightRecorder(name="edge e2")
    fr.note("import.open", dataset="t")
    fr.note("import.lease_lost")
    e = BrokenPipeError("lease lost")
    assert attach_flight(e, fr) is e
    assert "import.lease_lost" in e.flight_timeline
    assert "import.lease_lost" in str(e)  # visible in a bare traceback
    first = str(e)
    attach_flight(e, fr)  # second staple is a no-op
    assert str(e) == first
    # empty recorders attach nothing (clear the global fault recorder
    # too — attach_flight auto-includes it when non-empty, and earlier
    # suites may have fed it)
    telemetry.fault_recorder.clear()
    e2 = ValueError("x")
    attach_flight(e2, FlightRecorder())
    assert getattr(e2, "flight_timeline", None) is None


def test_attach_flight_appends_dump_file(tmp_path, monkeypatch):
    dump = tmp_path / "flight.txt"
    monkeypatch.setenv("PIPEGEN_FLIGHT_DUMP", str(dump))
    fr = FlightRecorder(name="edge e3")
    fr.note("export.open")
    attach_flight(OSError("boom"), fr)
    assert dump.exists() and "export.open" in dump.read_text()


def test_raised_pipe_error_carries_flight_timeline():
    """A real failure path: the importer's lease is lost (its renewals
    stop landing — the registration was GC'd) before any exporter shows
    up; the raised error arrives with the recorder timeline stapled."""
    from repro.core.directory import WorkerDirectory, set_directory

    d = WorkerDirectory(lease_ttl=0.2)
    d.renew = lambda *a, **k: 0  # every renewal finds the entry gone
    set_directory(d)
    pipe = DataPipeInput("db://flt?workers=1&query=f1",
                         transport="channel", lease_s=0.2)
    try:
        assert pipe._lease_lost.wait(10)
        with pytest.raises(BrokenPipeError) as ei:
            pipe.read()
        assert "flight recorder" in str(ei.value)
        assert "import.open" in ei.value.flight_timeline
        assert "import.lease_lost" in ei.value.flight_timeline
    finally:
        pipe.close()


# -- broker stats RPC + pipetop -----------------------------------------------------


def test_broker_stats_rpc_and_pipetop_render():
    from repro.core.broker import PipeBroker
    from repro.core.directory import DirectoryClient
    from repro.tools.pipetop import render

    broker = PipeBroker(serve=True, max_rings=8, lease_ttl=None,
                        hub=True).start()
    try:
        with broker.admit(tenant="acme", qos="latency", rings=2,
                          segments=2, nbytes=1 << 20):
            stats = DirectoryClient(broker.host, broker.port).stats()
        assert stats["admitted"] >= 1
        assert stats["active_by_tenant"] == {} or "acme" in str(stats)
        assert stats["grants_by"].get("acme/latency", 0) >= 1
        assert "grant_wait" in stats and stats["grant_wait"]["total"] >= 1
        assert "metrics" in stats and "counters" in stats["metrics"]
        json.dumps(stats)  # the RPC really is JSON end-to-end
        text = render(stats, now=time.time())
        assert "admitted=" in text and "acme" in text
        assert "grant wait" in text and "doorbells" in text
    finally:
        broker.stop()


def test_pipetop_renders_canned_snapshot_without_broker():
    from repro.tools.pipetop import render

    text = render({
        "admitted": 3, "queued": 1, "rejected": 2, "waiting": 4,
        "active_rings": 2, "active_segments": 2,
        "active_bytes": 3 * (1 << 20), "fds": 37,
        "active_by_qos": {"latency": 1, "bulk": 1},
        "active_by_tenant": {"acme": [2, 2, 3 * (1 << 20)]},
        "grants_by": {"acme/latency": 3},
        "rejects_by": {"acme/bulk": 2},
        "grant_wait": {"total": 3, "sum_s": 0.01, "p50_s": 0.0004,
                       "p95_s": 0.0016, "p99_s": 0.0016},
        "hub_registered": 2, "hub_wakeups": 40, "hub_waits": 41,
        "pool": {"spsc_parked": 1, "broadcast_parked": 0},
        "buffer_pool": {"hits": 10, "misses": 2, "bytes_retained": 4096},
        "metrics": {"histograms": {"feeder.get_wait_s": {
            "total": 21, "sum": 5.75, "p50": 0.0001, "p95": 0.0016}}},
    })
    assert "queue_depth=4" in text
    assert "input wait  n=21 sum=5.75s" in text
    assert "acme" in text and "latency=3" in text and "bulk=2" in text
    assert "registered=2" in text
    assert "hit/miss=10/2" in text
    # empty snapshot must not crash either
    assert "no tenants yet" in render({})


def test_pipetop_cli_once_against_live_broker(capsys):
    from repro.core.broker import PipeBroker
    from repro.tools.pipetop import main as pipetop_main

    broker = PipeBroker(serve=True, lease_ttl=None).start()
    try:
        rc = pipetop_main(["--port", str(broker.port), "--once"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "pipetop" in out and "admission" in out
    finally:
        broker.stop()

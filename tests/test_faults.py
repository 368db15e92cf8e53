"""Fault injection and self-healing transfers: the seeded fault harness
itself (determinism, kill/errno raising, suppression), resumable edges
(kill the importer mid-transfer on every transport, assert bit-identical
recovery with exactly one retry and a re-send bounded by the acked
watermark), transient-errno retry, the shm->socket failover ladder,
corruption recovery via full re-run, doorbell-degrade (broken doorbells
fall back to polling, transfer still completes), leased directory
registrations (expiry GC, renewal liveness), and the crash sweep that
unlinks orphaned ring segments *and* their doorbell fifos.

Seeded via ``PIPEGEN_FAULT_SEED`` so CI can run the same scenarios under
several fixed seeds (the chaos leg); every assertion is seed-independent
— the seed only perturbs rule evaluation order and jitter.
"""

import errno
import multiprocessing
import os
import signal
import threading
import time

import pytest

from repro.core import faults
from repro.core.datapipe import DataPipeInput, PipeConfig
from repro.core.directory import Endpoint, WorkerDirectory, set_directory
from repro.core.faults import FaultPlan, InjectedPeerDeath
from repro.core.plan import plan
from repro.core.shm_ring import (
    ShmRing,
    _db_path,
    doorbell_supported,
    sweep_orphans,
)
from repro.core.transport import Channel
from repro.engines import make_engine, make_paper_block
from repro.engines.base import assert_blocks_equal

SEED = int(os.environ.get("PIPEGEN_FAULT_SEED", "42"))

needs_doorbell = pytest.mark.skipif(
    not doorbell_supported(), reason="platform has no eventfd/fifo doorbell")

_mp = multiprocessing.get_context("spawn")
JOIN_S = 60

N_ROWS = 640
BLOCK_ROWS = 64  # -> 10 data frames per transfer
N_BLOCKS = N_ROWS // BLOCK_ROWS


def _edge_cfg(transport: str) -> PipeConfig:
    return PipeConfig(mode="arrowcol", block_rows=BLOCK_ROWS,
                      transport=transport)


def _one_edge(src, dst, transport: str, **options):
    set_directory(WorkerDirectory())
    return (plan(negotiate=False)
            .move(src, "t", dst, "t2", config=_edge_cfg(transport),
                  timeout=30)
            .options(**options)
            .compile()
            .execute(raise_on_error=False))


def _engines(seed: int = 7):
    src, dst = make_engine("colstore"), make_engine("colstore")
    block = make_paper_block(N_ROWS, seed=seed)
    src.put_block("t", block)
    return src, dst, block


# -- the harness itself -------------------------------------------------------------


def test_fault_plan_is_deterministic_per_seed():
    def run(seed):
        p = (FaultPlan(seed)
             .drop("transport.send", count=-1, prob=0.3)
             .duplicate("transport.send", count=-1, prob=0.1))
        out = []
        with faults.use(p):
            for _ in range(60):
                out.append(faults.fire("transport.send",
                                       transport="socket", kind=b"B"))
        return out, len(p.events)

    a, na = run(SEED)
    b, nb = run(SEED)
    assert a == b and na == nb  # same seed, same event order -> same fires
    assert 0 < na < 60  # probabilistic rules actually both fired and passed


def test_fire_raises_kill_and_errno_and_respects_suppression():
    p = (FaultPlan(SEED)
         .kill("transport.recv", at=1)
         .fail_errno("transport.send", errno.ECONNRESET, at=1))
    with faults.use(p):
        with faults.suppressed():  # masked: rules must not consume events
            assert faults.fire("transport.recv", transport="socket") is None
        with pytest.raises(InjectedPeerDeath) as death:
            faults.fire("transport.recv", transport="socket")
        assert isinstance(death.value, BrokenPipeError)  # the pipe contract
        with pytest.raises(OSError) as oe:
            faults.fire("transport.send", transport="socket", kind=b"S")
        assert oe.value.errno == errno.ECONNRESET
    assert p.fired("transport.recv") == 1 and p.fired() == 2


def test_rules_fire_on_nth_matching_event_only():
    p = FaultPlan(SEED).drop("transport.send", at=3, kind=b"B")
    with faults.use(p):
        # non-matching kinds do not advance the rule's event counter
        assert faults.fire("transport.send", transport="socket",
                           kind=b"S") is None
        for want in (None, None, "drop", None):
            got = faults.fire("transport.send", transport="socket",
                              kind=b"B")
            assert got == want


def test_drop_rpc_eats_a_directory_operation():
    d = WorkerDirectory()
    with faults.use(FaultPlan(SEED).drop_rpc("register")):
        with pytest.raises(ConnectionResetError):
            d.register("ds", Endpoint(channel=Channel()), "q0")
        d.register("ds", Endpoint(channel=Channel()), "q0")  # rule spent
    assert d.query("ds", "q0", timeout=1.0).is_channel


# -- resumable edges: kill the importer mid-transfer --------------------------------


@pytest.mark.parametrize("transport", ["socket", "channel", "shm"])
def test_kill_importer_midstream_resumes_bit_identical(transport):
    """The acceptance scenario: the importer dies on its 5th frame recv
    (schema, RESUME hello, two data blocks acked), the retry re-opens the
    edge, the exporter skips exactly the acked watermark, and the result
    is bit-identical — with exactly one retry."""
    src, dst, block = _engines()
    fp = FaultPlan(SEED).kill("transport.recv", at=5, count=1)
    with faults.use(fp):
        res = _one_edge(src, dst, transport, retries=1, failover=False)
    assert not res.exceptions, res.errors
    r = res.single()
    assert_blocks_equal(dst.get_block("t2"), block,
                        check_names=False)  # bit-identical data
    assert len(r.attempts) == 2  # exactly one retry
    assert r.attempts[0]["ok"] is False and r.attempts[1]["ok"] is True
    assert r.attempts[1]["transport"] == transport  # failover disabled
    assert r.errors and r.errors[0].startswith("attempt 0")
    # the re-send is bounded by the watermark gap: the importer acked two
    # data frames before dying, so the retry replays those locally and
    # the exporter ships only the remaining 8 (+ schema, hello, EOF)
    watermark = r.import_stats.resume_replayed
    assert watermark == 2
    assert r.export_stats.resume_skipped == watermark
    assert r.export_stats.frames_sent == (N_BLOCKS - watermark) + 3
    assert r.rows == N_ROWS


def test_transient_send_errno_is_retried_with_resume():
    """A transient sendmsg failure (ECONNRESET on the 4th frame = the 2nd
    data block) costs one retry; the first block was already acked, so the
    retry moves only the tail."""
    src, dst, block = _engines(seed=11)
    fp = FaultPlan(SEED).fail_errno("transport.send", errno.ECONNRESET,
                                    at=4, count=1)
    with faults.use(fp):
        res = _one_edge(src, dst, "socket", retries=2, failover=False)
    assert not res.exceptions, res.errors
    r = res.single()
    assert_blocks_equal(dst.get_block("t2"), block, check_names=False)
    assert len(r.attempts) == 2
    assert r.import_stats.resume_replayed == 1
    assert r.export_stats.resume_skipped == 1


def test_failover_ladder_shm_to_socket():
    """A transport-level fault on a shm edge retries over the socket
    rendezvous instead (the colocation assumption may itself be what
    broke); the attempt history records the ladder step."""
    src, dst, block = _engines(seed=5)
    fp = FaultPlan(SEED).fail_errno("transport.send", errno.EIO, at=3,
                                    count=1, transport="shm")
    with faults.use(fp):
        res = _one_edge(src, dst, "shm", retries=1)  # failover defaults on
    assert not res.exceptions, res.errors
    r = res.single()
    assert_blocks_equal(dst.get_block("t2"), block, check_names=False)
    assert [a["transport"] for a in r.attempts] == ["shm", "socket"]
    assert any("failover: shm -> socket" in e for e in r.errors)


def test_corrupt_schema_frame_recovers_via_full_rerun():
    """Corruption is the one failure resume must NOT heal: the poisoned
    frame is already staged in the importer's ledger, so the edge opts out
    of resume and the retry re-runs from frame 0."""
    src, dst, block = _engines(seed=3)
    fp = FaultPlan(SEED).corrupt("transport.send", at=1, count=1)
    with faults.use(fp):
        res = _one_edge(src, dst, "socket", retries=1, resume=False,
                        failover=False)
    assert not res.exceptions, res.errors
    r = res.single()
    assert_blocks_equal(dst.get_block("t2"), block, check_names=False)
    assert len(r.attempts) == 2
    # full re-run: nothing replayed, nothing skipped, all frames re-sent
    assert r.import_stats.resume_replayed == 0
    assert r.export_stats.resume_skipped == 0
    assert r.export_stats.frames_sent == N_BLOCKS + 2  # S + blocks + EOF


def test_retry_budget_deadline_caps_attempts():
    """An edge that keeps dying stops retrying once the deadline budget
    is spent, and says so in the error history."""
    src, dst, _ = _engines(seed=9)
    fp = FaultPlan(SEED).kill("transport.recv", count=-1)  # every recv dies
    t0 = time.monotonic()
    with faults.use(fp):
        res = _one_edge(src, dst, "socket", retries=50, backoff=0.2,
                        deadline=0.5, failover=False)
    assert res.exceptions  # genuinely unrecoverable
    r = res.single()
    assert 1 <= len(r.attempts) < 51
    assert any("retry budget exhausted" in e for e in r.errors)
    assert time.monotonic() - t0 < 10.0


# -- doorbell degrade (satellite: broken doorbell -> polling, not a hang) -----------


@needs_doorbell
def test_broken_doorbell_degrades_to_polling_and_completes():
    src, dst, block = _engines(seed=13)
    fp = (FaultPlan(SEED)
          .break_doorbell()
          # hold the first frame back long enough that the importer's
          # wait outlives the spin window and must poll-sleep
          .delay("transport.send", 0.05, at=1, count=1))
    with faults.use(fp):
        res = _one_edge(src, dst, "shm")
    assert not res.exceptions, res.errors
    r = res.single()
    assert_blocks_equal(dst.get_block("t2"), block, check_names=False)
    assert fp.fired("shm.doorbell.open") > 0  # the break actually happened
    total_polls = (r.import_stats.poll_sleeps + r.export_stats.poll_sleeps)
    assert total_polls > 0  # degraded to the capped-poll path, not a hang


# -- flight recorder: terminal failures arrive with a timeline ----------------------


def test_terminal_failure_carries_flight_timeline(tmp_path, monkeypatch):
    """An unrecoverable seeded fault must surface with the edge's flight
    recorder stapled on: the raised error names the injected fault and
    the attempts, and the chaos CI leg's PIPEGEN_FLIGHT_DUMP file gets a
    copy it can assert on."""
    from repro.core import telemetry

    dump = tmp_path / "flight.txt"
    monkeypatch.setenv("PIPEGEN_FLIGHT_DUMP", str(dump))
    src, dst, _ = _engines(seed=21)
    fp = FaultPlan(SEED).kill("transport.recv", count=-1)  # every recv dies
    with faults.use(fp):
        res = _one_edge(src, dst, "socket", retries=1, backoff=0.01,
                        failover=False)
    assert res.exceptions
    e = res.exceptions[0]
    timeline = getattr(e, "flight_timeline", None)
    assert timeline is not None  # the error carries its causal history
    assert "edge.attempt" in timeline
    assert "fault.injected" in timeline  # the seeded kill shows up
    assert "edge.attempt" in str(e)  # visible in a bare traceback too
    assert dump.exists() and "fault.injected" in dump.read_text()
    assert len(telemetry.fault_recorder) > 0


# -- leased registrations -----------------------------------------------------------


def test_lease_expiry_gc_drops_unrenewed_registration():
    d = WorkerDirectory(lease_ttl=0.15)
    d.register("stale", Endpoint(channel=Channel()), "q0")
    time.sleep(0.3)
    with pytest.raises(TimeoutError):
        d.query("stale", "q0", timeout=0.05)
    assert d.renew("stale", "q0") == 0  # too late: caller must re-register


def test_importer_lease_renewal_keeps_slow_rendezvous_alive():
    """A DataPipeInput opened with ``lease_s`` renews its own registration
    in the background: an exporter that shows up only after several TTLs
    still finds the endpoint (liveness by heartbeat, not luck)."""
    d = WorkerDirectory(lease_ttl=0.2)
    set_directory(d)
    pipe = DataPipeInput("db://leased?workers=1&query=L1",
                         config=PipeConfig(transport="channel", lease_s=0.2))
    try:
        time.sleep(0.65)  # > 3 lease TTLs
        ep = d.query("leased", "L1", timeout=0.5)
        assert ep.is_channel
        assert ep.lease_deadline > 0  # the entry really was leased
    finally:
        pipe.close()


# -- crash sweep: orphaned segments AND their doorbell fifos ------------------------


def _child_create_ring_and_die(name):
    from multiprocessing import resource_tracker

    ring = ShmRing.create(capacity=8192, name=name, role="reader")
    try:  # simulate a true crash leak: nobody tracks the segment
        resource_tracker.unregister(ring.shm._name, "shared_memory")
    except Exception:
        pass
    os.kill(os.getpid(), signal.SIGKILL)


@needs_doorbell
def test_directory_sweep_unlinks_orphan_segment_and_fifos():
    name = f"pgring-sweeptest{os.getpid():x}"
    fifos = [_db_path(name, "w"), _db_path(name, "r0")]
    p = _mp.Process(target=_child_create_ring_and_die, args=(name,))
    p.start()
    p.join(JOIN_S)
    assert not p.is_alive()
    assert os.path.exists(f"/dev/shm/{name}")  # the leak is real
    assert all(os.path.exists(f) for f in fifos)
    swept = WorkerDirectory().sweep(orphan_min_age_s=0.0)
    assert name in swept
    assert not os.path.exists(f"/dev/shm/{name}")
    assert not any(os.path.exists(f) for f in fifos)  # fifos swept too


@needs_doorbell
def test_sweep_removes_fifos_whose_segment_is_already_gone():
    # a process can die between fifo creation and segment registration —
    # or a foreign cleaner can take the segment first; either way the
    # fifos must not outlive it
    name = f"pgring-fifoonly{os.getpid():x}"
    fifos = [_db_path(name, "w"), _db_path(name, "r0")]
    for f in fifos:
        os.mkfifo(f)
    try:
        swept = sweep_orphans(min_age_s=0.0)
        assert all(os.path.basename(f) in swept for f in fifos)
        assert not any(os.path.exists(f) for f in fifos)
    finally:
        for f in fifos:
            try:
                os.unlink(f)
            except FileNotFoundError:
                pass


# -- continuous pipes: exporter crash + watermark resume ----------------------------


def _crash_publisher_child(port, name, start_epoch, n_epochs, ready):
    from repro.core.directory import DirectoryClient
    from repro.core.subscribe import Publication

    client = DirectoryClient("127.0.0.1", port)
    schema = make_paper_block(1).schema
    pub = Publication(name, schema, directory=client,
                      start_epoch=start_epoch)
    for e in range(start_epoch + 1, start_epoch + n_epochs + 1):
        pub.commit([make_paper_block(BLOCK_ROWS, seed=1000 + e)])
    ready.set()
    time.sleep(JOIN_S)  # parked: the parent SIGKILLs (crash) or reaps us


def test_dead_requester_query_does_not_eat_registration():
    """The endpoint-pop handoff must survive a requester that dies between
    asking and hearing the answer.  A SIGKILLed publisher leaves exactly
    such a query parked in a directory handler; when a new subscriber
    registers, that dead query pops the endpoint and writes the response
    into a void.  Without the ack/restitution handshake the registration
    is consumed forever and the live subscriber starves."""
    import json as _json
    import socket

    from repro.core.directory import DirectoryClient, DirectoryServer

    server = DirectoryServer().start()
    try:
        # park a query server-side, then "die" without reading the answer
        s = socket.create_connection(("127.0.0.1", server.port), timeout=10)
        s.sendall(_json.dumps(
            {"op": "query", "dataset": "ds", "query_id": "q",
             "timeout": 30.0}).encode() + b"\n")
        time.sleep(0.3)  # let the handler park in the rendezvous wait
        s.close()

        client = DirectoryClient("127.0.0.1", server.port)
        client.register("ds", Endpoint(host="127.0.0.1", port=12345), "q")
        # the dead query races us to the pop; whether it wins or not, the
        # endpoint must end up with the live query below
        ep = client.query("ds", "q", timeout=JOIN_S)
        assert ep.port == 12345
    finally:
        server.stop()


def test_publisher_sigkill_watermark_resume_over_socket():
    """Exporter crash + restart heals via re-publish + resubscribe: a
    publisher process is SIGKILLed mid-stream; its successor re-publishes
    the same name starting at the crashed head, and the subscriber —
    resubscribing at its watermark — receives exactly the missing epochs
    as replayed deltas (no snapshot, no duplicates), folding to the full
    relation bit-identically."""
    from repro.core.directory import DirectoryClient, DirectoryServer
    from repro.core.subscribe import Subscription
    from repro.core.types import ColumnBlock

    server = DirectoryServer().start()
    name = f"crash.pub{os.getpid():x}"
    p1 = p2 = None
    try:
        ready1 = _mp.Event()
        p1 = _mp.Process(target=_crash_publisher_child,
                         args=(server.port, name, 0, 3, ready1))
        p1.start()
        assert ready1.wait(JOIN_S)
        client = DirectoryClient("127.0.0.1", server.port)
        sub = Subscription(name, watermark=0, directory=client,
                           transport="socket", timeout=JOIN_S)
        got = []
        deadline = time.monotonic() + JOIN_S
        while len(got) < 3 and time.monotonic() < deadline:
            got.extend(sub.poll(timeout=0.2))
        assert [e.epoch for e in got] == [1, 2, 3]
        p1.kill()  # SIGKILL: no EOF courtesy, no unpublish, no lease release
        p1.join(JOIN_S)
        with pytest.raises(BrokenPipeError):
            deadline = time.monotonic() + JOIN_S
            while time.monotonic() < deadline:
                got.extend(sub.poll(timeout=0.2))
        wm = sub.watermark
        assert wm == 3  # the watermark survives the wreck
        sub.close()

        ready2 = _mp.Event()
        p2 = _mp.Process(target=_crash_publisher_child,
                         args=(server.port, name, wm, 2, ready2))
        p2.start()
        assert ready2.wait(JOIN_S)
        sub2 = Subscription(name, watermark=wm, directory=client,
                            transport="socket", timeout=JOIN_S)
        try:
            more = []
            deadline = time.monotonic() + JOIN_S
            while len(more) < 2 and time.monotonic() < deadline:
                more.extend(sub2.poll(timeout=0.2))
            assert [e.epoch for e in more] == [4, 5]
            assert all(e.kind == "delta" for e in more)  # replay, no snapshot
        finally:
            sub2.close()
        folded = ColumnBlock.concat([e.block for e in got + more])
        expect = ColumnBlock.concat(
            [make_paper_block(BLOCK_ROWS, seed=1000 + e)
             for e in range(1, 6)])
        assert_blocks_equal(folded, expect)
    finally:
        for p in (p1, p2):
            if p is not None and p.is_alive():
                p.kill()
                p.join(JOIN_S)
        server.stop()

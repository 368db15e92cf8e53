"""Broadcast shm ring (one writer, R reader cursor slots): bit-identical
1→3 delivery in and across processes, slow-reader backpressure via
min-tail recycling, reader-SIGKILL eviction that must not wedge the
writer, and the directory's join/publish broadcast rendezvous."""

import multiprocessing
import os
import signal
import threading
import time

import numpy as np
import pytest

from repro.core.datapipe import DataPipeInput, DataPipeOutput, PipeConfig
from repro.core.directory import (
    DirectoryClient,
    DirectoryServer,
    Endpoint,
    WorkerDirectory,
    set_directory,
)
from repro.core.shm_ring import ShmRing, ShmRingTransport
from repro.core.transport import FRAME_EOF, FRAME_TEXT
from repro.core.types import ColumnBlock
from repro.engines.base import assert_blocks_equal, make_paper_block

_mp = multiprocessing.get_context("spawn")

JOIN_S = 60


def _join_or_kill(procs):
    deadline = time.monotonic() + JOIN_S
    for p in procs:
        p.join(max(0.1, deadline - time.monotonic()))
    hung = [p for p in procs if p.is_alive()]
    for p in hung:
        p.kill()
        p.join(5)
    assert not hung, "child process hung (broadcast ring must fail fast)"


# -- directory rendezvous -----------------------------------------------------------


def test_join_broadcast_hands_out_slots_and_blocks_on_publication():
    d = WorkerDirectory()
    got = {}

    def join_late(i):
        got[i] = d.join_broadcast("ds", "q", readers=3, timeout=10.0)

    t1 = threading.Thread(target=join_late, args=(1,), daemon=True)
    t2 = threading.Thread(target=join_late, args=(2,), daemon=True)
    slot, ep = d.join_broadcast("ds", "q", readers=3)
    assert (slot, ep) == (0, None)  # first joiner creates the ring
    t1.start()
    t2.start()
    time.sleep(0.1)
    assert not got  # later joiners block until publication
    d.publish_broadcast("ds", Endpoint(shm_name="seg", shm_capacity=64,
                                       broadcast=3, shared=True), "q",
                        import_workers=1)
    t1.join(JOIN_S)
    t2.join(JOIN_S)
    slots = sorted(s for s, _ in got.values())
    assert slots == [1, 2]
    assert all(e.shm_name == "seg" and e.broadcast == 3
               for _, e in got.values())
    # the publication doubles as the exporter-facing registration
    assert d.query("ds", "q", export_workers=1).shm_name == "seg"


def test_join_broadcast_rejects_mismatch_and_exhaustion():
    d = WorkerDirectory()
    slot, ep = d.join_broadcast("ds", "q", readers=2)
    assert (slot, ep) == (0, None)
    with pytest.raises(IOError, match="disagree"):
        d.join_broadcast("ds", "q", readers=3)
    d.publish_broadcast("ds", Endpoint(shm_name="seg", shm_capacity=64,
                                       broadcast=2, shared=True), "q")
    slot, ep = d.join_broadcast("ds", "q", readers=2)
    assert slot == 1 and ep.shm_name == "seg"
    with pytest.raises(IOError, match="already claimed"):
        d.join_broadcast("ds", "q", readers=2)


# -- in-process delivery ------------------------------------------------------------


def test_broadcast_1x3_bit_identical_in_process():
    set_directory(WorkerDirectory())
    name = "db://bcast-inproc?query=1"
    block = make_paper_block(5000, seed=7, strings=True)
    got = {}

    def imp(i):
        pipe = DataPipeInput(name, config=PipeConfig(
            transport="shm", broadcast=3, shm_capacity=1 << 20))
        got[i] = list(pipe.blocks())
        pipe.close()
        got[f"stats{i}"] = pipe.stats

    ts = [threading.Thread(target=imp, args=(i,), daemon=True)
          for i in range(3)]
    for t in ts:
        t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=512))
    out.write_block(block)
    out.close()
    for t in ts:
        t.join(JOIN_S)
    assert not any(t.is_alive() for t in ts)
    for i in range(3):
        assert_blocks_equal(block, ColumnBlock.concat(got[i]),
                            check_names=False)
        assert got[f"stats{i}"].shm_spans > 0  # decoded in place
    # the writer encoded ONE stream: schema + ceil(5000/512) blocks + EOF
    assert out.stats.blocks == 10
    assert out.stats.frames_sent == 12


def test_broadcast_slow_reader_applies_backpressure():
    """Recycling is gated on min(tails): a lagging reader stalls the
    writer (bounded memory), and draining it releases everything to
    everyone."""
    ring = ShmRing.create(capacity=4096, role="reader", readers=2)
    fast = ShmRingTransport(ring)  # creator holds slot 0
    slow_ring = ShmRing.attach(ring.name, role="reader", slot=1)
    slow = ShmRingTransport(slow_ring)
    tx = ShmRingTransport(ShmRing.attach(ring.name, role="writer"))
    n_frames, payload = 16, b"x" * 1000
    sent = []

    def send():
        for i in range(n_frames):
            tx.send_frames(FRAME_TEXT, [payload])
            sent.append(i)

    th = threading.Thread(target=send, daemon=True)
    th.start()
    for _ in range(3):  # the fast reader takes what already fits
        kind, p = fast.recv_frame()
        assert (kind, bytes(p)) == (FRAME_TEXT, payload)
    time.sleep(0.3)
    # at most ~4 frames fit in 4096 bytes and slot 1 has consumed none:
    # the writer must be blocked on the slow cursor, not overwriting
    assert th.is_alive() and len(sent) < n_frames
    got = {0: 3, 1: 0}

    def drain(rx, idx, want):
        for _ in range(want):
            kind, p = rx.recv_frame()
            assert (kind, bytes(p)) == (FRAME_TEXT, payload)
            got[idx] += 1

    d0 = threading.Thread(target=drain, args=(fast, 0, n_frames - 3),
                          daemon=True)
    d1 = threading.Thread(target=drain, args=(slow, 1, n_frames),
                          daemon=True)
    d0.start()
    d1.start()
    th.join(JOIN_S)
    d0.join(JOIN_S)
    d1.join(JOIN_S)
    assert len(sent) == n_frames
    assert got == {0: n_frames, 1: n_frames}  # every frame, both readers
    tx.close()
    slow_ring.close()
    ring.close()


# -- cross-process children ---------------------------------------------------------


def _child_bcast_importer(dir_addr, name, q, idx):
    set_directory(DirectoryClient(*dir_addr))
    pipe = DataPipeInput(name, config=PipeConfig(
        transport="shm", broadcast=3, shm_capacity=1 << 20))
    rows = 0
    key_sum = 0
    for block in pipe.blocks():
        rows += len(block)
        key_sum += int(np.asarray(block.columns[0]).sum())
    pipe.close()
    q.put((idx, rows, key_sum, pipe.stats.shm_spans))


def _child_bcast_exporter(dir_addr, name, n_rows, q):
    set_directory(DirectoryClient(*dir_addr))
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=512))
    out.write_block(make_paper_block(n_rows, seed=11))
    out.close()
    q.put(("exp", out.stats.blocks, out.stats.frames_sent))


def test_broadcast_1x3_across_processes():
    """Three importer processes and one exporter process share ONE ring
    through the DirectoryServer's join/publish rendezvous; the exporter
    encodes each block exactly once."""
    n_rows = 8000
    server = DirectoryServer().start()
    try:
        q = _mp.Queue()
        name = "db://bcast-xproc?query=b1"
        addr = (server.host, server.port)
        procs = [
            _mp.Process(target=_child_bcast_importer,
                        args=(addr, name, q, i))
            for i in range(3)
        ]
        procs.append(_mp.Process(target=_child_bcast_exporter,
                                 args=(addr, name, n_rows, q)))
        for p in procs:
            p.start()
        # 2x margin: four simultaneous spawns each pay interpreter+import
        # startup, which stacks up on a loaded CI box
        results = [q.get(timeout=2 * JOIN_S) for _ in range(4)]
        _join_or_kill(procs)
        exp = next(r for r in results if r[0] == "exp")
        imps = [r for r in results if r[0] != "exp"]
        assert len(imps) == 3
        want_sum = n_rows * (n_rows - 1) // 2
        for _, rows, key_sum, spans in imps:
            assert rows == n_rows
            assert key_sum == want_sum  # bit-identical key column
            assert spans > 0
        # one export: ceil(8000/512) = 16 blocks, sent once, not thrice
        assert exp[1] == 16
    finally:
        server.stop()


def _child_bcast_reader_then_die(name, slot, frames_before_death, attached):
    ring = ShmRing.attach(name, role="reader", slot=slot)
    rx = ShmRingTransport(ring)
    attached.set()
    for _ in range(frames_before_death):
        rx.recv_frame()
    os.kill(os.getpid(), signal.SIGKILL)  # no close, slot left attached


def test_broadcast_reader_sigkill_is_evicted_not_wedging_writer():
    """A SIGKILLed reader's cursor stops moving; the writer must evict it
    by pid-probe once blocked and keep feeding the survivors."""
    ring = ShmRing.create(capacity=8192, role="reader", readers=2)
    attached = _mp.Event()
    p = _mp.Process(target=_child_bcast_reader_then_die,
                    args=(ring.name, 1, 2, attached))
    p.start()
    assert attached.wait(JOIN_S)
    tx = ShmRingTransport(ShmRing.attach(ring.name, role="writer"),
                          send_timeout=30.0)
    rx = ShmRingTransport(ring)
    n_frames, payload = 64, b"y" * 1024  # far beyond one ring's worth
    recvd = []

    def drain():
        for _ in range(n_frames):
            kind, pl = rx.recv_frame()
            recvd.append(bytes(pl))

    td = threading.Thread(target=drain, daemon=True)
    td.start()
    for _ in range(n_frames):  # must neither hang nor raise
        tx.send_frames(FRAME_TEXT, [payload])
    td.join(JOIN_S)
    assert not td.is_alive()
    assert recvd == [payload] * n_frames  # the survivor got everything
    assert tx.ring.readers_evicted >= 1
    _join_or_kill([p])
    tx.close()
    rx.close()


def _child_bcast_writer_then_die(name):
    w = ShmRingTransport(ShmRing.attach(name, role="writer"))
    for i in range(3):
        w.send_frames(FRAME_TEXT, [b"frame-%d" % i])
    os.kill(os.getpid(), signal.SIGKILL)


def test_broadcast_ring_pools_and_reuses_warm_segments():
    """A cleanly drained broadcast group parks its segment; the next
    group of the same shape re-leases it warm (slot table re-reserved,
    lease epoch bumped) and still delivers only its own data."""
    from repro.core.shm_ring import acquire_broadcast_ring

    cap = 20480  # capacity no other test parks

    def one_group(payloads):
        ring = acquire_broadcast_ring(cap, readers=2)
        r1 = ShmRing.attach(ring.name, role="reader", slot=1)
        tx = ShmRingTransport(ShmRing.attach(ring.name, role="writer"))
        rx0, rx1 = ShmRingTransport(ring), ShmRingTransport(r1)
        for p in payloads:
            tx.send_frames(FRAME_TEXT, [p])
        tx.send_frames(FRAME_EOF, [b""])
        got = {0: [], 1: []}
        for idx, rx in ((1, rx1), (0, rx0)):  # peer drains+closes first,
            while True:                       # so the owner's park lands
                kind, p = rx.recv_frame()
                if kind == FRAME_EOF:
                    break
                got[idx].append(bytes(p))
            rx.close()
        tx.close()
        assert got[0] == got[1] == payloads
        return ring

    r_a = one_group([b"group-a-%d" % i for i in range(4)])
    r_b = one_group([b"group-b-%d" % i for i in range(6)])
    assert r_b is r_a  # warm reuse of the parked segment
    assert r_b._epoch != 0  # fresh lease epoch: stale words cannot match
    # drain the pool so later tests see a clean slate
    r_c = acquire_broadcast_ring(cap, readers=2)
    assert r_c is r_a
    r_c.reader_close()


def test_broadcast_reserved_slot_evicted_after_claim_grace(monkeypatch):
    """An importer that dies between the directory join and the ring
    attach leaves its slot RESERVED; once the claim grace expires the
    writer evicts it instead of wedging, and a too-late attach fails
    loudly (its frames are already recycled)."""
    import repro.core.shm_ring as sr

    monkeypatch.setattr(sr, "_RESERVED_GRACE", 0.3)
    ring = ShmRing.create(capacity=4096, role="reader", readers=2)
    tx = ShmRingTransport(ShmRing.attach(ring.name, role="writer"),
                          send_timeout=30.0)
    rx = ShmRingTransport(ring)
    n_frames, payload = 32, b"x" * 1000  # far beyond one ring's worth
    got = []

    def drain():
        for _ in range(n_frames):
            kind, p = rx.recv_frame()
            got.append(bytes(p))

    td = threading.Thread(target=drain, daemon=True)
    td.start()
    for _ in range(n_frames):  # blocks on the reserved slot, then evicts
        tx.send_frames(FRAME_TEXT, [payload])
    td.join(JOIN_S)
    assert got == [payload] * n_frames
    assert tx.ring.readers_evicted >= 1
    with pytest.raises(IOError, match="evicted"):
        ShmRing.attach(ring.name, role="reader", slot=1)
    tx.close()
    rx.close()


def test_broadcast_writer_death_drains_then_eof():
    """Writer dies uncleanly: every reader drains what was published and
    then sees end-of-stream (same contract as the SPSC ring)."""
    ring = ShmRing.create(capacity=8192, role="reader", readers=2)
    r1 = ShmRing.attach(ring.name, role="reader", slot=1)

    p = _mp.Process(target=_child_bcast_writer_then_die, args=(ring.name,))
    p.start()
    for rx in (ShmRingTransport(ring), ShmRingTransport(r1)):
        got = []
        while True:
            kind, payload = rx.recv_frame()
            if kind == FRAME_EOF:
                break
            got.append(bytes(payload))
        assert got == [b"frame-0", b"frame-1", b"frame-2"]
    _join_or_kill([p])
    r1.close()
    ring.close()


def test_broadcast_warm_park_survives_straggler_readers():
    """The owner closing first must not forfeit warm reuse: a group whose
    peers finish far apart (well past the old ~20 ms inline probe) hands
    the segment to the background parker, which pools it once the last
    straggler drains — instead of unlinking and paying first-touch faults
    on the next group."""
    import repro.core.shm_ring as sr

    cap = 24576  # capacity no other test parks
    ring = sr.acquire_broadcast_ring(cap, readers=2)
    name = ring.name
    r1 = ShmRing.attach(name, role="reader", slot=1)
    tx = ShmRingTransport(ShmRing.attach(name, role="writer"))
    rx0, rx1 = ShmRingTransport(ring), ShmRingTransport(r1)
    for i in range(3):
        tx.send_frames(FRAME_TEXT, [b"warm-%d" % i])
    tx.send_frames(FRAME_EOF, [b""])
    tx.close()
    # the OWNER (slot 0) drains and closes first, straggler still attached
    while rx0.recv_frame()[0] != FRAME_EOF:
        pass
    rx0.close()  # must hand off to the background parker, not unlink

    def parked() -> bool:
        with sr._park_lock:
            return any(r.name == name
                       for lst in sr._bc_parked.values() for r in lst)

    time.sleep(0.1)  # well past the old inline probe window
    assert not parked()  # straggler still live: segment not pooled yet
    got = []
    while True:  # the straggler can still read: segment was not unlinked
        kind, p = rx1.recv_frame()
        if kind == FRAME_EOF:
            break
        got.append(bytes(p))
    assert got == [b"warm-0", b"warm-1", b"warm-2"]
    rx1.close()
    deadline = time.monotonic() + 3 * sr._BC_PARK_WAIT
    while not parked() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert parked()  # background parker pooled it after the stragglers left
    r2 = sr.acquire_broadcast_ring(cap, readers=2)
    assert r2.name == name  # warm reuse
    assert r2._epoch != 0  # fresh lease epoch
    r2.reader_close()  # drain the pool so later tests see a clean slate

"""Shared-memory ring transport: in-process ring mechanics (wrap markers,
backpressure, oversize frames), the event-driven doorbell (idle wakeup
latency, poll fallback), the per-frame seqlock (torn publications gate,
corrupt headers fail loudly), and the cross-process integration contract
(two real OS processes, zero intermediate block materializations,
reader/writer-death fail-fast, unclean-shutdown segment cleanup)."""

import multiprocessing
import os
import signal
import struct
import threading
import time
from multiprocessing import shared_memory

import numpy as np
import pytest

from repro.core.datapipe import DataPipeInput, DataPipeOutput, PipeConfig
from repro.core.directory import DirectoryClient, DirectoryServer, set_directory
from repro.core.shm_ring import (
    _FRAME,
    _KL,
    _OFF_HEAD,
    _U32,
    _token,
    ShmRing,
    ShmRingTransport,
    doorbell_supported,
)
from repro.core.transport import FRAME_BLOCK, FRAME_EOF, FRAME_TEXT
from repro.engines.base import assert_blocks_equal, make_paper_block

needs_doorbell = pytest.mark.skipif(
    not doorbell_supported(), reason="platform has no eventfd/fifo doorbell")

_mp = multiprocessing.get_context("spawn")

JOIN_S = 60  # generous: spawn pays an interpreter start per child


def _join_or_kill(procs):
    deadline = time.monotonic() + JOIN_S
    for p in procs:
        p.join(max(0.1, deadline - time.monotonic()))
    hung = [p for p in procs if p.is_alive()]
    for p in hung:
        p.kill()
        p.join(5)
    assert not hung, "child process hung (shm transport must fail fast)"


# -- in-process ring mechanics ------------------------------------------------------


def test_ring_frame_roundtrip_with_wrap_markers():
    ring = ShmRing.create(capacity=4096, role="reader")
    tx = ShmRingTransport(ring)
    rx = ShmRingTransport(ring)
    # sizes chosen to stagger across the 4096-byte region repeatedly so
    # several frames hit the wrap-marker path
    sizes = [900, 1500, 700, 1200, 3000, 10, 0, 2048] * 4
    want = [bytes([i % 251]) * n for i, n in enumerate(sizes)]
    got = []

    def recv():
        for _ in sizes:
            kind, payload = rx.recv_frame()
            # a span view is only valid until the next recv: copy now
            got.append((kind, bytes(payload)))

    t = threading.Thread(target=recv, daemon=True)
    t.start()
    for payload in want:
        tx.send_frames(FRAME_BLOCK, [payload])
    t.join(JOIN_S)
    assert not t.is_alive()
    assert [p for _, p in got] == want
    assert all(k == FRAME_BLOCK for k, _ in got)
    # header-byte accounting parity with the other transports
    assert tx.bytes_sent == sum(sizes) + 5 * len(sizes)
    assert tx.shm_spans == len(sizes)
    ring.close()


def test_ring_send_gathers_segments_in_place():
    ring = ShmRing.create(capacity=1 << 16, role="reader")
    tx, rx = ShmRingTransport(ring), ShmRingTransport(ring)
    arr = np.arange(100, dtype=np.int64)
    segs = [b"head", memoryview(b"-mid-"), bytearray(b"tail"), arr.data]
    tx.send_frames(FRAME_BLOCK, segs)
    kind, payload = rx.recv_frame()
    assert kind == FRAME_BLOCK
    assert isinstance(payload, memoryview)  # consumed in place, not copied
    assert bytes(payload) == b"head-mid-tail" + arr.tobytes()
    ring.close()


def test_ring_full_applies_backpressure():
    ring = ShmRing.create(capacity=4096, role="reader")
    tx, rx = ShmRingTransport(ring), ShmRingTransport(ring)
    n_frames, payload = 32, b"x" * 1000
    sent = []

    def send():
        for i in range(n_frames):
            tx.send_frames(FRAME_TEXT, [payload])
            sent.append(i)

    t = threading.Thread(target=send, daemon=True)
    t.start()
    time.sleep(0.3)
    # at most 4 frames fit in 4096 bytes: the sender must be blocked
    assert t.is_alive() and len(sent) < n_frames
    for _ in range(n_frames):
        kind, p = rx.recv_frame()
        assert (kind, bytes(p)) == (FRAME_TEXT, payload)
    t.join(JOIN_S)
    assert len(sent) == n_frames
    ring.close()


def test_ring_rejects_frame_larger_than_capacity():
    ring = ShmRing.create(capacity=1024, role="reader")
    tx = ShmRingTransport(ring)
    with pytest.raises(IOError, match="exceeds ring capacity"):
        tx.send_frames(FRAME_BLOCK, [b"z" * 2048])
    ring.close()


def test_ring_close_unlinks_segment():
    ring = ShmRing.create(capacity=1024, role="reader")
    name = ring.name
    ring.close()
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name=name, create=False)
    assert ShmRing.cleanup(name) is False  # nothing left behind


# -- doorbell wakeups + seqlock -----------------------------------------------------


@needs_doorbell
def test_doorbell_wakes_idle_reader_fast():
    """An idle (deep-parked) reader wakes well under the old 2 ms poll cap
    the moment a frame is committed — and never touched the poll path."""
    ring = ShmRing.create(capacity=1 << 16, role="reader")
    tx, rx = ShmRingTransport(ring), ShmRingTransport(ring)
    lats = []
    for _ in range(5):
        sent_at = []

        def send():
            time.sleep(0.08)  # reader reaches the parked doorbell wait
            sent_at.append(time.perf_counter())
            tx.send_frames(FRAME_TEXT, [b"ping"])

        th = threading.Thread(target=send, daemon=True)
        th.start()
        kind, payload = rx.recv_frame()
        lats.append(time.perf_counter() - sent_at[0])
        assert (kind, payload) == (FRAME_TEXT, b"ping")
        th.join(JOIN_S)
    assert ring.wakeups["doorbell"] >= 5
    assert ring.wakeups["poll"] == 0
    assert min(lats) < 2e-3, f"idle wakeup latencies {lats}"
    ring.close()


def _child_latency_writer(name, rounds):
    ring = ShmRing.attach(name, role="writer")
    tx = ShmRingTransport(ring)
    for _ in range(rounds):
        time.sleep(0.08)  # parent reader parks idle on the doorbell
        # CLOCK_MONOTONIC is system-wide on Linux: stamp the send time
        tx.send_frames(FRAME_TEXT, [struct.pack("<d", time.monotonic())])
    tx.send_frames(FRAME_EOF, [b""])
    tx.close()


@needs_doorbell
def test_multiprocess_doorbell_wakeup_latency():
    """The doorbell crosses process lines (the per-ring named pipe): an
    idle reader in THIS process wakes microseconds after a writer in a
    child process commits, not after a poll-backoff quantum."""
    ring = ShmRing.create(capacity=1 << 16, role="reader")
    p = _mp.Process(target=_child_latency_writer, args=(ring.name, 5))
    p.start()
    rx = ShmRingTransport(ring)
    lats = []
    while True:
        kind, payload = rx.recv_frame()
        if kind == FRAME_EOF:
            break
        lats.append(time.monotonic() - struct.unpack("<d", payload)[0])
    _join_or_kill([p])
    assert len(lats) == 5
    assert ring.wakeups["poll"] == 0
    assert ring.wakeups["doorbell"] > 0
    assert min(lats) < 2e-3, f"cross-process wakeup latencies {lats}"
    rx.close()


def test_seqlock_gates_torn_frame_until_commit():
    """A frame whose commit word was never stored (a writer dying between
    payload and publication, or head visible before payload off-TSO) reads
    as 'not ready' — never as a frame; storing the token releases it."""
    ring = ShmRing.create(capacity=4096, role="reader", doorbell=False)
    payload = b"torn"
    # hand-craft what an interrupted publication leaves behind: kind,
    # length and payload written, head advanced, commit word still clear
    _U32.pack_into(ring._data, 0, 0)
    _KL.pack_into(ring._data, _U32.size, FRAME_TEXT, len(payload))
    ring._data[_FRAME.size:_FRAME.size + len(payload)] = payload
    ring._set_u64(_OFF_HEAD, _FRAME.size + len(payload))
    with pytest.raises(TimeoutError):
        ring.recv(timeout=0.2)
    # a mismatched (stale-lap) token is equally not-ready
    _U32.pack_into(ring._data, 0, _token(12345))
    with pytest.raises(TimeoutError):
        ring.recv(timeout=0.2)
    # completing the publication releases the frame
    _U32.pack_into(ring._data, 0, _token(0))
    kind_byte, view = ring.recv(timeout=5.0)
    assert (kind_byte, bytes(view)) == (FRAME_TEXT[0], payload)
    ring.close()


def test_seqlock_corrupt_length_fails_loudly():
    ring = ShmRing.create(capacity=4096, role="reader", doorbell=False)
    _U32.pack_into(ring._data, 0, _token(0))
    _KL.pack_into(ring._data, _U32.size, FRAME_BLOCK, 999_999)
    ring._set_u64(_OFF_HEAD, 64)
    with pytest.raises(IOError, match="corrupt"):
        ring.recv(timeout=5.0)
    ring.close()


def test_pooled_ring_reuse_does_not_resurrect_stale_frames():
    """reset() rewinds the monotonic cursors but leaves the previous
    lease's frames (whose commit words are token-valid again — tokens
    derive from the byte offset alone) in the data region: the head gate
    must keep the next lease's reader from consuming them before its own
    writer publishes anything."""
    from repro.core.shm_ring import acquire_ring, attach_ring

    cap = 24576  # capacity no other test parks, so the pool hit is ours
    ring = acquire_ring(cap)
    tx = ShmRingTransport(attach_ring(ring.name))
    rx = ShmRingTransport(ring)
    tx.send_frames(FRAME_TEXT, [b"lease-one"])
    tx.send_frames(FRAME_EOF, [b""])
    assert rx.recv_frame() == (FRAME_TEXT, b"lease-one")
    assert rx.recv_frame() == (FRAME_EOF, b"")
    rx.close()  # clean EOF: parks the ring warm
    tx.close()
    ring2 = acquire_ring(cap)
    assert ring2 is ring  # same segment, stale frames still in the region
    # the new lease's reader polls before its writer attached: the stale
    # lease-one frame at offset 0 must read as "nothing published"
    with pytest.raises(TimeoutError):
        ring2.recv(timeout=0.2)
    # the epoch key guards even the weakly-ordered worst case (head
    # visible before the new frame's stores): with head hand-advanced
    # over the STALE lease-one commit word, the word must still mismatch
    assert ring2._epoch != 0  # reset() bumped the lease epoch
    ring2._set_u64(_OFF_HEAD, 64)
    with pytest.raises(TimeoutError):
        ring2.recv(timeout=0.2)
    ring2._set_u64(_OFF_HEAD, 0)
    tx2 = ShmRingTransport(attach_ring(ring2.name))
    rx2 = ShmRingTransport(ring2)
    tx2.send_frames(FRAME_TEXT, [b"lease-two"])
    assert rx2.recv_frame() == (FRAME_TEXT, b"lease-two")
    tx2.send_frames(FRAME_EOF, [b""])
    assert rx2.recv_frame() == (FRAME_EOF, b"")
    tx2.close()
    rx2.ring.reader_close()  # unlink: leave nothing parked behind


def test_poll_fallback_keeps_shm_transfers_green(monkeypatch):
    """Where the doorbell machinery is unavailable the ring must degrade
    to the backoff poll — visibly (poll_sleeps counted) but correctly."""
    import repro.core.shm_ring as sr

    monkeypatch.setattr(sr, "_DOORBELL_OK", False)
    from repro.core.directory import WorkerDirectory, set_directory as setd

    setd(WorkerDirectory())
    name = "db://fallback-shm?query=1"
    block = make_paper_block(3000, seed=9)
    got = {}

    def imp():
        pipe = DataPipeInput(name, config=PipeConfig(
            transport="shm", shm_capacity=1 << 20))
        got["blocks"] = list(pipe.blocks())
        pipe.close()
        got["stats"] = pipe.stats

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=512))
    out.write_block(block)
    out.close()
    t.join(JOIN_S)
    assert not t.is_alive()
    from repro.core.types import ColumnBlock

    assert_blocks_equal(block, ColumnBlock.concat(got["blocks"]),
                        check_names=False)
    assert got["stats"].doorbell_waits == 0
    assert got["stats"].poll_sleeps > 0  # the importer idled in the poll


# -- cross-process children ---------------------------------------------------------


def _child_importer(dir_addr, name, q):
    set_directory(DirectoryClient(*dir_addr))
    pipe = DataPipeInput(name, config=PipeConfig(
        transport="shm", shm_capacity=1 << 20))
    ring_name = pipe._transport.ring.name
    rows = 0
    key_sum = 0
    for block in pipe.blocks():
        rows += len(block)
        key_sum += int(np.asarray(block.columns[0]).sum())
    pipe.close()
    q.put(("ok", rows, key_sum, pipe.stats.shm_spans,
           pipe.stats.decode_pool_hits, ring_name))


def _child_exporter(dir_addr, name, n_rows, q):
    set_directory(DirectoryClient(*dir_addr))
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=512))
    out.write_block(make_paper_block(n_rows, seed=11))
    out.close()
    q.put(("ok", out.stats.copies_avoided, out.stats.shm_spans,
           out.stats.frames_sent))


def _child_reader_then_die(name, attached):
    ring = ShmRing.attach(name, role="reader")
    t = ShmRingTransport(ring)
    attached.set()
    t.recv_frame()  # take one frame, then die without closing
    os.kill(os.getpid(), signal.SIGKILL)


def _child_writer_then_die(name, frames_before_death):
    ring = ShmRing.attach(name, role="writer")
    t = ShmRingTransport(ring)
    for i in range(frames_before_death):
        t.send_frames(FRAME_TEXT, [b"frame-%d" % i])
    os.kill(os.getpid(), signal.SIGKILL)  # no EOF frame, no close


def test_shm_pipe_between_two_processes():
    """The acceptance transfer: exporter and importer in separate OS
    processes, zero intermediate block materializations."""
    n_rows = 20_000
    server = DirectoryServer().start()
    try:
        q = _mp.Queue()
        name = "db://xproc?query=s1"
        imp = _mp.Process(target=_child_importer,
                          args=((server.host, server.port), name, q))
        exp = _mp.Process(target=_child_exporter,
                          args=((server.host, server.port), name, n_rows, q))
        imp.start()
        exp.start()
        results = [q.get(timeout=JOIN_S), q.get(timeout=JOIN_S)]
        _join_or_kill([imp, exp])
        by_len = {len(r): r for r in results}
        _, copies_avoided, exp_spans, frames_sent = by_len[4]
        _, rows, key_sum, imp_spans, decode_hits, ring_name = by_len[6]
        assert rows == n_rows
        assert key_sum == n_rows * (n_rows - 1) // 2  # key column intact
        # zero intermediate materializations: every frame crossed as an
        # in-place span, and the fixed columns went in as live views
        assert exp_spans == frames_sent
        assert copies_avoided > 0
        assert imp_spans > 0  # block payloads decoded in place
        assert decode_hits > 0  # decode arena recycled stores across blocks
        # the importer unlinked the segment on close (no leak)
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=ring_name, create=False)
    finally:
        server.stop()


def test_writer_fails_fast_when_reader_dies():
    ring = ShmRing.create(capacity=8192, role="writer")
    try:
        attached = _mp.Event()
        p = _mp.Process(target=_child_reader_then_die,
                        args=(ring.name, attached))
        p.start()
        assert attached.wait(JOIN_S)
        tx = ShmRingTransport(ring, send_timeout=30.0)
        with pytest.raises(BrokenPipeError):
            for i in range(1000):  # ring fills, then the pid probe fires
                tx.send_frames(FRAME_TEXT, [b"y" * 1024])
        _join_or_kill([p])
    finally:
        ring.close()


def test_reader_sees_eof_when_writer_dies_uncleanly_and_cleans_up():
    ring = ShmRing.create(capacity=8192, role="reader")
    name = ring.name
    p = _mp.Process(target=_child_writer_then_die, args=(name, 3))
    p.start()
    rx = ShmRingTransport(ring)
    got = []
    while True:
        kind, payload = rx.recv_frame()
        if kind == FRAME_EOF:  # synthesized from writer death, ring drained
            break
        got.append(bytes(payload))
    _join_or_kill([p])
    assert got == [b"frame-0", b"frame-1", b"frame-2"]
    rx.close()  # owner close: unclean shutdown must still unlink the segment
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name=name, create=False)


def test_shm_transport_charges_header_bytes_like_socket_and_channel():
    from repro.core.transport import Channel, ChannelTransport

    payload = b"x" * 1000
    ch = Channel()
    ct = ChannelTransport(ch)
    ct.send_frame(FRAME_TEXT, payload)
    ring = ShmRing.create(capacity=1 << 16, role="reader")
    st = ShmRingTransport(ring)
    st.send_frame(FRAME_TEXT, payload)
    assert st.bytes_sent == ct.bytes_sent == len(payload) + 5
    ring.close()


def test_in_process_shm_transfer_matches_channel_semantics():
    """Same-process transfer over shm (threads), exercising EOF frames,
    schema negotiation and the decode arena plumbing end to end."""
    from repro.core.directory import WorkerDirectory, set_directory as setd

    setd(WorkerDirectory())
    name = "db://inproc-shm?query=1"
    block = make_paper_block(4000, seed=5, strings=True)
    got = {}

    def imp():
        pipe = DataPipeInput(name, config=PipeConfig(
            transport="shm", shm_capacity=1 << 20))
        got["blocks"] = list(pipe.blocks())
        pipe.close()
        got["stats"] = pipe.stats

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=777))
    out.write_block(block)
    out.close()
    t.join(JOIN_S)
    assert not t.is_alive()
    from repro.core.types import ColumnBlock

    assert_blocks_equal(block, ColumnBlock.concat(got["blocks"]),
                        check_names=False)
    assert out.stats.shm_spans == out.stats.frames_sent
    assert got["stats"].shm_spans > 0
    if doorbell_supported():
        # a regression back to polling is a latency bug: the importer's
        # idle waits must resolve through the doorbell (or the brief spin)
        assert got["stats"].poll_sleeps == 0
        assert got["stats"].doorbell_waits + got["stats"].spin_wakeups > 0

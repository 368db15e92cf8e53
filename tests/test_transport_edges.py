"""Transport edge cases: partial reads, empty frames, vectored sends,
deficit-based link accounting, pipelined-sender error propagation."""

import socket
import struct
import threading
import time

import numpy as np
import pytest

import repro.core.transport as transport_mod
from repro.core.astring import AString
from repro.core.datapipe import DataPipeInput, DataPipeOutput, PipeConfig
from repro.core.iobuf import SegmentList
from repro.core.transport import (
    FRAME_BLOCK,
    FRAME_EOF,
    FRAME_TEXT,
    Channel,
    ChannelTransport,
    LinkSim,
    SocketTransport,
    listen_socket,
)
from repro.engines.base import make_paper_block


def _tcp_pair():
    ls = listen_socket()
    h, p = ls.getsockname()
    c = socket.create_connection((h, p))
    s, _ = ls.accept()
    ls.close()
    return c, s


# -- partial / truncated streams ---------------------------------------------------

def test_recv_frame_short_header_is_eof():
    c, s = _tcp_pair()
    rx = SocketTransport(s)
    c.sendall(b"B\x01")  # 2 of 5 header bytes, then FIN
    c.close()
    kind, payload = rx.recv_frame()
    assert kind == FRAME_EOF and payload == b""
    rx.close()


def test_recv_frame_truncated_payload_is_eof():
    c, s = _tcp_pair()
    rx = SocketTransport(s)
    hdr = struct.Struct("<cI").pack(FRAME_BLOCK, 100)
    c.sendall(hdr + b"only-ten-b")  # 10 of 100 payload bytes, then FIN
    c.close()
    kind, payload = rx.recv_frame()
    assert kind == FRAME_EOF and payload == b""
    rx.close()


def test_zero_length_payload_frame_roundtrip():
    c, s = _tcp_pair()
    tx, rx = SocketTransport(c), SocketTransport(s)
    tx.send_frame(FRAME_TEXT, b"")
    tx.send_frame(FRAME_EOF, b"")
    assert rx.recv_frame() == (FRAME_TEXT, b"")
    assert rx.recv_frame() == (FRAME_EOF, b"")
    tx.close()
    rx.close()


# -- vectored scatter-gather send --------------------------------------------------

def test_send_frames_vectored_roundtrip_mixed_views():
    c, s = _tcp_pair()
    tx, rx = SocketTransport(c), SocketTransport(s)
    arr = np.arange(100, dtype=np.int64)
    segs = [b"head", memoryview(b"-mid-"), bytearray(b"tail"), arr.data]
    want = b"head-mid-tail" + arr.tobytes()
    tx.send_frames(FRAME_BLOCK, segs)
    kind, payload = rx.recv_frame()
    assert kind == FRAME_BLOCK and payload == want
    assert tx.bytes_sent == len(want) + 5  # header charged too
    assert tx.frames_sent == 1
    tx.close()
    rx.close()


def test_send_frames_many_segments_exceed_iov_max():
    c, s = _tcp_pair()
    tx, rx = SocketTransport(c), SocketTransport(s)
    segs = [bytes([i % 251]) * 3 for i in range(2000)]  # >> _IOV_MAX iovecs
    want = b"".join(segs)

    got = {}

    def recv():
        got["frame"] = rx.recv_frame()

    t = threading.Thread(target=recv)
    t.start()
    tx.send_frames(FRAME_BLOCK, segs)
    t.join(10)
    assert got["frame"] == (FRAME_BLOCK, want)
    tx.close()
    rx.close()


def test_send_frames_skips_empty_segments():
    c, s = _tcp_pair()
    tx, rx = SocketTransport(c), SocketTransport(s)
    tx.send_frames(FRAME_TEXT, [b"", b"ab", memoryview(b""), b"cd", b""])
    assert rx.recv_frame() == (FRAME_TEXT, b"abcd")
    tx.close()
    rx.close()


# -- simulated link accounting -----------------------------------------------------

def test_link_charges_header_bytes_on_both_transports():
    """SocketTransport and ChannelTransport must account identically."""
    payload = b"x" * 1000
    ch = Channel()
    ct = ChannelTransport(ch)
    ct.send_frame(FRAME_TEXT, payload)
    c, s = _tcp_pair()
    st = SocketTransport(c)
    st.send_frame(FRAME_TEXT, payload)
    assert ct.bytes_sent == st.bytes_sent == len(payload) + 5
    st.close()
    s.close()


def test_link_sim_deficit_coalesces_small_frames(monkeypatch):
    """Many small frames accumulate owed delay and sleep in few batches
    instead of once per frame (no per-frame oversleep)."""
    sleeps = []
    real_sleep = time.sleep

    def recording_sleep(d):
        sleeps.append(d)
        real_sleep(d)

    monkeypatch.setattr(transport_mod.time, "sleep", recording_sleep)
    ch = Channel(maxsize=200)
    link = LinkSim(latency_s=0.0004, min_sleep_s=0.002)
    tx = ChannelTransport(ch, link)
    for _ in range(20):  # 20 * 0.4ms = 8ms owed in total
        tx.send_frame(FRAME_TEXT, b"tiny")
    # coalesced: only every ~5th frame crosses the 2 ms threshold (the seed
    # slept once per frame); oversleep credit can only reduce the count
    assert 1 <= len(sleeps) <= 6
    # requested sleep time never exceeds what the link model owes (+ one
    # threshold of slack for the final pending batch)
    assert sum(sleeps) <= 20 * 0.0004 + link.min_sleep_s


def test_link_sim_oversleep_credited_back():
    """A measured oversleep becomes negative debt absorbed by later sends."""
    link = LinkSim(latency_s=0.001, min_sleep_s=0.002)
    ch = Channel(maxsize=200)
    tx = ChannelTransport(ch, link)
    t0 = time.perf_counter()
    for _ in range(10):  # 10 ms owed
        tx.send_frame(FRAME_TEXT, b"p")
    elapsed = time.perf_counter() - t0
    # owed 10 ms; allow generous scheduler slack but catch the seed
    # behavior of 10 independent sleeps each overshooting by a quantum
    assert elapsed < 0.1


def test_channel_close_unblocks_reader():
    ch = Channel()
    tx = ChannelTransport(ch)
    rx = ChannelTransport(ch)
    got = {}

    def recv():
        got["frame"] = rx.recv_frame()

    t = threading.Thread(target=recv, daemon=True)
    t.start()
    tx.close()  # no EOF frame was ever sent
    t.join(5)
    assert not t.is_alive()
    assert got["frame"] == (FRAME_EOF, b"")


# -- pipelined sender error propagation --------------------------------------------

class _BoomError(RuntimeError):
    pass


def _pump_rows(out, block):
    rb = block.to_rows()
    for row in rb.rows:
        parts = []
        for j, v in enumerate(row):
            if j:
                parts.append(",")
            parts.append(v)
        parts.append("\n")
        out.write(AString(parts))


def test_pipelined_send_error_surfaces_on_close_and_reader_terminates():
    name = "db://senderr?query=1"
    reader_done = threading.Event()
    reader_rows = []

    def imp():
        pipe = DataPipeInput(name)
        try:
            for b in pipe.blocks():
                reader_rows.append(len(b))
        except IOError:
            pass
        finally:
            pipe.close()
            reader_done.set()

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    # block_rows > row count: the single block is flushed inside close(),
    # so close() is the first place the sender error can possibly surface
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=1024))

    real_send = out._transport.send_frames

    def broken_send(kind, segs):
        if kind == FRAME_BLOCK:
            raise _BoomError("wire fell over")
        return real_send(kind, segs)

    out._transport.send_frames = broken_send
    _pump_rows(out, make_paper_block(200, seed=7))
    with pytest.raises(_BoomError):
        out.close()
    assert out.closed
    assert reader_done.wait(10), "reader must not hang after sender failure"


def test_pipelined_writer_fails_fast_after_sender_error():
    name = "db://senderr2?query=1"

    def imp():
        pipe = DataPipeInput(name)
        try:
            list(pipe.blocks())
        except IOError:
            pass
        finally:
            pipe.close()

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=8))

    def broken_send(kind, segs):
        raise _BoomError("wire fell over")

    out._transport.send_frames = broken_send
    block = make_paper_block(400, seed=8)
    with pytest.raises(_BoomError):
        # enough blocks that a post-latch write must observe the error
        for _ in range(50):
            _pump_rows(out, block)
    with pytest.raises(_BoomError):
        out.close()

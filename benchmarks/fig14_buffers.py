"""Figure 14: Arrow buffer (row-block) size sweep, Myria->Giraph analog.

Paper conclusion: as long as the buffer is not too small, size barely
matters.  With the pooled zero-copy path the sweep also reports buffer-pool
efficiency per block size: smaller blocks mean more frames, which is where
pooled reuse (hit rate) and the pipelined sender earn their keep.  The
decode-side twin sweeps the arena hit rate (reader allocations recycled
instead of reallocated), and a transport sweep compares the same blocks
over socket vs channel vs shm ring.
"""

from __future__ import annotations

import threading
import time

from repro.core import PipeConfig
from repro.core.datapipe import DataPipeInput, DataPipeOutput
from repro.core.directory import WorkerDirectory, set_directory
from repro.engines import make_paper_block

from .common import DEFAULT_ROWS, emit, pipe_transfer

SIZES = [64, 256, 1024, 4096, 16384, 65536]


def _stream(n_rows: int, block_rows: int, strings: bool = False):
    """One arrowcol pipe into a streaming importer; returns the transfer's
    seconds and both ends' ``PipeStats``.  Blocks are dropped as consumed,
    which is the lifecycle the decode arena accelerates (a bulk engine
    import holds every block until the final merge, so its stores cannot
    recycle until the stream ends — by design, not by accident)."""
    set_directory(WorkerDirectory())
    name = f"db://fig14-stream-{block_rows}?query=1"
    block = make_paper_block(n_rows, seed=1, strings=strings)
    got = {}

    def imp():
        pipe = DataPipeInput(name)
        got["rows"] = sum(len(b) for b in pipe.blocks())
        pipe.close()
        got["stats"] = pipe.stats

    t = threading.Thread(target=imp, daemon=True)
    t.start()
    t0 = time.perf_counter()
    out = DataPipeOutput(name, config=PipeConfig(mode="arrowcol",
                                                 block_rows=block_rows))
    out.write_block(block)
    out.close()
    t.join(120)
    assert got.get("rows") == n_rows
    return time.perf_counter() - t0, out.stats, got["stats"]


def main(n_rows: int = DEFAULT_ROWS) -> dict:
    out = {}
    # paper-faithful sweep: numeric paper block, Myria->Giraph analog
    for rows in SIZES:
        t = pipe_transfer("colstore", "graphstore", n_rows,
                          PipeConfig(mode="arrowcol", block_rows=rows))
        out[rows] = t
        emit(f"fig14.block_rows_{rows}", t)
    # pooled-buffer efficiency: string columns exercise the pooled offsets
    # path every block, so the exporter's hit rate shows reuse vs. block
    # size
    for rows in SIZES:
        t, exp, _ = _stream(n_rows, rows, strings=True)
        out[f"strings_{rows}"] = t
        total = exp.pool_hits + exp.pool_misses
        rate = (exp.pool_hits / total) if total else 0.0
        emit(f"fig14.strings_block_rows_{rows}", t,
             f"pool_hit_rate={rate:.2f} acquires={total}")
    # decode-arena efficiency: the importer-side mirror of the sweep above,
    # measured on a streaming consumer (the arena's target lifecycle)
    for rows in SIZES:
        # use the function's own transfer-only timing (it excludes block
        # construction and thread spin-up), best of two like timed(); the
        # hit rate is the second, warm run's
        runs = [_stream(n_rows, rows) for _ in range(2)]
        t = min(r[0] for r in runs)
        out[f"decode_{rows}"] = t
        imp = runs[-1][2]
        total = imp.decode_pool_hits + imp.decode_pool_misses
        rate = (imp.decode_pool_hits / total) if total else 0.0
        emit(f"fig14.decode_block_rows_{rows}", t,
             f"decode_hit_rate={rate:.2f} acquires={total}")
    # transport sweep at a frame-heavy block size: socket pays the kernel
    # round trip, channel one queue materialization, shm neither
    for transport in ("socket", "channel", "shm"):
        t = pipe_transfer("colstore", "graphstore", n_rows,
                          PipeConfig(mode="arrowcol", block_rows=2048,
                                     transport=transport))
        out[f"transport_{transport}"] = t
        emit(f"fig14.transport_{transport}", t)
    return out


if __name__ == "__main__":
    main()

"""The feeder's spans: the pivot of each pipe block into rows
(``feeder.rows``), the assembly of each batch (``feeder.batch``) and the
consumer's wait for it (``feeder.get``), the last two sharing the batch
id; and the pipe spans of the same export beside them."""

import threading

import pytest

from repro.core import telemetry
from repro.core.datapipe import PipeConfig
from repro.pipeline import PipeFeeder, SyntheticSource


@pytest.fixture(autouse=True)
def _tracing_off():
    telemetry.disable_tracing()
    yield
    telemetry.disable_tracing()


def test_traced_feeder_spans_share_batch_ids():
    tr = telemetry.enable_tracing()
    name = "db://feedspans?query=1"
    feeder = PipeFeeder([name], batch_size=4, seq_len=8).start()
    src = threading.Thread(
        target=SyntheticSource(64, 8, seed=3).serve, args=(name, 20),
        kwargs={"config": PipeConfig(block_rows=8)})
    src.start()
    batches = list(feeder.batches())
    src.join(20)
    assert not src.is_alive()
    assert [b.batch_id for b in batches] == [0, 1, 2, 3, 4]

    by = {}
    for s in tr.spans():
        by.setdefault(s.name, []).append(s)
    pivots = [s.attrs["rows"] for s in by["feeder.rows"]]
    assert sum(pivots) == 20
    assert sorted(s.attrs["batch"] for s in by["feeder.batch"]) == \
        [0, 1, 2, 3, 4]
    gets = by["feeder.get"]
    # one get per batch, then the end of stream (no batch id)
    assert [(s.attrs or {}).get("batch") for s in gets] == \
        [0, 1, 2, 3, 4, None]
    consumer = {s.tid for s in gets}
    assert consumer == {threading.get_ident()}
    # each batch is assembled before the consumer's get for it ends
    built = {s.attrs["batch"]: s for s in by["feeder.batch"]}
    for g in gets[:-1]:
        assert built[g.attrs["batch"]].t1 <= g.t1
    # the export's phase spans sit beside them on the same clock
    fills = by["export.fill"]
    assert [f.attrs["rows"] for f in fills] == pivots  # a frame a block
    assert fills[0].t0 < gets[0].t1

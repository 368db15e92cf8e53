"""The program's spans on the device trace's clock: the mapping, the pipe
and feeder quantities, the idle gaps named by program work spans, on
synthetic traces whose answers are known; and ``pipe_spans.py`` end to end
on the CPU at a small size."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import program_trace as pt  # noqa: E402
from chipbench import testkit, tracing  # noqa: E402
from repro.core.telemetry import Span as TSpan  # noqa: E402


def _span(name, t0, t1, tid=1, **attrs):
    return TSpan(name, "t", "s", "", t0, t1, 7, tid, attrs or None)


def test_load_places_spans_on_the_trace_clock(tmp_path):
    """Spans timed on the monotonic clock land on the trace's clock by the
    offset between the anchor and the window's start there."""
    anchor = 81234.567891234            # monotonic seconds at the window
    window_start = 0.000101544          # the annotation's start in the trace
    spans = [_span("export.fill", anchor + 0.25, anchor + 5.0, rows=3,
                   write_s=1.5),
             _span("feeder.get", anchor - 1.0, anchor + 5.75, batch=0)]
    pt.dump(str(tmp_path), spans, anchor)
    got = pt.load(str(tmp_path), window_start)
    for s, g in zip(spans, got):
        assert g.start == pytest.approx(s.t0 - anchor + window_start,
                                        abs=1e-6)
        assert g.end == pytest.approx(s.t1 - anchor + window_start, abs=1e-6)
    assert got[0].attrs["rows"] == 3 and got[0].attrs["write_s"] == 1.5
    assert [g.name for g in got] == ["export.fill", "feeder.get"]


def _program():
    S = pt.Span
    return [
        S(0.9, 1.0, "import.rendezvous", 2),     # before the window
        S(1.0, 1.5, "export.rendezvous", 3),
        S(1.5, 4.5, "export.fill", 3, {"rows": 8, "write_s": 1.0}),
        S(4.5, 5.0, "export.encode", 3, {"rows": 8}),
        S(5.0, 5.1, "export.send", 3, {"kind": "S"}),
        S(5.1, 5.3, "export.send", 3, {"kind": "B"}),
        S(5.3, 5.4, "import.decode", 2),
        S(5.4, 5.6, "feeder.rows", 4, {"rows": 8}),
        S(5.6, 5.7, "feeder.batch", 5, {"batch": 0}),
        S(1.0, 5.8, "feeder.get", 1, {"batch": 0}),
        S(9.0, 9.5, "feeder.get", 1, {"batch": 1}),
        S(9.5, 11.0, "feeder.get", 1, {"batch": 2}),   # ends after the window
        S(1.0, 9.0, "feeder.get", 6, {"batch": 3}),    # another consumer
    ]


def test_pipe_quantities_known_answers():
    q = pt.pipe_quantities(_program(), 1.0, 10.0, consumer_tid=1)
    assert q["first_frame_s"] == pytest.approx(5.3 - 1.0)
    assert q["source_s"] == pytest.approx(3.0 - 1.0)
    assert q["export_s"] == pytest.approx(1.0 + 0.5 + 0.1 + 0.2)
    assert q["unpack_s"] == pytest.approx(0.1 + 0.2 + 0.1)
    assert q["input_wait_s"] == pytest.approx(4.8 + 0.5 + 0.5)
    # the source and export work add up to the first frame less the
    # rendezvous
    assert q["source_s"] + q["export_s"] == pytest.approx(
        q["first_frame_s"] - 0.5)
    assert q["parts"] == {"write_s": 1.0, "encode_s": pytest.approx(0.5),
                          "send_s": pytest.approx(0.3),
                          "decode_s": pytest.approx(0.1),
                          "pivot_s": pytest.approx(0.2),
                          "batch_s": pytest.approx(0.1)}
    every = pt.pipe_quantities(_program(), 1.0, 10.0)
    assert every["input_wait_s"] == pytest.approx(5.8 + 8.0)


def test_pipe_quantities_clip_and_are_none_without_their_spans():
    q = pt.pipe_quantities(_program(), 3.0, 10.0)
    assert q["first_frame_s"] is None            # its rendezvous lay before
    assert q["source_s"] == pytest.approx(1.5 - 0.5)   # half the fill
    empty = pt.pipe_quantities([], 0.0, 10.0)
    parts = empty.pop("parts")
    assert set(empty) == {"first_frame_s", "source_s", "export_s",
                          "unpack_s", "input_wait_s"}
    assert all(v is None for v in empty.values())
    assert all(v is None for v in parts.values())
    only_feeder = [s for s in _program() if s.name.startswith("feeder.")]
    q = pt.pipe_quantities(only_feeder, 1.0, 10.0)
    assert q["source_s"] is q["export_s"] is q["first_frame_s"] is None
    assert q["unpack_s"] == pytest.approx(0.3)


def _trace():
    dev = tracing.DeviceTrace(
        ops=[(6.0, 8.0, "fusion"), (8.5, 9.0, "fusion")],
        modules=[(6.0, 9.0, "jit_step_fn(1)")])
    host = [(1.0, 10.0, "bench.window"), (1.0, 5.8, "bench.wait_batch"),
            (5.8, 6.0, "bench.dispatch"), (8.0, 8.5, "bench.wait_step")]
    return tracing.Trace({"/device:TPU:0": dev}, host)


def test_gaps_are_named_by_program_work_spans():
    t = _trace()
    assert pt.window(t) == (1.0, 10.0)
    # the harness's own naming is unchanged by any of this
    assert [g[0] for g in tracing.reduce(t).breakdown["idle_gaps"]] == \
        ["bench.wait_batch", "host.other", "bench.wait_step"]
    got = pt.name_gaps(t, _program(), 1.0, 10.0)
    assert got["idle_gaps"] == [
        ["bench.wait_batch / export.fill", pytest.approx(5.0)],
        ["host.other", pytest.approx(1.0)],
        ["bench.wait_step", pytest.approx(0.5)]]
    # work spans cover [1.5, 5.7] of 6.5 s idle
    assert got["work_cover_share"] == pytest.approx(4.2 / 6.5)


def test_first_step_lag_reads_both_clocks():
    t = _trace()
    assert pt.first_step_lag_s(t, _program(), 1.0, 10.0) == \
        pytest.approx(6.0 - 5.8)
    assert pt.first_step_lag_s(t, [], 1.0, 10.0) is None


@pytest.mark.parametrize("trace", [False, True], ids=["spans", "traced"])
def test_pipe_spans_reports_the_pipe_cell(tmp_path, monkeypatch, trace):
    import pipe_spans

    root = testkit.small_copy(tmp_path)
    testkit.cpu_trace(monkeypatch)
    rec = pipe_spans.run_seed(root, "train.smollm-360m.pipe", 2 ** 31 + 9,
                              1.5, trace, require_tpu=False)
    json.loads(json.dumps(rec))
    assert rec["correct"] is True
    for k in ("first_frame_s", "source_s", "export_s", "unpack_s",
              "input_wait_s"):
        assert rec[k] is not None and rec[k] > 0, k
    assert rec["program_spans"] > 0 and rec["program_spans_dropped"] == 0
    assert rec["first_frame_s"] <= rec["first_batch_s"]
    assert rec["input_wait_s"] >= rec["first_batch_s"] - 0.05
    assert rec["get_wait_hist_s"] >= rec["first_batch_s"] - 0.05
    if trace:
        assert rec["idle_gaps"][0][0].startswith("bench.wait_batch / ")
        assert 0 <= rec["first_step_lag_s"] < 0.05

"""The reduction from a profiler trace to busy, idle, module and collective
times and the breakdown, on a synthetic trace whose answers are known, and
the loader on a real (CPU) profile."""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import tracing  # noqa: E402
from chipbench.readers import idle_pct, module, share  # noqa: E402


def _trace():
    dev = tracing.DeviceTrace(
        ops=[(0.5, 1.5, "fusion.1"),      # starts before the window
             (1.2, 2.0, "fusion.2"),      # overlaps fusion.1
             (3.0, 4.0, "all-reduce.7"),  # half hidden under fusion.3
             (3.5, 4.5, "fusion.3"),
             (6.0, 7.0, "convolution"),
             (9.5, 10.5, "fusion.1")],    # ends after the window
        modules=[(1.0, 2.0, "jit_step_fn(1)"), (3.0, 4.5, "jit_step_fn(1)"),
                 (6.0, 7.0, "jit_other(2)")])
    host = [(1.0, 10.0, "bench.window"), (2.0, 3.0, "bench.wait_batch"),
            (4.5, 6.0, "bench.wait_step"), (7.0, 9.0, "bench.dispatch")]
    return tracing.Trace({"/device:TPU:0": dev}, host)


def test_busy_idle_and_modules():
    s = tracing.reduce(_trace())
    assert s.window_s == pytest.approx(9.0)
    # inside [1, 10]: [1, 2] + [3, 4.5] + [6, 7] + [9.5, 10]
    assert s.busy_s == pytest.approx(4.0)
    assert idle_pct(s) == pytest.approx(100 * 5 / 9)
    assert s.modules["jit_step_fn(1)"] == (2, pytest.approx(2.5))
    assert module(s, name_part="step_fn") == (2, pytest.approx(2.5))
    assert module(s, count=1)[0] == 1
    # all-reduce [3, 4] is covered by fusion.3 from 3.5: 0.5 s exposed
    assert s.collective_exposed_s == pytest.approx(0.5)


def test_breakdown_names_what_the_host_did():
    s = tracing.reduce(_trace())
    ops = dict(s.breakdown["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.5 + 0.5)   # clipped to the window
    assert ops["all-reduce.7"] == pytest.approx(1.0)
    gaps = s.breakdown["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([2.5, 1.5, 1.0])
    assert [g[0] for g in gaps] == ["bench.dispatch", "bench.wait_step",
                                    "bench.wait_batch"]


def test_op_names_are_cut_to_name_and_kind():
    hlo = ("%while.1 = (s32[]{:T(128)}, bf16[64,1,1536]{2,0,1:T(8,128)(2,1)}) "
           "while((s32[]{:T(128)}, bf16[64,1,1536]) %tuple.5), condition=%c")
    assert tracing.short_name(hlo) == "%while.1 while"
    assert tracing.short_name("%fusion.186 = bf16[64,8960]{1,0} fusion("
                              "bf16[28,1536,8960] %p), kind=kOutput") == \
        "%fusion.186 fusion bf16[64,8960]"
    assert tracing.short_name("fusion.1") == "fusion.1"


def test_shares_never_read_zero():
    assert share(0.0, 1.0, 1e12) is None
    assert share(1e12, 0.0, 1e12) is None
    assert share(5e11, 1.0, 1e12) == pytest.approx(50.0)


def test_reduce_needs_the_window_and_a_device():
    t = _trace()
    with pytest.raises(ValueError):
        tracing.reduce(tracing.Trace(t.devices, t.host[1:]))
    with pytest.raises(ValueError):
        tracing.reduce(tracing.Trace({}, t.host))


def test_loader_reads_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tracing.load(str(tmp_path))
    names = [n for _, _, n in t.host]
    assert "bench.window" in names and "bench.dispatch" in names
    assert all(n.startswith("bench.") for n in names)

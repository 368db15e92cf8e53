"""Small copies of the benchmark for the CPU tests.

``small_copy`` copies ``BENCHMARK.json`` and ``benchmarks/chip`` into a
directory and cuts every configuration and mix to a size the CPU runs in
seconds; ``cpu_trace`` gives a CPU profile the device lines it lacks (the
harness's dispatch and decode spans stand in for device ops), so that the
traced path runs end to end without a chip.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                  vocab_size=256, torch_dtype="float32")
TINY_TRAIN = dict(batch_per_chip=2, seq=32)
TINY_SERVE = dict(batch=4, max_context=64, rate_per_s=20.0,
                  prompt={"median": 8, "sigma": 0.8, "min": 2, "max": 16},
                  output={"median": 6, "sigma": 0.6, "min": 2, "max": 8})


def _edit(path: Path, **changes) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data, indent=1))


def small_copy(dst: Path) -> Path:
    dst = Path(dst)
    bench = dst / "benchmarks" / "chip"
    shutil.copytree(REPO / "benchmarks" / "chip", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for conf in (bench / "configs").glob("*.json"):
        _edit(conf, **TINY_MODEL)
    for mix in (bench / "traffic").glob("*.json"):
        kind = json.loads(mix.read_text())["driver"]
        _edit(mix, **(TINY_TRAIN if kind == "train" else TINY_SERVE))
    _edit(bench / "peaks.json",
          cpu={"bf16_flops_per_s": 1e10, "hbm_bytes_per_s": 1e10})
    return dst


def cpu_trace(monkeypatch) -> None:
    from chipbench import tracing

    real = tracing.load

    def load(trace_dir):
        t = real(trace_dir)
        dev = tracing.DeviceTrace()
        for s, e, n in t.host:
            if n in ("bench.dispatch", "bench.decode_step"):
                dev.ops.append((s, e, "fusion"))
                dev.modules.append(
                    (s, e, "jit_step_fn" if n == "bench.dispatch"
                     else "jit__lambda"))
        t.devices["/device:TPU:0"] = dev
        return t

    monkeypatch.setattr(tracing, "load", load)

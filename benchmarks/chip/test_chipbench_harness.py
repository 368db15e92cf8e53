"""The chip benchmark's harness on the CPU: every cell of ``BENCHMARK.json``
names files that exist and metrics it reports; no chip, no result; each
cell's driver at a small size prints the result line the contract names; a
cell made of new files and a new entry is found by name."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402
from chipbench import spec, testkit  # noqa: E402

REPO = HERE.parents[1]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_metric_workloads_are_cells():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= set(CELLS), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_it_reports_metrics(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    bench_dir = REPO / spec.BENCH_DIR
    assert (REPO / conf["file"]).is_file()
    assert (bench_dir / "traffic" / f"{w['traffic']}.json").is_file()
    assert (bench_dir / "limits" / f"{cell}.json").is_file()
    c = spec.load_cell(REPO, cell)
    assert {m["name"] for m in c.end_to_end} > {"setup_s"}
    assert c.per_layer
    for kind, name in ([("drivers", c.traffic["driver"]),
                        ("references", c.config["reference"])]
                       + [("metrics", m["name"]) for m in c.per_layer]):
        assert (bench_dir / kind / f"{name}.py").is_file(), (kind, name)


def _command(root, cwd, env):
    cmd = BENCH["command"] + ["--workload", CELLS[0], "--seed", "7",
                              "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _command(REPO, REPO, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_bare_benchmark_files_exit_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    (no program) gives no result."""
    testkit.small_copy(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _command(tmp_path, tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_the_result_line(tmp_path, monkeypatch, cell, trace):
    root = testkit.small_copy(tmp_path)
    testkit.cpu_trace(monkeypatch)
    result = run.run_cell(root, cell, 2 ** 31 + 5, 1.5, trace,
                          require_tpu=False)
    assert list(result) == KEYS + (["breakdown"] if trace else []) + ["compared"]
    json.loads(json.dumps(result))
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    bench = json.loads((root / "BENCHMARK.json").read_text())
    group = bench["per_layer" if trace else "end_to_end"]
    want = {m["name"] for m in group
            if cell in m.get("workloads", [cell])}
    got = set(result["metrics"])
    if trace:       # shares of the CPU's nominal peak read None, not 0
        assert got <= want and got
        assert result["device"]["busy_s"] > 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert got == want
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"}
    assert result["device"]["platform"] == "cpu"


def test_main_prints_the_result_as_its_last_line(tmp_path, monkeypatch,
                                                 capsys):
    """The command's own output: the compared numbers end standard error,
    and the result is the last line of standard output, ``compared`` last."""
    root = testkit.small_copy(tmp_path)
    monkeypatch.chdir(root)
    real = run.devices_for
    monkeypatch.setattr(run, "devices_for",
                        lambda chips, require_tpu: real(chips, False))
    monkeypatch.setattr(run, "use_compile_cache", lambda root: None)
    assert run.main(["--workload", "serve.qwen2-1.5b.chat", "--seed",
                     str(2 ** 32 + 3), "--seconds", "1", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result) == KEYS + ["compared"]
    tail = err.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[1] for line in tail] == list(result["compared"])


def test_new_cell_from_files_only(tmp_path):
    """A later PR adds a mix, a limits file and an entry; nothing else."""
    root = testkit.small_copy(tmp_path)
    bench_dir = root / "benchmarks" / "chip"
    mix = json.loads((bench_dir / "traffic" / "chat.json").read_text())
    mix["rate_per_s"] = 4.0
    (bench_dir / "traffic" / "chat-slow.json").write_text(json.dumps(mix))
    (bench_dir / "limits" / "serve.qwen2-1.5b.chat-slow.json").write_text(
        (bench_dir / "limits" / "serve.qwen2-1.5b.chat.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "serve.qwen2-1.5b.chat-slow",
                               "config": "qwen2-1.5b", "traffic": "chat-slow",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve.qwen2-1.5b.chat" in m.get("workloads", []):
            m["workloads"].append("serve.qwen2-1.5b.chat-slow")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result = run.run_cell(root, "serve.qwen2-1.5b.chat-slow", 3, 1.0, False,
                          require_tpu=False)
    assert result["correct"] is True
    assert result["attempted"] == 4
    assert set(result["metrics"]) == {"setup_s", "ttft_p90_s", "itl_p95_ms"}

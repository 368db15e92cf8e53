"""A cell's files, found by the names in ``BENCHMARK.json``.

    configs/<file named by the config entry>   sizes, source, reference name
    traffic/<traffic>.json                      the mix's parameters and driver
    limits/<workload>.json                      a limit for each number compared
    drivers/<driver>.py                         runs a kind of traffic
    references/<reference>.py                   plain reference of an architecture
    metrics/<metric>.py                         reads one per-layer metric
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path("benchmarks") / "chip"


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def dir(self) -> Path:
        return self.root / BENCH_DIR

    def module(self, kind: str, name: str) -> ModuleType:
        """``<kind>/<name>.py`` under the benchmark's directory."""
        return load_module(self.dir / kind / f"{name}.py", f"{kind}.{name}")


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=_read(root / conf["file"]),
        traffic_name=w["traffic"],
        traffic=_read(root / BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=_read(root / BENCH_DIR / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def peaks(root: Path, device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device not in the table is an error."""
    table = _read(Path(root) / BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table)}")
    return table[device_kind]

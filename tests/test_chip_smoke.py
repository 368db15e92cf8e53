"""The phases of ``chip_smoke.py`` rehearsed on the CPU at tiny sizes
(reduced configs, kernels interpreted), so a broken phase shows here and
not on the chip.  The script itself refuses to run without a TPU."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.models import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "'cpu'" in err


def test_train_phase_checks_pipe_batches(smoke):
    rec = smoke.phase_train(get_config("smollm-360m").reduced(), batch=4,
                            seq=32, steps=3)
    assert rec["ok"], rec
    assert rec["steps"] == 3 and rec["mismatched_batches"] == []


@pytest.mark.parametrize("exact", [False, True])
def test_serve_phase(smoke, exact):
    cfg = get_config("qwen2-1.5b").reduced()
    if exact:
        cfg = smoke.exact_serve_config(cfg)
    rec = smoke.phase_serve(cfg, exact=exact)
    assert rec["ok"], rec
    assert rec["requests"] == smoke.SERVE_REQUESTS
    if exact:
        assert rec["greedy_compared"] > 0


def test_kernel_phase_interpreted(smoke):
    tiny = {
        "pivot": {"source": "tiny", "rows": 300, "width": 70},
        "flashattn": {"source": "tiny", "B": 1, "S": 256, "H": 4, "KV": 2,
                      "hd": 64},
        "decode_attn": {"source": "tiny", "B": 2, "S": 512, "H": 6, "KV": 2,
                        "hd": 64, "length": 300},
        "rwkv6_scan": {"source": "tiny", "B": 1, "S": 128, "H": 2, "hd": 32},
        "mamba2_ssd": {"source": "tiny", "B": 1, "S": 128, "H": 3, "hd": 16,
                       "N": 16},
    }
    rec = smoke.phase_kernels(tiny, interpret=True)
    assert rec["ok"], rec
    assert set(rec["kernels"]) == set(tiny)


FOUR_CHIPS = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import chip_smoke
from repro.models import get_config
rec = chip_smoke.phase_four_chips(get_config("smollm-360m").reduced(),
                                  jax.devices(), batch=8, seq=32)
print(json.dumps(rec))
"""


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"], rec
    assert rec["mesh"] == {"data": 4, "model": 1}

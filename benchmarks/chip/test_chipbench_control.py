"""The control fails the check: the plain reference, put in the program's
place and computed with fp8 matrix products (the step below the bf16 the
configurations state), reads above a cell's limit, at a size a test run
holds.  The same readings at the cells' own sizes come from ``control.py``
on the chip and are in PERF.md."""

import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import spec, traffic  # noqa: E402

REF = spec.load_module(HERE / "references" / "dense_decoder.py", "ref")
DRIVER = spec.load_module(HERE / "drivers" / "train.py", "train")
SMALL = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
             vocab_size=2048)


def _cfg(name):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    cfg.update(SMALL)
    return cfg


def _limits(cell):
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def test_train_control_reads_above_a_limit():
    cfg = _cfg("smollm-360m")
    opt = json.loads((HERE / "traffic" / "pipe.json").read_text())["optimizer"]
    rows = traffic.token_rows(cfg["vocab_size"], 64, 5, 3 * 4).reshape(3, 4, 64)
    ref = REF.train_steps(cfg, opt, 7, list(rows))
    ctl = REF.train_steps(cfg, opt, 7, list(rows), fp8=True)
    ctl.update(rows=rows, feed_ok=True)
    nums = DRIVER.compare_steps(ctl, ref, rows)
    limits = _limits("train.smollm-360m.pipe")
    assert any(v > limits[k] for k, v in nums.items()), (nums, limits)


def test_serve_control_reads_above_the_limit():
    cfg = _cfg("qwen2-1.5b")
    rng = np.random.default_rng(3)
    served = [(rng.integers(0, cfg["vocab_size"], 24).tolist(),
               rng.integers(0, cfg["vocab_size"], 40).tolist())
              for _ in range(4)]
    _, gaps = REF.served_gaps(cfg, 11, served, 64, control=True)
    assert gaps.max() > _limits("serve.qwen2-1.5b.chat")["served_gap"]

"""The benchmark's yardstick on the CPU: the configurations' parameter
counts and layouts against the repo's model, the FLOP and byte functions,
the peak table, and the seeded traffic."""

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import spec, traffic  # noqa: E402
from chipbench.seeds import sub_seed  # noqa: E402
from repro.models import ModelConfig, build_model  # noqa: E402

REPO = HERE.parents[1]
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in (HERE / "configs").glob("*.json")}
REF = spec.load_module(HERE / "references" / "dense_decoder.py", "ref")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_count_and_layout_match_the_model(name):
    cfg = CONFIGS[name]
    model = build_model(ModelConfig(**REF.program_kwargs(cfg)))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: REF.init_params(cfg, 1))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(want))
    assert REF.param_count(cfg) == n


def test_published_sizes():
    assert REF.param_count(CONFIGS["smollm-360m"]) == 409_007_040   # 2 x 49152 x 960 + 32 x 9_832_320 + 960
    mp = REF.matmul_params(CONFIGS["qwen2-1.5b"])
    # bf16 weights streamed once per decode step: 3.09 GB
    assert 2 * (mp["layers"] + mp["head"]) == pytest.approx(3.09e9, rel=5e-3)


def test_train_flops_per_token():
    cfg = CONFIGS["smollm-360m"]
    mp = REF.matmul_params(cfg)
    attn = 6 * 32 * 15 * 64 * 2049
    assert REF.train_flops_per_token(cfg, 2048) == \
        6 * (mp["layers"] + mp["head"]) + attn
    assert REF.train_flops_per_token(cfg, 2048) == pytest.approx(2.548e9,
                                                                 rel=1e-3)


def test_decode_work_counts_held_positions_only():
    cfg = CONFIGS["qwen2-1.5b"]
    empty = REF.decode_bytes(cfg, [])
    one = REF.decode_bytes(cfg, [0])
    kv = 2 * 28 * 2 * 128 * 2
    assert REF.decode_bytes(cfg, [9]) - one == 9 * kv
    assert REF.decode_bytes(cfg, [3, 3]) - REF.decode_bytes(cfg, [3]) == \
        one - empty + 3 * kv
    mp = REF.matmul_params(cfg)
    assert REF.decode_flops(cfg, [0]) == 2 * (mp["layers"] + mp["head"]) + \
        4 * 28 * 12 * 128


def test_peaks_table():
    p = spec.peaks(REPO, "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks(REPO, "TPU v99")


def test_rows_match_the_source():
    from repro.pipeline import SyntheticSource
    got = traffic.token_rows(49152, 64, sub_seed(2 ** 33, "x"), 5)
    src = SyntheticSource(49152, 64, seed=sub_seed(2 ** 33, "x"))
    assert np.array_equal(got, np.stack(list(src.rows(5))))


def test_open_loop_same_work_every_seed():
    mix = json.loads((HERE / "traffic" / "chat.json").read_text())
    a = traffic.open_loop(mix, 1000, 1, 30.0)
    b = traffic.open_loop(mix, 1000, 2 ** 31 + 99, 30.0)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30)
    for attr in ("max_new",):
        assert sorted(getattr(r, attr) for r in a) == \
            sorted(getattr(r, attr) for r in b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    for reqs in (a, b):
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 30.0
        assert all(mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
                   for r in reqs)


def test_sub_seeds_are_31_bit_and_distinct():
    s = {sub_seed(2 ** 31 + 7, p) for p in ("weights", "rows.setup",
                                            "rows.window", "schedule")}
    assert len(s) == 4 and all(0 <= x < 2 ** 31 for x in s)


def test_steps_in_window_counts_the_step_in_flight():
    train = spec.load_module(HERE / "drivers" / "train.py", "train")
    count = train.steps_in_window
    # back to back, ready at 3, 6, 9, 12; the window [0, 10] holds a third
    # of the fourth step
    assert count([0, 0.1, 3.1, 6.1], [3, 6, 9, 12], 0.0, 10.0) == \
        pytest.approx(3 + 1 / 3)
    # the fourth step's batch came late: it ran from 9.5 to 11.5
    assert count([0, 1, 2, 9.5], [3, 6, 9, 11.5], 0.0, 10.0) == \
        pytest.approx(3.25)
    assert count([0, 1], [3, 6], 0.0, 10.0) == 2.0
    # the first step, in flight from the open
    assert count([0.5], [20.0], 0.0, 10.0) == pytest.approx(9.5 / 19.5)

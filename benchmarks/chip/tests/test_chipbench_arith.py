"""Token, operation and byte arithmetic, the comparison numbers, and the
per-layer readers, on numbers worked out by hand."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import compare  # noqa: E402
import flops  # noqa: E402
import registry  # noqa: E402
import trainjob  # noqa: E402

CONFIGS = ["granite_moe_3b_a800m", "minicpm3_4b"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("layers", [1, 5])
def test_active_params_match_the_programs_count(name, layers):
    """The weights a token meets are the program's own 6ND count's N."""
    c = dict(registry.config(name), num_hidden_layers=layers)
    cfg = trainjob.arch_config(c)
    assert flops.active_params(c) == cfg.flops_param_count()
    per_layer = (cfg.param_count(active_only=False)
                 - c["vocab_size"] * c["hidden_size"] * (1 if c["tie_word_embeddings"] else 2))
    assert layers * flops.layer_params(c, active=False) == per_layer


def test_granite_step_flops_by_hand():
    c = registry.config("granite_moe_3b_a800m")
    w = {"batch": 2, "seq": 4096, "trained": "all"}
    attn = 1536 * 64 * (24 + 16) + 24 * 64 * 1536
    moe = 8 * 3 * 1536 * 512 + 1536 * 40
    n = attn + moe + 1536 * 49155
    causal = 4096 * 24 * (64 + 64)
    assert flops.step_flops(c, w) == pytest.approx(3 * (2 * n + causal) * 8192)
    assert trainjob.tokens_per_step(w) == 8192


def test_finetune_step_flops_by_hand():
    c = registry.config("minicpm3_4b")
    w = {"batch": 1, "seq": 4096, "trained": ["attn", "ln1", "ln2"]}
    attn, mlp = flops.attention_params(c), flops.mlp_params(c)
    head = 2560 * 73448
    core = 4096 * 40 * (96 + 64)
    forward = 2 * (12 * (attn + mlp) + head) + 12 * core
    backward = 2 * head + 2 * mlp + 4 * attn + 2 * core
    assert flops.step_flops(c, w) == pytest.approx((forward + backward) * 4096)


def _readings(loss, grad, change):
    return {"loss": loss, "grad": grad, "change": change,
            "paths": [f"leaf{i}" for i in range(len(grad))]}


def test_gaps_by_worst_leaf_against_leaf_or_median():
    ref = _readings([10.0, 9.0], [1.0, 2.0, 4.0, 1e-9], [1.0, 1.0, 2.0, 5.0])
    prog = _readings([10.1, 9.0], [1.1, 2.0, 4.0, 1e-3], [1.0, 1.2, 2.0, 0.0])
    g = compare.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(0.01)
    # leaf 3's tiny gradient is judged against the median leaf (1.5)
    assert g["grad_gap"] == pytest.approx(0.1 / 1.5)
    assert g["grad_gap_median"] == pytest.approx((0.0 + 1e-3 / 1.5) / 2)
    # leaf 3 rests (gradient under a thousandth of the median): left out of change
    assert g["change_gap"] == pytest.approx(0.2)


def test_judge_and_passed():
    checks = compare.judge({"a": 0.1, "b": 3.0, "c": float("nan")},
                           {"a": 0.2, "b": None})
    assert checks["b"]["limit"] is None and checks["c"]["limit"] is None
    assert compare.passed(checks)
    checks["a"]["value"] = 0.3
    assert not compare.passed(checks)
    assert not compare.passed({"x": {"value": float("nan"), "limit": 1.0}})


def test_seed_key_takes_more_than_32_bits():
    a, b = trainjob.seed_key(5), trainjob.seed_key(5 + (1 << 32))
    assert (a != b).any()
    assert (trainjob.seed_key(3 << 31) == trainjob.seed_key(3 << 31)).all()


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_readers_by_hand():
    read = lambda name, obs: registry.metric_reader(name).read(obs)  # noqa: E731
    obs = {"steps": 10, "window_s": 2.0, "flops_per_step": 19.7e12, "peaks": PEAKS,
           "store_block_s": [0.5, 1.5], "save_tail_s": [2.0, 4.0], "hashed_bytes": 819e9,
           "trace": {"modules": {"blockhash_pallas": 4.0}, "busy_s": 1.5,
                     "window_s": 2.0, "devices": 1}}
    assert read("mfu", obs) == pytest.approx(50.0)
    assert read("store_block_ms", obs) == pytest.approx(1000.0)
    assert read("save_tail_s", obs) == pytest.approx(3.0)
    assert read("blockhash_roofline", obs) == pytest.approx(25.0)
    assert read("device_idle_share", obs) == pytest.approx(25.0)
    events = [{"ph": "B", "name": "train.load", "ts": 0, "pid": 1, "tid": 1},
              {"ph": "B", "name": "inner", "ts": 10, "pid": 1, "tid": 1},
              {"ph": "E", "ts": 20, "pid": 1, "tid": 1},
              {"ph": "E", "ts": 2_000_000, "pid": 1, "tid": 1},
              {"ph": "B", "name": "train.load", "ts": 0, "pid": 1, "tid": 2},
              {"ph": "E", "ts": 4_000_000, "pid": 1, "tid": 2}]
    assert read("load_s", {"spans": events}) == pytest.approx(3.0)

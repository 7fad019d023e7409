"""Token, operation and byte arithmetic, the comparison numbers, and the
per-layer readers, on numbers worked out by hand."""
from __future__ import annotations

import hashlib
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import compare  # noqa: E402
import registry  # noqa: E402
import trainjob  # noqa: E402
from test_chipbench_drivers import TINY  # noqa: E402

CONFIGS = ["granite_moe_3b_a800m", "minicpm3_4b"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("layers", [1, 5])
def test_active_params_match_the_programs_count(name, layers):
    """The weights a token meets are the program's own 6ND count's N."""
    c = dict(registry.config(name), num_hidden_layers=layers)
    family = registry.reference(c)
    cfg = family.arch_config(c)
    assert family.active_params(c) == cfg.flops_param_count()
    per_layer = (cfg.param_count(active_only=False)
                 - c["vocab_size"] * c["hidden_size"] * (1 if c["tie_word_embeddings"] else 2))
    assert layers * family.layer_params(c, active=False) == per_layer


def test_granite_step_flops_by_hand():
    c = registry.config("granite_moe_3b_a800m")
    w = {"batch": 2, "seq": 4096, "trained": "all"}
    attn = 1536 * 64 * (24 + 16) + 24 * 64 * 1536
    moe = 8 * 3 * 1536 * 512 + 1536 * 40
    n = attn + moe + 1536 * 49155
    causal = 4096 * 24 * (64 + 64)
    assert registry.reference(c).step_flops(c, w) == pytest.approx(3 * (2 * n + causal) * 8192)
    assert trainjob.tokens_per_step(w) == 8192


def test_finetune_step_flops_by_hand():
    c = registry.config("minicpm3_4b")
    family = registry.reference(c)
    w = {"batch": 1, "seq": 4096, "trained": ["attn", "ln1", "ln2"]}
    attn, mlp = family.attention_params(c), family.mlp_params(c)
    head = 2560 * 73448
    core = 4096 * 40 * (96 + 64)
    forward = 2 * (12 * (attn + mlp) + head) + 12 * core
    backward = 2 * head + 2 * mlp + 4 * attn + 2 * core
    assert family.step_flops(c, w) == pytest.approx((forward + backward) * 4096)


# Pinned from the harness as it was before the reference became one module
# per family, on the CPU; each is what a cell's `mfu` or `correct` rests on,
# so a change that moves one changes what the cells measure.  Sizes: the
# published ("full") and the drivers test's TINY; the workloads' own cells
# at their batch and sequence ("full") or at 2 x 64 ("tiny").
STEP_FLOPS = {
    ("granite_moe_3b_a800m", "full"): ("granite.full_l1_savebound", 5260286361600),
    ("granite_moe_3b_a800m", "tiny"): ("granite.full_l1_savebound", 34799616),
    ("minicpm3_4b", "full"): ("minicpm3.diff_finetune_l1", 11368577105920),
    ("minicpm3_4b", "tiny"): ("minicpm3.diff_finetune_l1", 56492032),
}
# (config, size, layers): active, one whole layer, attention, MLP or experts
PARAMS = {
    ("granite_moe_3b_a800m", "full", 1): (100729344, 100724736, 6291456, 18935808),
    ("granite_moe_3b_a800m", "full", 5): (201638400, 100724736, 6291456, 18935808),
    ("granite_moe_3b_a800m", "tiny", 1): (41216, 37120, 12288, 12544),
    ("granite_moe_3b_a800m", "tiny", 5): (140544, 37120, 12288, 12544),
    ("minicpm3_4b", "full", 1): (250695680, 62668800, 13516800, 49152000),
    ("minicpm3_4b", "full", 5): (501370880, 62668800, 13516800, 49152000),
    ("minicpm3_4b", "tiny", 1): (53760, 37376, 12800, 24576),
    ("minicpm3_4b", "tiny", 5): (203264, 37376, 12800, 24576),
}
# the seed's weights at the TINY sizes: leaves, and the sha256 of their
# trainjob.fingerprint rows
FINGERPRINTS = {
    "granite_moe_3b_a800m": (
        12, "ef33c47409ed9bdff5a4ece80b6decf9db60594f009a60ca8d85436432fd5d70"),
    "minicpm3_4b": (
        15, "03703e811e374c475a43f7511b8161b3d1dabbfa55235d6646cb4027c3d1f56b"),
}


def _sized(name, size):
    c = registry.config(name)
    return dict(c, **TINY[name]) if size == "tiny" else c


@pytest.mark.parametrize("name,size", sorted(STEP_FLOPS))
def test_step_flops_pinned(name, size):
    c = _sized(name, size)
    cell, want = STEP_FLOPS[name, size]
    w = registry.workload(cell)
    if size == "tiny":
        w = dict(w, batch=2, seq=64)
    got = registry.reference(c).step_flops(c, w)
    assert got == want and float(got).is_integer()


@pytest.mark.parametrize("name,size,layers", sorted(PARAMS))
def test_param_counts_pinned(name, size, layers):
    c = dict(_sized(name, size), num_hidden_layers=layers)
    family = registry.reference(c)
    got = (family.active_params(c), family.layer_params(c, active=False),
           family.attention_params(c), family.mlp_params(c))
    assert got == PARAMS[name, size, layers]


@pytest.mark.parametrize("name", sorted(FINGERPRINTS))
def test_seed_weights_pinned(name):
    c = _sized(name, "tiny")
    params = registry.reference(c).init_params(trainjob.keys(4294967301)["params"], c)
    rows = np.asarray(trainjob.fingerprint(params))
    assert (rows.shape[0], hashlib.sha256(rows.tobytes()).hexdigest()) == FINGERPRINTS[name]


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

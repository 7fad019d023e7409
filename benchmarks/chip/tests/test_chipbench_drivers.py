"""A whole run of each cell on the CPU at a tiny size, the look for a chip
stubbed here; then the same runs with the timed path broken underneath,
which must come out not correct.

The limits are the test's own, set for these tiny sizes from their CPU
readings (bfloat16 program against the float32 reference: loss gaps under
3e-4, gradient gaps under 1.1e-2, the median leaf's under 3e-3; the float8
control: the median leaf's gradient gap over 1.2e-2; half a batch: loss
gaps over 2.5e-3 and gradient gaps over 7e-2).  The cells' own limits
come from chip runs.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import registry  # noqa: E402
import run  # noqa: E402

TINY = {
    "granite_moe_3b_a800m": dict(
        hidden_size=64, num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=32,
        num_local_experts=4, num_experts_per_tok=2, vocab_size=256, moe_group_size=32),
    "minicpm3_4b": dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=128, vocab_size=256,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16),
}
LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 4e-2, "grad_gap_median": 6e-3,
          "change_gap": None, "restore_mismatch_leaves": 0, "digest_mismatch_blocks": 0}
CELLS = ["granite.full_l1_savebound", "minicpm3.diff_finetune_l1", "granite.resume_l1"]


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    import jax

    real_config, real_workload = registry.config, registry.workload

    def config(name, base=registry.HERE):
        return dict(real_config(name, base), **TINY[name])

    def workload(name, base=registry.HERE):
        w = dict(real_workload(name, base), batch=2, seq=64, limits=LIMITS)
        w["store"] = dict(w["store"], every=3)
        return w

    # every cell with a workload file runs here, in BENCHMARK.json or not yet
    spec = registry.benchmark()
    listed = {w["name"] for w in spec["workloads"]}
    spec["workloads"] += [{"name": c, "config": real_workload(c)["config"],
                           "traffic": c.split(".", 1)[1], "chips": 1}
                          for c in CELLS if c not in listed]
    monkeypatch.setattr(registry, "benchmark", lambda root=registry.ROOT: spec)
    monkeypatch.setattr(registry, "config", config)
    monkeypatch.setattr(registry, "workload", workload)
    monkeypatch.setattr(registry, "peaks", lambda kind, base=None: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(run, "require_accelerator", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "OUT", tmp_path)

    def execute(cell, trace=0, seed=4294967301):
        return run.execute(run.parse_args([
            "--workload", cell, "--seed", str(seed), "--seconds", "0.5",
            "--trace", str(trace)]))
    return execute


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(tiny, cell):
    res = tiny(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    names = {m["name"] for m in registry.end_to_end(registry.benchmark(), cell)}
    assert set(res["metrics"]) == names and "setup_s" in names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["count"] == 1


def test_traced_run_reports_per_layer_metrics(tiny):
    """The listed training cell's traced run reports its host-side
    per-layer metrics (the device-trace ones need a TPU's trace)."""
    spec = registry.benchmark()
    cell = next(w["name"] for w in spec["workloads"]
                if registry.workload(w["name"])["driver"] == "train_save")
    res = tiny(cell, trace=1)
    assert res["correct"], res["checks"]
    listed = {m["name"] for m in registry.per_layer(spec, cell)}
    assert {"store_block_ms", "save_tail_s", "mfu"} <= listed
    assert {"store_block_ms", "save_tail_s", "mfu"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def _wrap_train_step(monkeypatch, wrap):
    from repro.train import step as step_mod
    real = step_mod.make_train_step

    def make(model, opt_cfg, **kw):
        return wrap(real(model, opt_cfg, **kw))
    monkeypatch.setattr(step_mod, "make_train_step", make)


def _unchanged(inner):
    def step(state, batch):
        _, metrics = inner(state, batch)
        return state, metrics
    return step


def _half_batch(inner):
    def step(state, batch):
        return inner(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return step


@pytest.mark.parametrize("cell", ["granite.full_l1_savebound", "granite.resume_l1"])
def test_state_returned_unchanged_is_caught(tiny, monkeypatch, cell):
    _wrap_train_step(monkeypatch, _unchanged)
    res = tiny(cell)
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(tiny, monkeypatch):
    _wrap_train_step(monkeypatch, _half_batch)
    res = tiny("granite.full_l1_savebound")
    assert not res["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_checkpoint_is_caught(tiny, monkeypatch, cell):
    """One bit flipped in what a save writes: in the host snapshot of a
    FULL save, in the packed blocks of a DIFF save."""
    from repro.core import diff, pipeline
    real_to_host, real_pack = pipeline.to_host, diff._pack_dirty_blocks

    def flip(a):
        a = np.array(a)
        if a.size:
            a.reshape(-1).view(np.uint8)[0] ^= 1
        return a

    def to_host(named):
        out = real_to_host(named)
        big = max(out, key=lambda k: out[k].size)
        return dict(out, **{big: flip(out[big])})

    monkeypatch.setattr(pipeline, "to_host", to_host)
    monkeypatch.setattr(diff, "_pack_dirty_blocks",
                        lambda *a, **k: flip(real_pack(*a, **k)))
    res = tiny(cell)
    assert not res["correct"]
    assert res["checks"]["restore_mismatch_leaves"]["value"] >= 1


@pytest.mark.parametrize("cell", ["granite.full_l1_savebound", "minicpm3.diff_finetune_l1"])
def test_altered_digest_is_caught(tiny, monkeypatch, cell):
    """The digest pass altered where it is produced: one salted lane of
    every block left out."""
    from repro.kernels import ops
    real = ops.blockhash

    def blockhash(x, block_bytes=ops.DEFAULT_BLOCK_BYTES):
        return real(x, block_bytes).at[:, 1].set(0)

    monkeypatch.setattr(ops, "blockhash", blockhash)
    res = tiny(cell)
    assert not res["correct"]
    assert res["checks"]["digest_mismatch_blocks"]["value"] >= 1


@pytest.mark.parametrize("cell", ["granite.full_l1_savebound", "minicpm3.diff_finetune_l1"])
def test_control_and_half_batch_come_out_not_correct(tiny, cell, capsys):
    """control.py at the tiny size: the program's readings pass the
    limits, the float8 control's and the half batch's do not, and the exit
    code says so."""
    import json

    import control
    assert control.main(["--workload", cell, "--seeds", "4294967301"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    verdict = {x["reading"]: x["correct"] for x in lines if "correct" in x}
    assert verdict == {"program": True, "control": False, "half_batch": False}

"""The benchmark's files: the contract's shape of BENCHMARK.json, every
piece found by name, and a new cell picked up from added files alone."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import registry  # noqa: E402

SPEC = registry.benchmark(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("entry", SPEC["configs"] + SPEC["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    registry.check_name(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            registry.check_name(entry[key])
    if "unit" in entry:
        registry.check_unit(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
            assert "\t" not in entry[key]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    entry = registry.cell(SPEC, cell)
    w = registry.workload(cell)
    c = registry.config(entry["config"])
    assert w["config"] == entry["config"]
    assert hasattr(registry.driver(w["driver"]), "Job")
    assert set(w["limits"]) >= {"restore_mismatch_leaves"}
    assert c["source"] == next(x["source"] for x in SPEC["configs"]
                               if x["name"] == entry["config"])
    e2e = [m["name"] for m in registry.end_to_end(SPEC, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert registry.per_layer(SPEC, cell)


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda e: e["name"])
def test_config_file_records_its_cuts(config):
    c = registry.config(config["name"])
    assert (ROOT / config["file"]).is_file()
    assert sorted(c["reduced"]) == sorted(config["reduced"])
    assert "deployment" in c and "assumed" in c
    assert registry.reference(c).__file__.endswith(f"references/{c['reference']}.py")
    widths = ("hidden_size", "intermediate_size", "num_experts_per_tok", "head_dim")
    assert not [k for k in config["reduced"]
                if k in widths or k.endswith("_dim") or k.endswith("_rank")]


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda e: e["name"])
def test_per_layer_reader_and_moves(metric):
    assert callable(registry.metric_reader(metric["name"]).read)
    moved = next(m for m in SPEC["end_to_end"] if m["name"] == metric["moves"])
    for cell in metric["workloads"]:
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"]
    assert registry.metric_reader(metric["name"]).read({}) is None


def test_peaks_keyed_by_device_kind():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("cpu")


def test_new_cell_from_added_files_only(tmp_path):
    """A later change adds a cell, a config and a metric as new files plus
    BENCHMARK.json entries; nothing already there is edited."""
    base = tmp_path / "chip"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p.relative_to(base): p.read_bytes() for p in base.rglob("*") if p.is_file()}
    w = registry.workload(CELLS[0])
    w["store"] = dict(w["store"], every=7)
    (base / "workloads" / "granite.full_l1_every7.json").write_text(json.dumps(w))
    (base / "metrics" / "saves_per_s.py").write_text(
        "def read(obs):\n    return len(obs['store_block_s']) / obs['window_s']\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(registry.cell(SPEC, CELLS[0]),
                                  name="granite.full_l1_every7", traffic="full_l1_every7"))
    spec["per_layer"].append({"name": "saves_per_s", "unit": "1/s", "better": "higher",
                              "source": "host_clock", "layer": "device",
                              "moves": "tokens_per_s",
                              "workloads": ["granite.full_l1_every7"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    found = registry.benchmark(tmp_path)
    assert registry.cell(found, "granite.full_l1_every7")["config"] == w["config"]
    assert registry.workload("granite.full_l1_every7", base)["store"]["every"] == 7
    names = [m["name"] for m in registry.per_layer(found, "granite.full_l1_every7")]
    assert names == ["saves_per_s"]
    reader = registry.metric_reader("saves_per_s", base)
    assert reader.read({"store_block_s": [0.1, 0.2], "window_s": 4.0}) == 0.5
    after = {p.relative_to(base): p.read_bytes() for p in base.rglob("*")
             if p.is_file() and p.relative_to(base) in before}
    assert after == before


@pytest.mark.parametrize("config,error,says", [
    ({}, ValueError, "names no reference family"),
    ({"reference": "no_such_family"}, FileNotFoundError, "no_such_family"),
    ({"reference": "../reference"}, ValueError, "not a valid benchmark name"),
], ids=["no key", "no file", "not a name"])
def test_reference_family_named_and_found(config, error, says):
    with pytest.raises(error, match=says):
        registry.reference(dict(registry.config("minicpm3_4b"), **config) if config else {})


TOY = """\"\"\"Family ``toy``: the decoder, with the tree split counted.\"\"\"
import registry

_decoder = registry.reference({"reference": "decoder"})
init_params, loss, trained_part = _decoder.init_params, _decoder.loss, _decoder.trained_part
arch_config, step_flops = _decoder.arch_config, _decoder.step_flops
calls = {"split_last": 0, "join_last": 0}


def split_last(params):
    calls["split_last"] += 1
    return _decoder.split_last(params)


def join_last(rest, last):
    calls["join_last"] += 1
    return _decoder.join_last(rest, last)
"""

# one run of the copy's harness in a process of its own, the look for a
# chip stubbed; then the toy family's counts
DRIVE = """\
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import registry, run
run.require_accelerator = lambda chips: jax.devices()[:chips]
loaded, real = [], registry.reference
registry.reference = lambda c, base=registry.HERE: loaded.append(real(c, base)) or loaded[-1]
run.main(["--workload", "toy.finetune", "--seed", "4294967301", "--seconds", "0.5"])
print(json.dumps([m.calls for m in loaded if hasattr(m, "calls")]))
"""


def test_new_family_from_added_files_only(tmp_path):
    """A later change adds an architecture as a reference family, a config
    naming it, a cell and their BENCHMARK.json entries, all new files; a
    tiny run through them comes out correct, through the family's split."""
    from test_chipbench_drivers import LIMITS, TINY

    base = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns("out", "__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = {p.relative_to(base): p.read_bytes() for p in base.rglob("*") if p.is_file()}
    (base / "references" / "toy.py").write_text(TOY)
    c = dict(registry.config("minicpm3_4b"), **TINY["minicpm3_4b"], reference="toy")
    (base / "configs" / "toy_tiny.json").write_text(json.dumps(c))
    w = dict(registry.workload("minicpm3.diff_finetune_l1"), config="toy_tiny", batch=2,
             seq=64, limits=LIMITS)
    w["store"] = dict(w["store"], every=3)
    (base / "workloads" / "toy.finetune.json").write_text(json.dumps(w))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][0], name="toy_tiny",
                                file="benchmarks/chip/configs/toy_tiny.json"))
    spec["workloads"].append({"name": "toy.finetune", "config": "toy_tiny",
                              "traffic": "finetune", "chips": 1, "why": "a tiny toy"})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("toy.finetune")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    done = subprocess.run([sys.executable, "-c", DRIVE, str(base)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-4000:]
    result, calls = (json.loads(x) for x in done.stdout.splitlines()[-2:])
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    assert calls and all(x["split_last"] >= 1 and x["join_last"] >= 1 for x in calls)
    after = {p.relative_to(base): p.read_bytes() for p in base.rglob("*")
             if p.is_file() and p.relative_to(base) in before}
    assert after == before

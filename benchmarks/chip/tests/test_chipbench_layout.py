"""The benchmark's files: the contract's shape of BENCHMARK.json, every
piece found by name, and a new cell picked up from added files alone."""
from __future__ import annotations

import json
import shutil
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

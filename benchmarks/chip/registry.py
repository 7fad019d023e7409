"""Find the benchmark's pieces by name.

Everything that belongs to one cell, configuration, driver or per-layer
metric is a file of its own, so a later change adds a cell by adding
files and a ``BENCHMARK.json`` entry, never by editing one:

- ``BENCHMARK.json`` (repository root): cells, metrics, bounds;
- ``workloads/<cell>.json``: the cell's traffic, driver and limits;
- ``configs/<config>.json``: sizes as run, and the reference family the
  configuration names (``"reference": "<family>"``);
- ``references/<family>.py``: one per architecture, its plain reference,
  program mapping, tree split and operation count (``FAMILY``);
- ``drivers/<driver>.py``: one per kind of traffic, with a ``Job`` class;
- ``metrics/<metric>.py``: a ``read(obs)`` per per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_name(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"not a valid benchmark name: {name!r}")
    return name


def check_unit(unit: str) -> str:
    if not UNIT.match(unit):
        raise ValueError(f"not a valid unit: {unit!r}")
    return unit


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def workload(name: str, base: Path = HERE) -> Dict[str, Any]:
    return _json(base / "workloads" / f"{check_name(name)}.json")


def config(name: str, base: Path = HERE) -> Dict[str, Any]:
    return _json(base / "configs" / f"{check_name(name)}.json")


def _module(path: Path, qualname: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(qualname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# what every reference family exposes, and nothing else is asked of one
FAMILY = ("init_params", "loss", "trained_part", "split_last", "join_last",
          "arch_config", "step_flops")


def reference(config: Dict[str, Any], base: Path = HERE):
    """The reference family that configuration ``config`` names under its
    ``reference`` key: ``references/<family>.py``.  A configuration that
    names none, a family without a file, and a family that lacks one of
    ``FAMILY`` are errors."""
    if "reference" not in config:
        raise ValueError('the configuration names no reference family: give it '
                         '"reference": "<family>" for references/<family>.py')
    name = check_name(config["reference"])
    path = base / "references" / f"{name}.py"
    family = _module(path, f"reference_{name}")
    missing = [f for f in FAMILY if not callable(getattr(family, f, None))]
    if missing:
        raise AttributeError(f"{path} lacks {', '.join(missing)}")
    return family


def driver(name: str, base: Path = HERE):
    return _module(base / "drivers" / f"{check_name(name)}.py", f"driver_{name}")


def metric_reader(name: str, base: Path = HERE):
    return _module(base / "metrics" / f"{check_name(name)}.py",
                   "metric_" + name.replace(".", "_").replace("-", "_"))


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: Dict[str, Any], cell_name: str) -> List[Dict[str, Any]]:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: Dict[str, Any], cell_name: str) -> List[Dict[str, Any]]:
    return [m for m in bench["per_layer"] if _applies(m, cell_name)]


def peaks(kind: str, base: Path = HERE) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind`` ``kind``; a device
    that is not in the table is an error."""
    table = _json(base / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]

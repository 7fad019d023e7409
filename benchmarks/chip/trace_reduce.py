"""Profiler trace → device busy and idle time, device time per program and
per operation, and the device's idle gaps by what the host was doing.

Input is the ``perfetto_trace.json.gz`` that ``jax.profiler`` writes with
``create_perfetto_trace=True``.  In it every device is a process named
``/device:TPU:<n>``; its ``XLA Ops`` thread holds one complete event per
operation run, its ``XLA Modules`` thread one per program (named after the
jitted function, ``jit_<name>(<id>)``).  The host's threads carry the
harness's ``jax.profiler.TraceAnnotation`` spans: ``window`` around the
measured window, and ``step``, ``ctx.store``, ``ctx.wait``, ``resume``
inside it.

Busy time, operations and idle gaps are clipped to the ``window`` span:
``window_s`` is its length, ``busy_s`` the union of operation intervals
inside it, averaged over the devices used.  Program times (``modules``)
cover the whole trace, which ends once the window's saves have drained,
so they hold all the device work those saves caused.  An idle gap is attributed to the innermost host span that
covers each part of it (``other`` where none does).
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, Iterable, List, Tuple

HOST_SPANS = ("step", "ctx.store", "ctx.wait", "resume")
WINDOW = "window"
TOP = 10
_MODULE = re.compile(r"^(?:jit_)?(?P<name>[^(]+?)(?:\(\d+\))?$")

Interval = Tuple[float, float]


def trace_file(trace_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" /
                                 "perfetto_trace.json.gz")))
    if not found:
        raise FileNotFoundError(f"no perfetto trace under {trace_dir}")
    return Path(found[-1])


def load_events(path: Path) -> List[Dict[str, Any]]:
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


def module_name(name: str) -> str:
    m = _MODULE.match(name)
    return m.group("name") if m else name


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def reduce_events(events: List[Dict[str, Any]], n_devices: int = 1) -> Dict[str, Any]:
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    devices = sorted((pid for pid, n in procs.items() if n.startswith("/device:TPU:")),
                     key=lambda pid: procs[pid])[:n_devices]
    complete = [e for e in events if e.get("ph") == "X"]
    host = [e for e in complete if e["pid"] not in procs
            or not procs[e["pid"]].startswith("/device:")]
    windows = [e for e in host if e["name"] == WINDOW]
    if not windows:
        raise ValueError("the trace has no 'window' span")
    win = max(windows, key=lambda e: e["dur"])
    lo, hi = win["ts"], win["ts"] + win["dur"]

    ops_by_dev: Dict[int, List[Dict[str, Any]]] = defaultdict(list)
    modules: Dict[str, float] = defaultdict(float)
    for e in complete:
        if e["pid"] not in devices:
            continue
        line = threads.get((e["pid"], e["tid"]), "")
        if line == "XLA Ops":
            ops_by_dev[e["pid"]].append(e)
        elif line == "XLA Modules":
            modules[module_name(e["name"])] += e["dur"] / len(devices)

    busy_per_dev = []
    op_time: Dict[str, float] = defaultdict(float)
    for pid in devices:
        spans = []
        for e in ops_by_dev.get(pid, []):
            t = _clip(e["ts"], e["ts"] + e["dur"], lo, hi)
            if t > 0:
                spans.append((max(e["ts"], lo), min(e["ts"] + e["dur"], hi)))
                op_time[e["name"]] += t
        busy_per_dev.append(union(spans))
    busy_s = sum(sum(b - a for a, b in u) for u in busy_per_dev) / max(1, len(devices)) / 1e6

    gaps: Dict[str, float] = defaultdict(float)
    first = busy_per_dev[0] if busy_per_dev else []
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    annotations = [e for e in host if e["name"] in HOST_SPANS]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        inside = [e for e in annotations if e["ts"] < b and e["ts"] + e["dur"] > a]
        cuts = sorted({a, b} | {t for e in inside for t in (e["ts"], e["ts"] + e["dur"])
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = 0.5 * (x + y)
            cover = [e for e in inside if e["ts"] <= mid <= e["ts"] + e["dur"]]
            label = min(cover, key=lambda e: e["dur"])["name"] if cover else "other"
            gaps[label] += (y - x) / 1e6

    def top(d: Dict[str, float], scale: float = 1.0) -> List[List[Any]]:
        return [[k, v * scale] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "window_s": win["dur"] / 1e6,
        "busy_s": busy_s,
        "modules": {k: v / 1e6 for k, v in modules.items()},
        "device_ops": top(op_time, 1e-6 / max(1, len(devices))),
        "idle_gaps": top(gaps),
        "devices": len(devices),
    }


def reduce_dir(trace_dir: Path, n_devices: int = 1) -> Dict[str, Any]:
    return reduce_events(load_events(trace_file(trace_dir)), n_devices)

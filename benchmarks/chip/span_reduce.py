"""The program's own spans → where a save's time goes, on the device clock.

The program's tracer (``repro.telemetry.trace``) opens a
``jax.profiler.TraceAnnotation`` for each span it records, named as the
span and carrying the span's arguments, ``span_id`` among them.  In the
``perfetto_trace.json.gz`` of a traced run the program's spans are the
host events with a ``span_id`` argument, on the device ops' timeline.
JAX writes an annotation's arguments either as the event's ``args`` or
appended to its name (``name#k=v,k=v#``); both read the same here.

From the profiler's trace (``reduce_events``, ``reduce_dir``, and
``reduce_run``, which finds the traced run's own file under ``out/``):

- ``idle_by_program_span``: the first device's idle time inside the
  ``window`` span, each part attributed to the innermost program span
  that covers it on the thread holding ``window`` (the training thread),
  ``other`` where none does.  Spans of other threads (the CP thread's
  tail) take no blame.  These and the busy time make up the window.
- ``plan_idle_s``: the part of that idle time under ``pipeline.plan``;
  ``saves``: the ``chk.store`` spans that start on that thread inside
  the window.

From the tracer's own Chrome events (``obs["spans"]``, wall clock):
``closed_spans`` pairs them into spans, ``per_save_s`` sums one name's
durations over the number of saves.

    python3 benchmarks/chip/span_reduce.py benchmarks/chip/out/<cell>/trace

prints ``reduce_dir`` of a traced run's profiler trace as JSON.
"""
from __future__ import annotations

import glob
import json
import math
import sys
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

from trace_reduce import load_events, trace_file, union

WINDOW = "window"
SAVE = "chk.store"
PLAN = "pipeline.plan"
PROGRAM_ARG = "span_id"
OTHER = "other"
OUT = Path(__file__).resolve().parent / "out"   # run.py's working files

Interval = Tuple[float, float]


def split_name(name: str) -> Tuple[str, Dict[str, str]]:
    """``name#k=v,k=v#`` → (``name``, its arguments)."""
    base, sep, rest = name.partition("#")
    args: Dict[str, str] = {}
    if sep:
        for item in rest.rstrip("#").split(","):
            key, eq, value = item.partition("=")
            if eq:
                args[key] = value
    return base, args


def _first_device_busy(events: List[Dict[str, Any]], lo: float, hi: float
                       ) -> List[Interval]:
    """Union of the operation intervals of the first TPU device, clipped
    to ``lo..hi`` (the device whose gaps ``trace_reduce`` attributes)."""
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    devices = sorted((pid for pid, n in procs.items() if n.startswith("/device:TPU:")),
                     key=lambda pid: procs[pid])
    if not devices:
        return []
    busy = [(max(e["ts"], lo), min(e["ts"] + e["dur"], hi)) for e in events
            if e.get("ph") == "X" and e["pid"] == devices[0]
            and threads.get((e["pid"], e["tid"])) == "XLA Ops"
            and e["ts"] < hi and e["ts"] + e["dur"] > lo]
    return union(busy)


def _gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _segments(spans: List[Dict[str, Any]], lo: float, hi: float
              ) -> List[Tuple[float, float, str, bool]]:
    """``lo..hi`` cut at every span edge: (start, end, innermost span's
    name or ``other``, whether ``pipeline.plan`` covers it)."""
    cuts = sorted({lo, hi} | {t for s in spans for t in (s["ts"], s["end"]) if lo < t < hi})
    out = []
    for x, y in zip(cuts, cuts[1:]):
        mid = 0.5 * (x + y)
        cover = [s for s in spans if s["ts"] <= mid <= s["end"]]
        label = min(cover, key=lambda s: s["end"] - s["ts"])["name"] if cover else OTHER
        out.append((x, y, label, any(s["name"] == PLAN for s in cover)))
    return out


def reduce_events(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    complete = [e for e in events if e.get("ph") == "X"]
    windows = [e for e in complete if split_name(e["name"])[0] == WINDOW]
    if not windows:
        raise ValueError("the trace has no 'window' span")
    win = max(windows, key=lambda e: e["dur"])
    lo, hi = win["ts"], win["ts"] + win["dur"]
    track = (win["pid"], win["tid"])
    spans = []
    for e in complete:
        if (e["pid"], e["tid"]) != track:
            continue
        name, args = split_name(e["name"])
        args.update(e.get("args") or {})
        if PROGRAM_ARG in args:
            spans.append({"name": name, "ts": e["ts"], "end": e["ts"] + e["dur"]})

    idle: Dict[str, float] = defaultdict(float)
    plan_idle = 0.0
    segments = _segments(spans, lo, hi)
    j = 0
    for a, b in _gaps(_first_device_busy(events, lo, hi), lo, hi):
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            x, y, label, in_plan = segments[k]
            t = (min(b, y) - max(a, x)) / 1e6
            idle[label] += t
            plan_idle += t if in_plan else 0.0
            k += 1
    return {
        "window_s": win["dur"] / 1e6,
        "idle_by_program_span": [[k, v] for k, v in
                                 sorted(idle.items(), key=lambda kv: -kv[1])],
        "plan_idle_s": plan_idle,
        "saves": sum(1 for s in spans if s["name"] == SAVE and lo <= s["ts"] < hi),
    }


def reduce_dir(trace_dir: Path) -> Dict[str, Any]:
    return reduce_events(load_events(trace_file(trace_dir)))


def reduce_run(window_s: float, out: Optional[Path] = None) -> Optional[Dict[str, Any]]:
    """``reduce_events`` of a traced run's own profiler trace: the newest
    under ``out/<cell>/trace``, where ``run.py`` writes it, taken only if
    its ``window`` span lasted ``window_s`` (``trace_reduce``'s reading of
    the same run); None where no such trace is there."""
    out = OUT if out is None else out
    found = glob.glob(str(Path(out) / "*" / "trace" / "plugins" / "profile" / "*" /
                          "perfetto_trace.json.gz"))
    if not found or not window_s:
        return None
    r = reduce_events(load_events(Path(max(found, key=lambda f: Path(f).stat().st_mtime_ns))))
    return r if math.isclose(r["window_s"], window_s, rel_tol=1e-9) else None


# -- the tracer's Chrome events ------------------------------------------- #


def closed_spans(events: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Chrome ``B``/``E`` events → spans (name, pid, tid, ts and dur in µs,
    the ``B`` event's args).  ``E`` events carry no name: paired per thread."""
    stacks: Dict[Any, List[Dict[str, Any]]] = {}
    out = []
    for e in events:
        key = (e.get("pid"), e.get("tid"))
        if e.get("ph") == "B":
            stacks.setdefault(key, []).append(e)
        elif e.get("ph") == "E" and stacks.get(key):
            b = stacks[key].pop()
            out.append({"name": b.get("name"), "pid": key[0], "tid": key[1],
                        "ts": b["ts"], "dur": e["ts"] - b["ts"],
                        "args": b.get("args") or {}})
    return out


def per_save_s(events: Iterable[Dict[str, Any]], name: str) -> Optional[float]:
    """Seconds per save in the ``name`` spans: their durations summed over
    the number of ``chk.store`` spans (None without a save)."""
    spans = closed_spans(events)
    saves = sum(1 for s in spans if s["name"] == SAVE)
    if not saves:
        return None
    return sum(s["dur"] for s in spans if s["name"] == name) / 1e6 / saves


if __name__ == "__main__":
    print(json.dumps(reduce_dir(Path(sys.argv[1]))))

"""The program-span reduction and the metrics that read the program's
spans, on hand-made profiler events and hand-made observations."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

import registry  # noqa: E402
import span_reduce  # noqa: E402
from test_chipbench_drivers import tiny  # noqa: E402,F401  (the tiny-run fixture)

TRAIN, CP = 9, 11


def _meta(pid, tid=None, proc=None, thread=None):
    if proc is not None:
        return {"ph": "M", "name": "process_name", "pid": pid, "args": {"name": proc}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": thread}}


def _x(pid, tid, name, ts, dur, **args):
    e = {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts, "dur": dur}
    if args:
        e["args"] = args
    return e


def _events():
    """A window of 1000 µs on the training thread: a step, then a save
    (chk.store ⊃ pipeline.plan ⊃ diff.hash, diff.pack; cp.wait), while the
    CP thread runs a tail that overlaps the device's idle time."""
    return [
        _meta(1, proc="/host:CPU"), _meta(2, proc="/device:TPU:0"),
        _meta(2, 1, thread="XLA Ops"),
        _x(1, TRAIN, "window", 0, 1000),
        _x(1, TRAIN, "step", 0, 200),
        # arguments in the name, as some JAX versions write them
        _x(1, TRAIN, "chk.store#ckpt_id=5,span_id=1#", 300, 500),
        _x(1, TRAIN, "pipeline.plan", 320, 400, span_id=2, ckpt_id=5),
        _x(1, TRAIN, "diff.hash#span_id=3#", 340, 200),
        _x(1, TRAIN, "diff.pack", 560, 100, span_id=4),
        _x(1, TRAIN, "cp.wait", 750, 40, span_id=5),
        _x(1, CP, "pipeline.store", 300, 700, span_id=6, cause=2),
        _x(2, 1, "fusion.1", 0, 250),        # the step's ops, idle 250..300
        _x(2, 1, "blockhash", 400, 50),      # inside diff.hash
        _x(2, 1, "fusion.2", 900, 150),      # clipped to 900..1000
    ]


def test_split_name():
    assert span_reduce.split_name("diff.hash#span_id=3,bytes=8#") == (
        "diff.hash", {"span_id": "3", "bytes": "8"})
    assert span_reduce.split_name("window") == ("window", {})


def test_idle_by_innermost_program_span_on_the_training_thread():
    r = span_reduce.reduce_events(_events())
    idle = dict(r["idle_by_program_span"])
    # 250..300 under the harness's step only: no program span
    # 300..320 chk.store; 320..340 pipeline.plan; 340..400, 450..540
    # diff.hash; 540..560 pipeline.plan; 560..660 diff.pack;
    # 660..720 pipeline.plan; 720..750 chk.store; 750..790 cp.wait;
    # 790..800 chk.store; 800..900 none
    assert idle == pytest.approx({
        "other": 150e-6, "chk.store": 60e-6, "pipeline.plan": 100e-6,
        "diff.hash": 150e-6, "diff.pack": 100e-6, "cp.wait": 40e-6})
    assert "pipeline.store" not in idle          # the CP thread takes no blame
    assert r["plan_idle_s"] == pytest.approx(350e-6)
    assert r["saves"] == 1
    busy = 250e-6 + 50e-6 + 100e-6
    assert sum(idle.values()) + busy == pytest.approx(1000e-6)
    seconds = [v for _, v in r["idle_by_program_span"]]
    assert seconds == sorted(seconds, reverse=True)


def test_without_program_spans_everything_idle_is_other():
    """The program before its spans reached the profiler: nothing to blame,
    no save counted, nothing raised."""
    events = [e for e in _events() if e.get("ph") == "M"
              or e["name"] in ("window", "step") or e["pid"] == 2]
    r = span_reduce.reduce_events(events)
    assert [k for k, _ in r["idle_by_program_span"]] == ["other"]
    assert r["saves"] == 0 and r["plan_idle_s"] == 0.0


def test_window_span_required():
    with pytest.raises(ValueError):
        span_reduce.reduce_events([_x(1, 1, "chk.store", 0, 5, span_id=1)])


def _b(tid, name, ts, **args):
    return {"ph": "B", "pid": 1, "tid": tid, "name": name, "ts": ts, "args": args}


def _e(tid, ts):
    return {"ph": "E", "pid": 1, "tid": tid, "ts": ts}


def _chrome_spans():
    """Two saves on the training thread as the tracer records them; the
    first waited 3 ms for the queue.  Times in µs."""
    out = []
    for i, t0 in enumerate((0, 100_000)):
        out += [_b(TRAIN, "chk.store", t0, ckpt_id=i), _b(TRAIN, "pipeline.plan", t0 + 10),
                _b(TRAIN, "diff.hash", t0 + 20), _e(TRAIN, t0 + 80_020),
                _b(TRAIN, "diff.pack", t0 + 80_030), _e(TRAIN, t0 + 120_030),
                _e(TRAIN, t0 + 120_040), _b(TRAIN, "cp.wait", t0 + 120_050),
                _e(TRAIN, t0 + 120_050 + (3000 if i == 0 else 0)),
                _e(TRAIN, t0 + 125_000)]
        out += [_b(CP, "pipeline.store", t0 + 130_000, cause=2 * i + 2),
                _e(CP, t0 + 130_000 + 200_000 + 50_000 * i)]
    return out


@pytest.mark.parametrize("name, want, tails_only", [
    ("plan_hash_ms", 80.0, None), ("plan_pack_ms", 40.0, None),
    ("cp_queue_wait_ms", 1.5, None), ("cp_tail_s", 0.225, 0.225)])
def test_span_metric_reads_hand_made_spans(name, want, tails_only):
    reader = registry.metric_reader(name)
    assert reader.read({"spans": _chrome_spans()}) == pytest.approx(want)
    # only the CP thread's spans (a program without the directive's span):
    # nothing per save, the tail still read
    without_saves = [e for e in _chrome_spans() if e.get("tid") == CP]
    assert reader.read({"spans": without_saves}) == pytest.approx(tails_only)


def _write_trace(out, cell="cell", events=None):
    import gzip
    import json
    d = out / cell / "trace" / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    with gzip.open(d / "perfetto_trace.json.gz", "wt") as f:
        json.dump({"traceEvents": _events() if events is None else events}, f)


def test_plan_idle_reads_the_runs_own_trace(tmp_path, monkeypatch):
    """The reader finds the run's trace where run.py writes it, and only
    if its window is the one the harness measured."""
    monkeypatch.setattr(span_reduce, "OUT", tmp_path)
    reader = registry.metric_reader("plan_idle_ms")
    assert reader.read({"trace": {"window_s": 1000e-6}}) is None   # no trace yet
    _write_trace(tmp_path)
    assert reader.read({"trace": {"window_s": 1000e-6}}) == pytest.approx(350e-3)
    assert reader.read({"trace": {"window_s": 2000e-6}}) is None   # another run's
    assert reader.read({}) is None


def test_plan_idle_without_program_spans_reads_nothing(tmp_path, monkeypatch):
    """A program whose spans never reach the profiler: no save to divide
    by, so the metric is left out of the line."""
    monkeypatch.setattr(span_reduce, "OUT", tmp_path)
    _write_trace(tmp_path, events=[e for e in _events() if e.get("ph") == "M"
                                   or e["name"] in ("window", "step") or e["pid"] == 2])
    assert registry.metric_reader("plan_idle_ms").read(
        {"trace": {"window_s": 1000e-6}}) is None


def test_traced_run_reports_the_program_spans(tiny, tmp_path, monkeypatch):
    """The DIFF cell's traced run reads the program's own spans: DIFF Plan's
    hashing and packing, the queue wait and the tail from the tracer, and
    the idle time under them from the profiler's trace (on the CPU no
    device ops are traced, so the whole window reads idle)."""
    monkeypatch.setattr(span_reduce, "OUT", tmp_path)   # where the run writes
    cell = "minicpm3.diff_finetune_l1"
    res = tiny(cell, trace=1)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for name in ("plan_hash_ms", "plan_pack_ms", "cp_tail_s", "plan_idle_ms"):
        assert m[name] > 0, name
    assert m["cp_queue_wait_ms"] >= 0
    r = span_reduce.reduce_dir(tmp_path / cell / "trace")
    labels = [k for k, _ in r["idle_by_program_span"]]
    assert {"chk.store", "pipeline.plan", "diff.hash", "diff.pack"} <= set(labels)
    assert "pipeline.store" not in labels
    idle = sum(v for _, v in r["idle_by_program_span"])
    assert idle == pytest.approx(res["device"]["window_s"] - res["device"]["busy_s"])

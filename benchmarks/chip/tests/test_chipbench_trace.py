"""The trace reduction: by hand on a few events, and on a small trace
recorded on a TPU v5e (``fixtures/v5e_trace.json.gz``, made by
``record_trace_fixture.py``)."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

import trace_reduce  # noqa: E402

FIXTURE = HERE / "fixtures" / "v5e_trace.json.gz"


def _meta(pid, tid=None, proc=None, thread=None):
    if proc is not None:
        return {"ph": "M", "name": "process_name", "pid": pid, "args": {"name": proc}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": thread}}


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts, "dur": dur}


def test_reduce_by_hand():
    events = [
        _meta(1, proc="/host:CPU"), _meta(2, proc="/device:TPU:0"),
        _meta(2, 1, thread="XLA Ops"), _meta(2, 2, thread="XLA Modules"),
        _x(1, 9, "window", 100, 1000),
        _x(1, 9, "step", 100, 300), _x(1, 9, "ctx.store", 500, 400),
        _x(2, 2, "jit_blockhash_pallas(7)", 550, 100),
        _x(2, 2, "jit_blockhash_pallas(7)", 1150, 30),   # after the window: kept
        _x(2, 1, "fusion.1", 50, 150),       # clipped to 100..200
        _x(2, 1, "fusion.2", 150, 100),      # overlaps: union 100..250
        _x(2, 1, "custom-call.3", 550, 100),
        _x(2, 1, "fusion.1", 1050, 100),     # clipped to 1050..1100
    ]
    r = trace_reduce.reduce_events(events)
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx((150 + 100 + 50) * 1e-6)
    assert r["modules"] == {"blockhash_pallas": pytest.approx(130e-6)}
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(150e-6)
    gaps = dict(r["idle_gaps"])
    # 250..400 under step, 400..500 under nothing, 500..550 and 650..900
    # under ctx.store, 900..1050 under nothing
    assert gaps["step"] == pytest.approx(150e-6)
    assert gaps["ctx.store"] == pytest.approx(300e-6)
    assert gaps["other"] == pytest.approx(250e-6)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_window_span_required():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events([_meta(1, proc="/host:CPU"), _x(1, 1, "step", 0, 5)])


def test_recorded_v5e_trace():
    events = trace_reduce.load_events(FIXTURE)
    r = trace_reduce.reduce_events(events)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # 20 bf16 4096x4096 products and one 64 MiB hash, as recorded
    assert r["window_s"] == pytest.approx(0.01674933)
    assert r["busy_s"] == pytest.approx(0.0149193045)
    assert r["modules"]["blockhash_pallas"] == pytest.approx(0.000702135)
    assert dict(r["device_ops"])["convolution_tanh_fusion"] == pytest.approx(0.0134108189)
    assert 0 < len(r["device_ops"]) <= trace_reduce.TOP
    assert sum(v for _, v in r["idle_gaps"]) + r["busy_s"] == pytest.approx(r["window_s"])
    assert FIXTURE.stat().st_size < 1 << 20

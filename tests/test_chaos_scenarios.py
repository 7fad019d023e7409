"""The chaos scenario matrix: every scenario × every backend, zero loss.

Each cell runs the real store→inject→restart→verify cycle (scenarios.py);
this file asserts the harness contract — bit-exact restores, machine-
readable reports, and zero data loss everywhere — rather than re-testing
the mechanics the scenarios themselves verify.
"""
import json

import pytest

from repro.chaos import inject as chaos
from repro.chaos.scenarios import BACKENDS, SCENARIOS, run_matrix, run_scenario


@pytest.fixture(autouse=True)
def _clean_registry():
    chaos.reset()
    yield
    chaos.reset()


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_bit_exact_zero_loss(tmp_path, name, backend):
    r = run_scenario(name, backend, str(tmp_path))
    assert r.ok, f"{name}×{backend}: {r.detail}"
    assert r.data_loss_bytes == 0
    assert r.faults_fired >= 1
    assert r.recovery_path in ("local", "partner", "erasure", "global",
                               "objstore", "elastic")


def test_matrix_report_is_machine_readable(tmp_path):
    report = run_matrix(str(tmp_path), backends=("fti",),
                        names=["corrupt-chunk"])
    blob = json.loads(json.dumps(report))       # JSON round-trip
    assert blob["ok"] and blob["passed"] == blob["total"] == 1
    assert blob["data_loss_bytes"] == 0
    (cell,) = blob["scenarios"]
    for key in ("name", "backend", "ok", "faults_fired", "recovery_path",
                "recovery_s", "data_loss_bytes", "detail"):
        assert key in cell


def test_crashed_scenario_reports_failure_not_raise(tmp_path):
    SCENARIOS["_boom"] = lambda w, b: 1 / 0
    try:
        r = run_scenario("_boom", "fti", str(tmp_path))
        assert not r.ok and "ZeroDivisionError" in r.detail["error"]
    finally:
        del SCENARIOS["_boom"]


def test_runner_cli_writes_report(tmp_path, capsys):
    from repro.chaos.runner import main
    out = tmp_path / "report.json"
    rc = main(["--workdir", str(tmp_path / "w"), "--backend", "fti",
               "--scenario", "node-loss-mid-store", "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok"] and report["total"] == 1
    assert "PASS" in capsys.readouterr().out


def test_matrix_runs_supervised_scenarios_first(tmp_path, monkeypatch):
    """A supervised scenario spawns workers that need the accelerator, so
    it runs before any in-process scenario initializes a backend here;
    the report keeps the requested order."""
    from repro.chaos import scenarios
    ran = []

    def fake(name, backend, workdir, trace_dir=None):
        ran.append(name)
        return scenarios.ScenarioResult(name, backend, True, faults_fired=1,
                                        recovery_path="local",
                                        recovery_s=0.0, data_loss_bytes=0)

    monkeypatch.setattr(scenarios, "run_scenario", fake)
    report = run_matrix(str(tmp_path), backends=("fti",),
                        names=["corrupt-chunk"], include_supervised=True)
    assert ran == ["supervised-kill", "corrupt-chunk"]
    assert [c["name"] for c in report["scenarios"]] == [
        "corrupt-chunk", "supervised-kill"]

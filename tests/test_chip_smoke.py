"""``chip_smoke.py`` rehearsed on the CPU at a small size.

The script itself refuses to run without a TPU; its phase functions take
the configuration and the platform to check against, so the same control
flow — stores committed, resume bit-exact, DIFF not promoted, serve
resumed on the same tokens, 2x2 → 4x1 regions — runs here on tiny widths.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small(cfg, layers=4):
    return dataclasses.replace(cfg, n_layers=layers, d_model=256, n_heads=4,
                               n_kv_heads=2, d_ff=512, vocab_size=4096)


def test_one_chip_phases_rehearse_on_cpu(smoke, tmp_path):
    cfg = small(smoke.train_config())
    work = str(tmp_path)
    smoke.phase_train(work, cfg, batch=2, seq=32, platform="cpu")
    smoke.phase_diff(work, cfg, platform="cpu")
    smoke.phase_serve(work, full=False)


FOUR = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src"),
                    os.path.join({root!r}, "tests")]
    import chip_smoke as cs
    from test_chip_smoke import small
    cs.phase_four(sys.argv[1], small(cs.train_config(), layers=2))
    print("FOUR-OK")
""")


def test_four_chip_phase_rehearses_on_forced_cpu_devices(tmp_path):
    r = subprocess.run(
        [sys.executable, "-c", FOUR.format(root=ROOT), str(tmp_path)],
        capture_output=True, text=True, timeout=540,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "FOUR-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
    assert "restored onto 4x1 bit-exact" in r.stdout


def test_refuses_to_run_without_a_tpu(tmp_path):
    """No phase runs on the CPU: exit non-zero, print no result."""
    r = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, timeout=300, cwd=str(tmp_path),
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU" in r.stderr

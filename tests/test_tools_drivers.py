"""CLI tools and end-to-end drivers (chkls, launch.train, heat2d parity)."""
import os
import subprocess
import sys

import numpy as np
import pytest


def test_chkls_cli(tmp_path, capsys):
    from repro.core.formats import CHK5Writer
    from repro.tools.chkls import main as chkls_main
    p = str(tmp_path / "x.chk5")
    with CHK5Writer(p) as w:
        w.write_dataset("data/a", np.arange(6.0).reshape(2, 3))
        w.set_attrs("", {"id": 1})
    assert chkls_main([p, "--verify", "--stats"]) == 0
    out = capsys.readouterr().out
    assert "data/a" in out and "crc OK" in out and "μ=" in out


def test_chkls_json_and_clause_attrs(tmp_path, capsys):
    """--json emits a machine-readable inventory (attrs included) and the
    human listing shows clause attrs — what CI asserts container contents
    with."""
    import json
    from repro.core.formats import CHK5Writer
    from repro.core.protect import Protect
    from repro.core.tiers import pack_named
    from repro.tools.chkls import main as chkls_main
    p = str(tmp_path / "c.chk5")
    with CHK5Writer(p) as w:
        w.set_attrs("", {"kind": "FULL", "id": 4})
        pack_named(w, {"params/w": np.linspace(-1, 1, 2048, dtype=np.float32),
                       "step": np.int32(7)},
                   {"params/w": Protect("params/**", compress="int8"),
                    "step": None})
    assert chkls_main([p, "--json", "--verify"]) == 0
    inv = json.loads(capsys.readouterr().out)
    assert inv["attrs"] == {"kind": "FULL", "id": 4}
    by = {d["name"]: d for d in inv["datasets"]}
    assert by["data/params/w"]["attrs"]["codec"] == "int8"
    assert by["data/params/w"]["dtype"] == "|i1"
    assert "codec" not in by["data/step"]["attrs"]
    assert inv["verified"] is True
    assert inv["total_bytes"] == sum(d["nbytes"] for d in inv["datasets"])
    # human listing shows the clause column
    assert chkls_main([p]) == 0
    out = capsys.readouterr().out
    assert "codec=int8" in out and "kind=FULL" in out


def test_launch_train_worker_restart(tmp_path):
    """launch.train direct mode: fault → rerun → resume (subprocess)."""
    env = dict(os.environ, PYTHONPATH="src")
    d = str(tmp_path / "t")
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "tinyllama-1.1b", "--steps", "20", "--batch", "2", "--seq", "32",
            "--ckpt-every", "5", "--ckpt-dir", d, "--no-dedicated-thread"]
    r1 = subprocess.run(base + ["--inject-at", "0.8"], env=env,
                        capture_output=True, text=True, timeout=420)
    assert r1.returncode != 0
    assert "injected fault" in (r1.stderr + r1.stdout)
    r2 = subprocess.run(base, env=env, capture_output=True, text=True,
                        timeout=420)
    assert r2.returncode == 0, r2.stderr[-1000:]
    assert "restart detected" in r2.stdout
    assert "'final_step': 20" in r2.stdout


@pytest.mark.parametrize("variant", ["openchk", "fti", "scr", "veloc"])
def test_heat2d_variants_restart_parity(tmp_path, variant):
    """All four CR variants converge to the same physics after a fault."""
    sys.path.insert(0, ".")
    from benchmarks.apps import (
        heat2d_fti, heat2d_openchk, heat2d_scr, heat2d_veloc)
    from repro.ft.failures import FaultInjector, SimulatedFault
    mod = {"openchk": heat2d_openchk, "fti": heat2d_fti,
           "scr": heat2d_scr, "veloc": heat2d_veloc}[variant]
    from benchmarks.apps.heat2d_common import heat_step, init_grid, checksum
    g = init_grid(32)
    for _ in range(40):
        g = heat_step(g)
    want = checksum(g)
    d = str(tmp_path / variant)
    inj = FaultInjector(total_steps=40, at_progress=0.9)
    with pytest.raises(SimulatedFault):
        mod.run(n=32, steps=40, ckpt_every=10, ckpt_dir=d, injector=inj)
    # a real abort kills the CP thread with the process; the in-process
    # simulation must drain it so the restart doesn't race an orphan
    # (same pattern as benchmarks/bench_overhead.py)
    from repro.core.async_engine import drain_all
    drain_all()
    out = mod.run(n=32, steps=40, ckpt_every=10, ckpt_dir=d)
    assert out["restarted"]
    assert abs(out["checksum"] - want) < 1e-3


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_is_env_or_fixed_in_checkout(env_dir, tmp_path,
                                                       monkeypatch):
    """The entry points' compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (left to jax), else one fixed directory inside the checkout —
    never a temp name, so a later run finds what an earlier one cached."""
    import jax
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

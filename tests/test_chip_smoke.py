"""chip_smoke.py off the chip: it must refuse to pass without a TPU,
and its phases (rehearsed here at tiny sizes on the CPU mesh) must
hold the system to the scalar engine."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _no_result(stdout):
    for line in stdout.strip().splitlines()[-1:]:
        with pytest.raises(ValueError):
            json.loads(line)


def test_fails_without_tpu():
    proc = _run(REPO, "chip_smoke.py")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc.stdout)


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(tmp_path, "chip_smoke.py")
    assert proc.returncode != 0
    assert "no crdt_tpu package" in proc.stderr
    _no_result(proc.stdout)


def test_fanin_phase_matches_scalar_engine():
    out = chip_smoke.phase_fanin(R=40, K=25, scale=2)
    assert out == {"ops": 1000, "union_ops": 2000}


def test_sharded_phase_spans_every_device(monkeypatch):
    import jax

    from crdt_tpu.ops import shard

    monkeypatch.setenv(shard.MIN_ROWS_ENV, "256")
    n = len(jax.devices())
    out = chip_smoke.phase_sharded(R=60, K=20, chips=n)
    assert out["output_devices"] == n


def test_fleet_phase_matches_one_device():
    out = chip_smoke.phase_fleet(chips=4, R=8, N=32)
    assert out["output_devices"] == 4


def test_check_raises_smoke_failure():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")

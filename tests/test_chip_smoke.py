"""``chip_smoke.py`` off the chip: it refuses to run without a TPU or
outside a checkout, and its one-chip phases run end to end on the CPU at a
tiny slide size (so a change that breaks the smoke's control flow fails
here, not on the chip)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_refuses_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def test_one_chip_phases_run_on_cpu(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "SIZE", 512)
    chip_smoke.one_chip(jax)
    lines = capsys.readouterr().out.splitlines()
    phases = [ln for ln in lines if ln.startswith("phase ")]
    assert len(phases) == 8, lines
    assert any(ln.startswith("coefficients: 0 of ") for ln in lines), lines

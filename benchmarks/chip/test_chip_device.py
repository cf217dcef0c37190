"""The run refuses what it cannot measure: no chip, a stripped checkout,
a device that is not in the peak table."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

from chip import harness

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _cli(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "tgat-wiki.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_without_rehearsal_flag_exits_nonzero_with_no_result():
    out = _cli(ROOT)
    assert out.returncode != 0
    assert "metrics" not in out.stdout
    assert "no accelerator" in out.stderr


def test_checkout_of_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert "metrics" not in out.stdout


def _fake_devices(monkeypatch, kind, count=1):
    import jax

    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * count)


def test_unknown_device_kind_is_refused(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99 imaginary")
    with pytest.raises(harness.Refused, match="not in peaks.json"):
        harness.check_device(1, None)


def test_known_device_gets_its_peaks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite")
    info, peak = harness.check_device(1, None)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    table = json.loads((HERE / "peaks.json").read_text())
    assert peak == table["devices"]["TPU v5 lite"]
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9


def test_too_few_chips_are_refused(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite")
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.check_device(4, None)

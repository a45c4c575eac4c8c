"""The peak table and the benchmark's refusal to run off a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.peaks import PEAKS, peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p.flops_per_s == 197e12 and p.hbm_bytes_per_s == 819e9
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_an_error(kind):
    assert kind not in PEAKS
    with pytest.raises(ValueError):
        peaks_for(kind)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "resnet20_ring16_dir0.1_hybrid", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    out = _run(harness.ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's paths alone."""
    root = harness.ROOT
    with open(root / "BENCHMARK.json") as f:
        paths = json.load(f)["paths"]
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    for p in paths:
        shutil.copytree(root / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout

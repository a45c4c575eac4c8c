"""``chip_smoke.py`` on the CPU: it refuses to report, and its phases run end
to end at a tiny size (the control flow the chip run depends on).

The chip's choices are steered here, in the test: ``fused='auto'`` takes
the Pallas chain (interpret mode on the CPU), and the kernel marker check
is relaxed because an interpreted kernel leaves no ``tpu_custom_call``.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.telemetry import trace  # noqa: E402

TINY_RESNET = dict(steps=2, hw=8, batch=4)
TINY_MAMBA = dict(batch=1, seq=128, steps=2, data_vocab=256, reduced=True)


@pytest.fixture
def cpu_rehearsal(monkeypatch):
    from repro.core import transforms
    monkeypatch.setattr(transforms, "_fused_enabled", lambda f: f != "off")
    monkeypatch.setattr(chip_smoke, "KERNEL_MARKER", "func.func")
    yield trace.enable().compiles
    trace.disable()


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("phase", ["A", "B", "C"])
def test_phase_runs_at_tiny_size(cpu_rehearsal, capsys, phase):
    if phase == "A":
        chip_smoke.phase_a(cpu_rehearsal, **TINY_RESNET)
    elif phase == "B":
        chip_smoke.phase_b(cpu_rehearsal, **TINY_MAMBA)
    else:
        chip_smoke.phase_c(cpu_rehearsal, full=False, requests=4, max_new=4)
    out = capsys.readouterr().out
    assert out.startswith(f"[{phase} ") and "compile_s=" in out


_SHARDED = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, ".")
import chip_smoke
from repro.core import transforms
from repro.telemetry import trace
transforms._fused_enabled = lambda f: f != "off"
chip_smoke.phase_sharded(trace.enable().compiles, resnet=%r, mamba=%r)
print("SHARDED_OK")
""" % (TINY_RESNET, TINY_MAMBA)


def test_four_device_path_at_tiny_size():
    """``--chips 4``'s path on 4 forced host devices (subprocess: the
    device count must precede jax init)."""
    res = subprocess.run(
        [sys.executable, "-c", _SHARDED], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert "SHARDED_OK" in res.stdout, res.stdout[-2000:] + res.stderr[-3000:]
    assert "param_bytes_per_device=" in res.stdout

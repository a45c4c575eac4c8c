"""``correct`` on the CPU at a small size: a sound run passes, and the
control and each fault a training cell can have fail.

Each test drives the rest of a run (``harness.run_cell``: set-up, the
checked steps, a short window, the reference and the comparison) with the
harness's look for a chip skipped.  The faults are planted in the program
underneath the timed path; the control puts the reference, in bfloat16, in
the program's place.  The limits are the cells' own.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import compare, harness, reference

SEED = 2**33 + 12345       # larger than 32 bits, as the driver's are


def _run(cell):
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter())


@pytest.mark.parametrize("name", ["resnet20_ring16_dir0.1_hybrid",
                                  "mamba2_130m_ring4_sharded"])
def test_sound_run_is_correct(tiny_cell, name):
    rec = _run(tiny_cell(name))
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert list(rec)[-1] == "checks"
    assert set(rec["metrics"]) == {"step_ms", "peak_hbm_gb", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.train import trainer as tr
    step = tr.DecentralizedTrainer.step

    def frozen(self, state, batch, rng, collect=False):
        copy = jax.tree.map(jnp.copy, state)
        _, metrics = step(self, copy, batch, rng, collect)
        return state, metrics

    monkeypatch.setattr(tr.DecentralizedTrainer, "step", frozen)


def _half_batch(monkeypatch):
    from repro.train import trainer as tr
    put = tr.DecentralizedTrainer.put_batch

    def half(self, batch, lead=0):
        return put(self, jax.tree.map(lambda a: a[:, : a.shape[1] // 2],
                                      batch), lead)

    monkeypatch.setattr(tr.DecentralizedTrainer, "put_batch", half)


def _no_exchange(monkeypatch):
    """Every gossip path returns the node's own tree: the dense
    contraction, the compiled schedule and the block schedule."""
    from repro.core import gossip
    keep = lambda x, *args, **kw: x
    monkeypatch.setattr(gossip, "mix_leaf_dense", lambda w, x: x)
    monkeypatch.setattr(gossip, "apply_schedule_local", keep)
    monkeypatch.setattr(gossip, "apply_block_schedule_local", keep)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _no_exchange])
def test_fault_is_not_correct(tiny_cell, monkeypatch, fault):
    fault(monkeypatch)
    rec = _run(tiny_cell("resnet20_ring16_dir0.1_hybrid"))
    assert rec["correct"] is False, rec["checks"]


@pytest.mark.parametrize("name", ["resnet20_ring16_dir0.1_hybrid",
                                  "mamba2_130m_ring4_sharded"])
def test_control_is_not_correct(tiny_cell, name):
    """The reference in bfloat16, put in the program's place, against the
    reference in float32 ('highest'): the cell's limits refuse it."""
    cell = tiny_cell(name)
    su = harness.set_up(cell, SEED)
    batches = su.feed.kept
    del su
    x0, _ = harness._init_fn(cell)(jax.random.PRNGKey(SEED % (1 << 31)))
    args = (cell.model, cell.config, cell.traffic, jax.device_get(x0),
            batches)
    sound = reference.run(*args)
    control = reference.run(*args, dtype="bfloat16")
    assert not compare.verdict(compare.numbers(control, sound), cell.limits)


def test_sharded_cell_runs_on_four_devices():
    """The four-chip cell's sharded path on four virtual CPU devices."""
    code = """
import sys, time
sys.path[:0] = [{root!r}, {src!r}]
sys.path.insert(0, {here!r})
from conftest import cell_named, shrink
from bench import harness
import dataclasses
cell = shrink(cell_named("mamba2_130m_ring4_sharded"))
cell = dataclasses.replace(cell, chips=4)
cell.traffic["runtime"] = "sharded"
rec = harness.run_cell(cell, {seed}, 0.5, False, time.perf_counter())
print("CORRECT", rec["correct"], rec["device"]["count"])
""".format(root=harness.ROOT.as_posix(), src=(harness.ROOT / "src").as_posix(),
           here=os.path.dirname(os.path.abspath(__file__)), seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "CORRECT True 4" in out.stdout, out.stderr[-3000:]

"""The four-chip ``mamba2_130m_ring4_sharded`` cell, loaded by name so that
its own traffic mix and limits are the ones used, cut to a CPU size by
``shrink``: a sound run is ``correct``, the control and each planted fault
are not; and its two per-layer readers, ``gossip_exposed_ms`` and
``lm_mfu``, on a recorded reduction."""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import pytest
from conftest import shrink
from test_bench_correct import (SEED, _half_batch, _no_exchange,
                                _state_unchanged)

from bench import compare, harness, reference, trace
from bench.peaks import peaks_for

CELL = "mamba2_130m_ring4_sharded"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "resnet20_ring16_6steps.trace.json.gz")


@pytest.fixture(scope="module")
def cell():
    return shrink(harness.load_cell(CELL))


def test_cell_is_the_ring4_sharded_job():
    """4 nodes in a ring, one a chip, 4 x 2048 tokens a node, Dirichlet(0.1),
    QG-DSGDm-N at lr 0.02; its two readers and its own limits."""
    c = harness.load_cell(CELL)
    t = c.traffic
    assert (c.chips, t["nodes"], t["topology"], t["runtime"]) == \
        (4, 4, "ring", "sharded")
    assert (t["batch"], t["seq_len"], t["alpha"]) == (4, 2048, 0.1)
    assert (t["optimizer"]["name"], t["optimizer"]["lr"]) == \
        ("qg_dsgdm_n", 0.02)
    assert [m["name"] for m, _ in c.per_layer] == ["gossip_exposed_ms",
                                                   "lm_mfu"]
    assert set(c.limits) == set(compare.NUMBERS)


def test_sound_run_is_correct(cell):
    rec = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter())
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert set(rec["metrics"]) == {"step_ms", "peak_hbm_gb", "setup_s"}
    assert {k: c["limit"] for k, c in rec["checks"].items()} == cell.limits


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _no_exchange])
def test_fault_is_not_correct(cell, monkeypatch, fault):
    fault(monkeypatch)
    rec = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter())
    assert rec["correct"] is False, rec["checks"]


def test_control_is_not_correct(cell):
    """The reference in bfloat16 in the program's place, against the
    reference in float32 at 'highest': the cell's limits refuse it."""
    su = harness.set_up(cell, SEED)
    batches = su.feed.kept
    del su
    x0, _ = harness._init_fn(cell)(jax.random.PRNGKey(SEED % (1 << 31)))
    args = (cell.model, cell.config, cell.traffic, jax.device_get(x0),
            batches)
    nums = compare.numbers(reference.run(*args, dtype="bfloat16"),
                           reference.run(*args))
    assert not compare.verdict(nums, cell.limits), nums


def _reading(summary, cell, chips):
    return trace.Reading(
        summary=summary, steps=4, window_s=summary.window_s, chips=chips,
        peaks=peaks_for("TPU v5 lite"),
        flops_per_step=cell.traffic["nodes"]
        * cell.model.train_flops_per_node_step(cell.config, cell.traffic),
        rule_bytes_per_chip_step=0.0)


def _op(pid, tid, ts, dur, name, scope=""):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": scope}}


def test_readers_on_a_recorded_reduction():
    """``lm_mfu`` from the recorded one-chip trace; ``gossip_exposed_ms`` is
    None there (one chip: no collective) and, on two chips whose ring
    ppermute runs 3 ms a step beside nothing, 3 ms."""
    full = harness.load_cell(CELL)
    readers = {m["name"]: r for m, r in full.per_layer}
    one = trace.reduce(DATA, [0], skip=2, programs=4)
    r = _reading(one, full, 1)
    assert one.exposed_collective_s == 0
    assert readers["gossip_exposed_ms"].read(r) is None
    want = 100.0 * r.flops_per_step * 4 / (one.window_s * 197e12)
    assert readers["lm_mfu"].read(r) == pytest.approx(want, rel=1e-12)
    assert readers["lm_mfu"].read(dataclasses.replace(
        r, flops_per_step=0.0)) is None

    meta = []
    for pid in (1, 2):
        meta += [{"ph": "M", "name": "process_name", "pid": pid,
                  "args": {"name": f"/device:TPU:{pid - 1}"}},
                 {"ph": "M", "name": "thread_name", "pid": pid, "tid": 1,
                  "args": {"name": trace.OPS_THREAD}}]
    ops = [_op(pid, 1, 10_000 * k + off, dur, name, scope)
           for pid in (1, 2) for k in range(4)
           for off, dur, name, scope in (
               (0, 6000, "fusion.1", "jit(step)/tm/grad/dot"),
               (6000, 3000, "collective-permute-done.2",
                "jit(step)/tm/finish_mix/tm/gossip/ppermute/ppermute"))]
    two = trace.reduce_events(meta + ops, [0, 1])
    assert two.exposed_collective_s == pytest.approx(4 * 3e-3)
    r2 = _reading(two, full, 2)
    assert readers["gossip_exposed_ms"].read(r2) == pytest.approx(3.0)

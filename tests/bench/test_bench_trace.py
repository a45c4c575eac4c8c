"""The trace reduction, on a trace recorded on a TPU v5e and on small
hand-made event lists.

``data/resnet20_ring16_6steps.trace.json.gz`` is the profiler's trace-viewer
file of one traced call of six steps of ``resnet20_ring16_dir0.1_hybrid`` on
one v5e chip (``harness.traced_window``), trimmed for size: the device's
``XLA Ops`` events keep only their ``tf_op`` and ``hlo_category`` arguments,
its ``XLA Modules`` events none, and of the host's events only the
benchmark's ``bench/*`` spans and those of 0.5 ms or more are kept.
"""
from __future__ import annotations

import gzip
import json
import os
import types

import pytest

from bench import trace
from bench.peaks import peaks_for

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "resnet20_ring16_6steps.trace.json.gz")
SETTLE, STEPS = 2, 4


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(DATA, [0], skip=SETTLE, programs=STEPS)


def _reader(name):
    import importlib.util
    path = os.path.join(os.path.dirname(trace.__file__), "metrics",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scopes_are_in_the_device_ops():
    """The ``tm/*`` named scopes reach the device trace's ops."""
    with gzip.open(DATA, "rt") as f:
        events = json.load(f)["traceEvents"]
    scopes = [e["args"].get("tf_op", "") for e in events
              if e.get("ph") == "X" and "args" in e]
    for s in ("tm/grad", "tm/opt_step", "tm/stage/gossip_mix",
              "tm/fused_update"):
        assert any(s in x for x in scopes), s


def test_layers_partition_busy_time(summary):
    assert sum(summary.layer_s.values()) == pytest.approx(summary.busy_s,
                                                           rel=1e-9)
    assert summary.layer_s["grad"] > 0.85 * summary.busy_s
    assert 0 < summary.layer_s["gossip"] < summary.layer_s["opt"]
    # one chip, dense contraction: no collective at all
    assert summary.collective_s == 0 and summary.exposed_collective_s == 0


def test_breakdown(summary):
    ops, gaps = summary.breakdown["device_ops"], summary.breakdown["idle_gaps"]
    assert 0 < len(ops) <= trace.TOP and 0 < len(gaps) <= trace.TOP
    assert ops[0][0].startswith("grad: tm/grad/")
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    # once the pipeline is full the device never waits for a batch
    assert gaps[0][1] < 1e-3


def test_window_of_the_recorded_trace(summary):
    """The window holds the four steps after the two that fill the
    pipeline, each about 35 ms, with the device busy nearly throughout."""
    assert 4 * 0.030 < summary.window_s < 4 * 0.040
    assert summary.busy_s > 0.99 * summary.window_s
    whole = trace.reduce(DATA, [0], skip=0, programs=SETTLE + STEPS)
    assert whole.window_s > summary.window_s
    with pytest.raises(ValueError):
        trace.reduce(DATA, [0], skip=SETTLE, programs=STEPS + 1)


def _reading(summary, window_s=None):
    return trace.Reading(
        summary=summary, steps=STEPS,
        window_s=summary.window_s if window_s is None else window_s, chips=1,
        peaks=peaks_for("TPU v5 lite"), flops_per_step=125378101248.0,
        rule_bytes_per_chip_step=32 * 272970 * 16)


@pytest.mark.parametrize("name,lo,hi", [
    ("device_idle_pct", 0.0, 100.0), ("mfu", 0.0, 100.0),
    ("grad_ms", 20.0, 40.0), ("opt_ms", 0.5, 5.0),
    ("opt_roofline_pct", 0.0, 100.0), ("gossip_ms", 0.0, 1.0)])
def test_readers_on_the_recorded_trace(summary, name, lo, hi):
    v = _reader(name).read(_reading(summary))
    assert v is not None and lo < v < hi


def _ev(ts, dur, name, tf_op="", pid=1, tid=1):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": tf_op}}


META = [
    {"ph": "M", "pid": 1, "name": "process_name",
     "args": {"name": "/device:TPU:0"}},
    {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
     "args": {"name": "XLA Ops"}},
    {"ph": "M", "pid": 9, "name": "process_name",
     "args": {"name": "/host:CPU"}},
    {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
     "args": {"name": "python3"}},
]


def test_self_time_and_exposed_collectives():
    events = META + [
        _ev(0, 100, "while.1", "jit(step)/tm/grad/while"),
        _ev(10, 20, "fusion.2", "jit(step)/tm/grad/vmap()/dot_general"),
        _ev(100, 50, "collective-permute-done.3",
            "jit(step)/tm/finish_mix/tm/opt_step/tm/gossip/ppermute/x"),
        _ev(120, 60, "fusion.4", "jit(step)/tm/finish_mix/tm/opt_step/add"),
        _ev(300, 10, "copy.5"),
        {"ph": "X", "pid": 9, "tid": 1, "ts": 180, "dur": 120,
         "name": "bench/next_batch"},
    ]
    s = trace.reduce_events(events)
    us = 1e-6
    assert s.layer_s["grad"] == pytest.approx(100 * us)
    assert s.layer_s["gossip"] == pytest.approx(50 * us)
    assert s.layer_s["opt"] == pytest.approx(60 * us)
    assert s.layer_s["other"] == pytest.approx(10 * us)
    assert s.busy_s == pytest.approx(190 * us)
    assert s.collective_s == pytest.approx(50 * us)
    assert s.exposed_collective_s == pytest.approx(20 * us)
    assert s.breakdown["idle_gaps"][0] == ["bench/next_batch",
                                           pytest.approx(120 * us)]


def test_window_is_the_step_program_runs():
    """The window runs from the start of run ``skip`` of the longest
    program to the end of run ``skip + programs - 1``; operations count
    where they start inside it."""
    meta = META + [{"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
                    "args": {"name": "XLA Modules"}}]
    runs = [_ev(s, d, n, tid=2) for s, d, n in (
        (0, 5, "jit_split(1)"), (10, 100, "jit_step(2)"),
        (115, 5, "jit_split(1)"), (200, 100, "jit_step(2)"),
        (305, 5, "jit_split(1)"), (310, 100, "jit_step(2)"))]
    ops = [_ev(s, 90, "fusion", "jit(step)/tm/grad/x")
           for s in (15, 205, 315)]
    s = trace.reduce_events(meta + runs + ops, skip=1, programs=2)
    us = 1e-6
    assert s.window_s == pytest.approx(210 * us)
    assert s.busy_s == pytest.approx(180 * us)
    assert s.layer_s == {"grad": pytest.approx(180 * us)}
    assert s.breakdown["idle_gaps"][0][1] == pytest.approx(20 * us)
    with pytest.raises(ValueError):
        trace.reduce_events(meta + runs + ops, skip=2, programs=2)
    with pytest.raises(ValueError):
        trace.reduce_events(META + ops, skip=0, programs=1)


def test_category():
    assert trace.category("jit(step)/tm/grad/x") == "grad"
    assert trace.category(
        "jit(step)/tm/finish_mix/tm/opt_step/tm/stage/gossip_mix/dot") \
        == "gossip"
    assert trace.category("jit(step)/tm/launch_mix/tm/gossip/ppermute/x") \
        == "gossip"
    assert trace.category(
        "jit(step)/tm/finish_mix/tm/opt_step/tm/fused_update/pallas_call") \
        == "opt"
    assert trace.category("") == "other"


def test_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events(META[2:])


def test_readers_return_nothing_on_an_empty_layer():
    s = types.SimpleNamespace(layer_s={}, busy_s=0.0, collective_s=0.0,
                              exposed_collective_s=0.0)
    r = _reading(s, window_s=1.0)
    for name in ("grad_ms", "opt_ms", "opt_roofline_pct", "gossip_ms",
                 "device_idle_pct"):
        assert _reader(name).read(r) is None, name

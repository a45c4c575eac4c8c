"""The trace reduction on a chip trace that carries the program's own host
spans and named Pallas kernels.

``data/resnet20_ring16_6steps_spans.trace.json.gz`` is one traced call of six
steps of ``resnet20_ring16_dir0.1_hybrid`` on one v5e chip
(``harness.traced_window``), trimmed as the trace of ``test_bench_trace.py``
is; of the host's events it also keeps every ``train`` step span and every
``tm/*`` span.  The reduction reads the device as it does without them.  The
host spans are read here by hand, as a reader of them would: the k-th
``train`` span with the k-th run of the step program, since the host runs
ahead of the device and a cut by time would miss them.
"""
from __future__ import annotations

import gzip
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "resnet20_ring16_6steps_spans.trace.json.gz")
SETTLE, STEPS = 2, 4
NEXT, PUT, DISPATCH, FETCH = ("tm/host/next_batch", "tm/host/put_batch",
                              "tm/host/dispatch", "tm/host/fetch")


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)["traceEvents"]


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(DATA, [0], skip=SETTLE, programs=STEPS)


def _host(events) -> list:
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    return [e for e in events if e.get("ph") == "X"
            and procs[e["pid"]].startswith("/host:")]


def _steps(events) -> list:
    return sorted((e for e in _host(events) if e["name"] == "train"),
                  key=lambda e: e["ts"])


def _nested(events, step) -> list:
    return sorted((e for e in _host(events)
                   if e["name"].startswith("tm/host/")
                   and e["tid"] == step["tid"] and step["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= step["ts"] + step["dur"]),
                  key=lambda e: e["ts"])


def _step_runs(events) -> list:
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    return [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6, e["name"])
            for e in events if e.get("ph") == "X"
            and threads.get((e["pid"], e["tid"])) == trace.MODULES_THREAD]


def test_device_reading_with_program_spans(summary):
    """The program's host spans leave the device's reading as it was: four
    steps of about 35 ms, the device busy nearly throughout, the model's
    gradient most of it."""
    assert 4 * 0.030 < summary.window_s < 4 * 0.040
    assert summary.busy_s > 0.99 * summary.window_s
    assert sum(summary.layer_s.values()) == pytest.approx(summary.busy_s,
                                                           rel=1e-9)
    assert 30.0 < 1e3 * summary.layer_s["grad"] / STEPS < 33.0
    assert 0 < summary.layer_s["gossip"] < summary.layer_s["opt"]


def test_named_kernels_run_under_opt_step(events):
    """The fused QG kernels carry their names and sit under
    ``tm/opt_step``, so ``opt_ms`` reads them."""
    scopes = {e["args"]["tf_op"] for e in events
              if e.get("ph") == "X" and e.get("args", {}).get("tf_op")}
    for kernel in ("fused_halfstep", "fused_qg_buffer"):
        named = [s for s in scopes if f"/{kernel}/pallas_call" in s]
        assert named and all(trace.category(s) == "opt" for s in named)


def test_step_spans_nest_the_host_work(events):
    """One ``train`` span per iteration, numbered from 0, holding the batch
    pull, the rng split, the placement of the batch (with its bytes) and
    the step call; the last also holds the loop's one fetch."""
    steps = _steps(events)
    assert [int(s["args"]["step_num"]) for s in steps] == list(
        range(SETTLE + STEPS))
    for k, s in enumerate(steps):
        inside = _nested(events, s)
        want = [NEXT, DISPATCH, PUT, DISPATCH]
        if k == len(steps) - 1:
            want.append(FETCH)
        assert [e["name"] for e in inside] == want, k
        # 16 nodes x 32 images of 32x32x3 fp32, and their int32 labels
        put = next(e for e in inside if e["name"] == PUT)
        assert int(put["args"]["bytes"]) == 16 * 32 * (32 * 32 * 3 + 1) * 4


@pytest.mark.parametrize("names,lo,hi", [((NEXT, PUT), 4.0, 7.0),
                                         ((DISPATCH,), 3.0, 5.0)])
def test_host_ms_of_the_read_steps(events, summary, names, lo, hi):
    """Paired with the step runs the window reads, each ``train`` span
    starts before its run (the host is ahead), and the batch feed and the
    dispatch take the host milliseconds per step read from the trace by
    hand, together less than the device's step."""
    steps, runs = _steps(events), _step_runs(events)
    per_step = {NEXT: 0.0, PUT: 0.0, DISPATCH: 0.0}
    for k in range(SETTLE, SETTLE + STEPS):
        assert steps[k]["ts"] * 1e-6 < trace.step_window(runs, k, 1)[0]
        for e in _nested(events, steps[k]):
            if e["name"] in per_step:
                per_step[e["name"]] += e["dur"] * 1e-3 / STEPS
    assert lo < sum(per_step[n] for n in names) < hi
    assert sum(per_step.values()) < 1e3 * summary.window_s / STEPS

"""Host spans and the compile counter (``repro.telemetry.trace``): the
training loops' step and host spans in a CPU profiler trace, the in-process
registry off by default, histories untouched by tracing, and compile counts
after warm-up."""
from __future__ import annotations

import gzip
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from repro import api
from repro.api import presets
from repro.core import optim, topology
from repro.data import ClientDataset, dirichlet_partition, make_classification
from repro.telemetry import trace
from repro.train import (DecentralizedTrainer, run_training,
                         run_training_scanned)

NEXT, PUT, DISPATCH = ("tm/host/next_batch", "tm/host/put_batch",
                       "tm/host/dispatch")
silent = lambda *_: None


@pytest.fixture(autouse=True)
def tracing_off():
    trace.disable()
    yield
    trace.disable()


def _job(n_nodes=4, batch=8):
    x, y = make_classification(n=256, hw=4, seed=0)
    x = x.reshape(len(x), -1)
    ds = ClientDataset((x, y), dirichlet_partition(y, n_nodes, 1.0, seed=0),
                       batch=batch, seed=0)

    def init_fn(key):
        return {"w": jax.random.normal(key, (x.shape[1], 10)) * 0.05}, {}

    def loss_fn(p, ms, b, rng):
        xb, yb = b
        logits = xb @ p["w"]
        ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, yb.astype(jnp.int32)[:, None], -1)[:, 0])
        return ce, ({}, {})

    tr = DecentralizedTrainer(loss_fn, optim.make_optimizer("qg_dsgdm_n",
                                                            lr=0.05),
                              topology.ring(n_nodes))
    return tr, tr.init(jax.random.PRNGKey(0), init_fn), ds


def _loop(scanned, tr, st, it, steps, **kw):
    if scanned:
        return run_training_scanned(tr, st, it, steps, chunk=2,
                                    log_fn=silent, **kw)
    return run_training(tr, st, it, steps, log_fn=silent, **kw)


def _host_events(out_dir) -> list:
    path = sorted(pathlib.Path(out_dir).rglob("*.trace.json.gz"))[-1]
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    return [e for e in events if e.get("ph") == "X"
            and (e["name"] == "train" or e["name"].startswith("tm/"))]


@pytest.mark.parametrize("scanned", [False, True])
def test_loop_spans_in_a_profiler_trace(tmp_path, scanned):
    """Each iteration (a chunk of 2 in the scanned loop) is one ``train``
    span carrying its first step, holding the batch pull, its placement and
    the dispatch, in that order (the per-step loop splits its rng, a
    dispatch, before the placement); the final step's fetch sits in the
    last."""
    tr, st, ds = _job()
    st, _ = _loop(scanned, tr, st, iter(ds.next_batch, None), 4)  # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        _loop(scanned, tr, st, iter(ds.next_batch, None), 6, step_offset=4)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    steps = sorted((e for e in events if e["name"] == "train"),
                   key=lambda e: e["ts"])
    want = [4, 6, 8] if scanned else [4, 5, 6, 7, 8, 9]
    assert [int(e["args"]["step_num"]) for e in steps] == want
    for k, s in enumerate(steps):
        inside = sorted((e for e in events if e["name"].startswith("tm/host/")
                         and e["tid"] == s["tid"]
                         and s["ts"] <= e["ts"]
                         and e["ts"] + e["dur"] <= s["ts"] + s["dur"]),
                        key=lambda e: e["ts"])
        names = [e["name"] for e in inside]
        want = ([NEXT, PUT, DISPATCH] if scanned
                else [NEXT, DISPATCH, PUT, DISPATCH])
        if k == len(steps) - 1:
            want.append("tm/host/fetch")
        assert names == want, k
        put = next(e for e in inside if e["name"] == PUT)["args"]["bytes"]
        assert int(put) == (2 if scanned else 1) * sum(
            a.nbytes for a in ds.next_batch())


def test_tracing_off_records_nothing():
    """Off by default: spans are the bare profiler annotation, no registry
    totals, and no monitoring listener of the compile counter."""
    assert trace.totals() == {}
    assert isinstance(trace.host_span("tm/host/x"),
                      jax.profiler.TraceAnnotation)
    tr, st, ds = _job()
    run_training(tr, st, iter(ds.next_batch, None), 3, log_fn=silent)
    assert trace.totals() == {}
    events = monitoring.get_event_listeners()
    spans = monitoring.get_event_time_span_listeners()
    reg = trace.enable()
    assert trace.enable() is reg                 # once per process
    assert len(monitoring.get_event_listeners()) == len(events) + 1
    assert len(monitoring.get_event_time_span_listeners()) == len(spans) + 1
    trace.disable()
    assert monitoring.get_event_listeners() == events
    assert monitoring.get_event_time_span_listeners() == spans
    assert trace.totals() == {}


@pytest.mark.parametrize("chunk", [1, 2])
def test_history_is_identical_with_tracing_on(chunk):
    spec = presets.get("quickstart_ring16_alpha0.1_qg").override(
        "loop.steps=5", "loop.log_every=1", f"loop.chunk={chunk}")
    off = api.run(spec).history
    trace.enable()
    on = api.run(spec).history
    spans = trace.totals()["spans"]
    assert on == off
    assert spans["tm/setup/data"]["count"] == 1
    per_step = 2 if chunk == 1 else 1        # rng split, then the step
    assert spans["tm/host/dispatch"]["count"] == per_step * -(-5 // chunk)
    assert spans["tm/host/fetch"]["count"] == 5


def test_compile_counter_after_warm_up():
    """After warm-up, more steps compile nothing; a new batch shape compiles
    the step again."""
    tr, st, ds = _job()
    st, _ = run_training(tr, st, iter(ds.next_batch, None), 2,
                         log_fn=silent)
    trace.enable()
    trace.reset()
    st, _ = run_training(tr, st, iter(ds.next_batch, None), 3,
                         log_fn=silent)
    totals = trace.totals()
    assert totals["compile"]["count"] == 0
    assert totals["spans"]["tm/host/put_batch"]["count"] == 3
    smaller = (tuple(a[:, :4] for a in ds.next_batch()) for _ in range(2))
    run_training(tr, st, smaller, 2, log_fn=silent)
    comp = trace.totals()["compile"]
    assert comp["count"] >= 1
    assert comp["s"] > 0 and comp["phases"]["backend"]["count"] >= 1
    assert sum(f["count"] for f in comp["by_fun"].values()) == comp["count"]


def test_compile_log_counts_nested_phases_once():
    log = trace.CompileLog()
    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    log.on_time_span(trace_ev, 0.0, 4.0, fun_name="outer")
    log.on_time_span(trace_ev, 1.0, 2.0, fun_name="inner")  # nested trace
    log.on_time_span(backend, 5.0, 8.0, fun_name="outer")
    log.on_time_span("/jax/other", 0.0, 100.0, fun_name="x")
    log.on_event(trace.CACHE_REQUEST)
    log.on_event(trace.CACHE_HIT)
    t = log.totals()
    assert t["count"] == 1 and t["s"] == pytest.approx(7.0)
    assert t["phases"]["trace"] == {"count": 2, "s": pytest.approx(4.0)}
    assert t["by_fun"]["outer"] == {"count": 1, "s": pytest.approx(7.0)}
    assert t["by_fun"]["inner"] == {"count": 0, "s": pytest.approx(1.0)}
    assert (t["cache_requests"], t["cache_hits"]) == (1, 1)
    assert log.summary() == "compile_s=3.0 cache_hits=1/1"
    log.reset()
    assert log.totals()["count"] == 0 and log.seconds() == 0.0


def test_registry_times_spans():
    reg = trace.enable()
    with trace.host_span("tm/host/x", bytes=3):
        np.zeros(10).sum()
    with trace.host_span("tm/host/x"):
        pass
    spans = trace.totals()["spans"]
    assert spans["tm/host/x"]["count"] == 2 and spans["tm/host/x"]["s"] >= 0
    trace.reset()
    assert trace.totals()["spans"] == {} and reg.spans == {}


@pytest.mark.parametrize("scanned", [False, True])
@pytest.mark.parametrize("log_every, recorded", [(0, 1), (2, 3)])
def test_consensus_counter_counts_recorded_steps(scanned, log_every,
                                                 recorded):
    """``tm/metrics/consensus`` counts the steps dispatched with ``record``
    true: the final step alone without logging, else each ``log_every``
    boundary and the final step.  Every recorded row has its consensus."""
    tr, st, ds = _job()
    trace.enable()
    _, hist = _loop(scanned, tr, st, iter(ds.next_batch, None), 5,
                    log_every=log_every)
    assert trace.totals()["counters"]["tm/metrics/consensus"] == recorded
    assert len(hist) == recorded
    assert all(np.isfinite(r["consensus"]) for r in hist)

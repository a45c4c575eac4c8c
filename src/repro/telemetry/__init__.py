"""Pluggable telemetry: in-graph collectors, trace spans, sinks
(DESIGN.md §10).

Three layers, composable or separable:

  * **collectors** (:mod:`repro.telemetry.metrics`) — pure functions run
    INSIDE the jitted step on both execution backends, selected by a
    :class:`MetricsSpec`; the step cadence is gated on the HOST (the loops
    pick a separately compiled collecting trace per step/chunk), so the
    telemetry-off — and off-cadence — path runs the exact telemetry-less
    graph;
  * **spans + timing** (:mod:`repro.telemetry.trace`) — host spans on the
    profiler's timeline (``host_span``, ``step_span``), an in-process
    registry of span seconds and a compile counter (``CompileLog``), both
    off until ``trace.enable()``, and a host-side ring-buffer step timer;
  * **sinks + recorder** (:mod:`repro.telemetry.sinks`, ``.recorder``) —
    the host side: split ``tm.`` keys off the step metrics, stream rows to
    memory/JSONL/CSV, summarize.

Spec-level entry point: set ``telemetry=TelemetrySpec(enabled=True)`` on an
:class:`repro.api.ExperimentSpec` and ``run(spec)`` emits ``metrics.jsonl``
next to the Result; render it with ``python -m repro.telemetry.report``.
"""
from repro.telemetry.metrics import (
    METRICS, DEFAULT_METRICS, TM_PREFIX, CollectorCtx, MetricsSpec,
    TelemetryConfig, resolve_config)
from repro.telemetry.recorder import TelemetryRecorder
from repro.telemetry.sinks import (
    SINKS, CsvSink, JsonlSink, MemorySink, TelemetrySink, make_sink,
    read_csv, read_jsonl)
from repro.telemetry.trace import CompileLog, StepTimer, host_span, step_span

__all__ = [
    "METRICS", "DEFAULT_METRICS", "TM_PREFIX", "CollectorCtx", "MetricsSpec",
    "TelemetryConfig", "resolve_config", "TelemetryRecorder", "SINKS",
    "CsvSink", "JsonlSink", "MemorySink", "TelemetrySink", "make_sink",
    "read_csv", "read_jsonl", "StepTimer", "CompileLog", "host_span",
    "step_span",
]

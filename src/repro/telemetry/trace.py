"""Host spans, a compile counter and host-side step timing (DESIGN.md §10).

The device side needs nothing from here: the step's phases carry
``jax.named_scope`` labels (``tm/grad``, ``tm/opt_step``, ``tm/stage/<name>``,
``tm/gossip/*``, ``tm/comm/*``), written where the phases are traced
(``runtime/base.py``, ``core/transforms.py``, ``core/gossip.py``,
``comm/choco.py``).  They are HLO metadata and cost nothing at run time.

The host side, here:

  * :func:`host_span` — a ``jax.profiler.TraceAnnotation``: a span on the
    profiler's host timeline, on the same clock as the device's operations.
    The training loops mark their host work with it (``tm/host/next_batch``,
    ``tm/host/put_batch``, ``tm/host/dispatch``, ``tm/host/fetch``) and
    ``api.build`` its data synthesis (``tm/setup/data``).
  * :func:`step_span` — ``jax.profiler.StepTraceAnnotation("train",
    step_num=step)``, one per loop iteration (per chunk in the scanned
    loop); that iteration's host spans nest inside it.
  * :class:`CompileLog` — compiles counted and timed by function from JAX's
    own monitoring events: tracing, lowering and backend compilation (which
    includes loading from the persistent cache), and cache requests and hits.
  * :func:`enable` / :func:`disable` / :func:`totals` / :func:`reset` — the
    in-process registry.  Tracing is OFF by default: :func:`host_span` is
    then the bare annotation (nothing is recorded unless a profiler trace is
    running) and no monitoring listener is registered.  Enabled, each span
    also adds its ``perf_counter`` seconds and a count under its name, and
    the compile counter listens.  One registry per process, because JAX's
    monitoring listeners are process-wide.
  * :func:`count` — a named counter in the same registry, for facts of a
    trace (which gradient path a step compiled with, the gossip bytes it
    sends) and of a loop (the steps that compute the consensus metric).
  * :class:`StepTimer` — host wall-clock per dispatched step, kept in a
    fixed-size ring buffer with percentile summaries (p50/p90/p99).  The
    recorder drives it; its summary lands in ``Result.telemetry``.
"""
from __future__ import annotations

import time

import jax

__all__ = ["host_span", "step_span", "CompileLog", "Registry", "enable",
           "disable", "count", "totals", "reset", "StepTimer"]

# the compile phases JAX reports, by the name they are counted under
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _union_s(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, hi = 0.0, None
    for s, e in sorted(intervals):
        if hi is None or s > hi:
            total += e - s
            hi = e
        elif e > hi:
            total += e - hi
            hi = e
    return total


class CompileLog:
    """Compiles counted and timed from JAX's monitoring events.

    A phase's seconds are the union of its intervals, so the trace of a
    jitted function nested in another's is not counted twice; ``by_fun``
    sums each function's own intervals over the three phases.  A backend
    compile is one compiled program, whether compiled or loaded from the
    persistent cache.  :func:`enable` registers the listeners."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.intervals = {p: [] for p in COMPILE_EVENTS.values()}
        self.by_fun: dict = {}        # fun_name -> [backend compiles, s]
        self.requests = self.hits = 0

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_REQUEST:
            self.requests += 1
        elif event == CACHE_HIT:
            self.hits += 1

    def on_time_span(self, event: str, start: float, end: float, *,
                     fun_name: str = "", **_) -> None:
        phase = COMPILE_EVENTS.get(event)
        if phase is None:
            return
        self.intervals[phase].append((start, end))
        entry = self.by_fun.setdefault(fun_name, [0, 0.0])
        entry[0] += phase == "backend"
        entry[1] += end - start

    @property
    def compiles(self) -> int:
        """Programs compiled (or loaded from the persistent cache)."""
        return len(self.intervals["backend"])

    def seconds(self, phase: str | None = None) -> float:
        """Seconds spent in ``phase``, or in any of the three."""
        if phase is not None:
            return _union_s(self.intervals[phase])
        return _union_s(iv for ivs in self.intervals.values() for iv in ivs)

    def totals(self) -> dict:
        return {
            "count": self.compiles, "s": self.seconds(),
            "phases": {p: {"count": len(iv), "s": _union_s(iv)}
                       for p, iv in self.intervals.items()},
            "cache_requests": self.requests, "cache_hits": self.hits,
            "by_fun": {f: {"count": n, "s": s}
                       for f, (n, s) in self.by_fun.items()},
        }

    def summary(self) -> str:
        return (f"compile_s={self.seconds('backend'):.1f} "
                f"cache_hits={self.hits}/{self.requests}")


class Registry:
    """Seconds and counts of host spans by name, named counters, and the
    compile counter."""

    def __init__(self):
        self.spans: dict = {}         # name -> [count, seconds]
        self.counters: dict = {}      # name -> count
        self.compiles = CompileLog()

    def add(self, name: str, seconds: float) -> None:
        entry = self.spans.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += seconds

    def count(self, name: str, amount: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + amount

    def reset(self) -> None:
        self.spans.clear()
        self.counters.clear()
        self.compiles.reset()

    def totals(self) -> dict:
        return {"spans": {k: {"count": n, "s": s}
                          for k, (n, s) in self.spans.items()},
                "counters": dict(self.counters),
                "compile": self.compiles.totals()}


_registry: Registry | None = None


def enable() -> Registry:
    """Turn the registry on (once per process; again returns the same one)
    and register the compile counter's monitoring listeners."""
    global _registry
    if _registry is None:
        reg = Registry()
        jax.monitoring.register_event_listener(reg.compiles.on_event)
        jax.monitoring.register_event_time_span_listener(
            reg.compiles.on_time_span)
        _registry = reg
    return _registry


def disable() -> None:
    """Turn the registry off and remove its listeners."""
    global _registry
    reg, _registry = _registry, None
    if reg is not None:
        jax.monitoring.unregister_event_listener(reg.compiles.on_event)
        jax.monitoring.unregister_event_time_span_listener(
            reg.compiles.on_time_span)


def count(name: str, amount: int = 1) -> None:
    """Add ``amount`` to the registry's counter ``name`` (nothing when
    tracing is off).  The runtimes count here, at trace time, facts of each
    compiled step: its gradient path (``tm/grad/node_batched`` or
    ``tm/grad/vmap``) and the bytes one device sends in the gossip rounds
    of a step (``tm/gossip/bytes``).  The training loops count here each
    step they dispatch with ``record`` true, the steps that compute the
    ``consensus`` metric (``tm/metrics/consensus``)."""
    if _registry is not None:
        _registry.count(name, amount)


def totals() -> dict:
    """``{"spans": {name: {count, s}}, "counters": {name: count},
    "compile": {...}}``; ``{}`` when tracing is off."""
    return {} if _registry is None else _registry.totals()


def reset() -> None:
    """Zero the registry's spans and compile counts (no-op when off)."""
    if _registry is not None:
        _registry.reset()


class _TimedSpan:
    __slots__ = ("_reg", "_name", "_ann", "_t0")

    def __init__(self, reg: Registry, name: str, args: dict):
        self._reg, self._name = reg, name
        self._ann = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._reg.add(self._name, time.perf_counter() - self._t0)
        return False


def host_span(name: str, **args):
    """A span on the profiler's host timeline (``args`` become its
    arguments there); also timed into the registry when tracing is on."""
    if _registry is None:
        return jax.profiler.TraceAnnotation(name, **args)
    return _TimedSpan(_registry, name, args)


def step_span(step: int):
    """The parent span of one training-loop iteration, ``train`` with its
    ``step_num``: the profiler's step marker."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


class StepTimer:
    """Ring buffer of host-side per-step wall times with percentile
    summaries.

    Usage: ``timer.lap()`` after every dispatched step (or
    ``timer.lap(steps=k)`` after a k-step fused chunk — the chunk time is
    attributed evenly).  The first lap after construction/reset only arms
    the clock; compile time is excluded by calling :meth:`arm` after
    warm-up (the recorder does this on its first consumed step).
    """

    def __init__(self, capacity: int = 512):
        if capacity < 1:
            raise ValueError("StepTimer capacity must be >= 1")
        self.capacity = capacity
        self._buf: list[float] = []
        self._next = 0          # ring write cursor
        self._t0: float | None = None
        self.total_laps = 0
        self.last_s = 0.0       # most recent per-step lap (read by probes)

    def arm(self) -> None:
        """Start (or restart) the clock; the next lap measures from here."""
        self._t0 = time.perf_counter()

    def lap(self, steps: int = 1) -> None:
        """Record the time since the last lap/arm, split over ``steps``."""
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now
            return
        per_step = (now - self._t0) / max(steps, 1)
        self._t0 = now
        self.last_s = per_step
        for _ in range(steps):
            if len(self._buf) < self.capacity:
                self._buf.append(per_step)
            else:
                self._buf[self._next] = per_step
                self._next = (self._next + 1) % self.capacity
            self.total_laps += 1

    def summary(self) -> dict:
        """{count, mean_s, p50_s, p90_s, p99_s, steps_per_s} over the
        retained window (empty dict before the first measured lap)."""
        if not self._buf:
            return {}
        xs = sorted(self._buf)

        def pct(q: float) -> float:
            # nearest-rank on the retained window
            idx = min(int(q * len(xs)), len(xs) - 1)
            return xs[idx]

        mean = sum(xs) / len(xs)
        return {
            "count": self.total_laps,
            "mean_s": mean,
            "p50_s": pct(0.50),
            "p90_s": pct(0.90),
            "p95_s": pct(0.95),
            "p99_s": pct(0.99),
            "steps_per_s": (1.0 / mean) if mean > 0 else float("inf"),
        }

"""Reduction of a profiler trace to per-layer device time.

Reads the trace-viewer file that ``jax.profiler`` writes beside its
``.xplane.pb`` (``<host>.trace.json.gz``), with nothing but ``gzip`` and
``json``.  Each chip is a process ``/device:TPU:<id>``; its thread
``XLA Ops`` holds one complete event per executed HLO operation, whose
``args.tf_op`` is the operation's source scope (the ``jax.named_scope``
path, e.g. ``jit(step)/tm/grad/vmap(jvp())/conv_general_dilated``).  The
chip's thread ``XLA Modules`` holds one event per run of a compiled program.
The host's process ``/host:CPU`` carries the benchmark's own ``bench/*``
spans, on the same clock (microseconds).

The window read is the device's own: from the start of one run of the step
program (the program that ran longest in all) to the end of a later one, as
the first chip recorded them, so that the steps before it, which fill the
pipeline from the host, are left out.  Operations count where they start
inside it.  What comes out (``Summary``), per chip and then averaged:

* busy seconds: the union of the intervals in which an operation ran;
* device seconds by layer, from each operation's self time (its duration
  less that of operations nested in it) and its scope: ``grad`` under
  ``tm/grad``; ``gossip`` under ``tm/stage/gossip_mix``, ``tm/gossip/`` or
  ``tm/launch_mix``; ``opt`` under ``tm/opt_step`` and not gossip;
  ``other`` for the rest (operations with no scope, such as async copies);
* exposed collective seconds: time in collective operations during which no
  other operation ran on that chip (the largest over chips);
* ``breakdown``: the operations that took most time, by scope, and the
  longest idle gaps, by the host span that was open at their middle.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import pathlib
import re

__all__ = ["Summary", "Reading", "reduce", "reduce_events", "category",
           "trace_file", "step_window"]

OPS_THREAD = "XLA Ops"
MODULES_THREAD = "XLA Modules"
GOSSIP_SCOPES = ("tm/stage/gossip_mix", "tm/gossip/", "tm/launch_mix")
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float                # length of the window read
    busy_s: float                  # mean over chips
    layer_s: dict                  # layer -> device seconds, mean over chips
    exposed_collective_s: float    # largest over chips
    collective_s: float            # mean over chips
    breakdown: dict


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets."""

    summary: Summary
    steps: int                     # decentralized steps in the traced window
    window_s: float                # seconds of the window read
    chips: int
    peaks: object                  # bench.peaks.Peaks
    flops_per_step: float          # model FLOPs of one step, all nodes
    rule_bytes_per_chip_step: float  # QG rule bytes per chip per step


def category(scope: str) -> str:
    if any(s in scope for s in GOSSIP_SCOPES):
        return "gossip"
    if "tm/grad" in scope:
        return "grad"
    if "tm/opt_step" in scope:
        return "opt"
    return "other"


def _is_collective(name: str) -> bool:
    name = name.lower()
    return any(c in name for c in COLLECTIVES)


def _short_scope(scope: str, name: str) -> str:
    """The named scopes and the primitive, without ``jit(...)``/``vmap(...)``
    wrappers; the HLO operation's name where it has no scope."""
    if not scope:
        return re.sub(r"\.\d+$", "", name)
    parts = [p for p in scope.rstrip(":").split("/")
             if p and not re.fullmatch(r"[\w.]+\(.*\)", p)]
    return "/".join(parts)[-96:]


@dataclasses.dataclass
class _Op:
    start: float
    end: float
    name: str
    scope: str
    self_s: float = 0.0
    leaf: bool = True


def _nest(ops: list) -> list:
    """Self time of each operation: its duration less its children's."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: list = []
    for op in ops:
        op.self_s = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_s -= op.end - op.start
            stack[-1].leaf = False
        stack.append(op)
    return ops


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a: list, b: list) -> float:
    """Length of the union ``a`` not covered by the union ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _label(spans, t: float) -> str:
    """The shortest host span open at ``t``: what the host was doing."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host span"


def step_window(runs: list, skip: int, programs: int) -> tuple:
    """``(start, end)`` of runs ``skip`` to ``skip + programs - 1`` of the
    step program, the one of ``runs`` (``(start, end, name)``) that ran
    longest in all."""
    total: dict = {}
    for s, e, name in runs:
        total[name] = total.get(name, 0.0) + e - s
    if not total:
        raise ValueError("the trace has no program run on the device")
    step = max(total, key=total.get)
    mine = sorted((s, e) for s, e, name in runs if name == step)
    if len(mine) < skip + programs:
        raise ValueError(f"the trace has {len(mine)} runs of {step}, "
                         f"{skip + programs} wanted")
    return mine[skip][0], mine[skip + programs - 1][1]


def reduce_events(events: list, device_ids=None, skip: int = 0,
                  programs: int | None = None) -> Summary:
    """Reduce trace-viewer events (the ``traceEvents`` list) over runs
    ``skip`` to ``skip + programs - 1`` of the step program; over all the
    device's operations where ``programs`` is None."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    want = None if device_ids is None else {
        f"/device:TPU:{i}" for i in device_ids}
    chips = sorted(pid for pid, name in procs.items()
                   if name.startswith("/device:TPU:")
                   and (want is None or name in want))
    if not chips:
        raise ValueError("the trace has no TPU device")
    ops_by_chip = {pid: [] for pid in chips}
    runs, spans = [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        pid = e["pid"]
        start, end = e["ts"] * 1e-6, (e["ts"] + e.get("dur", 0.0)) * 1e-6
        thread = threads.get((pid, e["tid"]))
        if pid in ops_by_chip and thread == OPS_THREAD:
            ops_by_chip[pid].append(_Op(start, end, e.get("name", ""),
                                        e.get("args", {}).get("tf_op", "")))
        elif pid == chips[0] and thread == MODULES_THREAD:
            runs.append((start, end, e.get("name", "")))
        elif procs.get(pid, "").startswith("/host:") and end > start:
            spans.append((start, end, e.get("name", "")))
    if programs is None:
        every = [o for ops in ops_by_chip.values() for o in ops]
        if not every:
            raise ValueError("the trace has no operation on the device")
        lo, hi = min(o.start for o in every), max(o.end for o in every)
    else:
        lo, hi = step_window(runs, skip, programs)
    busy, layer, exposed, coll = [], [], [], []
    by_scope: dict = {}
    gaps = []
    for i, pid in enumerate(chips):
        ops = [o for o in _nest(ops_by_chip[pid]) if lo <= o.start < hi]
        busy_u = _union((o.start, min(o.end, hi)) for o in ops)
        busy.append(_length(busy_u))
        per: dict = {}
        for o in ops:
            c = category(o.scope)
            per[c] = per.get(c, 0.0) + o.self_s
            k = f"{c}: {_short_scope(o.scope, o.name)}"
            by_scope[k] = by_scope.get(k, 0.0) + o.self_s / len(chips)
        layer.append(per)
        c_u = _union((o.start, o.end) for o in ops if _is_collective(o.name))
        comp_u = _union((o.start, o.end) for o in ops
                        if o.leaf and not _is_collective(o.name))
        coll.append(_length(c_u))
        exposed.append(_minus(c_u, comp_u))
        if i == 0 and busy_u:
            edges = [lo] + [x for iv in busy_u for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    gaps.append((e - s, (s + e) / 2))
    layers = sorted({k for p in layer for k in p})
    return Summary(
        window_s=hi - lo,
        busy_s=sum(busy) / len(busy),
        layer_s={k: sum(p.get(k, 0.0) for p in layer) / len(layer)
                 for k in layers},
        exposed_collective_s=max(exposed),
        collective_s=sum(coll) / len(coll),
        breakdown={
            "device_ops": [[k, v] for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_label(spans, mid), dur] for dur, mid in
                          sorted(gaps, reverse=True)[:TOP]],
        })


def trace_file(out_dir: str) -> pathlib.Path:
    """The trace-viewer file of the newest profile under ``out_dir``."""
    files = sorted(pathlib.Path(out_dir).rglob("*.trace.json.gz"))
    if not files:
        raise FileNotFoundError(f"no *.trace.json.gz under {out_dir}")
    return files[-1]


def reduce(path, device_ids=None, skip: int = 0,
           programs: int | None = None) -> Summary:
    with gzip.open(path, "rt") as f:
        return reduce_events(json.load(f)["traceEvents"], device_ids, skip,
                             programs)

"""Run one benchmark cell: set up, check the first steps, time a window.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json`` with the plain reference
beside it (``<config>.py``), its traffic mix in ``bench/traffic/<mix>.json``,
its limits in ``bench/limits/<cell>.json`` and each per-layer metric's reader
in ``bench/metrics/<metric>.py``.

One run, in one process:

1. set-up (``setup_s``): build the job with ``repro.api.build``, put weights
   made from the seed by the configuration's own ``init_params`` into the
   trainer's state, and drive that state through the first three steps with
   ``repro.train.run_training`` on the task's own batch iterator, the call
   and feed the window uses.  Those steps compile the step and are read for
   the check: the loss of each, the quasi-global buffer after the first and
   the parameters' change after the third.
2. the window: the same trainer, state and iterator, ``run_training`` in
   blocks of ``block_steps`` until ``--seconds`` have passed, ending in
   ``jax.block_until_ready`` on the parameters.  With ``--trace 1`` one
   traced call takes its place: ``trace_settle_steps`` that fill the
   pipeline from the host, then ``trace_steps`` that are reduced to the
   per-layer metrics.
3. after the window: the device's peak memory is read, the program's state
   is freed, and the reference follows the same three steps from the same
   weights and batches (``bench/reference.py``); ``bench/compare.py``
   decides ``correct``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHECKED_STEPS = 3

__all__ = ["Cell", "load_cell", "run_cell", "main"]


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s workloads, with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    model: object                  # the configuration's reference module
    limits: dict
    per_layer: list                # [(metric entry, reader module)]
    end_to_end: list               # metric entries this cell reports


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    per_layer = [(m, _load_module(root / "bench" / "metrics" /
                                  f"{m['name']}.py", f"bench_metric_{i}"))
                 for i, m in enumerate(bench["per_layer"])
                 if _reports(m, name)]
    return make_cell(
        name, w["config"], w["traffic"], int(w["chips"]),
        limits=_read_json(root / "bench" / "limits" / f"{name}.json"),
        per_layer=per_layer,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        root=root)


def make_cell(name: str, config: str, traffic: str, chips: int, *,
              limits: dict, per_layer=(), end_to_end=(),
              root: pathlib.Path = ROOT) -> Cell:
    """A cell from its configuration's and traffic mix's files."""
    cfg = root / "bench" / "configs" / config
    return Cell(
        name=name, chips=chips, config=_read_json(cfg.with_suffix(".json")),
        traffic=_read_json(root / "bench" / "traffic" / f"{traffic}.json"),
        model=_load_module(cfg.with_suffix(".py"), f"bench_config_{config}"),
        limits=limits, per_layer=list(per_layer),
        end_to_end=list(end_to_end))


def make_spec(cell: Cell, seed: int):
    """The cell's job as a ``repro.api`` spec: the configuration's model and
    data shapes, the traffic mix's nodes, skew, batch and optimizer."""
    from repro.api.spec import ExperimentSpec
    c, t = cell.config, cell.traffic
    data = dict(c["data"], alpha=t["alpha"], batch=t["batch"],
                min_per_client=t["min_per_client"])
    for k in ("seq_len", "n_seq_per_domain"):
        if k in t:
            data[k] = t[k]
    opt = t["optimizer"]
    return ExperimentSpec.from_dict({
        "name": cell.name, "seed": seed, "runtime": t["runtime"],
        "data": data,
        "topology": {"name": t["topology"], "n": t["nodes"]},
        "optim": {"name": opt["name"], "lr": opt["lr"],
                  "weight_decay": opt["weight_decay"], "fused": t["fused"],
                  "kwargs": {"beta": opt["beta"], "mu": opt["mu"]}},
        "loop": {"steps": 1 << 30, "chunk": t["chunk"]},
        "eval": {"enabled": False},
        "model": c["model"],
    }).validate()


class Feed:
    """The task's own batch iterator, as the training loop sees it.  Keeps
    the first ``keep`` batches for the reference, and marks each pull with a
    profiler span when ``annotate`` is set."""

    def __init__(self, it, keep: int):
        self._it, self._keep, self.kept = it, keep, []
        self.annotate = False

    def __iter__(self):
        return self

    def __next__(self):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench/next_batch"):
                batch = next(self._it)
        else:
            batch = next(self._it)
        if len(self.kept) < self._keep:
            self.kept.append(batch)
        return batch


def _silent(*_):
    pass


@dataclasses.dataclass
class Setup:
    """The one object set-up builds and the window drives."""

    trainer: object
    state: object
    feed: Feed
    rng: object
    chunk: int                     # loop.chunk of the spec
    program: object = None         # reference.Readings of the program

    def loop(self, steps: int) -> list:
        """``repro.api.run``'s loop for this spec (the scanned loop where
        the spec asks for chunks, else the per-step loop) for ``steps``
        more steps; returns its history."""
        from repro.train import run_training, run_training_scanned
        if self.chunk > 1:
            self.state, hist = run_training_scanned(
                self.trainer, self.state, self.feed, steps, chunk=self.chunk,
                rng=self.rng, log_fn=_silent)
        else:
            self.state, hist = run_training(
                self.trainer, self.state, self.feed, steps, rng=self.rng,
                log_fn=_silent)
        return hist


def set_up(cell: Cell, seed: int) -> Setup:
    """Build the job, seed its weights, drive the first steps, read them."""
    import jax
    from repro import api
    from bench import reference

    spec = make_spec(cell, seed % (1 << 31))
    mesh = None
    if cell.traffic["runtime"] in ("sharded", "hybrid"):
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh((cell.chips,), ("data",))
    ex = api.build(spec, mesh=mesh)
    _check_program_config(cell, ex)
    ex.state = None                   # the program's own init is replaced
    key = jax.random.PRNGKey(seed % (1 << 31))
    init = _init_fn(cell)
    su = Setup(trainer=ex.trainer, state=ex.trainer.init(key, init),
               feed=Feed(ex.task.make_iter(), CHECKED_STEPS),
               rng=jax.random.PRNGKey(0), chunk=int(cell.traffic["chunk"]))
    opt = cell.traffic["optimizer"]
    scale = 1.0 / ((1.0 - opt["mu"]) * (1.0 + opt["beta"]))
    losses, first_grad = [], None
    for step in range(CHECKED_STEPS):
        losses.append(float(su.loop(1)[-1]["loss"]))
        if step == 0:
            m_hat = su.state.opt_state["qg_buffer"]["m_hat"]
            first_grad = {k: v * scale
                          for k, v in reference.leaf_norms(m_hat).items()}
    delta = reference.leaf_norms(_delta_fn(init)(su.state.params, key))
    su.program = reference.Readings(losses=losses, first_grad=first_grad,
                                    delta=delta)
    return su


def _init_fn(cell: Cell):
    """The configuration's own initialisation, one jitted call on the
    device, returning ``(params, model_state)`` for ``trainer.init``."""
    import jax
    return jax.jit(lambda key: cell.model.init_params(key, cell.config))


def _delta_fn(init):
    """x_k - x_0 for node-stacked ``params``, x_0 made again from the key."""
    import jax
    return jax.jit(lambda params, key: jax.tree.map(
        lambda p, p0: p - p0[None], params, init(key)[0]))


def _check_program_config(cell: Cell, ex) -> None:
    """The program must hold the configuration's parameter count per node."""
    import jax
    import numpy as np
    held = sum(int(np.prod(l.shape[1:]))
               for l in jax.tree.leaves(ex.state.params))
    want = cell.model.param_count(cell.config)
    if held != want or want != cell.config["params_per_node"]:
        raise RuntimeError(
            f"{cell.name}: the program holds {held} parameters per node, the "
            f"configuration's shapes give {want} and its file "
            f"{cell.config['params_per_node']}")


def timed_window(su: Setup, block: int, seconds: float):
    """``run_training`` for ``block`` steps, then once more for as many
    steps as fill the rest of ``seconds`` at the pace of the first call;
    returns (steps, elapsed seconds, steps whose call ended with a
    non-finite loss).  The loop syncs only where ``run_training`` records
    its last step, so the device pipeline drains twice per window."""
    import jax
    steps = failed = 0
    t0 = time.perf_counter()
    todo = block
    while todo > 0:
        hist = su.loop(todo)
        steps += todo
        if not math.isfinite(hist[-1]["loss"]):
            failed += todo
        elapsed = time.perf_counter() - t0
        todo = math.ceil((seconds - elapsed) * steps / elapsed)
    jax.block_until_ready(su.state.params)
    return steps, time.perf_counter() - t0, failed


def traced_window(su: Setup, steps: int, out_dir: str) -> int:
    """One ``run_training`` call of ``steps`` under the profiler, which
    writes its trace under ``out_dir``; returns the steps whose call ended
    with a non-finite loss.  The reduction (``bench/trace.py``) reads the
    device's runs of the step program, not this call's host time."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    su.feed.annotate = True
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/run_training"):
            hist = su.loop(steps)
        with jax.profiler.TraceAnnotation("bench/block_until_ready"):
            jax.block_until_ready(su.state.params)
    finally:
        jax.profiler.stop_trace()
        su.feed.annotate = False
    return 0 if math.isfinite(hist[-1]["loss"]) else steps


def _devices(cell: Cell):
    import jax
    return jax.devices()[:cell.chips]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def check(cell: Cell, seed: int, program, kept_batches) -> dict:
    """Run the reference and compare; returns the numbers."""
    import jax
    from bench import compare, reference
    x0, _ = _init_fn(cell)(jax.random.PRNGKey(seed % (1 << 31)))
    x0 = jax.device_get(x0)
    ref = reference.run(cell.model, cell.config, cell.traffic, x0,
                        kept_batches)
    for line in compare.worst_leaves(program, ref):
        log(line)
    return compare.numbers(program, ref)


def log(msg: str) -> None:
    """A progress line on standard error, with the process's clock."""
    print(f"[bench {time.perf_counter():9.1f}] {msg}", file=sys.stderr,
          flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run of ``cell``; returns the result record (without printing)."""
    from bench import compare
    from bench.peaks import peaks_for

    devices = _devices(cell)
    su = set_up(cell, seed)
    setup_s = time.perf_counter() - t_start
    log(f"set-up done in {setup_s:.1f} s; checked-step losses "
        f"{su.program.losses}")
    metrics, extra = {}, {}
    if trace:
        from bench import trace as trace_lib
        settle = int(cell.traffic["trace_settle_steps"])
        read = int(cell.traffic["trace_steps"])
        steps = settle + read
        with tempfile.TemporaryDirectory() as tmp:
            failed = traced_window(su, steps, tmp)
            summary = trace_lib.reduce(
                trace_lib.trace_file(tmp), [d.id for d in devices],
                skip=settle // su.chunk, programs=read // su.chunk)
        window_s = summary.window_s
        reading = trace_lib.Reading(
            summary=summary, steps=read, window_s=window_s,
            chips=len(devices), peaks=peaks_for(devices[0].device_kind),
            flops_per_step=cell.traffic["nodes"]
            * cell.model.train_flops_per_node_step(cell.config, cell.traffic),
            rule_bytes_per_chip_step=32 * cell.config["params_per_node"]
            * cell.traffic["nodes"] / len(devices))
        for m, reader in cell.per_layer:
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra = {"busy_s": summary.busy_s, "window_s": window_s}
        breakdown = summary.breakdown
    else:
        steps, window_s, failed = timed_window(
            su, int(cell.traffic["block_steps"]), seconds)
        breakdown = None
    peak = memory_peak(devices)
    log(f"window done: {steps} steps run, {window_s:.3f} s read")
    if not trace:
        values = {"step_ms": 1e3 * window_s / steps, "setup_s": setup_s,
                  "peak_hbm_gb": peak / 1e9}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    program, kept = su.program, su.feed.kept
    del su
    gc.collect()
    nums = check(cell, seed, program, kept)
    log("reference done")
    correct = compare.verdict(nums, cell.limits) and failed == 0
    record = {
        "correct": correct, "attempted": steps, "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": peak, **extra},
    }
    if breakdown is not None:
        record["breakdown"] = breakdown
    record["checks"] = {k: {"value": nums[k], "limit": cell.limits[k]}
                        for k in compare.NUMBERS}
    return record


def configure_compile_cache() -> str | None:
    """The program's own compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one), with every program
    written to it, however quick its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def check_devices(chips: int) -> str | None:
    """None when JAX sees at least ``chips`` TPUs, else why not."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"no TPU here (platform {devices[0].platform!r})"
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devices)}"
    return None


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    cell = load_cell(args.workload)
    why_not = check_devices(cell.chips)
    if why_not:
        print(f"bench: {why_not}", file=sys.stderr)
        return 1
    configure_compile_cache()
    record = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    for k, c in record["checks"].items():
        print(f"{k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(record), flush=True)
    return 0

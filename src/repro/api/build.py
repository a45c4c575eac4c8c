"""The ONE assembly path: ``build(spec) -> Experiment`` and
``run(spec) -> Result``.

Every entry point (examples, ``benchmarks/common.py``, ``launch/train.py``)
goes through here, so partition + topology + optimizer + comm + gossip
schedule + loop are wired once, identically, from the spec — the hand-wired
constructors they replace are preserved bit-for-bit (pinned by
tests/test_api.py against the pre-refactor quickstart trajectory).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import count_mix_sites, make_comm
from repro.core import topology as topo_lib
from repro.core.optim import ChainOptimizer, make_optimizer
from repro.telemetry.trace import host_span
from repro.train import (DecentralizedTrainer, TrainState, lr_schedule,
                         run_training, run_training_scanned)

from .data import Task, build_task
from .models import MODELS, ModelBundle
from .spec import ExperimentSpec

__all__ = ["Experiment", "Result", "build", "run", "wire_stats"]


@dataclasses.dataclass
class Experiment:
    """A built (but not yet run) experiment: everything ``run`` needs."""

    spec: ExperimentSpec
    trainer: DecentralizedTrainer
    state: TrainState                  # freshly initialized
    task: Task
    bundle: ModelBundle

    @property
    def eval_fn(self):
        return self.bundle.eval_fn


@dataclasses.dataclass
class Result:
    """JSON-dumpable outcome of ``run(spec)``."""

    spec: dict
    history: list
    final: dict                        # last-step train metrics + eval
    steps_run: int
    wall_time_s: float
    wire: dict                         # bytes-on-the-wire accounting
    telemetry: Optional[dict] = None   # recorder summary (sink path, row
                                       # count, step-time percentiles) when
                                       # spec.telemetry.enabled
    heterogeneity: Optional[dict] = None  # partition stats from the task
                                       # (mean pairwise TV distance +
                                       # client-size extremes)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self, *, indent: int | None = 2) -> str:
        import json
        return json.dumps(self.to_dict(), indent=indent)


def _make_opt(spec: ExperimentSpec):
    o = spec.optim
    if o.stages:
        return ChainOptimizer(
            lr=o.lr, weight_decay=o.weight_decay, fused=o.fused,
            stage_specs=tuple((n, dict(kw)) for n, kw in o.stages))
    return make_optimizer(o.name, lr=o.lr, weight_decay=o.weight_decay,
                          fused=o.fused, **o.kwargs)


def build(spec: ExperimentSpec, *, mesh: Any = None) -> Experiment:
    """Validate the spec eagerly, then assemble trainer + init state + client
    data + model bundle.  ``mesh`` (a runtime object, hence not part of the
    spec) activates the sharded gossip schedules per ``spec.gossip``."""
    spec.validate()
    topo = topo_lib.get_topology(spec.topology.name, spec.topology.n)
    with host_span("tm/setup/data"):    # synthesis + Dirichlet partition
        task = build_task(spec, topo.n)
    bundle = MODELS[spec.model.name](spec, task)

    lp = spec.loop
    lr_fn = None
    if lp.warmup or lp.decay_at:
        lr_fn = lr_schedule(spec.optim.lr, total_steps=lp.steps,
                            warmup=lp.warmup, decay_at=lp.decay_at,
                            decay=lp.decay, warmup_from=lp.warmup_from)

    telemetry_cfg = None
    if spec.telemetry.enabled:
        from repro.telemetry import resolve_config
        telemetry_cfg = resolve_config(spec.telemetry.metrics,
                                       spec.telemetry.every)

    scenario = None
    sc = spec.scenario
    if sc.enabled:
        from repro.scenario import ScenarioContext
        scenario = ScenarioContext(
            n=topo.n, seed=sc.seed, participation=sc.participation,
            dropout=sc.dropout, churn_window=sc.churn_window,
            straggler=sc.straggler)

    trainer = DecentralizedTrainer(
        bundle.loss_fn, _make_opt(spec), topo, lr_fn=lr_fn,
        comm=make_comm(spec.comm.compressor, gamma=spec.comm.gamma,
                       error_feedback=spec.comm.error_feedback,
                       backend=spec.comm.backend),
        mesh=mesh, node_axis=spec.gossip.node_axis,
        gossip_schedule=spec.gossip.schedule, runtime=spec.runtime,
        overlap=spec.overlap, scenario=scenario, telemetry=telemetry_cfg,
        loss_nodes_fn=bundle.loss_nodes_fn)
    state = trainer.init(jax.random.PRNGKey(spec.seed), bundle.init_fn)
    if telemetry_cfg is not None:
        # build-time constants for the 'wire'/'mixing' collectors — resolved
        # here (the trainer's gossip/comm wiring must exist) and baked into
        # the step graph as literals at first trace (compilation is lazy)
        gap = topo.spectral_gap()
        ws = wire_stats(trainer, state.params)
        telemetry_cfg.static.update({
            "spectral_gap": gap,
            # consensus DISTANCE (sqrt) contracts by sqrt(lambda_2) per mix
            "rho": float(np.sqrt(max(1.0 - gap, 0.0))),
            "wire_bits_per_node_per_step": ws["bits_per_node_per_step"],
        })
        het = task.meta.get("heterogeneity")
        if het:
            telemetry_cfg.static["data_mean_tv"] = float(het["mean_tv"])
        if "messages_per_step" in ws:
            telemetry_cfg.static["wire_messages_per_step"] = (
                ws["messages_per_step"])
        # analytic optimizer HBM traffic for the path actually taken
        # (fused='auto' resolves against the live backend) — the 'kernel'
        # collector surfaces it as tm.kernel_bytes_moved (DESIGN.md §14)
        from repro.core import transforms as T
        opt = trainer.optimizer
        n_elems = sum(int(np.prod(l.shape))
                      for l in jax.tree.leaves(state.params))
        telemetry_cfg.static["kernel_bytes_moved"] = float(
            T.chain_bytes_moved(opt._stages(), n_elems, fused=opt.fused))
    return Experiment(spec=spec, trainer=trainer, state=state, task=task,
                      bundle=bundle)


def _evaluate(trainer, state, eval_fn, batches) -> dict:
    """Paper protocol with per-node spread: each node's model on the full
    eval set; report mean and std over nodes per metric."""
    totals: dict[str, np.ndarray] = {}
    for batch in batches:
        batch = jax.tree.map(jnp.asarray, batch)
        res = jax.vmap(lambda p, ms: eval_fn(p, ms, batch))(
            state.params, state.model_state)
        for k, v in res.items():
            totals[k] = totals.get(k, 0) + np.asarray(v)
    if not totals:
        return {}
    count = totals.pop("count")
    out = {}
    for k, v in totals.items():
        per_node = v / count
        out[k] = float(np.mean(per_node))
        out[k + "_std_over_nodes"] = float(np.std(per_node))
    return out


def wire_stats(trainer: DecentralizedTrainer, params) -> dict:
    """THE wire model: bits each node puts on the wire per step (DESIGN.md
    §4 convention: one whole-tree transmission per mix site).  Shape-only —
    safe on donated/deleted param buffers.  Shared by ``Result.wire``
    accounting and the telemetry ``wire`` collector's build-time statics.

    Dense baseline: full 32-bit tree per site.  Compressed comm replaces
    that with the compressor's innovation bits — EXCEPT that under a
    physically executing ppermute schedule (resolved gossip kind ``ring`` /
    ``sparse``) the CHOCO/EF anchor gossip really ships the FULL anchor
    tree, one message per schedule edge per site (``comm/choco.mix_site``
    routes the anchors through ``mix_impl``), so those bytes are charged on
    top.  Uncompressed runs are unaffected (the full tree per site IS the
    traffic, whatever collective carries it).  Pinned by the regression in
    tests/test_telemetry.py: sparse compressed gossip must never account
    below its anchor traffic."""
    per_node = sum(l.size / l.shape[0] for l in jax.tree.leaves(params))
    try:
        sites = count_mix_sites(trainer.optimizer, params,
                                trainer.topology.w(0))
    except Exception:   # exotic custom chains: fall back to one site
        sites = 1
    dense_bits = 32.0 * per_node * sites
    out = {
        "mix_sites": int(sites),
        "params_per_node": int(per_node),
        "dense_bits_per_node_per_step": dense_bits,
    }
    resolved = trainer._resolved
    messages = None
    if resolved.kind == "sparse":
        messages = resolved.schedule.messages_per_step()
        out["messages_per_step"] = messages
    if trainer.comm is not None:
        comp_bits = trainer.comm.wire_bits_per_site(params) * sites
        anchor_bits = 0.0
        if messages is not None:
            # full-width anchor per edge message, averaged over the n senders
            anchor_bits = 32.0 * per_node * sites * (
                messages / trainer.topology.n)
        out["compressed_bits_per_node_per_step"] = comp_bits
        out["anchor_bits_per_node_per_step"] = anchor_bits
        out["bits_per_node_per_step"] = comp_bits + anchor_bits
    else:
        out["bits_per_node_per_step"] = dense_bits
    out["ratio_vs_dense"] = dense_bits / max(
        out["bits_per_node_per_step"], 1e-9)
    return out


def _wire_accounting(ex: Experiment, history: list) -> dict:
    """``Result.wire``: the :func:`wire_stats` model for this experiment."""
    return wire_stats(ex.trainer, ex.state.params)


def _make_recorder(ex: Experiment, telemetry_path: str = ""):
    """Recorder + sink for a telemetry-enabled experiment (None otherwise).
    ``telemetry_path`` overrides ``spec.telemetry.path``; file sinks with
    neither default to ``metrics.<ext>`` in the cwd."""
    if ex.trainer.telemetry is None:
        return None
    from repro.telemetry import TelemetryRecorder, make_sink
    tl = ex.spec.telemetry
    path = telemetry_path or tl.path
    if tl.sink != "memory" and not path:
        path = "metrics.jsonl" if tl.sink == "jsonl" else "metrics.csv"
    return TelemetryRecorder(ex.trainer.telemetry, make_sink(tl.sink, path))


def run(spec: ExperimentSpec, *, mesh: Any = None, log_fn=print,
        with_state: bool = False, checkpoint_path: str = "",
        resume: str = "", telemetry_path: str = ""):
    """Build + train + evaluate one spec.  Returns a :class:`Result`
    (history + final metrics + wire-bytes accounting, JSON-dumpable); with
    ``with_state=True`` returns ``(result, final_state)`` so launchers can
    checkpoint.

    ``checkpoint_path`` + ``spec.loop.checkpoint_every`` save the FULL
    TrainState (params, opt/comm state, step counter) and the loop rng every
    that many steps (and once at the end); ``resume=<path>`` restores such a
    checkpoint, fast-forwards the deterministic batch stream to the saved
    step, and runs the remaining ``loop.steps - step`` steps — the combined
    trajectory is identical to an uninterrupted run (pinned in
    tests/test_runtime.py).  History ``step`` indices are absolute.

    With ``spec.telemetry.enabled``, the jitted step additionally runs the
    selected in-graph collectors and one row per on-cadence step streams to
    the telemetry sink (``metrics.jsonl`` by default, ``telemetry_path``
    overrides the location); ``Result.telemetry`` carries the recorder
    summary (row count, sink path, host step-time percentiles).  Render the
    stream with ``python -m repro.telemetry.report`` (DESIGN.md §10)."""
    from repro.train.checkpoint import restore_train_state, save_train_state

    ex = build(spec, mesh=mesh)
    recorder = _make_recorder(ex, telemetry_path)
    lp = spec.loop
    rng = (jax.random.PRNGKey(0) if lp.rng_seed is None
           else jax.random.PRNGKey(lp.rng_seed))

    state, start = ex.state, 0
    batch_iter = ex.task.make_iter()
    if resume:
        state, rng, meta = restore_train_state(resume, ex.state,
                                               like_rng=rng)
        state = ex.trainer._runtime.finalize_state(state)
        start = int(meta["step"])
        if start > lp.steps:
            raise ValueError(
                f"resume checkpoint is at step {start} but loop.steps="
                f"{lp.steps}; raise loop.steps to continue")
        for _ in range(start):       # replay the deterministic batch stream
            next(batch_iter)
        log_fn(f"resumed from {resume} at step {start}")

    ckpt_kw = {}
    last_save = [start, rng]   # (absolute step, rng carry) of the last save
    if checkpoint_path and lp.checkpoint_every:
        def _periodic_save(done, st, r):
            save_train_state(checkpoint_path, st, rng=r, step=done)
            last_save[:] = [done, r]

        ckpt_kw = {"checkpoint_every": lp.checkpoint_every,
                   "checkpoint_fn": _periodic_save}

    t0 = time.time()
    if lp.chunk > 1:
        state, history = run_training_scanned(
            ex.trainer, state, batch_iter, lp.steps - start,
            chunk=lp.chunk, rng=rng, log_every=lp.log_every, log_fn=log_fn,
            step_offset=start, telemetry=recorder, **ckpt_kw)
    else:
        state, history = run_training(
            ex.trainer, state, batch_iter, lp.steps - start, rng=rng,
            log_every=lp.log_every, log_fn=log_fn, step_offset=start,
            telemetry=recorder, **ckpt_kw)
    jax.block_until_ready(state.params)
    wall = time.time() - t0
    if checkpoint_path:
        # final save: the loops don't return their rng carry, but the stream
        # is deterministic (one split per executed step), so advance it from
        # the last periodic save in ONE scanned dispatch; the state's own
        # counter is the absolute step
        abs_done = int(np.asarray(state.t))
        base_step, r_final = last_save
        if abs_done > base_step:
            r_final = jax.lax.scan(
                lambda c, _: (jax.random.split(c)[0], None), r_final, None,
                length=abs_done - base_step)[0]
        save_train_state(checkpoint_path, state, rng=r_final, step=abs_done)

    final = dict(history[-1]) if history else {}
    final.pop("step", None)
    if spec.eval.enabled and ex.bundle.eval_fn is not None \
            and ex.task.eval_batches:
        final.update(_evaluate(ex.trainer, state, ex.bundle.eval_fn,
                               ex.task.eval_batches))

    steps_run = (history[-1]["step"] + 1) if history else 0
    wire = _wire_accounting(ex, history)
    wire["total_mbytes_per_node"] = (
        wire["bits_per_node_per_step"] * steps_run / 8e6)
    telemetry_summary = recorder.close() if recorder is not None else None
    result = Result(spec=spec.to_dict(), history=history, final=final,
                    steps_run=steps_run, wall_time_s=wall, wire=wire,
                    telemetry=telemetry_summary,
                    heterogeneity=ex.task.meta.get("heterogeneity"))
    if with_state:
        return result, state
    return result

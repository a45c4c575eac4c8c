"""Decentralized training engine.

The step math lives in ONE place — ``repro.runtime.base.Runtime`` — and the
trainer delegates execution to a pluggable backend (DESIGN.md §9), selected
by the ``runtime`` field:

  * ``'vmap'``    — node-stacked layout: params/opt-state leaves are
                    ``[n_nodes, ...]`` with the node axis vmapped.  The
                    degenerate single-device path (CPU tests, benchmarks,
                    examples); with a mesh, gossip still runs the compiled
                    sparse-ppermute schedule per mix site.
  * ``'sharded'`` — the COMPLETE step (per-node grad, transform chain,
                    CHOCO/EF comm, gossip schedule) inside one ``shard_map``
                    over the mesh node axis: each device holds only its own
                    node's state (O(1) per-device memory in n), one dispatch
                    per step/chunk, buffers donated.
  * ``'hybrid'``  — node-batched blocks: n nodes on d devices, b = n/d per
                    device, same single-shard_map structure with the
                    block-compiled gossip schedule (the thousand-node
                    scenario backend, DESIGN.md §11).
  * ``'auto'``    — sharded when a mesh carries the node axis at size n,
                    hybrid when its size properly divides n, else vmap.

Trajectories are backend-identical (pinned in tests/test_runtime.py).

The step:   grads = per-node grad(loss)    (vmapped or device-local)
            params, opt_state = opt.step(params, grads, w=W_t)

The optimizer step is a pure transform chain (core/transforms.py), so whole
training chunks fuse under ``lax.scan``: ``run_training_scanned`` dispatches
k steps at a time (one device dispatch per chunk instead of per step),
producing step-identical metrics to ``run_training``.  Compilation is lazy
and backend-owned (the runtime jits with buffer donation on first use —
never in ``__post_init__``, so mesh/runtime choices can shape the options).

Model state (e.g. BN running stats) stays per-node and is NEVER gossiped —
the paper's local-statistics BN protocol.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.choco import CompressedGossip
from repro.core import gossip
from repro.core.optim import DecentralizedOptimizer
from repro.core.topology import Topology
from repro.telemetry import trace
from repro.telemetry.trace import host_span, step_span

PyTree = Any
_END = object()   # batch_iter ran dry
_consensus_distance = jax.jit(gossip.consensus_distance)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: PyTree          # [n, ...]
    opt_state: PyTree
    model_state: PyTree     # [n, ...] (BN stats etc.), not gossiped
    t: jnp.ndarray          # step counter
    comm_state: PyTree = None  # CHOCO replica/residual sites (DESIGN.md §4)
    mix_buf: PyTree = None  # overlap='delayed_1' in-flight exchange buffers:
                            # one tree per topology mix site (DESIGN.md §12)


def lr_schedule(base_lr: float, *, total_steps: int, warmup: int = 0,
                decay_at: tuple[float, ...] = (), decay: float = 0.1,
                warmup_from: float = 0.1):
    """Paper recipe: linear warmup from `warmup_from` then stage-wise decay
    at the given fractions of total steps."""
    decay_steps = tuple(int(f * total_steps) for f in decay_at)

    def fn(t):
        t = jnp.asarray(t, jnp.float32)
        lr = jnp.asarray(base_lr, jnp.float32)
        if warmup:
            frac = jnp.clip(t / warmup, 0.0, 1.0)
            start = min(warmup_from, base_lr)
            lr = start + (base_lr - start) * frac
        for ds in decay_steps:
            lr = jnp.where(t >= ds, lr * decay, lr)
        return lr

    return fn


@dataclasses.dataclass
class DecentralizedTrainer:
    """loss_fn(params_i, model_state_i, batch_i, rng_i) ->
    (loss, (new_model_state, metrics_dict)).

    When ``mesh`` is given (node axis sharded over ``node_axis``), the
    topology is compiled once into a sparse ppermute schedule
    (``gossip.compile_gossip_schedule``) and every mix — including the inner
    anchor gossip of compressed CHOCO/EF comm — runs those compiled rounds
    instead of the dense all-gather contraction (DESIGN.md §7).  With
    ``runtime='auto'`` a mesh also selects the SHARDED execution backend
    (DESIGN.md §9): the whole step runs inside one shard_map and the
    schedule executes on the local shards; ``runtime='vmap'`` keeps the
    node-stacked layout with a shard_map region per mix site.  The
    trajectory is identical either way.
    """

    loss_fn: Callable
    optimizer: DecentralizedOptimizer
    topology: Topology
    lr_fn: Callable[[Any], Any] = None  # defaults to optimizer.lr constant
    comm: Optional[CompressedGossip] = None  # compressed gossip (DESIGN.md §4)
    mesh: Any = None              # jax Mesh: auto-select the sparse schedule
    node_axis: str = "data"       # mesh axis carrying the node index
    gossip_schedule: str = "auto"  # gossip.GOSSIP_SCHEDULES
    runtime: str = "auto"          # repro.runtime.RUNTIMES (DESIGN.md §9)
    overlap: str = "none"          # repro.runtime.OVERLAPS: 'delayed_1'
                                   # pipelines one-step-stale gossip under
                                   # the next round's compute (DESIGN.md §12)
    telemetry: Any = None          # resolved telemetry.TelemetryConfig; when
                                   # set, the jitted step emits 'tm.'-prefixed
                                   # collector scalars (DESIGN.md §10).  None
                                   # (default) leaves the graph untouched.
    scenario: Any = None           # scenario.ScenarioContext: per-round
                                   # client sampling / churn / stragglers
                                   # (DESIGN.md §11).  None = full
                                   # participation, the exact default graph.
    loss_nodes_fn: Optional[Callable] = None  # loss_fn over a block of
                                   # nodes at once: (params[b], mstate[b],
                                   # batch[b], rngs[b]) -> (loss[b],
                                   # (mstate[b], metrics)); the runtimes
                                   # take it for every local block
                                   # (DESIGN.md §15)

    def __post_init__(self):
        if getattr(self.optimizer, "fused", "off") not in ("pallas", "off",
                                                           "auto"):
            raise ValueError(
                f"optimizer.fused must be 'pallas', 'off' or 'auto', got "
                f"{self.optimizer.fused!r}")
        if self.lr_fn is None:
            lr = self.optimizer.lr
            self.lr_fn = lambda t: jnp.asarray(lr, jnp.float32)
        self._mixing = jnp.asarray(self.topology.mixing, jnp.float32)
        from repro.runtime import make_runtime, resolve_runtime
        kind = resolve_runtime(self.runtime, mesh=self.mesh,
                               node_axis=self.node_axis, n=self.topology.n)
        if kind == "hybrid":
            # the node-granular resolver would reject the mesh (axis size
            # != n by construction); the hybrid backend block-compiles its
            # own schedule.  _resolved still carries the compiled
            # node-granular schedule so wire accounting sees the real
            # per-edge message counts.
            if self.gossip_schedule not in gossip.GOSSIP_SCHEDULES:
                raise ValueError(
                    f"unknown gossip schedule {self.gossip_schedule!r}; "
                    f"valid: {' | '.join(gossip.GOSSIP_SCHEDULES)}")
            if self.gossip_schedule == "dense" or self.topology.n == 1:
                self._resolved = gossip.ResolvedGossip("dense")
            else:
                self._resolved = gossip.ResolvedGossip(
                    "sparse", gossip.compile_gossip_schedule(self.topology),
                    self.mesh, self.node_axis)
        else:
            # the node-granular rules of the vmap and sharded runtimes;
            # raises eagerly on mismatches
            self._resolved = gossip.resolve_gossip(
                self.topology, schedule=self.gossip_schedule, mesh=self.mesh,
                node_axis=self.node_axis if self.mesh is not None else None)
        self._validate_scenario(kind)
        self._validate_overlap()
        self._record = True       # see recording()
        self._comm_gamma = None   # resolved on first sight of params
        self._comm_bits = None    # wire bits per site per node per step
        # the execution backend owns compilation (LAZY, with buffer
        # donation) — jitting here would bake options in before the
        # runtime/mesh could influence them
        self._runtime = make_runtime(self)

    def _validate_scenario(self, kind: str) -> None:
        """Eager checks for the participation/fault model (DESIGN.md §11) —
        every unsupported combination raises here with an actionable
        message, not from inside a jitted step."""
        sc = self.scenario
        if sc is None or getattr(sc, "trivial", False):
            return
        if sc.n != self.topology.n:
            raise ValueError(
                f"scenario is configured for n={sc.n} nodes, topology has "
                f"n={self.topology.n}")
        if self.comm is not None:
            raise ValueError(
                "scenario fault injection with compressed comm is not "
                "supported: CHOCO/EF replica states assume every node "
                "completes every round; run uncompressed (comm=None)")
        if kind == "sharded" or (kind == "vmap"
                                 and self._resolved.kind != "dense"):
            raise ValueError(
                "scenario fault injection runs on runtime='hybrid' (block-"
                "sparse masked gossip) or runtime='vmap' with dense gossip;"
                f" got runtime={kind!r}, gossip={self._resolved.kind!r}")
        mix = np.asarray(self.topology.mixing)
        if not np.allclose(mix, np.swapaxes(mix, 1, 2), atol=1e-8):
            raise ValueError(
                "scenario fault injection requires symmetric mixing "
                "(Metropolis weights) so the alive-subgraph renormalization "
                f"stays doubly stochastic; topology {self.topology.name!r} "
                "is asymmetric (e.g. one-peer exponential)")

    def _validate_overlap(self) -> None:
        """Eager checks for the delayed-gossip pipeline (DESIGN.md §12)."""
        from repro.runtime import OVERLAPS
        if self.overlap not in OVERLAPS:
            raise ValueError(
                f"overlap={self.overlap!r} is not one of {OVERLAPS}")
        if self.overlap == "none":
            return
        if self.comm is not None:
            raise ValueError(
                "overlap='delayed_1' with compressed comm is not supported: "
                "the CHOCO replica exchange already defines its own buffer "
                "protocol; run uncompressed (comm=None)")
        if self.scenario is not None and not getattr(
                self.scenario, "trivial", False):
            raise ValueError(
                "overlap='delayed_1' with scenario fault injection is not "
                "supported: the stale exchange buffers of dropped nodes "
                "would re-inject discarded state; run scenario=None")

    def _comm_setup(self, params):
        if self.comm is not None and self._comm_gamma is None:
            self._comm_gamma = self.comm.resolved_gamma(params)
            self._comm_bits = self.comm.wire_bits_per_site(params)
            self._dense_bits = sum(
                32.0 * l.size / l.shape[0] for l in jax.tree.leaves(params))

    # -- init ---------------------------------------------------------------
    def init(self, key, init_fn) -> TrainState:
        """init_fn(key) -> (params, model_state); every node starts from the
        SAME x^0 (the paper's setup).  The runtime builds the node-stacked
        state where it lives (``Runtime.init_state``: the sharded backend
        builds each device's own nodes there, so no device ever holds the
        whole node stack)."""
        params, mstate = init_fn(key)
        n = self.topology.n

        def build(params, mstate):
            stack = lambda tree: jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy()
                if hasattr(x, "shape") else x, tree)
            params_n = stack(params)
            comm_state = None
            if self.comm is not None:
                comm_state = self.comm.init_state(
                    self.optimizer, params_n, self._mixing[0])
            mix_buf = None
            if self.overlap != "none":
                # t=0 exchange buffers: the trees each topology mix site
                # would have contracted on the first step.  All nodes share
                # x^0, so the first delayed correction is exactly zero.
                from repro.runtime.overlap import capture_topology_mix_sites
                mix_buf = capture_topology_mix_sites(
                    self.optimizer, params_n, self._mixing[0])
            return TrainState(params=params_n,
                              opt_state=self.optimizer.init(params_n),
                              model_state=stack(mstate),
                              t=jnp.zeros((), jnp.int32),
                              comm_state=comm_state,
                              mix_buf=mix_buf)

        return self._runtime.init_state(build, params, mstate)

    # -- one jitted decentralized step ---------------------------------------
    @contextlib.contextmanager
    def recording(self, record):
        """Whether the steps dispatched within the block have their
        metrics read: a bool for :meth:`step`, a chunk's k bools for
        :meth:`step_chunk`.  Where it is false the same compiled step skips
        the ``consensus`` metric (NaN), whose all-reduce of every parameter
        is the costly one across chips (DESIGN.md §9).  Outside any block
        every step records.  The loops state it around each dispatch, so
        the ``step`` call, and whatever stands in for it, stays as it
        was."""
        prev, self._record = self._record, record
        try:
            yield
        finally:
            self._record = prev

    def step(self, state: TrainState, batch: PyTree, rng,
             collect: bool = False):
        """One decentralized step on the selected execution backend.
        DONATES ``state``: the input buffers back the output state (copy
        first to keep a state across repeated runs).  ``collect=True``
        selects the telemetry-collecting trace (DESIGN.md §10) — a
        separately compiled variant of the same step, so ``False`` (the
        default) stays the exact pre-telemetry graph.  Its metrics hold
        ``consensus`` unless a :meth:`recording` block says otherwise."""
        self._comm_setup(state.params)
        return self._runtime.step(state, batch, rng, record=self._record,
                                  collect=collect)

    # -- k fused steps under one dispatch (lax.scan over the chunk) -----------
    def step_chunk(self, state: TrainState, batches: PyTree, rng,
                   collect: bool = False):
        """Run ``k`` decentralized steps in ONE jitted dispatch (donating
        ``state`` like :meth:`step`).

        ``batches`` leaves are stacked ``[k, n, ...]``; the per-step rng
        stream is split inside the scan exactly as ``run_training`` splits it
        outside, so the trajectory is step-identical to k calls of ``step``.
        Returns the final state, the advanced rng, and metrics stacked [k].
        ``collect=True`` selects the telemetry-collecting chunk trace (every
        step of the chunk collects; the recorder keeps on-cadence rows).
        Which steps compute ``consensus`` follows :meth:`recording`.
        """
        self._comm_setup(state.params)
        record = self._record
        if isinstance(record, bool):
            record = (record,) * jax.tree.leaves(batches)[0].shape[0]
        return self._runtime.step_chunk(state, batches, rng, record,
                                        collect=collect)

    # -- host-side batch placement / probes ------------------------------------
    def put_batch(self, batch: PyTree, lead: int = 0):
        """Place one host batch where the execution backend wants it:
        device arrays for vmap, node-sharded (and, multi-process, globally
        assembled from each host's local rows — per-host data feeding)
        arrays for sharded/hybrid.  ``lead`` is the node axis position
        (1 for a chunked ``[k, n, ...]`` stack)."""
        return self._runtime.put_batch(batch, lead=lead)

    def probe_metrics(self, state: TrainState, batch: PyTree, rng,
                      chunked: bool = False) -> dict:
        """Host-timed overlap telemetry (``tm.gossip_wait_ms``) for this
        step; {} unless ``overlap`` is active.  Runs non-donating probe
        traces, so call BEFORE the real (donating) step."""
        return self._runtime.probe_metrics(state, batch, rng,
                                           chunked=chunked)

    # -- evaluation -----------------------------------------------------------
    def evaluate(self, state: TrainState, eval_fn, batches) -> dict:
        """Paper protocol: evaluate EACH node's local model on the FULL eval
        set, then average the per-node metrics.  eval_fn(params_i, mstate_i,
        batch) -> dict of sums + 'count'."""
        return self._runtime.evaluate(state, eval_fn, batches)


def _recorded(i, steps, log_every) -> bool:
    """Whether the loops record step ``i`` of ``steps``: on ``log_every``
    boundaries and at the final step.  The loops tell the step the same, so
    only these steps compute the ``consensus`` metric; each is counted as
    ``tm/metrics/consensus``."""
    return bool(log_every and i % log_every == 0) or i == steps - 1


def _record_step(history, i, steps, log_every, log_fn, get_metrics):
    """THE logging cadence, shared by both loops (the scanned loop's
    step-identical-history contract depends on it): print+append on log_every
    boundaries and the final step, append silently on the final step
    otherwise (:func:`_recorded`).  ``get_metrics() -> {name: float}`` is
    called lazily so the scanned loop only pulls a chunk's metrics
    off-device when some step in it is actually recorded.  That pull is the
    loop's only sync with the device, marked ``tm/host/fetch``."""
    if not _recorded(i, steps, log_every):
        return
    with host_span("tm/host/fetch"):
        m = get_metrics()
    history.append({"step": i, **m})
    if log_every:
        log_fn(f"step {i:5d}  " + "  ".join(
            f"{k}={v:.4f}" for k, v in m.items()))


def _nbytes(tree) -> int:
    return sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree))


def run_training(trainer: DecentralizedTrainer, state: TrainState,
                 batch_iter, steps: int, *, rng=None, log_every: int = 0,
                 log_fn=print, checkpoint_every: int = 0,
                 checkpoint_fn=None, step_offset: int = 0,
                 telemetry=None) -> tuple[TrainState, list[dict]]:
    """Per-step python loop.  ``checkpoint_fn(done, state, rng)`` is called
    whenever ``done`` (ABSOLUTE completed steps, offset included) hits a
    ``checkpoint_every`` multiple; the passed ``rng`` is the loop carry
    AFTER the step's split, so a run restarted from ``(state, rng)``
    continues the exact same stream (the save->resume parity pinned in
    tests/test_runtime.py).  ``step_offset`` makes a resumed run log/record
    absolute step indices with the uninterrupted run's cadence.  Each step
    is told whether it is recorded (:func:`_recorded`, through
    ``trainer.recording``); only recorded steps compute the ``consensus``
    metric.

    ``telemetry`` is an optional duck-typed recorder (see
    ``repro.telemetry.TelemetryRecorder``): on-cadence steps
    (``telemetry.wants(i)``) run the telemetry-collecting step trace, and
    each step's metrics pass through ``telemetry.consume(step, metrics)``,
    which strips the ``tm.``-prefixed collector outputs into the recorder's
    sink and returns the user-facing remainder — ``history`` keys are
    identical with or without it, and off-cadence steps run the exact
    telemetry-free graph.

    Each iteration is a ``train`` step span (``telemetry.trace.step_span``)
    holding host spans for the batch pull (``tm/host/next_batch``), its
    placement (``tm/host/put_batch``) and the dispatch of the step
    (``tm/host/dispatch``, twice: the rng split before the placement, then
    the probe and the step call, which returns once the step is queued).
    The split stays ahead of the placement: once the host is a full queue
    ahead of the device it waits at the split, and a batch placed before
    that wait holds device memory (with the placement first, the ResNet
    benchmark cell peaked four batches higher on a TPU v5e, +5.8 %)."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    history = []
    total = step_offset + steps
    it = iter(batch_iter)
    for i in range(step_offset, total):
        with step_span(i):
            with host_span("tm/host/next_batch"):
                batch = next(it, _END)
            if batch is _END:
                break
            with host_span("tm/host/dispatch"):
                rng, sub = jax.random.split(rng)
            with host_span("tm/host/put_batch", bytes=_nbytes(batch)):
                batch = trainer.put_batch(batch)
            with host_span("tm/host/dispatch"):
                collect = telemetry is not None and telemetry.wants(i)
                probe = (trainer.probe_metrics(state, batch, sub)
                         if collect else {})
                record = _recorded(i, total, log_every)
                if record:
                    trace.count("tm/metrics/consensus")
                with trainer.recording(record):
                    state, metrics = trainer.step(state, batch, sub,
                                                  collect=collect)
            if telemetry is not None:
                metrics = telemetry.consume(i, {**metrics, **probe})
            _record_step(history, i, total, log_every, log_fn,
                         lambda: {k: float(v) for k, v in metrics.items()})
            if checkpoint_fn and checkpoint_every \
                    and (i + 1) % checkpoint_every == 0:
                checkpoint_fn(i + 1, state, rng)
    return state, history


def run_training_scanned(trainer: DecentralizedTrainer, state: TrainState,
                         batch_iter, steps: int, *, chunk: int = 16,
                         rng=None, log_every: int = 0, log_fn=print,
                         checkpoint_every: int = 0, checkpoint_fn=None,
                         step_offset: int = 0,
                         telemetry=None) -> tuple[TrainState, list[dict]]:
    """``run_training`` with ``chunk`` steps fused under one ``lax.scan``
    dispatch — same rng stream, same math, step-identical metrics, but the
    per-step Python/jit dispatch overhead is paid once per chunk (the `loop`
    benchmark table quantifies the speedup on the CPU/bench path).

    A shorter tail (``steps % chunk``) runs as its own scan trace; history
    entries follow the exact ``run_training`` logging cadence.

    If ``batch_iter`` runs dry before ``steps`` are done, the loop stops,
    warns through ``log_fn``, and the history honestly covers only the steps
    that actually ran (the last executed step is always recorded).

    ``checkpoint_fn(done, state, rng)`` fires at the first chunk boundary
    at/after each ``checkpoint_every`` multiple of the ABSOLUTE step count
    (the scan carry is only available between dispatches) — a resume from
    any such save replays the identical stream, whatever the chunking.
    ``step_offset`` shifts logging/recording to absolute indices like
    ``run_training``.

    ``telemetry`` (optional duck-typed recorder): a chunk containing an
    on-cadence step (``telemetry.wants_chunk``) runs the telemetry-collecting
    chunk trace — every step of THAT chunk collects, and
    ``telemetry.consume_chunk(start_step, metrics)`` keeps the on-cadence
    rows, strips the ``tm.``-prefixed outputs, and returns the user-facing
    remainder (same history contract as ``run_training``).  Chunks with no
    on-cadence step run the exact telemetry-free graph, so a cadence that is
    a multiple of ``chunk`` amortizes best (see DESIGN.md §10).

    Each chunk is one ``train`` step span whose ``step_num`` is the chunk's
    first step, with the host spans of ``run_training``: ``put_batch``
    covers the host stacking too, ``dispatch`` the probe and the chunk call.
    """
    rng = jax.random.PRNGKey(0) if rng is None else rng
    it = iter(batch_iter)
    history = []
    done = 0
    exhausted = False
    last_metrics = None   # () -> metrics of the last executed step
    while done < steps and not exhausted:
        with step_span(step_offset + done):
            k = min(chunk, steps - done)
            batches = []
            with host_span("tm/host/next_batch"):
                for _ in range(k):
                    batch = next(it, _END)
                    if batch is _END:
                        exhausted = True
                        break
                    batches.append(batch)
            if not batches:
                break
            k = len(batches)
            # a short final chunk moves the "final step" recording boundary
            # so the last step that actually ran lands in the history
            total = done + k if exhausted else steps
            # stack on host, ship once: one transfer per chunk instead of
            # one device commit per step per leaf
            with host_span("tm/host/put_batch",
                           bytes=k * _nbytes(batches[0])):
                stacked = trainer.put_batch(
                    jax.tree.map(lambda *xs: np.stack(xs), *batches), lead=1)
            with host_span("tm/host/dispatch"):
                collect = (telemetry is not None
                           and telemetry.wants_chunk(step_offset + done, k))
                probe = (trainer.probe_metrics(state, stacked, rng,
                                               chunked=True)
                         if collect else {})
                record = tuple(
                    _recorded(step_offset + done + j, step_offset + total,
                              log_every) for j in range(k))
                trace.count("tm/metrics/consensus", sum(record))
                with trainer.recording(record):
                    state, rng, metrics = trainer.step_chunk(
                        state, stacked, rng, collect=collect)
            if telemetry is not None:
                # host probe scalars broadcast [k] so the chunk consumer's
                # per-step indexing sees them on every row
                metrics = telemetry.consume_chunk(step_offset + done, {
                    **metrics,
                    **{mk: np.full((k,), mv, np.float32)
                       for mk, mv in probe.items()}})

            host: dict = {}  # chunk metrics, moved once and only if needed

            def chunk_metrics(j, metrics=metrics, host=host):
                if not host:
                    host.update({mk: np.asarray(mv)
                                 for mk, mv in metrics.items()})
                return {mk: float(mv[j]) for mk, mv in host.items()}

            for j in range(k):
                _record_step(history, step_offset + done + j,
                             step_offset + total, log_every, log_fn,
                             lambda j=j: chunk_metrics(j))
            last_metrics = lambda k=k, cm=chunk_metrics: cm(k - 1)
            last_recorded = record[-1]
            abs_done = step_offset + done
            if checkpoint_fn and checkpoint_every and (
                    (abs_done + k) // checkpoint_every
                    > abs_done // checkpoint_every):
                checkpoint_fn(abs_done + k, state, rng)
            done += k
    if done < steps:
        log_fn(f"warning: batch_iter exhausted after {done} steps "
               f"({steps} requested); history covers the {done} steps run")
        # exhaustion discovered at a chunk boundary: the previous chunk was
        # recorded against total=steps, so its last step may be missing
        if last_metrics is not None and (
                not history
                or history[-1]["step"] != step_offset + done - 1):
            row = last_metrics()
            if not last_recorded:
                # that step skipped the metric; the state is its result
                row["consensus"] = float(_consensus_distance(state.params))
            history.append({"step": step_offset + done - 1, **row})
    return state, history

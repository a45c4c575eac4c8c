"""Execution-backend base: the ONE decentralized step, written once.

A :class:`Runtime` owns how the node axis of the paper's n independent
workers is realized on hardware (DESIGN.md §9):

  * :class:`~repro.runtime.vmap.VmapRuntime` — the node index is the stacked
    leading axis of every leaf; per-node work is ``jax.vmap``; node
    reductions are ordinary ``axis=0`` ops.  The degenerate single-device
    path (CPU tests, benchmarks, examples).
  * :class:`~repro.runtime.sharded.ShardedRuntime` — the node index is a
    mesh axis; the COMPLETE step (per-node grad, the transform-stage chain,
    CHOCO/EF comm updates, the compiled gossip schedule) runs inside a
    single ``shard_map``, so each device holds only its own node's
    params/opt/comm state and a step (or a whole scanned chunk) is exactly
    one dispatch.

Both backends run the SAME step math — the methods below — parameterized by
a handful of node-axis hooks (``_node_rngs``, ``_node_mean_scalar``,
``_node_sum_scalar``, ``_mix_impl``).  Everything the hooks do not touch is
shared verbatim, which is what makes the cross-backend trajectory-parity
pins in tests/test_runtime.py hold.

The step is an explicit three-stage PIPELINE (DESIGN.md §12):

    launch_mix  — issue the gossip of the one-step-stale exchange buffers
                  (``overlap='delayed_1'`` only; a no-op synchronously);
    compute     — per-node loss/grad;
    finish_mix  — the transform-stage chain: local update + mix.  Under
                  overlap the topology mix sites consume the in-flight
                  trees from launch_mix instead of gossiping fresh values.

Synchronously the stages compose to the exact pre-refactor graph (the
trajectory pins hold bit-for-bit).  With ``overlap='delayed_1'`` the
launch-stage collectives have no data dependency on the round's gradients,
so the compiled ppermute schedule overlaps the backward pass — the
``repro.runtime.overlap`` module holds the delayed-mix math and buffer
capture.

Compilation is LAZY and owned by the runtime: the trainer never jits in
``__post_init__`` anymore, so backends control jit options — in particular
``donate_argnums=0``: the incoming :class:`TrainState` buffers are donated
to the step/chunk outputs (the old state is dead the moment the new one
exists; callers that want to reuse a state across runs must copy it first,
see ``benchmarks/common.bench_loop``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gossip
from repro.telemetry.metrics import TM_PREFIX, CollectorCtx

PyTree = Any


def _hold_nodes(mask, new: PyTree, old: PyTree) -> PyTree:
    """Per-node old-vs-new select for the scenario hold semantics: leaves
    whose leading axis matches the local mask length are node-stacked — pick
    ``new`` where ``mask`` is 1, keep ``old`` where 0.  Non-node leaves
    (replicated scalars) take ``new`` unconditionally."""
    mb = mask.astype(bool)

    def sel(a, b):
        shape = getattr(a, "shape", ())
        if len(shape) >= 1 and shape[0] == mb.shape[0]:
            return jnp.where(mb.reshape((shape[0],) + (1,) *
                                        (len(shape) - 1)), a, b)
        return a

    return jax.tree.map(sel, new, old)


@dataclasses.dataclass
class Runtime:
    """Base execution backend.  ``trainer`` is the owning
    :class:`~repro.train.trainer.DecentralizedTrainer`; the runtime reads
    its loss/optimizer/topology/comm/gossip wiring and owns compilation."""

    trainer: Any
    name: str = "base"
    axis_name: str | None = None    # mesh node axis (sharded backend only)
    overlap: str = "none"           # 'none' | 'delayed_1' (DESIGN.md §12)

    def __post_init__(self):
        # one compiled fn per (step|chunk) x (plain|telemetry) — the
        # telemetry variants only exist once a loop asks for them, so the
        # default path compiles exactly what it always did
        self._step_fns = {}
        self._chunk_fns = {}
        # the step's ``record`` input, kept on the device by value (a bool,
        # or a chunk's tuple of bools) so no step pays a host transfer for it
        self._record_args = {}
        # non-donating probe fns for tm.gossip_wait_ms (built on first use)
        self._probe_fns = None
        from repro.telemetry.trace import StepTimer
        self.gossip_timer = StepTimer()

    # -- node-axis hooks (vmap semantics by default) -------------------------
    def _node_rngs(self, rng, n: int):
        """Per-node rng keys with the SAME stream in every backend: the
        sharded override picks row ``axis_index`` of this split."""
        return jax.random.split(rng, n)

    def _node_mean_scalar(self, x):
        """Global mean of a per-node quantity -> replicated scalar."""
        return jnp.mean(x)

    def _node_sum_scalar(self, x):
        """``x`` already accumulates the local node contributions; reduce to
        the global sum (identity when all nodes are stacked locally)."""
        return x

    def _node_max_scalar(self, x):
        """Global max of a per-node quantity -> replicated scalar."""
        return jnp.max(x)

    def _local_update_mask(self, u):
        """This backend's slice of the global ``[n]`` scenario update mask,
        aligned with the local node leading axis (identity for vmap; the
        sharded/hybrid overrides slice their device's rows)."""
        return u

    def _mix_impl(self, w, t, mix_mask=None):
        """The mix hook to install for this backend (None keeps the
        optimizer's dense default).  ``mix_mask`` is the scenario's [n]
        alive mask for this round's gossip (None = no scenario): the dense
        path renormalizes every mixing matrix onto the alive subgraph."""
        r = self.trainer._resolved
        if r.kind == "dense":
            if mix_mask is None:
                return None
            return lambda w_, tree: gossip.mix_dense(
                gossip.mask_renormalize(jnp.asarray(w_), mix_mask), tree)
        if mix_mask is not None:
            raise ValueError(
                "scenario fault injection needs runtime='vmap' (dense "
                "gossip) or runtime='hybrid'")  # trainer validates earlier
        return r.mix_fn(w_ref=w, t=t)

    def _scenario_masks(self, sc, t):
        """This round's scenario masks in this backend's carve-up:
        ``(update mask for the LOCAL nodes, mix-mask object for the mix
        executors, exact (alive_frac, mix_frac) scalars)``.

        Base/vmap derives the full ``[n]`` masks; the hybrid override
        derives only its device's ``b = n/d`` block (the per-node fold_in
        keying in ``repro.scenario`` makes any id subset computable without
        materializing ``[n]``).  The fractions are exact sums of 0/1 floats
        divided by n — bit-identical whichever carve-up computed them (the
        vmap-vs-hybrid equality pin in tests/test_scenario.py)."""
        u, m = sc.masks(t)
        n = sc.n
        fracs = (jnp.sum(u) / n, jnp.sum(m) / n)
        return self._local_update_mask(u), m, fracs

    def _gossip_tree(self, tree, w, t):
        """One synchronous application of the topology gossip to an
        arbitrary tree, in this backend's layout — the launch-stage
        primitive the overlap mode issues against the stale buffers."""
        mi = self._mix_impl(w, t)
        if mi is None:      # vmap dense: the optimizer-default contraction
            return gossip.mix_dense(w, tree)
        return mi(w, tree)

    # -- the step pipeline (shared by every backend) --------------------------
    def _stage_launch_mix(self, state, w):
        """Pipeline stage 1 — issue the mix.  Synchronous mode returns None
        (the mix rides finish_mix on fresh values).  Overlap mode gossips
        the one-step-stale exchange buffers ``state.mix_buf`` NOW: these
        collectives depend only on the previous step's output, never on
        this round's gradients, so the schedule can run under compute."""
        if self.overlap == "none" or state.mix_buf is None:
            return None
        with jax.named_scope("tm/launch_mix"):
            return [self._gossip_tree(s, w, state.t) for s in state.mix_buf]

    def _stage_compute(self, state, batch, rng, n):
        """Pipeline stage 2 — per-node loss/grad on this backend's layout:
        node-stacked ``[n, ...]`` leaves (vmap) or local blocks inside
        shard_map (sharded/hybrid).

        The local block takes the trainer's node-batched loss where the
        model offers one (DESIGN.md §15): the gradient of the block's summed
        loss is every node's own gradient, since node ``i``'s loss reads
        only its own parameters.  Otherwise ``jax.vmap`` of the per-node
        gradient.  The path taken is counted at trace time."""
        from repro.telemetry import trace
        rngs = self._node_rngs(rng, n)
        nodes_fn = self.trainer.loss_nodes_fn
        with jax.named_scope("tm/grad"):
            if nodes_fn is not None:
                trace.count("tm/grad/node_batched")

                def block_loss(params):
                    loss, aux = nodes_fn(params, state.model_state, batch,
                                         rngs)
                    return jnp.sum(loss), (loss, aux)

                (_, (loss, (new_ms, metrics))), grads = jax.value_and_grad(
                    block_loss, has_aux=True)(state.params)
            else:
                trace.count("tm/grad/vmap")
                grad_fn = jax.value_and_grad(self.trainer.loss_fn,
                                             has_aux=True)
                (loss, (new_ms, metrics)), grads = jax.vmap(grad_fn)(
                    state.params, state.model_state, batch, rngs)
        return loss, new_ms, metrics, grads

    def _stage_finish_mix(self, state, grads, w, lr, rng, mix_mask, inflight,
                          n):
        """Pipeline stage 3 — the transform-stage chain (local update + mix)
        with the right mix hook installed: the backend's synchronous mix, a
        CHOCO compressed round, or — when ``inflight`` carries launch-stage
        results — the delayed consumer that applies ``tree + (W s - s)`` and
        re-arms the exchange buffers.  Returns
        ``(new_params, new_opt, new_comm, new_mix_buf)``."""
        tr = self.trainer
        opt = tr.optimizer
        new_comm = state.comm_state
        new_buf = state.mix_buf
        if inflight is not None:
            # overlap: topology sites consume the in-flight stale mixes and
            # deposit this round's trees as the next exchange (validation
            # forbids combining with compressed comm / scenarios)
            from repro.runtime.overlap import make_delayed_mix_fn
            new_buf = list(state.mix_buf)
            opt = dataclasses.replace(opt, mix_fn=make_delayed_mix_fn(
                state.mix_buf, inflight, new_buf, w_ref=w,
                fallback=self._mix_impl(w, state.t)))
        else:
            mix_impl = self._mix_impl(w, state.t, mix_mask=mix_mask)
            if mix_impl is not None:
                opt = dataclasses.replace(opt, mix_fn=mix_impl)
            if tr.comm is not None and state.comm_state is not None:
                # compressed gossip: swap the mix hook for a CHOCO round
                # against this step's replica states (one site per mix call;
                # DESIGN.md §4)
                sites_in = list(state.comm_state)
                sites_out = list(sites_in)
                comm_key = jax.random.fold_in(rng, 0x0C0)
                opt = dataclasses.replace(opt, mix_fn=tr.comm.make_mix_fn(
                    sites_in, sites_out, comm_key, tr._comm_gamma,
                    mix_impl=mix_impl))
                new_comm = sites_out

        with jax.named_scope("tm/finish_mix"), jax.named_scope("tm/opt_step"):
            new_params, new_opt = opt.step(
                state.params, grads, state.opt_state, w=w, lr=lr, t=state.t,
                axis_name=self.axis_name, n_nodes=n)
        return new_params, new_opt, new_comm, new_buf

    # -- the step math (shared by every backend) -----------------------------
    def _step_math(self, state, batch, rng, record, collect: bool = False):
        """One decentralized step on whatever layout the backend presents:
        node-stacked ``[n, ...]`` leaves (vmap) or local ``[b, ...]`` shards
        inside shard_map (sharded/hybrid).  Returns (new TrainState,
        metrics).  Orchestrates the launch_mix → compute → finish_mix
        pipeline above; the overlap mode's launch-stage collectives are
        emitted BEFORE the gradient computation in the trace.

        ``record`` is a traced bool, replicated on every device: whether the
        loop records this step's metrics.  Only then is ``consensus``
        computed (:meth:`_consensus`); otherwise it reads NaN.

        ``collect`` is a TRACE-TIME flag: True adds the telemetry collectors
        (DESIGN.md §10) to this trace; False is the exact pre-telemetry
        graph."""
        from repro.train.trainer import TrainState

        tr = self.trainer
        n = tr.topology.n
        w = tr._mixing[state.t % tr._mixing.shape[0]]
        lr = tr.lr_fn(state.t)

        # scenario masks (DESIGN.md §11): who updates / who gossips this
        # round, pure in-graph functions of (scenario seed, t, node id) —
        # identical per node across backends.  A trivial scenario compiles
        # the exact no-scenario graph.
        sc = getattr(tr, "scenario", None)
        if sc is not None and sc.trivial:
            sc = None
        u_loc = mix_mask = fracs = None
        if sc is not None:
            u_loc, mix_mask, fracs = self._scenario_masks(sc, state.t)

        inflight = self._stage_launch_mix(state, w)
        loss, new_ms, metrics, grads = self._stage_compute(
            state, batch, rng, n)
        new_params, new_opt, new_comm, new_buf = self._stage_finish_mix(
            state, grads, w, lr, rng, mix_mask, inflight, n)

        if sc is not None:
            # dropped/unsampled nodes hold state exactly: select old-vs-new
            # per node.  Their mixing rows were identity (mask_renormalize),
            # so alive nodes never read the discarded intermediate values.
            new_params = _hold_nodes(u_loc, new_params, state.params)
            new_opt = _hold_nodes(u_loc, new_opt, state.opt_state)
            new_ms = _hold_nodes(u_loc, new_ms, state.model_state)

        out_metrics = {
            "loss": self._node_mean_scalar(loss),
            "lr": lr,
            "consensus": self._consensus(record, new_params),
            "grad_norm": jnp.sqrt(self._node_sum_scalar(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree.leaves(grads))) / n),
        }
        if tr.comm is not None and state.comm_state is not None:
            n_sites = len(state.comm_state)
            out_metrics["comm_bits_per_node"] = jnp.asarray(
                tr._comm_bits * n_sites, jnp.float32)
            out_metrics["comm_ratio"] = jnp.asarray(
                tr._dense_bits / max(tr._comm_bits, 1e-9), jnp.float32)
        for k, v in metrics.items():
            out_metrics[k] = self._node_mean_scalar(v)
        if sc is not None:
            # exact 0/1 sums (ints <= n, exact in f32), so the fractions are
            # bit-identical across vmap/hybrid (determinism pin) even though
            # hybrid only ever materializes its own block of the masks
            out_metrics["alive_frac"], out_metrics["mix_frac"] = fracs
        if collect:
            out_metrics.update(self._telemetry_metrics(
                state, grads, new_params, new_opt, new_comm, lr, n,
                alive=u_loc, mix_buf_new=new_buf))
        return TrainState(new_params, new_opt, new_ms, state.t + 1,
                          new_comm, new_buf), out_metrics

    def _consensus(self, record, params):
        """``gossip.consensus_distance`` of ``params`` where ``record`` is
        true, NaN where it is false.  Across chips the distance all-reduces
        every parameter, and it reads the step's last result, so nothing
        hides it: inside the conditional's true branch only the steps the
        loop records pay for it."""
        return jax.lax.cond(
            record,
            lambda p: gossip.consensus_distance(
                p, axis_name=self.axis_name).astype(jnp.float32),
            lambda p: jnp.full((), jnp.nan, jnp.float32),
            params)

    def _telemetry_metrics(self, state, grads, new_params, new_opt,
                           new_comm, lr, n, alive=None,
                           mix_buf_new=None) -> dict:
        """In-graph telemetry collection (DESIGN.md §10): when the trainer
        carries a resolved :class:`~repro.telemetry.metrics.TelemetryConfig`,
        run its collectors on this step and return their scalars under the
        ``tm.`` prefix (the host recorder splits them back off, so the
        user-facing metric keys are untouched).

        Cadence is gated on the HOST, not with an in-graph ``lax.cond``: the
        loops pick between the plain trace and this telemetry trace per
        step/chunk (``collect=``).  A cond gate was measured at ~9% steps/s
        on the ring-8 CPU micro-bench even when it NEVER took the collect
        branch — XLA:CPU marshals every captured tree (grads, old/new
        params/opt/comm state) as conditional operands each step.  With two
        traces, an off-cadence step runs the byte-identical pre-telemetry
        graph, so telemetry off — and off-cadence — costs exactly zero (the
        bit-for-bit history pin in tests/test_api.py covers this)."""
        tel = getattr(self.trainer, "telemetry", None)
        if tel is None:
            return {}
        ctx = CollectorCtx(
            grads=grads, params_old=state.params, params_new=new_params,
            opt_state_old=state.opt_state, opt_state_new=new_opt,
            comm_state_old=state.comm_state, comm_state_new=new_comm,
            lr=lr, t=state.t, n_nodes=n, axis_name=self.axis_name,
            node_mean=self._node_mean_scalar,
            node_sum=self._node_sum_scalar,
            node_max=self._node_max_scalar,
            static=tel.static, alive=alive,
            mix_buf_old=state.mix_buf, mix_buf_new=mix_buf_new)
        with jax.named_scope("tm/collect"):
            vals = tel.collect(ctx)
        return {TM_PREFIX + k: v for k, v in vals.items()}

    def _chunk_math(self, state, batches, rng, record,
                    collect: bool = False):
        """k steps fused under one ``lax.scan`` (the per-step rng stream is
        split inside the scan exactly as the outer loop splits it);
        ``record`` is the ``[k]`` mask of the steps the loop records."""
        def body(carry, xs):
            st, r = carry
            batch, rec = xs
            r, sub = jax.random.split(r)
            st, metrics = self._step_math(st, batch, sub, rec,
                                          collect=collect)
            return (st, r), metrics

        (state, rng), metrics = jax.lax.scan(body, (state, rng),
                                             (batches, record))
        return state, rng, metrics

    # -- backend surface ------------------------------------------------------
    def _build_step(self, collect: bool = False):
        def step(state, batch, rng, record):
            return self._step_math(state, batch, rng, record,
                                   collect=collect)

        return jax.jit(step, donate_argnums=0)

    def _build_chunk(self, collect: bool = False):
        def chunk(state, batches, rng, record):
            return self._chunk_math(state, batches, rng, record,
                                    collect=collect)

        return jax.jit(chunk, donate_argnums=0)

    def _put_replicated(self, x):
        """Place a host value every node reads (identity placement here;
        the sharded override commits it replicated over the mesh)."""
        return jnp.asarray(x)

    def _record_arg(self, record):
        """``record`` (a bool, or a chunk's tuple of bools) as the device
        array the step takes, made once per value."""
        arr = self._record_args.get(record)
        if arr is None:
            arr = self._record_args[record] = self._put_replicated(
                np.asarray(record, bool))
        return arr

    def step(self, state, batch, rng, record: bool = True,
             collect: bool = False):
        """One jitted step.  DONATES ``state``: the input buffers back the
        output state, so per-device memory holds one state, not two.
        ``record`` says whether the step's metrics are read, and so whether
        it computes ``consensus``.  ``collect=True`` selects the
        telemetry-collecting trace (compiled separately, on first use)."""
        if collect not in self._step_fns:
            self._step_fns[collect] = self._build_step(collect)
        return self._step_fns[collect](state, batch, rng,
                                       self._record_arg(record))

    def step_chunk(self, state, batches, rng, record, collect: bool = False):
        """k fused steps in ONE dispatch; donates ``state`` like ``step``.
        ``record`` is a tuple of k bools, one per step."""
        if collect not in self._chunk_fns:
            self._chunk_fns[collect] = self._build_chunk(collect)
        return self._chunk_fns[collect](state, batches, rng,
                                        self._record_arg(tuple(record)))

    def init_state(self, build, *args):
        """The TrainState ``build(*args)`` makes, placed where this backend
        keeps it.  Here: built on the default device, then
        :meth:`finalize_state`."""
        return self.finalize_state(build(*args))

    def finalize_state(self, state):
        """Place a freshly initialized (host/replicated) TrainState where
        this backend wants it.  Identity for vmap; the sharded backend
        device_puts every node-stacked leaf sharded over the node axis."""
        return state

    def put_batch(self, batch, lead: int = 0):
        """Place one host batch (node-stacked at axis ``lead``; ``lead=1``
        for a chunked ``[k, n, ...]`` stack) where this backend wants it.
        Base/vmap just converts to device arrays; the sharded override
        assembles multi-process global arrays from each host's local data
        (per-host data feeding, DESIGN.md §12)."""
        del lead
        return jax.tree.map(jnp.asarray, batch)

    # -- overlap probe (tm.gossip_wait_ms) ------------------------------------
    def _build_probe(self, state, chunked: bool = False):
        """(launch_fn, compute_fn) pair for the gossip-wait probe: the
        launch stage and compute stage of ONE step compiled as separate
        non-donating dispatches, so the host can time how long finish_mix
        would block on the in-flight collectives after compute drains.
        Backends override to apply their shard_map wrapping."""
        def launch(st):
            w = self.trainer._mixing[st.t % self.trainer._mixing.shape[0]]
            return self._stage_launch_mix(st, w)

        def compute(st, batch, rng):
            if chunked:
                batch = jax.tree.map(lambda x: x[0], batch)
            return self._stage_compute(st, batch, rng,
                                       self.trainer.topology.n)[0]

        return jax.jit(launch), jax.jit(compute)

    def probe_metrics(self, state, batch, rng, chunked: bool = False) -> dict:
        """Host-side overlap telemetry for this step: dispatch the launch
        stage, dispatch + drain the compute stage, then measure how long the
        in-flight mix takes to finish beyond that — the residual gossip wait
        the pipeline could not hide (``tm.gossip_wait_ms``).  Runs on its
        own non-donating traces on collect steps only; returns {} when the
        overlap pipeline is inactive."""
        if self.overlap == "none" or getattr(state, "mix_buf", None) is None:
            return {}
        if self._probe_fns is None or self._probe_fns[0] != chunked:
            self._probe_fns = (chunked, self._build_probe(state, chunked))
        launch_fn, compute_fn = self._probe_fns[1]
        inflight = launch_fn(state)
        loss = compute_fn(state, batch, rng)
        jax.block_until_ready(loss)
        self.gossip_timer.arm()
        jax.block_until_ready(inflight)
        self.gossip_timer.lap(1)
        return {TM_PREFIX + "gossip_wait_ms":
                float(self.gossip_timer.last_s * 1e3)}

    # -- evaluation -----------------------------------------------------------
    def _eval_batch(self, state, eval_fn, batch):
        """Per-node sums for one eval batch: dict of ``[n]`` arrays."""
        return jax.vmap(lambda p, ms: eval_fn(p, ms, batch))(
            state.params, state.model_state)

    def evaluate(self, state, eval_fn, batches) -> dict:
        """Paper protocol: evaluate EACH node's local model on the FULL eval
        set, then average the per-node metrics.  eval_fn(params_i, mstate_i,
        batch) -> dict of sums + 'count'.  Identical across backends."""
        totals: dict[str, np.ndarray] = {}
        for batch in batches:
            res = self._eval_batch(state, eval_fn, batch)
            for k, v in res.items():
                totals[k] = totals.get(k, 0) + np.asarray(v)
        count = totals.pop("count")
        return {k: float(np.mean(v / count)) for k, v in totals.items()}

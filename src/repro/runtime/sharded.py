"""ShardedRuntime — the whole decentralized step inside one ``shard_map``.

The paper's setting is n independent nodes, each holding its own params,
momentum and data shard.  This backend makes the hardware look exactly like
that: the node index is a mesh axis, every node-stacked ``[n, ...]`` leaf of
the :class:`TrainState` is sharded over it (``P(node_axis, ...)``), and the
COMPLETE step — per-node ``grad(loss)``, the full transform-stage chain,
CHOCO/EF comm updates, and the compiled ppermute gossip schedule — runs
inside a single ``shard_map`` over that axis:

  * per-device memory is O(1) in n — each device holds only its own node's
    params/opt/comm state (``[1, ...]`` local shards), never the replicated
    node stack;
  * a step (or a whole ``lax.scan``-fused chunk) is exactly ONE dispatch —
    no vmap<->shard_map boundary crossing per mix site: the schedule
    executor (``gossip.apply_schedule_local``) is called directly from
    inside the already-sharded step instead of wrapping its own shard_map;
  * the transform chain runs unchanged on the local shards — elementwise
    stages are layout-oblivious, and the node-reducing stages read the axis
    context threaded through ``StepCtx`` (DESIGN.md §9).

Sharding rule (the layout contract): a leaf is node-stacked iff its leading
dimension equals the topology's n; such leaves get ``P(node_axis, None...)``,
everything else (step counters, per-stage scalars) is replicated ``P()``.
RNG parity with the vmap backend is exact: the per-node key is row
``axis_index`` of the SAME ``jax.random.split(rng, n)``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import gossip

from .base import Runtime


def node_leaf_spec(leaf, *, n: int, axis_name: str, lead: int = 0):
    """THE layout contract, in one place: ``P(axis_name, None, ...)`` for a
    node-stacked leaf (dim ``lead`` equals the global node count ``n``),
    replicated ``P()`` for everything else (step counters, per-stage
    scalars).  ``lead=1`` handles chunked batches stacked [k, n, ...].
    Used by :class:`ShardedRuntime` and, through it, the hybrid runtime."""
    shape = getattr(leaf, "shape", None)
    if shape is not None and len(shape) > lead and shape[lead] == n:
        spec = [None] * len(shape)
        spec[lead] = axis_name
        return P(*spec)
    return P()


def node_specs(tree, *, n: int, axis_name: str, lead: int = 0):
    """Per-leaf :func:`node_leaf_spec` tree."""
    return jax.tree.map(
        lambda l: node_leaf_spec(l, n=n, axis_name=axis_name, lead=lead),
        tree)


@dataclasses.dataclass
class ShardedRuntime(Runtime):
    name: str = "sharded"

    def __post_init__(self):
        super().__post_init__()
        tr = self.trainer
        n = tr.topology.n
        if tr.mesh is None:
            raise ValueError(
                "runtime='sharded' needs a mesh whose node axis carries the "
                "n node index; pass DecentralizedTrainer(mesh=, node_axis=) "
                "or use runtime='vmap'")
        axes = dict(tr.mesh.shape)
        if axes.get(tr.node_axis) != n:
            raise ValueError(
                f"runtime='sharded': mesh axis {tr.node_axis!r} has size "
                f"{axes.get(tr.node_axis)}, topology has n={n}")
        self.axis_name = tr.node_axis
        self.mesh = tr.mesh
        # the compiled collective schedule this step executes in-place
        # (resolve_gossip already validated mesh x topology); 'dense'
        # (forced) runs every site as a local all-gather round
        self._schedule = tr._resolved.schedule

    # -- node-axis hooks ------------------------------------------------------
    def _node_rngs(self, rng, n: int):
        # row axis_index of the SAME split the vmap backend uses — per-node
        # rng streams are bit-identical across backends
        rngs = jax.random.split(rng, n)
        i = jax.lax.axis_index(self.axis_name)
        return jax.lax.dynamic_slice_in_dim(rngs, i, 1, axis=0)

    def _node_mean_scalar(self, x):
        return jax.lax.pmean(jnp.mean(x), self.axis_name)

    def _node_sum_scalar(self, x):
        return jax.lax.psum(x, self.axis_name)

    def _node_max_scalar(self, x):
        return jax.lax.pmax(jnp.max(x), self.axis_name)

    def _local_update_mask(self, u):
        i = jax.lax.axis_index(self.axis_name)
        return jax.lax.dynamic_slice_in_dim(u, i, 1, axis=0)

    def _mix_impl(self, w, t, mix_mask=None):
        # always installed: the optimizer's dense-einsum default would
        # contract the LOCAL leading axis (size 1), not the node axis
        if mix_mask is not None:
            raise ValueError(
                "scenario fault injection is not supported on "
                "runtime='sharded'; use runtime='hybrid' (one node per "
                "device is hybrid with n_devices == n) or 'vmap'")
        return gossip.make_local_mix_fn(
            self._schedule, axis_name=self.axis_name, w_ref=w, t=t)

    # -- sharding specs (the shared layout contract above) --------------------
    def _leaf_spec(self, leaf, lead: int = 0):
        return node_leaf_spec(leaf, n=self.trainer.topology.n,
                              axis_name=self.axis_name, lead=lead)

    def _specs(self, tree, lead: int = 0):
        return node_specs(tree, n=self.trainer.topology.n,
                          axis_name=self.axis_name, lead=lead)

    def _global_put(self, tree, lead: int = 0):
        """Multi-process placement: assemble each leaf as a GLOBAL jax.Array
        from this host's local rows (``jax.make_array_from_callback`` hands
        every process exactly the index slices its own devices carry —
        per-host data feeding, DESIGN.md §12).  Host values must be
        process-identical, which every caller guarantees: broadcast x^0 at
        init, the deterministic synthetic batch stream in the loops."""
        def put(l):
            sh = NamedSharding(self.mesh, self._leaf_spec(l, lead=lead))
            a = np.asarray(l)
            return jax.make_array_from_callback(
                a.shape, sh, lambda idx, a=a: a[idx])

        return jax.tree.map(put, tree)

    def init_state(self, build, *args):
        """Build the state with every node-stacked leaf born sharded over
        the node axis: one jitted ``build`` whose outputs carry the layout
        contract's shardings, so each device computes only its own nodes'
        rows.  Built on one device and then split, the whole node stack
        and its optimizer state pass through that device first (5.4 GB for
        four mamba2-130m nodes).  A multi-process mesh places host values
        instead (:meth:`finalize_state`)."""
        if jax.process_count() > 1:
            return super().init_state(build, *args)
        out = jax.tree.map(
            lambda l: NamedSharding(self.mesh, self._leaf_spec(l)),
            jax.eval_shape(build, *args))
        return jax.jit(build, out_shardings=out)(*args)

    def finalize_state(self, state):
        """Shard a freshly initialized TrainState over the node axis — after
        this, no device ever materializes the full node stack again.  On a
        multi-process mesh the leaves become global arrays assembled from
        each host's local slices."""
        if jax.process_count() > 1:
            return self._global_put(state)
        return jax.tree.map(
            lambda l: jax.device_put(
                l, NamedSharding(self.mesh, self._leaf_spec(l))), state)

    def put_batch(self, batch, lead: int = 0):
        """Single-process: plain device arrays (the jit sharding-matches
        against the in_specs).  Multi-process: global arrays built from this
        host's local rows of the (process-identical) host batch."""
        if jax.process_count() > 1:
            return self._global_put(batch, lead=lead)
        return jax.tree.map(jnp.asarray, batch)

    def _put_replicated(self, x):
        """Commit ``x`` replicated over the mesh.  The chunk returns its rng
        carry with this sharding, so a host-made key must enter with it too:
        otherwise the second chunk sees a new input type and retraces (and
        recompiles) the whole step."""
        sh = NamedSharding(self.mesh, P())
        if getattr(x, "sharding", None) == sh:
            return x
        if jax.process_count() > 1:
            a = np.asarray(x)
            return jax.make_array_from_callback(a.shape, sh,
                                                lambda idx: a[idx])
        return jax.device_put(x, sh)

    def step_chunk(self, state, batches, rng, record, collect: bool = False):
        return super().step_chunk(state, batches, self._put_replicated(rng),
                                  record, collect=collect)

    # -- compilation: ONE shard_map per step / per chunk ----------------------
    def _shard(self, fn, in_specs, out_specs):
        return gossip._shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            manual_axes=frozenset({self.axis_name}))

    def _build_step(self, collect: bool = False):
        def sharded_step(state, batch, rng, record):
            sspecs = self._specs(state)
            fn = self._shard(
                lambda st, b, r, rec: self._step_math(st, b, r, rec,
                                                      collect=collect),
                in_specs=(sspecs, self._specs(batch), P(), P()),
                out_specs=(sspecs, P()))
            return fn(state, batch, rng, record)

        return jax.jit(sharded_step, donate_argnums=0)

    def _build_chunk(self, collect: bool = False):
        def sharded_chunk(state, batches, rng, record):
            sspecs = self._specs(state)
            fn = self._shard(
                lambda st, b, r, rec: self._chunk_math(st, b, r, rec,
                                                       collect=collect),
                in_specs=(sspecs, self._specs(batches, lead=1), P(), P()),
                out_specs=(sspecs, P(), P()))
            return fn(state, batches, rng, record)

        return jax.jit(sharded_chunk, donate_argnums=0)

    def _build_probe(self, state, chunked: bool = False):
        """Probe stages wrapped in the same single-shard_map structure as
        the real step, but non-donating, so the gossip-wait timing reflects
        the actual compiled collective schedule."""
        tr = self.trainer

        def launch_outer(st):
            fn = self._shard(
                lambda s: self._stage_launch_mix(
                    s, tr._mixing[s.t % tr._mixing.shape[0]]),
                in_specs=(self._specs(st),),
                out_specs=self._specs(st.mix_buf))
            return fn(st)

        def compute_outer(st, batch, rng):
            def inner(s, b, r):
                if chunked:
                    b = jax.tree.map(lambda x: x[0], b)
                return self._stage_compute(s, b, r, tr.topology.n)[0]

            fn = self._shard(
                inner,
                in_specs=(self._specs(st),
                          self._specs(batch, lead=1 if chunked else 0), P()),
                out_specs=P(self.axis_name))
            return fn(st, batch, rng)

        return jax.jit(launch_outer), jax.jit(compute_outer)

    # -- evaluation -----------------------------------------------------------
    def evaluate(self, state, eval_fn, batches) -> dict:
        if jax.process_count() > 1:
            raise NotImplementedError(
                "evaluation on a multi-process mesh is not supported: "
                "checkpoint the run and evaluate in a single process "
                "(the per-node eval protocol replicates the full eval set)")
        return super().evaluate(state, eval_fn, batches)

    def _eval_batch(self, state, eval_fn, batch):
        """Each device evaluates its own node's model on the (replicated)
        batch; per-node sums come back as global [n] arrays, so the host
        aggregation is byte-identical to the vmap backend's."""
        batch = jax.tree.map(jnp.asarray, batch)

        def local_eval(p, ms, b):
            return jax.vmap(lambda pi, mi: eval_fn(pi, mi, b))(p, ms)

        fn = self._shard(
            local_eval,
            in_specs=(self._specs(state.params),
                      self._specs(state.model_state), P()),
            out_specs=P(self.axis_name))
        return fn(state.params, state.model_state, batch)

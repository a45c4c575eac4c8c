"""Gossip averaging primitives over a node-stacked pytree.

Layout convention (see DESIGN.md §3): every parameter / optimizer-state leaf
carries the decentralized node index as its *leading* axis, shape
``[n_nodes, ...]``.  On CPU that axis lives in memory; on a TPU mesh it is
sharded over the ``data`` mesh axis, so the mixing contraction
below becomes collectives over that axis.

Schedules (DESIGN.md §7):

* ``mix_dense``  — paper-faithful: ``x <- einsum('nm,m...->n...', W, x)``.
  For a sharded node axis XLA lowers this to an all-gather (every node reads
  every other node's model) even when W is sparse.
* ``mix_sparse_shardmap`` — the topology compiler's schedule: ANY
  doubly-stochastic ``W`` (including each phase of a time-varying stack) is
  decomposed once at setup time (``compile_gossip_schedule``) into weighted
  ``jax.lax.ppermute`` rounds — exact permutation splitting for 1-peer
  graphs, greedy edge-coloring for undirected graphs (social32, torus,
  star) — so bytes-on-wire scale with node degree, not n.  Phases whose
  decomposition would exceed the all-gather cost fall back to a dense
  all-gather round automatically.  A ring compiles to two rounds, one
  ppermute to each neighbour.

Both act on whole pytrees, compute the same weighted sum (tested
against each other), and are differentiable (gossip happens outside the
gradient in DSGD-family algorithms, but consensus experiments use it inside
jitted loops).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.telemetry import trace

from .topology import Topology

PyTree = Any

__all__ = [
    "mix_dense",
    "mix_leaf_dense",
    "mix_sparse_shardmap",
    "make_sparse_mix_fn",
    "apply_schedule_local",
    "make_local_mix_fn",
    "GossipSchedule",
    "PhaseSchedule",
    "ResolvedGossip",
    "resolve_gossip",
    "GOSSIP_SCHEDULES",
    "compile_gossip_schedule",
    "schedule_matrix",
    "consensus_distance",
    "node_mean",
    "mask_renormalize",
    "BlockMask",
    "BlockSchedule",
    "compile_block_schedule",
    "apply_block_schedule_local",
    "mix_leaf_dense_block",
    "make_block_mix_fn",
]


def tree_bytes(tree) -> int:
    """Bytes of the array leaves of ``tree`` (arrays, tracers or shape
    structs)."""
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree) if hasattr(l, "shape"))


def mix_leaf_dense(w: jax.Array, x: jax.Array) -> jax.Array:
    """x[n, ...] -> (W @ x) with the contraction on the node axis.

    The contraction runs in (at least) fp32 whatever the leaf dtype: casting
    W to bf16 leaves rows summing to 1 +- ~1e-2, a consensus drift that
    compounds over steps.  In fp32 the row-sum error (~1e-7) rounds away when
    the result is cast back to the leaf dtype.
    """
    flat = x.reshape(x.shape[0], -1)
    cdt = jnp.promote_types(flat.dtype, jnp.float32)
    out = jnp.einsum("nm,mf->nf", w.astype(cdt), flat.astype(cdt),
                     preferred_element_type=cdt)
    return out.astype(x.dtype).reshape(x.shape)


def mix_dense(w: jax.Array | np.ndarray, tree: PyTree) -> PyTree:
    """Dense mixing of a node-stacked pytree: leaf[n,...] <- sum_m W[n,m] leaf[m,...]."""
    w = jnp.asarray(w)
    return jax.tree.map(functools.partial(mix_leaf_dense, w), tree)


def _shard_map(f, *, mesh, in_specs, out_specs, manual_axes):
    """The one ``shard_map`` entry: manual over ``manual_axes``, every other
    mesh axis left compiler-managed.  Varying-axis checking is off: the step
    bodies mix per-node values with replicated ones freely (Pallas output
    structs, scan carries started from replicated zeros), and the out_specs
    already state the node-axis layout."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset(manual_axes), check_vma=False)


# ---------------------------------------------------------------------------
# topology compiler: any doubly-stochastic W -> weighted ppermute rounds
# ---------------------------------------------------------------------------

Round = tuple[tuple[tuple[int, int], ...], np.ndarray]  # (perm pairs, recv_w)


@dataclasses.dataclass(frozen=True)
class PhaseSchedule:
    """One mixing phase compiled to collective rounds (DESIGN.md §7).

    ``x_i' = self_weight[i] * x_i + sum_r recv_w_r[i] * ppermute_r(x)_i``

    Each round is a *partial permutation*: a set of directed (src, dst)
    pairs with distinct senders and distinct receivers, realizable as one
    ``jax.lax.ppermute`` (non-receivers get zeros, and their ``recv_w`` is
    zero too).  ``dense=True`` marks the all-gather fallback: the phase costs
    at least as much as an all-gather, so it runs as one
    ``lax.all_gather`` + row contraction instead.
    """

    n: int
    self_weight: np.ndarray                 # [n] diagonal of W
    rounds: tuple[Round, ...]
    dense: bool
    w: np.ndarray                           # [n, n] the phase matrix

    @property
    def messages(self) -> int:
        """Point-to-point model messages this phase puts on the wire."""
        if self.dense:
            return self.n * (self.n - 1)
        return sum(len(perm) for perm, _ in self.rounds)

    @property
    def most_sent(self) -> int:
        """Most model messages one node sends in this phase: ``n - 1`` in
        the all-gather fallback, else the rounds in which it is a sender."""
        if self.dense:
            return self.n - 1
        sent = np.zeros(self.n, int)
        for perm, _ in self.rounds:
            for src, _ in perm:
                sent[src] += 1
        return int(sent.max(initial=0))


@dataclasses.dataclass(frozen=True)
class GossipSchedule:
    """Compiled schedule for a (possibly time-varying) topology; step ``t``
    runs ``phases[t % len(phases)]``."""

    name: str
    n: int
    phases: tuple[PhaseSchedule, ...]

    @property
    def max_rounds(self) -> int:
        return max((len(p.rounds) for p in self.phases), default=0)

    @property
    def any_dense(self) -> bool:
        return any(p.dense for p in self.phases)

    def messages_per_step(self) -> float:
        """Average point-to-point model messages per gossip step."""
        return float(np.mean([p.messages for p in self.phases]))

    def dense_messages_per_step(self) -> float:
        """What the all-gather baseline ships per step: every node reads
        every other node's model."""
        return float(self.n * (self.n - 1))

    def bytes_sent(self, tree) -> int:
        """Bytes the busiest node sends in one gossip step of its ``tree``
        (its own shard), averaged over the phases."""
        sent = np.mean([p.most_sent for p in self.phases])
        return int(round(float(sent) * tree_bytes(tree)))


def _compile_phase(w: np.ndarray, *, dense_threshold: float) -> PhaseSchedule:
    """Greedy edge-coloring of one doubly-stochastic matrix.

    Directed edges (src j -> dst i wherever ``w[i, j] > 0``) are first-fit
    packed into partial permutations.  Edges are ordered by offset
    ``(dst - src) mod n`` so circulant structure (rings, tori, the 1-peer
    exponential phases) packs into whole cyclic shifts — the 1-peer phases
    compile to exactly one full-permutation round.

    Cost model (DESIGN.md §7): a pipelined all-gather costs ~``n - 1``
    link-message times and ships ``n (n-1)`` messages; the sparse schedule
    costs ``R`` rounds and ships one message per edge.  Fall back to dense
    when the rounds give neither a latency win (``R < n - 1``) nor at least
    a 2x bytes win at equal latency.
    """
    n = w.shape[0]
    edges = [(j, i) for i in range(n) for j in range(n)
             if i != j and w[i, j] > 0.0]
    edges.sort(key=lambda e: ((e[1] - e[0]) % n, e[0]))
    senders: list[set[int]] = []
    receivers: list[set[int]] = []
    rounds_pairs: list[list[tuple[int, int]]] = []
    for src, dst in edges:
        for r in range(len(rounds_pairs)):
            if src not in senders[r] and dst not in receivers[r]:
                rounds_pairs[r].append((src, dst))
                senders[r].add(src)
                receivers[r].add(dst)
                break
        else:
            rounds_pairs.append([(src, dst)])
            senders.append({src})
            receivers.append({dst})
    n_rounds = len(rounds_pairs)
    n_messages = len(edges)
    budget = dense_threshold * (n - 1)
    sparse_wins = n_rounds < budget or (
        n_rounds <= budget and n_messages * 2 <= n * (n - 1))
    if n > 1 and not sparse_wins:
        return PhaseSchedule(n=n, self_weight=np.diag(w).copy(), rounds=(),
                             dense=True, w=w.copy())
    rounds = []
    for pairs in rounds_pairs:
        recv_w = np.zeros(n)
        for src, dst in pairs:
            recv_w[dst] = w[dst, src]
        rounds.append((tuple(sorted(pairs)), recv_w))
    phase = PhaseSchedule(n=n, self_weight=np.diag(w).copy(),
                          rounds=tuple(rounds), dense=False, w=w.copy())
    np.testing.assert_allclose(schedule_matrix(phase), w, atol=0.0)
    return phase


def schedule_matrix(phase: PhaseSchedule) -> np.ndarray:
    """Reconstruct the mixing matrix a compiled phase implements (exact —
    every edge carries its original weight)."""
    if phase.dense:
        return phase.w.copy()
    m = np.diag(phase.self_weight)
    for pairs, recv_w in phase.rounds:
        for src, dst in pairs:
            m[dst, src] += recv_w[dst]
    return m


def compile_gossip_schedule(topo: Topology, *,
                            dense_threshold: float = 1.0) -> GossipSchedule:
    """Compile every phase of ``topo.mixing`` into a static ppermute
    schedule (with per-phase dense fallback).  Pure numpy; runs once at
    trainer/step-builder setup."""
    phases = tuple(_compile_phase(topo.mixing[k],
                                  dense_threshold=dense_threshold)
                   for k in range(topo.mixing.shape[0]))
    return GossipSchedule(name=topo.name, n=topo.n, phases=phases)


def _apply_phase_local(x: jax.Array, phase: PhaseSchedule, *,
                       axis_name: str) -> jax.Array:
    """One compiled phase on a local (per-node) shard inside shard_map.
    Per-node weights are gathered from [n] constants by ``axis_index``; the
    weighted sum runs in fp32 like ``mix_leaf_dense``.  Collectives ship the
    *native* leaf dtype — receivers upcast after receipt (exact for bf16),
    so low-precision models keep their full bytes-on-wire savings."""
    i = jax.lax.axis_index(axis_name)
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    if phase.dense:
        with jax.named_scope("tm/gossip/allgather"):
            g = jax.lax.all_gather(x, axis_name)       # [n, ...local]
        w_row = jnp.asarray(phase.w, cdt)[i]           # [n]
        out = jnp.tensordot(w_row, g.astype(cdt), axes=1)
    else:
        out = x.astype(cdt) * jnp.asarray(phase.self_weight, cdt)[i]
        for perm, recv_w in phase.rounds:
            with jax.named_scope("tm/gossip/ppermute"):
                recv = jax.lax.ppermute(x, axis_name, perm=list(perm))
            out = out + recv.astype(cdt) * jnp.asarray(recv_w, cdt)[i]
    return out.astype(x.dtype)


def apply_schedule_local(x: jax.Array, schedule: GossipSchedule,
                         t: jax.Array | int, *, axis_name: str) -> jax.Array:
    """One gossip round of a compiled schedule on a *local* (per-node) shard.

    THE schedule executor: the caller must already be inside a manual region
    over ``axis_name`` (``mix_sparse_shardmap`` wraps it in its own
    shard_map; the sharded execution runtime calls it directly from inside
    the whole-step shard_map, so the step stays ONE dispatch).  A python-int
    ``t`` (or a single-phase schedule) resolves the phase statically; a
    traced step counter selects it with ``lax.switch`` (``t`` is replicated,
    so every device takes the same branch and the collectives inside the
    branches stay coherent).
    """
    n_phases = len(schedule.phases)
    if n_phases == 1:
        return _apply_phase_local(x, schedule.phases[0], axis_name=axis_name)
    if isinstance(t, int):
        return _apply_phase_local(x, schedule.phases[t % n_phases],
                                  axis_name=axis_name)
    branches = [functools.partial(_apply_phase_local, phase=ph,
                                  axis_name=axis_name)
                for ph in schedule.phases]
    return jax.lax.switch(t % n_phases, branches, x)


def mix_leaf_dense_local(w: jax.Array, x: jax.Array, *,
                         axis_name: str) -> jax.Array:
    """Dense contraction of an EXPLICIT [n, n] matrix against local shards:
    ``out_i = sum_j w[i, j] x_j`` via one all-gather, row selected by
    ``axis_index``.  The in-shard-map analogue of :func:`mix_leaf_dense`
    (same fp32 contraction rule); used for mix sites that pass a matrix
    other than the compiled topology W (``buffer_sync(mode='complete')``'s
    1/n global average) and for the forced-dense schedule."""
    i = jax.lax.axis_index(axis_name)
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    g = jax.lax.all_gather(x, axis_name)            # [n, ...local]
    out = jnp.tensordot(jnp.asarray(w, cdt)[i], g.astype(cdt), axes=1)
    return out.astype(x.dtype)


def make_local_mix_fn(schedule: GossipSchedule | None, *, axis_name: str,
                      w_ref, t: jax.Array | int = 0):
    """``mix_fn(w, tree)`` for callers ALREADY inside a shard_map over
    ``axis_name`` — the sharded execution runtime's counterpart of
    :func:`make_sparse_mix_fn`, with the same w-operand dispatch: sites that
    mix with the topology matrix pass the exact ``w_ref`` object and get the
    compiled schedule at phase ``t`` executed directly on the local shards
    (NO shard_map re-entry); sites that pass any other [n, n] matrix — or
    every site when ``schedule`` is None (forced-dense gossip) — get the
    all-gather row contraction of the matrix they actually asked for.
    Each call counts, at trace time, the bytes this device sends
    (``tm/gossip/bytes`` in ``repro.telemetry.trace``): the schedule's
    :meth:`GossipSchedule.bytes_sent`, or ``n - 1`` copies of its shard in
    the all-gather."""

    def mix_fn(w, tree):
        if schedule is None or w is not w_ref:
            n = jnp.shape(w)[0]
            trace.count("tm/gossip/bytes", (n - 1) * tree_bytes(tree))
            return jax.tree.map(
                functools.partial(mix_leaf_dense_local, w,
                                  axis_name=axis_name), tree)
        trace.count("tm/gossip/bytes", schedule.bytes_sent(tree))
        return jax.tree.map(
            lambda x: apply_schedule_local(x, schedule, t,
                                           axis_name=axis_name), tree)

    return mix_fn


def mix_sparse_shardmap(
    tree: PyTree,
    *,
    topology: Topology | None = None,
    schedule: GossipSchedule | None = None,
    t: jax.Array | int = 0,
    mesh: jax.sharding.Mesh,
    axis_name: str,
) -> PyTree:
    """Sparse neighbor-exchange gossip for ANY registry topology.

    Equivalent to ``mix_dense(topology.w(t), tree)`` for leaves with a
    leading node axis sharded on ``axis_name`` (the mesh axis size must equal
    ``topology.n``), but exchanges only actual graph edges via the compiled
    ppermute rounds.  ``t`` may be a traced step counter: time-varying stacks
    select their phase with ``lax.switch`` inside the shard_map body (every
    node holds the same replicated ``t``, so all devices take the same
    branch).  Pass a pre-compiled ``schedule`` to skip recompilation in hot
    setup paths.
    """
    if schedule is None:
        if topology is None:
            raise ValueError("need topology= or schedule=")
        schedule = compile_gossip_schedule(topology)
    n = schedule.n
    if dict(mesh.shape).get(axis_name) != n:
        raise ValueError(
            f"schedule for n={n} nodes but mesh axis {axis_name!r} has size "
            f"{dict(mesh.shape).get(axis_name)}")
    # static t (python int) or a single phase: resolve the phase now and
    # compile no switch; only a traced step counter pays the lax.switch
    static = len(schedule.phases) == 1 or isinstance(t, int)

    def local_fn(t_, local_tree):
        tt = t if static else t_
        return jax.tree.map(
            lambda x: apply_schedule_local(x, schedule, tt,
                                           axis_name=axis_name),
            local_tree)

    specs = jax.tree.map(
        lambda x: P(axis_name, *([None] * (x.ndim - 1))), tree)
    return _shard_map(
        local_fn, mesh=mesh, in_specs=(P(), specs), out_specs=specs,
        manual_axes=frozenset({axis_name}),
    )(jnp.asarray(t, jnp.int32), tree)


def make_sparse_mix_fn(schedule: GossipSchedule, *, mesh, axis_name: str,
                       w_ref, t: jax.Array | int = 0):
    """``mix_fn(w, tree)`` closure over a compiled schedule — THE way to
    install the sparse schedule behind the zoo-wide hook.

    Dispatch is by identity of the ``w`` operand: sites that mix with the
    topology matrix pass the exact ``ctx.w`` object (``w_ref`` here) through
    the hook and get the compiled schedule at phase ``t``; sites that pass
    any OTHER matrix — ``buffer_sync(mode='complete')`` ships a 1/n global
    average — get the dense contraction of the matrix they actually asked
    for, since the schedule only encodes W_t.
    """

    def mix_fn(w, tree):
        if w is not w_ref:
            return mix_dense(w, tree)
        return mix_sparse_shardmap(tree, schedule=schedule, t=t, mesh=mesh,
                                   axis_name=axis_name)

    return mix_fn


GOSSIP_SCHEDULES = ("auto", "dense", "sparse_ppermute")


@dataclasses.dataclass(frozen=True)
class ResolvedGossip:
    """Outcome of ``resolve_gossip``: which mix implementation to install
    behind the zoo-wide ``mix_fn`` hook.

    ``kind`` is ``'dense'`` (keep the optimizer's dense contraction) or
    ``'sparse'`` (compiled schedule; ``schedule`` holds the
    :class:`GossipSchedule`).  ``mix_fn`` materializes the hook closure for
    the step's traced counter ``t``.
    """

    kind: str
    schedule: GossipSchedule | None = None
    mesh: Any = None
    node_axis: str | None = None

    def mix_fn(self, *, w_ref=None, t: jax.Array | int = 0):
        """The ``mix_fn(w, tree)`` to install, or ``None`` when the
        optimizer's dense default should stand."""
        if self.kind == "dense":
            return None
        return make_sparse_mix_fn(self.schedule, mesh=self.mesh,
                                  axis_name=self.node_axis, w_ref=w_ref, t=t)


def resolve_gossip(topo: Topology, *, schedule: str = "auto", mesh=None,
                   node_axis: str | None = None) -> ResolvedGossip:
    """The gossip-schedule selection rules of ``DecentralizedTrainer`` on
    its one-node-per-device runtimes (``vmap`` and ``sharded``).

    * ``'dense'`` — always the dense contraction (also the n=1 reduction).
    * ``'auto'``  — dense without a mesh; the compiled sparse schedule when
      a mesh carries the node axis.
    * ``'sparse_ppermute'`` — explicit; requires a mesh whose ``node_axis``
      has size ``topo.n``.

    All invalid combinations raise here, at resolve time, with actionable
    messages — not from deep inside a jitted step builder.
    """
    if schedule not in GOSSIP_SCHEDULES:
        raise ValueError(f"unknown gossip schedule {schedule!r}; valid: "
                         f"{' | '.join(GOSSIP_SCHEDULES)}")
    if topo.n == 1 or schedule == "dense":
        return ResolvedGossip("dense")
    if schedule == "auto" and (mesh is None or node_axis is None):
        return ResolvedGossip("dense")
    if mesh is None or node_axis is None:
        raise ValueError(f"{schedule} needs mesh + node_axis")
    axes = dict(mesh.shape)
    if node_axis not in axes:
        raise ValueError(
            f"mesh has no axis {node_axis!r} to carry the node index; "
            f"mesh axes: {sorted(axes)}")
    if axes[node_axis] != topo.n:
        raise ValueError(
            f"mesh axis {node_axis!r} has size {axes[node_axis]}, topology "
            f"has n={topo.n}")
    return ResolvedGossip("sparse", compile_gossip_schedule(topo), mesh,
                          node_axis)


def node_mean(tree: PyTree, *, axis_name: str | None = None) -> PyTree:
    """Global average over the node axis (the hypothetical 'global' model).

    ``axis_name=None`` reduces the stacked leading axis (keepdims, so the
    result broadcasts back against ``[n, ...]`` leaves); with an axis name
    the node axis is (block-)sharded over a mesh axis and the caller is
    inside a manual region — the local block mean (a no-op for the sharded
    runtime's ``[1, ...]`` shards) followed by ``lax.pmean`` gives the same
    average with a local ``[1, ...]`` shape that broadcasts against both
    ``[1, ...]`` shards and ``[b, ...]`` hybrid blocks, so the forms are
    drop-in interchangeable.
    """
    if axis_name is not None:
        return jax.tree.map(
            lambda x: jax.lax.pmean(jnp.mean(x, axis=0, keepdims=True),
                                    axis_name), tree)
    return jax.tree.map(lambda x: jnp.mean(x, axis=0, keepdims=True), tree)


def consensus_distance(tree: PyTree, *,
                       axis_name: str | None = None) -> jax.Array:
    """sqrt( mean_i || x_i - x_bar ||^2 / n ) aggregated over all leaves —
    the quantity plotted in Fig. 3 / Kong et al. 2021.  Axis-context rule as
    :func:`node_mean`: per-node squared distances reduce over the stacked
    leading axis, or over the named mesh axis when called from inside a
    sharded/hybrid step (``lax.pmean`` of the per-device block means — the
    local block may hold 1 node per device or ``b = n / n_devices``)."""
    sq, cnt = 0.0, 0.0
    for leaf in jax.tree.leaves(tree):
        if axis_name is not None:
            mean = jax.lax.pmean(jnp.mean(leaf, axis=0, keepdims=True),
                                 axis_name)
            sq = sq + jax.lax.pmean(
                jnp.sum((leaf - mean) ** 2) / leaf.shape[0], axis_name)
        else:
            mean = jnp.mean(leaf, axis=0, keepdims=True)
            sq = sq + jnp.sum((leaf - mean) ** 2) / leaf.shape[0]
        cnt = cnt + np.prod(leaf.shape[1:])
    return jnp.sqrt(sq / cnt)


# ---------------------------------------------------------------------------
# fault-model mixing: renormalize W onto the alive subgraph (DESIGN.md §11)
# ---------------------------------------------------------------------------


def mask_renormalize(w: jax.Array | np.ndarray,
                     m: jax.Array | np.ndarray) -> jax.Array:
    """Effective mixing matrix when only nodes with ``m_i = 1`` gossip.

    Off-diagonal mass flows only over edges whose BOTH endpoints are alive
    (``w_ij m_i m_j``); each alive node folds the mass of its dead
    neighbours back into its own diagonal (row sums stay 1), and a dead node
    keeps its state exactly (identity row).  For symmetric ``W`` (Metropolis
    weights — every generated/registry graph used with scenarios) the result
    is again symmetric, hence doubly stochastic on the alive subgraph; its
    ``spectral_gap`` measures how much the outage slows consensus (tested in
    test_scenario.py).
    """
    w = jnp.asarray(w)
    m = jnp.asarray(m, w.dtype)
    eye = jnp.eye(w.shape[0], dtype=w.dtype)
    offd = w * (m[:, None] * m[None, :]) * (1.0 - eye)
    diag = m * (1.0 - offd.sum(axis=1)) + (1.0 - m)
    return offd + eye * diag


# ---------------------------------------------------------------------------
# block-compiled schedules: n nodes on d devices, b = n/d nodes per device
# ---------------------------------------------------------------------------
#
# The hybrid runtime keeps node g's state at slot g % b on device g // b
# (block-major — a global [n, ...] array sharded P(axis) over d devices lands
# exactly in this layout).  A compiled PhaseSchedule round is a partial
# permutation of NODES; at block granularity each edge (src -> dst) becomes a
# whole-block ppermute by the DEVICE offset ((dst//b - src//b) mod d) plus a
# per-slot gather on the receiving device.  Grouping a round's edges by that
# offset turns each round into <= d ppermutes of full blocks, with [d, b]
# constant index/weight tables selected by ``axis_index`` — the same
# "per-node constants" trick as _apply_phase_local, one level up.


@dataclasses.dataclass(frozen=True)
class BlockMask:
    """Block-local view of a scenario alive mask (DESIGN.md §11): the hybrid
    runtime derives only its device's rows, so the executors never require a
    materialized ``[n]`` mask.  ``local`` is this device's ``[b]`` slice;
    ``of(ids)`` derives the mask rows for arbitrary global node ids (the
    per-node fold_in keying in ``repro.scenario`` makes any subset
    computable); ``full()`` materializes the whole ``[n]`` mask — only the
    dense all-gather fallback, which contracts global rows anyway, pays
    for it.  A plain traced ``[n]`` array is still accepted everywhere a
    ``BlockMask`` is (the vmap path and older callers)."""

    local: Any                    # [b] this device's alive rows (traced)
    of: Any                       # ids [k] -> [k] mask rows (traced fn)
    full: Any                     # () -> [n] global mask (dense fallback)


@dataclasses.dataclass(frozen=True)
class BlockGroup:
    """Edges of one round sharing one device offset.  ``recv_w[dev, slot]``
    is 0 for dst slots this group does not feed (their ``src_local`` /
    ``src_node`` default to the slot itself, so masked gathers stay benign).
    """

    offset: int              # recv block comes from device (i - offset) % d
    src_local: np.ndarray    # [d, b] slot within the received block
    src_node: np.ndarray     # [d, b] global src node id (for fault masks)
    recv_w: np.ndarray       # [d, b] edge weight into each dst slot


@dataclasses.dataclass(frozen=True)
class BlockRound:
    groups: tuple[BlockGroup, ...]


@dataclasses.dataclass(frozen=True)
class BlockPhase:
    dense: bool
    w: np.ndarray            # [n, n] the phase matrix
    self_weight: np.ndarray  # [d, b] diagonal of W, block-major
    rounds: tuple[BlockRound, ...]


@dataclasses.dataclass(frozen=True)
class BlockSchedule:
    """A :class:`GossipSchedule` re-compiled for block-sharded execution."""

    name: str
    n: int
    d: int                   # devices (mesh axis size)
    b: int                   # nodes per device, n // d
    phases: tuple[BlockPhase, ...]

    @property
    def max_ppermutes(self) -> int:
        """Worst-case whole-block ppermutes for one gossip step."""
        return max((sum(sum(1 for g in r.groups if g.offset != 0)
                        for r in p.rounds)
                    for p in self.phases if not p.dense), default=0)


def compile_block_schedule(schedule: GossipSchedule, n_devices: int, *,
                           dense_threshold: float = 1.0) -> BlockSchedule:
    """Regroup a compiled node-granular schedule into device-offset blocks.

    Pure numpy, runs once at runtime setup.  Dense phases stay dense (one
    all-gather of blocks + row contraction); sparse phases keep their round
    structure — weights are carried verbatim and each round still sums its
    edges, so the phase matrix is reproduced exactly.

    The DESIGN.md §7 cost model is re-applied at BLOCK granularity: a round
    now costs one whole-block ppermute per nonzero device offset, while the
    all-gather fallback costs ``d - 1`` link-block times regardless of n —
    so a phase the node-granular compiler kept sparse (e.g. a power-law
    graph: R ~ max-degree rounds << n) can still lose once blocked (R
    rounds x up to d offsets >> d - 1).  Such phases flip to dense here;
    rings/tori (offsets stay within +-1 device) stay sparse.
    """
    n = schedule.n
    if n_devices < 1 or n % n_devices:
        raise ValueError(
            f"block schedule needs n_devices dividing n={n}, got "
            f"{n_devices}")
    d, b = n_devices, n // n_devices
    phases = []
    for ph in schedule.phases:
        sw = ph.self_weight.reshape(d, b).copy()
        if ph.dense:
            phases.append(BlockPhase(dense=True, w=ph.w, self_weight=sw,
                                     rounds=()))
            continue
        n_ppermutes = sum(
            len({((dst // b) - (src // b)) % d for src, dst in pairs} - {0})
            for pairs, _ in ph.rounds)
        n_messages = sum(len(pairs) for pairs, _ in ph.rounds)
        budget = dense_threshold * (d - 1)
        sparse_wins = n_ppermutes < budget or (
            n_ppermutes <= budget and n_messages * 2 <= n * (n - 1))
        if d > 1 and not sparse_wins:
            phases.append(BlockPhase(dense=True, w=ph.w, self_weight=sw,
                                     rounds=()))
            continue
        rounds = []
        for pairs, recv_w in ph.rounds:
            groups: dict[int, dict[str, np.ndarray]] = {}
            for src, dst in pairs:
                o = ((dst // b) - (src // b)) % d
                g = groups.get(o)
                if g is None:
                    g = groups[o] = {
                        "src_local": np.tile(np.arange(b), (d, 1)),
                        "src_node": np.arange(n).reshape(d, b).copy(),
                        "recv_w": np.zeros((d, b)),
                    }
                g["src_local"][dst // b, dst % b] = src % b
                g["src_node"][dst // b, dst % b] = src
                g["recv_w"][dst // b, dst % b] = recv_w[dst]
            rounds.append(BlockRound(groups=tuple(
                BlockGroup(offset=o, **groups[o]) for o in sorted(groups))))
        phases.append(BlockPhase(dense=False, w=ph.w, self_weight=sw,
                                 rounds=tuple(rounds)))
    return BlockSchedule(name=schedule.name, n=n, d=d, b=b,
                         phases=tuple(phases))


def _dense_block_contract(w, x: jax.Array, *, axis_name: str, d: int, b: int,
                          mask=None) -> jax.Array:
    """``out_i = sum_j w[i, j] x_j`` for block-sharded ``x[b, ...]``: one
    all-gather of blocks, then the device's [b, n] row slab contracts the
    global [n, ...] stack.  With a fault ``mask`` the rows are renormalized
    onto the alive subgraph first (same math as :func:`mask_renormalize`,
    restricted to this device's rows)."""
    i = jax.lax.axis_index(axis_name)
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    n = d * b
    with jax.named_scope("tm/gossip/allgather"):
        g = jax.lax.all_gather(x, axis_name)            # [d, b, ...local]
    g = g.reshape((n,) + x.shape[1:])
    rows = jnp.asarray(w, cdt).reshape(d, b, n)[i]      # [b, n]
    if mask is not None:
        if isinstance(mask, BlockMask):
            m = jnp.asarray(mask.full(), cdt)
            m_loc = jnp.asarray(mask.local, cdt)
        else:
            m = jnp.asarray(mask, cdt)
            m_loc = jax.lax.dynamic_slice_in_dim(m, i * b, b, axis=0)
        eye = jnp.asarray(np.eye(n).reshape(d, b, n), cdt)[i]
        offd = rows * (m_loc[:, None] * m[None, :]) * (1.0 - eye)
        diag = m_loc * (1.0 - offd.sum(axis=-1)) + (1.0 - m_loc)
        rows = offd + eye * diag[:, None]
    out = jnp.einsum("bn,nf->bf", rows, g.reshape(n, -1).astype(cdt),
                     preferred_element_type=cdt)
    return out.astype(x.dtype).reshape(x.shape)


def _apply_block_phase_local(x: jax.Array, phase: BlockPhase, *,
                             axis_name: str, d: int, b: int,
                             mask=None) -> jax.Array:
    """One compiled phase on a local [b, ...] block inside shard_map.

    Sparse phases run each round's offset groups as whole-block ppermutes
    (offset 0 is the device-local group — no collective) with a per-slot
    gather + weight on the receiving side.  With a fault ``mask`` the edge
    weights become ``w_ij m_i m_j`` and each alive dst's self-weight absorbs
    its dead neighbours' mass (``+ sum_j w_ij (1 - m_j)``); dead nodes get
    an identity row — exactly :func:`mask_renormalize` evaluated edge-wise,
    so sparse and dense paths agree under faults.
    """
    if phase.dense:
        return _dense_block_contract(phase.w, x, axis_name=axis_name, d=d,
                                     b=b, mask=mask)
    i = jax.lax.axis_index(axis_name)
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    bshape = (b,) + (1,) * (x.ndim - 1)
    m_loc = mask_of = None
    if mask is not None:
        if isinstance(mask, BlockMask):
            # block-local: this device's rows plus on-demand peer rows —
            # never a materialized [n] mask
            m_loc = jnp.asarray(mask.local, cdt)
            mask_of = lambda ids: jnp.asarray(mask.of(ids), cdt)
        else:
            m = jnp.asarray(mask, cdt)
            m_loc = jax.lax.dynamic_slice_in_dim(m, i * b, b, axis=0)
            mask_of = lambda ids: m[ids]
    sw = jnp.asarray(phase.self_weight, cdt)[i]          # [b]
    if mask is not None:
        lost = jnp.zeros((b,), cdt)
        for rnd in phase.rounds:
            for grp in rnd.groups:
                w_g = jnp.asarray(grp.recv_w, cdt)[i]
                m_src = mask_of(jnp.asarray(grp.src_node)[i])
                lost = lost + w_g * (1.0 - m_src)
        sw = m_loc * (sw + lost) + (1.0 - m_loc)
    out = x.astype(cdt) * sw.reshape(bshape)
    for rnd in phase.rounds:
        acc = None
        for grp in rnd.groups:
            if grp.offset == 0:
                recv = x
            else:
                perm = [(j, (j + grp.offset) % d) for j in range(d)]
                with jax.named_scope("tm/gossip/ppermute"):
                    recv = jax.lax.ppermute(x, axis_name, perm=perm)
            w_g = jnp.asarray(grp.recv_w, cdt)[i]        # [b]
            if mask is not None:
                w_g = w_g * m_loc * mask_of(jnp.asarray(grp.src_node)[i])
            contrib = jnp.take(recv, jnp.asarray(grp.src_local)[i],
                               axis=0).astype(cdt) * w_g.reshape(bshape)
            acc = contrib if acc is None else acc + contrib
        out = out + acc
    return out.astype(x.dtype)


def apply_block_schedule_local(x: jax.Array, bsched: BlockSchedule,
                               t: jax.Array | int, *, axis_name: str,
                               mask=None) -> jax.Array:
    """Block-granular counterpart of :func:`apply_schedule_local` — one
    gossip round on a local ``[b, ...]`` block, caller already inside a
    manual region over ``axis_name``.  Phase selection rules are identical
    (static python ``t`` resolves now, a traced counter pays a
    ``lax.switch``); ``mask`` is an optional traced ``[n]`` alive mask
    applied via the edge-wise renormalization above."""
    n_phases = len(bsched.phases)
    kw = dict(axis_name=axis_name, d=bsched.d, b=bsched.b, mask=mask)
    if n_phases == 1:
        return _apply_block_phase_local(x, bsched.phases[0], **kw)
    if isinstance(t, int):
        return _apply_block_phase_local(x, bsched.phases[t % n_phases], **kw)
    branches = [functools.partial(_apply_block_phase_local, phase=ph, **kw)
                for ph in bsched.phases]
    return jax.lax.switch(t % n_phases, branches, x)


def mix_leaf_dense_block(w, x: jax.Array, *, axis_name: str, d: int, b: int,
                         mask=None) -> jax.Array:
    """Dense contraction of an EXPLICIT [n, n] matrix against block-sharded
    leaves — the block analogue of :func:`mix_leaf_dense_local`, for mix
    sites that pass a matrix other than the compiled topology W and for the
    forced-dense schedule."""
    return _dense_block_contract(w, x, axis_name=axis_name, d=d, b=b,
                                 mask=mask)


def make_block_mix_fn(bsched: BlockSchedule | None, *, axis_name: str,
                      w_ref, t: jax.Array | int = 0, d: int | None = None,
                      b: int | None = None, mask=None):
    """``mix_fn(w, tree)`` for callers inside a shard_map whose local leaves
    are ``[b, ...]`` node blocks — the hybrid runtime's counterpart of
    :func:`make_local_mix_fn`, same w-operand identity dispatch.  ``d``/``b``
    are only needed when ``bsched`` is None (forced-dense gossip)."""
    if bsched is not None:
        d, b = bsched.d, bsched.b
    if d is None or b is None:
        raise ValueError("make_block_mix_fn needs bsched= or explicit d=, b=")

    def mix_fn(w, tree):
        if bsched is None or w is not w_ref:
            return jax.tree.map(
                functools.partial(mix_leaf_dense_block, w,
                                  axis_name=axis_name, d=d, b=b, mask=mask),
                tree)
        return jax.tree.map(
            lambda x: apply_block_schedule_local(x, bsched, t,
                                                 axis_name=axis_name,
                                                 mask=mask), tree)

    return mix_fn

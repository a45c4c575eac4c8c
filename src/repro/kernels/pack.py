"""Packed flat-param layout + launch-size bucketing (DESIGN.md §14).

The fused chain segments (``core/transforms.chain_apply(fused=...)``) want a
whole pytree streamed through ONE ``pallas_call`` instead of one launch per
leaf per stage.  :func:`plan_pack` computes a static offset table from leaf
shapes; :func:`pack` flattens the node-local param/momentum/grad pytree into
one contiguous fp32 buffer per role; :func:`unpack` restores the tree.
Offsets/shapes are trace-time constants, so pack/unpack are pure
reshape+concatenate/slice — XLA fuses them around the kernel.

Two padding policies, both tracked by :func:`bucket_stats`:

* ``plan_pack`` pads the packed total to a multiple of the launch ``tile``
  (quantum padding — waste <= tile-1 elements on an arbitrarily large tree,
  so the roofline byte accounting stays honest);
* ``bucket_size`` is the policy for the per-leaf ``_flat_call``-style
  launchers in ``qg_update.py``/``compress.py``: pad to the next
  power-of-two tile multiple, so a heterogeneous pytree compiles O(log n)
  kernel variants instead of one per distinct leaf size (pad waste is
  capped at 2x below one tile, tile-count-pow2 above).

:func:`flat_call` is the shared 1D elementwise launcher built on these —
multiple outputs, optional traced scalar operands (lr is a traced value
inside the jitted step, so it rides as a [1] operand, not a static).
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PyTree = Any

log = logging.getLogger(__name__)

__all__ = [
    "PackSpec", "plan_pack", "pack", "unpack",
    "bucket_size", "bucket_stats", "reset_bucket_stats", "flat_call",
    "PACK_TILE",
]

#: pad quantum / launch tile for packed whole-tree buffers.  8Ki fp32 =
#: 32 KiB per operand block — small enough that quantum-padding waste is
#: < 1% beyond ~1M packed elements (the roofline gate depends on that),
#: large enough for the 8x128 VREG lane layout.
PACK_TILE = 8 * 1024


# ---------------------------------------------------------------------------
# launch-size bucketing (shared by the per-leaf kernel launchers)
# ---------------------------------------------------------------------------

_BUCKET_STATS: dict[int, dict] = {}


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _record_bucket(n: int, padded: int) -> None:
    st = _BUCKET_STATS.setdefault(padded, {"hits": 0, "max_waste": 0.0})
    st["hits"] += 1
    waste = (padded - n) / padded
    if waste > st["max_waste"]:
        st["max_waste"] = waste
    if st["hits"] == 1:
        log.debug("pallas launch bucket: n=%d -> padded=%d (waste %.1f%%)",
                  n, padded, 100.0 * waste)


def bucket_size(n: int, *, tile: int, floor: int) -> int:
    """Padded launch size for an ``n``-element flattened operand: the next
    power-of-two tile multiple (``floor``/``tile`` must be powers of two).

    Below one tile the buckets are powers of two in ``[floor, tile]``; above,
    a power-of-two number of tiles — so arbitrary leaf-size mixtures land in
    O(log n) distinct padded sizes (one compiled kernel variant each) and pad
    waste never exceeds 2x.  Every call is recorded in :func:`bucket_stats`.
    """
    n = max(int(n), 1)
    if n <= floor:
        padded = floor
    elif n <= tile:
        padded = _next_pow2(n)
    else:
        padded = tile * _next_pow2(-(-n // tile))
    _record_bucket(n, padded)
    return padded


def bucket_stats() -> dict[int, dict]:
    """``{padded_size: {"hits": int, "max_waste": float}}`` accumulated over
    every bucketed launch in this process (trace-time: retraces count, cached
    dispatches don't)."""
    return {k: dict(v) for k, v in sorted(_BUCKET_STATS.items())}


def reset_bucket_stats() -> None:
    _BUCKET_STATS.clear()


# ---------------------------------------------------------------------------
# packed flat-param layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static offset table for one pytree role (params / momentum / grads).

    Everything here is a trace-time constant — the same spec packs every
    role of the same structure (the fused segments rely on that: params,
    momentum and grads share one offset table)."""

    treedef: Any
    shapes: tuple
    dtypes: tuple
    offsets: tuple
    sizes: tuple
    total: int      # sum of leaf sizes
    padded: int     # quantum-padded buffer length (multiple of tile)
    tile: int

    @property
    def pad_waste(self) -> float:
        return (self.padded - self.total) / max(self.padded, 1)


def plan_pack(tree: PyTree, *, tile: int = PACK_TILE) -> PackSpec:
    """Offset table for ``tree`` (concrete arrays or ShapeDtypeStructs)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(math.prod(s)) for s in shapes)
    offsets, off = [], 0
    for s in sizes:
        offsets.append(off)
        off += s
    total = off
    padded = max(tile, -(-total // tile) * tile)
    _record_bucket(max(total, 1), padded)
    return PackSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=tuple(offsets), sizes=sizes, total=total,
                    padded=padded, tile=tile)


def pack(spec: PackSpec, tree: PyTree) -> jax.Array:
    """Flatten ``tree`` into one contiguous fp32 ``[spec.padded]`` buffer."""
    leaves = jax.tree.leaves(tree)
    if len(leaves) != len(spec.shapes):
        raise ValueError(f"pack: tree has {len(leaves)} leaves, spec expects "
                         f"{len(spec.shapes)}")
    flat = [l.reshape(-1).astype(jnp.float32) for l in leaves]
    buf = jnp.concatenate(flat) if flat else jnp.zeros((0,), jnp.float32)
    return jnp.pad(buf, (0, spec.padded - spec.total))


def unpack(spec: PackSpec, buf: jax.Array) -> PyTree:
    """Inverse of :func:`pack` (casts each leaf back to its spec dtype)."""
    leaves = [
        buf[o:o + n].reshape(shape).astype(dt)
        for o, n, shape, dt in zip(spec.offsets, spec.sizes, spec.shapes,
                                   spec.dtypes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# shared 1D elementwise launcher
# ---------------------------------------------------------------------------

def flat_call(kernel, args, *, name: str, n_out: int = 1, scalars=(),
              tile: int, floor: int, interpret: bool, bucket: bool = True):
    """Launch an elementwise kernel over 1D tiles of the flattened ``args``.

    ``scalars`` are traced per-launch values (lr, refresh gates) shipped as
    [1] fp32 operands with a broadcast BlockSpec — they cannot be statics
    because the jitted step traces them.  ``bucket=True`` pads to
    :func:`bucket_size`; ``bucket=False`` assumes the caller already padded
    to a tile multiple (the packed whole-tree path).  ``name`` is the
    kernel's name in compiled programs and device traces.  Returns a tuple
    of ``n_out`` outputs shaped like ``args[0]``.
    """
    flat = [a.reshape(-1) for a in args]
    n = flat[0].size
    if bucket:
        padded = bucket_size(n, tile=tile, floor=floor)
    else:
        padded = max(tile, -(-n // tile) * tile)
    blk = min(tile, padded)
    if padded != n:
        flat = [jnp.pad(f, (0, padded - n)) for f in flat]
    grid = (padded // blk,)
    spec = pl.BlockSpec((blk,), lambda i: (i,))
    sspec = pl.BlockSpec((1,), lambda i: (0,))
    out_shape = tuple(jax.ShapeDtypeStruct(flat[0].shape, flat[0].dtype)
                      for _ in range(n_out))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(flat) + [sspec] * len(scalars),
        out_specs=tuple(spec for _ in range(n_out)),
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(*flat, *[jnp.asarray(s, jnp.float32).reshape(1) for s in scalars])
    outs = tuple(o[:n].reshape(args[0].shape) for o in outs)
    return outs if n_out > 1 else outs[0]

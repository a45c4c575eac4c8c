"""Fused compression kernels for the comm subsystem — Pallas TPU.

Compressed gossip (comm/choco.py) runs every step over every parameter, so
like the QG update it is an HBM-bandwidth-bound streaming pass.  Unfused,
mask-apply / quantize and the residual each re-read the tensor; these kernels
stream each [node, feature] message tile through VMEM exactly once and emit
both the compressed value and the residual in the same pass:

  * ``threshold_mask``       q = x * [|x| >= thr_row],  r = x - q
    (the top-k hot path: the per-row k-th-magnitude threshold is a tiny
    [rows] reduction done outside; the O(d) mask+residual is the fused part)
  * ``quantize_dequantize``  QSGD stochastic quantize->dequantize + residual,
    q = sign(x) * scale * min(floor(|x|/scale*L + u), L) / L
  * ``gamma_correct``        the post-exchange wire-boundary fusion
    (DESIGN.md §14): the CHOCO/EF decompress  out = x + gamma*(mixed -
    anchor)  in one pass instead of the three-read tree.map re-read —
    ``comm/choco.mix_site`` packs the whole tree (``kernels/pack.py``) and
    calls it ONCE per mix site

Grid layout: (row-blocks, feature-tiles) over VMEM blocks of the flattened
per-node message.  A row block is 8 rows (the sublane count of a TPU vreg;
fewer rows form one whole-array block), and per-row scalars (threshold /
scale) ride as a [rows, 1] column in matching [8, 1] blocks.  Feature-tile
padding is bucketed to power-of-two tile multiples (``pack.bucket_size``)
so heterogeneous message widths compile O(log n) variants.  Oracles:
``ref.threshold_mask_ref`` / ``ref.quantize_dequantize_ref`` /
``ref.gamma_correct_ref``; parity is pinned in tests/test_comm.py and
tests/test_kernels.py, including non-tile-multiple shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pack as _pack

TILE = 16 * 1024  # fp32 lanes per block row: 512 KiB per [8, TILE] operand
_FLOOR = 128
_ROWS = 8         # sublanes per vreg: the row-block height the TPU requires

_TINY = 1e-12


def _threshold_mask_kernel(x_ref, thr_ref, q_ref, r_ref):
    x = x_ref[...]
    thr = thr_ref[...]                    # [rows, 1], broadcast per row
    q = jnp.where(jnp.abs(x) >= thr, x, 0.0)
    q_ref[...] = q
    r_ref[...] = x - q


def _qdq_kernel(x_ref, s_ref, u_ref, q_ref, r_ref, *, levels):
    x = x_ref[...]
    s = jnp.maximum(s_ref[...], _TINY)
    y = jnp.abs(x) * (levels / s)
    xi = jnp.minimum(jnp.floor(y + u_ref[...]), levels)
    q = jnp.sign(x) * xi * (s / levels)
    q_ref[...] = q
    r_ref[...] = x - q


def _rowwise_call(kernel, x2d, row_scalars, extras, *, name, interpret):
    """Launch over (row-blocks, feature-tiles); ``row_scalars`` are [rows]
    values broadcast per row, ``extras`` are [rows, f] element-wise
    operands."""
    rows, f = x2d.shape
    padded_f = _pack.bucket_size(f, tile=TILE, floor=_FLOOR)
    tile = min(TILE, padded_f)
    rb = rows if rows <= _ROWS else _ROWS
    pad_r = -rows % rb
    full = [x2d.astype(jnp.float32)] + [e.astype(jnp.float32) for e in extras]
    full = [jnp.pad(a, ((0, pad_r), (0, padded_f - f))) for a in full]
    scal = [jnp.pad(s.reshape(rows, 1).astype(jnp.float32),
                    ((0, pad_r), (0, 0))) for s in row_scalars]

    grid = ((rows + pad_r) // rb, padded_f // tile)
    full_spec = pl.BlockSpec((rb, tile), lambda i, j: (i, j))
    scal_spec = pl.BlockSpec((rb, 1), lambda i, j: (i, 0))
    out_shape = jax.ShapeDtypeStruct(full[0].shape, jnp.float32)
    # operand order: x, row-scalars, element-wise extras
    q, r = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[full_spec] + [scal_spec] * len(scal)
                 + [full_spec] * len(extras),
        out_specs=(full_spec, full_spec),
        out_shape=(out_shape, out_shape),
        interpret=interpret,
        name=name,
    )(full[0], *scal, *full[1:])
    return q[:rows, :f], r[:rows, :f]


@functools.partial(jax.jit, static_argnames=("interpret",))
def threshold_mask(x2d, thr, *, interpret: bool = True):
    """Fused magnitude-threshold sparsification.  x2d [rows, f]; thr [rows]
    (k-th largest |x| per row).  Returns (kept, residual), fp32."""
    return _rowwise_call(_threshold_mask_kernel, x2d, [thr], [],
                         name="threshold_mask", interpret=interpret)


@functools.partial(jax.jit, static_argnames=("levels", "interpret"))
def quantize_dequantize(x2d, scale, u, *, levels: int,
                        interpret: bool = True):
    """Fused QSGD stochastic quantize->dequantize.  x2d [rows, f];
    scale [rows] (max |x| per row); u [rows, f] uniform in [0, 1).
    Returns (dequantized, residual), fp32."""
    kernel = functools.partial(_qdq_kernel, levels=levels)
    return _rowwise_call(kernel, x2d, [scale], [u],
                         name="quantize_dequantize", interpret=interpret)


def _gamma_correct_kernel(x_ref, mx_ref, h_ref, o_ref, *, gamma):
    o_ref[...] = x_ref[...] + gamma * (mx_ref[...] - h_ref[...])


@functools.partial(jax.jit, static_argnames=("gamma", "interpret"))
def gamma_correct(x, mixed, anchor, *, gamma: float, interpret: bool = True):
    """Fused CHOCO/EF post-exchange correction in one VMEM pass:
    ``out = x + gamma * (mixed - anchor)``.  Unfused this is a three-read
    tree.map over every leaf; packed (see ``kernels/pack.py``) it streams
    the whole tree once.  ``gamma`` is the resolved consensus step size —
    a static, it never changes within a run."""
    kernel = functools.partial(_gamma_correct_kernel, gamma=gamma)
    return _pack.flat_call(kernel, (x, mixed, anchor), name="gamma_correct",
                           tile=TILE, floor=_FLOOR, interpret=interpret)

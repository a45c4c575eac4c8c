"""EvoNorm-S0 over a node block in the channel-major layout — Pallas TPU
kernels.

``y = x * sigmoid(v x) / std * scale + bias``, ``std`` the standard
deviation of ``x`` over one group's channels and one image's pixels (the
statistics of ``resnet._apply_norm('evonorm')``).  Activations are
``x[n, C, B*H*W]`` as in ``node_conv``; a tile holds whole images, so each
image's statistics are reduced in VMEM: first over the group's channel
rows (sublanes), then over the image's lanes, picked out by an iota mask.
The two-pass variance (mean, then mean squared deviation) is the oracle's.

  * ``node_evonorm_fwd``  ``y`` from ``x`` and the per-channel ``v``,
    ``scale``, ``bias``;
  * ``node_evonorm_bwd``  ``dx`` and the per-channel ``dv``, ``dscale``,
    ``dbias`` (accumulated over a node's tiles), recomputing the statistics
    from ``x`` so that the backward pass keeps only ``x``.

XLA reaches the same statistics only through a reshape of ``B*H*W`` into
``(B, H*W)``, which changes the tiled layout and costs a relayout copy of
the activation in each direction of every norm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import node_conv as _nc
from . import ref as _ref

# f32 arrays of a tile's size live in VMEM at once, for the tile chooser
LIVE_TILES = 12


def _per_image(row, img, images):
    """``row[1, Mt]`` -> each image's sum, broadcast over its lanes.  A
    masked reduction per image: products with a 0/1 ``[images, Mt]``
    segment matrix on the MXU (``HIGHEST``) instead measured slower on a
    v5e, 0.26 against 0.14 ms a forward call at 16 nodes x 32 images of
    16 channels at 32x32 (PERF.md §6)."""
    out = jnp.zeros_like(row)
    for j in range(images):
        sel = img == j
        s = jnp.sum(jnp.where(sel, row, 0.0), axis=1, keepdims=True)
        out = jnp.where(sel, s, out)
    return out


def _stats(xg, img, images, count, eps):
    """Mean and standard deviation rows ``[1, Mt]`` of one channel group."""
    mean = _per_image(jnp.sum(xg, axis=0, keepdims=True), img, images) / count
    d = xg - mean
    var = _per_image(jnp.sum(d * d, axis=0, keepdims=True), img,
                     images) / count
    return d, jnp.sqrt(var + eps)


def _fwd_kernel(x_ref, v_ref, s_ref, b_ref, o_ref, *, hw, groups, eps):
    x = x_ref[0]
    c, mt = x.shape
    cg, images = c // groups, mt // hw
    img = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (1, mt), 1), hw)
    for g in range(groups):
        rows = slice(g * cg, (g + 1) * cg)
        xg = x[rows]
        _, std = _stats(xg, img, images, cg * hw, eps)
        num = xg * jax.nn.sigmoid(v_ref[0, rows] * xg)
        o_ref[0, rows, :] = num / std * s_ref[0, rows] + b_ref[0, rows]


def _bwd_kernel(x_ref, g_ref, v_ref, s_ref, dx_ref, dv_ref, ds_ref, db_ref,
                *, hw, groups, eps):
    x, gy = x_ref[0], g_ref[0]
    c, mt = x.shape
    cg, images = c // groups, mt // hw
    count = cg * hw
    img = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (1, mt), 1), hw)
    parts = []
    for g in range(groups):
        rows = slice(g * cg, (g + 1) * cg)
        xg, gg, vg = x[rows], gy[rows], v_ref[0, rows]
        d, std = _stats(xg, img, images, count, eps)
        r = 1.0 / std
        sig = jax.nn.sigmoid(vg * xg)
        num = xg * sig
        gn = gg * s_ref[0, rows]                       # d loss / d (num/std)
        dstd = -_per_image(jnp.sum(gn * num, axis=0, keepdims=True), img,
                           images) * r * r
        dnum = gn * r
        dsig = sig * (1.0 - sig)
        dx_ref[0, rows, :] = (dnum * (sig + vg * xg * dsig)
                              + dstd * r * d / count)
        parts.append((jnp.sum(dnum * xg * xg * dsig, axis=1, keepdims=True),
                      jnp.sum(gg * num * r, axis=1, keepdims=True),
                      jnp.sum(gg, axis=1, keepdims=True)))
    dv, ds, db = (jnp.concatenate(p, axis=0) for p in zip(*parts))

    @pl.when(pl.program_id(1) == 0)
    def _():
        dv_ref[0], ds_ref[0], db_ref[0] = dv, ds, db

    @pl.when(pl.program_id(1) > 0)
    def _():
        dv_ref[0] += dv
        ds_ref[0] += ds
        db_ref[0] += db


def _tile(x, hw, images):
    n, c, m = x.shape
    batch = m // hw
    t = (_nc.images_per_tile(batch, hw, LIVE_TILES * c) if images is None
         else images)
    if batch % t:
        raise ValueError(f"node_evonorm: {t} images per tile do not divide "
                         f"the batch of {batch}")
    return t * hw


def _col(p):
    return p.reshape(p.shape + (1,))


@functools.partial(jax.jit, static_argnames=(
    "hw", "groups", "eps", "images", "interpret"))
def evonorm_fwd(x, v, scale, bias, *, hw: int, groups: int = 2,
                eps: float = 1e-5, images: int | None = None,
                interpret: bool = True):
    """``x[n, C, B*hw]``, per-channel ``v, scale, bias[n, C]`` -> ``y``."""
    n, c, m = x.shape
    mt = _tile(x, hw, images)
    tile = pl.BlockSpec((1, c, mt), lambda b, j: (b, 0, j))
    chan = pl.BlockSpec((1, c, 1), lambda b, j: (b, 0, 0))
    kernel = functools.partial(_fwd_kernel, hw=hw, groups=groups, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(n, m // mt),
        in_specs=[tile, chan, chan, chan],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n, c, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_nc.VMEM_LIMIT),
        interpret=interpret,
        name="node_evonorm_fwd",
    )(x, _col(v), _col(scale), _col(bias))


@functools.partial(jax.jit, static_argnames=(
    "hw", "groups", "eps", "images", "interpret"))
def evonorm_bwd(x, gy, v, scale, *, hw: int, groups: int = 2,
                eps: float = 1e-5, images: int | None = None,
                interpret: bool = True):
    """``(dx[n, C, M], dv[n, C], dscale[n, C], dbias[n, C])``."""
    n, c, m = x.shape
    mt = _tile(x, hw, images)
    tile = pl.BlockSpec((1, c, mt), lambda b, j: (b, 0, j))
    chan = pl.BlockSpec((1, c, 1), lambda b, j: (b, 0, 0))
    per_chan = jax.ShapeDtypeStruct((n, c, 1), jnp.float32)
    kernel = functools.partial(_bwd_kernel, hw=hw, groups=groups, eps=eps)
    dx, dv, ds, db = pl.pallas_call(
        kernel,
        grid=(n, m // mt),
        in_specs=[tile, tile, chan, chan],
        out_specs=[tile, chan, chan, chan],
        out_shape=[jax.ShapeDtypeStruct((n, c, m), jnp.float32), per_chan,
                   per_chan, per_chan],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_nc.VMEM_LIMIT),
        interpret=interpret,
        name="node_evonorm_bwd",
    )(x, gy, _col(v), _col(scale))
    return dx, dv[..., 0], ds[..., 0], db[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def evonorm(x, v, scale, bias, hw, interpret):
    """EvoNorm-S0 (2 groups, eps 1e-5) of ``x[n, C, B*hw]`` through the
    kernels; the backward pass keeps ``x`` and the per-channel weights."""
    return evonorm_fwd(x, v, scale, bias, hw=hw, interpret=interpret)


def _evonorm_fwd(x, v, scale, bias, hw, interpret):
    return evonorm(x, v, scale, bias, hw, interpret), (x, v, scale)


def _evonorm_bwd(hw, interpret, res, gy):
    x, v, scale = res
    return evonorm_bwd(x, gy, v, scale, hw=hw, interpret=interpret)


evonorm.defvjp(_evonorm_fwd, _evonorm_bwd)


def node_evonorm(x, v, scale, bias, *, hw: int, impl: str = "pallas",
                 interpret: bool = False):
    """The model entry: the kernels (``impl='pallas'``) or the jnp oracle
    under plain autodiff (``impl='ref'``)."""
    if impl == "ref":
        return _ref.node_evonorm_ref(x, v, scale, bias, hw=hw)
    if impl != "pallas":
        raise ValueError(f"node_evonorm: impl {impl!r} ('pallas' | 'ref')")
    return evonorm(x, v, scale, bias, hw, interpret)

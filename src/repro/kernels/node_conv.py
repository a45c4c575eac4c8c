"""Node-batched 2-D convolutions in a channel-major layout — Pallas TPU
kernels.

Decentralized training runs one small CNN per node.  ``jax.vmap`` of a
per-node ``lax.conv`` turns the node axis into a third spatial dimension of
one convolution, and XLA lays its activations out with the image batch on
the 128-wide lane axis, moving them in and out of that layout around every
convolution.  These kernels keep a node block's activations as
``x[n, C, M]`` with ``M = B*H*W`` (image-major, then rows, then columns):
``M`` is lane-dense at every ResNet stage, ``C`` fills the sublanes.

A 3x3 'SAME' convolution at stride 1 is then nine shifted copies of the
input contracted with the taps.  Tap ``t = (dh, dw)`` reads
``x[i, m + dh*W + dw]``, zero where the row or column leaves the image; a
tile of ``M`` holds whole images, so the shifts need no halo.  Each kernel
builds the nine shifts in VMEM (``pltpu.roll`` and iota masks), stacks them
along the contraction (``K = taps * I``) and runs one MXU matmul per tile:

  * ``node_conv_fwd``  ``y[n, O, M] = A[n, O, K] @ stack(x)[n, K, M]``;
  * ``node_conv_dx``   the same form on the output gradient, with the taps
    flipped and the weights transposed (:func:`dx_weights`);
  * ``node_conv_dw``   ``dA[n, O, K] = g[n, O, M] @ stack(x)[n, K, M]^T``,
    accumulated over the ``M`` tiles of a node.

Stride 2 ('SAME', on even sizes only) keeps every second row and column:
the odd ones for a 3x3 window (its centre), the even ones for a 1x1.  On
the lanes that is a compaction, which the kernels do on the MXU with a 0/1
selection matrix per group of images (:func:`selection`), applied where
the values are already rounded to ``mxu_dtype`` and so exactly: the
forward kernel selects the stacked taps' columns before its matmul
(``resample='down'``, a quarter of the stride-1 work); the input- and
weight-gradient kernels spread the output gradient back over the
full-resolution lanes (``resample='up'``) before theirs.

Operands reach the MXU in ``mxu_dtype``: bfloat16 on a TPU at JAX's
default matmul precision (the single bf16 pass with f32 accumulation that
a float32 ``lax.conv`` gets there), float32 at ``Precision.HIGHEST`` where
the caller asks for more (``ops.node_mxu_dtype``); inputs, outputs and
accumulation are float32.

:func:`node_conv` wraps the family in one ``custom_vjp`` (the backward pass
keeps only ``x`` and the weights); :func:`conv2d` adds the channel padding
the ResNet-20 stem needs.  ``impl='ref'`` runs the
same math through the jnp oracles in ``ref.py`` (the path off a TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref

# the nine taps of a 3x3 window, row-major: the order of HWIO's (kh, kw)
TAPS3 = tuple((dh, dw) for dh in (-1, 0, 1) for dw in (-1, 0, 1))

# bytes of VMEM one tile's stacked taps may take (f32); sets images per tile
STACK_BYTES = 8 * 1024 * 1024
VMEM_LIMIT = 64 * 1024 * 1024
CHANNEL_QUANTUM = 8       # input channels are padded to this many sublanes


def taps(ksize: int):
    if ksize == 1:
        return ((0, 0),)
    if ksize == 3:
        return TAPS3
    raise ValueError(f"node_conv: kernel size {ksize} (1 or 3)")


def images_per_tile(batch: int, hw: int, k: int, budget: int = STACK_BYTES,
                    multiple: int = 1) -> int:
    """The most images a tile holds: a divisor of ``batch`` and a multiple
    of ``multiple`` whose ``K x images*hw`` float32 tap stack fits
    ``budget``, with a lane-aligned tile (a multiple of 128 lanes) unless
    the tile is the whole node."""
    best = batch
    for t in range(batch, 0, -1):
        if batch % t or (t % multiple and t < batch):
            continue
        mt = t * hw
        if t < batch and mt % 128:
            continue
        best = t
        if 4 * k * mt <= budget:
            return t
    return best


def selection_group(hw: int) -> int:
    """Images per selection matrix: the fewest whose stride-2 output
    (``hw/4`` lanes each) fills whole 128-lane rows."""
    return 128 // math.gcd(hw // 4, 128)


def selection(height: int, width: int, offset: int, q: int,
              dtype=jnp.bfloat16):
    """``[q*H*W, q*H*W/4]`` 0/1 matrix for ``q`` images: column
    ``(image, i, j)`` picks row ``(image, 2i + offset, 2j + offset)``."""
    hw, ho, wo = height * width, height // 2, width // 2
    cols = np.arange(q * ho * wo)
    img, r = cols // (ho * wo), cols % (ho * wo)
    src = img * hw + (2 * (r // wo) + offset) * width + 2 * (r % wo) + offset
    return jnp.asarray(np.arange(q * hw)[:, None] == src[None, :], dtype)


def _stack_taps(x, *, height, width, ksize):
    """``x[I, Mt]`` (whole images) -> ``[taps*I, Mt]``: row ``t*I + i`` is
    ``x[i, m + dh*W + dw]``, zero where the tap leaves the image."""
    if ksize == 1:
        return x
    mt = x.shape[-1]
    m = jax.lax.broadcasted_iota(jnp.int32, (1, mt), 1)
    col = jax.lax.rem(m, width)
    row = jax.lax.rem(jax.lax.div(m, width), height)
    pieces = []
    for dh, dw in TAPS3:
        s = dh * width + dw
        xs = x if s == 0 else pltpu.roll(x, (-s) % mt, axis=1)
        valid = None
        if dh:
            valid = (row >= 1) if dh < 0 else (row < height - 1)
        if dw:
            v = (col >= 1) if dw < 0 else (col < width - 1)
            valid = v if valid is None else valid & v
        pieces.append(xs if valid is None else jnp.where(valid, xs, 0.0))
    return jnp.concatenate(pieces, axis=0)


def _precision(mxu_dtype):
    return (jax.lax.Precision.HIGHEST if jnp.dtype(mxu_dtype) == jnp.float32
            else None)


def _upsample(v, sel, mxu_dtype):
    """``v[R, Mt/4]`` -> ``[R, Mt]``: each value back at the lane it was
    selected from, zero elsewhere (values rounded to ``mxu_dtype``)."""
    qi, qo = sel.shape
    return jnp.concatenate([jax.lax.dot_general(
        v[:, g * qo:(g + 1) * qo].astype(mxu_dtype), sel,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_precision(mxu_dtype)) for g in range(v.shape[1] // qo)],
        axis=1)


def _taps_kernel(a_ref, x_ref, *refs, height, width, ksize, mxu_dtype,
                 resample):
    o_ref = refs[-1]
    x = x_ref[0]
    if resample == "up":
        x = _upsample(x, refs[0][...], mxu_dtype)
    cols = _stack_taps(x, height=height, width=width,
                       ksize=ksize).astype(mxu_dtype)
    a = a_ref[0].astype(mxu_dtype)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_precision(mxu_dtype))
    if resample != "down":
        o_ref[0] = dot(a, cols)
        return
    sel = refs[0][...]
    qi, qo = sel.shape
    for g in range(cols.shape[1] // qi):
        picked = dot(cols[:, g * qi:(g + 1) * qi], sel).astype(mxu_dtype)
        o_ref[0, :, g * qo:(g + 1) * qo] = dot(a, picked)


def _dw_kernel(x_ref, g_ref, *refs, height, width, ksize, mxu_dtype,
               resample):
    o_ref = refs[-1]
    cols = _stack_taps(x_ref[0], height=height, width=width, ksize=ksize)
    g = g_ref[0]
    if resample == "up":
        g = _upsample(g, refs[0][...], mxu_dtype)
    part = jax.lax.dot_general(
        g.astype(mxu_dtype), cols.astype(mxu_dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_precision(mxu_dtype))

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[0] = part

    @pl.when(pl.program_id(1) > 0)
    def _():
        o_ref[0] += part


def _tile(batch, height, width, k, images, resample):
    """Lanes a tile holds, and images a selection matrix covers: a
    :func:`selection_group`, or the whole tile where that is the node."""
    hw = height * width
    q = selection_group(hw) if resample else 1
    t = (images_per_tile(batch, hw, k, multiple=q) if images is None
         else images)
    if t % q and t == batch:
        q = t
    if batch % t or t % q:
        raise ValueError(f"node_conv: {t} images per tile do not divide "
                         f"the batch of {batch} into groups of {q}")
    return t * hw, q


def _resample_spec(height, width, offset, q, resample, mxu_dtype):
    """The selection matrix and its BlockSpec (one block, fetched once)."""
    if resample is None:
        return [], []
    if resample not in ("down", "up"):
        raise ValueError(f"node_conv: resample {resample!r}")
    sel = selection(height, width, offset, q, mxu_dtype)
    return [sel], [pl.BlockSpec(sel.shape, lambda b, j: (0, 0))]


def _compiler_params(last: str):
    return pltpu.CompilerParams(dimension_semantics=("parallel", last),
                                vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=(
    "height", "width", "ksize", "mxu_dtype", "resample", "offset", "images",
    "name", "interpret"))
def conv_taps(a, x, *, height: int, width: int, ksize: int,
              mxu_dtype=jnp.bfloat16, resample: str | None = None,
              offset: int = 0, images: int | None = None,
              name: str = "node_conv_fwd", interpret: bool = True):
    """``y[n, O, M] = a[n, O, taps*I] @ stack(x[n, I, M])``: the forward
    convolution (``name='node_conv_fwd'``) or, on the output gradient with
    :func:`dx_weights`, the input gradient (``name='node_conv_dx'``).
    ``height, width`` are the full-resolution image's; ``resample='down'``
    keeps the output's every second row and column from ``offset``,
    ``resample='up'`` takes ``x`` at that resolution."""
    n, o, k = a.shape
    _, i, m = x.shape
    if k != len(taps(ksize)) * i:
        raise ValueError(f"node_conv: weights {a.shape} for input {x.shape}")
    m_full = 4 * m if resample == "up" else m
    mt, q = _tile(m_full // (height * width), height, width, k, images,
                  resample)
    mt_in, mt_out, m_out = mt, mt, m_full
    if resample == "up":
        mt_in = mt // 4
    elif resample == "down":
        mt_out, m_out = mt // 4, m_full // 4
    sel, sel_spec = _resample_spec(height, width, offset, q, resample,
                                   mxu_dtype)
    kernel = functools.partial(_taps_kernel, height=height, width=width,
                               ksize=ksize, mxu_dtype=mxu_dtype,
                               resample=resample)
    return pl.pallas_call(
        kernel,
        grid=(n, m_full // mt),
        in_specs=[pl.BlockSpec((1, o, k), lambda b, j: (b, 0, 0)),
                  pl.BlockSpec((1, i, mt_in), lambda b, j: (b, 0, j)),
                  *sel_spec],
        out_specs=pl.BlockSpec((1, o, mt_out), lambda b, j: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, o, m_out), jnp.float32),
        compiler_params=_compiler_params("parallel"),
        interpret=interpret,
        name=name,
    )(a, x, *sel)


@functools.partial(jax.jit, static_argnames=(
    "height", "width", "ksize", "mxu_dtype", "resample", "offset", "images",
    "interpret"))
def conv_taps_dw(x, g, *, height: int, width: int, ksize: int,
                 mxu_dtype=jnp.bfloat16, resample: str | None = None,
                 offset: int = 0, images: int | None = None,
                 interpret: bool = True):
    """Weight gradient ``dA[n, O, taps*I] = g[n, O, M] @ stack(x)^T``, in the
    forward weights' layout, accumulated in float32 over a node's tiles;
    ``resample='up'`` takes ``g`` at the stride-2 output's resolution."""
    n, i, m = x.shape
    o = g.shape[1]
    k = len(taps(ksize)) * i
    mt, q = _tile(m // (height * width), height, width, k, images,
                  resample)
    mt_g = mt // 4 if resample == "up" else mt
    sel, sel_spec = _resample_spec(height, width, offset, q, resample,
                                   mxu_dtype)
    kernel = functools.partial(_dw_kernel, height=height, width=width,
                               ksize=ksize, mxu_dtype=mxu_dtype,
                               resample=resample)
    return pl.pallas_call(
        kernel,
        grid=(n, m // mt),
        in_specs=[pl.BlockSpec((1, i, mt), lambda b, j: (b, 0, j)),
                  pl.BlockSpec((1, o, mt_g), lambda b, j: (b, 0, j)),
                  *sel_spec],
        out_specs=pl.BlockSpec((1, o, k), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, o, k), jnp.float32),
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
        name="node_conv_dw",
    )(x, g, *sel)


# ---------------------------------------------------------------------------
# weight layouts: per-node HWIO <-> the kernels' [n, O, taps*I]
# ---------------------------------------------------------------------------

def fwd_weights(w):
    """``w[n, kh, kw, I, O]`` -> ``A[n, O, taps*I]`` (column ``t*I + i``)."""
    n, kh, kw, i, o = w.shape
    return jnp.swapaxes(w.reshape(n, kh * kw * i, o), 1, 2)


def dx_weights(w):
    """``w[n, kh, kw, I, O]`` -> ``A[n, I, taps*O]``: flipped taps, weights
    transposed, so that ``conv_taps`` of the output gradient is the input
    gradient."""
    n, kh, kw, i, o = w.shape
    wf = w[:, ::-1, ::-1]
    return jnp.transpose(wf.reshape(n, kh * kw, i, o),
                         (0, 2, 1, 3)).reshape(n, i, kh * kw * o)


def hwio_weights(a, ksize: int):
    """Inverse of :func:`fwd_weights`."""
    n, o, k = a.shape
    return jnp.swapaxes(a, 1, 2).reshape(n, ksize, ksize, k // ksize ** 2, o)


# ---------------------------------------------------------------------------
# the differentiable convolution
# ---------------------------------------------------------------------------

def _impl_fns(impl: str, interpret: bool):
    if impl == "pallas":
        fwd = functools.partial(conv_taps, interpret=interpret)
        dw = functools.partial(conv_taps_dw, interpret=interpret)
        dx = functools.partial(conv_taps, name="node_conv_dx",
                               interpret=interpret)
        return fwd, dx, dw
    if impl == "ref":
        return (_ref.node_conv_taps_ref, _ref.node_conv_taps_ref,
                _ref.node_conv_dw_ref)
    raise ValueError(f"node_conv: impl {impl!r} ('pallas' | 'ref')")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def node_conv(x, w, height, width, stride, impl, mxu_dtype, interpret):
    """'SAME' convolution of every node's images with its own weights at
    stride 1 or 2: ``x[n, I, B*H*W]``, ``w[n, k, k, I, O]`` (k = 1 or 3)
    -> ``[n, O, B*H'*W']``."""
    fwd, _, _ = _impl_fns(impl, interpret)
    return fwd(fwd_weights(w), x, height=height, width=width,
               ksize=w.shape[1], mxu_dtype=mxu_dtype,
               **_resample(stride, w.shape[1], "down"))


def _resample(stride, ksize, kind):
    """Stride 2 ('SAME' on even sizes): the odd rows and columns for a 3x3
    window (its centre), the even ones for a 1x1."""
    if stride == 1:
        return {}
    if stride != 2:
        raise ValueError(f"node_conv: stride {stride} (1 or 2)")
    return {"resample": kind, "offset": 1 if ksize == 3 else 0}


def _node_conv_fwd(x, w, height, width, stride, impl, mxu_dtype, interpret):
    return node_conv(x, w, height, width, stride, impl, mxu_dtype,
                     interpret), (x, w)


def _node_conv_bwd(height, width, stride, impl, mxu_dtype, interpret, res,
                   g):
    x, w = res
    _, dx, dw = _impl_fns(impl, interpret)
    k = w.shape[1]
    up = _resample(stride, k, "up")
    gx = dx(dx_weights(w), g, height=height, width=width, ksize=k,
            mxu_dtype=mxu_dtype, **up)
    ga = dw(x, g, height=height, width=width, ksize=k, mxu_dtype=mxu_dtype,
            **up)
    return gx, hwio_weights(ga, k)


node_conv.defvjp(_node_conv_fwd, _node_conv_bwd)


def conv2d(x, w, *, height: int, width: int, stride: int = 1,
           impl: str = "pallas", mxu_dtype=jnp.bfloat16,
           interpret: bool = False):
    """``lax.conv_general_dilated(..., (stride, stride), 'SAME')`` of each
    node's images with its own weights, in the channel-major layout:
    ``x[n, I, B*H*W]``, ``w[n, k, k, I, O]`` -> ``[n, O, B*H'*W']``.  Input
    channels are padded with zeros to a multiple of
    :data:`CHANNEL_QUANTUM` sublanes.  Stride 2 takes even sizes only."""
    if stride == 2 and (height % 2 or width % 2):
        raise ValueError(f"node_conv: stride 2 on a {height}x{width} image "
                         "(even sizes only)")
    i = x.shape[1]
    pad = -i % CHANNEL_QUANTUM
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    return node_conv(x, w, height, width, stride, impl, jnp.dtype(mxu_dtype),
                     interpret)

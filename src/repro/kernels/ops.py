"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True unless a TPU backend is present — on this CPU
container the kernels execute their Python bodies via the Pallas interpreter
(the sanctioned validation mode); on TPU they compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import compress as _cmp
from . import flash_attention as _fa
from . import node_conv as _nc
from . import node_norm as _nn
from . import qg_update as _qg
from . import ssd_scan as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def qg_local_step(x, m_hat, g, *, eta, beta, nesterov=False, interpret=None):
    return _qg.qg_local_step(
        x, m_hat, g, eta=eta, beta=beta, nesterov=nesterov,
        interpret=_default_interpret() if interpret is None else interpret)


def qg_buffer_update(x_old, x_new, m_hat, *, eta, mu, interpret=None):
    return _qg.qg_buffer_update(
        x_old, x_new, m_hat, eta=eta, mu=mu,
        interpret=_default_interpret() if interpret is None else interpret)


def fused_halfstep(x, m, g, eta, *, beta, wd=0.0, nesterov=False,
                   emit_m=True, interpret=None):
    return _qg.fused_halfstep(
        x, m, g, eta, beta=beta, wd=wd, nesterov=nesterov, emit_m=emit_m,
        interpret=_default_interpret() if interpret is None else interpret)


def fused_qg_buffer(x_pre, x_post, m_hat, eta, refresh, *, mu,
                    interpret=None):
    return _qg.fused_qg_buffer(
        x_pre, x_post, m_hat, eta, refresh, mu=mu,
        interpret=_default_interpret() if interpret is None else interpret)


def gamma_correct(x, mixed, anchor, *, gamma, interpret=None):
    return _cmp.gamma_correct(
        x, mixed, anchor, gamma=gamma,
        interpret=_default_interpret() if interpret is None else interpret)


def threshold_mask(x2d, thr, *, interpret=None):
    return _cmp.threshold_mask(
        x2d, thr,
        interpret=_default_interpret() if interpret is None else interpret)


def quantize_dequantize(x2d, scale, u, *, levels, interpret=None):
    return _cmp.quantize_dequantize(
        x2d, scale, u, levels=levels,
        interpret=_default_interpret() if interpret is None else interpret)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=None):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k,
        interpret=_default_interpret() if interpret is None else interpret)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           window=0, softcap=0.0, interpret=None):
    return _fa.paged_decode_attention(
        q, k_pages, v_pages, block_tables, lengths,
        window=window, softcap=softcap,
        interpret=_default_interpret() if interpret is None else interpret)


def node_kernels_native() -> bool:
    """Whether the node-batched kernels run natively here (on a TPU).
    Elsewhere their jnp oracle runs slower than ``jax.vmap`` of the
    per-node model, so the models offer the node-batched path only where
    this holds (DESIGN.md §15)."""
    return not _default_interpret()


def _node_impl(impl):
    """The node-batched kernels on a TPU, their jnp oracles elsewhere."""
    return ("ref" if _default_interpret() else "pallas") if impl is None \
        else impl


def node_mxu_dtype():
    """The MXU operand dtype of the node-batched convolution on a TPU: one
    bfloat16 pass at JAX's default matmul precision, as a float32
    ``lax.conv`` gets there; float32 at ``Precision.HIGHEST`` whenever
    ``jax.default_matmul_precision`` asks for more than that."""
    asked = jax.config.jax_default_matmul_precision
    return (jnp.bfloat16 if asked in (None, "default", "fastest", "bfloat16")
            else jnp.float32)


def node_conv2d(x, w, *, height, width, stride=1, impl=None):
    """Model entry of the node-batched convolution (``x[n, I, B*H*W]``,
    ``w[n, k, k, I, O]``).  ``impl=None`` picks by platform, as
    ``fused='auto'`` does: the Pallas kernels on a TPU, with operands in
    :func:`node_mxu_dtype`, the float32 jnp oracle elsewhere."""
    impl = _node_impl(impl)
    mxu = node_mxu_dtype() if impl == "pallas" else jnp.float32
    return _nc.conv2d(x, w, height=height, width=width, stride=stride,
                      impl=impl, mxu_dtype=mxu, interpret=False)


def node_evonorm(x, v, scale, bias, *, hw, impl=None):
    """Model entry of the node-batched EvoNorm-S0 (``x[n, C, B*hw]``):
    ``impl=None`` picks the Pallas kernels on a TPU, the jnp oracle
    elsewhere."""
    return _nn.node_evonorm(x, v, scale, bias, hw=hw, impl=_node_impl(impl))


def ssd_scan(x, dt, a, b, c, d_skip, *, chunk=128, interpret=None):
    """Model-layout entry: x [B,S,H,P], dt [B,S,H], a [H], b/c [B,S,N].

    Rearranges to the kernel's [B*H, ...] layout, runs the Pallas scan, adds
    the D-skip term, and returns (y [B,S,H,P], final_state [B,H,N,P])."""
    interpret = _default_interpret() if interpret is None else interpret
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    xf = jnp.moveaxis(x, 2, 1).reshape(bsz * h, s, p)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(bsz * h, s).astype(jnp.float32)
    adt = dtf * jnp.tile(a.astype(jnp.float32), bsz)[:, None]
    bf = jnp.broadcast_to(b[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    cf = jnp.broadcast_to(c[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    y, fin = _ssd.ssd_scan_bh(xf, dtf, adt, bf, cf, chunk=chunk,
                              interpret=interpret)
    y = jnp.moveaxis(y.reshape(bsz, h, s, p), 1, 2)
    y = y + x.astype(y.dtype) * d_skip[None, None, :, None].astype(y.dtype)
    fin = fin.reshape(bsz, h, n, p)
    return y, fin

"""Fused quasi-global momentum update — Pallas TPU kernels.

At 27-480B parameters the optimizer pass is an HBM-bandwidth-bound streaming
pass over every parameter.  Unfused, Alg. 1 lines 5-9 read/write each array
several times; these kernels fuse the arithmetic so each tensor is streamed
through VMEM exactly once per phase:

  * ``qg_local_step``    x_half = x - eta * (beta*m_hat + g)   (+ Nesterov)
  * ``qg_buffer_update`` m_hat' = mu*m_hat + (1-mu)*(x_old - x_new)/eta
  * ``fused_halfstep``   the whole pre-mix segment in ONE pass: weight decay
    + HeavyBall/QG-seeded momentum + the half step, emitting the new params
    half-step AND (for stateful momentum) the new buffer together — the
    packed-chain entry used by ``core/transforms.chain_apply(fused=...)``
  * ``fused_qg_buffer``  the post-mix QG refresh with the traced lr and the
    Alg. 3 tau gate streamed in the same pass

``qg_local_step``/``qg_buffer_update`` take eta as a static (the historical
microbench entry points); the ``fused_*`` forms take eta — and the tau
refresh gate — as traced [1] operands, because inside the jitted training
step the learning rate is a schedule value, not a constant.

1D grid over VMEM tiles of the flattened parameter; tile = 128Ki elements
(0.5 MiB fp32 per operand -> <=3 MiB VMEM live, well under the ~16 MiB
budget, and a multiple of the 8x128 VREG lane layout).  Launch sizes are
bucketed to power-of-two tile multiples (``pack.bucket_size``) so a
heterogeneous pytree compiles O(log n) kernel variants instead of one per
distinct leaf size.
"""
from __future__ import annotations

import functools

import jax

from . import pack as _pack

TILE = 128 * 1024
_FLOOR = 512


def _local_step_kernel(x_ref, m_ref, g_ref, o_ref, *, eta, beta, nesterov):
    x = x_ref[...]
    m = m_ref[...]
    g = g_ref[...]
    m_local = beta * m + g
    upd = g + beta * m_local if nesterov else m_local
    o_ref[...] = x - eta * upd


def _buffer_update_kernel(xo_ref, xn_ref, m_ref, o_ref, *, inv_eta, mu):
    xo = xo_ref[...]
    xn = xn_ref[...]
    m = m_ref[...]
    o_ref[...] = mu * m + (1.0 - mu) * (xo - xn) * inv_eta


def _flat_call(kernel, args, *, name: str, interpret: bool, n_out: int = 1,
               scalars=(), bucket: bool = True):
    """Launch an elementwise kernel over 1D tiles of flattened input
    (bucketed padding — see ``pack.bucket_size``)."""
    return _pack.flat_call(kernel, args, name=name, n_out=n_out,
                           scalars=scalars, tile=TILE, floor=_FLOOR,
                           interpret=interpret, bucket=bucket)


@functools.partial(jax.jit, static_argnames=("eta", "beta", "nesterov",
                                             "interpret"))
def qg_local_step(x, m_hat, g, *, eta: float, beta: float,
                  nesterov: bool = False, interpret: bool = True):
    kernel = functools.partial(_local_step_kernel, eta=eta, beta=beta,
                               nesterov=nesterov)
    return _flat_call(kernel, (x, m_hat, g), name="qg_local_step",
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eta", "mu", "interpret"))
def qg_buffer_update(x_old, x_new, m_hat, *, eta: float, mu: float,
                     interpret: bool = True):
    kernel = functools.partial(_buffer_update_kernel, inv_eta=1.0 / eta, mu=mu)
    return _flat_call(kernel, (x_old, x_new, m_hat), name="qg_buffer_update",
                      interpret=interpret)


# ---------------------------------------------------------------------------
# fused chain segments (packed whole-tree entry points)
# ---------------------------------------------------------------------------
#
# Arithmetic order matches the unfused transform stages EXPRESSION FOR
# EXPRESSION (weight_decay -> heavyball -> gossip_mix half step; qg_buffer
# scale -> lerp -> tau gate), so on identical fp32 inputs the fused chain is
# bit-identical to the stage-by-stage one — the parity contract the golden
# tests in tests/test_fused.py pin.

def _fused_halfstep_kernel(x_ref, m_ref, g_ref, eta_ref, half_ref,
                           *maybe_m_out, beta, wd, nesterov):
    x = x_ref[...]
    m = m_ref[...]
    g = g_ref[...]
    eta = eta_ref[0]
    ge = g + wd * x if wd else g          # weight_decay stage
    mn = beta * m + ge                    # heavyball buffer update
    upd = beta * mn + ge if nesterov else mn
    half_ref[...] = -eta * upd + x        # gossip_mix half step
    if maybe_m_out:
        maybe_m_out[0][...] = mn


def _fused_qg_buffer_kernel(xo_ref, xn_ref, m_ref, eta_ref, rf_ref, o_ref, *,
                            mu):
    s = 1.0 / eta_ref[0]
    d = s * (xo_ref[...] - xn_ref[...])
    new = mu * m_ref[...] + (1.0 - mu) * d
    o_ref[...] = jax.numpy.where(rf_ref[0] != 0.0, new, m_ref[...])


@functools.partial(jax.jit, static_argnames=("beta", "wd", "nesterov",
                                             "emit_m", "interpret"))
def fused_halfstep(x, m, g, eta, *, beta: float, wd: float = 0.0,
                   nesterov: bool = False, emit_m: bool = True,
                   interpret: bool = True):
    """One VMEM pass over (x, m, g): weight decay + momentum + half step.

    Returns ``(half, m_new)`` with ``emit_m=True`` (stateful HeavyBall), or
    just ``half`` with ``emit_m=False`` (QG/DMSGD-seeded momentum, whose
    local buffer is discarded — skipping the write saves a full output
    stream).  ``eta`` is a traced scalar.
    """
    kernel = functools.partial(_fused_halfstep_kernel, beta=beta, wd=wd,
                               nesterov=nesterov)
    return _flat_call(kernel, (x, m, g), name="fused_halfstep",
                      n_out=2 if emit_m else 1, scalars=(eta,),
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("mu", "interpret"))
def fused_qg_buffer(x_pre, x_post, m_hat, eta, refresh, *, mu: float,
                    interpret: bool = True):
    """Post-mix QG buffer refresh (Alg. 1 lines 8-9 / Alg. 3 tau gate) in
    one pass: ``m_hat' = mu*m_hat + (1-mu)*(x_pre - x_post)/eta`` where
    ``refresh`` (traced bool/int scalar) gates the write — off-cadence tau
    steps carry the old buffer through unchanged."""
    kernel = functools.partial(_fused_qg_buffer_kernel, mu=mu)
    return _flat_call(kernel, (x_pre, x_post, m_hat), name="fused_qg_buffer",
                      n_out=1, scalars=(eta, refresh), interpret=interpret)

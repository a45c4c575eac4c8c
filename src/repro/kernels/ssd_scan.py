"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the GPU version
leans on warp-level parallel scans; on TPU we instead exploit the *sequential*
grid execution — grid (B*H, n_chunks) with the chunk dimension innermost, the
running inter-chunk state [N, P] living in VMEM scratch.  Each grid step does
three MXU matmuls (C·Bᵀ gram, intra-chunk combine, state read/write) over an
aligned [L, N]x[N, P] working set, which is exactly the memory-hierarchy
shape the MXU wants (L, N, P multiples of 8/128 where possible).

Inputs are pre-arranged by ``ops.ssd_scan``:
  x   [BH, S, P]   per-head inputs
  dt  [BH, S]      discretization steps (softplus applied outside)
  adt [BH, S]      a * dt  (decay log-terms, <= 0)
  b   [BH, S, N]   input projections  (broadcast over heads outside)
  c   [BH, S, N]   output projections
Outputs: y [BH, S, P], final_state [BH, N, P].
(The D-skip term is applied outside the kernel.)

The wrapper splits S into [n_chunks, L] so that every block's last two dims
are whole array dims (the TPU's block-shape rule), and hands the per-step
vectors over as both a column [L, 1] and a row [1, L]: the kernel needs
them along both axes of the [L, L] intra-chunk matrix, and a vreg relayout
is not something Mosaic does for free.  The chunk-local cumulative decay
is a cheap [BH, S] XLA cumsum outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, cumc_ref, cumr_ref, tot_ref, b_ref,
                c_ref, y_ref, fin_ref, state_ref, *, chunk, n_chunks):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)   # [L, P]
    dt_c = dtc_ref[0, 0]                  # [L, 1]  dt_s down the column
    dt_r = dtr_ref[0, 0]                  # [1, L]  dt_s along the row
    cum_c = cumc_ref[0, 0]                # [L, 1]  s_t within chunk
    cum_r = cumr_ref[0, 0]                # [1, L]
    b = b_ref[0, 0].astype(jnp.float32)   # [L, N]
    c = c_ref[0, 0].astype(jnp.float32)   # [L, N]

    # intra-chunk: M[t,s] = (C_t . B_s) * exp(s_t - s_s) * dt_s   (causal)
    gram = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [L, L]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = t_idx >= s_idx
    m = jnp.where(causal, gram * jnp.exp(cum_c - cum_r) * dt_r, 0.0)
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, P]

    # inter-chunk: y += (C exp(s_t)) @ state
    state = state_ref[...]                # [N, P]
    y = y + jax.lax.dot_general(c * jnp.exp(cum_c), state,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update:
    #   state' = exp(total) * state + sum_s exp(total - s_s) dt_s B_s x_s
    # the chunk's total decay s_L is an SMEM scalar: a [1, 1] vector slice
    # cannot be broadcast to [N, P] (sublanes and lanes at once)
    total = tot_ref[0, 0, 0, 0]
    w_out = jnp.exp(total - cum_c) * dt_c  # [L, 1]
    decay = jnp.exp(jnp.full((1, state.shape[1]), total))
    state_new = decay * state + jax.lax.dot_general(
        b * w_out, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # [N, P]
    state_ref[...] = state_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(k == n_chunks - 1)
    def _emit_state():
        fin_ref[0, ...] = state_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_bh(x, dt, adt, b, c, *, chunk: int = 128,
                interpret: bool = True):
    """Pre-arranged layout entry point (see module docstring)."""
    bh, s, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk

    dt = dt.astype(jnp.float32).reshape(bh, n_chunks, chunk)
    cum = jnp.cumsum(adt.astype(jnp.float32).reshape(bh, n_chunks, chunk),
                     axis=-1)
    col = lambda v: v[..., None]          # [BH, nC, L, 1]
    row = lambda v: v[..., None, :]       # [BH, nC, 1, L]
    split = lambda v: v.reshape(bh, n_chunks, chunk, v.shape[-1])

    def blk(last):
        return pl.BlockSpec((1, 1) + last, lambda i, k: (i, k, 0, 0))

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, fin = pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[blk((chunk, p)), blk((chunk, 1)), blk((1, chunk)),
                  blk((chunk, 1)), blk((1, chunk)),
                  pl.BlockSpec((1, 1, 1, 1), lambda i, k: (i, k, 0, 0),
                               memory_space=pltpu.SMEM),
                  blk((chunk, n)), blk((chunk, n))],
        out_specs=[
            blk((chunk, p)),
            pl.BlockSpec((1, n, p), lambda i, k: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_chunks, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(split(x), col(dt), row(dt), col(cum), row(cum), col(col(cum[..., -1])),
      split(b), split(c))
    return y.reshape(bh, s, p), fin

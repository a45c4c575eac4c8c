"""Flash attention (causal GQA, sliding window, softcap) — Pallas TPU kernel.

TPU adaptation of the standard flash algorithm: grid (B*H, n_q, n_kv) with
the KV dimension innermost — TPU grids execute sequentially per core, so the
online-softmax running max / sum / accumulator live in VMEM scratch persisted
across the KV steps of one (head, q-block).  Block shapes are multiples of
(8, 128) for VREG/MXU alignment.

Sliding-window blocks that are entirely outside the (causal, window) band are
skipped with ``pl.when`` — zero MXU work, the structural analogue of the
query-chunked jnp path in models/attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               bq, bk, n_kv, s_valid, t_valid, causal, window, softcap,
               scale):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = i * bq
    k_start = j * bk
    # static-shape dynamic bounds: process only blocks intersecting the band
    live = jnp.asarray(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window:
        live &= k_start + bk - 1 > q_start - window

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, d]
        k = k_ref[0].astype(jnp.float32)                # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if softcap:
            sc = softcap * jnp.tanh(sc / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (q_pos < s_valid) & (k_pos < t_valid)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = True):
    """q [B,S,H,D]; k/v [B,T,K,D] -> [B,S,H,D].  GQA via H % K == 0."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    assert h % kh == 0
    g = h // kh
    bq = min(block_q, max(8, s))
    bk = min(block_k, max(128, t))
    s_pad = (-s) % bq
    t_pad = (-t) % bk
    scale = 1.0 / float(d) ** 0.5

    # layout: per (batch*q-head) rows
    qh = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
    kh_arr = jnp.moveaxis(k, 2, 1).reshape(b * kh, t, d)
    vh_arr = jnp.moveaxis(v, 2, 1).reshape(b * kh, t, d)
    if s_pad:
        qh = jnp.pad(qh, ((0, 0), (0, s_pad), (0, 0)))
    if t_pad:
        kh_arr = jnp.pad(kh_arr, ((0, 0), (0, t_pad), (0, 0)))
        vh_arr = jnp.pad(vh_arr, ((0, 0), (0, t_pad), (0, 0)))
    sp, tp = s + s_pad, t + t_pad
    n_q, n_kv = sp // bq, tp // bk

    kernel = functools.partial(
        _fa_kernel, bq=bq, bk=bk, n_kv=n_kv, s_valid=s, t_valid=t,
        causal=causal, window=window, softcap=softcap, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda hh, i, j: (hh, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda hh, i, j, g=g, kh=kh, h=h:
                         ((hh // h) * kh + (hh % h) // g, j, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda hh, i, j, g=g, kh=kh, h=h:
                         ((hh // h) * kh + (hh % h) // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda hh, i, j: (hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, d), q.dtype),
        # online-softmax accumulators persist across the (innermost,
        # sequential) KV grid dimension in VMEM scratch
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
        ],
        interpret=interpret,
        name="flash_attention",
    )(qh, kh_arr, vh_arr)
    out = out[:, :s, :].reshape(b, h, s, d)
    return jnp.moveaxis(out, 1, 2)


# ---------------------------------------------------------------------------
# paged decode attention (serving) — gather-free, block-table indexed
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, ps, p_max, window, softcap,
                  scale):
    b = pl.program_id(0)
    j = pl.program_id(2)   # page index (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    q_pos = length - 1
    # dead pages (unallocated / past the slot's length / outside the window)
    # cost zero MXU work — the scalar-prefetched block table made the DMA
    # fetch page 0, but the compute is skipped entirely
    live = (bt_ref[b, j] >= 0) & (j * ps < length)
    if window:
        live &= (j + 1) * ps - 1 > q_pos - window

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale     # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)             # [ps, D]
        v = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [G, ps]
        if softcap:
            sc = softcap * jnp.tanh(sc / softcap)
        k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        mask = k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == p_max - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           window: int = 0, softcap: float = 0.0,
                           interpret: bool = True):
    """Single-token decode over a paged KV pool (DESIGN.md §13).

    q [B,1,H,D]; k/v_pages [NP,K,ps,D]; block_tables [B,P] int32 page ids
    (-1 = unallocated); lengths [B] int32 tokens written per slot (incl. the
    current one).  Returns [B,1,H,D].

    The block table and lengths ride in as scalar prefetch: the k/v
    BlockSpec index maps read ``bt[b, j]`` to DMA exactly the slot's own
    pages — no [B, T] gather materialization, bytes moved per step are
    O(lengths), not O(pool).  Each page keeps its (ps, D) tile last, the
    block shape a TPU DMA can move per (page, KV head).
    """
    b, one, h, d = q.shape
    assert one == 1
    n_p, kh, ps, _ = k_pages.shape
    assert h % kh == 0
    g = h // kh
    p_max = block_tables.shape[1]
    scale = 1.0 / float(d) ** 0.5
    qr = q.reshape(b, kh, g, d)

    kernel = functools.partial(_paged_kernel, ps=ps, p_max=p_max,
                               window=window, softcap=softcap, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, p_max),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, hh, j, bt, ln: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bb, hh, j, bt, ln:
                         (jnp.maximum(bt[bb, j], 0), hh, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bb, hh, j, bt, ln:
                         (jnp.maximum(bt[bb, j], 0), hh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bb, hh, j, bt, ln: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),   # acc
            pltpu.VMEM((g, 1), jnp.float32),   # running max
            pltpu.VMEM((g, 1), jnp.float32),   # running sum
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables, lengths, qr, k_pages, v_pages)
    return out.reshape(b, 1, h, d)

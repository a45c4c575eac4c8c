"""Attention: GQA self-attention (full / sliding-window / softcap / qkv-bias),
cross-attention (VLM), and KV-cache decode.

The training/prefill path uses an *online-softmax chunked* implementation
(`chunked_attention`) — a pure-jnp flash-attention: `lax.scan` over KV chunks
so compiled peak memory is O(S * chunk) instead of O(S^2).  This is also the
semantics the Pallas kernel (`repro.kernels.flash_attention`) implements; the
model picks the kernel when ``use_pallas`` is set, jnp otherwise.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import layers

PyTree = Any
NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, *, qkv_bias: bool = False,
                   dtype=jnp.float32) -> PyTree:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(kq, d_model, n_heads * head_dim, dtype),
        "wk": layers.dense_init(kk, d_model, n_kv_heads * head_dim, dtype),
        "wv": layers.dense_init(kv, d_model, n_kv_heads * head_dim, dtype),
        "wo": layers.dense_init(ko, n_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _proj(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    return y if b is None else y + b.astype(y.dtype)


def qkv(params: PyTree, x: jax.Array, n_heads: int, n_kv_heads: int,
        head_dim: int):
    """x: [B,S,d] -> q [B,S,H,D], k/v [B,S,K,D]."""
    b, s, _ = x.shape
    q = _proj(x, params["wq"], params.get("bq")).reshape(b, s, n_heads, head_dim)
    k = _proj(x, params["wk"], params.get("bk")).reshape(b, s, n_kv_heads, head_dim)
    v = _proj(x, params["wv"], params.get("bv")).reshape(b, s, n_kv_heads, head_dim)
    return q, k, v


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — train / prefill
# ---------------------------------------------------------------------------

def chunked_attention(
    q: jax.Array,            # [B, S, H, D]
    k: jax.Array,            # [B, T, K, D]
    v: jax.Array,            # [B, T, K, D]
    *,
    causal: bool = True,
    window: int = 0,         # 0 = full; else sliding window (causal only)
    softcap: float = 0.0,
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention scanning KV in chunks; GQA via head groups."""
    b, s, h, d = q.shape
    t = k.shape[1]
    kh = k.shape[2]
    assert h % kh == 0
    g = h // kh
    chunk = min(chunk, t)
    t_valid = t
    if t % chunk:  # pad KV to a chunk multiple; padded keys masked below
        pad = chunk - t % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        t = t + pad
    n_chunks = t // chunk
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    qf = q.reshape(b, s, kh, g, d).astype(jnp.float32) * scale
    kc = k.reshape(b, n_chunks, chunk, kh, d)
    vc = v.reshape(b, n_chunks, chunk, kh, d)
    q_pos = jnp.arange(s)

    def one_chunk(carry, inp):
        acc, m, l = carry
        kb, vb, c_idx = inp
        k_pos = c_idx * chunk + jnp.arange(chunk)
        # scores: [B, S, KH, G, C]
        sc = jnp.einsum("bskgd,bckd->bskgc", qf, kb.astype(jnp.float32))
        if softcap:
            sc = layers.softcap(sc, softcap)
        mask = jnp.broadcast_to(k_pos[None, :] < t_valid, (s, chunk))
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        bmask = mask[None, :, None, None, :]
        sc = jnp.where(bmask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        # zero fully-masked chunks explicitly: exp(NEG_INF - NEG_INF) == 1
        p = jnp.where(bmask, jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bskgc,bckd->bskgd", p, vb.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, s, kh, g, d), jnp.float32)
    m0 = jnp.full((b, s, kh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s, kh, g), jnp.float32)

    kc_t = jnp.moveaxis(kc, 1, 0)  # [n_chunks, B, C, KH, D]
    vc_t = jnp.moveaxis(vc, 1, 0)
    idx = jnp.arange(n_chunks)

    (acc, m, l), _ = jax.lax.scan(one_chunk, (acc0, m0, l0), (kc_t, vc_t, idx))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, s, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (one query token over a KV cache)
# ---------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,            # [B, 1, H, D]
    k_cache: jax.Array,      # [B, T, K, D]
    v_cache: jax.Array,      # [B, T, K, D]
    cur_pos: jax.Array,      # [] int32 — position of the new token
    *,
    window: int = 0,
    softcap: float = 0.0,
    k_pos: jax.Array | None = None,  # [T] per-slot positions (ring buffers)
) -> jax.Array:
    b, _, h, d = q.shape
    t = k_cache.shape[1]
    kh = k_cache.shape[2]
    g = h // kh
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qf = q.reshape(b, 1, kh, g, d).astype(jnp.float32) * scale
    sc = jnp.einsum("bskgd,btkd->bskgt", qf, k_cache.astype(jnp.float32))
    if softcap:
        sc = layers.softcap(sc, softcap)
    if k_pos is None:
        k_pos = jnp.arange(t)
        mask = k_pos <= cur_pos
    else:
        mask = (k_pos >= 0) & (k_pos <= cur_pos)
    if window:
        mask &= k_pos > cur_pos - window
    sc = jnp.where(mask[None, None, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bskgt,btkd->bskgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged attention (serving) — per-row positions over a gathered page span
# ---------------------------------------------------------------------------

def paged_attention(
    q: jax.Array,            # [B, C, H, D] chunk of queries per slot
    k: jax.Array,            # [B, T, K, D] gathered from the page pool
    v: jax.Array,            # [B, T, K, D]
    q_pos: jax.Array,        # [B, C] int32 absolute position of each query
    *,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Dense reference semantics for gather-by-block-table attention
    (DESIGN.md §13).  Unlike :func:`decode_attention` the positions are
    per-(slot, token): every serving slot sits at its own offset, and a
    chunked-prefill slice carries C > 1 consecutive queries.

    The causal mask ``k_pos <= q_pos`` is also the slot-reuse guarantee:
    pool rows holding stale K/V from an evicted sequence only ever appear
    at logical positions >= the new sequence's length, so they are masked
    without any cache zeroing.
    """
    b, c, h, d = q.shape
    t = k.shape[1]
    kh = k.shape[2]
    g = h // kh
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qf = q.reshape(b, c, kh, g, d).astype(jnp.float32) * scale
    sc = jnp.einsum("bskgd,btkd->bskgt", qf, k.astype(jnp.float32))
    if softcap:
        sc = layers.softcap(sc, softcap)
    k_pos = jnp.arange(t)
    mask = k_pos[None, None, :] <= q_pos[:, :, None]          # [B, C, T]
    if window:
        mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
    sc = jnp.where(mask[:, :, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bskgt,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(b, c, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# cross attention (VLM) — queries from text, KV from image embeddings
# ---------------------------------------------------------------------------

def cross_attention(
    params: PyTree, x: jax.Array, kv_src: jax.Array,
    n_heads: int, n_kv_heads: int, head_dim: int,
) -> jax.Array:
    """x: [B,S,d] text hidden; kv_src: [B,T,d] image embeddings (stub)."""
    b, s, _ = x.shape
    t = kv_src.shape[1]
    q = _proj(x, params["wq"], params.get("bq")).reshape(b, s, n_heads, head_dim)
    k = _proj(kv_src, params["wk"], params.get("bk")).reshape(b, t, n_kv_heads, head_dim)
    v = _proj(kv_src, params["wv"], params.get("bv")).reshape(b, t, n_kv_heads, head_dim)
    out = chunked_attention(q, k, v, causal=False, chunk=min(1024, t))
    out = out.reshape(b, s, n_heads * head_dim)
    return jnp.einsum("...f,fd->...d", out, params["wo"])

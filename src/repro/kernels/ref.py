"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

These are the semantics the kernels must match bit-for-bit (up to fp
accumulation order); tests sweep shapes/dtypes and assert allclose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# qg_update — fused quasi-global momentum arithmetic (elementwise)
# ---------------------------------------------------------------------------

def qg_local_step_ref(x, m_hat, g, *, eta: float, beta: float,
                      nesterov: bool) -> jax.Array:
    """Alg. 1 lines 5-6 (+ PyTorch-style Nesterov): the half step
    x - eta * upd  with  upd = beta*m_hat + g  (HeavyBall)
                   or    upd = g + beta*(beta*m_hat + g)  (Nesterov)."""
    m_local = beta * m_hat + g
    upd = g + beta * m_local if nesterov else m_local
    return x - eta * upd


def qg_buffer_update_ref(x_old, x_new, m_hat, *, eta: float,
                         mu: float) -> jax.Array:
    """Alg. 1 lines 8-9:  m_hat <- mu*m_hat + (1-mu)*(x_old - x_new)/eta."""
    return mu * m_hat + (1.0 - mu) * (x_old - x_new) / eta


def fused_halfstep_ref(x, m, g, eta, *, beta: float, wd: float = 0.0,
                       nesterov: bool = False):
    """One-pass pre-mix chain segment (weight decay + HeavyBall/QG-seeded
    momentum + the gossip half step).  Expression order matches the unfused
    transform stages so the fused chain stays bit-identical.  Returns
    (half, m_new)."""
    ge = g + wd * x if wd else g
    mn = beta * m + ge
    upd = beta * mn + ge if nesterov else mn
    return -eta * upd + x, mn


def fused_qg_buffer_ref(x_pre, x_post, m_hat, eta, refresh, *, mu: float):
    """Post-mix QG buffer refresh with the Alg. 3 tau gate: where ``refresh``
    is nonzero,  m_hat <- mu*m_hat + (1-mu)*(x_pre - x_post)/eta,  else the
    old buffer carries through."""
    s = 1.0 / eta
    d = s * (x_pre - x_post)
    new = mu * m_hat + (1.0 - mu) * d
    return jnp.where(jnp.asarray(refresh, jnp.float32) != 0.0, new, m_hat)


def gamma_correct_ref(x, mixed, anchor, *, gamma: float) -> jax.Array:
    """CHOCO/EF post-exchange correction: x + gamma * (mixed - anchor)."""
    return x + gamma * (mixed - anchor)


# ---------------------------------------------------------------------------
# compress — fused gossip-compression hot paths (comm subsystem)
# ---------------------------------------------------------------------------

def threshold_mask_ref(x2d, thr):
    """Magnitude-threshold sparsification with residual.  x2d [rows, f];
    thr [rows].  Returns (kept, residual) in fp32."""
    x = x2d.astype(jnp.float32)
    q = jnp.where(jnp.abs(x) >= thr.astype(jnp.float32)[:, None], x, 0.0)
    return q, x - q


def quantize_dequantize_ref(x2d, scale, u, *, levels: int):
    """QSGD stochastic quantize->dequantize with residual.  x2d [rows, f];
    scale [rows] (max |x| per row); u [rows, f] uniform in [0, 1);
    q = sign(x) * scale * min(floor(|x|/scale*L + u), L) / L."""
    x = x2d.astype(jnp.float32)
    s = jnp.maximum(scale.astype(jnp.float32), 1e-12)[:, None]
    y = jnp.abs(x) * (levels / s)
    xi = jnp.minimum(jnp.floor(y + u.astype(jnp.float32)), levels)
    q = jnp.sign(x) * xi * (s / levels)
    return q, x - q


# ---------------------------------------------------------------------------
# flash_attention — causal GQA attention (optional window / softcap)
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0) -> jax.Array:
    """Quadratic masked softmax attention.  q [B,S,H,D]; k/v [B,T,K,D]."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qf = q.reshape(b, s, kh, g, d).astype(jnp.float32) * scale
    sc = jnp.einsum("bskgd,btkd->bskgt", qf, k.astype(jnp.float32))
    if softcap:
        sc = softcap * jnp.tanh(sc / softcap)
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    sc = jnp.where(mask[None, :, None, None, :], sc, -2.0e38)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bskgt,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged_decode_attention — block-table decode over a paged KV pool
# ---------------------------------------------------------------------------

def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                               window: int = 0,
                               softcap: float = 0.0) -> jax.Array:
    """Dense-gather oracle.  q [B,1,H,D]; k/v_pages [NP,K,ps,D];
    block_tables [B,P] (-1 = unallocated); lengths [B] tokens written per
    slot (incl. the current one).  Gathers each slot's pages into a dense
    [B, P*ps, K, D] cache and runs a masked softmax."""
    b, _, h, d = q.shape
    n_p, kh, ps, _ = k_pages.shape
    g = h // kh
    p_max = block_tables.shape[1]
    t = p_max * ps
    t_idx = jnp.arange(t)
    page = jnp.clip(block_tables[:, t_idx // ps], 0, n_p - 1)   # [B, T]
    ks = k_pages[page, :, t_idx % ps]                     # [B, T, K, D]
    vs = v_pages[page, :, t_idx % ps]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qf = q.reshape(b, 1, kh, g, d).astype(jnp.float32) * scale
    sc = jnp.einsum("bskgd,btkd->bskgt", qf, ks.astype(jnp.float32))
    if softcap:
        sc = softcap * jnp.tanh(sc / softcap)
    q_pos = (lengths - 1)[:, None]
    mask = t_idx[None, :] < lengths[:, None]
    mask &= block_tables[:, t_idx // ps] >= 0
    if window:
        mask &= t_idx[None, :] > q_pos - window
    sc = jnp.where(mask[:, None, None, None, :], sc, -2.0e38)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bskgt,btkd->bskgd", p, vs.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# ssd_scan — Mamba-2 SSD recurrence
# ---------------------------------------------------------------------------

def ssd_scan_ref(x, dt, a, b, c, *, initial_state=None):
    """Sequential oracle.  x [B,S,H,P]; dt [B,S,H]; a [H] (negative);
    b/c [B,S,N].  Returns (y [B,S,H,P], final_state [B,H,N,P]).
    NOTE: no D-skip here — the model applies it outside the kernel."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]

    def step(hstate, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(a * dtt)[..., None, None]
        inject = dtt[..., None, None] * bt[:, None, :, None] * xt[:, :, None, :]
        hstate = decay * hstate + inject
        yt = jnp.einsum("bhnp,bn->bhp", hstate, ct)
        return hstate, yt

    h0 = (jnp.zeros((bsz, h, n, p), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(b.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c.astype(jnp.float32), 1, 0))
    hfin, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), hfin

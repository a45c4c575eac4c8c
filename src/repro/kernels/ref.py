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


# ---------------------------------------------------------------------------
# node_conv — node-batched convolution taps in the channel-major layout
# ---------------------------------------------------------------------------

def node_conv_stack_ref(x, *, height: int, width: int, ksize: int):
    """``x[n, I, B*H*W]`` -> ``[n, taps*I, B*H*W]``: row ``t*I + i`` is
    ``x[i, m + dh*W + dw]`` for tap ``t = (dh, dw)`` (row-major over the
    window), zero where the tap leaves the image."""
    if ksize == 1:
        return x
    m = jnp.arange(x.shape[-1])
    col, row = m % width, (m // width) % height
    pieces = []
    for dh in (-1, 0, 1):
        for dw in (-1, 0, 1):
            valid = ((row + dh >= 0) & (row + dh < height)
                     & (col + dw >= 0) & (col + dw < width))
            shifted = jnp.roll(x, -(dh * width + dw), axis=-1)
            pieces.append(jnp.where(valid, shifted, 0.0))
    return jnp.concatenate(pieces, axis=1)


def node_conv_down_ref(y, *, height: int, width: int, offset: int):
    """Every second row and column of each image from ``offset``:
    ``[n, C, B*H*W]`` -> ``[n, C, B*(H/2)*(W/2)]``."""
    n, c, m = y.shape
    y = y.reshape(n, c, m // (height * width), height // 2, 2, width // 2, 2)
    return y[:, :, :, :, offset, :, offset].reshape(n, c, m // 4)


def node_conv_up_ref(v, *, height: int, width: int, offset: int):
    """The transpose of :func:`node_conv_down_ref`: zeros elsewhere."""
    n, c, m4 = v.shape
    b = 4 * m4 // (height * width)
    full = jnp.zeros((n, c, b, height // 2, 2, width // 2, 2), v.dtype)
    full = full.at[:, :, :, :, offset, :, offset].set(
        v.reshape(n, c, b, height // 2, width // 2))
    return full.reshape(n, c, 4 * m4)


def node_conv_taps_ref(a, x, *, height: int, width: int, ksize: int,
                       mxu_dtype=jnp.float32, resample=None, offset: int = 0):
    """``y[n, O, M] = a[n, O, taps*I] @ stack(x)``, operands rounded to
    ``mxu_dtype`` and accumulated in float32; ``resample='down'`` keeps
    the output's every second row and column, ``'up'`` spreads ``x`` from
    that resolution first (``height, width``: the full resolution)."""
    rs = dict(height=height, width=width, offset=offset)
    if resample == "up":
        x = node_conv_up_ref(x, **rs)
    cols = node_conv_stack_ref(x, height=height, width=width, ksize=ksize)
    y = jnp.einsum("nok,nkm->nom", a.astype(mxu_dtype),
                   cols.astype(mxu_dtype), preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    return node_conv_down_ref(y, **rs) if resample == "down" else y


def node_conv_dw_ref(x, g, *, height: int, width: int, ksize: int,
                     mxu_dtype=jnp.float32, resample=None, offset: int = 0):
    """``dA[n, O, taps*I] = g[n, O, M] @ stack(x)^T`` (``resample='up'``:
    ``g`` at the stride-2 resolution)."""
    if resample == "up":
        g = node_conv_up_ref(g, height=height, width=width, offset=offset)
    cols = node_conv_stack_ref(x, height=height, width=width, ksize=ksize)
    return jnp.einsum("nom,nkm->nok", g.astype(mxu_dtype),
                      cols.astype(mxu_dtype),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# node_norm — EvoNorm-S0 over a node block in the channel-major layout
# ---------------------------------------------------------------------------

def node_evonorm_ref(x, v, scale, bias, *, hw: int, groups: int = 2,
                     eps: float = 1e-5):
    """``x[n, C, B*hw]``, per-channel ``v, scale, bias[n, C]``: EvoNorm-S0
    with statistics per node, image and channel group."""
    n, c, m = x.shape
    xg = x.reshape(n, groups, c // groups, m // hw, hw)
    std = jnp.sqrt(jnp.var(xg, axis=(2, 4), keepdims=True) + eps)
    col = lambda p: p[:, :, None]
    num = x * jax.nn.sigmoid(col(v) * x)
    y = num / jnp.broadcast_to(std, xg.shape).reshape(n, c, m)
    return y * col(scale) + col(bias)

"""Mamba-2 (SSD, state-space duality — arXiv:2405.21060) mixer.

Scalar-identity per-head decay ``a = -exp(a_log)``, discretized with a
per-token, per-head step ``dt``:

    h_t = exp(a * dt_t) h_{t-1} + dt_t * B_t x_t^T      h in R^{N x P}
    y_t = C_t h_t + D x_t

Three implementations with identical semantics:
  * ``ssd_reference``  — naive sequential ``lax.scan`` over time (oracle);
  * ``ssd_chunked``    — chunked/blocked SSD (intra-chunk attention-like
    matmuls + inter-chunk state recurrence), the model's jnp path; compiled
    memory O(S * chunk) and MXU-friendly;
  * ``repro.kernels.ssd_scan`` — the Pallas TPU kernel mirroring the chunked
    algorithm (used when ``use_pallas``).

The decode path carries (conv_state, ssm_state) and costs O(1) per token —
this is why mamba2/zamba2 run the 500k-context shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import layers

PyTree = Any


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64          # P; n_heads = d_inner / head_dim

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


def init_mamba(key, d_model: int, cfg: SSMConfig, dtype=jnp.float32) -> PyTree:
    di = cfg.d_inner(d_model)
    nh = cfg.n_heads(d_model)
    k_in, k_conv, k_out, k_dt = jax.random.split(key, 4)
    # in_proj packs [z (gate), x, B, C, dt]
    d_bc = 2 * cfg.d_state
    return {
        "in_proj": layers.dense_init(k_in, d_model, 2 * di + d_bc + nh, dtype),
        "conv_w": (jax.random.normal(k_conv, (cfg.d_conv, di + d_bc)) * 0.1
                   ).astype(dtype),
        "a_log": jnp.zeros((nh,), jnp.float32),       # a = -exp(a_log) = -1
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_proj": layers.dense_init(k_out, di, d_model, dtype),
    }


def _split_proj(proj: jax.Array, di: int, n: int, nh: int):
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * n]
    dt = proj[..., di + di + 2 * n:]
    return z, xbc, dt  # dt: [..., nh]


def _causal_conv(xbc: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv1d; xbc [B,S,C], w [K,C]."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(k))
    return jax.nn.silu(out)


# ---------------------------------------------------------------------------
# SSD cores
# ---------------------------------------------------------------------------

def ssd_reference(x, dt, a, b, c, d_skip):
    """Naive sequential oracle.

    x [B,S,H,P], dt [B,S,H], a [H] (negative), b/c [B,S,N], d_skip [H].
    Returns y [B,S,H,P] and final state [B,H,N,P].
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]

    def step(hstate, inp):
        xt, dtt, bt, ct = inp  # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(a * dtt)[..., None, None]          # [B,H,1,1]
        inject = (dtt[..., None, None] * bt[:, None, :, None]
                  * xt[:, :, None, :])                     # [B,H,N,P]
        hstate = decay * hstate + inject
        yt = jnp.einsum("bhnp,bn->bhp", hstate, ct)
        return hstate, yt

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(b.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c.astype(jnp.float32), 1, 0))
    hfin, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * d_skip[None, None, :, None]
    return y.astype(x.dtype), hfin


def ssd_chunked(x, dt, a, b, c, d_skip, *, chunk: int = 128,
                initial_state=None):
    """Chunked SSD: O(S/L) sequential steps of attention-like matmuls."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    xf = x.astype(jnp.float32).reshape(bsz, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    bf = b.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cf = c.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    adt = a[None, None, None, :] * dtf                     # [B,nc,L,H] (<=0)
    cum = jnp.cumsum(adt, axis=2)                          # s_t within chunk
    total = cum[:, :, -1, :]                               # chunk total decay

    def per_chunk(args):
        xk, dtk, bk, ck, cumk, adtk = args
        # intra-chunk: M[t,s] = (C_t.B_s) exp(s_t - s_s) dt_s  (causal)
        gram = jnp.einsum("btn,bsn->bts", ck, bk)          # [B,L,L]
        dec = cumk[:, :, None, :] - cumk[:, None, :, :]    # [B,L,L,H] s_t - s_s
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        m = gram[..., None] * jnp.exp(jnp.where(causal[None, :, :, None],
                                                dec, -jnp.inf))
        m = m * dtk[:, None, :, :]                          # weight by dt_s
        y_intra = jnp.einsum("btsh,bshp->bthp", m, xk)
        # state to pass on: sum_s exp(s_L - s_s) dt_s B_s x_s
        w_out = jnp.exp(cumk[:, -1:, :] - cumk) * dtk       # [B,L,H]
        state_out = jnp.einsum("bsh,bsn,bshp->bhnp", w_out, bk, xk)
        # input-state read weights: C_t exp(s_t)
        w_in = jnp.exp(cumk)                                # [B,L,H]
        return y_intra, state_out, w_in

    chunks = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
              jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0),
              jnp.moveaxis(cum, 1, 0), jnp.moveaxis(adt, 1, 0))

    def scan_body(hstate, args):
        xk, dtk, bk, ck, cumk, adtk = args
        y_intra, state_out, w_in = per_chunk((xk, dtk, bk, ck, cumk, adtk))
        # inter-chunk contribution: C_t exp(s_t) h_{in}
        y_inter = jnp.einsum("btn,bhnp,bth->bthp", ck, hstate, w_in)
        tot = jnp.exp(cumk[:, -1, :])                      # [B,H]
        h_new = tot[:, :, None, None] * hstate + state_out
        return h_new, y_intra + y_inter

    h0 = (jnp.zeros((bsz, h, n, p), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    hfin, ys = jax.lax.scan(scan_body, h0, chunks)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)
    y = y + x.astype(jnp.float32) * d_skip[None, None, :, None]
    return y.astype(x.dtype), hfin


def ssd_decode_step(hstate, xt, dtt, a, bt, ct, d_skip):
    """One-token state update; hstate [B,H,N,P]."""
    decay = jnp.exp(a * dtt)[..., None, None]
    inject = dtt[..., None, None] * bt[:, None, :, None] * xt[:, :, None, :]
    h_new = decay * hstate.astype(jnp.float32) + inject
    yt = jnp.einsum("bhnp,bn->bhp", h_new, ct) + xt * d_skip[None, :, None]
    return h_new, yt


# ---------------------------------------------------------------------------
# full mixer
# ---------------------------------------------------------------------------

def mamba_mixer(params: PyTree, x: jax.Array, cfg: SSMConfig, *,
                chunk: int = 128, use_pallas: bool = False) -> jax.Array:
    """Train/prefill path. x: [B,S,d] -> [B,S,d]."""
    bsz, s, d_model = x.shape
    di = cfg.d_inner(d_model)
    nh = cfg.n_heads(d_model)
    n = cfg.d_state
    proj = jnp.einsum("bsd,df->bsf", x, params["in_proj"])
    z, xbc, dt = _split_proj(proj, di, n, nh)
    xbc = _causal_conv(xbc, params["conv_w"])
    xi = xbc[..., :di].reshape(bsz, s, nh, cfg.head_dim)
    b = xbc[..., di:di + n]
    c = xbc[..., di + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    if use_pallas:
        from repro.kernels import ops as kops
        y, _ = kops.ssd_scan(xi, dt, a, b, c, params["d_skip"], chunk=chunk)
    else:
        y, _ = ssd_chunked(xi, dt, a, b, c, params["d_skip"], chunk=chunk)
    y = y.reshape(bsz, s, di) * jax.nn.silu(z)
    return jnp.einsum("bsf,fd->bsd", y, params["out_proj"])


def mamba_decode(params: PyTree, x: jax.Array, state: dict, cfg: SSMConfig):
    """Decode path. x: [B,1,d]; state: {conv: [B,K-1,C], ssm: [B,H,N,P]}."""
    bsz, _, d_model = x.shape
    di = cfg.d_inner(d_model)
    nh = cfg.n_heads(d_model)
    n = cfg.d_state
    proj = jnp.einsum("bsd,df->bsf", x, params["in_proj"])[:, 0]
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * n]
    dt = proj[..., di + di + 2 * n:]
    # rolling conv state
    conv_in = jnp.concatenate([state["conv"], xbc[:, None, :]], axis=1)
    w = params["conv_w"]
    xbc = jax.nn.silu(jnp.einsum("bkc,kc->bc", conv_in, w))
    new_conv = conv_in[:, 1:, :]
    xi = xbc[..., :di].reshape(bsz, nh, cfg.head_dim)
    b = xbc[..., di:di + n]
    c = xbc[..., di + n:]
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    h_new, yt = ssd_decode_step(state["ssm"], xi.astype(jnp.float32), dtv,
                                a, b.astype(jnp.float32),
                                c.astype(jnp.float32), params["d_skip"])
    y = yt.reshape(bsz, di).astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("bf,fd->bd", y, params["out_proj"])[:, None, :]
    return out, {"conv": new_conv, "ssm": h_new.astype(state["ssm"].dtype)}


def init_mamba_state(bsz: int, d_model: int, cfg: SSMConfig, dtype=jnp.float32):
    di = cfg.d_inner(d_model)
    nh = cfg.n_heads(d_model)
    return {
        "conv": jnp.zeros((bsz, cfg.d_conv - 1, di + 2 * cfg.d_state), dtype),
        "ssm": jnp.zeros((bsz, nh, cfg.d_state, cfg.head_dim), jnp.float32),
    }

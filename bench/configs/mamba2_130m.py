"""Plain reference of ``mamba2_130m``: a Mamba-2 language model (Dao & Gu,
arXiv:2405.21060) in straightforward ``jax.numpy``; it imports nothing of
the program.  The parameter tree uses the program's key names (layers
stacked on a leading axis) so that the weights made here can be handed to
the program's trainer.

Per layer, with pre-norm residual ``x + mixer(rmsnorm(x))``:

    [z, xBC, dt] = h W_in
    xBC          = silu(causal_depthwise_conv1d(xBC))      -> x, B, C
    dt           = softplus(dt + dt_bias);  A = -exp(a_log)  (one per head)
    y_t          = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} A dt_r) dt_s x_s
                   + D x_t                    (the SSD "attention" form)
    out          = (y * silu(z)) W_out

The SSD is computed in its quadratic form over the whole sequence, not in
chunks; the layer is rematerialized so that the reference fits beside its
state.  Departures from the published block follow the configuration file
(``reduced``): untied head, no gated RMSNorm, no conv bias.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dims(cfg):
    d = cfg["d_model"]
    di = cfg["expand"] * d
    nh = di // cfg["head_dim"]
    n = cfg["d_state"] * cfg["ngroups"]
    return d, di, nh, n


def init_params(key, cfg):
    """Published Mamba-2 initialisation: N(0, 0.02) embedding, A in [1, 16],
    dt in [1e-3, 1e-1] log-uniform (stored through an inverse softplus),
    D = 1, out_proj scaled by 1/sqrt(n_layer); norms at scale 1."""
    d, di, nh, n = _dims(cfg)
    layers, rows, k = cfg["n_layer"], cfg["vocab_rows"], cfg["d_conv"]
    ks = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(ks[5], (layers, nh),
                                    minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    mixer = {
        "in_proj": jax.random.normal(ks[2], (layers, d, 2 * di + 2 * n + nh))
        / math.sqrt(d),
        "conv_w": jax.random.uniform(ks[3], (layers, k, di + 2 * n),
                                     minval=-1.0, maxval=1.0) / math.sqrt(k),
        "a_log": jnp.log(jax.random.uniform(ks[4], (layers, nh),
                                            minval=1.0, maxval=16.0)),
        "d_skip": jnp.ones((layers, nh)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "out_proj": jax.random.normal(ks[6], (layers, di, d))
        / math.sqrt(di * layers),
    }
    params = {
        "embed": jax.random.normal(ks[0], (rows, d)) * 0.02,
        "final_norm": jnp.zeros((d,)),
        "lm_head": jax.random.normal(ks[1], (d, rows)) * 0.02,
        "blocks": ({"ln": jnp.zeros((layers, d)), "mixer": mixer},),
        "tail": (),
    }
    return params, {}


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _mixer(p, h, cfg):
    d, di, nh, n = _dims(cfg)
    bsz, s, _ = h.shape
    proj = h @ p["in_proj"]
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], \
        proj[..., 2 * di + 2 * n:]
    k = p["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + s] * p["conv_w"][i] for i in range(k)))
    x = xbc[..., :di].reshape(bsz, s, nh, cfg["head_dim"])
    b, c = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [B,S,H]
    cum = jnp.cumsum(dt * -jnp.exp(p["a_log"]), axis=1)        # [B,S,H]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, cum[:, :, None] - cum[:, None], -jnp.inf))
    m = (c @ jnp.swapaxes(b, 1, 2))[..., None] * decay * dt[:, None]
    y = jnp.einsum("btsh,bshp->bthp", m, x) \
        + x * p["d_skip"][None, None, :, None]
    y = y.reshape(bsz, s, di) * jax.nn.silu(z)
    return y @ p["out_proj"]


def loss(params, batch, cfg):
    """Mean next-token cross-entropy of one node's batch ``(tokens
    [B, S+1],)`` over the ``vocab_size`` real rows, computed in the dtype of
    ``params``."""
    (toks,) = batch
    tokens, labels = toks[:, :-1], toks[:, 1:].astype(jnp.int32)
    eps, v = cfg["norm_eps"], cfg["vocab_size"]
    (blocks,) = params["blocks"]

    @jax.checkpoint
    def layer(x, lp):
        return x + _mixer(lp["mixer"], _rms_norm(x, lp["ln"], eps), cfg), None

    x, _ = jax.lax.scan(layer, params["embed"][tokens], blocks)
    logits = _rms_norm(x, params["final_norm"], eps) @ params["lm_head"][:, :v]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def param_count(cfg) -> int:
    """Parameters of one node as stored (embedding and head rows padded)."""
    d, di, nh, n = _dims(cfg)
    per_layer = (d + d * (2 * di + 2 * n + nh) + cfg["d_conv"] * (di + 2 * n)
                 + 3 * nh + di * d)
    return 2 * cfg["vocab_rows"] * d + d + cfg["n_layer"] * per_layer


def matmul_params(cfg) -> int:
    """Weights that multiply every token: in_proj, out_proj and the head over
    the real vocabulary (the embedding is a lookup)."""
    d, di, nh, n = _dims(cfg)
    return cfg["n_layer"] * (d * (2 * di + 2 * n + nh) + di * d) \
        + d * cfg["vocab_size"]


def ssd_flops_per_token(cfg) -> int:
    """Forward FLOPs of the chunked SSD per token per layer, at the
    program's chunk L: C.B^T within the chunk (2 L N), the masked matrix
    times x (2 L d_inner), the chunk's state (2 N d_inner) and its read-out
    (2 N d_inner).  Elementwise decay terms are not counted."""
    d, di, nh, n = _dims(cfg)
    chunk = cfg["ssd_chunk"]
    return 2 * chunk * n + 2 * chunk * di + 4 * n * di


def train_flops_per_node_step(cfg, traffic) -> int:
    """6 x matmul weights x tokens, plus 3 x the SSD's forward FLOPs;
    nothing recomputed is counted."""
    tokens = traffic["batch"] * traffic["seq_len"]
    return tokens * (6 * matmul_params(cfg)
                     + 3 * cfg["n_layer"] * ssd_flops_per_token(cfg))

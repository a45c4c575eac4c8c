"""Shared neural-net building blocks (plain pytree params, no flax).

Parameters are nested dicts of jnp arrays.  Homogeneous layer groups are
stacked on a leading axis and driven by ``jax.lax.scan`` (keeps the lowered
HLO compact, so a deep stack compiles once per period, not once per layer).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


def stack_layers(keys, init_fn):
    """init_fn(key) -> param pytree; returns pytree stacked on leading axis."""
    params = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32) -> PyTree:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d_model, d_ff, dtype),
        "up": dense_init(k2, d_model, d_ff, dtype),
        "down": dense_init(k3, d_ff, d_model, dtype),
    }


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: [..., S, H, D]; positions: [..., S] int32."""
    freqs = rope_frequencies(x.shape[-1], theta)  # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]  # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def token_nll(logits: jax.Array, labels: jax.Array,
              vocab_valid: int | None = None) -> jax.Array:
    """Each token's cross-entropy; padded vocab rows (>= vocab_valid)
    masked."""
    logits = logits.astype(jnp.float32)
    if vocab_valid is not None and vocab_valid < logits.shape[-1]:
        neg = jnp.finfo(jnp.float32).min
        pad = jnp.arange(logits.shape[-1]) >= vocab_valid
        logits = jnp.where(pad, neg, logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab_valid: int | None = None) -> jax.Array:
    """Mean token cross-entropy; padded vocab rows (>= vocab_valid) masked."""
    return jnp.mean(token_nll(logits, labels, vocab_valid))


def blocked_cross_entropy(h: jax.Array, head: jax.Array, labels: jax.Array,
                          vocab_valid: int | None = None, *,
                          cap: float = 0.0, block: int = 1024) -> jax.Array:
    """``cross_entropy(softcap(h @ head, cap), labels, vocab_valid)`` with
    the head applied ``block`` tokens at a time.

    ``h`` is ``[..., d]`` (the final-normed hidden state), ``head`` is
    ``[d, V]``.  The tokens are scanned in blocks (the last one padded, its
    padding left out of the sum), so no more than one block's fp32 logits
    are live.  Differentiated, each block's gradient is taken where its
    logits are made, in the forward pass: the pass keeps the hidden
    state's gradient and accumulates the head's, and the backward pass
    only scales them, so the logits are computed once.  Same logits and
    the same masked mean as :func:`cross_entropy` on the whole
    ``[tokens, V]`` logits."""
    return _blocked_ce(h, head, labels, vocab_valid, cap, block)


def _token_blocks(h, labels, block):
    d = h.shape[-1]
    t = h.size // d
    block = min(block, t)
    nb = -(-t // block)
    pad = nb * block - t
    hb = jnp.pad(h.reshape(t, d), ((0, pad), (0, 0))).reshape(nb, block, d)
    lb = jnp.pad(labels.reshape(t), (0, pad)).reshape(nb, block)
    real = (jnp.arange(nb * block) < t).reshape(nb, block)
    return (hb, lb, real), t


def _block_nll_sum(hb, head, lb, real, vocab_valid, cap):
    logits = jnp.einsum("td,dv->tv", hb, head).astype(jnp.float32)
    nll = token_nll(softcap(logits, cap), lb, vocab_valid)
    return jnp.sum(jnp.where(real, nll, 0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blocked_ce(h, head, labels, vocab_valid, cap, block):
    blocks, t = _token_blocks(h, labels, block)

    def one(total, xs):
        hb, lb, real = xs
        return total + _block_nll_sum(hb, head, lb, real, vocab_valid,
                                      cap), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), blocks)
    return total / t


def _blocked_ce_fwd(h, head, labels, vocab_valid, cap, block):
    blocks, t = _token_blocks(h, labels, block)
    grad = jax.value_and_grad(_block_nll_sum, argnums=(0, 1))

    def one(carry, xs):
        total, dhead = carry
        hb, lb, real = xs
        s, (dhb, dheadb) = grad(hb, head, lb, real, vocab_valid, cap)
        return (total + s, dhead + dheadb), dhb

    zero = (jnp.zeros((), jnp.float32), jnp.zeros_like(head))
    (total, dhead), dh = jax.lax.scan(one, zero, blocks)
    dh = dh.reshape(-1, h.shape[-1])[:t].reshape(h.shape)
    return total / t, (dh / t, dhead / t)


def _blocked_ce_bwd(vocab_valid, cap, block, res, g):
    dh, dhead = res
    return ((g * dh).astype(dh.dtype), (g * dhead).astype(dhead.dtype),
            None)


_blocked_ce.defvjp(_blocked_ce_fwd, _blocked_ce_bwd)

"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Dispatch/combine use the standard one-hot einsum formulation (Switch/GShard
style), which XLA lowers to all-to-all when experts are sharded over a mesh
axis (expert parallelism).  Router load-balance auxiliary loss follows
Switch Transformers; arctic-style configs add a *dense residual* FFN branch
that always runs alongside the routed experts.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import layers

PyTree = Any


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    dense_ff: int = 0          # arctic: parallel dense FFN width (0 = off)
    aux_loss_coef: float = 0.01


def init_moe(key, d_model: int, d_ff: int, cfg: MoEConfig,
             dtype=jnp.float32) -> PyTree:
    kr, kg, ku, kd, kdense = jax.random.split(key, 5)
    e = cfg.n_experts
    s = 1.0 / jnp.sqrt(d_model)
    p = {
        "router": layers.dense_init(kr, d_model, e, jnp.float32),
        "w_gate": (jax.random.normal(kg, (e, d_model, d_ff)) * s).astype(dtype),
        "w_up": (jax.random.normal(ku, (e, d_model, d_ff)) * s).astype(dtype),
        "w_down": (jax.random.normal(kd, (e, d_ff, d_model)) *
                   (1.0 / jnp.sqrt(d_ff))).astype(dtype),
    }
    if cfg.dense_ff:
        p["dense"] = layers.init_mlp(kdense, d_model, cfg.dense_ff, dtype)
    return p


def moe_ffn(params: PyTree, x: jax.Array, cfg: MoEConfig, *,
            token_mask=None):
    """x: [B, S, d] -> (y, aux_loss).

    Top-k routing with per-expert capacity C = ceil(T*k/E * factor); overflow
    tokens are dropped (standard capacity semantics).  Dispatch is
    scatter/gather based — peak extra memory O(E*C*d), *not* the O(T*E*C)
    one-hot dispatch tensor (which would be terabytes at arctic scale).

    ``token_mask`` [B, S] bool (serving): masked-out tokens are excluded from
    routing — they consume no expert capacity, produce zero output, and do
    not enter the load-balance statistics.  Without this, the junk padding
    in a serving batch would steal queue positions from real tokens and make
    outputs depend on whatever sits in the padded rows.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tokens = x.reshape(b * s, d)
    n_tok = b * s

    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    capacity = max(1, int(n_tok * k / e * cfg.capacity_factor))

    # queue position of each (token, slot) within its expert, computed with a
    # cumsum over the flattened (token, slot) stream:  [T*k]
    flat_e = expert_idx.reshape(-1)                       # [T*k] int32
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)   # [T*k, E]
    tmask = None
    if token_mask is not None:
        tmask = token_mask.reshape(-1)                    # [T] bool
        onehot = onehot * jnp.repeat(tmask, k)[:, None].astype(onehot.dtype)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot       # pos on own column
    flat_pos = jnp.sum(pos, axis=-1)                      # [T*k]
    valid = flat_pos < capacity
    if tmask is not None:
        valid &= jnp.repeat(tmask, k)

    # scatter token ids / gates into per-expert queues [E*C]
    slot = jnp.where(valid, flat_e * capacity + flat_pos, e * capacity)
    token_id = jnp.tile(jnp.arange(n_tok)[:, None], (1, k)).reshape(-1)
    tok_for_slot = jnp.zeros((e * capacity + 1,), jnp.int32).at[slot].set(
        token_id, mode="drop")
    gate_for_slot = jnp.zeros((e * capacity + 1,), jnp.float32).at[slot].set(
        gate_vals.reshape(-1), mode="drop")
    filled = jnp.zeros((e * capacity + 1,), jnp.bool_).at[slot].set(
        True, mode="drop")
    tok_for_slot, gate_for_slot, filled = (
        tok_for_slot[:-1], gate_for_slot[:-1], filled[:-1])

    xe = jnp.take(tokens, tok_for_slot, axis=0)           # [E*C, d]
    xe = jnp.where(filled[:, None], xe, 0).reshape(e, capacity, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])  # [E, C, d]
    ye = ye.reshape(e * capacity, d) * gate_for_slot[:, None].astype(ye.dtype)
    y = jnp.zeros((n_tok, d), ye.dtype).at[tok_for_slot].add(
        jnp.where(filled[:, None], ye, 0))

    if cfg.dense_ff:
        dp = params["dense"]
        y = y + layers.swiglu(tokens, dp["gate"], dp["up"], dp["down"])

    # Switch-style load-balance loss
    if tmask is None:
        me = jnp.mean(probs, axis=0)                          # mean router prob
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e), axis=0)  # top-1 load
    else:
        w = tmask.astype(jnp.float32)[:, None]
        denom = jnp.maximum(jnp.sum(w), 1.0)
        me = jnp.sum(probs * w, axis=0) / denom
        ce = jnp.sum(jax.nn.one_hot(expert_idx[:, 0], e) * w, axis=0) / denom
    aux = cfg.aux_loss_coef * e * jnp.sum(me * ce)

    return y.reshape(b, s, d), aux

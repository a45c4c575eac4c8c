"""Paper-faithful CV substrate: ResNet-20 (BN / GN / EvoNorm-S0 variants) and
VGG-11 (width 1/2, no normalization) for CIFAR-style 32x32 inputs.

These are the models of Table 1/5; the normalization study (§5.1 "BN and its
alternatives") is reproduced by switching ``norm``:

  * ``bn``      — BatchNorm with *local* statistics per decentralized node
                  (running stats live in a separate state pytree; only the
                  affine weights are gossiped, as in Goyal'17/Andreux'20);
  * ``gn``      — GroupNorm, 2 groups (Hsieh et al., 2020);
  * ``evonorm`` — EvoNorm-S0 (Liu et al., 2020), no batch statistics —
                  the paper's recommended replacement.

Functional API: ``init(key)`` -> (params, state); ``apply(params, state, x,
train)`` -> (logits, new_state).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_init(key, k, cin, cout):
    fan_in = k * k * cin
    std = jnp.sqrt(2.0 / fan_in)  # He init (paper: He et al. 2015)
    return jax.random.normal(key, (k, k, cin, cout)) * std


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def _init_norm(norm: str, c: int):
    p = {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}
    if norm == "evonorm":
        p["v"] = jnp.ones((c,))
    s = {}
    if norm == "bn":
        s = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
    return p, s


def _apply_norm(norm: str, p, s, x, train: bool, momentum=0.9, groups=2,
                eps=1e-5):
    if norm == "none":
        return x, s
    if norm == "bn":
        if train:
            mean = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.var(x, axis=(0, 1, 2))
            new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                     "var": momentum * s["var"] + (1 - momentum) * var}
        else:
            mean, var = s["mean"], s["var"]
            new_s = s
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        return y * p["scale"] + p["bias"], new_s
    if norm == "gn":
        b, h, w, c = x.shape
        g = groups
        xg = x.reshape(b, h, w, g, c // g)
        mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
        var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
        y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(b, h, w, c)
        return y * p["scale"] + p["bias"], s
    if norm == "evonorm":  # S0: x * sigmoid(v x) / group_std
        b, h, w, c = x.shape
        g = groups
        xg = x.reshape(b, h, w, g, c // g)
        std = jnp.sqrt(jnp.var(xg, axis=(1, 2, 4), keepdims=True) + eps)
        num = x * jax.nn.sigmoid(p["v"] * x)
        y = num / jnp.broadcast_to(std, xg.shape).reshape(b, h, w, c)
        return y * p["scale"] + p["bias"], s
    raise ValueError(norm)


# ---------------------------------------------------------------------------
# ResNet-20 (width-scalable: the paper's ResNet-20-x2 for ImageNet-32)
# ---------------------------------------------------------------------------

def init_resnet20(key, *, norm: str = "evonorm", width: int = 1,
                  num_classes: int = 10):
    base = (16 * width, 32 * width, 64 * width)
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    params: dict[str, Any] = {"stem": _conv_init(keys[next(ki)], 3, 3, base[0])}
    state: dict[str, Any] = {}
    pn, sn = _init_norm(norm, base[0])
    params["stem_norm"], state["stem_norm"] = pn, sn
    cin = base[0]
    for s_idx, cout in enumerate(base):
        for b_idx in range(3):
            stride = 2 if (s_idx > 0 and b_idx == 0) else 1
            blk, blk_s = {}, {}
            blk["conv1"] = _conv_init(keys[next(ki)], 3, cin, cout)
            blk["norm1"], blk_s["norm1"] = _init_norm(norm, cout)
            blk["conv2"] = _conv_init(keys[next(ki)], 3, cout, cout)
            blk["norm2"], blk_s["norm2"] = _init_norm(norm, cout)
            if stride != 1 or cin != cout:
                blk["proj"] = _conv_init(keys[next(ki)], 1, cin, cout)
            name = f"s{s_idx}b{b_idx}"
            params[name], state[name] = blk, blk_s
            cin = cout
    params["head"] = jax.random.normal(keys[next(ki)], (cin, num_classes)) \
        / jnp.sqrt(cin)
    params["head_b"] = jnp.zeros((num_classes,))
    return params, state


def apply_resnet20(params, state, x, *, norm: str = "evonorm",
                   train: bool = True):
    new_state = {}
    h = _conv(x, params["stem"])
    h, new_state["stem_norm"] = _apply_norm(
        norm, params["stem_norm"], state["stem_norm"], h, train)
    if norm != "evonorm":
        h = jax.nn.relu(h)
    widths = 3
    for s_idx in range(3):
        for b_idx in range(3):
            name = f"s{s_idx}b{b_idx}"
            blk, blk_s = params[name], state[name]
            stride = 2 if (s_idx > 0 and b_idx == 0) else 1
            ns = {}
            y = _conv(h, blk["conv1"], stride)
            y, ns["norm1"] = _apply_norm(norm, blk["norm1"], blk_s["norm1"],
                                         y, train)
            if norm != "evonorm":
                y = jax.nn.relu(y)
            y = _conv(y, blk["conv2"])
            y, ns["norm2"] = _apply_norm(norm, blk["norm2"], blk_s["norm2"],
                                         y, train)
            sc = h if "proj" not in blk else _conv(h, blk["proj"], stride)
            h = jax.nn.relu(y + sc) if norm != "evonorm" else y + sc
            new_state[name] = ns
    h = jnp.mean(h, axis=(1, 2))
    return h @ params["head"] + params["head_b"], new_state


# ---------------------------------------------------------------------------
# ResNet-20 over a block of nodes at once (DESIGN.md §15)
# ---------------------------------------------------------------------------

NODE_BATCHED_NORMS = ("evonorm", "gn")


def node_batched_serves(norm: str, hw: int) -> bool:
    """Whether :func:`apply_resnet20_nodes` serves this model here: a norm
    it has, ``hw x hw`` inputs whose stride-2 layers see even sizes (``hw``
    a multiple of 4), and the Pallas kernels native on this platform (off
    a TPU their jnp oracle runs slower than ``jax.vmap``; DESIGN.md §15)."""
    from repro.kernels import ops
    return (norm in NODE_BATCHED_NORMS and hw % 4 == 0
            and ops.node_kernels_native())


def _norm_nodes(norm: str, p, x, batch: int, impl, groups=2, eps=1e-5):
    """``_apply_norm`` for ``x[n, C, B*H*W]``: statistics per node, image
    and group, over the group's channels and the image's pixels.  EvoNorm
    runs through ``kernels.node_norm``; GroupNorm in XLA."""
    from repro.kernels import ops
    n, c, m = x.shape
    if norm == "evonorm":
        return ops.node_evonorm(x, p["v"], p["scale"], p["bias"],
                                hw=m // batch, impl=impl)
    if norm == "gn":
        xg = x.reshape(n, groups, c // groups, batch, m // batch)
        mean = jnp.mean(xg, axis=(2, 4), keepdims=True)
        var = jnp.var(xg, axis=(2, 4), keepdims=True)
        y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(n, c, m)
        return y * p["scale"][:, :, None] + p["bias"][:, :, None]
    raise ValueError(f"node-batched ResNet-20: norm {norm!r} "
                     f"(one of {NODE_BATCHED_NORMS})")


def apply_resnet20_nodes(params, x, *, norm: str = "evonorm",
                         impl: str | None = None):
    """``apply_resnet20`` for every node of a block at once: ``params``
    node-stacked ``[n, ...]``, ``x[n, B, H, W, 3]`` -> logits ``[n, B,
    classes]``.  Activations stay channel-major, ``[n, C, B*H*W]``, and
    every convolution runs through ``kernels.node_conv`` (Pallas on a TPU,
    its jnp oracle elsewhere).  EvoNorm-S0 and GroupNorm only: they keep no
    running statistics, so train and eval are the same function."""
    from repro.kernels import ops
    n, b, hh, ww, cin = x.shape
    h = jnp.transpose(x, (0, 4, 1, 2, 3)).reshape(n, cin, b * hh * ww)

    def conv(v, w, size, stride=1):
        return ops.node_conv2d(v, w, height=size, width=size, stride=stride,
                               impl=impl)

    def norm_(v, p):
        return _norm_nodes(norm, p, v, b, impl)

    size = hh
    h = norm_(conv(h, params["stem"], size), params["stem_norm"])
    if norm != "evonorm":
        h = jax.nn.relu(h)
    for s_idx in range(3):
        for b_idx in range(3):
            blk = params[f"s{s_idx}b{b_idx}"]
            stride = 2 if (s_idx > 0 and b_idx == 0) else 1
            y = norm_(conv(h, blk["conv1"], size, stride), blk["norm1"])
            if norm != "evonorm":
                y = jax.nn.relu(y)
            sc = h if "proj" not in blk else conv(h, blk["proj"], size,
                                                  stride)
            size //= stride
            y = norm_(conv(y, blk["conv2"], size), blk["norm2"])
            h = jax.nn.relu(y + sc) if norm != "evonorm" else y + sc
    pooled = jnp.mean(h.reshape(n, h.shape[1], b, size * size), axis=-1)
    # the head as a product and a sum over channels: a batched dot this
    # small is lowered on a TPU as a convolution over the node axis again
    logits = jnp.sum(pooled[:, :, :, None] * params["head"][:, :, None, :],
                     axis=1)
    return logits + params["head_b"][:, None, :]


# ---------------------------------------------------------------------------
# VGG-11 (width factor 1/2, no normalization — Table 1 bottom)
# ---------------------------------------------------------------------------

_VGG11 = (64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M")


def init_vgg11(key, *, width_factor: float = 0.5, num_classes: int = 10):
    keys = jax.random.split(key, 16)
    ki = iter(range(16))
    params = {"convs": []}
    cin = 3
    for v in _VGG11:
        if v == "M":
            continue
        cout = int(v * width_factor)
        params["convs"].append(_conv_init(keys[next(ki)], 3, cin, cout))
        cin = cout
    params["convs"] = tuple(params["convs"])
    params["head"] = jax.random.normal(keys[next(ki)], (cin, num_classes)) \
        / jnp.sqrt(cin)
    params["head_b"] = jnp.zeros((num_classes,))
    return params, {}


def apply_vgg11(params, state, x, *, train: bool = True):
    ci = 0
    h = x
    for v in _VGG11:
        if v == "M":
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        else:
            h = jax.nn.relu(_conv(h, params["convs"][ci]))
            ci += 1
    h = jnp.mean(h, axis=(1, 2))
    return h @ params["head"] + params["head_b"], state

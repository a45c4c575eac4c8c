"""Plain reference of ``resnet20_evonorm_cifar``: ResNet-20 (He et al. 2016,
CIFAR variant: 3 stages of 3 basic blocks, widths 16/32/64) with EvoNorm-S0
(Liu et al. 2020) in place of BatchNorm, as in Lin et al. (ICML 2021) §5.1.

Written from the papers in straightforward ``jax.numpy``; it imports nothing
of the program.  The parameter tree uses the program's key names so that the
weights made here can be handed to the program's trainer.

EvoNorm-S0: ``y = x * sigmoid(v x) / group_std(x) * gamma + beta``; there is
no ReLU after a norm or after the residual add (the norm is the nonlinearity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _stage_shapes(cfg):
    """(name, stride, cin, cout, has_proj) per block."""
    out, cin = [], cfg["widths"][0]
    for s, cout in enumerate(cfg["widths"]):
        for b in range(cfg["blocks_per_stage"]):
            stride = 2 if (s > 0 and b == 0) else 1
            out.append((f"s{s}b{b}", stride, cin, cout,
                        stride != 1 or cin != cout))
            cin = cout
    return out


def init_params(key, cfg):
    """He-normal convolutions, N(0, 1/c) head, EvoNorm gamma=v=1, beta=0.
    Returns ``(params, model_state)``; EvoNorm-S0 keeps no statistics."""
    blocks = _stage_shapes(cfg)
    keys = iter(jax.random.split(key, 2 + 3 * len(blocks)))

    def conv(k, cin, cout):
        std = (2.0 / (k * k * cin)) ** 0.5
        return jax.random.normal(next(keys), (k, k, cin, cout)) * std

    def norm(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,)),
                "v": jnp.ones((c,))}

    c0 = cfg["widths"][0]
    params = {"stem": conv(3, cfg["channels"], c0), "stem_norm": norm(c0)}
    state = {"stem_norm": {}}
    for name, _, cin, cout, proj in blocks:
        blk = {"conv1": conv(3, cin, cout), "norm1": norm(cout),
               "conv2": conv(3, cout, cout), "norm2": norm(cout)}
        if proj:
            blk["proj"] = conv(1, cin, cout)
        params[name] = blk
        state[name] = {"norm1": {}, "norm2": {}}
    c = cfg["widths"][-1]
    params["head"] = jax.random.normal(next(keys), (c, cfg["num_classes"])) \
        / jnp.sqrt(c)
    params["head_b"] = jnp.zeros((cfg["num_classes"],))
    return params, state


def _conv(x, w, stride=1):
    """'SAME' convolution as one matrix product: the k x k shifted, strided
    views of the zero-padded input, side by side, times the filter."""
    k, _, cin, cout = w.shape
    _, h, wd, _ = x.shape
    ho, wo = -(-h // stride), -(-wd // stride)
    ph = max((ho - 1) * stride + k - h, 0)
    pw = max((wo - 1) * stride + k - wd, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    cols = [xp[:, i:i + (ho - 1) * stride + 1:stride,
               j:j + (wo - 1) * stride + 1:stride, :]
            for i in range(k) for j in range(k)]
    return jnp.concatenate(cols, axis=-1) @ w.reshape(k * k * cin, cout)


def _evonorm_s0(x, p, groups, eps):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    std = jnp.broadcast_to(jnp.sqrt(var + eps), xg.shape).reshape(x.shape)
    return x * jax.nn.sigmoid(p["v"] * x) / std * p["scale"] + p["bias"]


def loss(params, batch, cfg):
    """Mean cross-entropy of one node's batch ``(images [B,H,W,C], labels
    [B])``, computed in the dtype of ``params``."""
    images, labels = batch
    dtype = jax.tree.leaves(params)[0].dtype
    g, eps = cfg["evonorm_groups"], cfg["evonorm_eps"]
    h = _conv(images.astype(dtype), params["stem"])
    h = _evonorm_s0(h, params["stem_norm"], g, eps)
    for name, stride, _, _, proj in _stage_shapes(cfg):
        p = params[name]
        y = _evonorm_s0(_conv(h, p["conv1"], stride), p["norm1"], g, eps)
        y = _evonorm_s0(_conv(y, p["conv2"]), p["norm2"], g, eps)
        h = y + (_conv(h, p["proj"], stride) if proj else h)
    logits = jnp.mean(h, axis=(1, 2)) @ params["head"] + params["head_b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels.astype(jnp.int32)[:, None],
                               axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def param_count(cfg) -> int:
    """Parameters of one node, from the layer shapes."""
    c0, ch = cfg["widths"][0], cfg["channels"]
    total = 9 * ch * c0 + 3 * c0
    for _, _, cin, cout, proj in _stage_shapes(cfg):
        total += 9 * cin * cout + 9 * cout * cout + 6 * cout
        total += cin * cout if proj else 0
    k = cfg["num_classes"]
    return total + cfg["widths"][-1] * k + k


def forward_flops_per_sample(cfg) -> int:
    """Multiply-adds x 2 of every convolution and of the head, from the
    layer shapes at ``image_hw`` ('SAME' padding: output = ceil(in/stride)).
    Every tap counts, those on the zero border too, as the MXU computes
    them.  Norms, the pool and the loss are elementwise and not counted."""
    hw, c0 = cfg["image_hw"], cfg["widths"][0]
    total = 2 * hw * hw * 9 * cfg["channels"] * c0
    for _, stride, cin, cout, proj in _stage_shapes(cfg):
        hw = -(-hw // stride)
        total += 2 * hw * hw * 9 * (cin * cout + cout * cout)
        total += 2 * hw * hw * cin * cout if proj else 0
    return total + 2 * cfg["widths"][-1] * cfg["num_classes"]


def train_flops_per_node_step(cfg, traffic) -> int:
    """Forward + backward (x3, nothing recomputed) over one node's batch."""
    return 3 * forward_flops_per_sample(cfg) * traffic["batch"]

"""Model/loss plugins for the declarative experiment layer.

A plugin is a factory ``factory(spec, task) -> ModelBundle`` registered
under a name; ``ModelSpec(name, kwargs)`` selects and parameterizes it.
``task`` is the built data task (``repro.api.data.Task``) so plugins can
read input dims / class counts.  The bundle carries the three callables the
trainer needs:

* ``init_fn(key) -> (params, model_state)``       (single-node; the trainer
  broadcasts to the node-stacked layout)
* ``loss_fn(params_i, mstate_i, batch_i, rng_i) -> (loss, (mstate, metrics))``
* ``eval_fn(params_i, mstate_i, batch) -> {metric_sums..., 'count'}`` or
  ``None`` when the experiment has no eval protocol (LM presets).

and may carry a fourth, ``loss_nodes_fn``: ``loss_fn`` over a block of nodes
at once, for models with a node-batched path that pays on this platform
(ResNet-20 with EvoNorm or GroupNorm on a TPU).

Register your own with ``@register_model("myname")`` and reference it from a
spec as ``ModelSpec(name="myname", kwargs={...})`` — that is the whole
"examples shrink to spec + a model plugin" contract.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ModelBundle", "MODELS", "register_model", "model_vocab",
           "resolve_transformer_config"]


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    init_fn: Callable
    loss_fn: Callable
    eval_fn: Optional[Callable] = None
    # the same loss over a block of nodes at once (node-stacked params,
    # model state, batch and rngs -> ``[b]`` losses); the runtimes take it
    # for every local block where the model offers it (DESIGN.md §15)
    loss_nodes_fn: Optional[Callable] = None


MODELS: dict[str, Callable[..., ModelBundle]] = {}

# datasets each built-in plugin can consume (spec.validate() cross-check);
# custom-registered plugins absent from this map are unconstrained
MODEL_DATASETS: dict[str, tuple[str, ...]] = {
    "mlp": ("classification",),
    "resnet20": ("classification",),
    "transformer": ("lm_domains",),
}


def register_model(name: str):
    def deco(fn):
        MODELS[name] = fn
        return fn
    return deco


def _pop_kwargs(spec, allowed: dict) -> dict:
    kw = dict(spec.model.kwargs)
    out = {k: kw.pop(k, default) for k, default in allowed.items()}
    if kw:
        raise ValueError(
            f"model {spec.model.name!r}: unknown kwargs {sorted(kw)}; "
            f"valid: {sorted(allowed)}")
    return out


def _ce(logits, yb):
    yb = yb.astype(jnp.int32)
    return jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, yb[:, None], -1)[:, 0])


# ---------------------------------------------------------------------------
# mlp — the quickstart / benchmark substrate
# ---------------------------------------------------------------------------

@register_model("mlp")
def _mlp(spec, task) -> ModelBundle:
    """One-hidden-layer ReLU MLP on flattened images.  ``init='lecun'``
    (1/sqrt(fan-in), the benchmark calibration) or ``init='quickstart'``
    (the quickstart example's fixed scales, kept for its pinned
    trajectory)."""
    kw = _pop_kwargs(spec, {"width": 64, "init": "lecun"})
    width, init = int(kw["width"]), kw["init"]
    d_in, classes = task.d_in, task.n_classes

    def init_fn(key):
        k1, k2 = jax.random.split(key)
        if init == "quickstart":
            s1, s2 = 0.05, 0.1
        elif init == "lecun":
            s1, s2 = 1.0 / np.sqrt(d_in), 1.0 / np.sqrt(width)
        else:
            raise ValueError(f"mlp: unknown init {init!r}; "
                             "'lecun' | 'quickstart'")
        return ({"w1": jax.random.normal(k1, (d_in, width)) * s1,
                 "b1": jnp.zeros(width),
                 "w2": jax.random.normal(k2, (width, classes)) * s2,
                 "b2": jnp.zeros(classes)}, {})

    def apply(p, xb):
        xb = xb.reshape(xb.shape[0], -1)
        return jax.nn.relu(xb @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def loss_fn(p, _ms, batch, _rng):
        xb, yb = batch
        return _ce(apply(p, xb), yb), ({}, {})

    def eval_fn(p, _ms, batch):
        xb, yb = batch
        logits = apply(p, xb)
        yi = yb.astype(jnp.int32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, yi[:, None], axis=1)[:, 0]
        return {"acc": jnp.sum(jnp.argmax(logits, -1) == yi),
                "eval_loss": jnp.sum(nll),
                "count": jnp.asarray(len(yb), jnp.float32)}

    return ModelBundle(init_fn, loss_fn, eval_fn)


# ---------------------------------------------------------------------------
# resnet20 — the paper's CV substrate (EvoNorm/GN/BN; local-statistics BN)
# ---------------------------------------------------------------------------

@register_model("resnet20")
def _resnet20(spec, task) -> ModelBundle:
    from repro.models import resnet

    kw = _pop_kwargs(spec, {"norm": "evonorm", "width": 1})
    norm, width = kw["norm"], int(kw["width"])

    def init_fn(key):
        return resnet.init_resnet20(key, norm=norm, width=width,
                                    num_classes=task.n_classes)

    def loss_fn(p, s, batch, _rng):
        xb, yb = batch
        logits, ns = resnet.apply_resnet20(p, s, xb, norm=norm, train=True)
        return _ce(logits, yb), (ns, {})

    loss_nodes_fn = None
    if resnet.node_batched_serves(norm, spec.data.hw):
        def loss_nodes_fn(p, s, batch, _rngs):
            xb, yb = batch
            logits = resnet.apply_resnet20_nodes(p, xb, norm=norm)
            return jax.vmap(_ce)(logits, yb), (s, {})

    def eval_fn(p, s, batch):
        xb, yb = batch
        logits, _ = resnet.apply_resnet20(p, s, xb, norm=norm, train=False)
        pred = jnp.argmax(logits, -1)
        return {"acc": jnp.sum(pred == yb.astype(jnp.int32)),
                "count": jnp.asarray(len(yb), jnp.float32)}

    return ModelBundle(init_fn, loss_fn, eval_fn, loss_nodes_fn)


# ---------------------------------------------------------------------------
# transformer — any configs/ arch (reduced or full), LM loss
# ---------------------------------------------------------------------------

_TRANSFORMER_KW = {"arch": "tinyllama-1.1b", "reduced": False,
                   "overrides": None, "chunk": None, "ssd_chunk": None}


def resolve_transformer_config(model_spec):
    """ModelSpec -> ModelConfig (arch lookup + reduced + field overrides).
    Shared with the lm_domains data builder, which reads the vocab off it."""
    from repro.configs import get_config

    kw = dict(model_spec.kwargs)
    arch = kw.get("arch", _TRANSFORMER_KW["arch"])
    cfg = get_config(arch, reduced=bool(kw.get("reduced", False)))
    overrides = kw.get("overrides") or {}
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def model_vocab(spec) -> int | None:
    """The vocab the model expects, for data builders (None: no vocab)."""
    if spec.model.name == "transformer":
        return resolve_transformer_config(spec.model).vocab_size
    return None


@register_model("transformer")
def _transformer(spec, task) -> ModelBundle:
    from repro.models import transformer as tf

    kw = _pop_kwargs(spec, _TRANSFORMER_KW)
    cfg = resolve_transformer_config(spec.model)
    fwd_kw = {}
    if kw["chunk"] is not None:
        fwd_kw["chunk"] = int(kw["chunk"])
    if kw["ssd_chunk"] is not None:
        fwd_kw["ssd_chunk"] = int(kw["ssd_chunk"])

    img = None
    if cfg.n_image_tokens:
        rng = np.random.default_rng(task.seed)
        img = jnp.asarray(rng.normal(
            size=(cfg.n_image_tokens, cfg.d_model)).astype(np.float32))

    def init_fn(key):
        return tf.init_lm(key, cfg), {}

    # each period of the layer scan is rematerialised in the backward pass
    # (DESIGN.md §16): on a v5e one mamba2-130m node's gradient at 4x512
    # tokens takes 44 ms rematerialised and 63 ms with its activations
    # kept, and at 4x2048 only the rematerialised step fits the chip
    def loss_fn(params, _ms, batch, _rng):
        (toks,) = batch
        b = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if img is not None:
            b["image_embeds"] = jnp.broadcast_to(
                img, (toks.shape[0],) + img.shape)
        return tf.train_loss(params, b, cfg, remat="full",
                             **fwd_kw), ({}, {})

    return ModelBundle(init_fn, loss_fn, eval_fn=None)

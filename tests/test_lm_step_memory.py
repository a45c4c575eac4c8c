"""The language-model step at lengths where its activations would not fit a
chip: the blocked output head and loss, the rematerialised layer scan of
the training loss, and the gossip bytes counter of the compiled
schedule."""
from __future__ import annotations

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import gossip, topology
from repro.models import layers
from repro.models import transformer as tf
from repro.telemetry import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _whole(h, head, labels, vocab_valid, cap):
    logits = layers.softcap(
        jnp.einsum("...d,dv->...v", h, head).astype(jnp.float32), cap)
    return layers.cross_entropy(logits, labels, vocab_valid)


@pytest.mark.parametrize("tokens,block,cap", [
    (2 * 12, 10, 0.0),        # three blocks, the last one ragged
    (2 * 12, 64, 0.0),        # one block
    (2 * 12, 8, 30.0),        # blocks that divide the tokens, soft cap
    (2 * 12, 12, 0.0),        # two whole blocks
])
def test_blocked_head_matches_whole_logits(tokens, block, cap):
    """Loss and gradients (hidden state and head) of the blocked head
    against ``cross_entropy`` on the whole logits, with padded vocabulary
    rows masked, in fp32 at 'highest'; undifferentiated, the same loss."""
    d, v, valid = 16, 40, 33
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(k[0], (2, tokens // 2, d))
    head = jax.random.normal(k[1], (d, v)) * 0.5
    labels = jax.random.randint(k[2], (2, tokens // 2), 0, valid)
    with jax.default_matmul_precision("highest"):
        want, (gh_w, gw_w) = jax.value_and_grad(
            lambda a, b: _whole(a, b, labels, valid, cap), (0, 1))(h, head)
        got, (gh, gw) = jax.value_and_grad(
            lambda a, b: layers.blocked_cross_entropy(
                a, b, labels, valid, cap=cap, block=block), (0, 1))(h, head)
        plain = layers.blocked_cross_entropy(h, head, labels, valid, cap=cap,
                                             block=block)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(plain, want, rtol=1e-6)
    np.testing.assert_allclose(gh, gh_w, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gw, gw_w, rtol=1e-5, atol=1e-7)


def _mamba_reduced():
    cfg = get_config("mamba2-130m", reduced=True)
    params = tf.init_lm(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0,
                              cfg.vocab_size)
    return cfg, params, {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_remat_matches_plain_forward(monkeypatch):
    """Each period rematerialised: the same loss and gradients as the
    forward pass whose activations are kept, for the reduced mamba2-130m,
    the head in several blocks."""
    cfg, params, batch = _mamba_reduced()
    monkeypatch.setattr(tf, "HEAD_BLOCK", 48)

    def loss(p, remat):
        return tf.train_loss(p, batch, cfg, remat=remat)

    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(loss)(params, "none")
        got, g_got = jax.value_and_grad(loss)(params, "full")
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def _lm_spec(runtime="vmap", n=2):
    from repro.api.spec import ExperimentSpec
    return ExperimentSpec.from_dict({
        "name": "lm", "seed": 3, "runtime": runtime,
        "data": {"dataset": "lm_domains", "vocab": 256, "batch": 2,
                 "seq_len": 32, "n_seq_per_domain": 16, "alpha": 1.0,
                 "min_per_client": 2},
        "topology": {"name": "ring", "n": n},
        "optim": {"name": "qg_dsgdm_n", "lr": 0.02},
        "loop": {"steps": 2},
        "eval": {"enabled": False},
        "model": {"name": "transformer",
                  "kwargs": {"arch": "mamba2-130m", "reduced": True}},
    }).validate()


@pytest.fixture
def registry():
    reg = trace.enable()
    reg.reset()
    yield reg
    trace.disable()


def _has_remat(jaxpr) -> bool:
    return re.search(r"\b(remat2?|checkpoint)\[", str(jaxpr)) is not None


@pytest.mark.parametrize("arch", ["mamba2-130m", "tinyllama-1.1b",
                                  "gemma2-27b"])
def test_training_loss_rematerialises_the_layer_scan(arch):
    """The transformer bundle's training loss rematerialises each period
    of the layer scan in its backward pass, and gives the loss and
    gradients of the loss whose activations are kept (reduced configs,
    fp32 at 'highest')."""
    from repro.api.models import MODELS
    spec = _lm_spec().replace(model={"name": "transformer",
                                     "kwargs": {"arch": arch,
                                                "reduced": True}})
    bundle = MODELS["transformer"](spec, None)
    cfg = get_config(arch, reduced=True)
    params, _ = bundle.init_fn(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0,
                              cfg.vocab_size)

    def bundle_loss(p):
        return bundle.loss_fn(p, {}, (toks,), None)[0]

    def kept(p):
        return tf.train_loss(p, {"tokens": toks[:, :-1],
                                 "labels": toks[:, 1:]}, cfg, remat="none")

    assert _has_remat(jax.make_jaxpr(jax.grad(bundle_loss))(params))
    assert not _has_remat(jax.make_jaxpr(jax.grad(kept))(params))
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(bundle_loss)(params)
        want, g_want = jax.value_and_grad(kept)(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_gossip_bytes_counter_ring4(registry):
    """One gossip step of a ring of 4 on the compiled schedule: two rounds,
    each node sends its whole tree in both, and the counter says so."""
    topo = topology.get_topology("ring", 4)
    sched = gossip.compile_gossip_schedule(topo)
    w = jnp.asarray(topo.mixing[0], jnp.float32)
    tree = {"a": jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6),
            "b": jnp.ones((4, 3, 5), jnp.bfloat16)}
    one_node = 6 * 4 + 15 * 2
    assert sched.bytes_sent(jax.tree.map(lambda l: l[0], tree)) \
        == 2 * one_node
    mix = gossip.make_local_mix_fn(sched, axis_name="data", w_ref=w)
    out = jax.vmap(lambda t: mix(w, t), axis_name="data")(tree)
    assert registry.counters["tm/gossip/bytes"] == 2 * one_node
    want = gossip.mix_dense(w, tree)
    np.testing.assert_allclose(out["a"], want["a"], rtol=1e-6)


_SHARDED_VS_VMAP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "tests")
import jax, numpy as np
from repro import api
from repro.launch.mesh import make_debug_mesh
from repro.models import transformer as tf
from repro.telemetry import trace
from test_lm_step_memory import _lm_spec

tf.HEAD_BLOCK = 24                                    # 64 tokens: 3 blocks
reg = trace.enable()
with jax.default_matmul_precision("highest"):
    spec = _lm_spec(n=4).replace(loop={"steps": 3})
    v = api.run(spec, log_fn=lambda *_: None)
    reg.reset()
    s = api.run(spec.replace(runtime="sharded"),
                mesh=make_debug_mesh((4,), ("data",)),
                log_fn=lambda *_: None)
lv = [h["loss"] for h in v.history]
ls = [h["loss"] for h in s.history]
print("LOSSES", lv, ls)
print("COUNTS", dict(reg.counters))
np.testing.assert_allclose(ls, lv, rtol=2e-5)
assert reg.counters["tm/gossip/bytes"] > 0
print("SHARDED_MATCHES_VMAP")
"""


def test_sharded_matches_vmap_with_remat_and_blocked_head():
    """The reduced mamba2 on 4 virtual devices, ``runtime=sharded`` against
    ``runtime=vmap``: the first 3 steps agree within fp32 tolerance with
    the rematerialised scan and a head in three blocks on both, and the
    sharded step counts its gossip bytes."""
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_VS_VMAP], capture_output=True,
        text=True, timeout=600, cwd=REPO,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert "SHARDED_MATCHES_VMAP" in out.stdout, (out.stdout[-2000:],
                                                  out.stderr[-4000:])

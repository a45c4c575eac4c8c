"""The work counts kept with the benchmark, against figures the program
gives: the parameters ``repro.api``'s model plugins hold per node, the
shapes of the weights that multiply every token, and XLA's own count of a
forward pass."""
from __future__ import annotations

import types

import jax
import numpy as np
import pytest

from bench import harness


def _program_param_shapes(cell):
    """Shapes of one node's parameters as the program's model plugin
    initialises them (no memory: ``jax.eval_shape``)."""
    from repro.api.models import MODELS
    spec = harness.make_spec(cell, 0)
    task = types.SimpleNamespace(n_classes=cell.config.get("num_classes"),
                                 seed=0)
    bundle = MODELS[spec.model.name](spec, task)
    return jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))[0]


@pytest.mark.parametrize("name,count", [
    ("resnet20_ring16_dir0.1_hybrid", 272_970),
    ("mamba2_130m_ring4_sharded", 167_752_128),
])
def test_param_count_matches_the_program(cell, name, count):
    c = cell(name)
    held = sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(_program_param_shapes(c)))
    assert c.model.param_count(c.config) == held == count \
        == c.config["params_per_node"]


@pytest.mark.parametrize("name", ["resnet20_ring16_dir0.1_hybrid",
                                  "mamba2_130m_ring4_sharded"])
def test_reference_tree_matches_the_program(cell, name):
    """The weights the configuration makes fit the program's layout."""
    c = cell(name)
    prog = _program_param_shapes(c)
    ours = jax.eval_shape(lambda k: c.model.init_params(k, c.config)[0],
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(ours)
    assert [l.shape for l in jax.tree.leaves(prog)] == \
        [l.shape for l in jax.tree.leaves(ours)]


def test_mamba_matmul_weights_match_the_program(cell):
    c = cell("mamba2_130m_ring4_sharded")
    p = _program_param_shapes(c)
    mixer = p["blocks"][0]["mixer"]
    per_token = (int(np.prod(mixer["in_proj"].shape))
                 + int(np.prod(mixer["out_proj"].shape))
                 + p["lm_head"].shape[0] * c.config["vocab_size"])
    assert c.model.matmul_params(c.config) == per_token
    # the head computes every real row of the published vocabulary
    assert p["lm_head"].shape[1] >= c.config["vocab_size"]


def test_mamba_train_flops(cell):
    c = cell("mamba2_130m_ring4_sharded")
    cfg, t = c.config, c.traffic
    tokens = t["batch"] * t["seq_len"]
    ssd = 2 * 128 * 128 + 2 * 128 * 1536 + 4 * 128 * 1536
    want = tokens * (6 * c.model.matmul_params(cfg) + 3 * 24 * ssd)
    assert c.model.train_flops_per_node_step(cfg, t) == want
    # ~1.76 TFLOP per 2048 tokens (two nodes at 2x512), SSD about a tenth
    assert 1.7e12 < want * 2048 / tokens < 1.8e12
    assert 0.08 < 3 * 24 * ssd / (6 * c.model.matmul_params(cfg)) < 0.14


def test_resnet_forward_flops_against_xla():
    """Convolutions and head by shapes, against XLA's count of the
    program's forward pass.  The two differ by design: ours counts every
    tap of a 'SAME' convolution (the padded border is computed all the
    same), XLA's leaves out the taps on padding and adds the norms'
    elementwise work; within a tenth of each other."""
    from repro.models import resnet
    c = harness.load_cell("resnet20_ring16_dir0.1_hybrid")
    params, state = resnet.init_resnet20(jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), np.float32)
    cost = jax.jit(lambda p, s, x: resnet.apply_resnet20(p, s, x)[0]).lower(
        params, state, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = c.model.forward_flops_per_sample(c.config)
    assert ours == 81_626_368
    assert 0.9 * ours < cost["flops"] < 1.1 * ours
    assert c.model.train_flops_per_node_step(c.config, c.traffic) \
        == 3 * ours * 32


def test_rule_bytes():
    """32 bytes per fp32 parameter per node: 4 streams of the half step
    (x, g, m_hat read; half written) and 4 of the buffer (x before and
    after the mix, m_hat read; m_hat written)."""
    assert (3 + 1 + 3 + 1) * 4 == 32

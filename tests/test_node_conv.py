"""Node-batched convolutions and EvoNorm (``kernels/node_conv.py``,
``kernels/node_norm.py``) and the node-batched ResNet-20 path: kernels
against their oracles and against ``jax.vmap`` of
``lax.conv_general_dilated``, the whole model against ``jax.vmap`` of
``apply_resnet20``, and the runtimes' choice of gradient path."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import presets
from repro.kernels import node_conv as nc
from repro.kernels import node_norm
from repro.kernels import ops
from repro.kernels import ref
from repro.models import resnet
from repro.telemetry import trace

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _to_nhwc(x, h):
    n, c, m = x.shape
    return jnp.transpose(x.reshape(n, c, m // (h * h), h, h), (0, 2, 3, 4, 1))


def _to_cm(y):
    n, b, h, w, c = y.shape
    return jnp.transpose(y, (0, 4, 1, 2, 3)).reshape(n, c, b * h * w)


def _lax_conv(x, w, h, stride):
    """The per-node convolution as the per-node model writes it, vmapped
    over nodes, in and out of the channel-major layout."""
    y = jax.vmap(lambda xx, ww: jax.lax.conv_general_dilated(
        xx, ww, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI))(
            _to_nhwc(x, h), w)
    return _to_cm(y)


# (input channels, output channels, kernel size, stride, image size,
#  images per tile); a stride-2 tile holds whole groups of images whose
#  output fills 128 lanes (one image at 32x32, two at 16x16)
CASES = [(3, 16, 3, 1, 8, 1), (16, 16, 3, 1, 8, 2), (16, 32, 3, 2, 32, 3),
         (32, 32, 3, 1, 8, 3), (16, 32, 1, 2, 32, 1), (32, 64, 1, 2, 16, 2),
         (64, 64, 3, 1, 8, 2), (32, 64, 3, 2, 16, 2)]
N, B = 2, 6


@pytest.mark.parametrize("ci,co,k,stride,h,ipt", CASES,
                         ids=[f"{c[0]}to{c[1]}-k{c[2]}-s{c[3]}-t{c[5]}"
                              for c in CASES])
def test_node_conv_matches_oracle_and_lax_conv(ci, co, k, stride, h, ipt):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(ci * 7 + co + k), 3)
    x = jax.random.normal(k1, (N, ci, B * h * h))
    w = jax.random.normal(k2, (N, k, k, ci, co)) * 0.3
    y_lax, vjp = jax.vjp(lambda x_, w_: _lax_conv(x_, w_, h, stride), x, w)
    g = jax.random.normal(k3, y_lax.shape)
    gx_lax, gw_lax = vjp(g)

    # the three kernels (interpret mode) against the oracle and against
    # lax.conv's forward pass and vjp
    kw = dict(height=h, width=h, ksize=k)
    down = up = {}
    if stride == 2:
        down = dict(resample="down", offset=1 if k == 3 else 0)
        up = dict(down, resample="up")
    a, a_dx = nc.fwd_weights(w), nc.dx_weights(w)
    for mxu in (F32, jnp.bfloat16):
        y = nc.conv_taps(a, x, mxu_dtype=mxu, images=ipt, **kw, **down)
        gx = nc.conv_taps(a_dx, g, mxu_dtype=mxu, images=ipt,
                          name="node_conv_dx", **kw, **up)
        ga = nc.conv_taps_dw(x, g, mxu_dtype=mxu, images=ipt, **kw, **up)
        for got, want in (
                (y, ref.node_conv_taps_ref(a, x, mxu_dtype=mxu, **kw,
                                           **down)),
                (gx, ref.node_conv_taps_ref(a_dx, g, mxu_dtype=mxu, **kw,
                                            **up)),
                (ga, ref.node_conv_dw_ref(x, g, mxu_dtype=mxu, **kw, **up))):
            assert got.shape == want.shape
            assert _rel(got, want) < 1e-5
        if mxu == F32:
            assert _rel(y, y_lax) < 1e-5
            assert _rel(gx, gx_lax) < 1e-5
            assert _rel(nc.hwio_weights(ga, k), gw_lax) < 1e-5

    # the differentiable convolution the model calls, through the kernels
    # and through the oracle, against lax.conv
    for impl, interpret in (("pallas", True), ("ref", False)):
        y, vjp = jax.vjp(lambda x_, w_: nc.conv2d(
            x_, w_, height=h, width=h, stride=stride, impl=impl,
            mxu_dtype=F32, interpret=interpret), x, w)
        for got, want in zip((y,) + vjp(g), (y_lax, gx_lax, gw_lax)):
            assert got.shape == want.shape
            assert _rel(got, want) < 1e-5, impl


# (channels, pixels an image, images, images per tile)
NORM_CASES = [(16, 64, 6, 2), (32, 16, 6, 3), (64, 256, 2, 1),
              (16, 64, 4, None)]


@pytest.mark.parametrize("c,hw,b,ipt", NORM_CASES,
                         ids=[f"c{c[0]}-hw{c[1]}-t{c[3]}" for c in NORM_CASES])
def test_node_evonorm_matches_oracle(c, hw, b, ipt):
    """The EvoNorm-S0 kernels (interpret mode) against the jnp oracle and
    its autodiff: ``y``, ``dx`` and the per-channel ``dv, dscale, dbias``."""
    ks = jax.random.split(jax.random.PRNGKey(c + hw), 5)
    x = jax.random.normal(ks[0], (N, c, b * hw)) * 2.0 + 0.5
    v, scale, bias = (jax.random.normal(k, (N, c)) for k in ks[1:4])
    g = jax.random.normal(ks[4], x.shape)
    want, vjp = jax.vjp(lambda *a: ref.node_evonorm_ref(*a, hw=hw),
                        x, v, scale, bias)
    assert _rel(node_norm.evonorm_fwd(x, v, scale, bias, hw=hw, images=ipt),
                want) < 1e-5
    got = node_norm.evonorm_bwd(x, g, v, scale, hw=hw, images=ipt)
    for a, w in zip(got, vjp(g)):
        assert a.shape == w.shape
        assert _rel(a, w) < 1e-5
    # the custom_vjp the model calls
    _, vjp_k = jax.vjp(lambda *a: node_norm.node_evonorm(
        *a, hw=hw, interpret=True), x, v, scale, bias)
    for a, w in zip(vjp_k(g), vjp(g)):
        assert _rel(a, w) < 1e-5


def _ce(logits, y):
    return jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


@pytest.mark.parametrize("norm", resnet.NODE_BATCHED_NORMS)
def test_resnet20_nodes_matches_vmap(norm):
    """Loss and every gradient leaf of the node-batched ResNet-20 (the jnp
    oracle's path) against ``jax.vmap`` of the per-node model, fp32 at
    ``highest``; n=2 nodes of 4 images."""
    n, b, hw = 2, 4, 16
    key = jax.random.PRNGKey(3)
    params = jax.tree.map(lambda *l: jnp.stack(l), *[
        resnet.init_resnet20(jax.random.fold_in(key, i), norm=norm)[0]
        for i in range(n)])
    _, state = resnet.init_resnet20(key, norm=norm)
    x = jax.random.normal(key, (n, b, hw, hw, 3))
    y = jax.random.randint(key, (n, b), 0, 10)

    def per_node(p, xb, yb):
        return _ce(resnet.apply_resnet20(p, state, xb, norm=norm)[0], yb)

    def block(p):
        losses = jax.vmap(_ce)(resnet.apply_resnet20_nodes(p, x, norm=norm),
                               y)
        return jnp.sum(losses), losses

    with jax.default_matmul_precision("highest"):
        want_l, want_g = jax.jit(jax.vmap(jax.value_and_grad(per_node)))(
            params, x, y)
        (_, got_l), got_g = jax.jit(jax.value_and_grad(block, has_aux=True))(
            params)
    assert _rel(got_l, want_l) < 1e-5
    for path, g in jax.tree_util.tree_leaves_with_path(got_g):
        w = _leaf(want_g, path)
        assert g.shape == w.shape
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)


def _leaf(tree, path):
    for p in path:
        tree = tree[p.key]
    return tree


# ---------------------------------------------------------------------------
# which gradient path the runtimes take
# ---------------------------------------------------------------------------

def _spec(norm="evonorm", n=4):
    s = presets.get("cifar_ring16_alpha0.1_qg").override(
        "loop.steps=1", "loop.chunk=1", "loop.log_every=0")
    return s.replace(data={"n_data": 256, "batch": 4, "hw": 8},
                     topology={"n": n},
                     model={"name": "resnet20", "kwargs": {"norm": norm}})


def _first_step(ex):
    reg = trace.enable()
    reg.reset()
    try:
        batch = ex.trainer._runtime.put_batch(next(ex.task.make_iter()))
        state, _ = ex.trainer.step(ex.state, batch, jax.random.PRNGKey(0))
        return state, dict(reg.counters)
    finally:
        trace.disable()


@pytest.fixture
def native(monkeypatch):
    """The node-batched path as on a TPU: the models offer it, and it runs
    its jnp oracle here (the Pallas kernels run natively on a TPU only)."""
    monkeypatch.setattr(ops, "node_kernels_native", lambda: True)


@pytest.mark.parametrize("norm,path", [("evonorm", "node_batched"),
                                       ("gn", "node_batched"),
                                       ("bn", "vmap")])
def test_gradient_path_by_model(native, norm, path):
    """EvoNorm and GroupNorm ResNet-20 blocks take the
    node-batched gradient and count it in the registry; BatchNorm keeps
    ``jax.vmap``.  The node-batched first step equals the vmap one."""
    ex = api.build(_spec(norm))
    assert (ex.trainer.loss_nodes_fn is not None) == (path == "node_batched")
    state, counters = _first_step(ex)
    assert counters == {f"tm/grad/{path}": 1}
    if path == "node_batched":
        ex = api.build(_spec(norm))
        ex.trainer.loss_nodes_fn = None
        want, counters = _first_step(ex)
        assert counters == {"tm/grad/vmap": 1}
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(want.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("norm", resnet.NODE_BATCHED_NORMS)
def test_off_tpu_and_odd_sizes_keep_vmap(native, monkeypatch, norm):
    """The models offer no node-batched loss where the kernels would run
    their oracle (off a TPU, slower than ``jax.vmap``) nor for inputs whose
    stride-2 layers see an odd size (30x30: 15x15 at stage 2), which the
    node-batched convolution refuses with a clear error."""
    assert api.build(_spec(norm)).trainer.loss_nodes_fn is not None
    odd = _spec(norm).replace(data={"n_data": 256, "batch": 4, "hw": 30})
    ex = api.build(odd)
    assert ex.trainer.loss_nodes_fn is None
    p = jax.eval_shape(lambda t: jax.tree.map(lambda l: l[:1], t),
                       ex.state.params)
    _, counters = _first_step(ex)
    assert counters == {"tm/grad/vmap": 1}
    with pytest.raises(ValueError, match="even sizes"):
        jax.eval_shape(lambda q: resnet.apply_resnet20_nodes(
            q, jnp.zeros((1, 2, 30, 30, 3)), norm=norm, impl="ref"), p)
    monkeypatch.setattr(ops, "node_kernels_native", lambda: False)
    ex = api.build(_spec(norm))
    assert ex.trainer.loss_nodes_fn is None
    _, counters = _first_step(ex)
    assert counters == {"tm/grad/vmap": 1}


@pytest.mark.parametrize("asked,mxu", [
    (None, jnp.bfloat16), ("default", jnp.bfloat16),
    ("bfloat16", jnp.bfloat16), ("high", F32), ("highest", F32),
    ("float32", F32)])
def test_kernels_follow_default_matmul_precision(monkeypatch, asked, mxu):
    """On a TPU the kernels' MXU operands follow
    ``jax.default_matmul_precision`` as a float32 ``lax.conv`` does: one
    bfloat16 pass at the default, float32 (``HIGHEST``) when more is
    asked for."""
    seen = {}

    def conv2d(x, w, **kw):
        seen.update(kw)
        return x

    monkeypatch.setattr(ops._nc, "conv2d", conv2d)
    x, w = jnp.zeros((2, 8, 64)), jnp.zeros((2, 3, 3, 8, 8))
    with jax.default_matmul_precision(asked):
        assert ops.node_mxu_dtype() == mxu
        jax.jit(lambda a, b: ops.node_conv2d(a, b, height=8, width=8,
                                             impl="pallas"))(x, w)
    assert seen["impl"] == "pallas" and seen["mxu_dtype"] == mxu
    ops.node_conv2d(x, w, height=8, width=8, impl="ref")
    assert seen["mxu_dtype"] == F32


def test_one_node_blocks_take_node_path_and_other_models_keep_vmap(native):
    """A one-node block takes the node-batched loss like any block where
    the model offers one (on a v5e it measured faster than ``lax.conv``
    there too); a model without one (VGG-11) keeps ``jax.vmap`` at any
    block size."""
    ex = api.build(_spec("evonorm"))
    rt = ex.trainer._runtime
    batch = next(ex.task.make_iter())
    one = lambda tree: jax.tree.map(lambda l: l[:1] if l.ndim else l, tree)
    reg = trace.enable()
    try:
        reg.reset()
        jax.eval_shape(lambda s, b: rt._stage_compute(
            s, b, jax.random.PRNGKey(0), 1), one(ex.state), one(batch))
        assert dict(reg.counters) == {"tm/grad/node_batched": 1}

        def vgg_loss(p, ms, b, _rng):
            logits, _ = resnet.apply_vgg11(p, ms, b[0])
            return _ce(logits, b[1].astype(jnp.int32)), (ms, {})

        from repro.core import optim, topology
        from repro.train import DecentralizedTrainer
        tr = DecentralizedTrainer(vgg_loss, optim.make_optimizer(
            "dsgd", lr=0.1), topology.ring(2))
        st = tr.init(jax.random.PRNGKey(0),
                     lambda k: resnet.init_vgg11(k, width_factor=0.125))
        xb = jnp.zeros((2, 2, 32, 32, 3))
        reg.reset()
        jax.eval_shape(lambda s, b: tr._runtime._stage_compute(
            s, b, jax.random.PRNGKey(0), 2), st, (xb, jnp.zeros((2, 2))))
        assert dict(reg.counters) == {"tm/grad/vmap": 1}
    finally:
        trace.disable()


def test_hybrid_block_takes_node_batched_path(native):
    """``runtime=hybrid`` on a one-device mesh (the benchmark cell's
    layout): the 4-node block takes the node-batched gradient inside the
    shard_map, and its first step equals the vmap runtime's per-node one."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    ex = api.build(_spec().replace(runtime="hybrid"), mesh=mesh)
    state, counters = _first_step(ex)
    assert counters == {"tm/grad/node_batched": 1}
    ex = api.build(_spec().replace(runtime="vmap"))
    ex.trainer.loss_nodes_fn = None
    want, _ = _first_step(ex)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(want.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np
import jax
from repro import api
from repro.api import presets
from repro.kernels import ops
from repro.telemetry import trace

s = presets.get("cifar_ring16_alpha0.1_qg").override(
    "loop.steps=1", "loop.chunk=1", "loop.log_every=0").replace(
    data={"n_data": 128, "batch": 4, "hw": 8}, topology={"n": 2})
reg = trace.enable()

def first(spec, mesh=None):
    ex = api.build(spec, mesh=mesh)
    reg.reset()
    batch = ex.trainer._runtime.put_batch(next(ex.task.make_iter()))
    st, _ = ex.trainer.step(ex.state, batch, jax.random.PRNGKey(0))
    return st, dict(reg.counters)

mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
ops.node_kernels_native = lambda: True   # as on a TPU
sh, c_sh = first(s.replace(runtime="sharded"), mesh)
ops.node_kernels_native = lambda: False  # per-node lax.conv
vm, c_vm = first(s.replace(runtime="vmap"))
grad_paths = {k: v for k, v in c_sh.items() if k.startswith("tm/grad/")}
assert grad_paths == {"tm/grad/node_batched": 1}, c_sh
# a ring of 2: each device sends its 272,970 fp32 parameters once a step
assert c_sh["tm/gossip/bytes"] == 4 * 272_970, c_sh
assert c_vm == {"tm/grad/vmap": 1}, c_vm
for a, b in zip(jax.tree.leaves(sh.params), jax.tree.leaves(vm.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
print("SHARDED_PATH_OK")
"""


def test_sharded_runtime_takes_node_path_and_matches():
    """One node per device: the sharded runtime's one-node blocks take the
    node-batched path, and its first step equals the vmap runtime's on the
    per-node ``lax.conv`` (2 forced host devices)."""
    res = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], capture_output=True,
        text=True, timeout=600, env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SHARDED_PATH_OK" in res.stdout, \
        res.stdout[-1500:] + res.stderr[-3000:]

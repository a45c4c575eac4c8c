"""Compiler rehearsal: every main-path Pallas kernel at real widths, compiled
ahead of time for a described TPU v5e (no chip needed).

Interpret mode cannot see what the chip's compiler refuses (block shapes off
the (8, 128) tiling, broadcasts Mosaic cannot lower, VMEM overruns); this
file can.  Each case lowers with ``interpret=False`` and asserts the kernel
reached the program as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  JAX's persistent compilation cache is off around these compiles (a
TPU executable written here could not be read back without a chip).
"""
from __future__ import annotations

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import (compress, flash_attention, node_conv, node_norm,
                           qg_update, ssd_scan)

PACKED = 1_100_000          # ~1.1M-element packed optimizer buffer
NODES, IMAGES = 16, 32      # the ResNet-20 ring-16 cell: 16 nodes x 32 images


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _cases(sh):
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32

    def s(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    vec = s((PACKED,))
    return {
        "fused_halfstep": (
            lambda x, m, g, e: qg_update.fused_halfstep(
                x, m, g, e, beta=0.9, wd=1e-4, nesterov=True,
                interpret=False),
            (vec, vec, vec, s(()))),
        "fused_qg_buffer": (
            lambda a, b, m, e, r: qg_update.fused_qg_buffer(
                a, b, m, e, r, mu=0.9, interpret=False),
            (vec, vec, vec, s(()), s(()))),
        "gamma_correct": (
            lambda x, mx, h: compress.gamma_correct(
                x, mx, h, gamma=0.5, interpret=False),
            (vec, vec, vec)),
        "threshold_mask": (
            lambda x, t: compress.threshold_mask(x, t, interpret=False),
            (s((64, 32768)), s((64,)))),
        "quantize_dequantize": (
            lambda x, sc, u: compress.quantize_dequantize(
                x, sc, u, levels=16, interpret=False),
            (s((64, 32768)), s((64,)), s((64, 32768)))),
        # tinyllama-1.1b head layout: 32 query heads, 4 KV heads, D=64
        "flash_attention_bf16": (
            lambda q, k, v: flash_attention.flash_attention(
                q, k, v, interpret=False),
            (s((1, 2048, 32, 64), bf16), s((1, 2048, 4, 64), bf16),
             s((1, 2048, 4, 64), bf16))),
        "flash_attention_fp32": (
            lambda q, k, v: flash_attention.flash_attention(
                q, k, v, interpret=False),
            (s((1, 2048, 32, 64)), s((1, 2048, 4, 64)),
             s((1, 2048, 4, 64)))),
        # 8 decode slots over a [pages, KH, page_size, D] pool
        "paged_decode_attention": (
            lambda q, k, v, bt, ln: flash_attention.paged_decode_attention(
                q, k, v, bt, ln, interpret=False),
            (s((8, 1, 32, 64)), s((128, 4, 16, 64)), s((128, 4, 16, 64)),
             s((8, 16), i32), s((8,), i32))),
        # ResNet-20 stage 0 (16 channels at 32x32) over a 16-node block
        "node_conv_fwd": (
            lambda a, x: node_conv.conv_taps(
                a, x, height=32, width=32, ksize=3, interpret=False),
            (s((NODES, 16, 144)), s((NODES, 16, IMAGES * 1024)))),
        "node_conv_dx": (
            lambda a, g: node_conv.conv_taps(
                a, g, height=32, width=32, ksize=3, name="node_conv_dx",
                interpret=False),
            (s((NODES, 16, 144)), s((NODES, 16, IMAGES * 1024)))),
        "node_conv_dw": (
            lambda x, g: node_conv.conv_taps_dw(
                x, g, height=32, width=32, ksize=3, interpret=False),
            (s((NODES, 16, IMAGES * 1024)), s((NODES, 16, IMAGES * 1024)))),
        # stage 1's first convolution: stride 2 from 32x32, the taps
        # selected on the MXU ('down') and the gradient spread back ('up')
        "node_conv_fwd_stride2": (
            lambda a, x: node_conv.conv_taps(
                a, x, height=32, width=32, ksize=3, resample="down",
                offset=1, interpret=False),
            (s((NODES, 32, 144)), s((NODES, 16, IMAGES * 1024)))),
        "node_conv_dw_stride2": (
            lambda x, g: node_conv.conv_taps_dw(
                x, g, height=32, width=32, ksize=3, resample="up",
                offset=1, interpret=False),
            (s((NODES, 16, IMAGES * 1024)), s((NODES, 32, IMAGES * 256)))),
        # float32 MXU operands (under default_matmul_precision 'highest')
        "node_conv_fwd_f32": (
            lambda a, x: node_conv.conv_taps(
                a, x, height=32, width=32, ksize=3, mxu_dtype=f32,
                interpret=False),
            (s((NODES, 16, 144)), s((NODES, 16, IMAGES * 1024)))),
        "node_conv_dw_f32_stride2": (
            lambda x, g: node_conv.conv_taps_dw(
                x, g, height=32, width=32, ksize=3, mxu_dtype=f32,
                resample="up", offset=1, interpret=False),
            (s((NODES, 16, IMAGES * 1024)), s((NODES, 32, IMAGES * 256)))),
        # stage 0 (16 channels at 32x32: 8 images a tile)
        "node_evonorm_fwd_stage0": (
            lambda x, v, sc, b: node_norm.evonorm_fwd(
                x, v, sc, b, hw=1024, interpret=False),
            (s((NODES, 16, IMAGES * 1024)), s((NODES, 16)), s((NODES, 16)),
             s((NODES, 16)))),
        "node_evonorm_bwd_stage0": (
            lambda x, g, v, sc: node_norm.evonorm_bwd(
                x, g, v, sc, hw=1024, interpret=False),
            (s((NODES, 16, IMAGES * 1024)), s((NODES, 16, IMAGES * 1024)),
             s((NODES, 16)), s((NODES, 16)))),
        # stage 2 (64 channels at 8x8, 64 lanes an image)
        "node_evonorm_fwd": (
            lambda x, v, sc, b: node_norm.evonorm_fwd(
                x, v, sc, b, hw=64, interpret=False),
            (s((NODES, 64, IMAGES * 64)), s((NODES, 64)), s((NODES, 64)),
             s((NODES, 64)))),
        "node_evonorm_bwd": (
            lambda x, g, v, sc: node_norm.evonorm_bwd(
                x, g, v, sc, hw=64, interpret=False),
            (s((NODES, 64, IMAGES * 64)), s((NODES, 64, IMAGES * 64)),
             s((NODES, 64)), s((NODES, 64)))),
        # mamba2-130m: 24 heads of P=64, d_state 128, chunk 128; 2 x 512
        "ssd_scan_bh": (
            lambda x, dt, adt, b, c: ssd_scan.ssd_scan_bh(
                x, dt, adt, b, c, chunk=128, interpret=False),
            (s((48, 512, 64)), s((48, 512)), s((48, 512)),
             s((48, 512, 128)), s((48, 512, 128)))),
    }


KERNELS = ["fused_halfstep", "fused_qg_buffer", "gamma_correct",
           "threshold_mask", "quantize_dequantize", "flash_attention_bf16",
           "flash_attention_fp32", "paged_decode_attention", "ssd_scan_bh",
           "node_conv_fwd", "node_conv_dx", "node_conv_dw",
           "node_conv_fwd_stride2", "node_conv_dw_stride2",
           "node_conv_fwd_f32", "node_conv_dw_f32_stride2",
           "node_evonorm_fwd_stage0", "node_evonorm_bwd_stage0",
           "node_evonorm_fwd", "node_evonorm_bwd"]


# the name each kernel passes to ``pallas_call``: the last scope of its
# op's ``op_name`` in compiled programs (``tf_op`` in device traces)
KERNEL_NAME = {"flash_attention_bf16": "flash_attention",
               "flash_attention_fp32": "flash_attention",
               "ssd_scan_bh": "ssd_scan",
               "node_conv_fwd_stride2": "node_conv_fwd",
               "node_conv_dw_stride2": "node_conv_dw",
               "node_conv_fwd_f32": "node_conv_fwd",
               "node_conv_dw_f32_stride2": "node_conv_dw",
               "node_evonorm_fwd_stage0": "node_evonorm_fwd",
               "node_evonorm_bwd_stage0": "node_evonorm_bwd"}


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, name
    kernel = KERNEL_NAME.get(name, name)    # the op's scope in the trace
    assert re.search(rf'op_name="[^"]*/{kernel}/pallas_call"', text), kernel


def test_node_batched_resnet20_gradient_compiles_for_v5e(one_chip):
    """The node-batched ResNet-20 gradient at the cell's shapes (16 nodes x
    32 images of 32x32x3) compiles with every convolution and EvoNorm in
    the named kernels, and no convolution left in XLA: ``jax.vmap`` of the
    per-node model lowers each one as a convolution whose window runs over
    the node axis (``size=...x16``)."""
    from repro.models import resnet

    p1, _ = jax.eval_shape(lambda k: resnet.init_resnet20(k),
                           jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (NODES,) + a.shape, a.dtype, sharding=one_chip), p1)
    x = jax.ShapeDtypeStruct((NODES, IMAGES, 32, 32, 3), jnp.float32,
                             sharding=one_chip)

    def block_grad(p, xb):
        return jax.grad(lambda q: jnp.sum(resnet.apply_resnet20_nodes(
            q, xb, impl="pallas")))(p)

    text = jax.jit(block_grad).lower(params, x).compile().as_text()
    assert not re.search(r"= \S+ convolution\(", text)
    for kernel in ("node_conv_fwd", "node_conv_dx", "node_conv_dw",
                   "node_evonorm_fwd", "node_evonorm_bwd"):
        assert re.search(rf'op_name="[^"]*/{kernel}/pallas_call"', text), \
            kernel

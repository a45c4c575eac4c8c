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

from repro.kernels import compress, flash_attention, qg_update, ssd_scan

PACKED = 1_100_000          # ~1.1M-element packed optimizer buffer


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
        # mamba2-130m: 24 heads of P=64, d_state 128, chunk 128; 2 x 512
        "ssd_scan_bh": (
            lambda x, dt, adt, b, c: ssd_scan.ssd_scan_bh(
                x, dt, adt, b, c, chunk=128, interpret=False),
            (s((48, 512, 64)), s((48, 512)), s((48, 512)),
             s((48, 512, 128)), s((48, 512, 128)))),
    }


KERNELS = ["fused_halfstep", "fused_qg_buffer", "gamma_correct",
           "threshold_mask", "quantize_dequantize", "flash_attention_bf16",
           "flash_attention_fp32", "paged_decode_attention", "ssd_scan_bh"]


# the name each kernel passes to ``pallas_call``: the last scope of its
# op's ``op_name`` in compiled programs (``tf_op`` in device traces)
KERNEL_NAME = {"flash_attention_bf16": "flash_attention",
               "flash_attention_fp32": "flash_attention",
               "ssd_scan_bh": "ssd_scan"}


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, name
    kernel = KERNEL_NAME.get(name, name)    # the op's scope in the trace
    assert re.search(rf'op_name="[^"]*/{kernel}/pallas_call"', text), kernel

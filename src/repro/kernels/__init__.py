"""Pallas TPU kernels (validated in interpret mode on CPU):

  qg_update        fused quasi-global momentum update (the paper's hot loop)
  compress         fused gossip compression (threshold+mask+residual, QSGD)
  flash_attention  causal GQA flash attention (window / softcap)
  node_conv        node-batched CNN convolutions, channel-major (ResNet-20)
  node_norm        node-batched EvoNorm-S0 in the same layout
  ssd_scan         Mamba-2 SSD chunked scan

Each kernel ships a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py.
"""
from . import (compress, flash_attention, node_conv, node_norm, ops,
               qg_update, ref, ssd_scan)

__all__ = ["compress", "flash_attention", "node_conv", "node_norm", "ops",
           "qg_update", "ref", "ssd_scan"]

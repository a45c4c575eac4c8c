"""mamba2-130m [ssm] — attention-free SSD backbone.  [arXiv:2405.21060]"""
from repro.models.ssm import SSMConfig
from .base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="mamba2-130m",
        family="ssm",
        n_layers=24,
        d_model=768,
        n_heads=0,          # attention-free
        n_kv_heads=0,
        d_ff=0,
        vocab_size=50280,   # stored padded to 50432 (a multiple of 256)
        head_dim=64,
        period=("mamba",),
        ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64),
        source="arXiv:2405.21060",
    )

from .config import MLAConfig, ModelConfig, MoEConfig, SSMConfig
from .transformer import (
    decode_step,
    encdec_forward,
    forward,
    init_cache,
    init_params,
    lm_loss,
    param_shapes,
    prefill,
    unembed,
)

__all__ = [
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "init_params",
    "param_shapes",
    "forward",
    "encdec_forward",
    "lm_loss",
    "unembed",
    "prefill",
    "decode_step",
    "init_cache",
]

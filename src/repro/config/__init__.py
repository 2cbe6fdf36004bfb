from repro.config.base import (
    ATTN, MAMBA,
    ALL_SHAPES, SHAPES, SINGLE_POD, MULTI_POD,
    TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K,
    MambaConfig, MeshConfig, ModelConfig, MoEConfig, RunConfig,
    ServeConfig, ShapeSpec, TrainConfig,
    get_config, list_configs, register, shape_applicable, smoke_config,
)
from repro.config.jax_env import (jax_enable_x64, set_host_device_count,
                                  use_compile_cache)

__all__ = [
    "jax_enable_x64", "set_host_device_count", "use_compile_cache",
    "ATTN", "MAMBA", "ALL_SHAPES", "SHAPES", "SINGLE_POD", "MULTI_POD",
    "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "MambaConfig", "MeshConfig", "ModelConfig", "MoEConfig", "RunConfig",
    "ServeConfig", "ShapeSpec", "TrainConfig",
    "get_config", "list_configs", "register", "shape_applicable",
    "smoke_config",
]

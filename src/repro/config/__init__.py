from repro.config.base import (
    ATTN, MAMBA,
    MambaConfig, ModelConfig, MoEConfig, ServeConfig, TrainConfig,
    get_config, list_configs, register, smoke_config,
)
from repro.config.jax_env import (jax_enable_x64, set_host_device_count,
                                  use_compile_cache)

__all__ = [
    "jax_enable_x64", "set_host_device_count", "use_compile_cache",
    "ATTN", "MAMBA",
    "MambaConfig", "ModelConfig", "MoEConfig", "ServeConfig", "TrainConfig",
    "get_config", "list_configs", "register", "smoke_config",
]

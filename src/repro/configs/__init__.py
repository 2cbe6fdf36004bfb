"""Assigned architecture configs (importing this package registers them)."""
from repro.configs import (  # noqa: F401
    granite_moe_1b_a400m,
    llama4_maverick_400b_a17b,
    stablelm_12b,
    phi3_medium_14b,
    qwen2_72b,
    internlm2_1p8b,
    musicgen_large,
    mamba2_130m,
    internvl2_1b,
    jamba_1p5_large_398b,
    bert_base,
    granite_4_0_h_small,
)

ASSIGNED_ARCHS = (
    "granite-moe-1b-a400m",
    "llama4-maverick-400b-a17b",
    "stablelm-12b",
    "phi3-medium-14b",
    "qwen2-72b",
    "internlm2-1.8b",
    "musicgen-large",
    "mamba2-130m",
    "internvl2-1b",
    "jamba-1.5-large-398b",
)

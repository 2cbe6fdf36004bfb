"""The dense decoder: rotary embeddings, grouped-query attention and
SwiGLU, read from the ``hf`` block of the configuration file; its plain
reference is ``reference.py``."""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

import reference
import work


def program_config(conf: dict):
    """The registry's config, set to the file's published values, and
    checked against its widths."""
    from repro.config import get_config

    hf = conf["hf"]
    cfg = get_config(conf["registry"]).replace(
        rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]))
    dims = work.Dims.from_hf(hf)
    got = work.Dims(
        layers=cfg.num_layers, d=cfg.d_model, heads=cfg.num_heads,
        kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
        d_ff=cfg.d_ff, vocab=cfg.vocab_size)
    if (got != dims or cfg.moe is not None
            or cfg.tie_embeddings != bool(hf["tie_word_embeddings"])):
        raise ValueError(f"{conf['registry']} runs {got}, the file "
                         f"states {dims}")
    return cfg


def dims(conf: dict) -> work.Dims:
    return work.Dims.from_hf(conf["hf"])


def vocab(conf: dict) -> int:
    return conf["hf"]["vocab_size"]


def gaps(conf: dict, seed32: int, prompts: Sequence[np.ndarray],
         served: Sequence[Sequence[int]], max_new: int) -> List[np.ndarray]:
    return reference.gaps(reference.Spec.from_hf(conf["hf"]), seed32,
                          prompts, served, max_new)

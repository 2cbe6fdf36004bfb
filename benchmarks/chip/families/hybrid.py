"""The hybrid decoder: Mamba-2 mixers and grouped-query attention without
positions, a routed MoE block with a shared expert in every layer, and
Granite's factors (granite-4.0-h-small). Everything is read from the
top-level keys of the configuration file, the catalog's copy of the
published ``config.json`` with the cut, and its ``deployment``; the plain
reference is ``reference_hybrid.py``.

The registry holds the model as published (every layer, every expert);
``program_config`` checks its widths against the file and then applies
the file's cut: the first ``num_hidden_layers`` layers of the pattern,
and ``num_local_experts`` experts from ``deployment.expert_first``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

import reference_hybrid
import work_hybrid

KINDS = {"mamba": "mamba", "attention": "attn"}


def registry(conf: dict):
    """The registry's config of the model, as published."""
    from repro.config import get_config

    return get_config(conf["registry"])


def _published(conf: dict) -> dict:
    heads = conf["num_attention_heads"]
    return {
        "hidden_size": conf["hidden_size"],
        "num_attention_heads": heads,
        "num_key_value_heads": conf["num_key_value_heads"],
        "head_dim": conf.get("head_dim") or conf["hidden_size"] // heads,
        "vocab_size": conf["vocab_size"],
        "tie_word_embeddings": conf["tie_word_embeddings"],
        "rms_norm_eps": conf["rms_norm_eps"],
        "position_embedding_type": conf["position_embedding_type"],
        "mamba_n_heads": conf["mamba_n_heads"],
        "mamba_d_head": conf["mamba_d_head"],
        "mamba_d_state": conf["mamba_d_state"],
        "mamba_d_conv": conf["mamba_d_conv"],
        "mamba_n_groups": conf["mamba_n_groups"],
        "mamba_chunk_size": conf["mamba_chunk_size"],
        "mamba_d_inner": conf["mamba_expand"] * conf["hidden_size"],
        "intermediate_size": conf["intermediate_size"],
        "shared_intermediate_size": conf["shared_intermediate_size"],
        "num_experts_per_tok": conf["num_experts_per_tok"],
        "experts": conf["deployment"]["experts_published"],
        "layers": conf["deployment"]["layers_published"],
        "embedding_multiplier": conf["embedding_multiplier"],
        "residual_multiplier": conf["residual_multiplier"],
        "attention_multiplier": conf["attention_multiplier"],
        "logits_scaling": conf["logits_scaling"],
    }


def _runs(cfg) -> dict:
    m, moe = cfg.mamba, cfg.moe
    return {
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim,
        "vocab_size": cfg.vocab_size,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rms_norm_eps": cfg.norm_eps,
        "position_embedding_type": "rope" if cfg.rope else "nope",
        "mamba_n_heads": m.n_heads(cfg.d_model),
        "mamba_d_head": m.headdim,
        "mamba_d_state": m.d_state,
        "mamba_d_conv": m.d_conv,
        "mamba_n_groups": 1,          # B and C are shared by every head
        "mamba_chunk_size": m.chunk_size,
        "mamba_d_inner": m.d_inner(cfg.d_model),
        "intermediate_size": moe.d_ff_expert,
        "shared_intermediate_size": moe.d_ff_shared,
        "num_experts_per_tok": moe.top_k,
        "experts": moe.num_experts,
        "layers": cfg.num_layers,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
    }


def program_config(conf: dict):
    """The registry's config, checked against the file's published
    values, then cut as the file says."""
    cfg = registry(conf)
    want, got = _published(conf), _runs(cfg)
    if want != got or cfg.moe.cut or not cfg.moe.is_moe_layer(0) or (
            cfg.moe.period != 1):
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"{conf['registry']} runs, against the file: {diff}")
    n = conf["num_hidden_layers"]
    kinds = tuple(KINDS[t] for t in conf["layer_types"])
    if len(kinds) != n or kinds != cfg.layer_kinds()[:n]:
        raise ValueError(f"the file's layer_types {kinds} are not the first "
                         f"{n} layers of {conf['registry']}")
    moe = dataclasses.replace(
        cfg.moe, expert_first=conf["deployment"]["expert_first"],
        experts_held=conf["num_local_experts"])
    return cfg.replace(num_layers=n, layer_pattern=kinds, moe=moe)


def dims(conf: dict) -> work_hybrid.Dims:
    return work_hybrid.Dims.from_conf(conf)


def vocab(conf: dict) -> int:
    return conf["vocab_size"]


def gaps(conf: dict, seed32: int, prompts: Sequence[np.ndarray],
         served: Sequence[Sequence[int]], max_new: int) -> List[np.ndarray]:
    return reference_hybrid.gaps(reference_hybrid.Spec.from_conf(conf),
                                 seed32, prompts, served, max_new)

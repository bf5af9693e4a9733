"""ZAYA1 family (``model_type: zaya``): from a configuration file's published
keys to the repo's model, the parameters a layer holds and the operations a
token needs.

Every layer is ``hybrid``: an attention sub-layer in a compressed latent
behind two causal convolutions (CCA) then top-1 SwiGLU experts behind an MLP
router whose state is carried from layer to layer; the head is tied.

The shared serving kind hands a reference ``n_head`` and
``layer_norm_epsilon``: carried as aliases of ``num_attention_heads`` and
``rms_norm_eps``; ``n_routed_experts`` is the name
``benchmark/kernels/moe_experts.py`` reads ``num_experts`` under. What else
the reference needs it gets here, when the model is built.
"""

from __future__ import annotations

ALIASES = (("n_head", "num_attention_heads"),
           ("layer_norm_epsilon", "rms_norm_eps"),
           ("n_routed_experts", "num_experts"))


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a ZAYA1 ``config.json``: the
    importer's own map of the published keys (which refuses what the family
    runs one value of), in the compute type."""
    import dataclasses

    import jax.numpy as jnp

    from deepspeed_tpu.models import config_from_hf

    for alias, key in ALIASES:
        if alias in published and published[alias] != published[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    return dataclasses.replace(
        config_from_hf(published),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import zaya as reference

    if flash_attention:
        raise ValueError("compressed convolutional attention is served "
                         "here, not trained: the flash kernel is the train "
                         "cells'")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE layer's attention (the latent's projections
    and the grouped conv), its router, ONE expert, and the tied head; norms,
    the depthwise taps, biases and the residual scales are left out."""
    p = published
    d, H, KV, hd = (p["hidden_size"], p["num_attention_heads"],
                    p["num_key_value_heads"], p["head_dim"])
    R = p["router_hidden_size"]
    return {"attention": 2 * d * H * hd + 2 * d * KV * hd
            + p["cca_time1"] * (H + KV) * hd * hd,
            "router": d * R + 2 * R * R + R * p["num_experts"],
            "expert": 3 * d * p["moe_intermediate_size"],
            "head": d * p["vocab_size"]}


def flops_per_token(published: dict, context: int) -> dict:
    """Forward FLOPs of one token with ``context`` positions behind it: 2 a
    parameter it is multiplied by (ONE expert a layer) and 2 H (hd + hd) a
    key it sees."""
    p, n = published, layer_params(published)
    L = p["num_hidden_layers"]
    return {"attention": L * (2.0 * n["attention"] + 2.0
                              * p["num_attention_heads"] * 2 * p["head_dim"]
                              * context),
            "experts": 2.0 * L * (n["router"] + n["expert"]),
            "head": 2.0 * n["head"]}


def train_flops_per_token(published: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward's, at the mean context of
    a ``seq_len`` sequence (the GPT-2 family's convention)."""
    return 3.0 * sum(flops_per_token(published, seq_len // 2).values())

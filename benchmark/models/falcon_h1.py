"""Falcon-H1 family (``model_type: falcon_h1``): from a configuration file's
published keys to the repo's model, the parameters a layer holds and the
FLOPs a token costs.

Every layer is alike: a Mamba-2 mixer and rotary GQA attention side by side
on one normed input, a gated FFN behind them, a muP multiplier on every
branch. The readers that are there take other families' spellings, so the
file's ``config`` carries aliases (listed under ``assumed``): ``n_head`` and
``layer_norm_epsilon`` for ``benchmark/kinds/_serving.py``, ``n_embd``;
``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``n_groups`` for
``benchmark/kernels/ssm_state_step.py``. An alias that differs from its key
is refused here; a key the trunk runs one value of (a bias, a tied head, the
norm before the gate) by the importer's map (``models/importer.py``), which
builds the configuration.
"""

from __future__ import annotations

ALIASES = (("n_head", "num_attention_heads"), ("n_embd", "hidden_size"),
           ("layer_norm_epsilon", "rms_norm_eps"),
           ("mamba_num_heads", "mamba_n_heads"),
           ("mamba_head_dim", "mamba_d_head"),
           ("ssm_state_size", "mamba_d_state"),
           ("n_groups", "mamba_n_groups"))


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a Falcon-H1 ``config.json``."""
    import dataclasses

    import jax.numpy as jnp

    from deepspeed_tpu.models import config_from_hf

    p = published
    for alias, key in ALIASES:
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    return dataclasses.replace(
        config_from_hf(p),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import falcon_h1 as reference

    if flash_attention:
        raise ValueError("a trunk of mixers side by side is served here, not "
                         "trained: the flash kernel is the train cells'")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE layer by branch, and the head; norms, the
    conv and the per-head scalars are left out. (No ``mamba`` key: the
    reducer of a trunk of one mixer a layer has nothing to read here.)"""
    p = published
    d, hd = p["hidden_size"], p["head_dim"]
    inner = p["mamba_d_ssm"]
    bc = 2 * p["mamba_n_groups"] * p["mamba_d_state"]
    return {"attention": 2 * d * p["num_attention_heads"] * hd
            + 2 * d * p["num_key_value_heads"] * hd,
            "ssm": d * (2 * inner + bc + p["mamba_n_heads"]) + inner * d,
            "mlp": 3 * d * p["intermediate_size"],
            "head": d * p["vocab_size"]}


def flops_per_token(published: dict, context: int) -> dict:
    """Forward FLOPs of one token with ``context`` positions behind it: 2 a
    parameter it is multiplied by, 2 H (hd + hd) a key it sees, and the
    recurrence's five a state value (decay, outer product, sum, times C,
    reduce)."""
    p, n = published, layer_params(published)
    L = p["num_hidden_layers"]
    state = p["mamba_n_heads"] * p["mamba_d_head"] * p["mamba_d_state"]
    return {"attention": L * (2.0 * n["attention"] + 2.0
                              * p["num_attention_heads"] * 2 * p["head_dim"]
                              * context),
            "ssm": L * (2.0 * n["ssm"] + 5.0 * state),
            "mlp": 2.0 * L * n["mlp"],
            "head": 2.0 * n["head"]}


def train_flops_per_token(published: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward's, at the mean context of
    a ``seq_len`` sequence (the GPT-2 family's convention)."""
    return 3.0 * sum(flops_per_token(published, seq_len // 2).values())

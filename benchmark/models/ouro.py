"""Ouro family (``model_type: ouro``, arXiv:2510.25741): from a configuration
file's published keys to the repo's model, and the parameters a token is
multiplied by.

The serving kind hands the plain reference only ``n_head`` and an epsilon
(``benchmark/kinds/_serving.py``), which a configuration of this family
carries as aliases of ``num_attention_heads`` and ``rms_norm_eps``
(``n_embd``, of ``hidden_size``, is what ``benchmark/kernels/
decode_attention.py`` reads); what else the reference needs and cannot read
off the weights' shapes (theta, the passes, the threshold) it gets here, when
the model is built (PERF.md section 7 (2)).
"""

from __future__ import annotations

# keys this family runs one value of. ``sliding_window`` and
# ``max_window_layers`` are read by the source only where
# ``use_sliding_window`` is true, which is refused; ``early_exit_threshold``
# 1 exits at the last pass, which is the one thing the served trunk does.
REFUSED = {"rope_scaling": None, "use_sliding_window": False,
           "hidden_act": "silu", "tie_word_embeddings": False,
           "early_exit_threshold": 1, "model_type": "ouro"}
ALIASES = (("n_head", "num_attention_heads"),
           ("layer_norm_epsilon", "rms_norm_eps"), ("n_embd", "hidden_size"))


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for an Ouro ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import ouro

    p = published
    for key, only in REFUSED.items():
        if p.get(key, only) != only:
            raise ValueError(f"{key}={p[key]!r}: this family runs {only!r}")
    if set(p["layer_types"]) != {"full_attention"} \
            or len(p["layer_types"]) != p["num_hidden_layers"]:
        raise ValueError("layer_types: this family runs full_attention in "
                         "every one of num_hidden_layers layers")
    if p["head_dim"] * p["num_attention_heads"] != p["hidden_size"]:
        raise ValueError("head_dim: the trunk's heads split hidden_size")
    for alias, key in ALIASES:
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    return ouro(
        "tiny", n_layer=p["num_hidden_layers"],
        n_head=p["num_attention_heads"], n_kv_head=p["num_key_value_heads"],
        d_model=p["hidden_size"], d_ff=p["intermediate_size"],
        vocab_size=p["vocab_size"], max_seq=p["max_position_embeddings"],
        norm_eps=p["rms_norm_eps"], rope_theta=float(p["rope_theta"]),
        loop_steps=p["total_ut_steps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import ouro as reference

    if flash_attention:
        raise ValueError("a looped trunk is served here, not trained: the "
                         "flash kernel is the train cells'")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Parameters a token is multiplied by: ``attention`` and ``mlp`` of ONE
    layer (every pass multiplies by them again), and the ``head``. Norm gains
    and the gate's 2049 are left out."""
    p = published
    d = p["hidden_size"]
    return {"attention": 2 * d * p["num_attention_heads"] * p["head_dim"]
            + 2 * d * p["num_key_value_heads"] * p["head_dim"],
            "mlp": 3 * d * p["intermediate_size"],
            "head": d * p["vocab_size"]}

"""NemotronH family (``model_type: nemotron_h``): from a configuration file's
published keys to the repo's model, and the parameters a layer holds.

A configuration of this family names each layer's one mixer in
``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` latent experts, ``*``
attention). A chip's share of a stated deployment holds ``n_routed_experts``
of the published ``router_experts`` (both under ``config``; the second, with
``first_expert_held``, is the file's ``deployment`` in numbers, under
``assumed``): the router keeps its published width and its experts per token.

The serving kind hands the plain reference only ``n_head`` and
``layer_norm_epsilon`` (``benchmark/kinds/_serving.py``): this family
publishes the second and carries the first as an alias of
``num_attention_heads`` (``n_embd``, of ``hidden_size``, likewise); what else
the reference needs it gets here, when the model is built (PERF.md section 7
(2)).
"""

from __future__ import annotations

# keys this family runs one value of
REFUSED = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
           "mamba_hidden_act": "silu", "n_group": 1, "topk_group": 1,
           "use_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
           "attention_bias": False, "use_conv_bias": True,
           "norm_topk_prob": True, "tie_word_embeddings": False,
           "n_shared_experts": 1, "sliding_window": None,
           "moe_shared_expert_overlap": False, "residual_in_fp32": False}
ALIASES = (("n_head", "num_attention_heads"), ("n_embd", "hidden_size"))


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a NemotronH ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import nemotron_h

    p = published
    for key, only in REFUSED.items():
        if p.get(key, only) != only:
            raise ValueError(f"{key}={p[key]!r}: this family runs {only!r}")
    for alias, key in ALIASES:
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    pattern = p["hybrid_override_pattern"]
    if len(pattern) != p["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern names num_hidden_layers "
                         "layers")
    if p["expand"] * p["hidden_size"] != p["mamba_num_heads"] \
            * p["mamba_head_dim"] or p["norm_eps"] != p["layer_norm_epsilon"]:
        raise ValueError("expand x hidden_size is the Mamba heads' width; "
                         "norm_eps and layer_norm_epsilon are one value")
    if p["head_dim"] * p["num_attention_heads"] != p["hidden_size"]:
        raise ValueError("head_dim: the trunk's heads split hidden_size")
    held = p["n_routed_experts"]
    return nemotron_h(
        "tiny", block_pattern=pattern, n_layer=len(pattern),
        n_head=p["num_attention_heads"], n_kv_head=p["num_key_value_heads"],
        d_model=p["hidden_size"], vocab_size=p["vocab_size"],
        max_seq=p["max_position_embeddings"],
        norm_eps=p["layer_norm_epsilon"],
        ssm_heads=p["mamba_num_heads"], ssm_head_dim=p["mamba_head_dim"],
        ssm_groups=p["n_groups"], ssm_state=p["ssm_state_size"],
        ssm_conv=p["conv_kernel"], ssm_chunk=p["chunk_size"],
        ssm_dt_init=(p["time_step_min"], p["time_step_max"],
                     p["time_step_floor"]),
        num_experts=p.get("router_experts", held), moe_experts_held=held,
        moe_first_held=p.get("first_expert_held", 0),
        moe_top_k=p["num_experts_per_tok"],
        moe_d_ff=p["moe_intermediate_size"],
        moe_latent_dim=p["moe_latent_size"],
        moe_shared_d_ff=p["moe_shared_expert_intermediate_size"],
        moe_routed_scale=float(p["routed_scaling_factor"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import nemotron_h as reference

    if flash_attention:
        raise ValueError("a trunk of one mixer a layer is served here, not "
                         "trained: the flash kernel is the train cells'")
    reference.configure(published, published.get("first_expert_held", 0))
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE layer of each kind as held here, the held
    experts apart (``experts``: one expert's two matrices), and the head's
    slice; norms, the conv and the per-head scalars are left out."""
    p = published
    d, lat = p["hidden_size"], p["moe_latent_size"]
    inner = p["mamba_num_heads"] * p["mamba_head_dim"]
    bc = 2 * p["n_groups"] * p["ssm_state_size"]
    hd = p["head_dim"]
    return {"mamba": d * (2 * inner + bc + p["mamba_num_heads"]) + inner * d,
            "attention": 2 * d * p["num_attention_heads"] * hd
            + 2 * d * p["num_key_value_heads"] * hd,
            "experts_other": d * p.get("router_experts",
                                       p["n_routed_experts"]) + 2 * d * lat
            + 2 * d * p["moe_shared_expert_intermediate_size"],
            "expert": 2 * lat * p["moe_intermediate_size"],
            "head": d * p["vocab_size"]}

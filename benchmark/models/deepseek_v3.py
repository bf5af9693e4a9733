"""DeepSeek-V3 family (``model_type: deepseek_v3``): from a configuration
file's published keys to the repo's model, and the operations a token needs.

The serving kind hands the plain reference only ``n_head`` and an epsilon
(``benchmark/kinds/_serving.py``), which a configuration of this family
carries as aliases of ``num_attention_heads`` and ``rms_norm_eps``; what else
the reference needs and cannot read off the weights' shapes (experts per
token, the scaling factor, theta, the head split) it gets here, when the
model is built (PERF.md section 7 (2) asks for the shared kind to hand a
reference its configuration's published keys itself).
"""

from __future__ import annotations

REFUSED = {"attention_bias": False, "hidden_act": "silu", "q_lora_rank": None,
           "rope_scaling": None, "rope_interleave": True,
           "scoring_func": "sigmoid", "topk_method": "noaux_tc",
           "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
           "tie_word_embeddings": False}


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a DeepSeek-V3 ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import deepseek_v3

    p = published
    for key, only in REFUSED.items():
        if p.get(key, only) != only:
            raise ValueError(f"{key}={p[key]!r}: this family runs {only!r}")
    for alias, key in (("n_head", "num_attention_heads"),
                       ("layer_norm_epsilon", "rms_norm_eps")):
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    return deepseek_v3(
        "tiny", n_layer=p["num_hidden_layers"],
        n_head=p["num_attention_heads"], d_model=p["hidden_size"],
        d_ff=p["intermediate_size"], vocab_size=p["vocab_size"],
        max_seq=p["max_position_embeddings"], norm_eps=p["rms_norm_eps"],
        rope_theta=float(p["rope_theta"]), kv_lora_rank=p["kv_lora_rank"],
        qk_nope_head_dim=p["qk_nope_head_dim"],
        qk_rope_head_dim=p["qk_rope_head_dim"], v_head_dim=p["v_head_dim"],
        num_experts=p["n_routed_experts"], moe_top_k=p["num_experts_per_tok"],
        moe_d_ff=p["moe_intermediate_size"],
        moe_shared_d_ff=p["n_shared_experts"] * p["moe_intermediate_size"],
        moe_norm_topk=p["norm_topk_prob"],
        moe_routed_scale=float(p["routed_scaling_factor"]),
        moe_first_dense=p["first_k_dense_replace"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import deepseek_v3 as reference

    if flash_attention:
        raise ValueError("latent attention brings its own blocked attention")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Parameters a token is multiplied by, per layer kind and for the head:
    ``attention`` (every layer), ``dense`` (a leading layer's MLP),
    ``router``, ``expert`` (ONE routed expert), ``shared``, ``head``."""
    p = published
    d, H = p["hidden_size"], p["num_attention_heads"]
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
    r = p["kv_lora_rank"]
    return {
        "attention": d * H * qk + d * (r + p["qk_rope_head_dim"])
        + r * H * (p["qk_nope_head_dim"] + p["v_head_dim"])
        + H * p["v_head_dim"] * d,
        "dense": 3 * d * p["intermediate_size"],
        "router": d * p["n_routed_experts"],
        "expert": 3 * d * p["moe_intermediate_size"],
        "shared": 3 * d * p["n_shared_experts"] * p["moe_intermediate_size"],
        "head": d * p["vocab_size"]}


def train_flops_per_token(published: dict, seq_len: int) -> float:
    """Forward + backward FLOPs a token of a ``seq_len`` sequence needs: 6
    per parameter it is multiplied by (the chosen experts only), and
    6 * L * H * (qk + v) * S for the scores and values (PaLM appendix B's
    convention, as the GPT-2 family counts)."""
    p, n = published, layer_params(published)
    L, k0 = p["num_hidden_layers"], p["first_k_dense_replace"]
    active = (L * n["attention"] + k0 * n["dense"] + (L - k0) * (
        n["router"] + p["num_experts_per_tok"] * n["expert"] + n["shared"])
        + n["head"])
    attn = 6.0 * L * p["num_attention_heads"] * (
        p["qk_nope_head_dim"] + p["qk_rope_head_dim"] + p["v_head_dim"]) * seq_len
    return 6.0 * active + attn

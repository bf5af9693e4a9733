"""Ling-3.0-flash family (``model_type: bailing_hybrid``): from a configuration
file's published keys to the repo's model, the parameters a layer holds and
the operations a token needs.

Layer ``l`` mixes with DeepSeek's latent attention where ``(l + 1) %
layer_group_size == 0`` (32 heads over a latent of ``kv_lora_rank`` beside a
roped key of ``qk_rope_head_dim``, every live position read, an output gate a
head: ``gated_attention_proj_granularity_type`` ``head_wise``) and with Kimi
Delta Attention elsewhere (as many heads of ``head_dim`` key and value
channels behind depthwise convs of ``short_conv_kernel_size``, a float32
delta-rule state a head; the gate bounded at ``kda_lower_bound``, its map and
the output gate's full: ``no_kda_lora``; a learned gain on q and k:
``use_qk_norm``). The first ``first_k_dense_replace`` layers feed forward
densely, the others through sigmoid-routed experts chosen within the
``topk_group`` best of ``n_group`` groups beside a shared one, the routed and
the shared clamped by a value a layer (the two ``*_swiglu_limit_list``). A
chip's share of a stated deployment holds ``num_experts`` of the published
``router_experts`` (both under ``config``; the second, with
``first_expert_held``, is the file's ``deployment`` in numbers, under
``assumed``).

The shared serving kind hands a reference ``n_head`` and
``layer_norm_epsilon``, and ``benchmark/kernels/kda_state_step.py`` reads
``linear_attn_config``: carried as aliases of the source's own keys.
"""

from __future__ import annotations

# keys this family runs one value of
REFUSED = {"model_type": "bailing_hybrid", "use_qk_norm": True,
           "no_kda_lora": True, "use_kda_lora": False, "kda_safe_gate": True,
           "use_mla_nope": False, "rope_interleave": True,
           "gated_attention_proj_granularity_type": "head_wise",
           "q_lora_rank": None, "rope_scaling": None, "linear_silu": True,
           "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
           "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
           "norm_topk_prob": True, "num_shared_experts": 1,
           "use_bias": False, "use_qkv_bias": False, "use_nGPT": False,
           "value_norm": False, "up_proj_norm": False,
           "scale_router_input": False, "tie_word_embeddings": False,
           "num_nextn_predict_layers": 0}
ALIASES = (("n_head", "num_attention_heads"),
           ("layer_norm_epsilon", "rms_norm_eps"))


def check(published: dict) -> str:
    """Refuse what this family does not run; the trunk's mixers, a letter a
    layer."""
    p = published
    for key, only in REFUSED.items():
        if p.get(key, only) != only:
            raise ValueError(f"{key}={p[key]!r}: this family runs {only!r}")
    for alias, key in ALIASES:
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    lin = p.get("linear_attn_config")
    if lin and lin != {"num_heads": p["num_attention_heads"],
                       "head_dim": p["head_dim"],
                       "short_conv_kernel_size": p["short_conv_kernel_size"]}:
        raise ValueError("linear_attn_config is an alias of "
                         "num_attention_heads, head_dim and "
                         "short_conv_kernel_size")
    L = p["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if len(p[key]) != L:
            raise ValueError(f"{key} gives every layer's clamp: "
                             f"num_hidden_layers={L} values")
    if p["qk_head_dim"] != p["qk_nope_head_dim"] + p["qk_rope_head_dim"] \
            or p["rotary_dim"] != p["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim = qk_nope_head_dim + qk_rope_head_dim, "
                         "the rope part rotary_dim wide")
    every = p["layer_group_size"]
    return "".join("A" if (i + 1) % every == 0 else "K" for i in range(L))


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a Ling-3.0-flash ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import bailing_hybrid

    p = published
    held = p["num_experts"]
    return bailing_hybrid(
        "tiny", mixer_pattern=check(p), n_layer=p["num_hidden_layers"],
        n_head=p["num_attention_heads"], d_model=p["hidden_size"],
        d_ff=p["intermediate_size"], vocab_size=p["vocab_size"],
        max_seq=p["max_position_embeddings"], norm_eps=p["rms_norm_eps"],
        rope_theta=float(p["rope_theta"]), kv_lora_rank=p["kv_lora_rank"],
        qk_nope_head_dim=p["qk_nope_head_dim"],
        qk_rope_head_dim=p["qk_rope_head_dim"], v_head_dim=p["v_head_dim"],
        kda_heads=p["num_attention_heads"], kda_head_dim=p["head_dim"],
        kda_conv=p["short_conv_kernel_size"],
        kda_gate_floor=float(p["kda_lower_bound"]),
        num_experts=p.get("router_experts", held),
        moe_experts_held=held if "router_experts" in p else 0,
        moe_first_held=p.get("first_expert_held", 0),
        moe_top_k=p["num_experts_per_tok"], moe_n_group=p["n_group"],
        moe_topk_group=p["topk_group"], moe_d_ff=p["moe_intermediate_size"],
        moe_shared_d_ff=p["num_shared_experts"]
        * p["moe_shared_expert_intermediate_size"],
        moe_first_dense=p["first_k_dense_replace"],
        moe_routed_scale=float(p["routed_scaling_factor"]),
        moe_swiglu_limits=tuple(p["expert_swiglu_limit_list"]),
        moe_shared_swiglu_limits=tuple(p["share_expert_swiglu_limit_list"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import bailing_hybrid as reference

    if flash_attention:
        raise ValueError("delta-rule mixers beside latent attention layers "
                         "are served here, not trained: the flash kernel is "
                         "the train cells'")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE KDA mixer, ONE latent attention (its gate
    with it), a dense layer's FFN, a layer's router and shared expert, ONE
    routed expert, and the head's slice as held here; norms, gains and convs
    left out."""
    p = published
    d, H, D = p["hidden_size"], p["num_attention_heads"], p["head_dim"]
    r = p["kv_lora_rank"]
    inner = H * D
    return {"kda": 6 * d * inner + d * H,
            "attention": d * H * p["qk_head_dim"]
            + d * (r + p["qk_rope_head_dim"])
            + r * H * (p["qk_nope_head_dim"] + p["v_head_dim"])
            + H * p["v_head_dim"] * d + d * H,
            "dense": 3 * d * p["intermediate_size"],
            "router": d * p.get("router_experts", p["num_experts"]),
            "shared": 3 * d * p["num_shared_experts"]
            * p["moe_shared_expert_intermediate_size"],
            "expert": 3 * d * p["moe_intermediate_size"],
            "head": d * p["vocab_size"]}


def kinds(published: dict) -> dict:
    """How many layers of each kind the configuration holds."""
    p = published
    pattern = check(p)
    dense = min(p["first_k_dense_replace"], len(pattern))
    return {"kda": pattern.count("K"), "attention": pattern.count("A"),
            "dense": dense, "routed": len(pattern) - dense,
            "layers": len(pattern)}


def state_bytes_per_slot(published: dict, itemsize: int = 2) -> dict:
    """What a slot holds whatever its length: the KDA layers' float32 state
    and conv tails."""
    p, k = published, kinds(published)
    H, D = p["num_attention_heads"], p["head_dim"]
    return {"kda": k["kda"] * H * D * D * 4,
            "conv": k["kda"] * (p["short_conv_kernel_size"] - 1) * 3 * H * D
            * itemsize}


def cache_bytes_per_token(published: dict, itemsize: int = 2) -> dict:
    """What a cached position holds: the attention layers' latents."""
    p, k = published, kinds(published)
    return {"latent": k["attention"] * (p["kv_lora_rank"]
                                        + p["qk_rope_head_dim"]) * itemsize}


def flops_per_token(published: dict, context: int) -> dict:
    """Forward FLOPs of one token with ``context`` positions behind it: 2 a
    parameter it is multiplied by (the chosen experts of ALL the router's),
    8 a state value of every KDA head, the absorbed attention over every
    position (scores over rank + rope, values over rank, a head)."""
    p, n, k = published, layer_params(published), kinds(published)
    H, D = p["num_attention_heads"], p["head_dim"]
    return {"kda": k["kda"] * (2.0 * n["kda"] + 8.0 * H * D ** 2),
            "attention": k["attention"] * (
                2.0 * n["attention"] + 2.0 * H * context
                * (2 * p["kv_lora_rank"] + p["qk_rope_head_dim"])),
            "dense": 2.0 * k["dense"] * n["dense"],
            "experts": 2.0 * k["routed"] * (
                n["router"] + n["shared"]
                + p["num_experts_per_tok"] * n["expert"]),
            "head": 2.0 * n["head"]}


def train_flops_per_token(published: dict, seq_len: int) -> float:
    """Not trained here (``build`` refuses the flash kernel; every
    ``mixer_pattern`` trunk is served): three times the forward's at the
    sequence's mean context, for a reader that asks."""
    return 3.0 * sum(flops_per_token(published, seq_len // 2).values())

"""MiMo-V2-Flash family (``model_type: mimo_v2_flash``): from a configuration
file's published keys to the repo's model, the parameters a layer holds and
the operations a token needs, by attention kind.

A configuration of this family names each layer's attention in
``hybrid_layer_pattern`` (0 full, 1 window) and its FFN in ``moe_layer_freq``
(0 dense, 1 experts). A chip's share of a stated deployment holds
``n_routed_experts`` of the published ``router_experts`` (both under
``config``; the second, with ``first_expert_held``, is the file's
``deployment`` in numbers, under ``assumed``): the router keeps its published
width and its experts per token.

The shared serving kind hands a reference ``n_head`` and
``layer_norm_epsilon``: carried as aliases of ``num_attention_heads`` and
``layernorm_epsilon``; what else the reference needs it gets here, when the
model is built.
"""

from __future__ import annotations

# keys this family runs one value of
REFUSED = {"model_type": "mimo_v2_flash", "hidden_act": "silu",
           "attention_bias": False, "tie_word_embeddings": False,
           "scoring_func": "sigmoid", "topk_method": "noaux_tc",
           "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
           "n_shared_experts": None, "routed_scaling_factor": None,
           "add_swa_attention_sink_bias": True,
           "add_full_attention_sink_bias": False}
ALIASES = (("n_head", "num_attention_heads"),
           ("layer_norm_epsilon", "layernorm_epsilon"),
           ("sliding_window_size", "sliding_window"),
           ("swa_num_attention_heads", "num_attention_heads"),
           ("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"))


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a MiMo-V2 ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import mimo_v2_flash

    p = published
    for key, only in REFUSED.items():
        if p.get(key, only) != only:
            raise ValueError(f"{key}={p[key]!r}: this family runs {only!r}")
    for alias, key in ALIASES:
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    L = p["num_hidden_layers"]
    pattern, freq = p["hybrid_layer_pattern"], p["moe_layer_freq"]
    if len(pattern) != L or len(freq) != L:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq name "
                         "num_hidden_layers layers")
    dense = freq.index(1) if 1 in freq else L
    if any(f != 1 for f in freq[dense:]):
        raise ValueError("the dense layers lead: moe_layer_freq is 0s, then 1s")
    held = p["n_routed_experts"]
    return mimo_v2_flash(
        "tiny", attn_pattern="".join("GS"[k] for k in pattern), n_layer=L,
        n_head=p["num_attention_heads"], n_kv_head=p["num_key_value_heads"],
        window_kv_heads=p["swa_num_key_value_heads"],
        d_model=p["hidden_size"], qk_head_dim=p["head_dim"],
        v_head_dim=p["v_head_dim"],
        rotary_dim=int(p["head_dim"] * p["partial_rotary_factor"]),
        window=p["sliding_window"], d_ff=p["intermediate_size"],
        rope_theta=float(p["rope_theta"]),
        window_rope_theta=float(p["swa_rope_theta"]),
        attn_value_scale=float(p["attention_value_scale"]),
        norm_eps=p["layernorm_epsilon"], vocab_size=p["vocab_size"],
        max_seq=p["max_position_embeddings"],
        num_experts=p.get("router_experts", held),
        moe_experts_held=held if "router_experts" in p else 0,
        moe_first_held=p.get("first_expert_held", 0),
        moe_top_k=p["num_experts_per_tok"],
        moe_d_ff=p["moe_intermediate_size"], moe_first_dense=dense,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import mimo_v2_flash as reference

    if flash_attention:
        raise ValueError("window layers beside full ones are served here, "
                         "not trained: the flash kernel is the train cells'")
    reference.configure(published, published.get("first_expert_held", 0))
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE layer's attention by kind, the dense FFN, an
    expert layer's router, ONE expert, and the head's slice as held here;
    norms and the sink's scalars are left out."""
    p = published
    d, H = p["hidden_size"], p["num_attention_heads"]
    hd, vd = p["head_dim"], p["v_head_dim"]
    qo = d * H * hd + H * vd * d
    return {"full_attention": qo + d * p["num_key_value_heads"] * (hd + vd),
            "window_attention": qo + d * p["swa_num_key_value_heads"]
            * (hd + vd),
            "dense": 3 * d * p["intermediate_size"],
            "router": d * p.get("router_experts", p["n_routed_experts"]),
            "expert": 3 * d * p["moe_intermediate_size"],
            "head": d * p["vocab_size"]}


def flops_per_token(published: dict, context: int) -> dict:
    """Forward FLOPs of one token with ``context`` positions behind it, by
    kind: 2 a parameter it is multiplied by (the chosen experts of ALL the
    router's, held here or not) and 2 H (qk + v) a key it sees — every
    position for a full layer, at most ``sliding_window`` for a window one."""
    p, n = published, layer_params(published)
    H, hv = p["num_attention_heads"], p["head_dim"] + p["v_head_dim"]
    kinds = list(zip(p["hybrid_layer_pattern"], p["moe_layer_freq"]))
    full = sum(1 for w, _ in kinds if not w)
    window = len(kinds) - full
    experts = sum(f for _, f in kinds)
    return {
        "full_attention": full * (2.0 * n["full_attention"]
                                  + 2.0 * H * hv * context),
        "window_attention": window * (2.0 * n["window_attention"] + 2.0 * H
                                      * hv * min(context,
                                                 p["sliding_window"])),
        "dense": 2.0 * (len(kinds) - experts) * n["dense"],
        "experts": 2.0 * experts * (n["router"] + p["num_experts_per_tok"]
                                    * n["expert"]),
        "head": 2.0 * n["head"]}


def train_flops_per_token(published: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward's, at the mean context of
    a ``seq_len`` sequence (the GPT-2 family's convention)."""
    return 3.0 * sum(flops_per_token(published, seq_len // 2).values())

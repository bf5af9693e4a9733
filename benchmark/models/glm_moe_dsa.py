"""GLM-5.2 family (``model_type: glm_moe_dsa``): from a configuration file's
published keys to the repo's model, the parameters a layer holds and the
operations a token needs.

The DeepSeek-V3 block (latent attention, sigmoid ``noaux_tc`` experts beside
a shared one) with a low-rank query and DeepSeek-V3.2's learned sparse
attention: ``indexer_types`` names each layer ``full`` (it has an indexer:
``index_n_heads`` heads of ``index_head_dim`` whose ``index_topk`` best
positions are all its attention reads) or ``shared`` (it reads what the last
``full`` layer before it chose). A chip's share of a stated deployment holds
``n_routed_experts`` of the published ``router_experts`` (both under
``config``; the second, with ``first_expert_held``, is the file's
``deployment`` in numbers, under ``assumed``).

The shared serving kind hands a reference ``n_head`` and
``layer_norm_epsilon``: carried as aliases of ``num_attention_heads`` and
``rms_norm_eps``; what else the reference needs it gets here, when the model
is built.
"""

from __future__ import annotations

# keys this family runs one value of
REFUSED = {"model_type": "glm_moe_dsa", "attention_bias": False,
           "hidden_act": "silu", "rope_interleave": True,
           "indexer_rope_interleave": True, "index_topk_pattern": None,
           "scoring_func": "sigmoid", "topk_method": "noaux_tc",
           "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
           "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
ALIASES = (("n_head", "num_attention_heads"),
           ("layer_norm_epsilon", "rms_norm_eps"),
           ("num_key_value_heads", "num_attention_heads"))


def check(published: dict) -> int:
    """Refuse what this family does not run; the leading dense layers."""
    p = published
    for key, only in REFUSED.items():
        if p.get(key, only) != only:
            raise ValueError(f"{key}={p[key]!r}: this family runs {only!r}")
    for alias, key in ALIASES:
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    if p["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("this family turns by theta alone (rope_type "
                         "default)")
    if p["qk_head_dim"] != p["qk_nope_head_dim"] + p["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    L = p["num_hidden_layers"]
    kinds, ffn = p["indexer_types"], p["mlp_layer_types"]
    dense = ffn.index("sparse") if "sparse" in ffn else L
    if len(kinds) != L or len(ffn) != L or set(kinds) - {"full", "shared"} \
            or kinds[0] != "full" or any(f != "sparse" for f in ffn[dense:]) \
            or dense != min(p["first_k_dense_replace"], L):
        raise ValueError("indexer_types and mlp_layer_types name "
                         "num_hidden_layers layers: full | shared, the first "
                         "full; first_k_dense_replace dense ones leading")
    return dense


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a GLM-5.2 ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import glm_moe_dsa

    p = published
    dense = check(p)
    held = p["n_routed_experts"]
    return glm_moe_dsa(
        "tiny", index_pattern="".join("F" if k == "full" else "s"
                                      for k in p["indexer_types"]),
        n_layer=p["num_hidden_layers"], n_head=p["num_attention_heads"],
        d_model=p["hidden_size"], d_ff=p["intermediate_size"],
        vocab_size=p["vocab_size"], max_seq=p["max_position_embeddings"],
        norm_eps=p["rms_norm_eps"],
        rope_theta=float(p["rope_parameters"]["rope_theta"]),
        q_lora_rank=p["q_lora_rank"], kv_lora_rank=p["kv_lora_rank"],
        qk_nope_head_dim=p["qk_nope_head_dim"],
        qk_rope_head_dim=p["qk_rope_head_dim"], v_head_dim=p["v_head_dim"],
        index_topk=p["index_topk"], index_heads=p["index_n_heads"],
        index_head_dim=p["index_head_dim"],
        num_experts=p.get("router_experts", held),
        moe_experts_held=held if "router_experts" in p else 0,
        moe_first_held=p.get("first_expert_held", 0),
        moe_top_k=p["num_experts_per_tok"],
        moe_d_ff=p["moe_intermediate_size"],
        moe_shared_d_ff=p["n_shared_experts"] * p["moe_intermediate_size"],
        moe_norm_topk=p["norm_topk_prob"],
        moe_routed_scale=float(p["routed_scaling_factor"]),
        moe_first_dense=dense,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import glm_moe_dsa as reference

    if flash_attention:
        raise ValueError("attention over an indexer's selection is served "
                         "here, not trained: the flash kernel is the train "
                         "cells'")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE layer's attention, an indexer, the dense
    FFN, an expert layer's router and shared expert, ONE routed expert, and
    the head's slice as held here; norms are left out."""
    p = published
    d, H, ql, r = (p["hidden_size"], p["num_attention_heads"],
                   p["q_lora_rank"], p["kv_lora_rank"])
    return {"attention": d * ql + ql * H * p["qk_head_dim"]
            + d * (r + p["qk_rope_head_dim"])
            + r * H * (p["qk_nope_head_dim"] + p["v_head_dim"])
            + H * p["v_head_dim"] * d,
            "indexer": ql * p["index_n_heads"] * p["index_head_dim"]
            + d * (p["index_head_dim"] + p["index_n_heads"]),
            "dense": 3 * d * p["intermediate_size"],
            "router": d * p.get("router_experts", p["n_routed_experts"]),
            "shared": 3 * d * p["n_shared_experts"]
            * p["moe_intermediate_size"],
            "expert": 3 * d * p["moe_intermediate_size"],
            "head": d * p["vocab_size"]}


def cache_bytes_per_token(published: dict, itemsize: int = 2) -> dict:
    """What a cached position holds: the latents every layer uses (``used``)
    and lays out (``stored``: a row of whole 128-word tiles, two 2-byte
    values a word, ``deepspeed_tpu/ops/sparse_mla_attention.py``), and the
    indexer's key of the ``full`` layers."""
    p = published
    values = p["kv_lora_rank"] + p["qk_rope_head_dim"]
    per_word = 4 // itemsize
    words = -(-values // (per_word * 128)) * 128
    full = sum(k == "full" for k in p["indexer_types"])
    L = p["num_hidden_layers"]
    return {"used": L * values * itemsize, "stored": L * words * 4,
            "indexer_keys": full * p["index_head_dim"] * itemsize}


def flops_per_token(published: dict, context: int) -> dict:
    """Forward FLOPs of one token with ``context`` positions behind it: 2 a
    parameter it is multiplied by (the chosen experts of ALL the router's),
    the indexer's score over every live key in a ``full`` layer, and the
    attention over the positions selected."""
    p, n = published, layer_params(published)
    L = p["num_hidden_layers"]
    full = sum(k == "full" for k in p["indexer_types"])
    dense = check(p)
    keys = min(context, p["index_topk"])
    H = p["num_attention_heads"]
    return {"attention": L * (2.0 * n["attention"] + 2.0 * H * keys * (
                p["qk_head_dim"] + p["v_head_dim"])),
            "indexer": full * (2.0 * n["indexer"] + 2.0 * p["index_n_heads"]
                               * p["index_head_dim"] * context),
            "dense": 2.0 * dense * n["dense"],
            "experts": 2.0 * (L - dense) * (
                n["router"] + n["shared"]
                + p["num_experts_per_tok"] * n["expert"]),
            "head": 2.0 * n["head"]}

"""GLM-5.3-Flash family (``model_type: glm5_next_text``): from a configuration
file's published keys to the repo's model, the parameters a layer holds and
the operations a token needs.

Layer ``i`` mixes with ``layer_types[i]`` — ``linear_attention`` (Kimi Delta
Attention: ``linear_attn_config``'s heads of ``head_dim`` key and value
channels behind depthwise convs, a float32 delta-rule state a head) or
``deepseek_sparse_attention`` (a latent with no rope part read through an
indexer's selection over keys pooled ``index_kpool`` positions a key) — and
feeds forward with ``mlp_layer_types[i]`` (``dense`` | ``sparse``: sigmoid
``noaux_tc`` experts beside a shared one), all around ``hc_mult`` residual
streams mixed by Sinkhorn maps (``mhc``), every gated FFN clamped at
``swiglu_limit``. A chip's share of a stated deployment holds
``n_routed_experts`` of the published ``router_experts`` (both under
``config``; the second, with ``first_expert_held``, is the file's
``deployment`` in numbers, under ``assumed``).

The shared serving kind hands a reference ``n_head`` and
``layer_norm_epsilon``: carried as aliases of ``num_attention_heads`` and
``rms_norm_eps``.
"""

from __future__ import annotations

# keys this family runs one value of
REFUSED = {"model_type": "glm5_next_text", "attention_bias": False,
           "hidden_act": "silu", "mhc": True, "mla_use_nope": True,
           "qk_rope_head_dim": 0, "index_kpool_compress": True,
           "index_kpool_always_select_tail": True,
           "scoring_func": "sigmoid", "topk_method": "noaux_tc",
           "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
           "num_nextn_predict_layers": 0}
ALIASES = (("n_head", "num_attention_heads"),
           ("layer_norm_epsilon", "rms_norm_eps"),
           ("num_key_value_heads", "num_attention_heads"))
MIXERS = {"linear_attention": "K", "deepseek_sparse_attention": "A"}


def check(published: dict) -> int:
    """Refuse what this family does not run; the leading dense layers."""
    p = published
    for key, only in REFUSED.items():
        if p.get(key, only) != only:
            raise ValueError(f"{key}={p[key]!r}: this family runs {only!r}")
    for alias, key in ALIASES:
        if alias in p and p[alias] != p[key]:
            raise ValueError(f"{alias} is an alias of {key}")
    if p["qk_head_dim"] != p["qk_nope_head_dim"] + p["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    L = p["num_hidden_layers"]
    mixers, ffn, lin = (p["layer_types"], p["mlp_layer_types"],
                        p["linear_attn_config"])
    sparse = [i for i, m in enumerate(mixers)
              if m == "deepseek_sparse_attention"]
    dense = ffn.index("sparse") if "sparse" in ffn else L
    if len(mixers) != L or len(ffn) != L or set(mixers) - set(MIXERS) \
            or any(f != "sparse" for f in ffn[dense:]) \
            or dense != min(p["first_k_dense_replace"], L):
        raise ValueError("layer_types and mlp_layer_types name "
                         "num_hidden_layers layers; first_k_dense_replace "
                         "dense ones leading")
    if lin["full_attn_layers"] != sparse or lin["kda_layers"] != [
            i for i in range(L) if i not in sparse]:
        raise ValueError("linear_attn_config's two layer lists have to say "
                         "what layer_types says")
    if len(p["indexer_types"]) != L or set(p["indexer_types"]) - {"full"}:
        raise ValueError("indexer_types names every layer 'full': no "
                         "attention layer of this family takes a selection "
                         "over")
    return dense


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a GLM-5.3-Flash ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import glm5_next

    p = published
    dense = check(p)
    held = p["n_routed_experts"]
    lin = p["linear_attn_config"]
    mixers = "".join(MIXERS[m] for m in p["layer_types"])
    return glm5_next(
        "tiny", mixer_pattern=mixers,
        index_pattern="".join("F" if m == "A" else "-" for m in mixers),
        n_layer=p["num_hidden_layers"], n_head=p["num_attention_heads"],
        d_model=p["hidden_size"], d_ff=p["intermediate_size"],
        vocab_size=p["vocab_size"], max_seq=p["max_position_embeddings"],
        norm_eps=p["rms_norm_eps"],
        q_lora_rank=p["q_lora_rank"], kv_lora_rank=p["kv_lora_rank"],
        qk_nope_head_dim=p["qk_nope_head_dim"],
        qk_rope_head_dim=p["qk_rope_head_dim"], v_head_dim=p["v_head_dim"],
        index_topk=p["index_topk"], index_heads=p["index_n_heads"],
        index_head_dim=p["index_head_dim"], index_kpool=p["index_kpool"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_rank=p.get("kda_low_rank", lin["head_dim"]),
        kda_gate_floor=float(lin["gate_lower_bound"]),
        hc_mult=p["hc_mult"], hc_sinkhorn_iters=p["hc_sinkhorn_iters"],
        hc_eps=float(p["hc_eps"]), swiglu_limit=float(p["swiglu_limit"]),
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

    from ..reference import glm5_next as reference

    if flash_attention:
        raise ValueError("delta-rule mixers beside attention over an "
                         "indexer's selection are served here, not trained: "
                         "the flash kernel is the train cells'")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE KDA mixer, ONE attention layer's attention,
    an indexer, a layer's mHC maps, the dense FFN, an expert layer's router
    and shared expert, ONE routed expert, and the head's slice as held here;
    norms, convs and biases are left out."""
    p = published
    d, H, ql, r = (p["hidden_size"], p["num_attention_heads"],
                   p["q_lora_rank"], p["kv_lora_rank"])
    lin, n = p["linear_attn_config"], p["hc_mult"]
    inner = lin["num_heads"] * lin["head_dim"]
    rank = p.get("kda_low_rank", lin["head_dim"])
    return {"kda": 4 * d * inner + d * lin["num_heads"]
            + 2 * rank * (d + inner),
            "attention": d * ql + ql * H * p["qk_head_dim"] + d * r
            + r * H * (p["qk_nope_head_dim"] + p["v_head_dim"])
            + H * p["v_head_dim"] * d,
            "indexer": ql * p["index_n_heads"] * p["index_head_dim"]
            + d * (p["index_head_dim"] + p["index_n_heads"]),
            "mhc": 2 * n * d * (n * n + 2 * n),
            "dense": 3 * d * p["intermediate_size"],
            "router": d * p.get("router_experts", p["n_routed_experts"]),
            "shared": 3 * d * p["n_shared_experts"]
            * p["moe_intermediate_size"],
            "expert": 3 * d * p["moe_intermediate_size"],
            "head": d * p["vocab_size"]}


def kinds(published: dict) -> dict:
    """How many layers of each kind the configuration holds."""
    p = published
    return {"kda": p["layer_types"].count("linear_attention"),
            "attention": p["layer_types"].count("deepseek_sparse_attention"),
            "dense": p["mlp_layer_types"].count("dense"),
            "routed": p["mlp_layer_types"].count("sparse"),
            "layers": p["num_hidden_layers"]}


def state_bytes_per_slot(published: dict, itemsize: int = 2) -> dict:
    """What a slot holds whatever its length: the KDA layers' float32 state
    and conv tails, the open group's indexer keys."""
    p, k = published, kinds(published)
    lin = p["linear_attn_config"]
    H, D = lin["num_heads"], lin["head_dim"]
    return {"kda": k["kda"] * H * D * D * 4,
            "conv": k["kda"] * (lin["short_conv_kernel_size"] - 1) * 3 * H * D
            * itemsize,
            "open_keys": k["attention"] * (p["index_kpool"] - 1)
            * p["index_head_dim"] * itemsize}


def cache_bytes_per_token(published: dict, itemsize: int = 2) -> dict:
    """What a cached position holds: the attention layers' latents (a row is
    exactly the latent: no rope part, no padding) and a pooled indexer key's
    share."""
    p, k = published, kinds(published)
    return {"latents": k["attention"] * p["kv_lora_rank"] * itemsize,
            "pooled_keys": k["attention"] * p["index_head_dim"] * itemsize
            // p["index_kpool"]}


def flops_per_token(published: dict, context: int) -> dict:
    """Forward FLOPs of one token with ``context`` positions behind it: 2 a
    parameter it is multiplied by (the chosen experts of ALL the router's),
    8 a state value of every KDA head, the indexer's score over every closed
    group, the attention over the positions selected."""
    p, n, k = published, layer_params(published), kinds(published)
    lin = p["linear_attn_config"]
    keys = min(context, p["index_topk"] + p["index_kpool"])
    H = p["num_attention_heads"]
    return {"kda": k["kda"] * (2.0 * n["kda"] + 8.0 * lin["num_heads"]
                               * lin["head_dim"] ** 2),
            "attention": k["attention"] * (2.0 * n["attention"] + 2.0 * H
                                           * keys * (p["qk_head_dim"]
                                                     + p["v_head_dim"])),
            "indexer": k["attention"] * (
                2.0 * n["indexer"] + 2.0 * p["index_n_heads"]
                * p["index_head_dim"] * context / p["index_kpool"]),
            "mhc": 2.0 * k["layers"] * n["mhc"],
            "dense": 2.0 * k["dense"] * n["dense"],
            "experts": 2.0 * k["routed"] * (
                n["router"] + n["shared"]
                + p["num_experts_per_tok"] * n["expert"]),
            "head": 2.0 * n["head"]}

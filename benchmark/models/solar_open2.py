"""Solar-Open2 family (``model_type: solar_open2``): from a configuration
file's published keys to the repo's model, the parameters a layer holds and
the operations a token needs.

Layer ``l`` mixes with softmax GQA where ``l in gqa_layers`` (64 query heads
over 8 KV heads of 128, no position code: ``use_rope`` false, an output gate:
``use_gqa_gate``) and with Kimi Delta Attention elsewhere
(``linear_attn_config``'s heads of ``head_dim`` key and value channels behind
depthwise convs, a float32 delta-rule state a head; the gate has no floor
and, with ``kda_allow_neg_eigval``, beta reaches 2); every layer feeds forward
through sigmoid-routed experts beside a shared one (``first_k_dense_replace``
0). A chip's share of a stated deployment holds ``n_routed_experts`` of the
published ``router_experts`` (both under ``config``; the second, with
``first_expert_held``, is the file's ``deployment`` in numbers, under
``assumed``).

The shared serving kind hands a reference ``n_head`` and
``layer_norm_epsilon``: carried as aliases of ``num_attention_heads`` and
``rms_norm_eps``.
"""

from __future__ import annotations

# keys this family runs one value of
REFUSED = {"model_type": "solar_open2", "use_rope": False,
           "use_gqa_gate": True, "kda_use_full_proj": False,
           "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
           "tie_word_embeddings": False}
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
    L, every = p["num_hidden_layers"], p["gqa_interval"] + 1
    if p["gqa_layers"] != list(range(0, L, every)):
        raise ValueError("gqa_layers has to name every (gqa_interval + 1)-th "
                         "layer from 0 on")
    lin = p["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("linear_attn_config.num_kv_heads: as many key heads "
                         "as value heads (null)")
    return "".join("A" if i in p["gqa_layers"] else "K" for i in range(L))


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a Solar-Open2 ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import solar_open2

    p = published
    held = p["n_routed_experts"]
    lin = p["linear_attn_config"]
    return solar_open2(
        "tiny", mixer_pattern=check(p), n_layer=p["num_hidden_layers"],
        n_head=p["num_attention_heads"], n_kv_head=p["num_key_value_heads"],
        d_model=p["hidden_size"], qk_head_dim=p["head_dim"],
        d_ff=p["intermediate_size"], vocab_size=p["vocab_size"],
        max_seq=p["max_position_embeddings"], norm_eps=p["rms_norm_eps"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_rank=p.get("kda_low_rank", lin["head_dim"]),
        num_experts=p.get("router_experts", held),
        moe_experts_held=held if "router_experts" in p else 0,
        moe_first_held=p.get("first_expert_held", 0),
        moe_top_k=p["num_experts_per_tok"],
        moe_d_ff=p["moe_intermediate_size"],
        moe_shared_d_ff=p["n_shared_experts"] * p["moe_intermediate_size"],
        moe_norm_topk=p["norm_topk_prob"],
        moe_routed_scale=float(p["routed_scaling_factor"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    from ..reference import solar_open2 as reference

    if flash_attention:
        raise ValueError("delta-rule mixers beside gated GQA layers are "
                         "served here, not trained: the flash kernel is the "
                         "train cells'")
    reference.configure(published)
    cfg = model_config(published, compute_dtype)
    return cfg, build_model(cfg)


def layer_params(published: dict) -> dict:
    """Matmul parameters of ONE KDA mixer, ONE attention layer's attention
    (its output gate with it), a layer's router and shared expert, ONE routed
    expert, and the head's slice as held here; norms and convs left out."""
    p = published
    d, H, KV, hd = (p["hidden_size"], p["num_attention_heads"],
                    p["num_key_value_heads"], p["head_dim"])
    lin = p["linear_attn_config"]
    inner = lin["num_heads"] * lin["head_dim"]
    rank = p.get("kda_low_rank", lin["head_dim"])
    return {"kda": 4 * d * inner + d * lin["num_heads"]
            + 2 * rank * (d + inner),
            "attention": 3 * d * H * hd + 2 * d * KV * hd,
            "router": d * p.get("router_experts", p["n_routed_experts"]),
            "shared": 3 * d * p["n_shared_experts"]
            * p["moe_intermediate_size"],
            "expert": 3 * d * p["moe_intermediate_size"],
            "head": d * p["vocab_size"]}


def kinds(published: dict) -> dict:
    """How many layers of each kind the configuration holds."""
    p = published
    L, A = p["num_hidden_layers"], len(p["gqa_layers"])
    return {"kda": L - A, "attention": A, "routed": L, "layers": L}


def state_bytes_per_slot(published: dict, itemsize: int = 2) -> dict:
    """What a slot holds whatever its length: the KDA layers' float32 state
    and conv tails."""
    p, k = published, kinds(published)
    lin = p["linear_attn_config"]
    H, D = lin["num_heads"], lin["head_dim"]
    return {"kda": k["kda"] * H * D * D * 4,
            "conv": k["kda"] * (lin["short_conv_kernel_size"] - 1) * 3 * H * D
            * itemsize}


def cache_bytes_per_token(published: dict, itemsize: int = 2) -> dict:
    """What a cached position holds: the attention layers' K and V."""
    p, k = published, kinds(published)
    return {"kv": k["attention"] * 2 * p["num_key_value_heads"]
            * p["head_dim"] * itemsize}


def flops_per_token(published: dict, context: int) -> dict:
    """Forward FLOPs of one token with ``context`` positions behind it: 2 a
    parameter it is multiplied by (the chosen experts of ALL the router's),
    8 a state value of every KDA head, the attention over every position."""
    p, n, k = published, layer_params(published), kinds(published)
    lin = p["linear_attn_config"]
    return {"kda": k["kda"] * (2.0 * n["kda"] + 8.0 * lin["num_heads"]
                               * lin["head_dim"] ** 2),
            "attention": k["attention"] * (
                2.0 * n["attention"] + 4.0 * p["num_attention_heads"]
                * p["head_dim"] * context),
            "experts": 2.0 * k["routed"] * (
                n["router"] + n["shared"]
                + p["num_experts_per_tok"] * n["expert"]),
            "head": 2.0 * n["head"]}

"""Model-family presets over the unified TransformerLM.

Covers the model families of the reference's published results (SURVEY.md §6):
GPT-2 (125M/1.5B), Llama-2 (7B/13B/70B), BERT-class encoder sizes are served
by the same trunk with ``causal=False`` planned, Mixtral via ``num_experts``.
"""

from __future__ import annotations

from .transformer import MuP, TransformerConfig, TransformerLM


def gpt2(size: str = "125m", **overrides) -> TransformerConfig:
    table = {
        "125m": dict(n_layer=12, n_head=12, d_model=768),
        "350m": dict(n_layer=24, n_head=16, d_model=1024),
        "774m": dict(n_layer=36, n_head=20, d_model=1280),
        "1.5b": dict(n_layer=48, n_head=25, d_model=1600),
    }
    base = dict(vocab_size=50257, max_seq=1024, pos_embedding="learned",
                norm="layernorm", activation="gelu", use_bias=True,
                tie_embeddings=True)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def llama2(size: str = "7b", **overrides) -> TransformerConfig:
    table = {
        "tiny": dict(n_layer=4, n_head=8, n_kv_head=4, d_model=256, d_ff=688),
        "7b": dict(n_layer=32, n_head=32, d_model=4096, d_ff=11008),
        "13b": dict(n_layer=40, n_head=40, d_model=5120, d_ff=13824),
        "70b": dict(n_layer=80, n_head=64, n_kv_head=8, d_model=8192, d_ff=28672),
    }
    base = dict(vocab_size=32000, max_seq=4096, pos_embedding="rope",
                norm="rmsnorm", activation="silu_glu", use_bias=False,
                tie_embeddings=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def mixtral(size: str = "8x7b", **overrides) -> TransformerConfig:
    table = {
        "tiny": dict(n_layer=4, n_head=8, n_kv_head=4, d_model=256, d_ff=512,
                     num_experts=4, moe_top_k=2),
        "8x7b": dict(n_layer=32, n_head=32, n_kv_head=8, d_model=4096, d_ff=14336,
                     num_experts=8, moe_top_k=2),
    }
    base = dict(vocab_size=32000, max_seq=4096, pos_embedding="rope",
                norm="rmsnorm", activation="silu_glu", use_bias=False,
                tie_embeddings=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def deepseek_v3(size: str = "kanana-2-30b-a3b", **overrides) -> TransformerConfig:
    """The DeepSeek-V3 block (``model_type: deepseek_v3``): latent
    attention, a leading dense layer, then sigmoid-routed experts beside a
    shared MLP. ``kanana-2-30b-a3b`` is kakaocorp/kanana-2-30b-a3b-
    instruct-2601's ``config.json`` (``q_lora_rank`` null; ``n_group`` 1, so
    the router chooses over one group)."""
    table = {
        "tiny": dict(n_layer=3, n_head=4, d_model=128, d_ff=256,
                     vocab_size=509, max_seq=256, kv_lora_rank=32,
                     qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                     num_experts=8, moe_top_k=2, moe_d_ff=64,
                     moe_shared_d_ff=64),
        "kanana-2-30b-a3b": dict(
            n_layer=48, n_head=32, d_model=2048, d_ff=6144,
            vocab_size=128256, max_seq=32768, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            num_experts=128, moe_top_k=6, moe_d_ff=768,
            moe_shared_d_ff=2 * 768, moe_routed_scale=2.448),
    }
    base = dict(attention="mla", pos_embedding="rope", norm="rmsnorm",
                norm_eps=1e-6, activation="silu_glu", use_bias=False,
                tie_embeddings=False, moe_router="sigmoid",
                moe_norm_topk=True, moe_first_dense=1, rope_theta=1e6,
                fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def glm_moe_dsa(size: str = "tiny", **overrides) -> TransformerConfig:
    """GLM-5.2 family (``model_type: glm_moe_dsa``): the DeepSeek-V3 block
    with a low-rank query (``q_lora_rank``) whose attention reads only the
    ``index_topk`` positions a learned indexer picks (``index_pattern``,
    models/dsa.py: ``F`` layers have an indexer, ``s`` layers take the pick
    of the last ``F`` before them). ``"5.2"`` is zai-org/GLM-5.2's
    ``config.json`` (78 layers: ``FFF`` then ``sssF`` eighteen times and
    ``sss``; three dense layers, then 256 experts top-8 beside a shared
    one). ``"tiny"`` keeps what the cache's layout turns on at unit-test
    size: a whole period ``F s s s F s s`` behind one dense layer, a
    selection smaller than a test's contexts, an odd number of indexer
    heads."""
    table = {
        "tiny": dict(index_pattern="FsssFss", n_layer=7, n_head=4,
                     d_model=64, d_ff=128, vocab_size=251, max_seq=256,
                     q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, index_topk=16,
                     index_heads=3, index_head_dim=16, num_experts=8,
                     moe_top_k=2, moe_d_ff=32, moe_shared_d_ff=32,
                     moe_first_dense=1),
        "5.2": dict(index_pattern="FFF" + 18 * "sssF" + "sss", n_layer=78,
                    n_head=64, d_model=6144, d_ff=12288, vocab_size=154880,
                    max_seq=1048576, q_lora_rank=2048, kv_lora_rank=512,
                    qk_nope_head_dim=192, qk_rope_head_dim=64,
                    v_head_dim=256, index_topk=2048, index_heads=32,
                    index_head_dim=128, num_experts=256, moe_top_k=8,
                    moe_d_ff=2048, moe_shared_d_ff=2048, moe_first_dense=3),
    }
    base = dict(attention="mla", pos_embedding="rope", norm="rmsnorm",
                norm_eps=1e-5, activation="silu_glu", use_bias=False,
                tie_embeddings=False, moe_router="sigmoid",
                moe_norm_topk=True, moe_routed_scale=2.5, rope_theta=8e6,
                fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def glm5_next(size: str = "tiny", **overrides) -> TransformerConfig:
    """GLM-5.3-Flash family (``model_type: glm5_next_text``): three layers
    in four mix with Kimi Delta Attention (``mixer_pattern`` "K",
    models/kda.py) where the fourth attends over a latent with no rope part
    through an indexer's selection over pooled keys (``index_kpool``), four
    residual streams mixed by Sinkhorn maps (``hc_mult``, models/mhc.py),
    every gated FFN clamped (``swiglu_limit``). ``"5.3-flash"`` is
    zai-org/GLM-5.3-Flash's ``config.json`` (45 layers ``KKKA`` eleven times
    and ``K``; three dense layers, then 288 experts top-8 beside a shared
    one; KDA's two low-rank widths are Kimi Linear's, the head width).
    ``"tiny"`` keeps what the cache's layout turns on at unit-test size: a
    whole period behind a dense KDA layer, a selection smaller than a
    test's contexts."""
    table = {
        "tiny": dict(mixer_pattern="KAKKK", index_pattern="-F---", n_layer=5,
                     n_head=4, d_model=64, d_ff=128, vocab_size=251,
                     max_seq=256, q_lora_rank=48, kv_lora_rank=32,
                     qk_nope_head_dim=16, v_head_dim=16, index_topk=16,
                     index_heads=3, index_head_dim=16, kda_heads=4,
                     kda_head_dim=16, kda_rank=16, num_experts=8,
                     moe_top_k=2, moe_d_ff=32, moe_shared_d_ff=32,
                     moe_first_dense=1),
        "5.3-flash": dict(mixer_pattern=11 * "KKKA" + "K",
                          index_pattern=11 * "---F" + "-", n_layer=45,
                          n_head=64, d_model=4096, d_ff=12288,
                          vocab_size=154880, max_seq=1048576,
                          q_lora_rank=1536, kv_lora_rank=512,
                          qk_nope_head_dim=256, v_head_dim=256,
                          index_topk=2048, index_heads=32, index_head_dim=128,
                          kda_heads=64, kda_head_dim=128, kda_rank=128,
                          num_experts=288, moe_top_k=8, moe_d_ff=2048,
                          moe_shared_d_ff=2048, moe_first_dense=3),
    }
    base = dict(attention="mla", pos_embedding="none", norm="rmsnorm",
                norm_eps=1e-5, activation="silu_glu", use_bias=False,
                tie_embeddings=False, moe_router="sigmoid",
                moe_norm_topk=True, moe_routed_scale=2.5, qk_rope_head_dim=0,
                index_kpool=4, kda_conv=4, kda_gate_floor=-5.0, hc_mult=4,
                hc_sinkhorn_iters=20, hc_eps=1e-6, swiglu_limit=10.0,
                fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def solar_open2(size: str = "tiny", **overrides) -> TransformerConfig:
    """Solar-Open2 family (``model_type: solar_open2``): three layers in
    four mix with Kimi Delta Attention behind Kimi Linear's own gate — no
    floor (``kda_gate_floor`` 0) — and a beta in (0, 2)
    (``kda_neg_eigval``), where the fourth (the first of every period: G K K
    K) is softmax GQA with no position code whose output passes a sigmoid
    gate a head a channel (``attn_out_gate``); one residual stream; sigmoid
    experts beside a shared one in every layer. ``"250b"`` is
    upstage/Solar-Open2-250B's ``config.json`` (48 layers, 64 query heads of
    128 over 8 KV heads, 320 experts of 1280 top-8; KDA's two low-rank widths
    are Kimi Linear's, the head width). ``"tiny"`` keeps a whole period at
    unit-test size, four query heads a KV head."""
    table = {
        "tiny": dict(mixer_pattern="AKKK", n_layer=4, n_head=4, n_kv_head=1,
                     d_model=64, qk_head_dim=32, d_ff=128, vocab_size=251,
                     max_seq=512, kda_heads=4, kda_head_dim=16, kda_rank=16,
                     num_experts=8, moe_top_k=2, moe_d_ff=32,
                     moe_shared_d_ff=32),
        "250b": dict(mixer_pattern=12 * "AKKK", n_layer=48, n_head=64,
                     n_kv_head=8, d_model=4096, qk_head_dim=128, d_ff=10240,
                     vocab_size=196608, max_seq=1048576, kda_heads=64,
                     kda_head_dim=128, kda_rank=128, num_experts=320,
                     moe_top_k=8, moe_d_ff=1280, moe_shared_d_ff=1280),
    }
    base = dict(attention="mha", pos_embedding="none", norm="rmsnorm",
                norm_eps=1e-5, activation="silu_glu", use_bias=False,
                tie_embeddings=False, moe_router="sigmoid",
                moe_norm_topk=True, moe_routed_scale=1.0, moe_first_dense=0,
                kda_conv=4, kda_gate_floor=0.0, kda_neg_eigval=True,
                attn_out_gate=True, fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def bailing_hybrid(size: str = "tiny", **overrides) -> TransformerConfig:
    """Ling-3.0-flash family (``model_type: bailing_hybrid``): five layers in
    six mix with Kimi Delta Attention behind the bounded gate, full maps for
    the decay and the output gate (``kda_rank`` 0: ``no_kda_lora``) and a
    learned gain on q and k (``kda_qk_norm``), the sixth is DeepSeek's latent
    attention over every live position with rope on its rope part and an
    output gate a head (``attn_out_gate='head'``); one residual stream; two
    dense layers, then sigmoid experts chosen within the best groups
    (``moe_n_group`` / ``moe_topk_group``) beside a shared one, the routed
    and the shared clamped by a value a layer. ``"3.0-flash"`` is
    inclusionAI/Ling-3.0-flash's ``config.json`` (42 layers ``KKKKKA`` seven
    times, 32 heads of 128, 512 experts of 768 top-8 in 8 groups of which 4
    are kept). ``"tiny"`` keeps a whole period behind a dense KDA layer at
    unit-test size, four groups of four experts, both clamps on the last
    layers."""
    table = {
        "tiny": dict(mixer_pattern="KKKKKKA", n_layer=7, n_head=4,
                     d_model=64, d_ff=128, vocab_size=251, max_seq=512,
                     kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, kda_heads=4,
                     kda_head_dim=16, num_experts=16, moe_top_k=4,
                     moe_n_group=4, moe_topk_group=2, moe_d_ff=32,
                     moe_shared_d_ff=32, moe_first_dense=1,
                     moe_swiglu_limits=(0, 0, 0, 0, 4, 4, 4),
                     moe_shared_swiglu_limits=(0, 0, 0, 5, 5, 7, 7)),
        "3.0-flash": dict(mixer_pattern=7 * "KKKKKA", n_layer=42, n_head=32,
                          d_model=2560, d_ff=6144, vocab_size=157184,
                          max_seq=262144, kv_lora_rank=512,
                          qk_nope_head_dim=128, qk_rope_head_dim=64,
                          v_head_dim=128, kda_heads=32, kda_head_dim=128,
                          num_experts=512, moe_top_k=8, moe_n_group=8,
                          moe_topk_group=4, moe_d_ff=768,
                          moe_shared_d_ff=768, moe_first_dense=2,
                          moe_swiglu_limits=35 * (0,) + 7 * (4,),
                          moe_shared_swiglu_limits=34 * (0,) + 6 * (5,)
                          + 2 * (7,)),
    }
    base = dict(attention="mla", pos_embedding="rope", norm="rmsnorm",
                norm_eps=1e-6, activation="silu_glu", use_bias=False,
                tie_embeddings=False, moe_router="sigmoid",
                moe_norm_topk=True, moe_routed_scale=2.5, rope_theta=6e6,
                kda_conv=4, kda_rank=0, kda_gate_floor=-5.0,
                kda_qk_norm=True, attn_out_gate="head",
                mla_query_init_gain=3.0, fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def nemotron_h(size: str = "3-super-120b-a12b", **overrides) -> TransformerConfig:
    """The NemotronH block (``model_type: nemotron_h``): every layer ONE
    mixer, its kind a letter of the published ``hybrid_override_pattern`` —
    ``M`` Mamba-2, ``E`` latent experts, ``*`` GQA attention with no position
    code (``models/hybrid.py``). ``3-super-120b-a12b`` is
    nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's ``config.json``."""
    table = {
        "tiny": dict(block_pattern="MEM*EM", n_head=4, n_kv_head=2,
                     d_model=64, vocab_size=251, max_seq=256, ssm_heads=8,
                     ssm_head_dim=16, ssm_groups=2, ssm_state=16,
                     ssm_chunk=8, num_experts=16, moe_top_k=4, moe_d_ff=48,
                     moe_latent_dim=32, moe_shared_d_ff=96,
                     moe_routed_scale=2.5),
        "3-super-120b-a12b": dict(
            block_pattern="MEMEMEM*E" + 2 * "MEMEMEM*E" + 4 * "MEMEMEMEM*E"
            + "MEMEMEM*E" + "MEMEMEME",
            n_head=32, n_kv_head=2, d_model=4096, vocab_size=131072,
            max_seq=262144, ssm_heads=128, ssm_head_dim=64, ssm_groups=8,
            ssm_state=128, ssm_chunk=128, num_experts=512, moe_top_k=22,
            moe_d_ff=2688, moe_latent_dim=1024, moe_shared_d_ff=5376,
            moe_routed_scale=5.0),
    }
    base = dict(pos_embedding="none", norm="rmsnorm", norm_eps=1e-5,
                activation="relu2", use_bias=False, tie_embeddings=False,
                moe_router="sigmoid", moe_norm_topk=True, fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    base.setdefault("n_layer", len(base["block_pattern"]))
    return TransformerConfig(**base)


def ouro(size: str = "2.6b", **overrides) -> TransformerConfig:
    """The Ouro looped LM (``model_type: ouro``, arXiv:2510.25741): a
    Llama-shaped trunk (rope, RMSNorm, SwiGLU, no biases, untied head) with
    a norm after each sub-layer as well as before it, applied
    ``total_ut_steps`` times over the same weights, the final norm closing
    every pass, and an exit gate. ``2.6b`` is ByteDance/Ouro-2.6B's
    ``config.json``; its ``early_exit_threshold`` 1 exits at the last pass,
    which is the one thing the trunk does."""
    table = {
        "tiny": dict(n_layer=4, n_head=4, d_model=64, d_ff=176,
                     vocab_size=251, max_seq=256, loop_steps=3),
        "2.6b": dict(n_layer=48, n_head=16, d_model=2048, d_ff=5632,
                     vocab_size=49152, max_seq=65536, loop_steps=4),
    }
    base = dict(pos_embedding="rope", norm="rmsnorm", norm_eps=1e-6,
                activation="silu_glu", use_bias=False, tie_embeddings=False,
                rope_theta=1e6, sandwich_norm=True, exit_gate=True,
                fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def bert(size: str = "base", **overrides) -> TransformerConfig:
    """Encoder (bidirectional) trunk + MLM objective — the BERT family the
    reference's flagship pretraining baseline uses
    (``docs/_tutorials/bert-pretraining.md``)."""
    table = {
        "tiny": dict(n_layer=2, n_head=4, d_model=128, d_ff=512, max_seq=128),
        "base": dict(n_layer=12, n_head=12, d_model=768, max_seq=512),
        "large": dict(n_layer=24, n_head=16, d_model=1024, max_seq=512),
    }
    base = dict(vocab_size=30522, pos_embedding="learned", norm="layernorm",
                activation="gelu", use_bias=True, tie_embeddings=True,
                causal=False, objective="mlm")
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def opt(size: str = "125m", **overrides) -> TransformerConfig:
    """OPT family (reference inference container ``containers/opt.py``):
    decoder with learned positions and ReLU FFN."""
    table = {
        "tiny": dict(n_layer=2, n_head=4, d_model=64, d_ff=256, max_seq=64),
        "125m": dict(n_layer=12, n_head=12, d_model=768),
        "1.3b": dict(n_layer=24, n_head=32, d_model=2048),
        "6.7b": dict(n_layer=32, n_head=32, d_model=4096),
        "13b": dict(n_layer=40, n_head=40, d_model=5120),
    }
    base = dict(vocab_size=50272, max_seq=2048, pos_embedding="learned",
                norm="layernorm", activation="relu", use_bias=True,
                tie_embeddings=True)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def bloom(size: str = "560m", **overrides) -> TransformerConfig:
    """Bloom family (reference container ``containers/bloom.py``): ALiBi
    position bias, no positional table. HF checkpoints import via the
    ``bloom`` family (fused per-head qkv split + embedding layernorm)."""
    table = {
        "tiny": dict(n_layer=2, n_head=4, d_model=64, d_ff=256, max_seq=64),
        "560m": dict(n_layer=24, n_head=16, d_model=1024),
        "7b": dict(n_layer=30, n_head=32, d_model=4096),
        "176b": dict(n_layer=70, n_head=112, d_model=14336),
    }
    base = dict(vocab_size=250880, max_seq=2048, pos_embedding="alibi",
                norm="layernorm", activation="gelu", use_bias=True,
                tie_embeddings=True)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def mimo_v2_flash(size: str = "tiny", **overrides) -> TransformerConfig:
    """MiMo-V2-Flash family (``model_type: mimo_v2_flash``): window layers
    (a sink a head, their own KV heads and theta) beside full ones
    (``attn_pattern``, models/windowed.py), keys and queries wider than
    values, rope on the leading third of a head, a dense SwiGLU layer then
    sigmoid-routed experts with no shared one. ``"flash"`` is the published
    309B-A15B configuration (48 layers: layer 0 full, then ``SSSSG`` once
    and ``SSSSSG`` seven times)."""
    table = {
        "tiny": dict(attn_pattern="GSSGS", n_layer=5, n_head=4, n_kv_head=1,
                     window_kv_heads=2, d_model=64, qk_head_dim=24,
                     v_head_dim=16, rotary_dim=8, window=128, d_ff=128,
                     num_experts=8, moe_top_k=2, moe_d_ff=32,
                     vocab_size=256, max_seq=1024),
        "flash": dict(attn_pattern="G" + "SSSSG" + 7 * "SSSSSG", n_layer=48,
                      n_head=64, n_kv_head=4, window_kv_heads=8,
                      d_model=4096, qk_head_dim=192, v_head_dim=128,
                      rotary_dim=64, window=128, d_ff=16384,
                      num_experts=256, moe_top_k=8, moe_d_ff=2048,
                      vocab_size=152576, max_seq=262144),
    }
    base = dict(pos_embedding="rope", norm="rmsnorm", norm_eps=1e-5,
                activation="silu_glu", use_bias=False, tie_embeddings=False,
                rope_theta=5e6, window_rope_theta=1e4, rope_halves=True,
                attn_sink=True, attn_value_scale=0.707,
                moe_router="sigmoid", moe_norm_topk=True,
                moe_routed_scale=1.0, moe_first_dense=1)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def zaya(size: str = "tiny", **overrides) -> TransformerConfig:
    """ZAYA1 family (``model_type: zaya``): every layer an attention
    sub-layer in a compressed latent behind two causal convolutions
    (``attention="cca"``, models/cca.py) then top-1 SwiGLU experts behind an
    MLP router whose state is carried from layer to layer
    (``moe_router="zaya"``, models/moe.py), a scale and a bias a channel on
    each side of each sub-layer (``residual_scale``), a tied head. ``"8b"``
    is the published ZAYA1-8B (40 layers, 8.3 B outside the embedding)."""
    table = {
        "tiny": dict(n_layer=3, n_head=4, n_kv_head=2, d_model=64,
                     qk_head_dim=8, rotary_dim=4, num_experts=4, moe_d_ff=32,
                     router_hidden=16, vocab_size=256, max_seq=1024),
        "8b": dict(n_layer=40, n_head=8, n_kv_head=2, d_model=2048,
                   qk_head_dim=128, rotary_dim=64, num_experts=16,
                   moe_d_ff=2048, router_hidden=256, vocab_size=262272,
                   max_seq=131072),
    }
    base = dict(pos_embedding="rope", norm="rmsnorm", norm_eps=1e-5,
                activation="silu_glu", use_bias=False, tie_embeddings=True,
                rope_theta=5e6, rope_halves=True, attention="cca",
                cca_conv=(2, 2), residual_scale=True, moe_router="zaya",
                moe_top_k=1, moe_norm_topk=False, fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    return TransformerConfig(**base)


def falcon_h1(size: str = "tiny", **overrides) -> TransformerConfig:
    """Falcon-H1 family (``model_type: falcon_h1``): every layer a Mamba-2
    mixer AND rotary GQA attention on the same normed input, both added to
    the stream, then a gated FFN (``block_pattern`` of ``P``,
    models/hybrid.py), every branch times a muP scalar (``mup``), an untied
    head. ``"34b"`` is tiiuae/Falcon-H1-34B-Instruct's ``config.json``;
    ``"tiny"`` keeps what the kernels' layouts turn on at unit-test size: 4
    heads a group, a head dim off the state size, 5 query heads a KV head,
    every multiplier off 1."""
    table = {
        "tiny": dict(
            n_layer=3, n_head=10, n_kv_head=2, d_model=64, qk_head_dim=8,
            d_ff=96, vocab_size=251, max_seq=256, ssm_heads=8,
            ssm_head_dim=16, ssm_groups=2, ssm_state=32, ssm_chunk=8,
            rope_theta=1e4,
            mup=MuP(embed=2.5, head=0.125, attn_in=0.5, attn_out=0.2,
                    key=0.25, ssm_in=0.5, ssm_out=0.3,
                    ssm=(0.35, 0.25, 0.18, 0.5, 0.7), mlp_gate=0.4,
                    mlp_down=0.15)),
        "34b": dict(
            n_layer=72, n_head=20, n_kv_head=4, d_model=5120,
            qk_head_dim=128, d_ff=21504, vocab_size=261120, max_seq=262144,
            ssm_heads=32, ssm_head_dim=128, ssm_groups=2, ssm_state=256,
            ssm_chunk=128, rope_theta=1e11,
            mup=MuP(embed=5.656854249492381, head=0.0078125, attn_in=1.0,
                    attn_out=0.0375, key=0.011048543456039804, ssm_in=0.25,
                    ssm_out=0.08838834764831845,
                    ssm=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
                    mlp_gate=0.1767766952966369,
                    mlp_down=0.011160714285714284)),
    }
    base = dict(pos_embedding="rope", rope_halves=True, norm="rmsnorm",
                norm_eps=1e-5, activation="silu_glu", use_bias=False,
                tie_embeddings=False, fused_xent=False)
    base.update(table[size])
    base.update(overrides)
    base.setdefault("block_pattern", "P" * base["n_layer"])
    return TransformerConfig(**base)


def tiny_test(**overrides) -> TransformerConfig:
    """Unit-test sized config (analog of the reference tests' SimpleModel)."""
    base = dict(vocab_size=256, n_layer=2, n_head=4, d_model=64, d_ff=128,
                max_seq=64, tie_embeddings=True)
    base.update(overrides)
    return TransformerConfig(**base)


def build_model(cfg, attention_fn=None):
    from .t5 import T5Config, T5Model

    if isinstance(cfg, T5Config):
        assert attention_fn is None, "T5 uses its own unscaled attention"
        return T5Model(cfg)
    if cfg.block_pattern:
        from .hybrid import HybridLM

        return HybridLM(cfg, attention_fn=attention_fn)
    if cfg.num_experts > 1:
        from .moe import MoETransformerLM

        return MoETransformerLM(cfg, attention_fn=attention_fn)
    return TransformerLM(cfg, attention_fn=attention_fn)


# (what tells a trunk that is served here and not trained, why)
_SERVED_NOT_TRAINED = (
    (lambda cfg: getattr(cfg, "mixer_pattern", ""),
     "a trunk of delta-rule mixers beside attention layers (mixer_pattern) "
     "is served, not trained here: the chunkwise delta-rule scan has no "
     "backward, and an indexer's selection has no gradient of its own"),
    (lambda cfg: getattr(cfg, "index_pattern", ""),
     "a trunk whose attention reads an indexer's selection (index_pattern) "
     "is served, not trained here: the selection has no gradient of its own "
     "(the indexer is trained by a loss against the attention's scores, "
     "arXiv:2512.02556), and a next-token loss through a frozen top-k under "
     "the model's name would be a guess"),
    (lambda cfg: getattr(cfg, "loop_steps", 1) > 1,
     "a looped trunk (loop_steps > 1) is served, not trained here: its "
     "objective is the expected loss over the exit distribution with an "
     "entropy term (arXiv:2510.25741), and a next-token loss on the last "
     "pass under the model's name would be a guess"),
    (lambda cfg: "P" in getattr(cfg, "block_pattern", ""),
     "a trunk of a Mamba-2 mixer and attention side by side in every layer "
     "(block_pattern 'P') is served, not trained here: the chunked scan has "
     "no backward"),
    (lambda cfg: getattr(cfg, "block_pattern", ""),
     "a trunk of one mixer a layer (block_pattern) is served, not trained "
     "here: the chunked scan's backward and the held experts' exchange are "
     "not written"),
    (lambda cfg: getattr(cfg, "attn_pattern", ""),
     "window layers beside full ones (attn_pattern) are served, not trained "
     "here: the blocked attention has no backward that skips the blocks "
     "outside a window, and the held experts' exchange is not written"),
    (lambda cfg: getattr(cfg, "attention", "") == "cca",
     "compressed convolutional attention behind the zaya router "
     "(attention='cca') is served, not trained here: the sorted expert rows "
     "have no backward, and the router's carried state and the convs' have "
     "none under a test"),
)


def why_not_trained(cfg):
    """Why a model of ``cfg`` is served here and not trained, or None: the
    one lookup the trainer makes (``runtime/engine.py``)."""
    return next((why for tells, why in _SERVED_NOT_TRAINED if tells(cfg)),
                None)

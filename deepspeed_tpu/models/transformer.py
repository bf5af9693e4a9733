"""Unified decoder-only transformer LM (GPT-2 and Llama families).

This is the flagship model the framework trains and serves. Functional style:
``init`` builds a param pytree, ``apply`` is a pure function, ``param_specs``
returns the TP/EP sharding rules as a matching pytree of ``PartitionSpec``.

Design choices that matter on TPU:
- **scan over stacked layers**: every per-layer weight carries a leading
  ``L`` dim and the block runs under ``lax.scan`` — one compiled layer body,
  remat-friendly, and the unit at which ZeRO-3 all-gathers params
  (the compiled analog of the reference fetch coordinator's per-submodule
  gather, ``partitioned_param_coordinator.py:256``).
- **parallelism by constraint**: batch dim sharded over ``(data, expert)``,
  sequence dim over ``seq``, heads/ffn over ``model``. Ulysses sequence
  parallelism (reference ``sequence/layer.py:15-85``, all-to-all that trades
  the sequence shard for a head shard around attention) is expressed as two
  resharding constraints — GSPMD emits the same all-to-alls.
- **MXU-friendly shapes**: weights live in (possibly stacked) 2-D matmul
  layouts, computation in bf16 with fp32 softmax/layernorm accumulations.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from itertools import groupby
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..platform.mesh import BATCH_AXES, constrain, current_mesh

B_AXES = BATCH_AXES  # ("data", "zero", "expert")


@dataclasses.dataclass(frozen=True)
class MuP:
    """The scalars a muP-parametrised model (Falcon-H1, ``model_type:
    falcon_h1``) multiplies its branches by, each applied where the model
    publishes it; 1 everywhere is every other family."""

    embed: float = 1.0        # the embedding's rows
    head: float = 1.0         # the logits
    attn_in: float = 1.0      # the attention's normed input
    attn_out: float = 1.0     # the attention's output, behind wo
    key: float = 1.0          # the keys, before the rotation
    ssm_in: float = 1.0       # the Mamba-2 mixer's normed input
    ssm_out: float = 1.0      # the mixer's output, behind w_out
    ssm: tuple = ()           # w_in's output by segment [z, x, B, C, dt]
    mlp_gate: float = 1.0     # the gate's pre-activation
    mlp_down: float = 1.0     # the FFN's output, behind w_out


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: Optional[int] = None       # < n_head => GQA/MQA (Llama-2-70B style)
    d_model: int = 768
    d_ff: Optional[int] = None            # default 4*d_model (gpt2) / from preset
    max_seq: int = 1024
    # family switches
    pos_embedding: str = "learned"        # "learned" (gpt2/opt) | "rope"
                                          # (llama) | "alibi" (bloom)
    norm: str = "layernorm"               # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-5                # HF llama checkpoints vary (1e-5/1e-6)
    activation: str = "gelu"              # "gelu" | "silu_glu" (llama) | "relu" (opt)
    use_bias: bool = True                 # gpt2 yes, llama no
    tie_embeddings: bool = True
    causal: bool = True                   # False => encoder (BERT family)
    objective: str = "clm"                # "clm" next-token | "mlm" (BERT)
                                          # | "feature" (CLIP text encoder:
                                          # apply() returns hidden states)
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None      # partial rotary (GPT-J/NeoX):
                                          # rotate only the first N dims/head
    # parallel residual: x + attn(norm1(x)) + mlp(norm_mlp(x)) in one hop
    # (GPT-J / GPT-NeoX / Falcon) instead of the sequential two-hop block.
    parallel_residual: bool = False
    # GPT-J / Falcon-7B share ONE layernorm for both branches (norm_mlp =
    # norm1); NeoX / Falcon-40B keep a second one.
    parallel_shared_ln: bool = False
    embed_norm: bool = False              # Bloom word_embeddings_layernorm
    lm_head_bias: bool = False            # GPT-J lm_head has a bias
    # >1: compute the unembedding matmul as a scan over that many vocab
    # column tiles (ops/tiled.py; reference zero/tiling.py TiledLinear) —
    # bounds the logits working set of a giant-vocab head on the XLA loss
    # path. The fused-xent path never materializes logits and ignores this.
    tiled_head: int = 1
    # post-LN block (BERT family): x = LN(x + attn(x)); x = LN(x + mlp(x)).
    # The norm params keep their pre-LN names: ln1 = post-attention LN,
    # ln2 = post-FFN LN; no final lnf exists.
    post_ln: bool = False
    # BERT MLM head transform: LN(gelu(x @ W + b)) before the tied decoder
    # (+ output bias). Only meaningful with objective="mlm".
    mlm_transform: bool = False
    # Fused Pallas softmax-xent over the unembedding (ops/xent.py): never
    # materializes (B,S,V) logits. None = auto (on for TPU when eligible:
    # tied embeddings, clm/mlm, seq/pipe axes unsharded; data-parallel
    # and vocab-sharded TP meshes both supported via shard_map).
    fused_xent: Optional[bool] = None
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16             # compute dtype
    # MoE (dense when num_experts == 1); see models/moe.py
    num_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: Optional[float] = None  # eval default: 2x train
    moe_min_capacity: int = 4
    moe_drop_tokens: bool = True          # False: capacity covers ALL tokens
    moe_aux_loss_weight: float = 0.01
    # DeepSeek-V3 expert layers (models/moe.py ``experts``):
    # "sigmoid" scores each expert with a sigmoid, chooses the top-k of
    # score + a learned selection bias (``router_bias``; the weight is the
    # unbiased score), never drops a token and has no aux loss.
    # "zaya" (ZAYA1, arXiv:2511.17127): an MLP router over a state carried
    # from layer to layer at the same token (``router_hidden`` wide), softmax
    # over the experts, the top-1 of p + a balancing bias chosen and weighted
    # by its own p, unnormalised (``moe_norm_topk`` would make it 1)
    moe_router: str = "gshard"            # "gshard" (softmax, capacity) | "sigmoid" | "zaya"
    router_hidden: int = 0                # the zaya router's state and MLP width
    moe_d_ff: Optional[int] = None        # an expert's width (default ffn_dim)
    moe_shared_d_ff: int = 0              # shared experts, as ONE MLP of this
                                          # width on every token (0: none)
    moe_norm_topk: bool = True            # chosen weights sum to 1 ...
    moe_routed_scale: float = 1.0         # ... times this
    moe_first_dense: int = 0              # leading layers with a dense FFN of
                                          # width ffn_dim before the expert layers
    # attention kind: "mha" (MHA/GQA/MQA over cached K and V) | "mla"
    # (latent attention, models/mla.py: the cache holds kv_lora_rank +
    # qk_rope_head_dim values a token) | "cca" (compressed convolutional
    # attention, models/cca.py: the whole attention in a latent of
    # ``n_head x head_dim`` behind two causal convolutions over positions,
    # kernels ``cca_conv``; K/V planes beside a conv tail a slot). The kind
    # decides the cache (inference/kinds).
    attention: str = "mha"
    cca_conv: tuple = (2, 2)              # depthwise taps, then grouped taps
    # a scale and a bias a channel on each side of each sub-layer (ZAYA1):
    # x <- (a_res x + b_res) + (a_out F(norm(x)) + b_out); ``res_scale``
    # (L, 2, 4, d) = [attention | FFN][a_res, b_res, a_out, b_out]
    residual_scale: bool = False
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # looped trunk (Ouro, arXiv:2510.25741): the whole stack of n_layer
    # layers is applied loop_steps times to every token over the SAME
    # weights, the final norm closing every pass (the last included); a
    # pass has its own keys and values, so the cache holds n_layer x
    # loop_steps planes (inference/kinds/dense.py)
    loop_steps: int = 1
    # a norm AFTER each sub-layer as well as before it: x + norm(attn(
    # norm(x))), x + norm(mlp(norm(x))) (ln1_post_scale, ln2_post_scale)
    sandwich_norm: bool = False
    # a looped trunk's exit gate: sigmoid of ONE Linear(d_model, 1) on the
    # hidden state closing each pass; exit_pdf() turns the passes' values
    # into the distribution over exit passes. Every pass always runs (the
    # published early_exit_threshold 1 exits at the last): the
    # distribution comes back beside the logits, it steers nothing
    exit_gate: bool = False
    # one mixer a layer (NemotronH, ``model_type: nemotron_h``): layer i is
    # ``block_pattern[i]`` alone, x + mixer(norm(x)) with no FFN beside it —
    # "M" a Mamba-2 mixer (models/ssm.py), "E" latent experts, "*" attention
    # with no position code (models/hybrid.py). "" is the attention + FFN
    # block of every other family. The cache: inference/kinds/hybrid.py.
    # "P" (Falcon-H1, ``model_type: falcon_h1``; every layer, or none): a
    # Mamba-2 mixer AND rotary GQA attention on the same normed input, both
    # added to the stream, then a gated FFN behind a norm of its own, every
    # branch times its ``mup`` scalar. The cache: inference/kinds/parallel.py
    block_pattern: str = ""
    mup: MuP = MuP()
    ssm_heads: int = 0                    # Mamba-2: heads H ...
    ssm_head_dim: int = 0                 # ... of P channels (d_inner = H P)
    ssm_groups: int = 1                   # B and C are shared by H / G heads
    ssm_state: int = 0                    # N: a head's state is P x N
    ssm_conv: int = 4                     # the depthwise conv's kernel
    ssm_chunk: int = 128                  # the chunked scan's block
    ssm_dt_init: tuple = (1e-3, 1e-1, 1e-4)   # dt at init: min, max, floor
    # latent experts: one projection d_model -> moe_latent_dim before the
    # routed experts and one back after them, shared by all experts, which
    # are moe_d_ff wide over the latent (0: experts on d_model itself)
    moe_latent_dim: int = 0
    # the experts THIS device holds of every expert layer: moe_experts_held
    # of num_experts from moe_first_held on (0: all). The router keeps its
    # num_experts outputs and its top-k; the layer computes its own experts'
    # part of the result (the model-configs guide's chip's share)
    moe_experts_held: int = 0
    moe_first_held: int = 0
    # two kinds of attention layer in one trunk (MiMo-V2-Flash,
    # ``model_type: mimo_v2_flash``; models/windowed.py): layer i is
    # ``attn_pattern[i]``, "G" full causal attention (``n_kv_head`` KV
    # heads, ``rope_theta``) | "S" a sliding window of ``window`` positions
    # (query i sees keys i - window + 1 .. i) with ``window_kv_heads`` KV
    # heads, ``window_rope_theta`` and, with ``attn_sink``, a learned sink
    # logit a head in the softmax's denominator. Both kinds: heads
    # ``qk_head_dim`` wide for q and k over ``v_head_dim`` for v, V times
    # ``attn_value_scale``. "" is one kind of layer, as every other family
    # has. The cache: inference/kinds/windowed.py
    attn_pattern: str = ""
    window: int = 0
    window_kv_heads: Optional[int] = None
    window_rope_theta: Optional[float] = None
    attn_sink: bool = False
    qk_head_dim: int = 0                  # 0: d_model / n_head
    attn_value_scale: float = 1.0
    # the rotation pairs dim i with i + rotary_dim / 2 (HF ``rotate_half``)
    # instead of 2i with 2i + 1
    rope_halves: bool = False
    # latent attention's query through a latent of its own (DeepSeek-V2/V3
    # ``q_lora_rank``): cq = RMSNorm(y wq_a), q = cq wq_b (0: one matrix wq)
    q_lora_rank: int = 0
    # the latent attention's query matrix drawn this much wider at init: a
    # trained head's softmax is peaked (a few keys take most of a query's
    # weight); a random one's, scores of sd 1 over thousands of keys, is
    # diffuse, its output an average near zero, and a path that drops or
    # mis-rotates the rope then reads the same (PERF.md §6 "PR 62")
    mla_query_init_gain: float = 1.0
    # learned sparse attention over a latent cache (DeepSeek-V3.2's, with
    # GLM-5.2's ``indexer_types``; ``model_type: glm_moe_dsa``,
    # models/dsa.py): layer i is ``index_pattern[i]`` — "F" has an indexer
    # (``index_heads`` heads of ``index_head_dim`` over the query's latent
    # and ONE key of that width a position, rope on the first
    # ``qk_rope_head_dim`` of both) whose ``index_topk`` best positions are
    # all its attention reads; "s" reads what the last "F" before it chose
    # and has no indexer. "" is attention over every cached position. The
    # cache: inference/kinds/sparse_latent.py
    index_pattern: str = ""
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    # pooled indexer keys (GLM-5.3-Flash ``index_kpool``): ONE cached key a
    # group of this many positions, the mean of the group's keys once it is
    # full; a query scores the closed groups before its own, reads the
    # ``index_topk / index_kpool`` best whole and its own group up to itself
    index_kpool: int = 1
    # a mixer a layer beside the FFN kinds (GLM-5.3-Flash, ``model_type:
    # glm5_next_text``; Solar-Open2, ``model_type: solar_open2``): layer i's
    # mixer is ``mixer_pattern[i]`` — "K" Kimi Delta Attention
    # (models/kda.py: ``kda_heads`` heads of ``kda_head_dim`` key and value
    # channels, a float32 delta-rule state a head, depthwise convs of
    # ``kda_conv`` taps, the decay and the output gate behind low-rank maps
    # ``kda_rank`` wide, the decay's log floored at ``kda_gate_floor`` or,
    # at 0, not floored at all: ``-exp(A_log) softplus(.)``; beta in (0, 2)
    # with ``kda_neg_eigval``) | "A" the config's attention: with
    # ``attention='mla'`` and an ``index_pattern`` ("-" for the "K" layers:
    # no attention) a latent read through a selection (the cache:
    # inference/kinds/linear_sparse.py); with ``attention='mha'`` softmax
    # GQA over whole K/V planes, no position code, its output times
    # ``sigmoid(y w_ogate)`` a head a channel with ``attn_out_gate``
    # (arXiv:2505.06708; the cache: inference/kinds/delta_gqa.py); with
    # ``attention='mla'`` and NO ``index_pattern`` (Ling-3.0-flash,
    # ``model_type: bailing_hybrid``) every live latent read, rope
    # (``pos_embedding='rope'``) on the "A" layers' ``qk_rope_head_dim``
    # alone, the output times ``sigmoid(y w_ogate)`` a head with
    # ``attn_out_gate='head'`` (the cache: inference/kinds/delta_latent.py).
    # ``kda_rank`` 0: the decay's and the gate's maps are full (d_model x
    # heads x head_dim; ``no_kda_lora``); ``kda_qk_norm``: a learned gain a
    # channel on every head's q and k before the L2 norm. "" is one mixer
    # kind.
    mixer_pattern: str = ""
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_rank: int = 0
    kda_gate_floor: float = -5.0
    kda_neg_eigval: bool = False
    kda_qk_norm: bool = False
    attn_out_gate: Any = False            # False | True (a channel) | "head"
    # manifold-constrained hyper-connections (mHC, arXiv:2512.24880;
    # models/mhc.py): ``hc_mult`` residual streams a token, each sub-layer
    # reading a mix of them and writing back through a doubly stochastic map
    # (``hc_sinkhorn_iters`` Sinkhorn passes, ``hc_eps`` in the sums).
    # 0 / 1: one stream, ``x + f(norm(x))``
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    # gated FFNs clamp before the product (``swiglu_limit``): silu(min(gate,
    # limit)) * clip(up, -limit, limit), dense, shared and routed alike
    # (0: no clamp). Or a value a LAYER, one list for the routed experts and
    # one for the shared expert (Ling-3.0-flash's ``expert_swiglu_limit_list``
    # / ``share_expert_swiglu_limit_list``; 0: that layer's are not clamped):
    # a segment is then a run of layers equal in both values too, each a
    # constant of its programs (:attr:`segment_limits`)
    swiglu_limit: float = 0.0
    moe_swiglu_limits: tuple = ()
    moe_shared_swiglu_limits: tuple = ()
    # group-limited choice (DeepSeek-V3's ``n_group`` / ``topk_group``): the
    # router's experts in ``moe_n_group`` equal groups, a group's score the
    # sum of its two best biased scores, the ``moe_topk_group`` best groups
    # kept and the top-k taken among their experts (1: one group)
    moe_n_group: int = 1
    moe_topk_group: int = 1

    @property
    def held_experts(self) -> int:
        return self.moe_experts_held or self.num_experts

    @property
    def head_dim(self) -> int:
        """A head's query/key width."""
        if self.attention == "mla":
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.qk_head_dim or self.d_model // self.n_head

    @property
    def v_dim(self) -> int:
        """A head's value width (MLA's, and a ``v_head_dim`` given to MHA,
        differ from the query/key width)."""
        return self.v_head_dim or self.head_dim

    def attn_kv_heads(self, kind: str = "") -> int:
        """KV heads of an attention layer of ``kind`` ("S": a window
        layer's; else the trunk's)."""
        return (self.window_kv_heads if kind == "S" else None) \
            or self.kv_heads

    def attn_theta(self, kind: str = "") -> float:
        return (self.window_rope_theta if kind == "S" else None) \
            or self.rope_theta

    @property
    def latent_dim(self) -> int:
        """Values the latent cache holds per token per layer (0: K/V)."""
        return (self.kv_lora_rank + self.qk_rope_head_dim
                if self.attention == "mla" else 0)

    @property
    def expert_dim(self) -> int:
        return self.moe_d_ff or self.ffn_dim

    @property
    def segments(self) -> tuple:
        """The trunk as an ordered list of (FFN kind, layers): each segment
        is one block kind scanned over its own stacked weights
        (``params["layers"]``: the stacked tree of a one-segment trunk, a
        tuple of them otherwise). The attention kind is the model's, or
        with an ``attn_pattern`` the segment's (:attr:`segment_attn`). With a
        ``block_pattern`` the kind is the layer's one mixer ("M" | "E" |
        "*"), a segment a run of equal letters."""
        if self.block_pattern:
            return tuple((kind, len(list(run)))
                         for kind, run in groupby(self.block_pattern))
        return tuple((kind[1], n) for kind, n in self._layer_runs())

    @property
    def segment_attn(self) -> tuple:
        """Each segment's attention kind ("G" | "S" with an
        ``attn_pattern``, "K" | "A" with a ``mixer_pattern``, else ""): a
        segment is a run of layers equal in mixer kind AND FFN kind."""
        if self.block_pattern:
            return ("",) * len(self.segments)
        return tuple(kind[0] for kind, _ in self._layer_runs())

    @property
    def segment_limits(self) -> tuple:
        """Each segment's (routed experts', shared expert's) clamp: the
        layer's own with ``moe_swiglu_limits`` / ``moe_shared_swiglu_limits``
        (an expert segment's), else ``swiglu_limit`` twice."""
        if self.block_pattern:
            return ((self.swiglu_limit,) * 2,) * len(self.segments)
        return tuple(kind[2:] for kind, _ in self._layer_runs())

    def _layer_runs(self) -> list:
        k = min(self.moe_first_dense, self.n_layer) if self.num_experts > 1 \
            else self.n_layer
        pat = self.attn_pattern or self.mixer_pattern
        one = self.swiglu_limit

        def limit(per_layer, i):
            return float(per_layer[i]) if per_layer and i >= k else one

        kinds = [((pat[i] if pat else ""), "dense" if i < k else "moe",
                  limit(self.moe_swiglu_limits, i),
                  limit(self.moe_shared_swiglu_limits, i))
                 for i in range(self.n_layer)]
        return [(kind, len(list(run))) for kind, run in groupby(kinds)]

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def ffn_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def is_glu(self) -> bool:
        return self.activation.endswith("glu")

    def flops_per_token(self) -> float:
        """Fwd+bwd model FLOPs per token for MFU accounting (Megatron
        convention): 6*N_active trunk matmul FLOPs + the attention
        score/value term (12*L*d*S) + the output-logit projection
        (6*d*V) — the unembedding is a real (B*S, d) x (d, V) matmul on
        the MXU, so omitting it (as pure-6N accounting does) under-reports
        achieved FLOPs; Megatron's model-FLOPs formula includes the logit
        layer explicitly. The token-embedding *lookup* is a gather, not a
        matmul, and stays excluded.

        For MoE only the ``moe_top_k`` routed experts do work per token, so
        FLOPs use the *active* parameter count, not the total bank size."""
        n_params = self.loop_steps * self.param_count(
            non_embedding=True, active_only=True)   # every pass multiplies
        # scores + values: 2 * S * H * (qk width + v width) forward, x3
        n_attn = sum(map(self.block_pattern.count, "*P")) \
            if self.block_pattern else self.n_layer
        # a window layer's query sees at most ``window`` keys
        n_win = self.attn_pattern.count("S")
        keys = (n_attn - n_win) * self.max_seq \
            + n_win * min(self.max_seq, self.window)
        attn = 6 * self.loop_steps * self.n_head * (
            self.head_dim + self.v_dim) * keys
        head = (0 if self.objective == "feature"
                else 6 * self.d_model * self.vocab_size)
        return 6 * n_params + attn + head

    def _ffn_params_per_layer(self, active_only: bool = False,
                              kind: Optional[str] = None) -> int:
        """Matmul parameters of one layer's FFN of ``kind`` ("dense" |
        "moe"; default: the trunk's last segment's)."""
        d, E = self.d_model, self.num_experts
        mats = 3 if self.is_glu else 2
        if (kind or self.segments[-1][0]) == "dense":
            return d * self.ffn_dim * mats
        R = self.router_hidden
        router = d * R + 2 * R * R + R * E if self.moe_router == "zaya" \
            else d * E
        mult = min(self.moe_top_k, E) if active_only else self.held_experts
        return (router + mult * d * self.expert_dim * mats
                + d * self.moe_shared_d_ff * mats)

    def _attn_params_per_layer(self, kind: str = "") -> int:
        d, h = self.d_model, self.n_head
        if kind == "K":
            # q, k, v and the output; beta; the decay's and the gate's
            # low-rank pairs
            # low-rank pairs (or, at kda_rank 0, full maps)
            inner = self.kda_heads * self.kda_head_dim
            maps = 2 * self.kda_rank * (d + inner) if self.kda_rank \
                else 2 * d * inner
            return 4 * d * inner + d * self.kda_heads + maps
        vd = self.v_dim
        gate = d * h * (1 if self.attn_out_gate == "head" else vd) \
            if self.attn_out_gate else 0
        if self.attention == "mla":
            r, ql = self.kv_lora_rank, self.q_lora_rank
            q = ql * (d + h * self.head_dim) if ql else d * h * self.head_dim
            return (q + d * self.latent_dim
                    + r * h * (self.qk_nope_head_dim + vd)
                    + h * vd * d + gate)
        kv, hd = self.attn_kv_heads(kind), self.head_dim
        # cca: the grouped conv's one hd x hd matrix a head a tap (the
        # depthwise taps, like norms and biases, are left out)
        conv = self.cca_conv[1] * (h + kv) * hd * hd \
            if self.attention == "cca" else 0
        return d * (h * hd) + d * kv * (hd + vd) + (h * vd) * d + conv + gate

    def _mixer_params_per_layer(self, kind: str, active_only: bool) -> int:
        """Matmul parameters of one ``block_pattern`` layer of ``kind``."""
        d = self.d_model
        if kind == "*":
            return self._attn_params_per_layer()
        inner = self.ssm_heads * self.ssm_head_dim
        bc = 2 * self.ssm_groups * self.ssm_state
        mamba = d * (2 * inner + bc + self.ssm_heads) + inner * d
        if kind == "M":
            return mamba
        if kind == "P":
            return mamba + self._attn_params_per_layer() \
                + self._ffn_params_per_layer(kind="dense")
        lat = self.moe_latent_dim or d
        experts = min(self.moe_top_k, self.num_experts) if active_only \
            else self.held_experts
        return (d * self.num_experts + (2 * d * lat if lat != d else 0)
                + experts * 2 * lat * self.expert_dim
                + 2 * d * self.moe_shared_d_ff)

    def param_count(self, non_embedding: bool = False,
                    active_only: bool = False) -> int:
        """Matmul parameters (norms, biases and position tables left out);
        of an expert layer's bank the experts held here."""
        d = self.d_model
        emb = self.vocab_size * d
        if self.block_pattern:
            total = sum(n * self._mixer_params_per_layer(kind, active_only)
                        for kind, n in self.segments)
        else:
            total = sum(n * (self._attn_params_per_layer(attn)
                             + self._ffn_params_per_layer(active_only, kind))
                        for (kind, n), attn in zip(self.segments,
                                                   self.segment_attn))
        # an indexer: its queries off the query's latent, one key and the
        # heads' weights off the stream
        total += self.index_pattern.count("F") * (
            self.q_lora_rank * self.index_heads * self.index_head_dim
            + d * (self.index_head_dim + self.index_heads))
        if self.hc_mult > 1:
            # two sub-layers a layer, each a map off the streams' whole width
            n = self.hc_mult
            total += self.n_layer * 2 * n * d * (n * n + 2 * n)
        total += emb if not non_embedding else 0
        if (not self.tie_embeddings and not non_embedding
                and self.objective != "feature"):
            total += emb
        return total


# residual_scale at init: sd of [a_res, b_res, a_out, b_out] about 1, 0, 1, 0.
# The biases are small: each is one vector added to EVERY token at every
# sub-layer, and at sd 0.01 their sum was the direction all hidden states
# shared 512 positions deep (a router then sends most rows to one expert)
RES_SCALE_SD = (0.05, 0.001, 0.1, 0.001)


# ------------------------------------------------------------------ helpers
def _norm(x, scale, bias, kind: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _rope(q, k, positions, theta: float, rotary_dim: int | None = None,
          halves: bool = False):
    """Rotary embeddings on (B, S, H, hd) q/k (interleaved-pair basis; with
    ``halves`` dim i pairs with i + rotary_dim / 2, HF's ``rotate_half``).

    ``rotary_dim`` < head_dim rotates only the leading dims of each head
    (GPT-J's ``rotary_dim``, NeoX's ``rotary_pct``); the tail passes through.
    Frequencies are computed over ``rotary_dim``, matching those models.
    """
    hd = q.shape[-1]
    rd = rotary_dim or hd
    freqs = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, rd/2)
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]

    def rot(x):
        xr, xp = x[..., :rd], x[..., rd:]
        x1, x2 = (xr[..., :rd // 2], xr[..., rd // 2:]) if halves \
            else (xr[..., ::2], xr[..., 1::2])
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        out = jnp.concatenate([xr1, xr2], axis=-1) if halves \
            else jnp.stack([xr1, xr2], axis=-1).reshape(xr.shape)
        return jnp.concatenate([out, xp], axis=-1) if rd < hd else out

    return (rot(q.astype(jnp.float32)).astype(q.dtype),
            rot(k.astype(jnp.float32)).astype(k.dtype))


def _activation(u, name: str):
    """Named activation; unknown names fail loudly (a silent silu fallback
    once imported gelu_new checkpoints with the wrong nonlinearity)."""
    if name == "gelu":
        return jax.nn.gelu(u)                      # tanh approx (gelu_new)
    if name == "gelu_exact":
        return jax.nn.gelu(u, approximate=False)   # erf gelu
    if name == "relu":
        return jax.nn.relu(u)
    if name == "relu2":
        return jnp.square(jax.nn.relu(u))           # NemotronH's experts
    if name in ("silu", "swish"):
        return jax.nn.silu(u)
    if name == "quick_gelu":
        return u * jax.nn.sigmoid(1.702 * u)       # CLIP's sigmoid approx
    raise ValueError(f"unknown activation {name!r}")


def _swiglu(gate, up, limit: float = 0.0):
    """``silu(gate) * up``; with ``limit`` (``swiglu_limit``) the gate
    clamped from above and the up product on both sides first. ``up`` may
    be a thunk, taken behind the gate's activation: a caller that used to
    write ``silu(a) * (b)`` keeps the program it lowered to."""
    if limit:
        gate = jnp.minimum(gate, jnp.asarray(limit, gate.dtype))
    act = jax.nn.silu(gate)
    up = up() if callable(up) else up
    return act * (jnp.clip(up, -limit, limit) if limit else up)


def clamp_gain(cfg, limit=None) -> float:
    """What a clamped gated FFN's gate and up matrices are drawn wider by
    (and its down matrix narrower by the square): with ``swiglu_limit`` (or
    ``limit``, a segment's own: ``cfg.segment_limits``) the pre-activations
    of a unit-RMS input then have sd 0.6 of the limit, a tenth of them beyond
    it as in a trained model that needs the clamp, so that a path which drops
    the clamp reads differently. 1 with no limit."""
    limit = cfg.swiglu_limit if limit is None else limit
    return 0.6 * limit if limit else 1.0


def exit_pdf(lam):
    """A looped trunk's gate values ``lam`` (passes, ...) as the distribution
    over exit passes (..., passes): p_r = lam_r * prod_{j<r} (1 - lam_j),
    and the last pass takes what is left, prod_{j<R} (1 - lam_j) — its own
    gate value is never used."""
    before = jnp.cumprod(jnp.concatenate(
        [jnp.ones_like(lam[:1]), 1.0 - lam[:-1]]), axis=0)
    return jnp.moveaxis(jnp.concatenate(
        [lam[:-1] * before[:-1], before[-1:]]), 0, -1)


def vocab_parallel_lookup(table, ids):
    """Vocab-parallel embedding lookup (shared by every trunk).

    Embedding tables are vocab-sharded over ``model`` (``param_specs``); a
    plain gather there makes GSPMD replicate the whole table
    ("involuntary full rematerialization", ``spmd_partitioner.cc:652`` —
    the round-2 dryrun regression). The TPU-native fix is Megatron's
    vocab-parallel lookup: each shard gathers its own vocab range, masks
    foreign ids to zero, and one psum over ``model`` assembles the rows —
    activation-sized traffic instead of table-sized.
    """
    ctx = current_mesh()
    from ..platform.mesh import manual_axes_of
    manual = manual_axes_of(ctx) if ctx is not None else frozenset()
    if (ctx is None or "model" not in getattr(ctx, "axis_names", ())
            or ctx.shape["model"] == 1 or manual
            or table.shape[0] % ctx.shape["model"] != 0):
        # (a vocab the model axis does not divide is replicated —
        # platform.mesh.fit_spec — so the plain gather is already local)
        return table[ids]

    def lookup(tbl, idx):
        v_local = tbl.shape[0]
        local = idx - lax.axis_index("model") * v_local
        ok = (local >= 0) & (local < v_local)
        rows = tbl[jnp.clip(local, 0, v_local - 1)]
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        return lax.psum(rows, "model")

    # Fully-manual region (partial-manual psum trips an XLA partitioner
    # CHECK on composed meshes): batch/seq stay sharded as in the trunk,
    # the table enters model-sharded on vocab with full embedding rows.
    fn = jax.shard_map(lookup, mesh=ctx,
                       in_specs=(P("model", None), P(B_AXES, "seq")),
                       out_specs=P(B_AXES, "seq", None))
    return fn(table, ids)


def alibi_slopes(n_head: int) -> jnp.ndarray:
    """Standard ALiBi per-head slopes (Bloom; geometric in 2^(-8/n))."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_head).is_integer():
        slopes = pow2_slopes(n_head)
    else:
        closest = 2 ** math.floor(math.log2(n_head))
        slopes = pow2_slopes(closest)
        extra = pow2_slopes(2 * closest)[0::2][:n_head - closest]
        slopes += extra
    return jnp.asarray(slopes, jnp.float32)


def alibi_bias(slopes, S: int) -> jnp.ndarray:
    """Dense (H, S, S) ALiBi distance bias: slope·(key_pos − query_pos).
    ONE definition of the ramp convention — the flash/ring/decode kernels
    rebuild the same ramp from positions instead of taking this tensor
    (it is O(S²); only the dense fallbacks materialize it)."""
    rel = (jnp.arange(S)[None, :] - jnp.arange(S)[:, None])
    return (jnp.asarray(slopes, jnp.float32)[:, None, None]
            * rel[None].astype(jnp.float32))


def _token_nll_impl(logits, targets):
    """Per-token NLL in fp32 without materializing a (B, S, V) fp32 tensor:
    nll = logsumexp(logits) - logit[target]. The bf16→fp32 cast and exp
    fuse into a single reduction pass over V (log_softmax + take_along_axis
    instead writes the full fp32 log-probability cube — ~2x the head's HBM
    traffic at GPT-2 vocab sizes)."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    se = jnp.sum(jnp.exp((logits - m).astype(jnp.float32)), axis=-1)
    lse = jnp.log(se) + m[..., 0].astype(jnp.float32)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt.astype(jnp.float32)


# checkpoint: the backward recomputes exp(shifted) fused into the
# d_logits = softmax - onehot epilogue instead of saving it as a resident
# (B, S, V) fp32 tensor between forward and backward.
_token_nll = jax.checkpoint(_token_nll_impl)


def mesh_dp_world(mesh) -> int:
    """Product of the batch (token-sharding) axes of a mesh."""
    return int(math.prod(mesh.shape[a] for a in BATCH_AXES
                         if a in mesh.axis_names))


def fused_nll_sharded(feats, targets, table, bias=None):
    """(B, S', D) features + (B, S') targets → (B, S') fp32 NLL via the
    fused Pallas kernel (ops/xent.py), shard_mapped over the batch axes
    when data-parallel and over the model axis (vocab-sharded variant)
    when tensor-parallel. ``table`` is the (V, D) unembedding in
    embedding layout; shared by the decoder trunk's and T5's loss paths."""
    from ..ops.xent import fused_token_nll, fused_token_nll_tp

    B, S, dm = feats.shape
    h2 = feats.reshape(B * S, dm)
    t2 = targets.reshape(B * S).astype(jnp.int32)
    mesh = current_mesh()
    in_mesh = mesh is not None and not mesh.empty
    dp = mesh_dp_world(mesh) if in_mesh else 1
    tp = int(mesh.shape.get("model", 1)) if in_mesh else 1
    if dp > 1 or tp > 1:
        has_b = bias is not None

        # a vocab the model axis does not divide is replicated
        # (platform.mesh.fit_spec): the model axis then splits the batch
        # too, and every device runs the whole-vocab kernel over its own
        # sequences (_fused_xent_active admits only batches that divide)
        vocab_tp = tp > 1 and table.shape[0] % tp == 0

        def body(h, w, *rest):
            b, t = rest if has_b else (None, rest[0])
            if vocab_tp:
                return fused_token_nll_tp(h, w, b, t, "model")
            return fused_token_nll(h, w, b, t)

        # Specs name only axes the mesh actually carries: a user-built
        # mesh with, say, just a "data" axis still takes the fused path
        # instead of crashing on an unknown axis name (advisor r3). tp > 1
        # implies "model" exists (tp is read off the mesh above).
        b_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
        if tp > 1 and not vocab_tp:
            b_axes += ("model",)
        b_axes = b_axes or None
        mdl = "model" if vocab_tp else None
        in_specs = ((P(b_axes, None), P(mdl, None))
                    + ((P(mdl),) if has_b else ()) + (P(b_axes),))
        args = (h2, table) + ((bias,) if has_b else ()) + (t2,)
        nll2 = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=P(b_axes), check_vma=False)(*args)
    else:
        nll2 = fused_token_nll(h2, table, bias, t2)
    return nll2.reshape(B, S)


def causal_attention(q, k, v, *, mask: jnp.ndarray | None = None,
                     causal: bool = True, bias: jnp.ndarray | None = None):
    """Plain attention, fp32 softmax. q:(B,S,H,hd) k/v:(B,S,KV,hd).

    ``causal=False`` = bidirectional (encoder); ``bias`` is an additive
    score bias, shape (S, S), (H, S, S) (ALiBi) or (B|1, H|1, S, S)
    (evoformer pair bias) — broadcast gradients flow correctly through the
    ``broadcast_to``. Heads are grouped for GQA by repeating kv. The
    Pallas flash kernel (ops/flash_attention.py) replaces this on TPU for
    long sequences.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / math.sqrt(hd)
    if bias is not None:
        b4 = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        scores = scores + jnp.broadcast_to(b4, scores.shape).astype(jnp.float32)
    big_neg = jnp.finfo(jnp.float32).min
    if causal:
        tri = jnp.tril(jnp.ones((S, S), dtype=bool))
        scores = jnp.where(tri[None, None, :, :], scores, big_neg)
    if mask is not None:  # (B, S) padding mask on keys
        scores = jnp.where(mask[:, None, None, :].astype(bool), scores, big_neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


# -------------------------------------------------------------------- model
class TransformerLM:
    """init/apply/param_specs over a :class:`TransformerConfig`."""

    def __init__(self, config: TransformerConfig, attention_fn=None):
        self.cfg = config
        if attention_fn is not None and not config.causal:
            raise ValueError(
                "encoder (causal=False) configs require the default "
                "attention: the flash/sparse/Ulysses attention_fns apply a "
                "causal mask and would silently break bidirectionality")
        if attention_fn is not None and config.pos_embedding == "alibi" \
                and not (getattr(attention_fn, "accepts_bias", False)
                         or getattr(attention_fn, "accepts_alibi_slopes",
                                    False)):
            raise ValueError(
                "alibi needs an additive score bias; this attention_fn "
                "accepts neither a bias nor alibi slopes (flash and ring "
                "attention do; sparse/Ulysses still do not)")
        if config.attention == "mla":
            # a latent with no rope part (GLM-5.3-Flash ``mla_use_nope``):
            # no position code at all, beside mixers that carry the order
            nope = config.pos_embedding == "none" \
                and config.qk_rope_head_dim == 0 and config.mixer_pattern
            if (config.use_bias
                    or (config.pos_embedding != "rope" and not nope)
                    or not config.causal or config.post_ln
                    or config.parallel_residual or attention_fn is not None
                    or min(config.kv_lora_rank, config.qk_nope_head_dim,
                           config.v_head_dim) <= 0
                    or (config.qk_rope_head_dim <= 0 and not nope)):
                raise ValueError(
                    "attention='mla' is the DeepSeek block: causal, rope on "
                    "qk_rope_head_dim (none, with pos_embedding='none', only "
                    "beside the mixers of a mixer_pattern), no biases, "
                    "pre-norm, its own blocked attention (no attention_fn), "
                    "and kv_lora_rank / qk_nope_head_dim / qk_rope_head_dim "
                    "/ v_head_dim set")
            if config.index_pattern:
                from .dsa import check_config as check_dsa

                check_dsa(config)
        elif config.attention == "cca":
            from .cca import check_config as check_cca

            check_cca(config, attention_fn)
        elif config.attention != "mha":
            raise ValueError(f"unknown attention kind {config.attention!r}")
        if config.index_pattern and config.attention != "mla":
            raise ValueError("index_pattern selects positions of a latent "
                             "(attention='mla') cache")
        if config.mixer_pattern or config.hc_mult > 1 \
                or config.index_kpool > 1 or config.attn_out_gate:
            from .kda import check_config as check_kda

            check_kda(config)
        limits = (config.moe_swiglu_limits, config.moe_shared_swiglu_limits)
        if (config.swiglu_limit or any(limits)) and not config.is_glu:
            raise ValueError("swiglu_limit clamps a gated FFN's gate and up "
                             "(activation '*_glu')")
        if any(limits) and (
                config.swiglu_limit or config.moe_router != "sigmoid"
                or not config.mixer_pattern
                or any(len(per) != config.n_layer for per in limits)):
            raise ValueError(
                "moe_swiglu_limits / moe_shared_swiglu_limits give every "
                "layer's clamp, the routed experts' and the shared expert's "
                "(n_layer values each, in place of the one swiglu_limit): a "
                "mixer_pattern trunk's sigmoid-routed expert layers'")
        if (config.moe_n_group, config.moe_topk_group) != (1, 1) and (
                config.moe_router != "sigmoid" or config.block_pattern
                or config.num_experts % config.moe_n_group
                or not 1 <= config.moe_topk_group <= config.moe_n_group
                or config.moe_top_k > config.moe_topk_group
                * (config.num_experts // config.moe_n_group)
                or config.num_experts // config.moe_n_group < 2):
            raise ValueError(
                "moe_n_group / moe_topk_group are the sigmoid router's "
                "group-limited choice (models/moe.py route): num_experts in "
                "equal groups of two or more, 1 <= topk_group <= n_group, "
                "and room for the top-k in the groups kept")
        if config.moe_router == "zaya" and (
                config.attention != "cca" or config.num_experts < 2
                or config.router_hidden < 1 or config.moe_top_k != 1
                or config.moe_experts_held or config.moe_shared_d_ff
                or not config.is_glu):
            raise ValueError(
                "moe_router='zaya' is the ZAYA1 expert sub-layer: beside "
                "attention='cca', router_hidden set, top-1 of SwiGLU experts "
                "all held here, no shared expert")
        if config.residual_scale and (config.post_ln
                                      or config.parallel_residual):
            raise ValueError("residual_scale scales the two sides of a "
                             "pre-norm two-hop block")
        if config.mup != MuP() and "P" not in config.block_pattern:
            raise ValueError(
                "mup: the branch multipliers are the Falcon-H1 block's "
                "(block_pattern 'P', models/hybrid.py); no other block "
                "applies them")
        if config.attn_pattern:
            from .windowed import check_config

            check_config(config, attention_fn)
        if config.moe_experts_held and not config.block_pattern:
            E, held = config.num_experts, config.moe_experts_held
            if (config.moe_router != "sigmoid" or held > E
                    or config.moe_first_held % held
                    or config.moe_first_held >= E):
                raise ValueError(
                    "moe_experts_held is the sigmoid router's (models/moe.py "
                    "experts): moe_experts_held of num_experts from a "
                    "multiple of that on")
        if config.loop_steps < 1 or (config.exit_gate
                                     and config.loop_steps == 1):
            raise ValueError("loop_steps counts the trunk's passes (>= 1); "
                             "exit_gate is the gate between them")
        if (config.loop_steps > 1 or config.sandwich_norm) and (
                config.attention != "mha" or config.num_experts > 1
                or config.post_ln or config.parallel_residual
                or config.use_bias or config.objective != "clm"):
            raise ValueError(
                "a looped trunk (loop_steps > 1) and sandwich norms are the "
                "Ouro block: a causal LM of pre-norm MHA layers with a "
                "dense FFN, two-hop residual, no biases")
        self.attention_fn = attention_fn or partial(causal_attention,
                                                    causal=config.causal)

    # ----------------------------------------------------------------- init
    def init(self, rng) -> dict:
        cfg = self.cfg
        d, L = cfg.d_model, cfg.n_layer
        k = iter(jax.random.split(rng, 16))

        def dense(key, shape, scale=None):
            scale = scale or (1.0 / math.sqrt(shape[-2] if len(shape) > 1 else shape[-1]))
            return (jax.random.normal(key, shape, jnp.float32) * scale)

        segs = cfg.segments
        if len(segs) == 1:
            layers = self._init_segment(k, dense, segs[0][0], L, L,
                                        cfg.segment_attn[0])
        else:
            # block kinds: every segment draws from its own key, so a
            # segment's weights do not depend on what stands beside it
            layers = tuple(
                self._init_segment(
                    iter(jax.random.split(jax.random.fold_in(rng, 100 + i), 16)),
                    dense, kind, n, L, cfg.segment_attn[i])
                for i, (kind, n) in enumerate(segs))
        params = {
            "tok_embed": jax.random.normal(next(k), (cfg.vocab_size, d), jnp.float32) * 0.02,
            "layers": layers,
        }
        if cfg.index_pattern:
            from .dsa import init_indexers

            params["indexer"] = init_indexers(
                cfg, jax.random.fold_in(rng, 99), dense)
        if not cfg.post_ln:
            params["lnf_scale"] = jnp.ones((d,), jnp.float32)
        if cfg.pos_embedding == "learned":
            params["pos_embed"] = jax.random.normal(next(k), (cfg.max_seq, d),
                                                    jnp.float32) * 0.02
        if cfg.use_bias and not cfg.post_ln:
            params["lnf_bias"] = jnp.zeros((d,), jnp.float32)
        if cfg.mlm_transform:
            params["mlm_dense_w"] = dense(next(k), (d, d))
            params["mlm_dense_b"] = jnp.zeros((d,), jnp.float32)
            params["mlm_ln_scale"] = jnp.ones((d,), jnp.float32)
            params["mlm_ln_bias"] = jnp.zeros((d,), jnp.float32)
        if cfg.embed_norm:
            params["embed_ln_scale"] = jnp.ones((d,), jnp.float32)
            if cfg.use_bias:
                params["embed_ln_bias"] = jnp.zeros((d,), jnp.float32)
        if cfg.lm_head_bias:
            params["lm_head_bias"] = jnp.zeros((cfg.vocab_size,), jnp.float32)
        if not cfg.tie_embeddings and cfg.objective != "feature":
            params["lm_head"] = dense(next(k), (d, cfg.vocab_size), scale=0.02)
        if cfg.exit_gate:
            # on a closed pass (unit RMS) the gate's logit is ~N(0, 1)
            params["exit_gate_w"] = dense(next(k), (d,))
            params["exit_gate_b"] = jnp.zeros((), jnp.float32)
        return params


    def _init_segment(self, k, dense, kind: str, n: int, depth: int,
                      attn: str = "") -> dict:
        """Stacked weights of ``n`` layers of one block kind (attention
        kind ``attn``, "" the model's one, x FFN ``kind``); ``depth`` is the
        whole trunk's, for the residual projections' scale. "moe" segments
        get their expert banks from the MoE trunk (models/moe.py)."""
        cfg = self.cfg
        d, f, L = cfg.d_model, cfg.ffn_dim, n
        h, kv, hd = cfg.n_head, cfg.attn_kv_heads(attn), cfg.head_dim
        dense_ffn = kind == "dense"
        two_ln = not (cfg.parallel_residual and cfg.parallel_shared_ln)
        layers = {"ln1_scale": jnp.ones((L, d), jnp.float32)}
        if cfg.hc_mult > 1:
            from .mhc import init_params as init_mhc

            layers.update(init_mhc(cfg, next(k), L))
        if attn == "K":
            from .kda import init_params as init_kda

            layers.update(init_kda(cfg, next(k), dense, L, depth))
        elif cfg.attention == "mla":
            r, ql = cfg.kv_lora_rank, cfg.q_lora_rank
            wide = cfg.mla_query_init_gain
            layers.update({
                "wq_a": dense(next(k), (L, d, ql)),
                "q_norm_scale": jnp.ones((L, ql), jnp.float32),
                "wq_b": dense(next(k), (L, ql, h * hd)) * wide,
            } if ql else {"wq": dense(next(k), (L, d, h * hd)) * wide})
            layers.update({
                "wkv_a": dense(next(k), (L, d, cfg.latent_dim)),
                "kv_norm_scale": jnp.ones((L, r), jnp.float32),
                "wkv_b": dense(next(k), (
                    L, r, h * (cfg.qk_nope_head_dim + cfg.v_dim))),
                "wo": dense(next(k), (L, h * cfg.v_dim, d),
                            scale=1.0 / math.sqrt(2 * depth * d)),
            })
            if cfg.attn_out_gate:
                layers["w_ogate"] = dense(next(k), (L, d, self._gate_width()))
        elif cfg.attention == "cca":
            from .cca import init_params as init_cca

            layers.update(init_cca(cfg, k, dense, L, depth))
        else:
            layers.update({
                "wq": dense(next(k), (L, d, h * hd)),
                "wk": dense(next(k), (L, d, kv * hd)),
                "wv": dense(next(k), (L, d, kv * cfg.v_dim)),
                "wo": dense(next(k), (L, h * cfg.v_dim, d),
                            scale=1.0 / math.sqrt(2 * depth * d)),
            })
            if cfg.attn_out_gate:
                # a pre-activation of sd 1 on a normed input: the gate
                # stands in 0.27 .. 0.73 for most channels, no constant half
                layers["w_ogate"] = dense(next(k), (L, d, self._gate_width()))
            if attn == "S" and cfg.attn_sink:
                from .windowed import SINK_INIT

                # a trained sink takes a real share of a head's softmax;
                # drawn so, that a path which drops it reads differently
                layers["sink"] = SINK_INIT[0] + SINK_INIT[1] \
                    * jax.random.normal(next(k), (L, h), jnp.float32)
        if two_ln:
            layers["ln2_scale"] = jnp.ones((L, d), jnp.float32)
        if cfg.sandwich_norm:
            # a norm after the sub-layer undoes the depth scaling of wo /
            # w_out below, so its gain carries it: every branch then adds
            # 1/sqrt(2 depth) of a unit vector to the stream, as in the
            # plain block. At gain 1 a randomly initialised trunk is
            # chaotic: bf16 and float32 logits part by 0.2-0.4 of the
            # largest (PERF.md, PR 34)
            post = jnp.full((L, d), 1.0 / math.sqrt(2 * depth), jnp.float32)
            layers["ln1_post_scale"] = layers["ln2_post_scale"] = post
        if cfg.residual_scale:
            # a trained model's scales lie near 1 and its biases near 0;
            # drawn so, that a path which drops them reads differently
            sd = jnp.asarray(RES_SCALE_SD, jnp.float32)[:, None]
            layers["res_scale"] = jnp.asarray([1.0, 0.0, 1.0, 0.0])[:, None] \
                + sd * jax.random.normal(next(k), (L, 2, 4, d), jnp.float32)
        if dense_ffn:
            gain = clamp_gain(cfg)
            layers["w_in"] = dense(next(k), (L, d, f)) * gain
            layers["w_out"] = dense(next(k), (L, f, d),
                                    scale=1.0 / math.sqrt(2 * depth * f)) \
                / gain ** 2
            if cfg.is_glu:
                layers["w_gate"] = dense(next(k), (L, d, f)) * gain
        if cfg.use_bias:
            layers.update({
                "ln1_bias": jnp.zeros((L, d), jnp.float32),
                "bq": jnp.zeros((L, h * hd), jnp.float32),
                "bk": jnp.zeros((L, kv * hd), jnp.float32),
                "bv": jnp.zeros((L, kv * hd), jnp.float32),
                "bo": jnp.zeros((L, d), jnp.float32),
            })
            if two_ln:
                layers["ln2_bias"] = jnp.zeros((L, d), jnp.float32)
            if dense_ffn:
                layers["b_in"] = jnp.zeros((L, f), jnp.float32)
                layers["b_out"] = jnp.zeros((L, d), jnp.float32)
        return layers

    def _gate_width(self) -> int:
        """Columns of ``w_ogate``: a value a head a channel, or a head."""
        cfg = self.cfg
        return cfg.n_head * (1 if cfg.attn_out_gate == "head" else cfg.v_dim)

    @staticmethod
    def segment_params(layers) -> tuple:
        """``params["layers"]`` as one stacked tree per segment."""
        return tuple(layers) if isinstance(layers, (tuple, list)) \
            else (layers,)

    # ---------------------------------------------------------------- specs
    def param_specs(self) -> dict:
        """TP (Megatron-style) sharding over the ``model`` axis:
        qkv/w_in column-split, wo/w_out row-split, embeddings vocab-split."""
        cfg = self.cfg
        layers = tuple(self._segment_specs(kind, attn) for (kind, _), attn
                       in zip(cfg.segments, cfg.segment_attn))
        if len(layers) == 1:
            layers = layers[0]
        specs = {
            "tok_embed": P("model", None),
            "layers": layers,
        }
        if cfg.index_pattern:
            from .dsa import indexer_specs

            specs["indexer"] = indexer_specs()
        if not cfg.post_ln:
            specs["lnf_scale"] = P(None)
        if cfg.pos_embedding == "learned":
            specs["pos_embed"] = P(None, None)
        if cfg.use_bias and not cfg.post_ln:
            specs["lnf_bias"] = P(None)
        if cfg.mlm_transform:
            specs["mlm_dense_w"] = P(None, None)
            specs["mlm_dense_b"] = P(None)
            specs["mlm_ln_scale"] = P(None)
            specs["mlm_ln_bias"] = P(None)
        if cfg.embed_norm:
            specs["embed_ln_scale"] = P(None)
            if cfg.use_bias:
                specs["embed_ln_bias"] = P(None)
        if not cfg.tie_embeddings and cfg.objective != "feature":
            specs["lm_head"] = P(None, "model")
        if cfg.lm_head_bias:
            specs["lm_head_bias"] = P("model")
        if cfg.exit_gate:
            specs["exit_gate_w"] = P(None)
            specs["exit_gate_b"] = P()
        return specs

    def _segment_specs(self, kind: str, attn: str = "") -> dict:
        cfg = self.cfg
        dense_ffn = kind == "dense"
        two_ln = not (cfg.parallel_residual and cfg.parallel_shared_ln)
        layers = {"ln1_scale": P(None, None)}
        if cfg.hc_mult > 1:
            from .mhc import param_specs as mhc_specs

            layers.update(mhc_specs())
        if attn == "K":
            from .kda import param_specs as kda_specs

            layers.update(kda_specs(cfg))
        elif cfg.attention == "mla":
            # heads column-split as wq/wo are; the latent projection and
            # its norm are shared by all heads and stay replicated
            layers.update({
                "wq_a": P(None, None, None), "q_norm_scale": P(None, None),
                "wq_b": P(None, None, "model"),
            } if cfg.q_lora_rank else {"wq": P(None, None, "model")})
            layers.update({
                "wkv_a": P(None, None, None),
                "kv_norm_scale": P(None, None),
                "wkv_b": P(None, None, "model"), "wo": P(None, "model", None),
            })
            if cfg.attn_out_gate:
                layers["w_ogate"] = P(None, None, "model")
        elif cfg.attention == "cca":
            from .cca import param_specs as cca_specs

            layers.update(cca_specs())
        else:
            layers.update({
                "wq": P(None, None, "model"),
                "wk": P(None, None, "model"),
                "wv": P(None, None, "model"),
                "wo": P(None, "model", None),
            })
            if cfg.attn_out_gate:
                layers["w_ogate"] = P(None, None, "model")
            if attn == "S" and cfg.attn_sink:
                layers["sink"] = P(None, "model")
        if two_ln:
            layers["ln2_scale"] = P(None, None)
        if cfg.sandwich_norm:
            layers["ln1_post_scale"] = P(None, None)
            layers["ln2_post_scale"] = P(None, None)
        if cfg.residual_scale:
            layers["res_scale"] = P(None, None, None, None)
        if dense_ffn:
            layers["w_in"] = P(None, None, "model")
            layers["w_out"] = P(None, "model", None)
            if cfg.is_glu:
                layers["w_gate"] = P(None, None, "model")
        if cfg.use_bias:
            layers.update({
                "ln1_bias": P(None, None),
                "bq": P(None, "model"), "bk": P(None, "model"), "bv": P(None, "model"),
                "bo": P(None, None),
            })
            if two_ln:
                layers["ln2_bias"] = P(None, None)
            if dense_ffn:
                layers["b_in"] = P(None, "model")
                layers["b_out"] = P(None, None)
        return layers

    def stacked_fn(self):
        """Which param shapes are layer-stacked (leading scan dim)."""
        counts = {n for _, n in self.cfg.segments}

        def is_stacked(shape) -> bool:
            return len(shape) >= 2 and shape[0] in counts

        return is_stacked

    # ---------------------------------------------------------------- apply
    def _maybe_bias(self, y, p, name):
        return y + p[name].astype(y.dtype) if self.cfg.use_bias and name in p else y

    @jax.named_scope("attn")
    def _attention_block(self, x, p, positions, attn_mask, attn: str = ""):
        """Shared attention half of a layer (dense and MoE trunks);
        ``attn`` the segment's attention kind under an ``attn_pattern``."""
        cfg = self.cfg
        B, S, d = x.shape
        h, kv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
        y = x if cfg.post_ln else _norm(x, p["ln1_scale"], p.get("ln1_bias"),
                                        cfg.norm, cfg.norm_eps)
        if attn:
            from . import windowed

            if attn_mask is not None:
                raise NotImplementedError(
                    "window and full layers side by side take no padding "
                    "mask yet")
            return windowed.attention_block(cfg, y, p, positions, attn)
        if cfg.attention == "cca":
            if attn_mask is not None:
                raise NotImplementedError(
                    "the convolutions over positions take no padding mask")
            from . import cca

            return cca.attention_block(cfg, y, p, positions,
                                       self.attention_fn)
        if cfg.attention == "mla":
            if attn_mask is not None:
                raise NotImplementedError(
                    "latent attention takes no padding mask yet")
            from . import mla

            q_nope, q_rope, new = mla.project(cfg, y, p, positions)
            # the full forward is prefill into an empty cache: the latents
            # lie as the cache holds them, positions on the lanes
            o = mla.attend_expanded(cfg, p, q_nope, q_rope,
                                    new.transpose(0, 2, 1), positions, S)
            o = constrain(o, P(B_AXES, "seq", "model", None))
            return o.reshape(B, S, h * cfg.v_dim) @ p["wo"].astype(x.dtype)
        q = self._maybe_bias(y @ p["wq"].astype(y.dtype), p, "bq").reshape(B, S, h, hd)
        kk = self._maybe_bias(y @ p["wk"].astype(y.dtype), p, "bk").reshape(B, S, kv, hd)
        vv = self._maybe_bias(y @ p["wv"].astype(y.dtype), p, "bv").reshape(B, S, kv, hd)
        if cfg.pos_embedding == "rope":
            q, kk = _rope(q, kk, positions, cfg.rope_theta, cfg.rotary_dim)
        attn_kw = {}
        if cfg.pos_embedding == "alibi":
            # ALiBi (Bloom): linear distance bias on the scores instead of
            # any positional embedding. Attention fns that take slopes
            # build the ramp themselves (flash: in-kernel from block
            # indices; ring: from the global ring-step positions) — no
            # (H, S, S) bias ever materializes, which is what makes ALiBi
            # long-context viable; the dense path gets the explicit bias.
            if getattr(self.attention_fn, "accepts_alibi_slopes", False):
                attn_kw["alibi_slopes"] = alibi_slopes(h)
            else:
                attn_kw["bias"] = alibi_bias(alibi_slopes(h), S)
        if getattr(self.attention_fn, "handles_sharding", False):
            # Explicit-collective attention (sequence/layer.py Ulysses or
            # ring): the wrapper does its own shard_map resharding.
            o = self.attention_fn(q, kk, vv, mask=attn_mask, **attn_kw)
        else:
            # Ulysses via GSPMD: trade the sequence shard for a head shard
            # around attention (reference sequence/layer.py all_to_all pair).
            qs = constrain(q, P(B_AXES, None, ("model", "seq"), None))
            ks = constrain(kk, P(B_AXES, None, None, None)) \
                if kv < h else constrain(kk, P(B_AXES, None, ("model", "seq"), None))
            vs = constrain(vv, P(B_AXES, None, None, None)) \
                if kv < h else constrain(vv, P(B_AXES, None, ("model", "seq"), None))
            o = self.attention_fn(qs, ks, vs, mask=attn_mask, **attn_kw)
            o = constrain(o, P(B_AXES, "seq", "model", None))
        o = self._maybe_bias(o.reshape(B, S, h * hd) @ p["wo"].astype(x.dtype), p, "bo")
        return o

    def _proj(self, y, p, name):
        """``y @ p[name]`` whether the weight is dense or int8/int4
        (inference WOQ: the engine keeps weights quantized end-to-end and
        the decode step consumes them at the point of use — via the fused
        Pallas GEMM when ``self.woq_kernel`` is set, else a per-use XLA
        dequant). Training trees never carry quantized leaves, so this is
        a plain matmul there."""
        w = p[name]
        from ..inference.quantization import QuantizedTensor, woq_dot

        if isinstance(w, QuantizedTensor):
            return woq_dot(y, w, use_kernel=getattr(self, "woq_kernel",
                                                    False))
        return y @ w.astype(y.dtype)

    @jax.named_scope("mlp")
    def _mlp_block(self, y, p):
        """FFN half. Returns (out, aux_loss); MoE trunks override this.

        NOTE: ``inference/kinds/steps.py _mlp_tp_quant`` mirrors this math
        with the w_out psum quantized (tp_comm_quant) — a change to the
        activation/gate/bias sequence here must be mirrored there or the
        quantized-TP greedy-parity oracle breaks for knob-on users."""
        cfg = self.cfg
        u = self._maybe_bias(self._proj(y, p, "w_in"), p, "b_in")
        if cfg.is_glu:
            # GLU: tag the gated product — bwd still recomputes the gate
            # matmul for the silu grad, but w_out's input is saved
            u = _swiglu(self._proj(y, p, "w_gate"), u, cfg.swiglu_limit)
            u = checkpoint_name(u, "mlp_h")
        else:
            # Tag the PRE-activation: under save_names_mlp the bwd then
            # recomputes only the elementwise nonlinearity (for both the
            # activation grad and w_out's input) — the w_in matmul, the
            # largest single dot in the layer, is never recomputed
            u = checkpoint_name(u, "mlp_h")
            u = _activation(u, cfg.activation)
        u = constrain(u, P(B_AXES, "seq", "model"))
        out = self._maybe_bias(self._proj(u, p, "w_out"), p, "b_out")
        return out, jnp.float32(0.0)

    def _layer(self, x, layer_params, positions, attn_mask, attn: str = ""):
        cfg = self.cfg
        p = layer_params
        # Remat-policy anchors (reference cpu_checkpointing,
        # activation_checkpointing/checkpointing.py:1036): the residual
        # stream entering the layer and the attention's output, in the
        # cheapest form that spares the backward the S^2 work. The engine's
        # names policies keep these in HBM (save_names, save_names_mlp) or
        # park them in pinned host memory during the forward and fetch them
        # back in the backward (offload_dots); under any other policy
        # checkpoint_name is an identity. An attention function that names
        # its own residuals (names_residuals: the flash kernel's flash_o and
        # flash_lse, ops/flash_attention.py) has said what its backward
        # needs: the projected output stays untagged and the backward redoes
        # one wo product from the saved o. Without that (dense, latent,
        # ring / Ulysses, sparse) the projected attn_out is the tag:
        # recomputing it would redo the whole S^2 attention.
        # a zaya router's state rides beside x, layer to layer (_trunk)
        x, s = x if isinstance(x, tuple) else (x, None)
        x = checkpoint_name(x, "layer_in")
        o = self._attention_block(x, p, positions, attn_mask, attn)
        if not getattr(self.attention_fn, "names_residuals", False):
            o = checkpoint_name(o, "attn_out")
        if cfg.post_ln:
            # BERT block: norms AFTER each residual; FFN input is the
            # post-attention-LN output directly
            x = _norm(x + o, p["ln1_scale"], p.get("ln1_bias"),
                      cfg.norm, cfg.norm_eps)
            out, aux = self._mlp_block(x, p)
            x = _norm(x + out, p["ln2_scale"], p.get("ln2_bias"),
                      cfg.norm, cfg.norm_eps)
            return constrain(x, P(B_AXES, "seq", None)), aux
        if cfg.parallel_residual:
            # x + attn(n1(x)) + mlp(n(x)) — GPT-J/NeoX/Falcon block shape;
            # shared_ln reuses n1 (XLA CSEs the recompute with the one
            # inside the attention branch).
            ln = ("ln1" if cfg.parallel_shared_ln else "ln2")
            y = _norm(x, p[f"{ln}_scale"], p.get(f"{ln}_bias"),
                      cfg.norm, cfg.norm_eps)
            out, aux = self._mlp_block(y, p)
            x = x + o + out
        else:
            x = self._residual(x, self._post_norm(o, p, "ln1"), p, 0)
            y = _norm(x, p["ln2_scale"], p.get("ln2_bias"),
                      cfg.norm, cfg.norm_eps)
            if s is None:
                out, aux = self._mlp_block(y, p)
            else:
                out, aux, s = self._mlp_block(y, p, state=s)
            x = self._residual(x, self._post_norm(out, p, "ln2"), p, 1)
        x = constrain(x, P(B_AXES, "seq", None))
        return (x if s is None else (x, s)), aux

    @staticmethod
    def _residual(x, out, p, side: int):
        """A sub-layer's output joining the stream: ``x + out``, or with
        ``residual_scale`` (a ``res_scale`` leaf) a scale and a bias a
        channel on each: ``(a_res x + b_res) + (a_out out + b_out)``;
        ``side`` 0 the attention's, 1 the FFN's."""
        if "res_scale" not in p:
            return x + out
        a_res, b_res, a_out, b_out = p["res_scale"][side].astype(x.dtype)
        return (a_res * x + b_res) + (a_out * out + b_out)

    def _post_norm(self, y, p, ln: str):
        """A sub-layer's output on its way into the residual stream: normed
        once more under ``sandwich_norm``, as it is otherwise."""
        if not self.cfg.sandwich_norm:
            return y
        return _norm(y, p[f"{ln}_post_scale"], None, self.cfg.norm,
                     self.cfg.norm_eps)

    def _tok_lookup(self, table, ids):
        return vocab_parallel_lookup(table, ids)

    @staticmethod
    def _positions(B: int, S: int):
        return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    @jax.named_scope("embed")
    def _embed(self, params, input_ids):
        """(B, S) int32 → ((B, S, D) embeddings, (B, S) positions)."""
        cfg = self.cfg
        B, S = input_ids.shape
        x = self._tok_lookup(params["tok_embed"].astype(cfg.dtype), input_ids)
        if cfg.mup.embed != 1.0:
            x = x * jnp.asarray(cfg.mup.embed, x.dtype)
        positions = self._positions(B, S)
        if cfg.pos_embedding == "learned":
            x = x + params["pos_embed"].astype(cfg.dtype)[positions[0]][None]
        if cfg.embed_norm:
            # Bloom word_embeddings_layernorm
            x = _norm(x, params["embed_ln_scale"], params.get("embed_ln_bias"),
                      cfg.norm, cfg.norm_eps)
        return constrain(x, P(B_AXES, "seq", None)), positions

    def _scan_layers(self, x, layers, positions, attn_mask, remat_policy,
                     attn: str = ""):
        """Scan the (remat-wrapped) layer body over a stacked layer pytree
        (``attn``: the segment's attention kind under an ``attn_pattern``).

        ``layers`` may be the full stack or (under pipeline shard_map) the
        local stage's slice. Returns (x, summed aux losses).

        When ``self.params_on_host`` is set (ZeRO-Infinity param offload,
        reference ``runtime/swap_tensor/partitioned_param_swapper.py:36``),
        the stacked weights live in pinned host memory and each scan step
        copies its layer slice into device HBM right before use — XLA's
        latency-hiding scheduler overlaps the next slice's DMA with the
        current layer's compute, so HBM only ever holds ~2 layers of weights.
        """
        body = partial(self._layer, positions=positions, attn_mask=attn_mask,
                       attn=attn)
        if remat_policy is not None:
            body = jax.checkpoint(body, policy=remat_policy, prevent_cse=False)
        stream = getattr(self, "params_on_host", False)
        if stream:
            from ..platform.mesh import to_device_memory

            specs = self.param_specs()["layers"]
            slice_specs = jax.tree.map(
                lambda s: P(*tuple(s)[1:]), specs,
                is_leaf=lambda x: isinstance(x, P))

        def scan_fn(carry, layer_params):
            if stream:
                layer_params = to_device_memory(layer_params, slice_specs)
            new_x, aux = body(carry, layer_params)
            return new_x, aux

        x, aux = lax.scan(scan_fn, x, layers)
        return x, self._fold_aux(aux)

    @staticmethod
    def _fold_aux(aux):
        """A segment's per-layer aux (stacked by the scan) as one value:
        aux losses add up."""
        return jnp.sum(aux)

    @staticmethod
    def _join_aux(auxes: list):
        """The segments' folded aux as the trunk's."""
        return sum(auxes[1:], auxes[0])

    def _head_norm(self, params, x):
        """Final layernorm only (the pipeline's vocab-sharded head applies
        its own unembedding slice). Post-LN trunks have no final norm —
        each block already ends normalized — and a looped trunk's passes
        each end in it (:meth:`loop_passes`), the last included."""
        if self.cfg.post_ln or self.cfg.loop_steps > 1:
            return x
        return self._final_norm(params, x)

    def _final_norm(self, params, x):
        return _norm(x, params["lnf_scale"], params.get("lnf_bias"),
                     self.cfg.norm, self.cfg.norm_eps)

    def loop_passes(self, params, x, carry, one_pass):
        """A looped trunk's passes as ONE scan over the pass index:
        ``one_pass(x, carry, r) -> (x, carry)`` runs the whole stack (the
        same weights every pass; ``carry`` is what the caller threads
        through, a cache or nothing), the final norm closes the pass, and
        what it leaves is what the next pass starts from and what the gate
        reads. Returns (the last pass's closed state, carry, {"hidden":
        (passes, B, T, d) every pass's closed state, "exit_pdf": (B, T,
        passes) float32, with ``exit_gate``})."""
        gate = self.cfg.exit_gate

        def body(c, r):
            x, carry = one_pass(*c, r)
            x = self._final_norm(params, x)
            lam = jax.nn.sigmoid(
                jnp.sum(x.astype(jnp.float32)
                        * params["exit_gate_w"].astype(jnp.float32), -1)
                + params["exit_gate_b"].astype(jnp.float32)) if gate else None
            return (x, carry), (x, lam)

        (x, carry), (hidden, lam) = lax.scan(
            body, (x, carry),
            jnp.arange(self.cfg.loop_steps, dtype=jnp.int32))
        passes = {"hidden": hidden}
        if gate:
            passes["exit_pdf"] = exit_pdf(lam)
        return x, carry, passes

    def _pre_head(self, params, x):
        """Final norm + (BERT) MLM transform: everything before the
        unembedding matmul — shared by the logits head and the fused-xent
        loss path."""
        cfg = self.cfg
        x = self._head_norm(params, x)
        if cfg.mlm_transform:
            # BERT cls.predictions.transform: dense + hidden_act + LN before
            # the tied decoder (HF uses config.hidden_act here too); output
            # bias added by the head / fused kernel via lm_head_bias
            x = _activation(x @ params["mlm_dense_w"].astype(x.dtype)
                            + params["mlm_dense_b"].astype(x.dtype),
                            cfg.activation)
            x = _norm(x, params["mlm_ln_scale"], params.get("mlm_ln_bias"),
                      cfg.norm, cfg.norm_eps)
        return x

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        """Final norm + unembedding: (B, S, D) → (B, S, V) logits."""
        cfg = self.cfg
        x = self._pre_head(params, x)
        w = (params["tok_embed"].astype(x.dtype).T if cfg.tie_embeddings
             else params["lm_head"].astype(x.dtype))
        if cfg.tiled_head > 1 and w.shape[1] % cfg.tiled_head == 0:
            from ..ops.tiled import tiled_matmul

            logits = tiled_matmul(x, w, cfg.tiled_head)
        else:
            logits = x @ w
        if cfg.mup.head != 1.0:
            logits = logits * jnp.asarray(cfg.mup.head, logits.dtype)
        if cfg.lm_head_bias:
            logits = logits + params["lm_head_bias"].astype(logits.dtype)
        return constrain(logits, P(B_AXES, "seq", "model"))

    def sparse_grad_names(self) -> tuple[str, ...]:
        """Param leaves whose gradient is row-sparse in the batch's tokens
        (the engine's ``sparse_gradients`` offload-D2H compression,
        reference ``sparse_allreduce`` engine.py:2427). ONLY the untied
        input embedding qualifies: a tied table also receives the
        unembedding's softmax gradient, which is dense over the vocab —
        top-k row selection there would silently drop gradient mass."""
        return () if self.cfg.tie_embeddings else ("tok_embed",)

    def _trunk(self, params, input_ids, attn_mask, remat_policy):
        """Embed + layer stack: (B, S) → ((B, S, D) pre-final-norm, aux).
        A looped trunk hands back its last pass's closed state (the head's
        norm is then none) and, as aux, what :meth:`loop_passes` says of
        the passes."""
        x, positions = self._embed(params, input_ids)
        if self.cfg.mixer_pattern:
            if attn_mask is not None or remat_policy is not None:
                raise NotImplementedError(
                    "a trunk of delta-rule mixers beside attention layers "
                    "(mixer_pattern) is served, not trained: no padding "
                    "mask, no remat")
            from .kda import trunk

            return trunk(self, params, x, positions)
        if self.cfg.index_pattern:
            from .dsa import trunk

            # the selection rides beside x from a layer with an indexer to
            # the layers that take it over: its own loop (models/dsa.py)
            if attn_mask is not None or remat_policy is not None:
                raise NotImplementedError(
                    "a trunk that selects positions (index_pattern) is "
                    "served, not trained: no padding mask, no remat")
            return trunk(self, params, x, positions)
        zaya = self.cfg.moe_router == "zaya"
        if zaya:
            # s_{-1} = 0: the router's state, carried from layer to layer
            x = (x, jnp.zeros(x.shape[:2] + (self.cfg.router_hidden,),
                              jnp.float32))

        def stack(x):
            auxes = []
            for seg, attn in zip(self.segment_params(params["layers"]),
                                 self.cfg.segment_attn):
                # (the schedules that override _scan_layers take no kind)
                x, a = self._scan_layers(x, seg, positions, attn_mask,
                                         remat_policy,
                                         **({"attn": attn} if attn else {}))
                auxes.append(a)
            return x, self._join_aux(auxes)

        if self.cfg.loop_steps > 1:
            x, _, passes = self.loop_passes(
                params, x, None, lambda x, _, r: (stack(x)[0], None))
            return x, passes
        x, aux = stack(x)
        return (x[0] if zaya else x), aux

    def apply(self, params, input_ids, *, attn_mask=None, remat_policy=None,
              return_aux: bool = False):
        """Forward: (B, S) int32 → (B, S, V) logits (compute dtype), or
        (B, S, D) final-norm hidden states for ``objective='feature'``."""
        x, aux = self._trunk(params, input_ids, attn_mask, remat_policy)
        if self.cfg.objective == "feature":
            # Feature extractor (CLIP text tower): no unembedding exists;
            # the product is the final-norm hidden states (B, S, D).
            out = self._head_norm(params, x)
        else:
            out = self._head(params, x)
        if return_aux:
            return out, aux
        return out

    # ----------------------------------------------------------------- loss
    def loss(self, params, batch, *, remat_policy=None):
        """Objective-dependent cross-entropy, fp32, mean over counted tokens,
        plus the MoE load-balancing aux loss when the trunk routes.

        ``clm``: next-token over (possibly loss-masked) positions.
        ``mlm`` (encoder / BERT): predict ``batch['labels']`` at the
        positions marked by ``batch['loss_mask']`` — no shift."""
        if self.cfg.objective == "feature":
            raise ValueError(
                "objective='feature' models have no unembedding/LM loss; "
                "train them under a task head (apply() gives hidden states)")
        ids = batch["input_ids"]
        mlm = self.cfg.objective == "mlm"
        B, S = ids.shape
        if self._fused_xent_active(
                batch_size=B, compute_dtype=params["tok_embed"].dtype):
            x, aux = self._trunk(params, ids, batch.get("attention_mask"),
                                 remat_policy)
            feats = self._pre_head(params, x)
            if mlm:
                nll = self._fused_nll(params, feats, batch["labels"])
            else:
                nll = self._fused_nll(params, feats[:, :-1], ids[:, 1:])
        else:
            logits, aux = self.apply(params, ids,
                                     attn_mask=batch.get("attention_mask"),
                                     remat_policy=remat_policy,
                                     return_aux=True)
            if mlm:
                nll = _token_nll(logits, batch["labels"])
            else:
                nll = _token_nll(logits[:, :-1], ids[:, 1:])
        mask = batch["loss_mask"] if mlm else batch.get("loss_mask")
        if mask is not None:
            mask = (mask if mlm else mask[:, 1:]).astype(jnp.float32)
            ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            ce = jnp.mean(nll)
        if self.cfg.num_experts > 1 and self.cfg.moe_router == "gshard":
            ce = ce + self.cfg.moe_aux_loss_weight * aux
        return ce

    def _fused_xent_active(self, batch_size: Optional[int] = None,
                           compute_dtype=None) -> bool:
        """Route the loss through the fused Pallas softmax-xent kernel?
        Auto (fused_xent=None): on for TPU when the head is expressible —
        tied embeddings (W stays in (V, d) table layout, no transpose) and
        no seq/pipe sharding (the kernel runs per data shard under
        shard_map; a seq-sharded head keeps the XLA path; model-axis
        sharding takes the vocab-sharded TP kernel). A batch size not
        divisible by the data-parallel world also keeps the XLA path:
        shard_map would split the flattened rows mid-sequence, which is
        numerically fine (the kernel is per-token) but forces a resharding
        gather against the batch-sharded feature layout right in the hot
        loss path — and partial eval batches must not start erroring
        because the fused path auto-activated."""
        cfg = self.cfg
        if cfg.fused_xent is False or not cfg.tie_embeddings \
                or cfg.objective not in ("clm", "mlm"):
            return False
        # hardware eligibility (f16-on-TPU, VMEM at wide d): ops/xent.py
        from ..ops.xent import fused_xent_eligible

        if not fused_xent_eligible(cfg.dtype, compute_dtype, cfg.d_model):
            return False
        mesh = current_mesh()
        if mesh is not None and not mesh.empty:
            from ..platform.mesh import manual_axes_of
            if manual_axes_of(mesh):
                return False
            for ax in ("seq", "pipe"):
                if ax in mesh.axis_names and mesh.shape[ax] != 1:
                    return False
            # model-axis sharding IS supported: the vocab-sharded TP
            # kernel (per-shard partials + two collectives) when the vocab
            # splits evenly across the axis, else the whole-vocab kernel
            # on the replicated table (fused_nll_sharded)
            tp = int(mesh.shape.get("model", 1))
            rows = self._dp_world(mesh) * (
                tp if cfg.vocab_size % tp != 0 else 1)
            if batch_size is not None and batch_size % rows != 0:
                return False
        if cfg.fused_xent:
            return True
        return jax.default_backend() == "tpu"

    @staticmethod
    def _dp_world(mesh) -> int:
        return mesh_dp_world(mesh)

    def _fused_nll(self, params, feats, targets):
        cfg = self.cfg
        bias = (params["lm_head_bias"].astype(feats.dtype)
                if cfg.lm_head_bias else None)
        return fused_nll_sharded(feats, targets,
                                 params["tok_embed"].astype(feats.dtype),
                                 bias)

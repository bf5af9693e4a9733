"""HF-checkpoint importer: load pretrained weights onto the native trunk.

This is the TPU-native answer to the reference's kernel-injection / AutoTP
machinery (``module_inject/replace_module.py:182``, ``auto_tp.py:175``,
``module_inject/load_checkpoint.py``): instead of walking a live torch module
graph and swapping layers for fused replacements, we map a *checkpoint* —
HF-format ``safetensors`` / ``pytorch_model.bin`` plus ``config.json`` — onto
the native :class:`TransformerLM` parameter pytree.  The trunk's
``param_specs()`` then plays the role of the ~20 per-architecture injection
policies: sharding is a property of the destination, not a rewrite of the
source, so TP/ZeRO/offload all apply to imported models for free.

Per-architecture mapping lives in small ``_Family`` converters (the analog of
``module_inject/containers/*``): name mapping, per-layer stacking into the
scan-friendly ``(L, ...)`` layout, qkv handling (GPT-2's fused ``c_attn`` is
split; Llama's separate projections are transposed from torch's ``(out, in)``
to matmul ``(in, out)``), and the RoPE basis permutation (HF "rotate-half"
→ interleaved pairs) absorbed into the q/k projection weights.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Callable, Dict, Tuple

import jax.numpy as jnp
import numpy as np

from ..utils.logging import log_dist
from .transformer import TransformerConfig

__all__ = ["load_hf_checkpoint", "import_state_dict", "config_from_hf"]


# ----------------------------------------------------------- tensor plumbing
def _to_numpy(t) -> np.ndarray:
    """torch / jax / numpy tensor → numpy, preserving the storage dtype.

    bf16 checkpoints stay bf16 (``ml_dtypes.bfloat16`` numpy arrays — the
    stack/transpose/permute ops all work on them), so a 70B import costs
    ~1× the checkpoint size in host RAM, not 3×; fp32 master creation
    upcasts leaf-by-leaf downstream in the engine."""
    if isinstance(t, np.ndarray):
        return t
    if isinstance(t, jnp.ndarray):
        return np.asarray(t)          # bf16 → ml_dtypes.bfloat16 view
    import torch

    if isinstance(t, torch.Tensor):
        t = t.detach().cpu()
        if t.dtype == torch.bfloat16:
            import ml_dtypes

            return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
        return t.numpy()
    raise TypeError(f"unsupported tensor type {type(t)!r}")


def _rope_interleave_perm(n_heads: int, head_dim: int,
                          rotary_dim: int | None = None) -> np.ndarray:
    """Column permutation converting HF rotate-half q/k projections to the
    trunk's interleaved-pair RoPE basis.

    HF rotates dim ``j`` with dim ``j + rd/2`` (shared freq_j); the trunk
    rotates dims ``(2j, 2j+1)``.  Mapping output column ``2j ← j`` and
    ``2j+1 ← j + rd/2`` per head makes both compute identical attention
    scores (the permutation is applied to q AND k, so dot products are
    invariant and ``wo`` needs no change).  With partial rotary
    (``rotary_dim`` < head_dim, NeoX ``rotary_pct``), only the leading
    rotary columns permute; the pass-through tail keeps identity order.
    GPT-J needs NO permutation — its rotary is natively interleaved."""
    rd = rotary_dim or head_dim
    half = rd // 2
    per_head = np.arange(head_dim, dtype=np.int64)
    rot = np.empty((rd,), dtype=np.int64)
    rot[0::2] = np.arange(half)
    rot[1::2] = np.arange(half) + half
    per_head[:rd] = rot
    return (np.arange(n_heads)[:, None] * head_dim + per_head[None, :]).reshape(-1)


class _SDict:
    """State-dict view with prefix stripping + access tracking."""

    def __init__(self, sd: Dict[str, Any], strip: Tuple[str, ...] = ()):
        self._sd = {}
        for k, v in sd.items():
            for p in strip:
                if k.startswith(p):
                    k = k[len(p):]
                    break
            self._sd[k] = v
        self.used: set[str] = set()

    def __contains__(self, k):
        return k in self._sd

    def take(self, k: str) -> np.ndarray:
        self.used.add(k)
        return _to_numpy(self._sd[k])

    def get(self, k: str):
        return self.take(k) if k in self._sd else None

    def unused(self) -> list[str]:
        return sorted(set(self._sd) - self.used)


def _stack(layers: list[dict[str, np.ndarray]]) -> dict[str, np.ndarray]:
    """Per-layer dicts → one dict of (L, ...)-stacked arrays."""
    keys = layers[0].keys()
    return {k: np.stack([lyr[k] for lyr in layers]) for k in keys}


# ------------------------------------------------------------- family: gpt2
def _gpt2_config(hf: dict) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["n_layer"],
        n_head=hf["n_head"],
        d_model=hf["n_embd"],
        d_ff=hf.get("n_inner") or 4 * hf["n_embd"],
        max_seq=hf.get("n_positions", 1024),
        pos_embedding="learned", norm="layernorm", activation="gelu",
        use_bias=True, tie_embeddings=True,
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
    )


def _gpt2_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """GPT-2: Conv1D stores weights as (in, out) — no transpose; fused
    ``c_attn`` (d, 3d) splits into wq/wk/wv."""
    d = cfg.d_model
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"h.{i}."
        ca_w = sd.take(h + "attn.c_attn.weight")          # (d, 3d)
        ca_b = sd.take(h + "attn.c_attn.bias")            # (3d,)
        wq, wk, wv = ca_w[:, :d], ca_w[:, d:2 * d], ca_w[:, 2 * d:]
        bq, bk, bv = ca_b[:d], ca_b[d:2 * d], ca_b[2 * d:]
        per_layer.append({
            "ln1_scale": sd.take(h + "ln_1.weight"),
            "ln1_bias": sd.take(h + "ln_1.bias"),
            "wq": wq, "wk": wk, "wv": wv, "bq": bq, "bk": bk, "bv": bv,
            "wo": sd.take(h + "attn.c_proj.weight"),
            "bo": sd.take(h + "attn.c_proj.bias"),
            "ln2_scale": sd.take(h + "ln_2.weight"),
            "ln2_bias": sd.take(h + "ln_2.bias"),
            "w_in": sd.take(h + "mlp.c_fc.weight"),
            "b_in": sd.take(h + "mlp.c_fc.bias"),
            "w_out": sd.take(h + "mlp.c_proj.weight"),
            "b_out": sd.take(h + "mlp.c_proj.bias"),
        })
    params = {
        "tok_embed": sd.take("wte.weight"),
        "pos_embed": sd.take("wpe.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("ln_f.weight"),
        "lnf_bias": sd.take("ln_f.bias"),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = sd.take("lm_head.weight").T
    return params


# ------------------------------------------------------ family: llama-like
def _llama_config(hf: dict) -> TransformerConfig:
    if hf.get("rope_scaling"):
        raise ValueError(
            "checkpoint uses rope_scaling (extended-context RoPE remap); the "
            "native trunk applies plain rope_theta positions — importing "
            "would silently change long-range attention. Unsupported.")
    if hf.get("sliding_window") and hf.get("use_sliding_window", True):
        log_dist("importer: checkpoint declares sliding_window="
                 f"{hf['sliding_window']} — the native trunk runs full causal "
                 "attention, so outputs diverge from HF beyond the window")
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf.get("num_key_value_heads") or hf["num_attention_heads"],
        d_model=hf["hidden_size"],
        d_ff=hf["intermediate_size"],
        max_seq=hf.get("max_position_embeddings", 4096),
        pos_embedding="rope", norm="rmsnorm", activation="silu_glu",
        use_bias=False, tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        rope_theta=hf.get("rope_theta", 10000.0),
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        num_experts=hf.get("num_local_experts", 1),
        moe_top_k=hf.get("num_experts_per_tok", 2),
    )


def _llama_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """Llama/Mistral/Mixtral: torch Linear (out, in) → transpose; absorb the
    RoPE basis change into wq/wk columns; Mixtral expert banks stacked."""
    hd = cfg.head_dim
    q_perm = _rope_interleave_perm(cfg.n_head, hd)
    kv_perm = _rope_interleave_perm(cfg.kv_heads, hd)
    moe = cfg.num_experts > 1
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"layers.{i}."
        lyr = {
            "ln1_scale": sd.take(h + "input_layernorm.weight"),
            "wq": sd.take(h + "self_attn.q_proj.weight").T[:, q_perm],
            "wk": sd.take(h + "self_attn.k_proj.weight").T[:, kv_perm],
            "wv": sd.take(h + "self_attn.v_proj.weight").T,
            "wo": sd.take(h + "self_attn.o_proj.weight").T,
            "ln2_scale": sd.take(h + "post_attention_layernorm.weight"),
        }
        if moe:
            m = h + "block_sparse_moe."
            lyr["router"] = sd.take(m + "gate.weight").T          # (d, E)
            # Mixtral expert order: w1=gate, w2=down, w3=up (all (out, in)).
            lyr["w_gate"] = np.stack([sd.take(f"{m}experts.{e}.w1.weight").T
                                      for e in range(cfg.num_experts)])
            lyr["w_out"] = np.stack([sd.take(f"{m}experts.{e}.w2.weight").T
                                     for e in range(cfg.num_experts)])
            lyr["w_in"] = np.stack([sd.take(f"{m}experts.{e}.w3.weight").T
                                    for e in range(cfg.num_experts)])
        else:
            lyr["w_gate"] = sd.take(h + "mlp.gate_proj.weight").T
            lyr["w_in"] = sd.take(h + "mlp.up_proj.weight").T
            lyr["w_out"] = sd.take(h + "mlp.down_proj.weight").T
        per_layer.append(lyr)
    params = {
        "tok_embed": sd.take("embed_tokens.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("norm.weight"),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = sd.take("lm_head.weight").T
    return params


# --------------------------------------------------------- family: internlm
def _internlm_config(hf: dict) -> TransformerConfig:
    """InternLM v1 (reference ``module_inject/containers/internlm.py``): a
    Llama block whose attention projections carry biases (config
    ``"bias": true``)."""
    cfg = _llama_config(hf)
    if hf.get("bias", True):
        cfg = dataclasses.replace(cfg, use_bias=True)
    return cfg


def _internlm_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """Llama mapping + attention biases. The q/k biases feed pre-RoPE
    activations, so they get the same interleave basis change as the wq/wk
    columns. The trunk's use_bias is all-or-nothing; InternLM has no
    rmsnorm/FFN biases, so those leaves are zeros (numeric no-ops)."""
    params = _llama_convert(sd, cfg)
    if not cfg.use_bias:
        return params
    hd = cfg.head_dim
    perms = {"q_proj": _rope_interleave_perm(cfg.n_head, hd),
             "k_proj": _rope_interleave_perm(cfg.kv_heads, hd)}
    layers = params["layers"]
    for name, leaf in (("q_proj", "bq"), ("k_proj", "bk"),
                       ("v_proj", "bv"), ("o_proj", "bo")):
        rows = np.stack([sd.take(f"layers.{i}.self_attn.{name}.bias")
                         for i in range(cfg.n_layer)])
        perm = perms.get(name)
        layers[leaf] = rows[:, perm] if perm is not None else rows
    L, d, f = cfg.n_layer, cfg.d_model, cfg.ffn_dim
    layers["ln1_bias"] = np.zeros((L, d), np.float32)
    layers["ln2_bias"] = np.zeros((L, d), np.float32)
    layers["b_in"] = np.zeros((L, f), np.float32)
    layers["b_out"] = np.zeros((L, d), np.float32)
    params["lnf_bias"] = np.zeros((d,), np.float32)
    return params


# -------------------------------------------------------------- family: opt
def _opt_config(hf: dict) -> TransformerConfig:
    if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
        raise ValueError("OPT variants with word_embed_proj_dim != "
                         "hidden_size (350m) are not supported")
    if not hf.get("do_layer_norm_before", True):
        raise ValueError("OPT-350m's post-norm layout is not supported")
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        d_model=hf["hidden_size"],
        d_ff=hf["ffn_dim"],
        max_seq=hf.get("max_position_embeddings", 2048),
        pos_embedding="learned", norm="layernorm",
        activation=hf.get("activation_function", "relu"),
        use_bias=True, tie_embeddings=True,
    )


def _opt_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """OPT: torch Linear (out, in) → transpose; embed_positions rows are
    offset by 2 (HF quirk: positions 0.. use rows 2..)."""
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"layers.{i}."
        per_layer.append({
            "ln1_scale": sd.take(h + "self_attn_layer_norm.weight"),
            "ln1_bias": sd.take(h + "self_attn_layer_norm.bias"),
            "wq": sd.take(h + "self_attn.q_proj.weight").T,
            "wk": sd.take(h + "self_attn.k_proj.weight").T,
            "wv": sd.take(h + "self_attn.v_proj.weight").T,
            "bq": sd.take(h + "self_attn.q_proj.bias"),
            "bk": sd.take(h + "self_attn.k_proj.bias"),
            "bv": sd.take(h + "self_attn.v_proj.bias"),
            "wo": sd.take(h + "self_attn.out_proj.weight").T,
            "bo": sd.take(h + "self_attn.out_proj.bias"),
            "ln2_scale": sd.take(h + "final_layer_norm.weight"),
            "ln2_bias": sd.take(h + "final_layer_norm.bias"),
            "w_in": sd.take(h + "fc1.weight").T,
            "b_in": sd.take(h + "fc1.bias"),
            "w_out": sd.take(h + "fc2.weight").T,
            "b_out": sd.take(h + "fc2.bias"),
        })
    return {
        "tok_embed": sd.take("embed_tokens.weight"),
        "pos_embed": sd.take("embed_positions.weight")[2:],   # offset-2 rows
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("final_layer_norm.weight"),
        "lnf_bias": sd.take("final_layer_norm.bias"),
    }



# ------------------------------------------------------------- family: gptj
def _gptj_config(hf: dict) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["n_layer"],
        n_head=hf["n_head"],
        d_model=hf["n_embd"],
        d_ff=hf.get("n_inner") or 4 * hf["n_embd"],
        max_seq=hf.get("n_positions", 2048),
        pos_embedding="rope", rotary_dim=hf.get("rotary_dim"),
        norm="layernorm", activation="gelu",   # gelu_new = tanh approx
        use_bias=True, tie_embeddings=False, lm_head_bias=True,
        parallel_residual=True, parallel_shared_ln=True,
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
    )


def _gptj_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """GPT-J: parallel residual, ONE layernorm, separate unbiased q/k/v,
    partial interleaved rotary (native basis — no permutation)."""
    d, hh = cfg.d_model, cfg.n_head * cfg.head_dim
    zeros_h = np.zeros((hh,), np.float32)
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"h.{i}."
        per_layer.append({
            "ln1_scale": sd.take(h + "ln_1.weight"),
            "ln1_bias": sd.take(h + "ln_1.bias"),
            "wq": sd.take(h + "attn.q_proj.weight").T,
            "wk": sd.take(h + "attn.k_proj.weight").T,
            "wv": sd.take(h + "attn.v_proj.weight").T,
            "bq": zeros_h, "bk": zeros_h, "bv": zeros_h,
            "wo": sd.take(h + "attn.out_proj.weight").T,
            "bo": np.zeros((d,), np.float32),
            "w_in": sd.take(h + "mlp.fc_in.weight").T,
            "b_in": sd.take(h + "mlp.fc_in.bias"),
            "w_out": sd.take(h + "mlp.fc_out.weight").T,
            "b_out": sd.take(h + "mlp.fc_out.bias"),
        })
    return {
        "tok_embed": sd.take("wte.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("ln_f.weight"),
        "lnf_bias": sd.take("ln_f.bias"),
        "lm_head": sd.take("lm_head.weight").T,
        "lm_head_bias": sd.take("lm_head.bias"),
    }


# ---------------------------------------------------------- family: gpt_neo
def _gptneo_config(hf: dict) -> TransformerConfig:
    """EleutherAI GPT-Neo (reference ``module_inject/containers/gptneo.py``).

    HF alternates global/local attention per layer (``attention_types``);
    the native trunk runs full causal attention everywhere, which is exact
    for sequences up to ``window_size`` (default 256) and diverges beyond it
    on the local layers — same policy as the Mistral sliding-window import.
    """
    att = hf.get("attention_types") or []
    if any("local" in str(block).lower() for block in att):
        log_dist("importer: gpt_neo declares local-attention layers "
                 f"(window_size={hf.get('window_size', 256)}) — the native "
                 "trunk runs full causal attention, so outputs diverge from "
                 "HF beyond the window on those layers")
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["num_layers"],
        n_head=hf["num_heads"],
        d_model=hf["hidden_size"],
        d_ff=hf.get("intermediate_size") or 4 * hf["hidden_size"],
        max_seq=hf.get("max_position_embeddings", 2048),
        pos_embedding="learned", norm="layernorm", activation="gelu",
        use_bias=True, tie_embeddings=True,
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
    )


def _gptneo_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """GPT-Neo: torch Linear (out, in) → transpose; q/k/v carry no bias
    (zeros, GPT-J pattern) but out_proj and the MLP do.  GPT-Neo applies NO
    1/sqrt(head_dim) attention scale (trained that way) — fold sqrt(hd) into
    wq to cancel the trunk's scaling exactly."""
    hh = cfg.n_head * cfg.head_dim
    q_scale = math.sqrt(cfg.head_dim)
    zeros_h = np.zeros((hh,), np.float32)
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"h.{i}."
        a = h + "attn.attention."
        per_layer.append({
            "ln1_scale": sd.take(h + "ln_1.weight"),
            "ln1_bias": sd.take(h + "ln_1.bias"),
            "wq": sd.take(a + "q_proj.weight").T * q_scale,
            "wk": sd.take(a + "k_proj.weight").T,
            "wv": sd.take(a + "v_proj.weight").T,
            "bq": zeros_h, "bk": zeros_h, "bv": zeros_h,
            "wo": sd.take(a + "out_proj.weight").T,
            "bo": sd.take(a + "out_proj.bias"),
            "ln2_scale": sd.take(h + "ln_2.weight"),
            "ln2_bias": sd.take(h + "ln_2.bias"),
            "w_in": sd.take(h + "mlp.c_fc.weight").T,
            "b_in": sd.take(h + "mlp.c_fc.bias"),
            "w_out": sd.take(h + "mlp.c_proj.weight").T,
            "b_out": sd.take(h + "mlp.c_proj.bias"),
        })
    return {
        "tok_embed": sd.take("wte.weight"),
        "pos_embed": sd.take("wpe.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("ln_f.weight"),
        "lnf_bias": sd.take("ln_f.bias"),
    }


# --------------------------------------------------------- family: gpt_neox
def _neox_config(hf: dict) -> TransformerConfig:
    hd = hf["hidden_size"] // hf["num_attention_heads"]
    if not hf.get("use_parallel_residual", True):
        raise ValueError("gpt_neox with use_parallel_residual=False: use the "
                         "sequential trunk via a custom config")
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        d_model=hf["hidden_size"],
        d_ff=hf["intermediate_size"],
        max_seq=hf.get("max_position_embeddings", 2048),
        pos_embedding="rope",
        rotary_dim=int(hd * hf.get("rotary_pct", 0.25)),
        rope_theta=hf.get("rotary_emb_base", 10000.0),
        norm="layernorm", activation="gelu_exact",
        use_bias=True, tie_embeddings=False,
        parallel_residual=True, parallel_shared_ln=False,
        norm_eps=hf.get("layer_norm_eps", 1e-5),
    )


def _split_fused_qkv_per_head(w, n_head, head_dim, d):
    """(3*h*hd, d) torch weight with per-head [q|k|v] interleave →
    three (d, h*hd) matmul weights (NeoX/Bloom layout)."""
    w = w.reshape(n_head, 3, head_dim, d)
    return tuple(w[:, j].reshape(n_head * head_dim, d).T for j in range(3))


def _split_fused_qkv_bias_per_head(b, n_head, head_dim):
    """Bias sibling of :func:`_split_fused_qkv_per_head`: (3*h*hd,) with
    per-head [q|k|v] interleave → three (h*hd,) bias vectors."""
    b = b.reshape(n_head, 3, head_dim)
    return tuple(b[:, j].reshape(-1) for j in range(3))


def _neox_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """GPT-NeoX: parallel residual with TWO layernorms, fused per-head-
    interleaved qkv, partial rotate-half rotary → permute rotary columns."""
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    perm = _rope_interleave_perm(h, hd, cfg.rotary_dim)
    per_layer = []
    for i in range(cfg.n_layer):
        p = f"layers.{i}."
        wq, wk, wv = _split_fused_qkv_per_head(
            sd.take(p + "attention.query_key_value.weight"), h, hd, d)
        bq, bk, bv = _split_fused_qkv_bias_per_head(
            sd.take(p + "attention.query_key_value.bias"), h, hd)
        per_layer.append({
            "ln1_scale": sd.take(p + "input_layernorm.weight"),
            "ln1_bias": sd.take(p + "input_layernorm.bias"),
            "ln2_scale": sd.take(p + "post_attention_layernorm.weight"),
            "ln2_bias": sd.take(p + "post_attention_layernorm.bias"),
            "wq": wq[:, perm], "wk": wk[:, perm], "wv": wv,
            "bq": bq[perm], "bk": bk[perm], "bv": bv,
            "wo": sd.take(p + "attention.dense.weight").T,
            "bo": sd.take(p + "attention.dense.bias"),
            "w_in": sd.take(p + "mlp.dense_h_to_4h.weight").T,
            "b_in": sd.take(p + "mlp.dense_h_to_4h.bias"),
            "w_out": sd.take(p + "mlp.dense_4h_to_h.weight").T,
            "b_out": sd.take(p + "mlp.dense_4h_to_h.bias"),
        })
    return {
        "tok_embed": sd.take("embed_in.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("final_layer_norm.weight"),
        "lnf_bias": sd.take("final_layer_norm.bias"),
        "lm_head": sd.take("embed_out.weight").T,
    }


# ------------------------------------------------------------ family: falcon
def _falcon_config(hf: dict) -> TransformerConfig:
    new_arch = hf.get("new_decoder_architecture", False)
    if not hf.get("parallel_attn", True):
        raise ValueError("falcon with parallel_attn=False is not supported")
    if hf.get("alibi", False):
        raise ValueError(
            "falcon with alibi=True (falcon-rw style): the converter maps "
            "the falcon family to rotary positions; importing would silently "
            "change attention. Unsupported.")
    if new_arch:
        n_kv = hf.get("num_kv_heads") or hf["num_attention_heads"]
    else:
        n_kv = 1 if hf.get("multi_query", True) else hf["num_attention_heads"]
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=n_kv,
        d_model=hf["hidden_size"],
        d_ff=hf.get("ffn_hidden_size") or 4 * hf["hidden_size"],
        max_seq=hf.get("max_position_embeddings", 2048),
        pos_embedding="rope", rope_theta=hf.get("rope_theta", 10000.0),
        norm="layernorm", activation="gelu_exact",
        use_bias=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        parallel_residual=True,
        parallel_shared_ln=not new_arch,   # 7B: one ln; 40B: ln_attn+ln_mlp
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
    )


def _falcon_split_qkv(w, n_head, n_kv, head_dim):
    """Falcon fused qkv → (wq, wk, wv) matmul weights.

    multi_query (7B): rows = [h*hd q | hd k | hd v].
    new_decoder_architecture (40B): rows grouped per kv head:
    [group0: q*(h/kv)·hd, k·hd, v·hd | group1: ...]."""
    d = w.shape[1]
    if n_kv == n_head:   # grouped layout degenerates per-head
        w = w.reshape(n_head, 3, head_dim, d)
        return tuple(w[:, j].reshape(-1, d).T for j in range(3))
    if n_kv == 1:
        hh = n_head * head_dim
        return (w[:hh].T, w[hh:hh + head_dim].T, w[hh + head_dim:].T)
    q_per = n_head // n_kv
    w = w.reshape(n_kv, q_per + 2, head_dim, d)
    wq = w[:, :q_per].reshape(-1, d).T
    wk = w[:, q_per].reshape(-1, d).T
    wv = w[:, q_per + 1].reshape(-1, d).T
    return wq, wk, wv


def _falcon_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim
    q_perm = _rope_interleave_perm(h, hd)
    kv_perm = _rope_interleave_perm(kv, hd)

    def bias_or_zeros(key, size):
        got = sd.get(key)     # falcon-rw ships biases; mainline has none
        return got if got is not None else np.zeros((size,), np.float32)

    per_layer = []
    for i in range(cfg.n_layer):
        p = f"h.{i}."
        wq, wk, wv = _falcon_split_qkv(
            sd.take(p + "self_attention.query_key_value.weight"), h, kv, hd)
        qkv_b = sd.get(p + "self_attention.query_key_value.bias")
        if qkv_b is not None:
            bq, bk, bv = (b.reshape(-1) for b in _falcon_split_qkv(
                qkv_b[:, None], h, kv, hd))
            bq, bk, bv = bq[q_perm], bk[kv_perm], bv
        else:
            bq = np.zeros((h * hd,), np.float32)
            bk = np.zeros((kv * hd,), np.float32)
            bv = np.zeros((kv * hd,), np.float32)
        lyr = {
            "wq": wq[:, q_perm], "wk": wk[:, kv_perm], "wv": wv,
            "bq": bq, "bk": bk, "bv": bv,
            "bo": bias_or_zeros(p + "self_attention.dense.bias", d),
            "b_in": bias_or_zeros(p + "mlp.dense_h_to_4h.bias", cfg.ffn_dim),
            "b_out": bias_or_zeros(p + "mlp.dense_4h_to_h.bias", d),
            "wo": sd.take(p + "self_attention.dense.weight").T,
            "w_in": sd.take(p + "mlp.dense_h_to_4h.weight").T,
            "w_out": sd.take(p + "mlp.dense_4h_to_h.weight").T,
        }
        if cfg.parallel_shared_ln:   # 7B: single input_layernorm
            lyr["ln1_scale"] = sd.take(p + "input_layernorm.weight")
            lyr["ln1_bias"] = sd.take(p + "input_layernorm.bias")
        else:                        # 40B: ln_attn (attn) + ln_mlp (mlp)
            lyr["ln1_scale"] = sd.take(p + "ln_attn.weight")
            lyr["ln1_bias"] = sd.take(p + "ln_attn.bias")
            lyr["ln2_scale"] = sd.take(p + "ln_mlp.weight")
            lyr["ln2_bias"] = sd.take(p + "ln_mlp.bias")
        per_layer.append(lyr)
    params = {
        "tok_embed": sd.take("word_embeddings.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("ln_f.weight"),
        "lnf_bias": sd.take("ln_f.bias"),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = sd.take("lm_head.weight").T
    return params


# ------------------------------------------------------------- family: bloom
def _bloom_config(hf: dict) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf.get("n_layer") or hf["num_hidden_layers"],
        n_head=hf.get("n_head") or hf["num_attention_heads"],
        d_model=hf.get("hidden_size") or hf["n_embed"],
        d_ff=4 * (hf.get("hidden_size") or hf["n_embed"]),
        max_seq=hf.get("seq_length", 2048),
        pos_embedding="alibi", norm="layernorm", activation="gelu",
        use_bias=True, tie_embeddings=True, embed_norm=True,
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
    )


def _bloom_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """Bloom-HF: sequential residual, ALiBi, word-embedding layernorm,
    fused per-head-interleaved qkv (same layout as NeoX)."""
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    per_layer = []
    for i in range(cfg.n_layer):
        p = f"h.{i}."
        wq, wk, wv = _split_fused_qkv_per_head(
            sd.take(p + "self_attention.query_key_value.weight"), h, hd, d)
        bq, bk, bv = _split_fused_qkv_bias_per_head(
            sd.take(p + "self_attention.query_key_value.bias"), h, hd)
        per_layer.append({
            "ln1_scale": sd.take(p + "input_layernorm.weight"),
            "ln1_bias": sd.take(p + "input_layernorm.bias"),
            "ln2_scale": sd.take(p + "post_attention_layernorm.weight"),
            "ln2_bias": sd.take(p + "post_attention_layernorm.bias"),
            "wq": wq, "wk": wk, "wv": wv,
            "bq": bq, "bk": bk, "bv": bv,
            "wo": sd.take(p + "self_attention.dense.weight").T,
            "bo": sd.take(p + "self_attention.dense.bias"),
            "w_in": sd.take(p + "mlp.dense_h_to_4h.weight").T,
            "b_in": sd.take(p + "mlp.dense_h_to_4h.bias"),
            "w_out": sd.take(p + "mlp.dense_4h_to_h.weight").T,
            "b_out": sd.take(p + "mlp.dense_4h_to_h.bias"),
        })
    return {
        "tok_embed": sd.take("word_embeddings.weight"),
        "embed_ln_scale": sd.take("word_embeddings_layernorm.weight"),
        "embed_ln_bias": sd.take("word_embeddings_layernorm.bias"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("ln_f.weight"),
        "lnf_bias": sd.take("ln_f.bias"),
    }



# ------------------------------------------------------------- family: qwen2
def _qwen2_config(hf: dict) -> TransformerConfig:
    cfg = _llama_config(hf)
    # Qwen2 = llama trunk + attention-projection biases (q/k/v only; the
    # remaining bias slots import as zeros)
    return dataclasses.replace(cfg, use_bias=True)


def _qwen2_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """Llama layout + q/k/v biases (RoPE basis permutation applies to the
    bias vectors exactly as to the projection columns)."""
    params = _llama_convert(sd, cfg)
    hd = cfg.head_dim
    q_perm = _rope_interleave_perm(cfg.n_head, hd)
    kv_perm = _rope_interleave_perm(cfg.kv_heads, hd)
    d, f = cfg.d_model, cfg.ffn_dim
    L = cfg.n_layer
    zeros = {
        "ln1_bias": np.zeros((L, d), np.float32),
        "ln2_bias": np.zeros((L, d), np.float32),
        "bo": np.zeros((L, d), np.float32),
        "b_in": np.zeros((L, f), np.float32),
        "b_out": np.zeros((L, d), np.float32),
    }
    bq = np.stack([sd.take(f"layers.{i}.self_attn.q_proj.bias")[q_perm]
                   for i in range(L)])
    bk = np.stack([sd.take(f"layers.{i}.self_attn.k_proj.bias")[kv_perm]
                   for i in range(L)])
    bv = np.stack([sd.take(f"layers.{i}.self_attn.v_proj.bias")
                   for i in range(L)])
    params["layers"].update({"bq": bq, "bk": bk, "bv": bv, **zeros})
    params["lnf_bias"] = np.zeros((d,), np.float32)
    return params


# --------------------------------------------------------------- family: phi
def _phi_config(hf: dict) -> TransformerConfig:
    if hf.get("qk_layernorm"):
        raise ValueError(
            "phi with qk_layernorm=True: the trunk has no per-head Q/K "
            "normalization — importing would silently change attention. "
            "Unsupported.")
    hd = hf["hidden_size"] // hf["num_attention_heads"]
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf.get("num_key_value_heads") or hf["num_attention_heads"],
        d_model=hf["hidden_size"],
        d_ff=hf["intermediate_size"],
        max_seq=hf.get("max_position_embeddings", 2048),
        pos_embedding="rope",
        rotary_dim=int(hd * hf.get("partial_rotary_factor", 0.5)),
        rope_theta=hf.get("rope_theta", 10000.0),
        norm="layernorm", activation="gelu",   # gelu_new = tanh approx
        use_bias=True, tie_embeddings=False, lm_head_bias=True,
        parallel_residual=True, parallel_shared_ln=True,
        norm_eps=hf.get("layer_norm_eps", 1e-5),
    )


def _phi_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """Phi: parallel residual with ONE layernorm, separate biased q/k/v,
    partial rotate-half rotary → permuted rotary columns + bias entries."""
    hd = cfg.head_dim
    q_perm = _rope_interleave_perm(cfg.n_head, hd, cfg.rotary_dim)
    kv_perm = _rope_interleave_perm(cfg.kv_heads, hd, cfg.rotary_dim)
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"layers.{i}."
        per_layer.append({
            "ln1_scale": sd.take(h + "input_layernorm.weight"),
            "ln1_bias": sd.take(h + "input_layernorm.bias"),
            "wq": sd.take(h + "self_attn.q_proj.weight").T[:, q_perm],
            "bq": sd.take(h + "self_attn.q_proj.bias")[q_perm],
            "wk": sd.take(h + "self_attn.k_proj.weight").T[:, kv_perm],
            "bk": sd.take(h + "self_attn.k_proj.bias")[kv_perm],
            "wv": sd.take(h + "self_attn.v_proj.weight").T,
            "bv": sd.take(h + "self_attn.v_proj.bias"),
            "wo": sd.take(h + "self_attn.dense.weight").T,
            "bo": sd.take(h + "self_attn.dense.bias"),
            "w_in": sd.take(h + "mlp.fc1.weight").T,
            "b_in": sd.take(h + "mlp.fc1.bias"),
            "w_out": sd.take(h + "mlp.fc2.weight").T,
            "b_out": sd.take(h + "mlp.fc2.bias"),
        })
    return {
        "tok_embed": sd.take("embed_tokens.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("final_layernorm.weight"),
        "lnf_bias": sd.take("final_layernorm.bias"),
        "lm_head": sd.take("lm_head.weight").T,
        "lm_head_bias": sd.take("lm_head.bias"),
    }



# ----------------------------------------------------------- family: codegen
def _codegen_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """CodeGen = GPT-J block with a TPU-blocked fused qkv: the projection is
    stored as mp_num=4 blocks, each [q | v | k] over n_head/4 heads
    (HF ``CodeGenAttention._split_heads``). Rotary is natively interleaved
    (no basis permutation), like GPT-J."""
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    mp = 4
    local = h * hd // mp
    zeros_h = np.zeros((h * hd,), np.float32)
    per_layer = []
    for i in range(cfg.n_layer):
        p = f"h.{i}."
        w = sd.take(p + "attn.qkv_proj.weight").reshape(mp, 3 * local, d)
        wq = w[:, :local].reshape(h * hd, d).T
        wv = w[:, local:2 * local].reshape(h * hd, d).T
        wk = w[:, 2 * local:].reshape(h * hd, d).T
        per_layer.append({
            "ln1_scale": sd.take(p + "ln_1.weight"),
            "ln1_bias": sd.take(p + "ln_1.bias"),
            "wq": wq, "wk": wk, "wv": wv,
            "bq": zeros_h, "bk": zeros_h, "bv": zeros_h,
            "wo": sd.take(p + "attn.out_proj.weight").T,
            "bo": np.zeros((d,), np.float32),
            "w_in": sd.take(p + "mlp.fc_in.weight").T,
            "b_in": sd.take(p + "mlp.fc_in.bias"),
            "w_out": sd.take(p + "mlp.fc_out.weight").T,
            "b_out": sd.take(p + "mlp.fc_out.bias"),
        })
    return {
        "tok_embed": sd.take("wte.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("ln_f.weight"),
        "lnf_bias": sd.take("ln_f.bias"),
        "lm_head": sd.take("lm_head.weight").T,
        "lm_head_bias": sd.take("lm_head.bias"),
    }


# ------------------------------------------------------ family: gpt_bigcode
def _bigcode_config(hf: dict) -> TransformerConfig:
    if not hf.get("multi_query", True):
        raise ValueError("gpt_bigcode with multi_query=False is untested; "
                         "refusing a silent mis-split of the fused qkv")
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["n_layer"],
        n_head=hf["n_head"],
        n_kv_head=1,
        d_model=hf["n_embd"],
        d_ff=hf.get("n_inner") or 4 * hf["n_embd"],
        max_seq=hf.get("n_positions", 8192),
        pos_embedding="learned", norm="layernorm", activation="gelu",
        use_bias=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
    )


def _bigcode_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """GPT-BigCode (StarCoder): GPT-2 block shape but torch Linear (out, in)
    layout and MQA — fused c_attn rows are [d q | hd k | hd v]."""
    d, hd = cfg.d_model, cfg.head_dim
    per_layer = []
    for i in range(cfg.n_layer):
        p = f"h.{i}."
        w = sd.take(p + "attn.c_attn.weight")           # (d + 2hd, d)
        b = sd.take(p + "attn.c_attn.bias")
        per_layer.append({
            "ln1_scale": sd.take(p + "ln_1.weight"),
            "ln1_bias": sd.take(p + "ln_1.bias"),
            "wq": w[:d].T, "wk": w[d:d + hd].T, "wv": w[d + hd:].T,
            "bq": b[:d], "bk": b[d:d + hd], "bv": b[d + hd:],
            "wo": sd.take(p + "attn.c_proj.weight").T,
            "bo": sd.take(p + "attn.c_proj.bias"),
            "ln2_scale": sd.take(p + "ln_2.weight"),
            "ln2_bias": sd.take(p + "ln_2.bias"),
            "w_in": sd.take(p + "mlp.c_fc.weight").T,
            "b_in": sd.take(p + "mlp.c_fc.bias"),
            "w_out": sd.take(p + "mlp.c_proj.weight").T,
            "b_out": sd.take(p + "mlp.c_proj.bias"),
        })
    params = {
        "tok_embed": sd.take("wte.weight"),
        "pos_embed": sd.take("wpe.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("ln_f.weight"),
        "lnf_bias": sd.take("ln_f.bias"),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = sd.take("lm_head.weight").T
    return params



# -------------------------------------------------------------- family: bert
_HF_ACT = {"gelu": "gelu_exact", "gelu_new": "gelu",
           "gelu_pytorch_tanh": "gelu", "relu": "relu", "silu": "silu",
           "swish": "silu"}


def _bert_config(hf: dict) -> TransformerConfig:
    act = hf.get("hidden_act", "gelu")
    if act not in _HF_ACT:
        raise ValueError(f"bert hidden_act {act!r} has no native mapping")
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        d_model=hf["hidden_size"],
        d_ff=hf["intermediate_size"],
        max_seq=hf.get("max_position_embeddings", 512),
        pos_embedding="learned", norm="layernorm",
        activation=_HF_ACT[act],
        use_bias=True, tie_embeddings=True, lm_head_bias=True,
        causal=False, objective="mlm",
        post_ln=True, embed_norm=True, mlm_transform=True,
        norm_eps=hf.get("layer_norm_eps", 1e-12),
    )


def _bert_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """BERT encoder (post-LN, embedding LayerNorm, MLM transform head).

    ``token_type_embeddings``: only segment A (type 0) is representable —
    its row folds into every position embedding (x = tok + pos + type[0]);
    the converter refuses checkpoints only through the unused-keys log,
    since all public MLM usage with a single segment passes type 0.
    """
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"encoder.layer.{i}."
        per_layer.append({
            "wq": sd.take(h + "attention.self.query.weight").T,
            "bq": sd.take(h + "attention.self.query.bias"),
            "wk": sd.take(h + "attention.self.key.weight").T,
            "bk": sd.take(h + "attention.self.key.bias"),
            "wv": sd.take(h + "attention.self.value.weight").T,
            "bv": sd.take(h + "attention.self.value.bias"),
            "wo": sd.take(h + "attention.output.dense.weight").T,
            "bo": sd.take(h + "attention.output.dense.bias"),
            "ln1_scale": sd.take(h + "attention.output.LayerNorm.weight"),
            "ln1_bias": sd.take(h + "attention.output.LayerNorm.bias"),
            "w_in": sd.take(h + "intermediate.dense.weight").T,
            "b_in": sd.take(h + "intermediate.dense.bias"),
            "w_out": sd.take(h + "output.dense.weight").T,
            "b_out": sd.take(h + "output.dense.bias"),
            "ln2_scale": sd.take(h + "output.LayerNorm.weight"),
            "ln2_bias": sd.take(h + "output.LayerNorm.bias"),
        })
    pos = sd.take("embeddings.position_embeddings.weight")
    type0 = sd.take("embeddings.token_type_embeddings.weight")[0]
    return {
        "tok_embed": sd.take("embeddings.word_embeddings.weight"),
        "pos_embed": pos + type0[None, :],    # segment-A fold
        "embed_ln_scale": sd.take("embeddings.LayerNorm.weight"),
        "embed_ln_bias": sd.take("embeddings.LayerNorm.bias"),
        "layers": _stack(per_layer),
        "mlm_dense_w": sd.take("cls.predictions.transform.dense.weight").T,
        "mlm_dense_b": sd.take("cls.predictions.transform.dense.bias"),
        "mlm_ln_scale": sd.take("cls.predictions.transform.LayerNorm.weight"),
        "mlm_ln_bias": sd.take("cls.predictions.transform.LayerNorm.bias"),
        "lm_head_bias": sd.take("cls.predictions.bias"),
    }


# -------------------------------------------------------- family: distilbert
def _distilbert_config(hf: dict) -> TransformerConfig:
    act = hf.get("activation", "gelu")
    if act not in _HF_ACT:
        raise ValueError(f"distilbert activation {act!r} has no native mapping")
    return TransformerConfig(
        vocab_size=hf["vocab_size"],
        n_layer=hf["n_layers"],
        n_head=hf["n_heads"],
        d_model=hf["dim"],
        d_ff=hf["hidden_dim"],
        max_seq=hf.get("max_position_embeddings", 512),
        pos_embedding="learned", norm="layernorm",
        activation=_HF_ACT[act],
        use_bias=True, tie_embeddings=True, lm_head_bias=True,
        causal=False, objective="mlm",
        post_ln=True, embed_norm=True, mlm_transform=True,
        norm_eps=1e-12,
    )


def _distilbert_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """DistilBERT: BERT block without token types, flat layer names."""
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"transformer.layer.{i}."
        per_layer.append({
            "wq": sd.take(h + "attention.q_lin.weight").T,
            "bq": sd.take(h + "attention.q_lin.bias"),
            "wk": sd.take(h + "attention.k_lin.weight").T,
            "bk": sd.take(h + "attention.k_lin.bias"),
            "wv": sd.take(h + "attention.v_lin.weight").T,
            "bv": sd.take(h + "attention.v_lin.bias"),
            "wo": sd.take(h + "attention.out_lin.weight").T,
            "bo": sd.take(h + "attention.out_lin.bias"),
            "ln1_scale": sd.take(h + "sa_layer_norm.weight"),
            "ln1_bias": sd.take(h + "sa_layer_norm.bias"),
            "w_in": sd.take(h + "ffn.lin1.weight").T,
            "b_in": sd.take(h + "ffn.lin1.bias"),
            "w_out": sd.take(h + "ffn.lin2.weight").T,
            "b_out": sd.take(h + "ffn.lin2.bias"),
            "ln2_scale": sd.take(h + "output_layer_norm.weight"),
            "ln2_bias": sd.take(h + "output_layer_norm.bias"),
        })
    return {
        "tok_embed": sd.take("embeddings.word_embeddings.weight"),
        "pos_embed": sd.take("embeddings.position_embeddings.weight"),
        "embed_ln_scale": sd.take("embeddings.LayerNorm.weight"),
        "embed_ln_bias": sd.take("embeddings.LayerNorm.bias"),
        "layers": _stack(per_layer),
        "mlm_dense_w": sd.take("vocab_transform.weight").T,
        "mlm_dense_b": sd.take("vocab_transform.bias"),
        "mlm_ln_scale": sd.take("vocab_layer_norm.weight"),
        "mlm_ln_bias": sd.take("vocab_layer_norm.bias"),
        "lm_head_bias": sd.take("vocab_projector.bias"),
    }



# ------------------------------------------------------ family: megatron_gpt
def _megatron_config(hf: dict) -> TransformerConfig:
    """Megatron-LM GPT checkpoint (reference
    ``module_inject/containers/megatron_gpt.py``).  Megatron has no HF
    config.json; callers pass the training args as a dict with
    ``model_type='megatron_gpt'``.  Default activation is the tanh-approx
    gelu (Megatron's fused bias-gelu); pass ``activation='gelu_exact'``
    for checkpoints trained with the unfused erf gelu."""
    return TransformerConfig(
        vocab_size=hf["vocab_size"] if "vocab_size" in hf
        else hf["padded_vocab_size"],
        n_layer=hf["num_layers"],
        n_head=hf["num_attention_heads"],
        d_model=hf["hidden_size"],
        d_ff=hf.get("ffn_hidden_size") or 4 * hf["hidden_size"],
        max_seq=hf.get("max_position_embeddings", 1024),
        pos_embedding="learned", norm="layernorm",
        activation=hf.get("activation", "gelu"),
        use_bias=True, tie_embeddings=True,
        norm_eps=hf.get("layernorm_epsilon", 1e-5),
    )


def _megatron_attn_layer(sd: _SDict, p: str, cfg: TransformerConfig) -> dict:
    """Shared attention/LN half of a Megatron layer (dense and MoE)."""
    h, hd, d = cfg.n_head, cfg.head_dim, cfg.d_model
    wq, wk, wv = _split_fused_qkv_per_head(
        sd.take(p + "self_attention.query_key_value.weight"), h, hd, d)
    bq, bk, bv = _split_fused_qkv_bias_per_head(
        sd.take(p + "self_attention.query_key_value.bias"), h, hd)
    return {
        "ln1_scale": sd.take(p + "input_layernorm.weight"),
        "ln1_bias": sd.take(p + "input_layernorm.bias"),
        "wq": wq, "wk": wk, "wv": wv, "bq": bq, "bk": bk, "bv": bv,
        "wo": sd.take(p + "self_attention.dense.weight").T,
        "bo": sd.take(p + "self_attention.dense.bias"),
        "ln2_scale": sd.take(p + "post_attention_layernorm.weight"),
        "ln2_bias": sd.take(p + "post_attention_layernorm.bias"),
    }


def _megatron_embed_head(sd: _SDict, per_layer: list) -> dict:
    return {
        "tok_embed": sd.take("embedding.word_embeddings.weight"),
        "pos_embed": sd.take("embedding.position_embeddings.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("encoder.final_layernorm.weight"),
        "lnf_bias": sd.take("encoder.final_layernorm.bias"),
    }


def _megatron_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """Megatron-LM GPT: sequential block, learned positions, fused
    per-head-interleaved qkv (the layout NeoX inherited), biased
    projections, word-embedding-tied head."""
    per_layer = []
    for i in range(cfg.n_layer):
        p = f"encoder.layers.{i}."
        lyr = _megatron_attn_layer(sd, p, cfg)
        lyr.update({
            "w_in": sd.take(p + "mlp.dense_h_to_4h.weight").T,
            "b_in": sd.take(p + "mlp.dense_h_to_4h.bias"),
            "w_out": sd.take(p + "mlp.dense_4h_to_h.weight").T,
            "b_out": sd.take(p + "mlp.dense_4h_to_h.bias"),
        })
        per_layer.append(lyr)
    return _megatron_embed_head(sd, per_layer)


# ------------------------------------------------- family: megatron_gpt_moe
def _megatron_moe_config(hf: dict) -> TransformerConfig:
    """Megatron-DeepSpeed MoE GPT (reference
    ``module_inject/containers/megatron_gpt_moe.py``): the dense Megatron
    block with the MLP replaced by ``deepspeed_moe`` (TopKGate + expert
    bank, ``moe/sharded_moe.py``). ``num_experts`` may arrive as the
    Megatron arg list form; top-k defaults to the reference TopKGate's
    k=1 (Switch-style) unless the args say otherwise."""
    cfg = _megatron_config(hf)
    E = hf["num_experts"]
    if isinstance(E, (list, tuple)):
        if len(set(E)) != 1:
            raise ValueError(
                f"per-layer expert counts {E} are not supported: the trunk "
                "routes a uniform expert bank (expert-interval checkpoints "
                "with dense layers mixed in cannot be imported)")
        E = E[0]
    if int(E) < 2:
        raise ValueError(
            "num_experts=1 deepspeed_moe checkpoint: the routed trunk needs "
            ">=2 experts (a 1-expert bank would import into shapes the dense "
            "model cannot consume) — import it as model_type='megatron_gpt' "
            "after renaming the expert MLP keys to the dense layout")
    return dataclasses.replace(
        cfg, num_experts=int(E),
        moe_top_k=int(hf.get("moe_top_k", hf.get("topk", 1))))


def _megatron_moe_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """Megatron-DS MoE: router = ``gate.wg.weight`` (E, d) → (d, E);
    experts ``deepspeed_experts.{e}.dense_*`` stacked into (E, d, f) /
    (E, f, d) banks with per-expert biases."""
    E = cfg.num_experts
    per_layer = []
    for i in range(cfg.n_layer):
        p = f"encoder.layers.{i}."
        moe = p + "mlp.deepspeed_moe."
        if moe + "gate.wg.weight" not in sd:
            raise ValueError(
                f"layer {i} has no deepspeed_moe gate: mixed dense/MoE "
                "(expert-interval > 1) checkpoints are not importable — the "
                "trunk routes every layer")
        lyr = _megatron_attn_layer(sd, p, cfg)
        ex = moe + "experts.deepspeed_experts."
        lyr.update({
            "router": sd.take(moe + "gate.wg.weight").T,          # (d, E)
            "w_in": np.stack([sd.take(f"{ex}{e}.dense_h_to_4h.weight").T
                              for e in range(E)]),                # (E, d, f)
            "b_in": np.stack([sd.take(f"{ex}{e}.dense_h_to_4h.bias")
                              for e in range(E)]),                # (E, f)
            "w_out": np.stack([sd.take(f"{ex}{e}.dense_4h_to_h.weight").T
                               for e in range(E)]),               # (E, f, d)
            "b_out": np.stack([sd.take(f"{ex}{e}.dense_4h_to_h.bias")
                               for e in range(E)]),               # (E, d)
        })
        per_layer.append(lyr)
    return _megatron_embed_head(sd, per_layer)


# -------------------------------------------------------------- family: clip
def _clip_config(hf: dict) -> TransformerConfig:
    """CLIP text tower (reference ``module_inject/containers/clip.py`` —
    the Stable-Diffusion text conditioner).  Accepts a full CLIPConfig
    (nested ``text_config``) or a standalone CLIPTextConfig.  The tower is
    a pre-LN *causal* encoder whose product is final-norm hidden states,
    so it imports as ``objective='feature'`` (no unembedding)."""
    txt = hf.get("text_config") or hf
    return TransformerConfig(
        vocab_size=txt["vocab_size"],
        n_layer=txt["num_hidden_layers"],
        n_head=txt["num_attention_heads"],
        d_model=txt["hidden_size"],
        d_ff=txt["intermediate_size"],
        max_seq=txt.get("max_position_embeddings", 77),
        pos_embedding="learned", norm="layernorm",
        activation=txt.get("hidden_act", "quick_gelu"),
        use_bias=True, tie_embeddings=False, causal=True,
        objective="feature",
        norm_eps=txt.get("layer_norm_eps", 1e-5),
    )


def _clip_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    """CLIP text encoder: torch Linear (out, in) → transpose; all
    projections biased; learned positions; final layernorm, no head."""
    per_layer = []
    for i in range(cfg.n_layer):
        h = f"encoder.layers.{i}."
        per_layer.append({
            "ln1_scale": sd.take(h + "layer_norm1.weight"),
            "ln1_bias": sd.take(h + "layer_norm1.bias"),
            "wq": sd.take(h + "self_attn.q_proj.weight").T,
            "bq": sd.take(h + "self_attn.q_proj.bias"),
            "wk": sd.take(h + "self_attn.k_proj.weight").T,
            "bk": sd.take(h + "self_attn.k_proj.bias"),
            "wv": sd.take(h + "self_attn.v_proj.weight").T,
            "bv": sd.take(h + "self_attn.v_proj.bias"),
            "wo": sd.take(h + "self_attn.out_proj.weight").T,
            "bo": sd.take(h + "self_attn.out_proj.bias"),
            "ln2_scale": sd.take(h + "layer_norm2.weight"),
            "ln2_bias": sd.take(h + "layer_norm2.bias"),
            "w_in": sd.take(h + "mlp.fc1.weight").T,
            "b_in": sd.take(h + "mlp.fc1.bias"),
            "w_out": sd.take(h + "mlp.fc2.weight").T,
            "b_out": sd.take(h + "mlp.fc2.bias"),
        })
    return {
        "tok_embed": sd.take("embeddings.token_embedding.weight"),
        "pos_embed": sd.take("embeddings.position_embedding.weight"),
        "layers": _stack(per_layer),
        "lnf_scale": sd.take("final_layer_norm.weight"),
        "lnf_bias": sd.take("final_layer_norm.bias"),
    }


# ---------------------------------------------------------------- family: t5
def _t5_config(hf: dict):
    from .t5 import T5Config

    proj = hf.get("feed_forward_proj", "relu")
    if proj not in ("relu", "gated-gelu"):
        raise ValueError(f"t5 feed_forward_proj {proj!r} unsupported")
    return T5Config(
        vocab_size=hf["vocab_size"],
        d_model=hf["d_model"],
        d_kv=hf["d_kv"],
        d_ff=hf["d_ff"],
        n_layer=hf["num_layers"],
        n_dec_layer=hf.get("num_decoder_layers") or hf["num_layers"],
        n_head=hf["num_heads"],
        rel_buckets=hf.get("relative_attention_num_buckets", 32),
        rel_max_distance=hf.get("relative_attention_max_distance", 128),
        gated_ffn=proj == "gated-gelu",
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        pad_token_id=hf.get("pad_token_id", 0),
        norm_eps=hf.get("layer_norm_epsilon", 1e-6),
    )


def _t5_convert(sd: _SDict, cfg) -> dict:
    """T5 encoder-decoder: relative-bias tables live on block 0 only;
    DenseReluDense wi/wo (or wi_0/wi_1 gated); all torch Linear (out, in)."""
    def stack(prefix, n, cross):
        per = []
        for i in range(n):
            b = f"{prefix}.block.{i}."
            ff = 2 if cross else 1
            lyr = {
                "ln1": sd.take(b + "layer.0.layer_norm.weight"),
                "wq": sd.take(b + "layer.0.SelfAttention.q.weight").T,
                "wk": sd.take(b + "layer.0.SelfAttention.k.weight").T,
                "wv": sd.take(b + "layer.0.SelfAttention.v.weight").T,
                "wo": sd.take(b + "layer.0.SelfAttention.o.weight").T,
                "ln_ffn": sd.take(b + f"layer.{ff}.layer_norm.weight"),
                "w_out": sd.take(b + f"layer.{ff}.DenseReluDense.wo.weight").T,
            }
            if cfg.gated_ffn:
                lyr["w_gate"] = sd.take(
                    b + f"layer.{ff}.DenseReluDense.wi_0.weight").T
                lyr["w_in"] = sd.take(
                    b + f"layer.{ff}.DenseReluDense.wi_1.weight").T
            else:
                lyr["w_in"] = sd.take(
                    b + f"layer.{ff}.DenseReluDense.wi.weight").T
            if cross:
                lyr.update({
                    "ln_cross": sd.take(b + "layer.1.layer_norm.weight"),
                    "cq": sd.take(b + "layer.1.EncDecAttention.q.weight").T,
                    "ck": sd.take(b + "layer.1.EncDecAttention.k.weight").T,
                    "cv": sd.take(b + "layer.1.EncDecAttention.v.weight").T,
                    "co": sd.take(b + "layer.1.EncDecAttention.o.weight").T,
                })
            per.append(lyr)
        return _stack(per)

    params = {
        "shared": sd.take("shared.weight"),
        "enc": {
            "layers": stack("encoder", cfg.n_layer, cross=False),
            "rel_bias": sd.take("encoder.block.0.layer.0.SelfAttention."
                                "relative_attention_bias.weight"),
            "final_ln": sd.take("encoder.final_layer_norm.weight"),
        },
        "dec": {
            "layers": stack("decoder", cfg.n_dec_layer, cross=True),
            "rel_bias": sd.take("decoder.block.0.layer.0.SelfAttention."
                                "relative_attention_bias.weight"),
            "final_ln": sd.take("decoder.final_layer_norm.weight"),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = sd.take("lm_head.weight").T
    return params


# -------------------------------------------------- family: mimo_v2_flash
def _mimo_v2_config(hf: dict) -> TransformerConfig:
    """MiMo-V2-Flash's ``config.json`` → the native configuration: window
    layers beside full ones (``attn_pattern``), keys wider than values,
    partial rope at two thetas, a dense layer then sigmoid-routed experts."""
    from .presets import mimo_v2_flash

    for key, only in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True), ("attention_bias", False),
                      ("add_full_attention_sink_bias", False),
                      ("hidden_act", "silu")):
        if hf.get(key, only) != only:
            raise ValueError(f"mimo_v2_flash with {key}={hf[key]!r}: the "
                             f"native trunk runs {only!r}")
    if hf.get("n_shared_experts") or hf.get("rope_scaling"):
        raise ValueError("mimo_v2_flash with shared experts or rope_scaling "
                         "is not what the native trunk runs")
    L, freq = hf["num_hidden_layers"], list(hf["moe_layer_freq"])
    dense = freq.index(1) if 1 in freq else L
    if len(freq) != L or len(hf["hybrid_layer_pattern"]) != L \
            or any(f != 1 for f in freq[dense:]):
        raise ValueError("hybrid_layer_pattern and moe_layer_freq name every "
                         "layer, the dense ones leading")
    return mimo_v2_flash(
        "tiny", attn_pattern="".join("GS"[k] for k in
                                     hf["hybrid_layer_pattern"]),
        n_layer=L, n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"],
        window_kv_heads=hf["swa_num_key_value_heads"],
        d_model=hf["hidden_size"], qk_head_dim=hf["head_dim"],
        v_head_dim=hf["v_head_dim"],
        rotary_dim=int(hf["head_dim"] * hf.get("partial_rotary_factor", 1.0)),
        window=hf["sliding_window"], attn_sink=bool(
            hf.get("add_swa_attention_sink_bias", False)),
        d_ff=hf["intermediate_size"], rope_theta=float(hf["rope_theta"]),
        window_rope_theta=float(hf["swa_rope_theta"]),
        attn_value_scale=float(hf.get("attention_value_scale", 1.0)),
        norm_eps=hf["layernorm_epsilon"], vocab_size=hf["vocab_size"],
        max_seq=hf["max_position_embeddings"],
        num_experts=hf["n_routed_experts"],
        moe_top_k=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"], moe_first_dense=dense,
        moe_routed_scale=float(hf.get("routed_scaling_factor") or 1.0))


def _mimo_v2_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    raise NotImplementedError(
        "mimo_v2_flash: config.json maps to the native configuration "
        "(config_from_hf), the checkpoint's tensors do not yet: the names "
        "and layouts of its weights (the fused or split q/k/v, the experts' "
        "banks, the sink's parameter) are not in config.json and were not "
        "to hand when the family was written; a guessed key map would load "
        "a different model under this one's name")


# --------------------------------------------------- family: glm_moe_dsa
def _glm_moe_dsa_config(hf: dict) -> TransformerConfig:
    """GLM-5.2's ``config.json`` -> the native configuration: the DeepSeek-V3
    block with a low-rank query, and an indexer in the layers
    ``indexer_types`` calls ``"full"`` whose pick the ``"shared"`` layers
    behind it take over."""
    from .presets import glm_moe_dsa

    for key, only in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("rope_interleave", True),
                      ("indexer_rope_interleave", True),
                      ("index_topk_pattern", None),
                      ("tie_word_embeddings", False)):
        if hf.get(key, only) != only:
            raise ValueError(f"glm_moe_dsa with {key}={hf[key]!r}: the "
                             f"native trunk runs {only!r}")
    rope = hf.get("rope_parameters") or {"rope_theta": hf.get("rope_theta")}
    if rope.get("rope_type", "default") != "default":
        raise ValueError("glm_moe_dsa with a scaled rope is not what the "
                         "native trunk runs")
    L = hf["num_hidden_layers"]
    kinds, ffn = list(hf["indexer_types"]), list(hf["mlp_layer_types"])
    dense = ffn.index("sparse") if "sparse" in ffn else L
    if len(kinds) != L or len(ffn) != L or set(kinds) - {"full", "shared"} \
            or any(f != "sparse" for f in ffn[dense:]) \
            or dense != min(hf["first_k_dense_replace"], L):
        raise ValueError("indexer_types and mlp_layer_types name every "
                         "layer, full | shared and the dense ones leading")
    if hf["qk_head_dim"] != hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    return glm_moe_dsa(
        "tiny", index_pattern="".join("F" if k == "full" else "s"
                                      for k in kinds),
        n_layer=L, n_head=hf["num_attention_heads"],
        d_model=hf["hidden_size"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], max_seq=hf["max_position_embeddings"],
        norm_eps=hf["rms_norm_eps"], rope_theta=float(rope["rope_theta"]),
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        index_topk=hf["index_topk"], index_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"],
        num_experts=hf["n_routed_experts"],
        moe_top_k=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"],
        moe_shared_d_ff=hf["n_shared_experts"] * hf["moe_intermediate_size"],
        moe_norm_topk=hf["norm_topk_prob"],
        moe_routed_scale=float(hf["routed_scaling_factor"]),
        moe_first_dense=dense)


def _glm_moe_dsa_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    raise NotImplementedError(
        "glm_moe_dsa: config.json maps to the native configuration "
        "(config_from_hf), the checkpoint's tensors do not yet: no weights "
        "were to hand when the family was written, the published indexer "
        "holds its keys in 8 bits behind a Hadamard turn that this trunk "
        "does not state, and a guessed key map would load a different model "
        "under this one's name")


# ----------------------------------------------------------- family: zaya
def _zaya_config(hf: dict) -> TransformerConfig:
    """ZAYA1's ``config.json`` → the native configuration: every layer
    ``hybrid`` (an attention sub-layer in a compressed latent behind two
    causal convolutions, then top-1 SwiGLU experts behind an MLP router with
    a carried state), a tied head."""
    from .presets import zaya

    for key, only in (("hidden_act", "silu"), ("attention_bias", False),
                      ("lm_head_bias", False), ("tie_word_embeddings", True),
                      ("sliding_window", None), ("num_experts_per_tok", 1)):
        if hf.get(key, only) != only:
            raise ValueError(f"zaya with {key}={hf[key]!r}: the native trunk "
                             f"runs {only!r}")
    L = hf["num_hidden_layers"]
    if list(hf["layer_types"]) != ["hybrid"] * L:
        raise ValueError("zaya: layer_types names num_hidden_layers hybrid "
                         "layers (a sliding layer is the larger sibling's)")
    rope = hf["rope_parameters"]["hybrid"]
    return zaya(
        "tiny", n_layer=L, n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"], d_model=hf["hidden_size"],
        qk_head_dim=hf["head_dim"],
        rotary_dim=int(hf["head_dim"] * rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        cca_conv=(hf["cca_time0"], hf["cca_time1"]),
        num_experts=hf["num_experts"], moe_d_ff=hf["moe_intermediate_size"],
        router_hidden=hf["router_hidden_size"], norm_eps=hf["rms_norm_eps"],
        vocab_size=hf["vocab_size"], max_seq=hf["max_position_embeddings"])


def _zaya_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    raise NotImplementedError(
        "zaya: config.json maps to the native configuration "
        "(config_from_hf), the checkpoint's tensors do not yet: the names "
        "and layouts of its weights (the convs' taps, the value shift's two "
        "projections, the router's MLP and its depth-averaging coefficient, "
        "the residual scales, the experts' banks) are not in config.json "
        "and were not to hand when the family was written; a guessed key map "
        "would load a different model under this one's name")


# ------------------------------------------------------ family: falcon_h1
def _falcon_h1_config(hf: dict) -> TransformerConfig:
    """Falcon-H1's ``config.json`` → the native configuration: every layer a
    Mamba-2 mixer and rotary GQA attention side by side, then a gated FFN,
    each branch times its published multiplier (``MuP``)."""
    from .presets import falcon_h1
    from .transformer import MuP

    for key, only in (("hidden_act", "silu"), ("attention_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("mamba_rms_norm", True), ("mamba_use_mlp", True),
                      ("mamba_norm_before_gate", False),
                      ("attn_layer_indices", None), ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        if hf.get(key, only) != only:
            raise ValueError(f"falcon_h1 with {key}={hf[key]!r}: the native "
                             f"trunk runs {only!r}")
    heads, hd = hf["mamba_n_heads"], hf["mamba_d_head"]
    if heads * hd != hf["mamba_d_ssm"]:
        raise ValueError("falcon_h1: mamba_d_ssm is mamba_n_heads heads of "
                         "mamba_d_head")
    gate, down = hf["mlp_multipliers"]
    return falcon_h1(
        "tiny", n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"], d_model=hf["hidden_size"],
        qk_head_dim=hf["head_dim"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], max_seq=hf["max_position_embeddings"],
        norm_eps=hf["rms_norm_eps"], rope_theta=float(hf["rope_theta"]),
        ssm_heads=heads, ssm_head_dim=hd, ssm_groups=hf["mamba_n_groups"],
        ssm_state=hf["mamba_d_state"], ssm_conv=hf["mamba_d_conv"],
        ssm_chunk=hf["mamba_chunk_size"],
        mup=MuP(embed=float(hf["embedding_multiplier"]),
                head=float(hf["lm_head_multiplier"]),
                attn_in=float(hf["attention_in_multiplier"]),
                attn_out=float(hf["attention_out_multiplier"]),
                key=float(hf["key_multiplier"]),
                ssm_in=float(hf["ssm_in_multiplier"]),
                ssm_out=float(hf["ssm_out_multiplier"]),
                ssm=tuple(float(v) for v in hf["ssm_multipliers"]),
                mlp_gate=float(gate), mlp_down=float(down)))


def _falcon_h1_convert(sd: _SDict, cfg: TransformerConfig) -> dict:
    raise NotImplementedError(
        "falcon_h1: config.json maps to the native configuration "
        "(config_from_hf), the checkpoint's tensors do not yet: the names "
        "and layouts of its weights (the mixer's fused in_proj and its "
        "column order, the conv's taps, whether a multiplier is already "
        "folded into a stored matrix) are not in config.json and were not "
        "to hand when the family was written; a guessed key map would load "
        "a different model under this one's name")


_FAMILIES: dict[str, tuple[Callable, Callable, tuple[str, ...]]] = {
    # model_type → (config_fn, convert_fn, state-dict prefixes to strip)
    "gpt2": (_gpt2_config, _gpt2_convert, ("transformer.",)),
    "llama": (_llama_config, _llama_convert, ("model.",)),
    "internlm": (_internlm_config, _internlm_convert, ("model.",)),
    "mistral": (_llama_config, _llama_convert, ("model.",)),
    "mixtral": (_llama_config, _llama_convert, ("model.",)),
    "opt": (_opt_config, _opt_convert, ("model.decoder.", "decoder.")),
    "gptj": (_gptj_config, _gptj_convert, ("transformer.",)),
    "gpt_neo": (_gptneo_config, _gptneo_convert, ("transformer.",)),
    "gpt_neox": (_neox_config, _neox_convert, ("gpt_neox.",)),
    "falcon": (_falcon_config, _falcon_convert, ("transformer.",)),
    "bloom": (_bloom_config, _bloom_convert, ("transformer.",)),
    "qwen2": (_qwen2_config, _qwen2_convert, ("model.",)),
    "phi": (_phi_config, _phi_convert, ("model.",)),
    # CodeGen is a GPT-J block family: same config mapping, own qkv split
    "codegen": (_gptj_config, _codegen_convert, ("transformer.",)),
    "gpt_bigcode": (_bigcode_config, _bigcode_convert, ("transformer.",)),
    "bert": (_bert_config, _bert_convert, ("bert.",)),
    "distilbert": (_distilbert_config, _distilbert_convert,
                   ("distilbert.",)),
    "t5": (_t5_config, _t5_convert, ()),
    "clip": (_clip_config, _clip_convert, ("text_model.",)),
    "clip_text_model": (_clip_config, _clip_convert, ("text_model.",)),
    "megatron_gpt": (_megatron_config, _megatron_convert,
                     ("model.language_model.", "language_model.")),
    "megatron_gpt_moe": (_megatron_moe_config, _megatron_moe_convert,
                         ("model.language_model.", "language_model.")),
    # the configuration alone: the converter refuses with its reason
    "mimo_v2_flash": (_mimo_v2_config, _mimo_v2_convert, ("model.",)),
    "zaya": (_zaya_config, _zaya_convert, ("model.",)),
    "falcon_h1": (_falcon_h1_config, _falcon_h1_convert, ("model.",)),
    "glm_moe_dsa": (_glm_moe_dsa_config, _glm_moe_dsa_convert, ("model.",)),
}


def _detect_family(state_dict: Dict[str, Any]) -> str:
    keys = state_dict.keys()
    for k in keys:
        if "attn.c_attn.weight" in k:
            # gpt2 and gpt_bigcode share every key NAME; only the fused-qkv
            # shape tells them apart (Conv1D (d, 3d) vs Linear (d+2hd, d))
            shape = tuple(state_dict[k].shape)
            return "gpt2" if shape[1] == 3 * shape[0] else "gpt_bigcode"
    if any("block_sparse_moe" in k for k in keys):
        return "mixtral"
    if any("decoder.layers" in k and "fc1" in k for k in keys):
        return "opt"
    if any("attn.qkv_proj" in k for k in keys):
        return "codegen"
    if any("attn.attention.q_proj" in k for k in keys):
        return "gpt_neo"
    if any("mlp.fc_in" in k for k in keys):
        return "gptj"

    if any("self_attn.dense" in k for k in keys) and \
            any("mlp.fc1" in k for k in keys):
        return "phi"
    if any("self_attn.q_proj.bias" in k for k in keys) and \
            any("mlp.gate_proj" in k for k in keys):
        # qwen2 biases q/k/v only; internlm v1 also biases o_proj
        return ("internlm"
                if any("self_attn.o_proj.bias" in k for k in keys)
                else "qwen2")
    if any("language_model" in k for k in keys) and \
            any("self_attention.query_key_value" in k for k in keys):
        # both anchors: multimodal HF checkpoints (LLaVA-style) also prefix
        # llama-layout keys with "language_model."
        return ("megatron_gpt_moe"
                if any("deepspeed_moe" in k for k in keys)
                else "megatron_gpt")
    if any("gpt_neox" in k or "embed_in" in k for k in keys):
        return "gpt_neox"
    if any("word_embeddings_layernorm" in k for k in keys):
        return "bloom"
    if any("self_attention.query_key_value" in k for k in keys):
        return "falcon"
    if any("EncDecAttention" in k for k in keys):
        return "t5"
    if any("attention.self.query" in k for k in keys):
        return "bert"
    if any("attention.q_lin" in k for k in keys):
        return "distilbert"
    if any("token_embedding" in k for k in keys) and \
            any("layer_norm1" in k for k in keys):
        return "clip_text_model"
    if any("self_attn.q_proj" in k for k in keys):
        return "llama"
    raise ValueError("cannot detect model family from checkpoint keys; "
                     f"sample: {sorted(keys)[:8]}")


# ------------------------------------------------------------- public entry
def config_from_hf(hf_config: dict) -> TransformerConfig:
    """HF ``config.json`` dict → native :class:`TransformerConfig`."""
    family = hf_config.get("model_type")
    if family not in _FAMILIES:
        raise ValueError(f"unsupported model_type {family!r}; "
                         f"supported: {sorted(_FAMILIES)}")
    return _FAMILIES[family][0](hf_config)


def import_state_dict(state_dict: Dict[str, Any],
                      config: TransformerConfig | None = None,
                      family: str | None = None,
                      hf_config: dict | None = None) -> Tuple[TransformerConfig, dict]:
    """Convert an HF-format state dict (torch/numpy tensors) into the native
    param pytree. Returns ``(config, params)`` with fp32 numpy leaves
    (the engine/inference cast to compute dtype and shard on device_put)."""
    family = family or (hf_config or {}).get("model_type") or _detect_family(state_dict)
    if family not in _FAMILIES:
        raise ValueError(f"unsupported model family {family!r}")
    if family == "mixtral":
        # Static-capacity routing can drop over-capacity tokens that HF's
        # dropless top-k would route; raise the factor for serving fidelity
        # (still overridable via a caller-supplied config).
        log_dist("importer: mixtral uses static-capacity expert routing — "
                 "over-capacity tokens are dropped; raise "
                 "moe_capacity_factor if imported outputs must match HF")
    config_fn, convert_fn, strip = _FAMILIES[family]
    if config is None:
        if hf_config is None:
            raise ValueError("need either a TransformerConfig or the HF "
                             "config.json dict to size the model")
        config = config_fn(hf_config)
    sd = _SDict(state_dict, strip=strip)
    params = convert_fn(sd, config)
    if (getattr(config, "pos_embedding", None) == "learned"
            and config.max_seq > params["pos_embed"].shape[0]):
        raise ValueError(
            f"max_seq={config.max_seq} exceeds the checkpoint's learned "
            f"position table ({params['pos_embed'].shape[0]} rows); "
            "positions past the table would silently clamp")
    leftovers = [k for k in sd.unused()
                 if not k.endswith((
                     "rotary_emb.inv_freq", "attn.bias", "attn.masked_bias",
                     # GPT-Neo nests the causal-mask buffers one level deeper
                     "attention.bias", "attention.masked_bias",
                     "lm_head.weight",
                     # tied-decoder duplicates + buffers (BERT/DistilBERT)
                     "cls.predictions.decoder.weight",
                     "cls.predictions.decoder.bias",
                     "vocab_projector.weight", "vocab_projector.bias",
                     "embeddings.position_ids",
                     # T5 per-stack duplicates of shared.weight
                     "encoder.embed_tokens.weight",
                     "decoder.embed_tokens.weight"))]
    if leftovers:
        log_dist(f"importer: {len(leftovers)} unused checkpoint keys "
                 f"(first 5: {leftovers[:5]})")
    return config, params


def _load_files(path: str) -> Dict[str, Any]:
    """Load all weight shards under an HF checkpoint directory."""
    def _safetensors(fp):
        import jax

        try:  # bf16-capable path — pinned to host so shards never touch HBM
            from safetensors.flax import load_file as lf
            with jax.default_device(jax.devices("cpu")[0]):
                return dict(lf(fp))
        except Exception:
            from safetensors.torch import load_file as lf
            return dict(lf(fp))

    candidates = [
        ("model.safetensors.index.json", _safetensors, "model.safetensors"),
        ("pytorch_model.bin.index.json", None, "pytorch_model.bin"),
    ]
    for index_name, loader, single_name in candidates:
        index_fp = os.path.join(path, index_name)
        single_fp = os.path.join(path, single_name)
        if loader is None:
            import torch

            def loader(fp):
                return torch.load(fp, map_location="cpu", weights_only=True)
        if os.path.exists(index_fp):
            with open(index_fp) as f:
                index = json.load(f)
            sd: Dict[str, Any] = {}
            for shard in sorted(set(index["weight_map"].values())):
                sd.update(loader(os.path.join(path, shard)))
            return sd
        if os.path.exists(single_fp):
            return loader(single_fp)
    raise FileNotFoundError(f"no model.safetensors / pytorch_model.bin under {path}")


def load_hf_checkpoint(path: str,
                       config: TransformerConfig | None = None,
                       **overrides) -> Tuple[TransformerConfig, dict]:
    """Load an HF checkpoint directory (config.json + safetensors/bin shards)
    onto the native trunk.

    >>> cfg, params = load_hf_checkpoint("/ckpts/llama-2-7b")
    >>> engine = ds.initialize(ds_config, build_model(cfg), params=params)

    ``overrides`` are applied to the derived TransformerConfig (e.g.
    ``max_seq=8192`` to serve longer than the checkpoint's default)."""
    hf_config = None
    cfg_fp = os.path.join(path, "config.json")
    if os.path.exists(cfg_fp):
        with open(cfg_fp) as f:
            hf_config = json.load(f)
    sd = _load_files(path)
    cfg, params = import_state_dict(sd, config=config, hf_config=hf_config)
    if overrides:
        # type(cfg): works for TransformerConfig AND T5Config alike
        cfg = type(cfg)(**{**cfg.__dict__, **overrides})
        if (getattr(cfg, "pos_embedding", None) == "learned"
                and cfg.max_seq > params["pos_embed"].shape[0]):
            # same guard as import_state_dict, re-checked post-override
            raise ValueError(
                f"max_seq={cfg.max_seq} exceeds the checkpoint's learned "
                f"position table ({params['pos_embed'].shape[0]} rows); "
                "positions past the table would silently clamp")
    n = sum(int(np.prod(p.shape)) for p in
            __import__("jax").tree.leaves(params))
    log_dist(f"importer: loaded {n / 1e6:.1f}M params from {path} "
             f"({hf_config.get('model_type') if hf_config else 'detected'})")
    return cfg, params

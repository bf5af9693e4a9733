"""GPT-2 family: from a configuration file's published keys to the repo's
model, and the operations its training needs per token."""

from __future__ import annotations


def model_config(published: dict, compute_dtype: str):
    """The repo's ``TransformerConfig`` for a GPT-2 ``config.json``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import gpt2

    if published["activation_function"] != "gelu_new":
        raise ValueError("GPT-2 uses gelu_new")
    return gpt2(
        "125m", n_layer=published["n_layer"], n_head=published["n_head"],
        d_model=published["n_embd"], d_ff=published.get("n_inner"),
        vocab_size=published["vocab_size"],
        max_seq=published["n_positions"],
        norm_eps=published["layer_norm_epsilon"],
        tie_embeddings=published["tie_word_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[compute_dtype])


def build(published: dict, compute_dtype: str, flash_attention: bool):
    from deepspeed_tpu.models import build_model

    cfg = model_config(published, compute_dtype)
    attn = None
    if flash_attention:
        from deepspeed_tpu.ops.flash_attention import make_flash_attention

        attn = make_flash_attention()
    return cfg, build_model(cfg, attention_fn=attn)


def matmul_params(published: dict) -> int:
    """Parameters that a token is multiplied by: the layers' four attention
    projections and two MLP matrices, and the tied embedding once, as the
    output head. Biases, norms and the position table are left out, and the
    embedding lookup is a gather."""
    d, L = published["n_embd"], published["n_layer"]
    f = published.get("n_inner") or 4 * d
    return L * (4 * d * d + 2 * d * f) + published["vocab_size"] * d


def train_flops_per_token(published: dict, seq_len: int) -> float:
    """Forward + backward FLOPs a token of a ``seq_len`` sequence needs:
    6 per multiplied parameter, and 12 * L * d * S for the attention scores
    and values (the usual MFU convention, PaLM appendix B: the causal mask's
    saving is not taken off, recomputation is not added)."""
    d, L = published["n_embd"], published["n_layer"]
    return 6.0 * matmul_params(published) + 12.0 * L * d * seq_len


"""What one call of ``cca_decode_attention`` needs (``decode_attention`` over
the planes of compressed convolutional attention: the whole attention runs in
the latent, so K and V are ``num_key_value_heads`` heads of ``head_dim``
whatever the model's width): one query row a running slot and query head
against that slot's live keys and values, the step's new column appended in
place.

Counted by KV heads, as ``full_decode_attention`` counts
(``benchmark/kernels/decode_attention.py`` counts K/V by ``n_head``): the live
K and V once, the block of 128 positions written back for every running
slot, q and o; FLOPs 2 H (head_dim + head_dim) a live position. The lengths
are the program's own (``live_positions`` and ``slots`` of its
``decode_step`` spans); a program whose spans carry none (any parent of
PR 44) has nothing to read.
"""

from __future__ import annotations

from .full_decode_attention import ops_and_bytes, step_means


def calls(facts: dict) -> dict:
    m = facts["model"]
    live, running = step_means("live_positions")
    if live is None or "cca_time0" not in m:
        return {}
    return {"cca_decode_attention": ops_and_bytes(
        live=live, running=running, heads=m["num_attention_heads"],
        kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        v_dim=m["head_dim"])}

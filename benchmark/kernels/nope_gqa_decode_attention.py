"""What one call of ``nope_gqa_decode_attention`` needs (``decode_attention``
over the attention layer's planes of a trunk of delta-rule mixers beside
gated GQA layers: 64 query heads over 8 KV heads of 128, eight query rows a
KV head, no position code, slots of tens of thousands of positions): one
query row a running slot and query head against that slot's live keys and
values, the step's new column appended in place.

Counted by KV heads, as ``full_decode_attention`` counts: the live K and V
once, the block of 128 positions written back for every running slot, q and
o; FLOPs 2 H (head_dim + head_dim) a live position. The lengths are the
program's own (``live_positions`` and ``slots`` of its ``decode_step``
spans): the count reads the same work whatever implements it. A program
whose spans carry none, or a family without the gate's key (any parent of
PR 57), has nothing to read.
"""

from __future__ import annotations

from .full_decode_attention import ops_and_bytes, step_means


def calls(facts: dict) -> dict:
    m = facts["model"]
    live, running = step_means("live_positions")
    if live is None or "use_gqa_gate" not in m:
        return {}
    return {"nope_gqa_decode_attention": ops_and_bytes(
        live=live, running=running, heads=m["num_attention_heads"],
        kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        v_dim=m["head_dim"])}

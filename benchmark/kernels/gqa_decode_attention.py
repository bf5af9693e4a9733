"""What one call of ``gqa_decode_attention`` needs (``decode_attention`` over
a layer's planes of a trunk whose every layer runs attention beside a Mamba-2
mixer: 20 query heads over 4 KV heads of 128, five query rows a KV head): one
query row a running slot and query head against that slot's live keys and
values, the step's new column appended in place.

Counted by KV heads, as ``full_decode_attention`` counts
(``benchmark/kernels/decode_attention.py`` counts K/V by ``n_head``, five
times this model's, and would read over 100%): the live K and V once, the
block of 128 positions written back for every running slot, q and o; FLOPs
2 H (head_dim + head_dim) a live position. The lengths are the program's own
(``live_positions`` and ``slots`` of its ``decode_step`` spans); a program
whose spans carry none, or a family without the side-by-side mixers' key
(any parent of PR 48), has nothing to read.
"""

from __future__ import annotations

from .full_decode_attention import ops_and_bytes, step_means


def calls(facts: dict) -> dict:
    m = facts["model"]
    live, running = step_means("live_positions")
    if live is None or "ssm_out_multiplier" not in m:
        return {}
    return {"gqa_decode_attention": ops_and_bytes(
        live=live, running=running, heads=m["num_attention_heads"],
        kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        v_dim=m["head_dim"])}

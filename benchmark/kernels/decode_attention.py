"""What one call of ``decode_attention`` needs: one query row per slot and
head against that slot's live keys and values.

FLOPs: scores and values, 2 matmuls of 1 x live x hd per head. Bytes: the
live K and V once (what has to come from HBM; the padding behind the live
length does not), plus q and o. The kernel is called once per layer per
decode step, so a step's call is given the step's live tokens summed over
slots; slots that are empty hold length 0 and need nothing.
"""

from __future__ import annotations


def ops_and_bytes(*, live_tokens: int, slots: int, heads: int, head_dim: int,
                  bytes_per_value: int = 2) -> tuple:
    flops = 2 * 2.0 * live_tokens * heads * head_dim
    nbytes = (2 * live_tokens * heads * head_dim
              + 2 * slots * heads * head_dim) * bytes_per_value
    return flops, float(nbytes)


def calls(facts: dict) -> dict:
    """(flops, bytes) of the mean call over the traced decode steps."""
    m = facts["model"]
    live = facts["decode_live_tokens"]          # one entry per traced step
    if not live:
        return {}
    return {"decode_attention": ops_and_bytes(
        live_tokens=sum(live) / len(live), slots=facts["slots"],
        heads=m["n_head"], head_dim=m["n_embd"] // m["n_head"])}

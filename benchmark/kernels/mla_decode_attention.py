"""What one call of the latent decode step's two kernels needs.

``mla_decode_attention``: a slot's H absorbed query rows against that slot's
live latents. FLOPs: scores over rank + rope values and the weighted sum
over rank values, 2 * live * H * ((rank + rope) + rank). Bytes: the live
latents ONCE (all heads share them; what lies behind the live length is
not needed), plus q and o. One call a layer a decode step, so a call is
given the step's live tokens summed over slots.

``mla_cache_append``: no FLOPs; per slot the one tile of 128 positions that
holds the new one is read and written whole (the least a read-modify-write
of a lane-tiled buffer can move), plus the new values.
"""

from __future__ import annotations


def ops_and_bytes(*, live_tokens: float, slots: int, heads: int, rank: int,
                  rope: int, bytes_per_value: int = 2) -> tuple:
    width = rank + rope
    flops = 2.0 * live_tokens * heads * (width + rank)
    nbytes = (live_tokens * width + slots * heads * (width + rank)) \
        * bytes_per_value
    return flops, float(nbytes)


def append_bytes(*, slots: int, rank: int, rope: int,
                 bytes_per_value: int = 2) -> float:
    return float(slots * (rank + rope) * (2 * 128 + 1) * bytes_per_value)


def calls(facts: dict) -> dict:
    """(flops, bytes) of the mean call over the traced decode steps."""
    m = facts["model"]
    live = facts.get("decode_live_tokens")
    if not live or "kv_lora_rank" not in m:
        return {}
    rank, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return {
        "mla_decode_attention": ops_and_bytes(
            live_tokens=sum(live) / len(live), slots=facts["slots"],
            heads=m["num_attention_heads"], rank=rank, rope=rope),
        "mla_cache_append": (0.0, append_bytes(
            slots=facts["slots"], rank=rank, rope=rope))}

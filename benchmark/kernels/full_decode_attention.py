"""What one call of ``full_decode_attention`` needs (``decode_attention``
over a full layer's planes of a trunk of window layers beside full ones, keys
``head_dim`` wide over values of ``v_head_dim``): one query row a running
slot and head against that slot's live keys and values, the step's new
column appended in place.

Bytes: the live K and V of the layer's KV heads once (what has to come from
HBM; the padding behind the live length does not), the block of 128
positions written back for every running slot, q and o. FLOPs: scores and
values, 2 H (head_dim + v_head_dim) a live position. The lengths are the
program's own (``live_positions`` and ``slots`` of its ``decode_step``
spans); a program whose spans carry none (any parent of PR 42) has nothing
to read.
"""

from __future__ import annotations

BLOCK = 128


def ops_and_bytes(*, live: float, running: float, heads: int, kv_heads: int,
                  head_dim: int, v_dim: int, bytes_per_value: int = 2):
    width = head_dim + v_dim
    flops = 2.0 * live * heads * width
    nbytes = (live * kv_heads * width + running * kv_heads * width * BLOCK
              + running * heads * width) * bytes_per_value
    return flops, float(nbytes)


def step_means(key: str) -> tuple:
    """(mean of meta ``key``, mean running slots) over the traced decode
    steps that carry ``key``; (None, None) where none does."""
    from ..reducers.program_span import _captured

    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and key in e.meta]
    if not steps:
        return None, None
    return (sum(e.meta[key] for e in steps) / len(steps),
            sum(e.meta["slots"] for e in steps) / len(steps))


def calls(facts: dict) -> dict:
    m = facts["model"]
    live, running = step_means("live_positions")
    if live is None or "v_head_dim" not in m:
        return {}
    return {"full_decode_attention": ops_and_bytes(
        live=live, running=running, heads=m["num_attention_heads"],
        kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        v_dim=m["v_head_dim"])}

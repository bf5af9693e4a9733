"""What one call of ``window_decode_attention`` needs (``decode_attention``
over a window layer's ring: a running slot's last ``sliding_window``
positions, a sink logit a head in the sum, the step's new column appended in
place).

Bytes: K and V of the positions INSIDE the running slots' windows
(min(length, window) each: what has to come from HBM; the rest of the one or
two ring blocks the kernel fetches does not count), the block of 128
positions written back for every running slot, q and o; the sink's scalars
are free. FLOPs: 2 H (head_dim + v_head_dim) a position in a window. The
lengths are the program's own (``window_live`` and ``slots`` of its
``decode_step`` spans); a program whose spans carry none (any parent of
PR 42) has nothing to read.
"""

from __future__ import annotations

from .full_decode_attention import ops_and_bytes, step_means


def calls(facts: dict) -> dict:
    m = facts["model"]
    live, running = step_means("window_live")
    if live is None or "swa_num_key_value_heads" not in m:
        return {}
    return {"window_decode_attention": ops_and_bytes(
        live=live, running=running, heads=m["num_attention_heads"],
        kv_heads=m["swa_num_key_value_heads"], head_dim=m["head_dim"],
        v_dim=m["v_head_dim"])}

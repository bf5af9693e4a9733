"""What one call of the KDA state step needs (``kda_state_step``,
``deepspeed_tpu/ops/kda_step.py``: one layer's delta-rule state of every
running slot, once in and once out, in place).

Bytes: the running slots' float32 state ``H x D x D`` read and written; q,
k, v and the decay in and o out (``H x D`` float32 each) and beta (``H``). A
slot that is not running moves nothing (the kernel borrows a neighbour's
block), so the count is over the requests running at dispatch (``slots`` of
the program's ``decode_step`` spans). FLOPs: about eight a state value (the
decay, k^T S, the outer product and its sum, S^T q): bound by memory by two
orders of magnitude. The count is of what the step has to move, whatever
implements it. A program whose spans carry no ``state_bytes_per_slot``, or a
model with no ``linear_attn_config`` (every other family), has nothing to
read.
"""

from __future__ import annotations


def ops_and_bytes(*, running: float, H: int, D: int) -> dict:
    state = H * D * D * 4
    side = (5 * H * D + H) * 4
    return {"kda_state_step": (8.0 * running * H * D * D,
                               running * (2 * state + side))}


def calls(facts: dict) -> dict:
    from ..reducers.program_span import _captured

    lin = facts["model"].get("linear_attn_config")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "state_bytes_per_slot" in e.meta]
    if not lin or not steps:
        return {}
    running = sum(e.meta["slots"] for e in steps) / len(steps)
    return ops_and_bytes(running=running, H=lin["num_heads"],
                         D=lin["head_dim"])

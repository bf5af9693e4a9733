"""What one call of the Mamba-2 state step needs (``ssm_state_step``,
``deepspeed_tpu/ops/ssm_step.py``: one layer's state of every running slot,
once in and once out, in place).

Bytes: the running slots' float32 state ``H x P x N`` read and written; the
packed columns in and y out, a lane tile of ``P x 128`` float32 a group each;
B and C. A slot that is not running moves nothing (the kernel borrows a
neighbour's block), so the count is over the requests running at dispatch
(``slots`` of the program's ``decode_step`` spans). FLOPs: five a state
value (decay, outer product, sum, times C, reduce): bound by memory by two
orders of magnitude. A program whose spans carry no ``state_bytes_per_slot``
(any parent of PR 37) has nothing to read.
"""

from __future__ import annotations


def ops_and_bytes(*, running: float, H: int, P: int, N: int, G: int) -> dict:
    state = H * P * N * 4
    side = G * (2 * P * 128 + 2 * N) * 4
    return {"ssm_state_step": (5.0 * running * H * P * N,
                               running * (2 * state + side))}


def calls(facts: dict) -> dict:
    from ..reducers.program_span import _captured

    m = facts["model"]
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "state_bytes_per_slot" in e.meta]
    if "mamba_num_heads" not in m or not steps:
        return {}
    running = sum(e.meta["slots"] for e in steps) / len(steps)
    return ops_and_bytes(running=running, H=m["mamba_num_heads"],
                         P=m["mamba_head_dim"], N=m["ssm_state_size"],
                         G=m["n_groups"])

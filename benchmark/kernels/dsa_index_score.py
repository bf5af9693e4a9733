"""What one call of the indexer's score needs (``dsa_index_score``,
``deepspeed_tpu/ops/sparse_mla_attention.py``).

A slot's ``index_n_heads`` indexer queries against every LIVE indexer key of
that slot: FLOPs 2 * live * heads * index_head_dim (the ReLU and the
weighted sum over heads are not counted); bytes: the live keys once
(index_head_dim x 2 B a position), the scores out (4 B a position of the
whole cache: what lies behind the live length is written as -inf), q and w.
One call a ``full`` layer a decode step, given the step's live positions
summed over the running slots (meta ``dsa_live`` of the program's
``decode_step`` spans).
"""

from __future__ import annotations

from .sparse_mla_decode_attention import _steps


def ops_and_bytes(*, live: float, slots: int, max_len: int, heads: int,
                  width: int, bytes_per_value: int = 2) -> tuple:
    flops = 2.0 * live * heads * width
    nbytes = live * width * bytes_per_value + slots * max_len * 4 \
        + slots * heads * (width * bytes_per_value + 4)
    return flops, float(nbytes)


def calls(facts: dict) -> dict:
    m = facts["model"]
    steps = [e for e in _steps() if "dsa_live" in e.meta]
    if not steps or "index_head_dim" not in m:
        return {}
    live = sum(e.meta["dsa_live"] for e in steps) / len(steps)
    return {"dsa_index_score": ops_and_bytes(
        live=live, slots=facts["slots"], max_len=facts["seq_len"],
        heads=m["index_n_heads"], width=m["index_head_dim"])}

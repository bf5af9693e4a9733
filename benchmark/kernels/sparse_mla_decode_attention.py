"""What one call of the selected latent read needs
(``sparse_mla_decode_attention``, ``deepspeed_tpu/ops/sparse_mla_attention.py``).

A slot's H absorbed query rows against the latents of the positions its
layer's selection holds, and nothing else of the cache. FLOPs: scores over
rank + rope values and the weighted sum over rank values, 2 * selected * H *
((rank + rope) + rank). Bytes: the SELECTED positions' latents once (all
heads share them) at the bytes their values take — (rank + rope) x 2, 1152 B
a position: the row's padding to whole tiles is the layout's cost, not work
the step needs, and ``dsa.fetched_over_selected`` reports it — plus q and o
and the appended position a slot. One call a layer a decode step, so a call
is given the step's selected positions summed over the running slots (meta
``dsa_selected`` of the program's ``decode_step`` spans, a host count from
the mirror of the slots' lengths: min(length, index_topk) a running slot).
"""

from __future__ import annotations


def _steps() -> list:
    """The program's closed ``decode_step`` spans of the capture that carry
    the count (none from a program that keeps none)."""
    from ..reducers.program_span import _captured

    return [e for e in _captured() if e.t1 is not None
            and e.kind == "decode_step" and "dsa_selected" in e.meta]


def ops_and_bytes(*, selected: float, slots: int, heads: int, rank: int,
                  rope: int, bytes_per_value: int = 2) -> tuple:
    width = rank + rope
    flops = 2.0 * selected * heads * (width + rank)
    nbytes = (selected * width + slots * (heads * (width + rank) + width)) \
        * bytes_per_value
    return flops, float(nbytes)


def calls(facts: dict) -> dict:
    """(flops, bytes) of the mean call over the traced decode steps."""
    m, steps = facts["model"], _steps()
    if not steps or "index_topk" not in m:
        return {}
    selected = sum(e.meta["dsa_selected"] for e in steps) / len(steps)
    return {"sparse_mla_decode_attention": ops_and_bytes(
        selected=selected, slots=facts["slots"],
        heads=m["num_attention_heads"], rank=m["kv_lora_rank"],
        rope=m["qk_rope_head_dim"])}

"""What one call of the grouped expert product needs (``moe_experts_up``:
gate and up projections with the SwiGLU between them; ``moe_experts_down``).

FLOPs: the rows ROUTED (tokens x experts per token; padding is not needed
work) through d x f, twice for up. Bytes: the weights of the experts the
call's tokens touched, once, plus the routed rows in and out. The decode
step (slots tokens) and the prefill chunk (its size) call the same kernels,
so a trace holds two populations: the mean call weighs them by how many
``decode_step`` and ``prefill_chunk`` spans the program recorded during the
capture. Experts touched in a decode step is the program's own count (meta
``experts_touched`` of its ``decode_step`` spans); for a chunk of n tokens it
is reckoned, E * (1 - (1 - k/E)^n): all 128 at 512 tokens.
"""

from __future__ import annotations


def _spans() -> list:
    """The program's closed spans of the capture (none from a program that
    keeps none: ``benchmark/reducers/program_span.py``)."""
    from ..reducers.program_span import _captured

    return [e for e in _captured() if e.t1 is not None]


def ops_and_bytes(*, rows: float, touched: float, d: int, f: int,
                  bytes_per_value: int = 2) -> dict:
    io = rows * (d + f) * bytes_per_value
    return {"moe_experts_up": (2.0 * rows * d * f * 2,
                               touched * 2 * d * f * bytes_per_value + io),
            "moe_experts_down": (2.0 * rows * f * d,
                                 touched * f * d * bytes_per_value + io)}


def calls(facts: dict) -> dict:
    m = facts["model"]
    if "n_routed_experts" not in m:
        return {}
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    spans = _spans()
    steps = [e for e in spans if e.kind == "decode_step"
             and "experts_touched" in e.meta]
    chunks = [e for e in spans if e.kind == "prefill_chunk"]
    if not steps:
        return {}
    groups = [(len(steps), facts["slots"] * k,
               sum(e.meta["experts_touched"] for e in steps) / len(steps))]
    for size in {e.meta.get("size") for e in chunks} - {None}:
        n = sum(e.meta.get("size") == size for e in chunks)
        groups.append((n, size * k, E * (1 - (1 - k / E) ** size)))
    total = sum(n for n, _, _ in groups)
    out: dict = {}
    for n, rows, touched in groups:
        for name, (fl, by) in ops_and_bytes(rows=rows, touched=touched,
                                            d=d, f=f).items():
            a, b = out.get(name, (0.0, 0.0))
            out[name] = (a + fl * n / total, b + by * n / total)
    return out

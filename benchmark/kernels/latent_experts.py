"""What one call of the latent experts' grouped product needs
(``latent_experts_up``: ONE up projection with relu^2 behind it, at the
latent width; ``latent_experts_down``; ``deepspeed_tpu/ops/moe_matmul.py``
``experts_relu2``) — not ``moe_experts``' count, which is gate + up at the
model width.

FLOPs: the rows that chose an expert HELD here (padding is not needed work)
through ``latent x f``, once each way. Bytes: the weights of the held
experts the call's rows touched, once, plus those rows in and out. The
decode step and the prefill chunk call the same kernels, so a trace holds
two populations; both counts are the program's own, a layer's mean
(``held_rows`` and ``experts_touched`` of its ``decode_step`` and
``prefill_chunk`` spans), and the mean call weighs the populations by how
many spans of each carry them. A program without them has nothing to read.
"""

from __future__ import annotations


def ops_and_bytes(*, rows: float, touched: float, lat: int, f: int,
                  bytes_per_value: int = 2) -> dict:
    io = rows * (lat + f) * bytes_per_value
    weights = touched * lat * f * bytes_per_value
    return {"latent_experts_up": (2.0 * rows * lat * f, weights + io),
            "latent_experts_down": (2.0 * rows * f * lat, weights + io)}


def calls(facts: dict) -> dict:
    from ..reducers.program_span import _captured

    m = facts["model"]
    spans = [e for e in _captured() if e.t1 is not None
             and e.kind in ("decode_step", "prefill_chunk")
             and "held_rows" in e.meta and "experts_touched" in e.meta]
    if "moe_latent_size" not in m or not spans:
        return {}
    out: dict = {}
    for e in spans:
        for name, (fl, by) in ops_and_bytes(
                rows=e.meta["held_rows"], touched=e.meta["experts_touched"],
                lat=m["moe_latent_size"],
                f=m["moe_intermediate_size"]).items():
            a, b = out.get(name, (0.0, 0.0))
            out[name] = (a + fl / len(spans), b + by / len(spans))
    return out

"""The decode step of a trunk of window layers beside full ones against the
memory it has to move: the least time the chip's HBM needs for what a traced
step reads and writes — the weights outside the routed experts (every
layer's attention by kind, the dense FFN, the routers), the head's slice, the
held experts the step touched (the program's ``experts_touched``, a layer's
mean, x the expert layers), the live K/V of the full layers (the program's
``live_positions`` x ``cache_bytes_per_token``), the positions inside the
running slots' windows (``window_live``: min(length, window) a slot, a window
layer each), and the block of 128 positions every running slot writes back
in every layer — over the step program's median device time, in %. The step
moves at least this, so it reads under 100: the cell's share of the whole
step. A family whose module has no ``layer_params`` with a
``window_attention``, or a program whose ``decode_step`` spans carry no
``window_live`` (any parent of PR 42), has nothing to read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured

BLOCK = 128


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "window_live" in e.meta
             and "experts_touched" in e.meta and "held_rows_share" in e.meta]
    if not hasattr(fam, "layer_params") or not steps:
        return None
    m = facts["model"]
    n = fam.layer_params(m)
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if "window_attention" not in n or not took_ms:
        return None
    kinds = list(zip(m["hybrid_layer_pattern"], m["moe_layer_freq"]))
    window = sum(w for w, _ in kinds)
    full = len(kinds) - window
    routed = sum(f for _, f in kinds)
    mean = lambda key: sum(e.meta[key] for e in steps) / len(steps)  # noqa: E731
    width = (m["head_dim"] + m["v_head_dim"]) * bytes_per_value
    per_full, per_window = (m["num_key_value_heads"] * width,
                            m["swa_num_key_value_heads"] * width)
    other = (full * n["full_attention"] + window * n["window_attention"]
             + (len(kinds) - routed) * n["dense"] + routed * n["router"]) \
        * bytes_per_value
    head = n["head"] * bytes_per_value
    experts = routed * mean("experts_touched") * n["expert"] * bytes_per_value
    kv = mean("live_positions") * full * per_full
    rings = mean("window_live") * window * per_window
    written = mean("slots") * BLOCK * (full * per_full + window * per_window)
    least_s = (other + head + experts + kv + rings + written) \
        / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        f"mixed decode step's least traffic: weights outside the routed "
        f"experts {other / 1e9:.3f} GB, the head {head / 1e9:.3f} GB, held "
        f"experts touched {experts / 1e9:.3f} GB, live full-layer K/V "
        f"{kv / 1e9:.3f} GB, the positions inside the windows "
        f"{rings / 1e9:.3f} GB, the blocks written back "
        f"{written / 1e9:.3f} GB -> {1e3 * least_s:.3f} ms at the chip's HBM "
        f"peak, against {took_ms:.3f} ms; experts touched a layer "
        f"{mean('experts_touched'):.2f}, held_rows_share "
        f"{mean('held_rows_share'):.4f} (rows that chose an expert held "
        f"here over slots x experts per token)")
    return 100.0 * 1e3 * least_s / took_ms

"""The decode step of a trunk of delta-rule mixers beside gated GQA layers
against the memory it has to move: the least time the chip's HBM needs for
what a traced step reads and writes — the weights outside the routed experts
(every KDA mixer, the attention layers' projections and output gate, the
routers and shared experts), the head's slice, the held experts the step
touched (the program's ``experts_touched``, a layer's mean, x the layers),
TWICE the running slots' recurrent state (once in, once out:
``state_bytes_per_slot``) and the live K and V (``live_positions`` x
``cache_bytes_per_token``: the attention layers read every live key and
value) — over the step program's median device time, in %. The step moves at
least this, so it reads under 100. A family whose module has no
``layer_params`` with a ``kda``, or a program whose ``decode_step`` spans
carry no ``kv_share_of_step_bytes`` (any parent of PR 57), has nothing to
read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured


def terms(n: dict, k: dict, *, touched: float, running: float,
          state_bytes: float, live: float, token_bytes: float,
          bytes_per_value: int = 2) -> dict:
    """Each term of a step's least traffic, in bytes."""
    return {
        "weights outside the routed experts": (
            k["kda"] * n["kda"] + k["attention"] * n["attention"]
            + k["routed"] * (n["router"] + n["shared"])) * bytes_per_value,
        "the head": n["head"] * bytes_per_value,
        "held experts touched": k["routed"] * touched * n["expert"]
        * bytes_per_value,
        "the running slots' state in and out": 2 * running * state_bytes,
        "the live K and V": live * token_bytes}


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "kv_share_of_step_bytes" in e.meta
             and "experts_touched" in e.meta
             and "state_bytes_per_slot" in e.meta]
    if not hasattr(fam, "layer_params") or not steps:
        return None
    m = facts["model"]
    n = fam.layer_params(m)
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if "kda" not in n or not took_ms:
        return None
    mean = lambda key: sum(e.meta[key] for e in steps) / len(steps)  # noqa: E731
    parts = terms(n, fam.kinds(m), touched=mean("experts_touched"),
                  running=mean("slots"),
                  state_bytes=steps[-1].meta["state_bytes_per_slot"],
                  live=mean("live_positions"),
                  token_bytes=steps[-1].meta["cache_bytes_per_token"],
                  bytes_per_value=bytes_per_value)
    least_s = sum(parts.values()) / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        "delta-rule + GQA decode step's least traffic: " + ", ".join(
            f"{name} {v / 1e9:.3f} GB" for name, v in parts.items())
        + f" -> {1e3 * least_s:.3f} ms at the chip's HBM peak, against "
        f"{took_ms:.3f} ms; experts touched a layer "
        f"{mean('experts_touched'):.2f}, running slots {mean('slots'):.1f}, "
        f"live positions {mean('live_positions'):.0f}")
    return 100.0 * 1e3 * least_s / took_ms

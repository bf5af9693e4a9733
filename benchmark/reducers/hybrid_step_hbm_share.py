"""The decode step of a trunk of one mixer a layer against the memory it has
to move: the least time the chip's HBM needs for what a traced step reads
and writes — the weights outside the routed experts (every Mamba-2 and
attention layer, an expert layer's router, latent projections and shared
expert), the head's slice, the held experts the step touched (the program's
``experts_touched``, a layer's mean, x the expert layers), TWICE the running
slots' recurrent state (once in, once out: ``state_bytes_per_slot``), and
the live K/V (the benchmark's live tokens x ``cache_bytes_per_token``) — over
the step program's median device time, in %. The step moves at least this,
so it reads under 100. A family whose module has no ``layer_params`` with a
``mamba``, or a program whose ``decode_step`` spans carry no
``state_bytes_per_slot`` (any parent of PR 37), has nothing to read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "state_bytes_per_slot" in e.meta
             and "experts_touched" in e.meta]
    live = facts.get("decode_live_tokens")
    if not hasattr(fam, "layer_params") or not steps or not live:
        return None
    n = fam.layer_params(facts["model"])
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if "mamba" not in n or not took_ms:
        return None
    pattern = facts["model"]["hybrid_override_pattern"]
    kinds = {k: pattern.count(k) for k in "ME*"}
    mean = lambda key: sum(e.meta[key] for e in steps) / len(steps)  # noqa: E731
    other = (kinds["M"] * n["mamba"] + kinds["*"] * n["attention"]
             + kinds["E"] * n["experts_other"]) * bytes_per_value
    head = n["head"] * bytes_per_value
    experts = kinds["E"] * mean("experts_touched") * n["expert"] \
        * bytes_per_value
    state = 2 * mean("slots") * steps[-1].meta["state_bytes_per_slot"]
    kv = sum(live) / len(live) * steps[-1].meta["cache_bytes_per_token"]
    least_s = (other + head + experts + state + kv) \
        / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        f"hybrid decode step's least traffic: weights outside the routed "
        f"experts {other / 1e9:.3f} GB, the head {head / 1e9:.3f} GB, held "
        f"experts touched {experts / 1e9:.3f} GB, the running slots' state "
        f"in and out {state / 1e9:.3f} GB, live K/V {kv / 1e9:.3f} GB -> "
        f"{1e3 * least_s:.3f} ms at the chip's HBM peak, against "
        f"{took_ms:.3f} ms")
    return 100.0 * 1e3 * least_s / took_ms

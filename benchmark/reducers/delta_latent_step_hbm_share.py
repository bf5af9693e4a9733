"""The decode step of a trunk of delta-rule mixers beside latent attention
layers against the memory it has to move: the least time the chip's HBM needs
for what a traced step reads and writes — the weights outside the routed
experts (every KDA mixer with its full maps, the latent attention's
projections and gate, the routers and shared experts, a dense layer's FFN),
the head's slice, the held experts the step touched (the program's
``experts_touched``, a layer's mean, x the layers), TWICE the running slots'
recurrent state (once in, once out: ``state_bytes_per_slot``) and the live
latents (``live_positions`` x ``cache_bytes_per_token``: the attention layers
read every live position) — over the step program's median device time, in
%. The step moves at least this, so it reads under 100. The terms are
``delta_gqa_step_hbm_share``'s with the latents for the K and V; a family
whose ``layer_params`` has no ``kda``, or a program whose ``decode_step``
spans carry no ``held_group_token_share`` (any parent of PR 62), has nothing
to read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .delta_gqa_step_hbm_share import terms
from .program_span import _captured

LATENTS = "the live latents"


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "held_group_token_share" in e.meta
             and "experts_touched" in e.meta
             and "state_bytes_per_slot" in e.meta]
    if not hasattr(fam, "layer_params") or not steps:
        return None
    m = facts["model"]
    n, k = fam.layer_params(m), fam.kinds(m)
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if "kda" not in n or not took_ms:
        return None
    mean = lambda key: sum(e.meta[key] for e in steps) / len(steps)  # noqa: E731
    parts = terms(n, k, touched=mean("experts_touched"),
                  running=mean("slots"),
                  state_bytes=steps[-1].meta["state_bytes_per_slot"],
                  live=mean("live_positions"),
                  token_bytes=steps[-1].meta["cache_bytes_per_token"],
                  bytes_per_value=bytes_per_value)
    parts[LATENTS] = parts.pop("the live K and V")
    parts["weights outside the routed experts"] += \
        k.get("dense", 0) * n.get("dense", 0) * bytes_per_value
    least_s = sum(parts.values()) / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        "delta-rule + latent decode step's least traffic: " + ", ".join(
            f"{name} {v / 1e9:.3f} GB" for name, v in parts.items())
        + f" -> {1e3 * least_s:.3f} ms at the chip's HBM peak, against "
        f"{took_ms:.3f} ms; experts touched a layer "
        f"{mean('experts_touched'):.2f}, running slots {mean('slots'):.1f}, "
        f"live positions {mean('live_positions'):.0f}, tokens that kept "
        f"the held group {mean('held_group_token_share'):.3f}")
    return 100.0 * 1e3 * least_s / took_ms

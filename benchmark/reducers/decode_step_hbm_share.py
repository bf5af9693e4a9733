"""The decode step against the memory it has to read: the least time the
chip's HBM needs for what the traced steps had to read — every layer's
weights outside the routed experts (attention, the dense layers' MLP, the
routers, the shared experts), the output head, the routed experts the step's
tokens touched (the program's count, meta ``experts_touched`` of its
``decode_step`` spans), and the live latents (the benchmark's live tokens x
the program's ``cache_bytes_per_token``) — over the step program's median
device time, in %. A step is bound by these reads; what is left is what the
program adds to them. A family whose module has no ``layer_params``, or a
program that records no such span, has nothing to read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    if not hasattr(fam, "layer_params"):
        return None
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "experts_touched" in e.meta]
    live = facts.get("decode_live_tokens")
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if not steps or not live or not took_ms:
        return None
    p, n = facts["model"], fam.layer_params(facts["model"])
    L, k0 = p["num_hidden_layers"], p["first_k_dense_replace"]
    touched = sum(e.meta["experts_touched"] for e in steps) / len(steps)
    weights = (L * n["attention"] + k0 * n["dense"] + n["head"]
               + (L - k0) * (n["router"] + n["shared"]
                             + touched * n["expert"])) * bytes_per_value
    latents = sum(live) / len(live) * steps[-1].meta["cache_bytes_per_token"]
    least_s = (weights + latents) / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        f"decode step's least reads: weights {weights / 1e9:.3f} GB "
        f"({touched:.1f} of {p['n_routed_experts']} experts a layer), live "
        f"latents {latents / 1e9:.3f} GB -> {1e3 * least_s:.3f} ms at the "
        f"chip's HBM peak, against {took_ms:.3f} ms")
    return 100.0 * 1e3 * least_s / took_ms

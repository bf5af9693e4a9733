"""The decode step of a trunk of delta-rule mixers beside selected latent
attention against the memory it has to move: the least time the chip's HBM
needs for what a traced step reads and writes — the weights outside the
routed experts (every KDA mixer, the attention layers' attention and
indexers, the mHC maps, the dense FFN, the routers and shared experts), the
head's slice, the held experts the step touched (the program's
``experts_touched``, a layer's mean, x the expert layers), TWICE the running
slots' recurrent state (once in, once out: ``state_bytes_per_slot``), the
SELECTED positions' latents (``dsa_selected`` x the bytes a latent takes,
every attention layer) and the indexer keys scored (``dsa_keys_scored`` x
index_head_dim x 2 B) — over the step program's median device time, in %.
The step moves at least this, so it reads under 100. A family whose module
has no ``layer_params`` with a ``kda``, or a program whose ``decode_step``
spans carry no ``dsa_keys_scored`` (any parent of PR 55), has nothing to
read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured


def terms(m: dict, n: dict, k: dict, *, touched: float, running: float,
          state_bytes: float, selected: float, scored: float,
          bytes_per_value: int = 2) -> dict:
    """Each term of a step's least traffic, in bytes."""
    return {
        "weights outside the routed experts": (
            k["kda"] * n["kda"] + k["attention"] * (n["attention"]
                                                    + n["indexer"])
            + k["layers"] * n["mhc"] * 2          # float32 maps
            + k["dense"] * n["dense"]
            + k["routed"] * (n["router"] + n["shared"])) * bytes_per_value,
        "the head": n["head"] * bytes_per_value,
        "held experts touched": k["routed"] * touched * n["expert"]
        * bytes_per_value,
        "the running slots' state in and out": 2 * running * state_bytes,
        "the selected latents": selected * k["attention"] * m["kv_lora_rank"]
        * bytes_per_value,
        "the indexer keys scored": scored * k["attention"]
        * m["index_head_dim"] * bytes_per_value}


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "dsa_keys_scored" in e.meta
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
    parts = terms(m, n, fam.kinds(m), touched=mean("experts_touched"),
                  running=mean("slots"),
                  state_bytes=steps[-1].meta["state_bytes_per_slot"],
                  selected=mean("dsa_selected"),
                  scored=mean("dsa_keys_scored"),
                  bytes_per_value=bytes_per_value)
    least_s = sum(parts.values()) / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        "linear decode step's least traffic: " + ", ".join(
            f"{name} {v / 1e9:.3f} GB" for name, v in parts.items())
        + f" -> {1e3 * least_s:.3f} ms at the chip's HBM peak, against "
        f"{took_ms:.3f} ms; experts touched a layer "
        f"{mean('experts_touched'):.2f}, running slots {mean('slots'):.1f}")
    return 100.0 * 1e3 * least_s / took_ms

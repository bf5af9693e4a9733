"""The decode step of a ``cca`` trunk against the memory it has to move: the
least time the chip's HBM needs for what a traced step reads and writes —
the weights outside the experts (every layer's attention with its grouped
conv, and its router), the tied head, the experts the step touched (the
program's ``experts_touched``, a layer's mean, x the layers), the live K/V
(the program's ``live_positions`` x ``cache_bytes_per_token``), the conv
tails of every slot in and out (``state_bytes_per_slot``), and the block of
128 positions every running slot writes back in every layer — over the step
program's median device time, in %. The step moves at least this, so it reads
under 100: the cell's share of the whole step. A family whose module has no
``layer_params`` with a ``router``, or a program whose ``decode_step`` spans
carry no ``router_top_p`` (any parent of PR 44), has nothing to read:
``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured

BLOCK = 128


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "router_top_p" in e.meta
             and "live_positions" in e.meta]
    if not hasattr(fam, "layer_params") or not steps:
        return None
    m = facts["model"]
    n = fam.layer_params(m)
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if "cca_time0" not in m or not took_ms:
        return None
    L = m["num_hidden_layers"]
    mean = lambda key: sum(e.meta[key] for e in steps) / len(steps)  # noqa: E731
    other = L * (n["attention"] + n["router"]) * bytes_per_value
    head = n["head"] * bytes_per_value
    experts = L * mean("experts_touched") * n["expert"] * bytes_per_value
    per_token = mean("cache_bytes_per_token")
    kv = mean("live_positions") * per_token
    tails = 2 * facts["slots"] * mean("state_bytes_per_slot")
    written = mean("slots") * BLOCK * per_token
    least_s = (other + head + experts + kv + tails + written) \
        / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        f"cca decode step's least traffic: weights outside the experts "
        f"{other / 1e9:.3f} GB, the tied head {head / 1e9:.3f} GB, experts "
        f"touched {experts / 1e9:.3f} GB, live K/V {kv / 1e9:.3f} GB, the "
        f"conv tails in and out {tails / 1e9:.4f} GB, the blocks written "
        f"back {written / 1e9:.3f} GB -> {1e3 * least_s:.3f} ms at the "
        f"chip's HBM peak, against {took_ms:.3f} ms; experts touched a layer "
        f"{mean('experts_touched'):.2f} of {m['num_experts']}, the router's "
        f"mean top p {mean('router_top_p'):.3f}, live positions "
        f"{mean('live_positions'):.0f} in {mean('slots'):.1f} running slots")
    return 100.0 * 1e3 * least_s / took_ms

"""The decode step of a trunk whose attention reads an indexer's selection
against the memory it has to move: the least time the chip's HBM needs for
what a traced step reads and writes — the weights outside the routed experts
(every layer's attention, the ``full`` layers' indexers, the dense FFN, the
routers and shared experts), the head's slice, the held experts the step
touched (the program's ``experts_touched``, a layer's mean, x the expert
layers), the SELECTED positions' latents (``dsa_selected`` x the bytes their
values take, every layer), the live indexer keys of the ``full`` layers
(``dsa_live`` x index_head_dim x 2 B each), and what is appended (a position's
latents a layer and a key a ``full`` layer, a running slot) — over the step
program's median device time, in %. The step moves at least this, so it
reads under 100: the cell's share of the whole step. A family whose module
has no ``layer_params`` with an ``indexer``, or a program whose
``decode_step`` spans carry no ``dsa_selected`` (any parent of PR 51), has
nothing to read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "dsa_selected" in e.meta
             and "experts_touched" in e.meta and "held_rows_share" in e.meta]
    if not hasattr(fam, "layer_params") or not steps:
        return None
    m = facts["model"]
    n = fam.layer_params(m)
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if "indexer" not in n or not took_ms:
        return None
    L = m["num_hidden_layers"]
    full = sum(k == "full" for k in m["indexer_types"])
    routed = sum(k == "sparse" for k in m["mlp_layer_types"])
    mean = lambda key: sum(e.meta[key] for e in steps) / len(steps)  # noqa: E731
    latent = (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * bytes_per_value
    key = m["index_head_dim"] * bytes_per_value
    other = (L * n["attention"] + full * n["indexer"]
             + (L - routed) * n["dense"]
             + routed * (n["router"] + n["shared"])) * bytes_per_value
    head = n["head"] * bytes_per_value
    experts = routed * mean("experts_touched") * n["expert"] * bytes_per_value
    chosen = mean("dsa_selected") * L * latent
    keys = mean("dsa_live") * full * key
    written = mean("slots") * (L * latent + full * key)
    least_s = (other + head + experts + chosen + keys + written) \
        / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        f"sparse decode step's least traffic: weights outside the routed "
        f"experts {other / 1e9:.3f} GB, the head {head / 1e9:.3f} GB, held "
        f"experts touched {experts / 1e9:.3f} GB, the selected latents "
        f"{chosen / 1e9:.3f} GB, the live indexer keys {keys / 1e9:.3f} GB, "
        f"appended {written / 1e6:.3f} MB -> {1e3 * least_s:.3f} ms at the "
        f"chip's HBM peak, against {took_ms:.3f} ms; experts touched a layer "
        f"{mean('experts_touched'):.2f}, held_rows_share "
        f"{mean('held_rows_share'):.4f}, selected over live "
        f"{mean('dsa_selected') / max(mean('dsa_live'), 1):.4f}")
    return 100.0 * 1e3 * least_s / took_ms

"""A looped trunk's decode step against the memory it has to move: the least
time the chip's HBM needs for what a traced step reads and writes — the
layers' weights once a pass (``loop_steps`` x, whatever the batch), the
output head, the live K/V of every pass (the benchmark's live tokens x the
program's ``cache_bytes_per_token``), and the blocks of 128 positions the
cache's layout makes the kernel write back to append one (the program's
``append_moved_over_new`` x the requests running: positions written, each
``cache_bytes_per_token``) — over the step program's median device time, in
%. The step moves at least this, so it reads under 100; what is left is what
the 192 kernel calls and the small ops add. A family whose module has no
``layer_params`` with an ``mlp``, or a program whose ``decode_step`` spans
carry no ``loop_steps`` (any parent of the PR that added it), has nothing to
read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "loop_steps" in e.meta]
    live = facts.get("decode_live_tokens")
    if not hasattr(fam, "layer_params") or not steps or not live:
        return None
    n = fam.layer_params(facts["model"])
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if "mlp" not in n or not took_ms:
        return None
    meta = steps[-1].meta
    layers = facts["model"]["num_hidden_layers"] * (n["attention"] + n["mlp"])
    weights = meta["loop_steps"] * layers * bytes_per_value
    head = n["head"] * bytes_per_value
    token = meta["cache_bytes_per_token"]
    read = sum(live) / len(live) * token
    written = sum(e.meta.get("append_moved_over_new", 0.0) * e.meta["slots"]
                  for e in steps) / len(steps) * token
    least_s = (weights + head + read + written) \
        / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        f"looped decode step's least traffic: {meta['loop_steps']} x the "
        f"layers' weights {weights / 1e9:.3f} GB, the head {head / 1e9:.3f} "
        f"GB, live K/V {read / 1e9:.3f} GB, appended blocks written back "
        f"{written / 1e9:.3f} GB -> {1e3 * least_s:.3f} ms at the chip's HBM "
        f"peak, against {took_ms:.3f} ms")
    return 100.0 * 1e3 * least_s / took_ms

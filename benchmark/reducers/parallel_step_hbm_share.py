"""The decode step of a trunk of a Mamba-2 mixer and attention side by side
in every layer against the memory it has to move: the least time the chip's
HBM needs for what a traced step reads and writes — the layers' weights
(attention, mixer and MLP of every layer), the head, TWICE the running slots'
recurrent state (once in, once out: ``state_bytes_per_slot``), and the live
K/V (the benchmark's live tokens x ``cache_bytes_per_token``) — over the step
program's median device time, in %. The step moves at least this, so it reads
under 100. The weights are counted from the family's ``layer_params``, not
from what the program says it read. A family whose module has no
``layer_params`` with an ``ssm`` beside an ``mlp``, or a program whose
``decode_step`` spans carry no ``state_bytes_step`` (any parent of PR 48),
has nothing to read: ``None``.
"""

from __future__ import annotations

import importlib

from ..reduce import program_time
from .program_span import _captured


def reduce(facts, *, program: str, bytes_per_value: int = 2):
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    steps = [e for e in _captured() if e.kind == "decode_step"
             and e.t1 is not None and "state_bytes_step" in e.meta]
    live = facts.get("decode_live_tokens")
    if not hasattr(fam, "layer_params") or not steps or not live:
        return None
    n = fam.layer_params(facts["model"])
    took_ms = program_time(facts, program=program, measure="duration",
                           statistic="median")
    if not {"ssm", "mlp"} <= set(n) or not took_ms:
        return None
    L = facts["model"]["num_hidden_layers"]
    mean = lambda key: sum(e.meta[key] for e in steps) / len(steps)  # noqa: E731
    mixers = L * (n["attention"] + n["ssm"]) * bytes_per_value
    mlp = L * n["mlp"] * bytes_per_value
    head = n["head"] * bytes_per_value
    state = 2 * mean("slots") * steps[-1].meta["state_bytes_per_slot"]
    kv = sum(live) / len(live) * steps[-1].meta["cache_bytes_per_token"]
    least_s = (mixers + mlp + head + state + kv) \
        / facts["peaks"]["hbm_bytes_per_s"]
    facts.setdefault("notes", []).append(
        f"side-by-side decode step's least traffic: the mixers' weights "
        f"{mixers / 1e9:.3f} GB, the MLPs' {mlp / 1e9:.3f} GB, the head "
        f"{head / 1e9:.3f} GB, the running slots' state in and out "
        f"{state / 1e9:.3f} GB, live K/V {kv / 1e9:.3f} GB -> "
        f"{1e3 * least_s:.3f} ms at the chip's HBM peak, against "
        f"{took_ms:.3f} ms")
    return 100.0 * 1e3 * least_s / took_ms

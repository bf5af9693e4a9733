"""Per-layer metrics from the program's own spans.

The capture is reduced after it is deleted, ``load_trace`` keeps only the
benchmark's own host annotations, and ``facts`` holds no handle on the
engine; so the program's spans reach a metric through the process's
memory. ``deepspeed_tpu.observability.spans.captured()`` hands out what
the program recorded while the profiler's capture was live. The harness
opens the capture between two iterations and closes it after the last, so
these are whole iterations of the traced tail and need no clock to cut
them. A program without that accessor (the parent of the PR that added
it) has nothing to read: ``None``.
"""

from __future__ import annotations

import statistics
from typing import Optional

from ..reduce import _stat, clip, merge, total


def _captured() -> list:
    try:
        from deepspeed_tpu.observability import spans
    except ImportError:
        return []
    read = getattr(spans, "captured", None)
    return list(read()) if read is not None else []


def reduce(facts, *, parent: str, exclude: tuple = (),
           statistic: str = "median", meta: Optional[str] = None,
           scale: float = 1e3):
    """Over the ``parent`` spans the capture holds: each one's duration
    less that of its children whose kind is in ``exclude``, then the
    statistic, in ms. A child is a span with the parent's ``step`` that
    lies inside it. One note gives the median of every child beside it,
    which is the split of the parent a reader wants. With ``meta`` the
    statistic is taken of that count on the parent spans instead of their
    time, as it stands."""
    spans = [e for e in _captured() if e.t1 is not None]
    parents = [e for e in spans if e.kind == parent]
    if not parents:
        return None
    if meta is not None:
        return _stat([float(e.meta[meta]) for e in parents
                      if meta in e.meta], statistic)
    by_step: dict = {}
    for e in spans:
        if e.kind != parent and e.step is not None:
            by_step.setdefault(e.step, []).append(e)
    families = [(p, [c for c in by_step.get(p.step, [])
                     if p.t0 <= c.t0 and c.t1 <= p.t1]) for p in parents]
    values = [p.t1 - p.t0 - sum(c.t1 - c.t0 for c in kids
                                if c.kind in exclude)
              for p, kids in families]
    facts.setdefault("notes", []).append(split_note(parent, families, scale))
    return _stat(values, statistic) * scale


def split_note(parent: str, families: list, scale: float) -> str:
    """``srv.step 105.1 ms median over 37; inside it (median ms, in how
    many): srv.decode_readback 101.2 (37), ...; its own 0.31``: every
    child kind's median over the parents it ran in, and what the children
    together leave of the parent (their union, so a child that nests in
    another is not taken off twice)."""
    per_kind: dict = {}
    own = []
    for p, kids in families:
        for c in kids:
            per_kind.setdefault(c.kind, {}).setdefault(id(p), 0.0)
            per_kind[c.kind][id(p)] += c.t1 - c.t0
        covered = total(clip(merge((c.t0, c.t1) for c in kids), p.t0, p.t1))
        own.append(p.t1 - p.t0 - covered)
    medians = sorted(((statistics.median(v.values()), k, len(v))
                      for k, v in per_kind.items()), reverse=True)
    parts = ", ".join(f"{k} {m * scale:.3f} ({n})" for m, k, n in medians)
    whole = statistics.median(p.t1 - p.t0 for p, _ in families)
    return (f"{parent} {whole * scale:.3f} ms median over {len(families)}; "
            f"inside it (median ms, in how many): {parts}; "
            f"its own {statistics.median(own) * scale:.3f}")

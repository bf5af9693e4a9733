"""``backlog`` for delta-rule mixers beside roped latent attention layers
(``models/kda.py``, ``inference/kinds/delta_latent.py``: a float32 state and
conv tails a slot beside ONE plane of latents, a routing group of every
layer's experts held): ``backlog_delta``'s run — the window, the set-up and
the ramp of ``_serving.serve``; every logit row that ``ServingEngine.
_chunk_impl`` / ``_final_impl`` / ``_insert_impl`` / ``_step_impl`` produced,
on the engine's own slot state, against the reference's one full forward
following those programs' routing at its own near-ties; every buffer of a
retired slot bit-equal; served requests against solo ``generate()`` and,
where they differ, against the reference — with three things of its own:

- the buffers a retired slot has to keep (:data:`BUFFERS`: ``c`` for ``k`` /
  ``v``);
- the warm-ups (:func:`warm_buckets`): this mix's prompts start under one
  chunk, so a final bucket is reached from a fresh cache, behind one chunk
  and behind a chunk that followed a chunk, each a program of its own;
- the controls (:data:`CONTROLS`): ``python3 -m
  benchmark.kinds.backlog_delta_latent --workload <cell> --seed <n>
  [--prompts 513,2050] [--rehearse]`` computes the system's rows once and
  runs the comparison under each control of the reference
  (``reference/bailing_hybrid.py`` ``CONTROLS``, and every matrix but the
  router's rounded to 3 mantissa bits); every control has to come out not
  correct.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..harness import Cell, Outcome
from ..reference.bailing_hybrid import CONTROLS as DEVIATIONS
from ..traffic import rng_for
from . import backlog_delta as base

BUFFERS = ("c", "kda", "conv")
CONTROLS = (*DEVIATIONS, "weights-8bit")


def warm_buckets(cell: Cell, cfg, srv) -> None:
    """One request for every final bucket the mix's prompts can end in,
    behind no chunk, one chunk and two: a program is compiled apart by what
    fed it (a fresh cache, a chunk that a fresh cache fed, a chunk that a
    chunk fed), not by how many chunks came before. The window still refuses
    a run in which anything compiled."""
    from deepspeed_tpu.serving.scheduler import plan_chunks

    chunk = int(cell.mix["engine"]["prefill_chunk"])
    lo, hi = (int(cell.mix["prompt_tokens"][k]) for k in ("min", "max"))
    rng = rng_for(cell.seed + 3)
    seen = set()
    for p in range(lo, min(hi, lo + 3 * chunk) + 1):
        sizes = [c.size for c in plan_chunks(np.zeros(p, np.int32), chunk,
                                             overlap=False)]
        shape = (min(len(sizes) - 1, 2), sizes[-1])
        if shape not in seen:
            seen.add(shape)
            srv.submit(rng.integers(0, cfg.vocab_size, p, dtype=np.int32), 2,
                       seed=p)
    srv.drain()
    srv.end_drain()
    srv.results.clear()


@contextlib.contextmanager
def _own():
    """``backlog_delta`` with this kind's buffers, warm-ups and controls."""
    names = ("BUFFERS", "warm_buckets", "DEVIATIONS", "CONTROLS")
    was = [getattr(base, name) for name in names]
    for name in names:
        setattr(base, name, globals()[name])
    try:
        yield
    finally:
        for name, value in zip(names, was):
            setattr(base, name, value)


def run(cell: Cell) -> Outcome:
    with _own():
        return base.run(cell)


def main(argv=None) -> int:
    """The kind's comparison under each control, the system's rows computed
    once (``backlog_delta.main`` over this kind's controls)."""
    with _own():
        return base.main(argv)


if __name__ == "__main__":
    import sys

    sys.exit(main())

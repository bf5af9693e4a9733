"""``backlog`` for a trunk of compressed convolutional attention behind a
router with a carried state (``models/cca.py``, the zaya router of
``models/moe.py``: K/V planes beside a conv tail a slot a layer, top-1
experts): the window, the set-up and every other check are
``_serving.serve``'s; the comparisons with the plain reference are put
together from ``backlog_routed`` and ``backlog_windowed`` as those were.

**Routing.** The expert is the top-1 of ``p + b_bal``, so the reference
follows the system's choice at its own near-ties (``route_gap`` between the
first and the second) and nowhere else (``backlog_routed``, top of file); the
forward's comparison is that file's ``check_logits`` as it stands. With
top-1 a flipped choice changes a token's whole FFN: the notes count the
token-layers that followed.

**Through the cache.** ``InferenceEngine.forward`` has no cache and solo
``generate()`` shares the cache code, so neither would notice a tail dropped
at a chunk boundary, a tail advanced by a bucket's padding, a value shift
that reads this token, or a slot reading its predecessor's last positions.
So each of the mix's ``check_prompt_tokens`` prompts is prefilled in the
engine's own chunks (``plan_chunks(..., overlap=False)``: a tail is never
rewound, the last chunk is right-padded), seated in every slot but one in
sixteen (retired with a prompt's planes and tails in them), and
``check_decode_steps`` given tokens are decoded with the slots' step: that is
``backlog_windowed.through_the_cache`` / ``compare_rows`` with this cache's
buffers. Every logit row of every seated slot is held to the reference's ONE
full forward within ``logit_tolerance``; with the decode kernels on (the
chip) the planes and tails of every retired slot have to come out bit-equal.

**The ramp** goes on after the slots are full (``_serving.serve`` stops
there) until as many requests have finished as there are slots or
``ramp_max_iterations`` iterations have run in all: the answers are a
thousand steps long, and a window opened on 48 requests at their first step
would time a cache a quarter as full as the steady state's.

**The weights** come from the mix's ``weights_seed`` where it has one
(``weights_seed_why``), else from ``--seed``.

**Controls** (:data:`CONTROLS`): ``python3 -m benchmark.kinds.backlog_cca
--workload <cell> --seed <n>`` computes the system's rows once and runs the
cache comparison on them under each control of the reference, at the timed
sizes on the chip (``--rehearse``: the small ones, anywhere); every control
has to come out not correct.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses

from ..harness import Cell, Outcome
from . import _serving, backlog_routed, backlog_windowed
from .backlog_routed import check_served
from .backlog_windowed import compare_rows, round8

BUFFERS = ("k", "v", "tail")


def cache_rows(cell: Cell, cfg, eng):
    """``backlog_windowed.cache_rows`` over this cache's buffers."""
    was, backlog_windowed.BUFFERS = backlog_windowed.BUFFERS, BUFFERS
    try:
        return backlog_windowed.cache_rows(cell, cfg, eng)
    finally:
        backlog_windowed.BUFFERS = was


_shared_build = _serving.build


def build(cell: Cell):
    """``_serving.build``, the weights drawn from the mix's ``weights_seed``
    where it has one."""
    seed = cell.mix.get("weights_seed")
    return _shared_build(cell if seed is None
                         else dataclasses.replace(cell, seed=int(seed)))


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    # the forward, with the routing it reported: backlog_routed's
    ok = backlog_routed.check_logits(cell, cfg, params, eng, notes)
    return compare_rows(cell, params, cache_rows(cell, cfg, eng), notes) and ok


def ramp_on(cell: Cell, srv, notes: list) -> None:
    """Behind ``_serving.serve``'s ramp (the slots full): iterate until as
    many requests have finished as there are slots, or the mix's
    ``ramp_max_iterations`` have run in all."""
    slots = int(cell.mix["engine"]["slots"])
    first, done = srv._iterations, 0
    while done < slots and srv._iterations - first < int(
            cell.mix["ramp_max_iterations"]) and srv.sched.queue:
        for r in srv.step():
            srv.results.pop(r.rid, None)
            done += 1
    notes.append(f"the ramp went on for {srv._iterations - first} "
                 f"iterations behind the full slots: {done} requests "
                 f"finished, {len(srv.sched.running)} of {slots} slots "
                 "occupied")


def run(cell: Cell) -> Outcome:
    # the harness keeps reading this very cell (the capture's directory is
    # written onto it), so the shared window gets it, not a copy: for the
    # generator the mix is a backlog, and the checks are this file's. The
    # warm-up is the shared one and hands over the engine it is given; the
    # ramp goes on where the shared one hands over to the window
    mix = cell.mix
    cell.mix = dict(mix, kind="backlog")
    shared = (_serving.build, _serving.check_logits, _serving.check_served,
              _serving.warm_buckets, _serving.settle_host)
    shared_warm, shared_settle = shared[3:]
    serving: list = []
    notes: list = []

    def warm_buckets(cell, cfg, srv):
        serving.append(srv)
        shared_warm(cell, cfg, srv)

    def settle_host():
        ramp_on(cell, serving[0], notes)
        shared_settle()

    (_serving.build, _serving.check_logits, _serving.check_served,
     _serving.warm_buckets, _serving.settle_host) = (
         build, check_logits, check_served, warm_buckets, settle_host)
    try:
        out = _serving.serve(cell, open_loop=False)
        out.notes[:0] = notes
        return out
    finally:
        cell.mix = mix
        (_serving.build, _serving.check_logits, _serving.check_served,
         _serving.warm_buckets, _serving.settle_host) = shared


# ---------------------------------------------------------------- controls
# What each control changes on the REFERENCE's side of the comparison through
# the cache (the system's rows are the system's): a leaf of the weights it is
# handed, a published key, one of its named pieces, its widening.
LEAVES = {"temperature-dropped": ("cca_temp", 1.0),
          "gamma-zero": ("router_gamma", 0.0),
          "bias-dropped": ("router_bias", 0.0)}
PIECES = ("tail-zeroed-at-chunk-boundary", "value-shift-dropped",
          "qk-mean-dropped", "l2-norm-dropped", "weight-one")
CONTROLS = (*PIECES, *LEAVES, "residual-scales-dropped", "rope-on-all-dims",
            "weights-8bit")


def _zeroed_every(shift, chunk: int):
    """``shift`` with nothing handed across a multiple of ``chunk``: what a
    cache path that dropped its tail at a chunk boundary computes."""
    import jax.numpy as jnp

    def wrapped(a):
        first = jnp.arange(a.shape[1]) % chunk == 0
        return jnp.where(first.reshape((1, -1) + (1,) * (a.ndim - 2)), 0.0,
                         shift(a))
    return wrapped


def _weight_one(router):
    """The reference's router with the chosen expert weighted 1, not p."""
    def wrapped(y, w, c, state, follow=None, gap: float = 0.0):
        g, state, took = router(y, w, c, state, follow, gap)
        return (g > 0).astype(g.dtype), state, took
    return wrapped


@contextlib.contextmanager
def control(name: str, ref, params, chunk: int = 512):
    """The reference under control ``name``; yields the weights to hand
    it."""
    import jax.numpy as jnp

    pieces = ("shifted", "value_shifted", "qk_mean", "unit", "router")
    was = (copy.deepcopy(ref.PUBLISHED), ref.ROUND,
           {k: getattr(ref, k) for k in pieces})

    def with_leaf(leaf, value):
        return {**params, "layers": {**params["layers"],
                                     leaf: value(params["layers"][leaf])}}
    try:
        if name in LEAVES:
            leaf, value = LEAVES[name]
            params = with_leaf(leaf, lambda a: jnp.full_like(a, value))
        elif name == "residual-scales-dropped":
            params = with_leaf("res_scale", lambda a: jnp.broadcast_to(
                jnp.asarray([1.0, 0.0, 1.0, 0.0], a.dtype)[:, None], a.shape))
        elif name == "rope-on-all-dims":
            ref.PUBLISHED["rope_parameters"]["hybrid"][
                "partial_rotary_factor"] = 1.0
        elif name == "tail-zeroed-at-chunk-boundary":
            ref.shifted = _zeroed_every(was[2]["shifted"], chunk)
            ref.value_shifted = _zeroed_every(was[2]["value_shifted"], chunk)
        elif name == "value-shift-dropped":
            ref.value_shifted = lambda a: a
        elif name == "qk-mean-dropped":
            ref.qk_mean = lambda z2q, z2k, zq, zk: (z2q, z2k)
        elif name == "l2-norm-dropped":
            ref.unit = lambda x: x
        elif name == "weight-one":
            ref.router = _weight_one(was[2]["router"])
        elif name == "weights-8bit":
            ref.ROUND = round8
        else:
            raise ValueError(f"no control {name!r} of the reference")
        yield params
    finally:
        ref.PUBLISHED.clear()
        ref.PUBLISHED.update(was[0])
        ref.ROUND = was[1]
        for k, fn in was[2].items():
            setattr(ref, k, fn)


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import time

    from .. import harness

    ap = argparse.ArgumentParser(
        description="The cache comparison under each control of the "
                    "reference, the system's rows computed once: every one "
                    "has to fail.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--prompts", default=None,
                    help="check_prompt_tokens for this run, e.g. 24,514")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = harness.load_cell(spec, args.workload, args.seed, 0.0, False,
                             args.rehearse, time.perf_counter())
    if args.prompts:
        cell.mix["check_prompt_tokens"] = [
            int(n) for n in args.prompts.split(",")]
    harness.place_compile_cache()
    harness.require_devices(cell)
    cfg, params, eng = build(cell)
    rows = cache_rows(cell, cfg, eng)
    chunk = int(cell.mix["engine"]["prefill_chunk"])
    fails = True
    for name in ("sound", *args.controls.split(",")):
        notes: list = []
        if name == "sound":
            ok = compare_rows(cell, params, rows, notes)
        else:
            with control(name, cell.reference, params, chunk) as theirs:
                ok = compare_rows(cell, theirs, rows, notes)
        fails &= ok if name == "sound" else not ok
        for note in notes:
            harness.say(f"{name}: {note}")
        print(json.dumps({"control": name, "correct": bool(ok)}), flush=True)
    return 0 if fails else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())

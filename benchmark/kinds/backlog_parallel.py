"""``backlog`` for a trunk of a Mamba-2 mixer and attention side by side in
every layer (``models/hybrid.py`` kind ``P``, ``inference/kinds/parallel.py``:
one layer owns a K/V plane and a recurrent state; no expert layers): the
window, the set-up, the ramp and the forward's check are ``_serving.serve``'s;
the comparison through the cache is ``backlog_hybrid``'s, less the routing.

**Through the cache.** ``InferenceEngine.forward`` has no cache and solo
``generate()`` shares the cache code, so neither would notice a conv window
dropped at a chunk boundary, a state advanced by a bucket's padding, a key
rotated by the wrong position, or a slot's state touched by another row. So
each of the mix's ``check_prompt_tokens`` prompts is prefilled in the engine's
own chunks (``plan_chunks(..., overlap=False)``: a recurrent state is never
rewound, the last chunk is right-padded) into a batch-1 cache of the slots'
``max_len`` and seated (``insert_request``) in a cache of the slots' shape —
**in every slot, the prompts taking turns, and one slot in sixteen is then
retired** (``backlog_hybrid.seating``) — because the step's kernels depend on
who is running and the window that is timed runs nearly full. Then
``check_decode_steps`` given tokens are decoded through what the slot-step
program runs (``forward_with_cache`` on per-slot lengths with the decode
kernels, all slots in one batch). Every logit row of every seated slot — the
prompt's last position and each step — is held to the reference's ONE full
forward over prompt + those tokens (layer by layer off the engine's own
weights, the head by blocks of the vocabulary) within ``logit_tolerance``.
The state, the window and, with the decode kernels on, the K/V planes of
every retired slot have to come out bit-equal: a row at length 0 is not
running.

**Served requests** are ``backlog_looped.check_served``: token for token
against solo ``generate()``, and a request that differs is held to the
reference's draw directly.

**Controls** (:data:`CONTROLS`): ``python3 -m benchmark.kinds.backlog_parallel
--workload <cell> --seed <n>`` computes the system's rows once and runs the
comparison on them under each control of the REFERENCE (a multiplier left
out, a branch dropped, the window cut at every chunk boundary, every
product's operands in 8 bits), then computes the rows again under each fault
of the SYSTEM (:data:`FAULTS`: a bucket's padding advancing the state, what
only the path itself can do) against the sound reference; at the timed sizes
on the chip (``--rehearse``: the small ones, anywhere). Every one has to come
out not correct.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..harness import Cell, Outcome
from ..traffic import rng_for
from . import _serving, backlog_looped
from .backlog_hybrid import seating
from .backlog_windowed import round8

BUFFERS = ("ssm", "conv", "k", "v")
FAULTS = ("padding-advances-the-state",)


def through_the_cache(cell: Cell, cfg, eng, prompts: list, given: list,
                      fault: str = ""):
    """Per prompt, one (1 + steps, V) float32 array a slot that ran it: the
    logits of the cache path; and whether the buffers of the retired slots
    came out of the steps bit-equal (the planes only where the step runs the
    decode kernels: XLA's dense append lands in an idle row's own extent).
    ``fault``: one of :data:`FAULTS`, for a control."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.decode import (GenCarry, forward_with_cache,
                                                init_cache)
    from deepspeed_tpu.serving.scheduler import plan_chunks
    from deepspeed_tpu.serving.slots import init_slots, insert_request

    e = cell.mix["engine"]
    slots, max_len, chunk = (int(e[k]) for k in ("slots", "max_len",
                                                 "prefill_chunk"))
    model, dtype = eng.model, eng.compute_dtype
    flash = eng.config.flash_decode_resolved()

    def chunk_fn(p, cache, ids, start):
        return forward_with_cache(model, p, ids,
                                  cache._replace(length=start))[1]

    def final_fn(p, cache, ids, start, last, true_len):
        if fault == "padding-advances-the-state":
            # the true length not handed on: every token of the bucket real
            lg, cache = forward_with_cache(
                model, p, ids, cache._replace(length=start))
            lg = jax.lax.dynamic_slice_in_dim(lg, last, 1, axis=1)
        else:
            lg, cache = forward_with_cache(
                model, p, ids, cache._replace(length=start),
                last_token_head=True, last_index=last)
        return lg[0, 0], cache._replace(length=true_len)

    def step_fn(p, cache, toks):
        lg, cache = forward_with_cache(model, p, toks[:, None], cache,
                                       flash_decode=flash)
        return lg[:, 0], cache

    chunk_fn, final_fn, step_fn, seat = (
        jax.jit(f, donate_argnums=(d,)) for f, d in (
            (chunk_fn, 1), (final_fn, 1), (step_fn, 1), (insert_request, 0)))
    i32 = jnp.int32
    holds, idle = seating(slots, len(prompts))
    ran = [[s for s in range(slots) if holds[s] == i and s not in idle]
           for i in range(len(prompts))]
    first = []
    with eng.mesh:
        state = init_slots(cfg, slots, max_len, dtype)
        for i, prompt in enumerate(prompts):
            cache = init_cache(cfg, 1, max_len, dtype)
            for ch in plan_chunks(prompt, chunk, overlap=False):
                ids = jnp.asarray(ch.ids[None], i32)
                if ch.final:
                    row, cache = final_fn(
                        eng.params, cache, ids, i32(ch.start),
                        i32(ch.last_index), i32(ch.true_len))
                else:
                    cache = chunk_fn(eng.params, cache, ids, i32(ch.start))
            first.append(np.asarray(row, np.float32))
            carry = GenCarry(tok=jnp.zeros((1,), i32), cache=cache,
                             rng=jnp.zeros((1, 2), jnp.uint32),
                             done=jnp.zeros((1,), bool))
            for s in range(slots):        # the idle ones too, retired below
                if holds[s] == i:
                    state = seat(state, i32(s), carry)
            del cache, carry
        cache = state.cache
        del state
        # the idle slots stop running with a predecessor's state in them
        at = jnp.asarray(idle, i32)
        cache = cache._replace(length=cache.length.at[at].set(0))
        watched = BUFFERS if flash else BUFFERS[:2]
        # (a slot at a time: one gather over the idle slots of the whole
        # state needs a second copy of it, 2.25 GB here)
        def held(cache):
            return [np.asarray(getattr(cache, n)[:, s])
                    for n in watched for s in idle]

        before = held(cache)
        steps = [[] for _ in range(slots)]
        for t in range(len(given[0])):
            toks = jnp.asarray([given[i][t] for i in holds], i32)
            lg, cache = step_fn(eng.params, cache, toks)
            lg = np.asarray(lg, np.float32)
            for s in range(slots):
                steps[s].append(lg[s])
        untouched = all(np.array_equal(a, b)
                        for a, b in zip(before, held(cache)))
        del cache, before
    return ([[np.stack([first[i]] + steps[s]) for s in ran[i]]
             for i in range(len(prompts))], untouched)


def cache_rows(cell: Cell, cfg, eng, fault: str = ""):
    """The system's side of the comparison through the cache: the check
    prompts and given tokens drawn from ``--seed``, and what
    :func:`through_the_cache` read of them."""
    steps = int(cell.mix["check_decode_steps"])
    lengths = [int(n) for n in cell.mix["check_prompt_tokens"]]
    rng = rng_for(cell.seed + 4)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lengths]
    given = [rng.integers(0, cfg.vocab_size, steps, dtype=np.int32)
             for _ in lengths]
    got, untouched = through_the_cache(cell, cfg, eng, prompts, given, fault)
    return prompts, given, got, untouched


def compare_rows(cell: Cell, params, rows, notes: list) -> bool:
    """Every row :func:`cache_rows` read against the reference's one full
    forward over prompt + given tokens on ``params``."""
    import jax

    ref = cell.reference
    tol = float(cell.mix["logit_tolerance"])
    prompts, given, got, untouched = rows
    steps = len(given[0])
    ok = bool(untouched)
    if not ok:
        notes.append("through the cache: the state, the window or the "
                     "planes of a slot at length 0 did NOT come out of the "
                     "steps bit-equal")
    for prompt, toks, ran in zip(prompts, given, got):
        n = len(prompt)
        ids = jax.numpy.asarray(np.concatenate([prompt, toks])[None])
        want = np.asarray(jax.block_until_ready(ref.run_highest(
            ref.logits, params, ids,
            rows=tuple(range(n - 1, n + steps)))))[0]
        rel = np.stack([np.where(
            np.isfinite(sys_rows).all(-1),
            np.abs(sys_rows - want).max(-1) / np.abs(want).max(-1), np.inf)
            for sys_rows in ran])                     # (slots, 1 + steps)
        good = float(rel.max()) <= tol
        ok &= good
        notes.append(
            f"through the cache, prompt of {n} prefilled in chunks, seated "
            f"in {len(ran)} slots, then {steps} given tokens decoded with "
            f"the slots' step: max difference from the float32 reference's "
            f"one full forward {float(rel.max()):.2e} of a row's largest "
            f"logit (the prompt's last position {rel[:, 0].max():.2e}, the "
            f"steps {rel[:, 1:].min():.2e} to {rel[:, 1:].max():.2e}; "
            f"{'within' if good else 'OUTSIDE'} {tol:.1e}); retired slots "
            f"bit-equal: {untouched}")
    return ok


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    # the forward, last position: the shared kind's
    ok = backlog_looped.shared_check_logits(cell, cfg, params, eng, notes)
    return compare_rows(cell, params, cache_rows(cell, cfg, eng), notes) and ok


def run(cell: Cell) -> Outcome:
    # the harness keeps reading this very cell (the capture's directory is
    # written onto it), so the shared window gets it, not a copy: for the
    # generator the mix is a backlog, and the checks are this file's
    mix = cell.mix
    cell.mix = dict(mix, kind="backlog")
    held: dict = {}       # the shared kind hands check_served no weights

    def first(cell, cfg, params, eng, notes):
        held["params"] = params
        return check_logits(cell, cfg, params, eng, notes)

    def second(cell, cfg, eng, srv, notes):
        return backlog_looped.check_served(cell, cfg, eng, srv, notes,
                                           held["params"])

    shared = _serving.check_logits, _serving.check_served
    _serving.check_logits, _serving.check_served = first, second
    try:
        return _serving.serve(cell, open_loop=False)
    finally:
        cell.mix = mix
        _serving.check_logits, _serving.check_served = shared


# ---------------------------------------------------------------- controls
# What each control changes on the REFERENCE's side of the comparison (the
# system's rows are the system's): published keys, its rounding, its window.
KEYS = {"ssm-branch-dropped": {"ssm_out_multiplier": 0.0},
        "attention-branch-dropped": {"attention_out_multiplier": 0.0},
        "key-multiplier-left-out": {"key_multiplier": 1.0},
        "ssm-multipliers-left-out": {"ssm_multipliers": [1.0] * 5}}
CONTROLS = (*KEYS, "window-zeroed-at-chunk-boundary", "products-8bit",
            *FAULTS)


@contextlib.contextmanager
def control(name: str, ref, chunk: int):
    """The reference under control ``name``."""
    was = dict(ref.PUBLISHED), ref.ROUND, ref.WINDOW_CUT
    try:
        if name in KEYS:
            ref.PUBLISHED.update(KEYS[name])
        elif name == "window-zeroed-at-chunk-boundary":
            ref.WINDOW_CUT = chunk
        elif name == "products-8bit":
            ref.ROUND = round8
        else:
            raise ValueError(f"no control {name!r} of the reference")
        yield
    finally:
        ref.PUBLISHED.clear()
        ref.PUBLISHED.update(was[0])
        ref.ROUND, ref.WINDOW_CUT = was[1:]


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import time

    from .. import harness

    ap = argparse.ArgumentParser(
        description="The cache comparison under each control: every one "
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
    cfg, params, eng = _serving.build(cell)
    rows = cache_rows(cell, cfg, eng)
    chunk = int(cell.mix["engine"]["prefill_chunk"])
    fails = True
    for name in ("sound", *args.controls.split(",")):
        notes: list = []
        if name == "sound":
            ok = compare_rows(cell, params, rows, notes)
        elif name in FAULTS:
            ok = compare_rows(cell, params,
                              cache_rows(cell, cfg, eng, fault=name), notes)
        else:
            with control(name, cell.reference, chunk):
                ok = compare_rows(cell, params, rows, notes)
        fails &= ok if name == "sound" else not ok
        for note in notes:
            harness.say(f"{name}: {note}")
        print(json.dumps({"control": name, "correct": bool(ok)}), flush=True)
    return 0 if fails else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())

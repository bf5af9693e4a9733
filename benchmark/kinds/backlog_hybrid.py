"""``backlog`` for a trunk of one mixer a layer (``models/hybrid.py``:
Mamba-2 | latent experts | attention, a recurrent state a slot beside K/V
planes for the attention layers only): the window, the set-up, the ramp and
every other check are ``_serving.serve``'s; the comparisons with the plain
reference are this file's, put together from what ``backlog_routed`` and
``backlog_looped`` do.

**Routing.** The experts are a top-k over scores, so the reference follows
the system's choice at its own near-ties (``route_gap``) and nowhere else
(``backlog_routed``, top of file): the system's logits come with its routing
from the same program, and the forward's comparison is that file's
``check_logits`` as it stands.

**Through the cache.** ``InferenceEngine.forward`` has no cache and solo
``generate()`` shares the cache code, so neither would notice a conv window
dropped at a chunk boundary, a state advanced by a bucket's padding, or a
slot's state touched by another row. So, as ``backlog_looped`` does, each of
the mix's ``check_prompt_tokens`` prompts is prefilled in the engine's own
chunks (``plan_chunks(..., overlap=False)``: a recurrent state is never
rewound, the last chunk is right-padded) into a batch-1 cache of the slots'
``max_len`` and seated (``insert_request``) in a cache of the slots' shape —
**in every slot, the prompts taking turns, and one slot in sixteen is then
retired** — because the step's kernels depend on who is running
(``ops/ssm_step.py``: a slot at length 0 borrows a neighbour's block) and the
window that is timed runs nearly full.
Then ``check_decode_steps`` given tokens are decoded through what the
slot-step program runs (``forward_with_cache`` on per-slot lengths with the
decode kernels, all slots in one batch). Every logit row of every seated slot
— the prompt's last position and each step — is held to the reference's ONE
full forward over prompt + those tokens, following the routing those very
programs reported (slots of one prompt whose steps routed alike share a
forward), within ``logit_tolerance``. The recurrent state of every slot left
idle has to come out bit-equal: a row at length 0 is not running.

**Served requests** are ``backlog_routed.check_served``: token for token
against solo ``generate()``, and a request that differs is held to the
reference directly, following the served path's own routing
(``ServingEngine.routing_log``).
"""

from __future__ import annotations

import numpy as np

from ..harness import Cell, Outcome
from ..traffic import rng_for
from . import _serving, backlog_routed
from .backlog_routed import check_served


def seating(slots: int, prompts: int) -> tuple:
    """(the prompt each slot is seated with, the slots retired before the
    steps): one slot in sixteen (at least one) stands idle, apart from the
    others, with the first prompt's state in it; the prompts take turns over
    the slots that run."""
    n = max(1, slots // 16)
    idle = [(2 * i + 1) * slots // (2 * n) for i in range(n)]
    if slots - len(idle) < prompts:
        raise ValueError(f"{prompts} check prompts and {len(idle)} idle "
                         f"slots do not fit {slots} slots")
    turn = iter(range(slots))
    return [0 if s in idle else next(turn) % prompts
            for s in range(slots)], idle


def through_the_cache(cell: Cell, cfg, eng, prompts: list, given: list):
    """Per prompt, one entry a slot that ran it: the (1 + steps, V) float32
    logits of the cache path and its routing (expert layers, 1, prompt +
    steps, k); and whether the recurrent state of the idle slots came out of
    the steps bit-equal."""
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
        _, cache, routing = forward_with_cache(
            model, p, ids, cache._replace(length=start), with_routing=True)
        return cache, routing

    def final_fn(p, cache, ids, start, last, true_len):
        lg, cache, routing = forward_with_cache(
            model, p, ids, cache._replace(length=start),
            last_token_head=True, last_index=last, with_routing=True)
        return lg[0, 0], cache._replace(length=true_len), routing

    def step_fn(p, cache, toks):
        lg, cache, routing = forward_with_cache(
            model, p, toks[:, None], cache, flash_decode=flash,
            with_routing=True)
        return lg[:, 0], cache, routing

    chunk_fn, final_fn, step_fn, seat = (
        jax.jit(f, donate_argnums=(d,)) for f, d in (
            (chunk_fn, 1), (final_fn, 1), (step_fn, 1), (insert_request, 0)))
    i32 = jnp.int32
    holds, idle = seating(slots, len(prompts))
    ran = [[s for s in range(slots) if holds[s] == i and s not in idle]
           for i in range(len(prompts))]
    first, prefill = [], []
    with eng.mesh:
        state = init_slots(cfg, slots, max_len, dtype)
        for i, prompt in enumerate(prompts):
            cache, routes = init_cache(cfg, 1, max_len, dtype), []
            for ch in plan_chunks(prompt, chunk, overlap=False):
                ids = jnp.asarray(ch.ids[None], i32)
                if ch.final:
                    row, cache, r = final_fn(
                        eng.params, cache, ids, i32(ch.start),
                        i32(ch.last_index), i32(ch.true_len))
                    real = ch.last_index + 1
                else:
                    cache, r = chunk_fn(eng.params, cache, ids, i32(ch.start))
                    real = ch.size
                routes.append(np.asarray(r)[:, :, :real])
            first.append(np.asarray(row, np.float32))
            prefill.append(routes)
            carry = GenCarry(tok=jnp.zeros((1,), i32), cache=cache,
                             rng=jnp.zeros((1, 2), jnp.uint32),
                             done=jnp.zeros((1,), bool))
            for s in range(slots):        # the idle ones too, retired below
                if holds[s] == i:
                    state = seat(state, i32(s), carry)
            del cache, carry
        cache = state.cache
        del state
        # the idle slots stop running with a predecessor's state in them.
        # Their K/V rows are not held to bit-equality: off the decode kernel
        # the dense append lands in an idle row's own extent
        at = jnp.asarray(idle, i32)
        cache = cache._replace(length=cache.length.at[at].set(0))
        before = [buf[:, at] for buf in (cache.ssm, cache.conv)]
        steps = [[] for _ in range(slots)]
        for t in range(len(given[0])):
            toks = jnp.asarray([given[i][t] for i in holds], i32)
            lg, cache, r = step_fn(eng.params, cache, toks)
            lg, r = np.asarray(lg, np.float32), np.asarray(r)
            for s in range(slots):
                steps[s].append((lg[s], r[:, s:s + 1]))
        untouched = all(bool(jnp.array_equal(a, buf[:, at])) for a, buf in
                        zip(before, (cache.ssm, cache.conv)))
        del cache, before
    return ([[(np.stack([first[i]] + [lg for lg, _ in steps[s]]),
               np.concatenate(prefill[i] + [r for _, r in steps[s]], axis=2))
              for s in ran[i]] for i in range(len(prompts))], untouched)


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    import jax

    # the forward, with the routing it reported: backlog_routed's
    ok = backlog_routed.check_logits(cell, cfg, params, eng, notes)
    ref = cell.reference
    tol, gap = float(cell.mix["logit_tolerance"]), float(cell.mix["route_gap"])
    steps = int(cell.mix["check_decode_steps"])
    lengths = [int(n) for n in cell.mix["check_prompt_tokens"]]
    rng = rng_for(cell.seed + 4)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lengths]
    given = [rng.integers(0, cfg.vocab_size, steps, dtype=np.int32)
             for _ in lengths]
    got, untouched = through_the_cache(cell, cfg, eng, prompts, given)
    if not untouched:
        ok = False
        notes.append("through the cache: a slot at length 0 did NOT come "
                     "out of the steps bit-equal")
    for n, prompt, toks, ran in zip(lengths, prompts, given, got):
        ids = jax.numpy.asarray(np.concatenate([prompt, toks])[None])
        # one forward of the reference for the slots whose steps routed alike
        wants: dict = {}
        rel, followed = [], 0
        for rows, route in ran:
            key = route[:, :, n:].tobytes()
            if key not in wants:
                want, took = jax.block_until_ready(ref.run_highest(
                    lambda p, i, theirs: ref.logits(
                        p, i, rows=tuple(range(n - 1, n + steps)),
                        follow=theirs, gap=gap),
                    params, ids, jax.numpy.asarray(route)))
                wants[key] = np.asarray(want)[0]
                followed = max(followed, int(took))
            want = wants[key]
            rel.append(np.where(np.isfinite(rows).all(-1), np.abs(
                rows - want).max(-1) / np.abs(want).max(-1), np.inf))
        rel = np.stack(rel)                          # (slots, 1 + steps)
        good = float(rel.max()) <= tol
        ok &= good
        notes.append(
            f"through the cache, prompt of {n} prefilled in chunks, seated "
            f"in {len(ran)} slots, then {steps} given tokens decoded with "
            f"the slots' step: max difference from the float32 reference's "
            f"one full forward {float(rel.max()):.2e} of a row's largest "
            f"logit (the prompt's last position {rel[:, 0].max():.2e}, the "
            f"steps {rel[:, 1:].min():.2e} to {rel[:, 1:].max():.2e}; "
            f"{'within' if good else 'OUTSIDE'} {tol:.1e}); the reference "
            f"followed the path's experts for up to {followed} token-layers, "
            f"once for each of the {len(wants)} routings the slots' steps "
            f"took; idle slots bit-equal: {untouched}")
    return ok


def run(cell: Cell) -> Outcome:
    # the harness keeps reading this very cell (the capture's directory is
    # written onto it), so the shared window gets it, not a copy: for the
    # generator the mix is a backlog, and the checks are this file's
    mix = cell.mix
    cell.mix = dict(mix, kind="backlog")
    shared = _serving.check_logits, _serving.check_served
    _serving.check_logits, _serving.check_served = check_logits, check_served
    try:
        return _serving.serve(cell, open_loop=False)
    finally:
        cell.mix = mix
        _serving.check_logits, _serving.check_served = shared

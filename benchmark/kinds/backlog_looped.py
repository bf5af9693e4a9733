"""``backlog`` for a looped trunk (a stack of layers applied several times
over the same weights, each pass with cache planes of its own): the window,
the set-up, the ramp and every check are ``_serving.serve``'s, and one
comparison stands beside them.

The shared kind's ``check_logits`` goes through ``InferenceEngine.forward``,
which has no cache, and its ``check_served`` compares the served path with
solo ``generate()``, which shares the cache code: neither would notice K/V
planes shared between passes, a pass reading another pass's keys. So each of
the mix's ``check_prompt_tokens`` prompts is prefilled in the engine's own
chunks (``serving.scheduler.plan_chunks``) into a batch-1 cache of the slots'
``max_len``, seated in a slot of a cache of the slots' shape
(``serving.slots.insert_request``), and ``check_decode_steps`` given tokens
are then decoded through what the slot-step program runs:
``inference.decode.forward_with_cache`` on per-slot lengths with the decode
kernel, all prompts in one batch, the other slots idle. The logits at each
prompt's last position and after each given token are held to the plain
reference's ONE full forward over prompt + those tokens, within the mix's
``logit_tolerance``: the guide's "prefill and then decoding through the cache
must agree with the reference's full forward pass", at the published widths,
on the functions the timed programs are made of.

Served requests are compared with solo ``generate()`` token for token, as in
the shared kind. What excuses a first difference is another rule here: the
shared kind's near-tie (two candidates within 2^-6 of the row's largest
logit) is GPT-2's rounding level, and 192 layer applications in bf16 part
two differently shaped programs by more (read on the chip, PR 34: a served
request differed from solo in 7 of 40, by 1.3e-3 to 2.5e-2 of the largest
logit at the draw, two of them over 2^-6). So, as ``backlog_routed`` does, a
request that differs is held to the plain reference directly: every served
token has to be the draw (the request's own Gumbel noise, within twice
``logit_tolerance`` of the row's largest logit: the system's and the
reference's logits each lie within one of it) of the reference's logits at
its position over prompt + answer. That holds chunking, slots, the cache and
the sampling chain to the reference, whatever solo ``generate()`` rounded.
"""

from __future__ import annotations

import numpy as np

from ..harness import Cell, Outcome
from ..traffic import rng_for
from . import _serving

shared_check_logits = _serving.check_logits


def through_the_cache(cell: Cell, cfg, eng, prompts: list, given: list) -> list:
    """Per prompt the (1 + steps, V) float32 logits of the cache path."""
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
    # the prompts sit apart, idle slots between and around them
    seats = [1 + i * max(1, (slots - 1) // len(prompts))
             for i in range(len(prompts))]
    rows = [[] for _ in prompts]
    with eng.mesh:
        state = init_slots(cfg, slots, max_len, dtype)
        for i, prompt in enumerate(prompts):
            cache = init_cache(cfg, 1, max_len, dtype)
            for ch in plan_chunks(prompt, chunk):
                ids = jnp.asarray(ch.ids[None], i32)
                if ch.final:
                    first, cache = final_fn(
                        eng.params, cache, ids, i32(ch.start),
                        i32(ch.last_index), i32(ch.true_len))
                else:
                    cache = chunk_fn(eng.params, cache, ids, i32(ch.start))
            rows[i].append(np.asarray(first, np.float32))
            state = seat(state, i32(seats[i]), GenCarry(
                tok=jnp.zeros((1,), i32), cache=cache,
                rng=jnp.zeros((1, 2), jnp.uint32),
                done=jnp.zeros((1,), bool)))
            del cache
        cache = state.cache
        del state
        for t in range(len(given[0])):
            toks = np.zeros(slots, np.int32)
            toks[seats] = [g[t] for g in given]
            lg, cache = step_fn(eng.params, cache, jnp.asarray(toks))
            lg = np.asarray(lg, np.float32)
            for i, s in enumerate(seats):
                rows[i].append(lg[s])
        del cache
    return [np.stack(r) for r in rows]


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    import jax

    ok = shared_check_logits(cell, cfg, params, eng, notes)
    rng = rng_for(cell.seed + 4)
    tol, steps = float(cell.mix["logit_tolerance"]), int(
        cell.mix["check_decode_steps"])
    lengths = [int(n) for n in cell.mix["check_prompt_tokens"]]
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lengths]
    given = [rng.integers(0, cfg.vocab_size, steps, dtype=np.int32)
             for _ in lengths]
    got = through_the_cache(cell, cfg, eng, prompts, given)
    for n, prompt, toks, rows in zip(lengths, prompts, given, got):
        ids = np.concatenate([prompt, toks])[None]
        want = np.asarray(jax.block_until_ready(cell.reference.run_highest(
            cell.reference.logits, params, jax.numpy.asarray(ids),
            rows=tuple(range(n - 1, n + steps)))))[0]
        rel = np.abs(rows - want).max(-1) / np.abs(want).max(-1)
        good = bool(np.isfinite(rows).all()) and float(rel.max()) <= tol
        ok &= good
        notes.append(
            f"through the cache, prompt of {n} prefilled in chunks then "
            f"{steps} given tokens decoded with the slots' step: max "
            f"difference from the float32 reference's one full forward "
            f"{float(rel.max()):.2e} of a row's largest logit (the prompt's "
            f"last position {rel[0]:.2e}, the steps {rel[1:].min():.2e} to "
            f"{rel[1:].max():.2e}; "
            f"{'within' if good else 'OUTSIDE'} {tol:.1e})")
    return ok


def drawn_from_the_reference(cell: Cell, params, prompt, toks,
                             seed: int) -> int:
    """How many of the served tokens ``toks`` are NOT the draw of the
    reference's logits at their position over prompt + answer."""
    import jax

    tol = float(cell.mix["logit_tolerance"])
    P, n = len(prompt), len(toks)
    ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])[None]
    rows = np.asarray(cell.reference.run_highest(
        cell.reference.logits, params, jax.numpy.asarray(ids),
        rows=tuple(range(P - 1, P + n - 1))))[0]
    key, missed = jax.random.PRNGKey(int(seed)), 0
    for t in range(n):
        key, sub = jax.random.split(key)
        val = rows[t] + np.asarray(jax.random.gumbel(sub, rows[t].shape,
                                                     np.float32))
        missed += val[int(toks[t])] < val.max() - 2 * tol * np.abs(rows[t]).max()
    return int(missed)


def check_served(cell: Cell, cfg, eng, srv, notes: list, params) -> bool:
    """``_serving.check_served``, with a request that differs from solo
    ``generate()`` held to the reference (on ``params``, the tree as the
    family built it) instead of to a near-tie of the draw (see the top of
    this file)."""
    rng = rng_for(cell.seed + 2)
    max_len = int(cell.mix["engine"]["max_len"])
    ok = True
    for shape in cell.mix["check_requests"]:
        k, p, n = (int(shape[x]) for x in ("count", "prompt", "answer"))
        prompts = rng.integers(0, cfg.vocab_size, (k, p), dtype=np.int32)
        seeds = [int(s) for s in rng.integers(0, 2 ** 31 - 1, k)]
        rids = [srv.submit(prompts[i], n, seed=seeds[i]) for i in range(k)]
        srv.drain()
        srv.end_drain()
        got = [np.asarray(srv.pop_result(r).tokens) for r in rids]
        want = np.asarray(eng.generate(prompts, n, request_seeds=seeds,
                                       cache_len=max_len))
        for i in range(k):
            if len(got[i]) != n:
                ok = False
                notes.append(f"served answer of {len(got[i])} tokens, "
                             f"asked for {n}")
            elif not (got[i] == want[i]).all():
                pos = int(np.nonzero(got[i] != want[i])[0][0])
                missed = drawn_from_the_reference(cell, params, prompts[i],
                                                  got[i], seeds[i])
                ok &= missed == 0
                notes.append(
                    f"served and solo tokens first differ at position {pos} "
                    f"of a {p}-token prompt; against the reference's full "
                    f"forward over prompt + answer {n - missed} of the {n} "
                    "served tokens are its draw"
                    + ("" if missed == 0 else ": NOT all"))
        notes.append(f"{k} served requests (prompt {p}, answer {n}) against "
                     "solo generate(), and against the reference where they "
                     "differ: " + ("equal or its draw" if ok else "DIFFERENT"))
    return ok


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
        return check_served(cell, cfg, eng, srv, notes, held["params"])

    shared_check_served = _serving.check_served
    _serving.check_logits, _serving.check_served = first, second
    try:
        return _serving.serve(cell, open_loop=False)
    finally:
        cell.mix = mix
        _serving.check_logits = shared_check_logits
        _serving.check_served = shared_check_served

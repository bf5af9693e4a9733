"""``backlog`` for a model whose experts are chosen by a top-k over scores:
the window, the set-up and every other check are ``_serving.serve``'s; the
comparison with the plain reference follows the system's routing at
near-ties.

With random weights a token's k-th and (k+1)-th expert scores often lie
closer together than bf16 rounds, the system takes the other expert, and
from there on it runs a different (equally valid) model: last-position
logits then differ from the float32 reference's by tenths of the largest
logit, whatever the tolerance (PERF.md, PR 29). So the system's logits come
with its routing, from one program (``model.apply(..., return_aux=True)``:
``InferenceEngine.forward``'s trunk with the routing as a second result), and
the reference takes the system's experts for a token only where its OWN k-th
and (k+1)-th scores lie within the mix's ``route_gap``; everywhere else it
keeps its own choice, so a system that routes wrongly still fails. The notes
count the tokens that followed. ``InferenceEngine.forward`` itself is
compared with that program's logits and noted.

The same near-ties stand between the served path and solo ``generate()``:
two bf16 programs of different batch shapes round a router's input
differently now and then, one takes the other expert for some token of the
context, and the sampled tokens part by far more than a near-tie of the draw
(two runs in ten on the chip). So served requests are first compared with
solo ``generate()`` as in the shared kind; a request that differs is then
held to the plain reference directly: the serving engine's ``routing_log``
tap gives the experts the served path itself chose for every token of prompt
and answer, the reference follows them at its near-ties over prompt + answer,
and every served token has to be the draw (the request's own Gumbel noise,
within twice ``logit_tolerance`` of the row's largest logit: the system's and
the reference's logits each lie within one of it) of the reference's logits at
its position. That holds chunking, slots, the cache and the sampling chain to
the reference, whatever solo ``generate()`` rounded.
"""

from __future__ import annotations

import numpy as np

from ..harness import Cell, Outcome
from ..traffic import rng_for
from . import _serving

def _routed_program(eng):
    """``InferenceEngine.forward``'s trunk with its routing beside the
    logits: (last-position logits (B, V), routing (layers, B, S, k))."""
    import jax

    return jax.jit(lambda p, ids: tuple(
        a[:, -1] if a.ndim == 3 else a
        for a in eng.model.apply(p, ids, return_aux=True)))


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    import jax

    rng = rng_for(cell.seed + 1)
    tol, gap = float(cell.mix["logit_tolerance"]), float(cell.mix["route_gap"])
    routed = _routed_program(eng)
    ok = True
    for n in cell.mix["check_prompt_tokens"]:
        ids = rng.integers(0, cfg.vocab_size, (1, int(n)), dtype=np.int32)
        with eng.mesh:
            got, routing = routed(eng.params, jax.numpy.asarray(ids))
        got = np.asarray(got[0], np.float32)
        fwd = np.asarray(eng.forward(ids)[0, -1], np.float32)
        want, followed = jax.block_until_ready(cell.reference.run_highest(
            lambda p, i, theirs: cell.reference.logits(
                p, i, last_only=True, follow=theirs, gap=gap),
            params, jax.numpy.asarray(ids), routing))
        want = np.asarray(want)[0]
        top = np.abs(want).max()
        rel = float(np.abs(got - want).max() / top)
        same = float(np.abs(fwd - got).max() / top)
        good = bool(np.isfinite(got).all() and np.isfinite(fwd).all()) \
            and rel <= tol
        ok &= good
        notes.append(
            f"last-position logits, prompt of {n}: max difference from the "
            f"float32 reference {rel:.2e} of the largest logit "
            f"({'within' if good else 'OUTSIDE'} {tol:.1e}); the reference "
            f"followed the system's experts for {int(followed)} of "
            f"{routing.size // routing.shape[-1]} token-layers whose own "
            f"scores tied within {gap:g}; InferenceEngine.forward differs "
            f"from the routed program by {same:.2e}")
    return ok


def served_routing(log: list, positions: int) -> np.ndarray:
    """A request's entries of ``ServingEngine.routing_log`` as one array
    (expert layers, 1, positions, k): written in the order computed, a later
    entry over an earlier one, every position covered."""
    layers, _, k = log[0][1].shape
    full = np.full((layers, 1, positions, k), -1, np.int32)
    for start, chose in log:
        chose = chose[:, :max(0, positions - start)]
        full[:, 0, start:start + chose.shape[1]] = chose
    if (full < 0).any():
        raise ValueError("the routing log leaves positions uncovered")
    return full


def drawn_from_the_reference(cell: Cell, eng, prompt, toks, log, seed: int):
    """How many of the served tokens ``toks`` are NOT the draw of the
    reference's logits at their position, the reference following the served
    path's own routing (``log``) over prompt + answer; and how many
    token-layers followed."""
    import jax

    tol, gap = float(cell.mix["logit_tolerance"]), float(cell.mix["route_gap"])
    P, n = len(prompt), len(toks)
    ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])[None]
    rows, followed = cell.reference.run_highest(
        lambda p, i, theirs: (lambda out, took: (out[0, P - 1:], took))(
            *cell.reference.logits(p, i, follow=theirs, gap=gap)),
        eng.params, jax.numpy.asarray(ids),
        jax.numpy.asarray(served_routing(log, P + n - 1)))
    rows = np.asarray(rows)
    key, missed = jax.random.PRNGKey(int(seed)), 0
    for t in range(n):
        key, sub = jax.random.split(key)
        val = rows[t] + np.asarray(jax.random.gumbel(sub, rows[t].shape,
                                                     np.float32))
        missed += val[int(toks[t])] < val.max() - 2 * tol * np.abs(rows[t]).max()
    return int(missed), int(followed)


def check_served(cell: Cell, cfg, eng, srv, notes: list) -> bool:
    """``_serving.check_served``, with a request that differs from solo
    ``generate()`` held to the reference instead of to a near-tie of the
    draw (see the top of this file)."""
    rng = rng_for(cell.seed + 2)
    max_len = int(cell.mix["engine"]["max_len"])
    ok = True
    srv.routing_log = {}
    try:
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
                    missed, followed = drawn_from_the_reference(
                        cell, eng, prompts[i], got[i],
                        srv.routing_log[rids[i]], seeds[i])
                    ok &= missed == 0
                    notes.append(
                        f"served and solo tokens first differ at position "
                        f"{pos} of a {p}-token prompt; against the reference "
                        f"following the served path's own routing "
                        f"({followed} token-layers at its near-ties) "
                        f"{n - missed} of the {n} served tokens are its draw"
                        + ("" if missed == 0 else ": NOT all"))
            notes.append(f"{k} served requests (prompt {p}, answer {n}) "
                         "against solo generate(), and against the reference "
                         "where they differ: "
                         + ("equal or its draw" if ok else "DIFFERENT"))
            srv.routing_log.clear()
    finally:
        srv.routing_log = None
    return ok


def run(cell: Cell) -> Outcome:
    # the harness keeps reading this very cell (the capture's directory is
    # written onto it), so the shared window gets it, not a copy: for the
    # generator the mix is a backlog, and the check is this file's
    mix = cell.mix
    cell.mix = dict(mix, kind="backlog")
    shared = _serving.check_logits, _serving.check_served
    _serving.check_logits, _serving.check_served = check_logits, check_served
    try:
        return _serving.serve(cell, open_loop=False)
    finally:
        cell.mix = mix
        _serving.check_logits, _serving.check_served = shared

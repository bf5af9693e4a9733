"""``backlog`` for latent attention that reads an indexer's selection
(``models/dsa.py``, ``inference/kinds/sparse_latent.py``: a position's
latents a row beside the indexer's keys, a share of every expert layer's
experts held): the window, the set-up, the ramp and every other check are
``_serving.serve``'s; the comparisons with the plain reference are this
file's, put together from ``backlog_routed`` and ``backlog_windowed`` as
those were.

**Following.** The experts are a top-8 over scores and the positions a
top-2048 over scores, so the reference follows the system's choice at its
own near-ties and nowhere else: a token's experts where its own 8th and 9th
scores lie within ``route_gap`` (``backlog_routed``, top of file), a
query-layer's positions where every position in which the two sets differ
scores within ``select_gap`` of the reference's own 2048th
(``reference/glm_moe_dsa.py``). A system that routes or selects wrongly
still fails; the notes count what followed and give the largest distance
from the threshold of a position the sets differed in.

**Through the cache.** ``InferenceEngine.forward`` has no cache and solo
``generate()`` shares the cache code, so neither would notice a kernel that
fetched another slot's row, an indexer key appended a position late, a
``shared`` layer reading the wrong selection, or a row packed in the wrong
half of its word. So each of the mix's ``check_prompt_tokens`` prompts is
prefilled in the engine's own chunks (``plan_chunks``: the last chunk
rewinds to its bucket) into a batch-1 cache of the slots' ``max_len`` and
seated (``insert_request``) in a cache of the slots' shape — in every slot,
the prompts taking turns, one slot in sixteen (at least one) then retired
with a prompt's rows in it. Then ``check_decode_steps`` given tokens are
decoded through what the slot-step program runs (``forward_with_cache`` on
per-slot lengths with the kernels, all slots in one batch). Every logit row
of every seated slot — the prompt's last position and each step — is held to
the reference's ONE full forward over prompt + those tokens, following the
routing and the selection those very programs reported, within
``logit_tolerance``. With the kernels on (the chip) ``c`` and ``ik`` of every
retired slot have to come out bit-equal: a row at length 0 is not running.

**The weights** come from the mix's ``weights_seed``, not from ``--seed``
(``weights_seed_why``): ``--seed`` draws every token id and every sampling
seed.

**Served requests**: against solo ``generate()`` (one request at a time,
its answers made before the serving engine and its slots exist: a solo
prefill of 5000 tokens takes 12.3 GiB) and, where they
differ, against the reference following the served path's own routing and
selection (``ServingEngine.routing_log``), every served token its draw.

**Controls** (:data:`CONTROLS`): ``python3 -m benchmark.kinds.backlog_sparse
--workload <cell> --seed <n>`` computes the system's rows once and runs this
file's comparison on them under each control, at the timed sizes on the chip
(``--rehearse``: the small ones, anywhere); every control has to come out not
correct.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..harness import Cell, Outcome
from ..traffic import rng_for
from . import _serving
from .backlog_hybrid import seating
from .backlog_windowed import build, round8

BUFFERS = ("c", "ik")


def _rows(parts: list, positions: int) -> np.ndarray:
    """(start, (layers, n, width)) entries as one (layers, 1, positions,
    width), a later entry over an earlier one, every position covered."""
    layers, _, width = parts[0][1].shape
    full = np.full((layers, 1, positions, width), -2, np.int32)
    for start, rows in parts:
        rows = rows[:, :max(0, positions - start)]
        full[:, 0, start:start + rows.shape[1]] = rows
    if (full == -2).any():
        raise ValueError("the log leaves positions uncovered")
    return full


def through_the_cache(cell: Cell, cfg, eng, prompts: list, given: list):
    """Per prompt, one entry a slot that ran it: the (1 + steps, V) float32
    logits of the cache path, its routing (expert layers, 1, prompt + steps,
    k) and its selection (full layers, 1, prompt + steps, K); and whether
    the buffers of the retired slots came out of the steps bit-equal (None
    where the step runs without the kernels)."""
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
        _, cache, chose = forward_with_cache(
            model, p, ids, cache._replace(length=start), with_routing=True)
        return cache, chose

    def final_fn(p, cache, ids, start, last, true_len):
        lg, cache, chose = forward_with_cache(
            model, p, ids, cache._replace(length=start),
            last_token_head=True, last_index=last, with_routing=True)
        return lg[0, 0], cache._replace(length=true_len), chose

    def step_fn(p, cache, toks):
        lg, cache, chose = forward_with_cache(
            model, p, toks[:, None], cache, flash_decode=flash,
            with_routing=True)
        return lg[:, 0], cache, chose

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
            cache, parts = init_cache(cfg, 1, max_len, dtype), ([], [])
            for ch in plan_chunks(prompt, chunk):
                ids = jnp.asarray(ch.ids[None], i32)
                if ch.final:
                    row, cache, chose = final_fn(
                        eng.params, cache, ids, i32(ch.start),
                        i32(ch.last_index), i32(ch.true_len))
                    real = ch.last_index + 1
                else:
                    cache, chose = chunk_fn(eng.params, cache, ids,
                                            i32(ch.start))
                    real = ch.size
                for part, a in zip(parts, chose):
                    part.append((ch.start, np.asarray(a)[:, 0, :real]))
            first.append(np.asarray(row, np.float32))
            prefill.append(parts)
            carry = GenCarry(tok=jnp.zeros((1,), i32), cache=cache,
                             rng=jnp.zeros((1, 2), jnp.uint32),
                             done=jnp.zeros((1,), bool))
            for s in range(slots):        # the idle ones too, retired below
                if holds[s] == i:
                    state = seat(state, i32(s), carry)
            del cache, carry
        cache = state.cache
        del state
        at = jnp.asarray(idle, i32)
        cache = cache._replace(length=cache.length.at[at].set(0))
        before = [np.asarray(getattr(cache, n)[:, at]) for n in BUFFERS] \
            if flash else None
        steps = [[] for _ in range(slots)]
        for t in range(len(given[0])):
            toks = jnp.asarray([given[i][t] for i in holds], i32)
            lg, cache, chose = step_fn(eng.params, cache, toks)
            lg = np.asarray(lg, np.float32)
            chose = [np.asarray(a) for a in chose]
            for s in range(slots):
                steps[s].append((lg[s], [a[:, s] for a in chose]))
        untouched = None if before is None else all(
            np.array_equal(a, np.asarray(getattr(cache, n)[:, at]))
            for a, n in zip(before, BUFFERS))
        del cache, before
    out = []
    for i, prompt in enumerate(prompts):
        n, per_slot = len(prompt), []
        for s in ran[i]:
            follow = tuple(_rows(
                prefill[i][k] + [(n + t, chose[k])
                                 for t, (_, chose) in enumerate(steps[s])],
                n + len(steps[s])) for k in (0, 1))
            per_slot.append((np.stack([first[i]] + [lg for lg, _ in steps[s]]),
                             follow))
        out.append(per_slot)
    return out, untouched


def cache_rows(cell: Cell, cfg, eng):
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
    got, untouched = through_the_cache(cell, cfg, eng, prompts, given)
    return prompts, given, got, untouched


def _reference(cell: Cell, params, ids, rows, follow):
    """The reference's rows ``rows`` of ``ids`` (1, S) following ``follow``,
    and what followed: (tokens x layers, query rows x layers, the largest
    distance)."""
    import jax

    ref, mix = cell.reference, cell.mix
    want, took = jax.block_until_ready(ref.run_highest(
        lambda p, i, r, s: ref.logits(
            p, i, rows=rows, follow=(r, s), gap=float(mix["route_gap"]),
            select_gap=float(mix["select_gap"])),
        params, jax.numpy.asarray(ids), *(jax.numpy.asarray(a)
                                          for a in follow)))
    return np.asarray(want)[0], tuple(float(t) for t in took)


def compare_rows(cell: Cell, params, rows, notes: list) -> bool:
    """Every row :func:`cache_rows` read against the reference's one full
    forward over prompt + given tokens on ``params``."""
    tol = float(cell.mix["logit_tolerance"])
    prompts, given, got, untouched = rows
    steps = len(given[0])
    ok = untouched is not False
    if not ok:
        notes.append("through the cache: c and ik of a slot at length 0 did "
                     "NOT come out of the steps bit-equal")
    for prompt, toks, ran in zip(prompts, given, got):
        n = len(prompt)
        ids = np.concatenate([prompt, toks])[None]
        # one forward of the reference for the slots whose steps chose alike
        wants: dict = {}
        rel, took = [], (0.0, 0.0, 0.0)
        for sys_rows, follow in ran:
            key = b"".join(a[:, :, n:].tobytes() for a in follow)
            if key not in wants:
                wants[key], t = _reference(
                    cell, params, ids, tuple(range(n - 1, n + steps)), follow)
                took = tuple(max(a, b) for a, b in zip(took, t))
            want = wants[key]
            rel.append(np.where(np.isfinite(sys_rows).all(-1), np.abs(
                sys_rows - want).max(-1) / np.abs(want).max(-1), np.inf))
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
            f"followed the path's experts for up to {took[0]:.0f} "
            f"token-layers and its selection for up to {took[1]:.0f} "
            f"query-layers (the sets differed at most {took[2]:.3e} from the "
            f"reference's threshold; select_gap "
            f"{float(cell.mix['select_gap']):g}), once for each of the "
            f"{len(wants)} choices the slots' steps took; retired slots "
            f"bit-equal: {untouched}")
    return ok


def check_requests(cell: Cell, cfg):
    """The mix's ``check_requests`` drawn from ``--seed``: (count, prompt
    length, answer length, prompts (count, length), sampling seeds)."""
    rng = rng_for(cell.seed + 2)
    for shape in cell.mix["check_requests"]:
        k, p, n = (int(shape[x]) for x in ("count", "prompt", "answer"))
        prompts = rng.integers(0, cfg.vocab_size, (k, p), dtype=np.int32)
        yield k, p, n, prompts, [int(s) for s in
                                 rng.integers(0, 2 ** 31 - 1, k)]


# solo generate()'s answers to the check requests, made before the serving
# engine exists: a solo prefill of 5000 tokens takes 12.3 GiB by
# memory_analysis() and does not fit beside the slots' cache
_SOLO: list = []


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    max_len = int(cell.mix["engine"]["max_len"])
    _SOLO[:] = [[np.asarray(eng.generate(
        prompts[i:i + 1], n, request_seeds=seeds[i:i + 1],
        cache_len=max_len))[0] for i in range(k)]
        for k, _, n, prompts, seeds in check_requests(cell, cfg)]
    return compare_rows(cell, params, cache_rows(cell, cfg, eng), notes)


def drawn_from_the_reference(cell: Cell, eng, prompt, toks, log, seed: int):
    """How many of the served tokens ``toks`` are NOT the draw of the
    reference's logits at their position, the reference following the served
    path's own routing and selection (``log``) over prompt + answer; and
    what followed."""
    import jax

    tol = float(cell.mix["logit_tolerance"])
    P, n = len(prompt), len(toks)
    ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])[None]
    follow = tuple(_rows([(start, np.asarray(chose[k]))
                          for start, chose in log], P + n - 1)
                   for k in (0, 1))
    rows, took = _reference(cell, eng.params, ids,
                            tuple(range(P - 1, P + n - 1)), follow)
    key, missed = jax.random.PRNGKey(int(seed)), 0
    for t in range(n):
        key, sub = jax.random.split(key)
        val = rows[t] + np.asarray(jax.random.gumbel(sub, rows[t].shape,
                                                     np.float32))
        missed += val[int(toks[t])] < val.max() - 2 * tol * np.abs(rows[t]).max()
    return int(missed), took


# served requests that differ from solo generate(): held to the reference
# once the window is over and the slots' cache is gone (the reference over
# 5000 tokens takes 2.3 GiB of temporaries; beside the slots 2.0 are free)
_PENDING: list = []


def check_served(cell: Cell, cfg, eng, srv, notes: list) -> bool:
    """``_serving.check_served`` against solo ``generate()``'s answers
    (:data:`_SOLO`, one request at a time); a request that differs from it
    is held to the reference instead of to a near-tie of the draw (see the
    top of this file), behind the window (:func:`held_to_the_reference`)."""
    ok = True
    _PENDING.clear()
    srv.routing_log = {}
    try:
        for (k, p, n, prompts, seeds), solo in zip(
                check_requests(cell, cfg), _SOLO):
            rids = [srv.submit(prompts[i], n, seed=seeds[i]) for i in range(k)]
            srv.drain()
            srv.end_drain()
            got = [np.asarray(srv.pop_result(r).tokens) for r in rids]
            for i in range(k):
                want = solo[i]
                if len(got[i]) != n:
                    ok = False
                    notes.append(f"served answer of {len(got[i])} tokens, "
                                 f"asked for {n}")
                elif not (got[i] == want).all():
                    _PENDING.append((
                        eng, prompts[i], got[i], srv.routing_log[rids[i]],
                        seeds[i], int(np.nonzero(got[i] != want)[0][0])))
            notes.append(f"{k} served requests (prompt {p}, answer {n}) "
                         "against solo generate(): "
                         + ("equal, or held to the reference behind the "
                            "window" if ok else "DIFFERENT"))
            srv.routing_log.clear()
    finally:
        srv.routing_log = None
    return ok


def held_to_the_reference(cell: Cell, notes: list) -> bool:
    """The served requests that differed from solo ``generate()``, each
    against the reference following the served path's own routing and
    selection: every served token has to be its draw."""
    ok = True
    for eng, prompt, toks, log, seed, pos in _PENDING:
        missed, took = drawn_from_the_reference(cell, eng, prompt, toks, log,
                                                seed)
        ok &= missed == 0
        notes.append(
            f"served and solo tokens first differ at position {pos} of a "
            f"{len(prompt)}-token prompt; against the reference following "
            f"the served path's own routing ({took[0]:.0f} token-layers at "
            f"its near-ties) and selection ({took[1]:.0f} query-layers) "
            f"{len(toks) - missed} of the {len(toks)} served tokens are its "
            "draw" + ("" if missed == 0 else ": NOT all"))
    _PENDING.clear()
    return ok


def warm_buckets(cell: Cell, cfg, srv) -> None:
    """One request for every final bucket the mix's prompts can end in,
    behind the full chunks a prompt of the mix's least length has: a program
    is compiled apart by what fed it (a fresh cache, a chunk), not by how
    many chunks came before, and no prompt of this mix is shorter than eight
    chunks. The window still refuses a run in which anything compiled."""
    from deepspeed_tpu.serving.scheduler import plan_chunks

    chunk = int(cell.mix["engine"]["prefill_chunk"])
    lo, hi = (int(cell.mix["prompt_tokens"][k]) for k in ("min", "max"))
    rng = rng_for(cell.seed + 3)
    seen = set()
    for p in range(lo, min(hi, lo + chunk) + 1):
        sizes = [c.size for c in plan_chunks(np.zeros(p, np.int32), chunk)]
        shape = (min(len(sizes) - 1, 2), sizes[-1])
        if shape not in seen:
            seen.add(shape)
            srv.submit(rng.integers(0, cfg.vocab_size, p, dtype=np.int32), 2,
                       seed=p)
    srv.drain()
    srv.end_drain()
    srv.results.clear()


def ramp_on(cell: Cell, srv, notes: list) -> None:
    """Behind ``_serving.serve``'s ramp (every slot seated): the mix's
    ``ramp_more_iterations`` more, so that the window opens on slots of
    mixed ages (``backlog_cca``'s way)."""
    first, done = srv._iterations, 0
    while srv._iterations - first < int(
            cell.mix["ramp_more_iterations"]) and srv.sched.queue:
        for r in srv.step():
            srv.results.pop(r.rid, None)
            done += 1
    notes.append(f"the ramp went on for {srv._iterations - first} "
                 f"iterations behind the full slots: {done} requests "
                 f"finished, {len(srv.sched.running)} slots occupied")


def run(cell: Cell) -> Outcome:
    # the harness keeps reading this very cell (the capture's directory is
    # written onto it), so the shared window gets it, not a copy: for the
    # generator the mix is a backlog, and the checks are this file's. The
    # ramp goes on where the shared one hands over to the window
    mix = cell.mix
    cell.mix = dict(mix, kind="backlog")
    shared = (_serving.build, _serving.check_logits, _serving.check_served,
              _serving.warm_buckets, _serving.settle_host)
    shared_settle = shared[4]
    serving: list = []
    notes: list = []

    def warm(cell, cfg, srv):
        serving.append(srv)
        warm_buckets(cell, cfg, srv)

    def settle_host():
        ramp_on(cell, serving[0], notes)
        shared_settle()

    (_serving.build, _serving.check_logits, _serving.check_served,
     _serving.warm_buckets, _serving.settle_host) = (
         build, check_logits, check_served, warm, settle_host)
    try:
        out = _serving.serve(cell, open_loop=False)
        # the slots' cache goes before the reference comes back
        srv = serving.pop()
        srv.close()
        srv._state = srv._prefill = srv._ahead = None
        out.correct &= held_to_the_reference(cell, notes)
        out.notes[:0] = notes
        return out
    finally:
        cell.mix = mix
        (_serving.build, _serving.check_logits, _serving.check_served,
         _serving.warm_buckets, _serving.settle_host) = shared


# ---------------------------------------------------------------- controls
# What each control changes on the REFERENCE's side of the comparison through
# the cache (the system's rows are the system's): a deviation a wrong system
# would compute (``reference.CONTROL``), or its widening.
DEVIATIONS = ("newest-selected", "shared-takes-first",
              "shared-selects-itself", "relu-dropped", "head-weights-dropped",
              "k-norm-dropped", "k-rope-dropped", "q-norm-dropped")
CONTROLS = (*DEVIATIONS, "weights-8bit")


@contextlib.contextmanager
def control(name: str, ref):
    """The reference under control ``name``."""
    was = set(ref.CONTROL), ref.ROUND
    try:
        if name in DEVIATIONS:
            ref.CONTROL.add(name)
        elif name == "weights-8bit":
            ref.ROUND = round8
        else:
            raise ValueError(f"no control {name!r} of the reference")
        yield
    finally:
        ref.CONTROL.clear()
        ref.CONTROL.update(was[0])
        ref.ROUND = was[1]


def first_layer_shares(ref, params, ids, last: int = 64) -> tuple:
    """What the init gives the mechanism, in the reference's arithmetic on
    layer 0 (a ``full`` layer) of one sequence ``ids`` (S,), over its last
    ``last`` queries: (the RMS of the attention branch's output over the
    stream's before it, the share of the FULL causal softmax's sum that the
    selected keys carry, a head's mean, the share of the positions that are
    selected). With near-flat random scores the second is near the third: a
    trained indexer's would be near 1."""
    import jax
    import jax.numpy as jnp

    c = ref.PUBLISHED

    def shares(params, ids):
        S = ids.shape[0]
        w = ref._at(params["layers"][0], 0)
        ip = ref._at(params["indexer"], 0)
        x = ref._f32(params["tok_embed"])[ids]
        eps = c["rms_norm_eps"]
        h, cq = ref.query_latent(x, w, c)
        mask, _, _ = ref.select(h, cq, ip, c)
        out = ref.attention(x, w, c, mask)
        rms = lambda a: jnp.sqrt((a[-last:] ** 2).mean())  # noqa: E731
        # the full softmax of the last queries, head by head
        H, nope, rd, r = (c["num_attention_heads"], c["qk_nope_head_dim"],
                          c["qk_rope_head_dim"], c["kv_lora_rank"])
        theta = float(c["rope_parameters"]["rope_theta"])
        kva = h @ ref._f32(w["wkv_a"])
        lat = ref._rmsnorm(kva[:, :r], ref._f32(w["kv_norm_scale"]), eps)
        k_rope = ref.rope(kva[:, None, r:], theta, rd)[:, 0]
        q = (cq @ ref._f32(w["wq_b"])).reshape(S, H, nope + rd)
        q = jnp.concatenate([q[..., :nope],
                             ref.rope(q[..., nope:], theta, rd)], -1)[-last:]
        k_nope = (lat @ ref._f32(w["wkv_b"])).reshape(
            S, H, -1)[..., :nope]
        s = (jnp.einsum("qhn,shn->hqs", q[..., :nope], k_nope)
             + jnp.einsum("qhr,sr->hqs", q[..., nope:], k_rope)) \
            / (nope + rd) ** 0.5
        causal = jnp.tril(jnp.ones((S, S), bool))[-last:]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        return (rms(out) / rms(x), (p * mask[-last:][None]).sum(-1).mean(),
                mask[-last:].sum() / causal.sum())

    return tuple(float(v) for v in ref.run_highest(
        shares, params, jax.numpy.asarray(ids)))


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import time

    from .. import harness

    ap = argparse.ArgumentParser(
        description="The kind's comparison under each control, the system's "
                    "rows computed once: every one has to fail.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--prompts", default=None,
                    help="check_prompt_tokens for this run, e.g. 24,2100")
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
    longest = max(rows[0], key=len)
    branch, carried, chosen = first_layer_shares(cell.reference, params,
                                                 longest)
    harness.say(
        f"the selection's share, layer 0 of the {len(longest)}-token check "
        f"prompt, its last 64 queries: the attention branch is {branch:.3f} "
        f"of the stream's RMS; the selected keys ({chosen:.3f} of the "
        f"positions) carry {carried:.3f} of the full softmax's sum")
    fails = True
    for name in ("sound", *args.controls.split(",")):
        notes: list = []
        if name == "sound":
            ok = compare_rows(cell, params, rows, notes)
        else:
            with control(name, cell.reference):
                ok = compare_rows(cell, params, rows, notes)
        fails &= ok if name == "sound" else not ok
        for note in notes:
            harness.say(f"{name}: {note}")
        print(json.dumps({"control": name, "correct": bool(ok)}), flush=True)
    return 0 if fails else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())

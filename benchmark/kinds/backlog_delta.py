"""``backlog`` for delta-rule mixers beside gated softmax GQA layers
(``models/kda.py``, ``inference/kinds/delta_gqa.py``: a float32 state and
conv tails a slot beside whole K/V planes, a share of every layer's experts
held): the window, the set-up and the ramp are ``_serving.serve``'s; the
comparisons with the plain reference are this file's, on ``backlog_linear``'s
pattern with the routing as the one thing followed.

**What is compared is what the engine's own programs produced.** The check
prompts go through ``ServingEngine._chunk_impl`` / ``_final_impl`` (the
scheduler's own ``plan_chunks(overlap=False)``: a recurrent state is never
rewound, the last chunk is right-padded), are seated by ``_insert_impl`` in
the engine's OWN slot state (every slot, the prompts taking turns, one slot
in sixteen then retired with a prompt's state in it) and decoded by
``_step_impl``, ``check_decode_steps`` given tokens a slot — the very
functions the timed window jits, with the flags the engine was built with,
each traced here with ONE more output: the logits its sampler was handed
(``backlog_linear.tapped``). No forward is rebuilt in this file, so a kernel
the engine takes is a kernel the comparison sees. Every logit row of every
seated slot — the prompt's last position and each step — is held to the
reference's ONE full forward over prompt + those tokens, the reference
following the routing those programs reported where the experts the two
chose otherwise stand within ``route_gap`` of its own threshold, within
``logit_tolerance``. With the kernels on, every
buffer of a retired slot has to come out of the steps bit-equal. The
reference runs behind the window, when the slots' state is gone: 6.2 GiB of
weights and 6.3 of slots leave it no room before.

**Served requests**: against solo ``generate()`` (its answers made before
the serving engine and its slots exist) and, where they differ, against the
reference following the served path's own routing
(``ServingEngine.routing_log``), every served token its draw.

**Controls** (:data:`CONTROLS`): ``python3 -m benchmark.kinds.backlog_delta
--workload <cell> --seed <n>`` computes the system's rows once and runs this
file's comparison under each control of the reference, at the timed sizes on
the chip (``--rehearse``: the small ones, anywhere); every control has to
come out not correct.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..harness import Cell, Outcome
from ..traffic import rng_for
from . import _serving, backlog_sparse
from .backlog_hybrid import seating
from .backlog_linear import check_logits, tapped
from .backlog_sparse import _rows, check_requests, ramp_on
from .backlog_windowed import build, round8

BUFFERS = ("k", "v", "kda", "conv")


def engine_rows(cell: Cell, cfg, eng, srv, prompts: list, given: list):
    """Per prompt, one entry a slot that ran it: the (1 + steps, V) float32
    logits of the engine's programs and their routing (expert layers, 1,
    prompt + steps, k); and whether the buffers of the retired slots came
    out of the steps bit-equal (None where the step runs without the
    kernels)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.decode import init_cache
    from deepspeed_tpu.serving.scheduler import plan_chunks
    from deepspeed_tpu.serving.slots import retire_slots

    e = cell.mix["engine"]
    slots, max_len, chunk = (int(e[k]) for k in ("slots", "max_len",
                                                 "prefill_chunk"))
    chunk_fn = jax.jit(srv._chunk_impl, donate_argnums=(1,))
    final_fn = jax.jit(tapped(srv, srv._final_impl), donate_argnums=(1,))
    step_fn = jax.jit(tapped(srv, srv._step_impl), donate_argnums=(1,))
    seat = jax.jit(srv._insert_impl, donate_argnums=(0,))
    retire = jax.jit(retire_slots, donate_argnums=(0,))
    i32 = np.int32
    holds, idle = seating(slots, len(prompts))
    ran = [[s for s in range(slots) if holds[s] == i and s not in idle]
           for i in range(len(prompts))]
    params, first, prefill = eng.params, [], []
    key = jax.random.PRNGKey(0)[None]
    with eng.mesh:
        state, srv._state = srv._state, None
        for i, prompt in enumerate(prompts):
            cache = init_cache(cfg, 1, max_len, eng.compute_dtype)
            parts = []
            for ch in plan_chunks(prompt, chunk, overlap=False):
                ids = ch.ids[None]
                if ch.final:
                    (pf, _, chose), row = final_fn(
                        params, cache, ids, i32(ch.start),
                        i32(ch.last_index), i32(ch.true_len), key)
                    real = ch.last_index + 1
                else:
                    cache, _, chose = chunk_fn(params, cache, ids,
                                               i32(ch.start))
                    real = ch.size
                parts.append((ch.start, np.asarray(chose)[:, 0, :real]))
            first.append(np.asarray(row, np.float32)[0])
            prefill.append(parts)
            for s in range(slots):        # the idle ones too, retired below
                if holds[s] == i:
                    state, _ = seat(state, i32(s), pf, i32(2 ** 30))
            del cache, pf
        mask = np.zeros(slots, bool)
        mask[idle] = True
        state = retire(state, jnp.asarray(mask))
        # (a slot at a time: a gather out of the whole planes is a second
        # copy of them beside a full chip)
        before = [[np.asarray(getattr(state.cache, n)[:, s]) for s in idle]
                  for n in BUFFERS] if srv._flash else None
        steps = [[] for _ in range(slots)]
        for t in range(len(given[0])):
            toks = jnp.asarray([given[i][t] for i in holds], jnp.int32)
            (state, read), lg = step_fn(params, state._replace(tok=toks))
            lg, chose = np.asarray(lg, np.float32), np.asarray(read[-1])
            for s in range(slots):
                steps[s].append((lg[s], chose[:, s]))
        untouched = None if before is None else all(
            np.array_equal(a, np.asarray(getattr(state.cache, n)[:, s]))
            for was, n in zip(before, BUFFERS) for a, s in zip(was, idle))
        # the engine takes its slots back as it gave them: nobody running
        srv._state = retire(state, jnp.ones((slots,), bool))
        del state, before
    out = []
    for i, prompt in enumerate(prompts):
        n = len(prompt)
        out.append([
            (np.stack([first[i]] + [lg for lg, _ in steps[s]]),
             _rows(prefill[i] + [(n + t, chose) for t, (_, chose)
                                 in enumerate(steps[s])], n + len(steps[s])))
            for s in ran[i]])
    return out, untouched


def cache_rows(cell: Cell, cfg, eng, srv):
    """The system's side of the comparison: the check prompts and given
    tokens drawn from ``--seed``, and what :func:`engine_rows` read."""
    steps = int(cell.mix["check_decode_steps"])
    lengths = [int(n) for n in cell.mix["check_prompt_tokens"]]
    rng = rng_for(cell.seed + 4)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lengths]
    given = [rng.integers(0, cfg.vocab_size, steps, dtype=np.int32)
             for _ in lengths]
    got, untouched = engine_rows(cell, cfg, eng, srv, prompts, given)
    return prompts, given, got, untouched


def _reference(cell: Cell, params, ids, rows, routing):
    """The reference's rows ``rows`` of ``ids`` (1, S) following
    ``routing``, and (the token-layers that followed, the largest distance
    from the reference's threshold of an expert the two chose otherwise)."""
    import jax

    ref = cell.reference
    want, took = jax.block_until_ready(ref.run_highest(
        lambda p, i, r: ref.logits(p, i, rows=rows, follow=r,
                                   gap=float(cell.mix["route_gap"])),
        params, jax.numpy.asarray(ids), jax.numpy.asarray(routing)))
    return np.asarray(want)[0], tuple(float(t) for t in took)


def compare_rows(cell: Cell, params, rows, notes: list) -> bool:
    """Every row :func:`cache_rows` read against the reference's one full
    forward over prompt + given tokens on ``params``."""
    tol = float(cell.mix["logit_tolerance"])
    prompts, given, got, untouched = rows
    steps = len(given[0])
    ok = untouched is not False
    if not ok:
        notes.append("through the engine's programs: a buffer of a slot at "
                     "length 0 did NOT come out of the steps bit-equal")
    for prompt, toks, ran in zip(prompts, given, got):
        n = len(prompt)
        ids = np.concatenate([prompt, toks])[None]
        # one forward of the reference for the slots whose steps chose alike
        wants: dict = {}
        rel, took = [], (0.0, 0.0)
        for sys_rows, routing in ran:
            key = routing[:, :, n:].tobytes()
            if key not in wants:
                wants[key], t = _reference(
                    cell, params, ids, tuple(range(n - 1, n + steps)),
                    routing)
                took = tuple(max(a, b) for a, b in zip(took, t))
            want = wants[key]
            rel.append(np.where(np.isfinite(sys_rows).all(-1), np.abs(
                sys_rows - want).max(-1) / np.abs(want).max(-1), np.inf))
        rel = np.stack(rel)                          # (slots, 1 + steps)
        good = float(rel.max()) <= tol
        ok &= good
        notes.append(
            f"through the engine's own programs, prompt of {n} prefilled in "
            f"its chunks, seated in {len(ran)} slots, then {steps} given "
            f"tokens decoded with its step: max difference from the float32 "
            f"reference's one full forward {float(rel.max()):.2e} of a row's "
            f"largest logit (the prompt's last position "
            f"{rel[:, 0].max():.2e}, the steps {rel[:, 1:].min():.2e} to "
            f"{rel[:, 1:].max():.2e}; {'within' if good else 'OUTSIDE'} "
            f"{tol:.1e}); the reference followed the path's experts for up "
            f"to {took[0]:.0f} token-layers (the experts the two chose "
            f"otherwise lie at most {took[1]:.3e} from the reference's "
            f"threshold; route_gap {float(cell.mix['route_gap']):g}), once "
            f"for each of "
            f"the {len(wants)} choices the slots' steps took; retired slots "
            f"bit-equal: {untouched}")
    return ok


# the served requests that differ from solo generate()'s answers (made before
# the serving engine and its slots exist: ``backlog_linear.check_logits``
# keeps them in ``backlog_sparse._SOLO``), held to the reference once the
# window is over and the slots are gone
_PENDING: list = []


def check_served(cell: Cell, cfg, eng, srv, notes: list) -> bool:
    """Served requests against solo ``generate()``'s answers; one that
    differs is held to the reference behind the window
    (:func:`held_to_the_reference`)."""
    ok = True
    _PENDING.clear()
    srv.routing_log = {}
    try:
        for (k, p, n, prompts, seeds), solo in zip(
                check_requests(cell, cfg), backlog_sparse._SOLO):
            rids = [srv.submit(prompts[i], n, seed=seeds[i]) for i in range(k)]
            srv.drain()
            srv.end_drain()
            got = [np.asarray(srv.pop_result(r).tokens) for r in rids]
            for i in range(k):
                if len(got[i]) != n:
                    ok = False
                    notes.append(f"served answer of {len(got[i])} tokens, "
                                 f"asked for {n}")
                elif not (got[i] == solo[i]).all():
                    _PENDING.append((
                        eng, prompts[i], got[i], srv.routing_log[rids[i]],
                        seeds[i], int(np.nonzero(got[i] != solo[i])[0][0])))
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
    against the reference following the served path's own routing: every
    served token has to be the draw of the reference's logits at its
    position (within twice the logit tolerance of the row's largest)."""
    import jax

    tol = float(cell.mix["logit_tolerance"])
    ok = True
    for eng, prompt, toks, log, seed, pos in _PENDING:
        P, n = len(prompt), len(toks)
        ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])[None]
        routing = _rows([(start, np.asarray(chose)) for start, chose in log],
                        P + n - 1)
        rows, took = _reference(cell, eng.params, ids,
                                tuple(range(P - 1, P + n - 1)), routing)
        key, missed = jax.random.PRNGKey(int(seed)), 0
        for t in range(n):
            key, sub = jax.random.split(key)
            val = rows[t] + np.asarray(jax.random.gumbel(
                sub, rows[t].shape, np.float32))
            missed += val[int(toks[t])] < val.max() \
                - 2 * tol * np.abs(rows[t]).max()
        ok &= missed == 0
        notes.append(
            f"served and solo tokens first differ at position {pos} of a "
            f"{P}-token prompt; against the reference following the served "
            f"path's own routing ({took[0]:.0f} token-layers at its "
            f"near-ties, at most {took[1]:.3e} from its threshold) "
            f"{n - int(missed)} of the {n} served tokens are its draw"
            + ("" if missed == 0 else ": NOT all"))
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


def run(cell: Cell) -> Outcome:
    # the harness keeps reading this very cell, so the shared window gets
    # it, not a copy: for the generator the mix is a backlog, and the checks
    # are this file's. The ramp goes on where the shared one hands over
    mix = cell.mix
    cell.mix = dict(mix, kind="backlog")
    shared = (_serving.build, _serving.check_logits, _serving.check_served,
              _serving.warm_buckets, _serving.settle_host)
    shared_settle = shared[4]
    serving: list = []
    notes: list = []
    kept: dict = {}

    def served(cell, cfg, eng, srv, notes):
        ok = check_served(cell, cfg, eng, srv, notes)
        kept.update(eng=eng, rows=cache_rows(cell, cfg, eng, srv))
        return ok

    def warm(cell, cfg, srv):
        serving.append(srv)
        warm_buckets(cell, cfg, srv)

    def settle_host():
        ramp_on(cell, serving[0], notes)
        shared_settle()

    (_serving.build, _serving.check_logits, _serving.check_served,
     _serving.warm_buckets, _serving.settle_host) = (
         build, check_logits, served, warm, settle_host)
    try:
        out = _serving.serve(cell, open_loop=False)
        # the slots' state goes before the reference comes
        srv = serving.pop()
        srv.close()
        srv._state = srv._prefill = srv._ahead = None
        out.correct &= compare_rows(cell, kept["eng"].params, kept["rows"],
                                    notes)
        out.correct &= held_to_the_reference(cell, notes)
        out.notes[:0] = notes
        return out
    finally:
        cell.mix = mix
        (_serving.build, _serving.check_logits, _serving.check_served,
         _serving.warm_buckets, _serving.settle_host) = shared


# ---------------------------------------------------------------- controls
# What each control changes on the REFERENCE's side of the comparison (the
# system's rows are the system's): a reading of the config that the
# configuration file's ``assumed`` excludes (``reference.CONTROL``), or the
# reference's widening.
DEVIATIONS = ("gate-floored", "beta-sigmoid", "out-gate-dropped",
              "out-gate-per-head", "rope-on-attention", "softmax-router")
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


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import time

    import deepspeed_tpu as ds

    from .. import harness

    ap = argparse.ArgumentParser(
        description="The kind's comparison under each control, the system's "
                    "rows computed once: every one has to fail.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--prompts", default=None,
                    help="check_prompt_tokens for this run, e.g. 510,2101")
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
    srv = ds.ServingEngine(eng, dict(cell.mix["engine"]),
                           clock=time.perf_counter)
    rows = cache_rows(cell, cfg, eng, srv)
    srv.close()
    srv._state = None
    del srv
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

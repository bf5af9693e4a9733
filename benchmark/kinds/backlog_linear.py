"""``backlog`` for delta-rule mixers beside latent attention over an indexer's
selection (``models/kda.py``, ``inference/kinds/linear_sparse.py``: a float32
state and conv tails a slot beside latents a row and pooled indexer keys, a
share of every expert layer's experts held): the window, the set-up and the
ramp are ``_serving.serve``'s; the comparisons with the plain reference are
this file's.

**What is compared is what the engine's own programs produced.** The check
prompts go through ``ServingEngine._chunk_impl`` / ``_final_impl`` (the
scheduler's own ``plan_chunks(overlap=False)``: a recurrent state is never
rewound, the last chunk is right-padded), are seated by ``_insert_impl`` in
the engine's OWN slot state (every slot, the prompts taking turns, one slot
in sixteen then retired with a prompt's state in it) and decoded by
``_step_impl``, ``check_decode_steps`` given tokens a slot — the very
functions the timed window jits, with the flags the engine was built with,
each traced here with ONE more output: the logits its sampler was handed
(:func:`tapped`). No forward is rebuilt in this file, so a kernel the engine
takes is a kernel the comparison sees. Every logit row of every seated slot —
the prompt's last position and each step — is held to the reference's ONE
full forward over prompt + those tokens, the reference following the routing
and the selection those programs reported at its own near-ties
(``route_gap``, ``select_gap``: ``reference/glm5_next.py``), within
``logit_tolerance``. With the kernels on, every buffer of a retired slot has
to come out of the steps bit-equal. The reference runs behind the window,
when the slots' state is gone: 8.8 GiB of weights and 3.9 of slots leave it
no room before.

**Served requests**: against solo ``generate()`` and, where they differ,
against the reference following the served path's own choices
(``backlog_sparse``'s way and functions).

**Controls** (:data:`CONTROLS`): ``python3 -m benchmark.kinds.backlog_linear
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
from .backlog_sparse import _rows, check_requests, held_to_the_reference
from .backlog_windowed import build, round8

BUFFERS = ("ik", "c", "kda", "conv", "ikt")


def tapped(srv, impl):
    """``impl`` (one of the serving engine's program functions) with the
    logits its sampler was handed as one more result."""
    def run(*args):
        sampler, seen = srv._sampler, []

        def tap(logits, key):
            seen.append(logits)
            return sampler(logits, key)

        srv._sampler = tap
        try:
            out = impl(*args)
        finally:
            srv._sampler = sampler
        return out, seen[0]
    return run


def engine_rows(cell: Cell, cfg, eng, srv, prompts: list, given: list):
    """Per prompt, one entry a slot that ran it: the (1 + steps, V) float32
    logits of the engine's programs, their routing (expert layers, 1, prompt
    + steps, k) and their selection (attention layers, 1, prompt + steps,
    K'); and whether the buffers of the retired slots came out of the steps
    bit-equal (None where the step runs without the kernels)."""
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
            parts = ([], [])
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
                for part, a in zip(parts, chose):
                    part.append((ch.start, np.asarray(a)[:, 0, :real]))
            first.append(np.asarray(row, np.float32)[0])
            prefill.append(parts)
            for s in range(slots):        # the idle ones too, retired below
                if holds[s] == i:
                    state, _ = seat(state, i32(s), pf, i32(2 ** 30))
            del cache, pf
        mask = np.zeros(slots, bool)
        mask[idle] = True
        state = retire(state, jnp.asarray(mask))
        at = np.asarray(idle)
        before = [np.asarray(getattr(state.cache, n)[:, at])
                  for n in BUFFERS] if srv._flash else None
        steps = [[] for _ in range(slots)]
        for t in range(len(given[0])):
            toks = jnp.asarray([given[i][t] for i in holds], jnp.int32)
            (state, read), lg = step_fn(params, state._replace(tok=toks))
            lg = np.asarray(lg, np.float32)
            chose = [np.asarray(a) for a in read[-1]]
            for s in range(slots):
                steps[s].append((lg[s], [a[:, s] for a in chose]))
        untouched = None if before is None else all(
            np.array_equal(a, np.asarray(getattr(state.cache, n)[:, at]))
            for a, n in zip(before, BUFFERS))
        # the engine takes its slots back as it gave them: nobody running
        srv._state = retire(state, jnp.ones((slots,), bool))
        del state, before
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
        rel, took = [], (0.0, 0.0, 0.0)
        for sys_rows, follow in ran:
            key = b"".join(a[:, :, n:].tobytes() for a in follow)
            if key not in wants:
                wants[key], t = backlog_sparse._reference(
                    cell, params, ids, tuple(range(n - 1, n + steps)), follow)
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
            f"to {took[0]:.0f} token-layers and its selection for up to "
            f"{took[1]:.0f} query-layers (the sets differed at most "
            f"{took[2]:.3e} from the reference's threshold; select_gap "
            f"{float(cell.mix['select_gap']):g}), once for each of the "
            f"{len(wants)} choices the slots' steps took; retired slots "
            f"bit-equal: {untouched}")
    return ok


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    """Before the serving engine stands: solo ``generate()``'s answers to
    the check requests, for :func:`backlog_sparse.check_served`."""
    max_len = int(cell.mix["engine"]["max_len"])
    backlog_sparse._SOLO[:] = [[np.asarray(eng.generate(
        prompts[i:i + 1], n, request_seeds=seeds[i:i + 1],
        cache_len=max_len))[0] for i in range(k)]
        for k, _, n, prompts, seeds in check_requests(cell, cfg)]
    return True


def warm_buckets(cell: Cell, cfg, srv) -> None:
    """One request for every final bucket the mix's prompts can end in,
    behind none, one and two full chunks: a program is compiled apart by
    what fed it (a fresh cache, a chunk), not by how many chunks came
    before. The window still refuses a run in which anything compiled."""
    from deepspeed_tpu.serving.scheduler import plan_chunks

    chunk = int(cell.mix["engine"]["prefill_chunk"])
    lo, hi = (int(cell.mix["prompt_tokens"][k]) for k in ("min", "max"))
    rng = rng_for(cell.seed + 3)
    seen = set()
    for p in range(lo, min(hi, 3 * chunk) + 1):
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

    def check_served(cell, cfg, eng, srv, notes):
        ok = backlog_sparse.check_served(cell, cfg, eng, srv, notes)
        kept.update(eng=eng, rows=cache_rows(cell, cfg, eng, srv))
        return ok

    def warm(cell, cfg, srv):
        serving.append(srv)
        warm_buckets(cell, cfg, srv)

    def settle_host():
        backlog_sparse.ramp_on(cell, serving[0], notes)
        shared_settle()

    (_serving.build, _serving.check_logits, _serving.check_served,
     _serving.warm_buckets, _serving.settle_host) = (
         build, check_logits, check_served, warm, settle_host)
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
# system's rows are the system's): a deviation a wrong system would compute
# (``reference.CONTROL``), or its widening.
DEVIATIONS = ("state-bf16", "sinkhorn-once", "open-group-unread",
              "max-for-mean", "clamp-dropped", "gate-unbounded")
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

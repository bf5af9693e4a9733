"""``backlog`` for a trunk of window layers beside full ones
(``models/windowed.py``: planes for the full layers beside a ring a slot for
each window layer, keys wider than values, a share of every expert layer's
experts held): the window, the set-up, the ramp and every other check are
``_serving.serve``'s; the comparisons with the plain reference are this
file's, put together from ``backlog_routed`` and ``backlog_hybrid`` as those
were.

**Routing.** The experts are a top-k over scores, so the reference follows
the system's choice at its own near-ties (``route_gap``) and nowhere else
(``backlog_routed``, top of file); the forward's comparison is that file's
``check_logits`` as it stands.

**Through the cache.** ``InferenceEngine.forward`` has no cache and solo
``generate()`` shares the cache code, so neither would notice a ring that
dropped a position at its wrap, a window one position short, a sink left
out of the kernel's sum, or a slot reading its predecessor's ring. So each
of the mix's ``check_prompt_tokens`` prompts is prefilled in the engine's
own chunks (``plan_chunks(..., overlap=False)``: a ring is never rewound,
the last chunk is right-padded) into a batch-1 cache of the slots'
``max_len`` and seated (``insert_request``) in a cache of the slots' shape —
**in every slot, the prompts taking turns, and one slot in sixteen is then
retired** with a prompt's planes and rings in it: the window that is timed
runs nearly full, and the kernels depend on who is running. Then
``check_decode_steps`` given tokens are decoded through what the slot-step
program runs (``forward_with_cache`` on per-slot lengths with the decode
kernels, all slots in one batch). Every logit row of every seated slot — the
prompt's last position and each step — is held to the reference's ONE full
forward over prompt + those tokens, following the routing those very
programs reported, within ``logit_tolerance``. With the decode kernels on
(the chip) the planes and rings of every retired slot have to come out
bit-equal: a row at length 0 is not running.

**The held experts' product alone.** 16 of 256 experts are held, so the
expert products carry a sixteenth of what they carry in the whole model and
the logits hardly notice their precision. Each expert layer is therefore also
run by itself — ``MoETransformerLM.experts`` on ``expert_check_rows`` rows
drawn normal from ``--seed`` against the reference's ``experts`` on the same
rows, following the system's choice at its own near-ties — and held to
``expert_tolerance`` of the layer's largest output.

**The weights** come from the mix's ``weights_seed``, not from ``--seed``
(``weights_seed_why``): ``--seed`` draws every token id and every sampling
seed.

**Served requests** are ``backlog_routed.check_served``.

**Controls** (:data:`CONTROLS`): ``python3 -m benchmark.kinds.backlog_windowed
--workload <cell> --seed <n>`` computes the system's rows once and runs this
file's comparisons on them under each control, at the timed sizes on the chip
(``--rehearse``: the small ones, anywhere); every control has to come out
not correct.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from ..harness import Cell, Outcome
from ..traffic import rng_for
from . import _serving, backlog_routed
from .backlog_hybrid import seating
from .backlog_routed import check_served

BUFFERS = ("k", "v", "wk", "wv")


def through_the_cache(cell: Cell, cfg, eng, prompts: list, given: list):
    """Per prompt, one entry a slot that ran it: the (1 + steps, V) float32
    logits of the cache path and its routing (expert layers, 1, prompt +
    steps, k); and whether the buffers of the retired slots came out of the
    steps bit-equal (None where the step runs without the decode kernels,
    whose dense append lands in an idle row's own extent)."""
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
        at = jnp.asarray(idle, i32)
        cache = cache._replace(length=cache.length.at[at].set(0))
        before = [np.asarray(getattr(cache, n)[:, at]) for n in BUFFERS] \
            if flash else None
        steps = [[] for _ in range(slots)]
        for t in range(len(given[0])):
            toks = jnp.asarray([given[i][t] for i in holds], i32)
            lg, cache, r = step_fn(eng.params, cache, toks)
            lg, r = np.asarray(lg, np.float32), np.asarray(r)
            for s in range(slots):
                steps[s].append((lg[s], r[:, s:s + 1]))
        untouched = None if before is None else all(
            np.array_equal(a, np.asarray(getattr(cache, n)[:, at]))
            for a, n in zip(before, BUFFERS))
        del cache, before
    return ([[(np.stack([first[i]] + [lg for lg, _ in steps[s]]),
               np.concatenate(prefill[i] + [r for _, r in steps[s]], axis=2))
              for s in ran[i]] for i in range(len(prompts))], untouched)


_shared_build = _serving.build


def build(cell: Cell):
    """``_serving.build`` with the weights drawn from the mix's
    ``weights_seed`` (``weights_seed_why``)."""
    return _shared_build(dataclasses.replace(
        cell, seed=int(cell.mix["weights_seed"])))


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


def compare_rows(cell: Cell, params, rows, notes: list) -> bool:
    """Every row :func:`cache_rows` read against the reference's one full
    forward over prompt + given tokens on ``params``."""
    import jax

    ref = cell.reference
    tol, gap = float(cell.mix["logit_tolerance"]), float(cell.mix["route_gap"])
    prompts, given, got, untouched = rows
    steps = len(given[0])
    ok = untouched is not False
    if not ok:
        notes.append("through the cache: the planes and rings of a slot at "
                     "length 0 did NOT come out of the steps bit-equal")
    for prompt, toks, ran in zip(prompts, given, got):
        n = len(prompt)
        ids = jax.numpy.asarray(np.concatenate([prompt, toks])[None])
        # one forward of the reference for the slots whose steps routed alike
        wants: dict = {}
        rel, followed = [], 0
        for sys_rows, route in ran:
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
            f"followed the path's experts for up to {followed} token-layers, "
            f"once for each of the {len(wants)} routings the slots' steps "
            f"took; retired slots bit-equal: {untouched}")
    return ok


def round8(a):
    """A float rounded to the 3 mantissa bits of e4m3, its exponent kept:
    the nearest precision below bf16's 7, with no scale to choose."""
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)


def check_experts(cell: Cell, cfg, params, eng, notes: list,
                  rounded: bool = False) -> bool:
    """Every expert layer by itself, the system's sorted rows against the
    reference's every-held-expert-on-every-token, on the same normal rows.
    The layer's bank is sliced out here (the step hands the kernel the
    segment's bank and an index; that path is the cache comparison's).
    ``rounded``: the control, the system's three matrices through
    :func:`round8`."""
    import jax
    import jax.numpy as jnp

    ref, model = cell.reference, eng.model
    tol, gap = float(cell.mix["expert_tolerance"]), float(cell.mix["route_gap"])
    rows = int(cell.mix["expert_check_rows"])
    banks = model.BANKS

    def system(y, layer):
        if rounded:
            layer = {k: round8(v) if k in banks else v
                     for k, v in layer.items()}
        out, _, idx = model.experts(y, layer)
        return out, idx

    system = jax.jit(system)
    key = jax.random.PRNGKey(Cell.jax_seed(cell) + 5)
    rel, followed, at = [], 0, 0
    for (ffn, n), seg in zip(cfg.segments,
                             model.segment_params(params["layers"])):
        for i in range(n if ffn == "moe" else 0):
            layer = {k: seg[k][i] for k in
                     (*banks, "router", "router_bias")}
            y = jax.random.normal(jax.random.fold_in(key, at + i),
                                  (1, rows, cfg.d_model), eng.compute_dtype)
            with eng.mesh:
                got, idx = system(y, layer)
            want, took = ref.run_highest(
                lambda y, w, theirs: ref.experts(y, w, ref.PUBLISHED, theirs,
                                                 gap),
                y[0].astype(jnp.float32), layer, idx[0])
            got, want = np.asarray(got[0], np.float32), np.asarray(want)
            rel.append(float(np.abs(got - want).max() / np.abs(want).max())
                       if np.isfinite(got).all() else np.inf)
            followed += int(took)
            del layer
        at += n
    good = max(rel) <= tol
    notes.append(
        f"the held experts' product alone, {rows} normal rows a layer: max "
        f"difference from the float32 reference "
        + ", ".join(f"{r:.2e}" for r in rel) + " of the layer's largest "
        f"output ({'within' if good else 'OUTSIDE'} {tol:.1e}); the "
        f"reference followed the system's experts for {followed} rows")
    return good


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    # the forward, with the routing it reported: backlog_routed's
    ok = backlog_routed.check_logits(cell, cfg, params, eng, notes)
    ok &= check_experts(cell, cfg, params, eng, notes)
    return compare_rows(cell, params, cache_rows(cell, cfg, eng), notes) and ok


def warm_buckets(cell: Cell, cfg, srv) -> None:
    """One request for every final bucket the mix's prompts can end in: on
    a fresh cache where a prompt that short exists, behind one full chunk,
    and behind two (a chunk fed by a chunk). The shared kind warms every
    distinct SEQUENCE of chunk sizes; prompts of 128 to 24 576 in chunks of
    512 have 336 of them, eight thousand chunks of set-up, for the same
    dozen programs: a program is compiled apart by what fed it (a fresh
    cache, a chunk), not by how many chunks came before. The window still
    refuses a run in which anything compiled."""
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
    # the harness keeps reading this very cell (the capture's directory is
    # written onto it), so the shared window gets it, not a copy: for the
    # generator the mix is a backlog, and the checks are this file's
    mix = cell.mix
    cell.mix = dict(mix, kind="backlog")
    shared = (_serving.build, _serving.check_logits, _serving.check_served,
              _serving.warm_buckets)
    (_serving.build, _serving.check_logits, _serving.check_served,
     _serving.warm_buckets) = build, check_logits, check_served, warm_buckets
    try:
        return _serving.serve(cell, open_loop=False)
    finally:
        cell.mix = mix
        (_serving.build, _serving.check_logits, _serving.check_served,
         _serving.warm_buckets) = shared


# ---------------------------------------------------------------- controls
# What each control changes on the REFERENCE's side of the comparison through
# the cache (the system's rows are the system's): published keys, a leaf of
# the weights it is handed, its router, its widening. "experts-8bit" is the
# other way round: the SYSTEM's expert matrices rounded, in check_experts.
KEYS = {"window-127": {"sliding_window": 127},
        "no-window": {"sliding_window": 1 << 30},
        "value-scale-dropped": {"attention_value_scale": 1.0},
        "rope-on-all-dims": {"partial_rotary_factor": 1.0}}
LEAVES = {"sink-dropped": ("sink", -1e4),   # exp(-1e4 - m) = 0 in the sum
          "bias-dropped": ("router_bias", 0.0)}
CONTROLS = (*KEYS, "thetas-swapped", *LEAVES, "held-only-weights",
            "weights-8bit", "experts-8bit")


def _held_only(router):
    """The reference's router with the weights normalised over the experts
    held here: an absent expert's share not left out."""
    def wrapped(y, w, c, follow=None, gap: float = 0.0):
        g, took = router(y, w, c, follow, gap)
        lo, n = c["first_held"], c["n_routed_experts"]
        held = g[:, lo:lo + n]
        return g.at[:, lo:lo + n].set(
            held / (held.sum(-1, keepdims=True) + 1e-20)), took
    return wrapped


@contextlib.contextmanager
def control(name: str, ref, params):
    """The reference under control ``name``; yields the weights to hand
    it."""
    import jax.numpy as jnp

    was = dict(ref.PUBLISHED), ref.ROUND, ref.router
    try:
        if name in KEYS:
            ref.PUBLISHED.update(KEYS[name])
        elif name == "thetas-swapped":
            ref.PUBLISHED.update(rope_theta=was[0]["swa_rope_theta"],
                                 swa_rope_theta=was[0]["rope_theta"])
        elif name in LEAVES:
            leaf, value = LEAVES[name]
            params = {**params, "layers": tuple(
                {**seg, leaf: jnp.full_like(seg[leaf], value)}
                if leaf in seg else seg for seg in params["layers"])}
        elif name == "held-only-weights":
            ref.router = _held_only(ref.router)
        elif name == "weights-8bit":
            ref.ROUND = round8
        else:
            raise ValueError(f"no control {name!r} of the reference")
        yield params
    finally:
        ref.PUBLISHED.clear()
        ref.PUBLISHED.update(was[0])
        ref.ROUND, ref.router = was[1:]


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import time

    from .. import harness

    ap = argparse.ArgumentParser(
        description="The kind's comparisons under each control, the "
                    "system's rows computed once: every one has to fail.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--prompts", default=None,
                    help="check_prompt_tokens for this run, e.g. 131,1532")
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
    fails = True
    for name in ("sound", *args.controls.split(",")):
        notes: list = []
        if name == "sound":
            ok = check_experts(cell, cfg, params, eng, notes)
            ok &= compare_rows(cell, params, rows, notes)
        elif name == "experts-8bit":
            ok = check_experts(cell, cfg, params, eng, notes, rounded=True)
        else:
            with control(name, cell.reference, params) as theirs:
                ok = compare_rows(cell, theirs, rows, notes)
        fails &= ok if name == "sound" else not ok
        for note in notes:
            harness.say(f"{name}: {note}")
        print(json.dumps({"control": name, "correct": bool(ok)}), flush=True)
    return 0 if fails else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())

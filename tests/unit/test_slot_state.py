"""The slot state's contract (docs/SERVING.md, "The slot state"): a row that
is not running is ``done`` and stands at length 0 from the step after its
last token until the next insert, whatever ended its request; the decode
step leaves such a row where it is, so it is neither fetched nor written;
and the host's mirror of the lengths follows the device exactly.

Three cache kinds run the one rule (``inference/decode.py``
``forward_with_cache``: ``new_len``): K and V contiguous (on the kernels and
on XLA's own ops), the latent buffer, the page pool.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _fake_clock import TickClock

import deepspeed_tpu as ds
from deepspeed_tpu.inference.decode import (GenCarry, decode_step,
                                            forward_with_cache, init_cache)
from deepspeed_tpu.inference.sampling import sample_logits
from deepspeed_tpu.models import build_model, deepseek_v3, tiny_test
from deepspeed_tpu.serving.pages import init_paged_slots, insert_paged
from deepspeed_tpu.serving.scheduler import RequestStatus
from deepspeed_tpu.serving.slots import (init_slots, insert_request,
                                         retire_slots)

S = 128                 # a slot's positions: one lane tile
PS = 8                  # page size of the paged kind
# ("contiguous": heads of 16, whose step writes the block back every time;
# "contiguous-tail": one head of 64, K beside V a whole lane tile, whose
# cache keeps the deferred tail of ``inference/kinds/dense.py``)
KINDS = ["contiguous", "contiguous-tail", "latent", "paged"]
TAIL_ROWS = 8           # of float32
_BUILT = {}


def _model(kind):
    """(cfg, model, params) of a kind's tiny model, built once a process."""
    name = "latent" if kind.startswith("latent") else \
        "tail" if "-tail" in kind else "dense"
    if name not in _BUILT:
        cfg = (deepseek_v3("tiny", dtype=jnp.float32, max_seq=S)
               if name == "latent" else
               tiny_test(n_layer=2, vocab_size=256, max_seq=S, d_ff=128,
                         dtype=jnp.float32,
                         **({"n_head": 1} if name == "tail" else {})))
        model = build_model(cfg)
        _BUILT[name] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _BUILT[name]


def _one_device_mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def _engine(kind, eos=None):
    cfg, model, params = _model(kind)
    return ds.init_inference(
        model, params,
        {"dtype": "float32", "eos_token_id": eos,
         # the kernels where a kind has them at T = 1 (interpreted here)
         "flash_decode": kind != "paged"},
        mesh=_one_device_mesh())


def _serving(kind, eng, slots=2, clock=None, **extra):
    conf = {"slots": slots, "max_len": S, "prefill_chunk": 16,
            "greedy": True, "spans": True, **extra}
    if kind == "paged":
        conf.update(page_size=PS, prefix_sharing=False)
    return ds.ServingEngine(eng, conf, clock=clock)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(8, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _check(srv, settle=True):
    """The device's lengths against the scheduler's own account (a running
    request has all but its newest token cached, every other row stands at
    0 and is ``done``) and, where the engine keeps one, against the mirror.
    The books run one step behind the device (docs/SERVING.md, "The host
    loop"), so the step in flight is read back first; ``settle`` False
    leaves it out and holds the account to what it can know meanwhile: a
    row the books have running is one position further on the device, or
    at 0 where the step in flight ended it, and the mirror says the
    former."""
    if settle:
        srv._settle()
    dev = np.asarray(srv._state.cache.length)
    want = np.zeros_like(dev)
    flying = srv._inflight is not None
    for slot, req in srv.sched.running.items():
        want[slot] = req.prompt_len + len(req.tokens) - 1 + flying
    if flying:
        assert ((dev == want) | (dev == 0)).all()
        np.testing.assert_array_equal(np.asarray(srv._state.done), dev == 0)
        if srv._slot_len is not None:
            np.testing.assert_array_equal(srv._slot_len, want)
            np.testing.assert_array_equal(srv._inflight.lens, want)
        return
    np.testing.assert_array_equal(dev, want)
    np.testing.assert_array_equal(np.asarray(srv._state.done), want == 0)
    if srv._slot_len is not None:
        np.testing.assert_array_equal(srv._slot_len, dev)


# ---------------------------------------------- (a) the mirror and the device
@pytest.mark.parametrize("books", ["settled", "a_step_behind"])
@pytest.mark.parametrize("reason", ["max_new", "eos", "cancel", "deadline",
                                    "nonfinite"])
@pytest.mark.parametrize("kind", KINDS)
def test_device_lengths_follow_every_retirement(kind, reason, books):
    """Place two requests into two slots, step, retire one of them for
    ``reason``, place a third into the slot that came free, drain: after
    EVERY iteration the device's ``length`` is the scheduler's account of
    it, the mirror (the contiguous cache on the kernels keeps one) equals
    it, and no ``decode_step`` span saw a position fetched for a row that
    was not running. ``settled``: the step in flight is read back before
    every look; ``a_step_behind``: the loop runs as it does in service,
    each step out before the one in front of it is read."""
    settle = books == "settled"
    cfg, model, params = _model(kind)
    a, b, c = _prompts(cfg, (20, 9, 13))
    eos, extra = None, {}
    clock = TickClock()
    if reason == "eos":
        # the token request ``a`` emits third becomes the eos
        solo = np.asarray(_engine(kind).generate(
            jnp.asarray(a[None]), 8, greedy=True, request_seeds=[1],
            cache_len=S))[0]
        assert solo[2] not in solo[:2], "pick another prompt seed"
        eos = int(solo[2])
    if reason == "nonfinite":
        extra["chaos"] = {"enabled": True, "seed": 0,
                          "nonfinite_decode_step": 2}
    srv = _serving(kind, _engine(kind, eos), clock=clock, **extra)
    assert (srv._slot_len is not None) == kind.startswith("contiguous")
    assert (getattr(srv._state.cache, "tail", None) is not None) == (
        kind == "contiguous-tail")
    lens, counts = [], srv._attn_counts
    srv._attn_counts = lambda fl: lens.append(fl.lens) or counts(fl)
    _check(srv)
    ra = srv.submit(a, 8 if reason != "max_new" else 4, seed=1,
                    total_deadline_s=5.0 if reason == "deadline" else None)
    rb = srv.submit(b, 16, seed=2)
    rc = None
    ended = {}
    for it in range(200):
        for req in srv.step():
            ended[req.rid] = req
        if it == 2:
            assert len(srv.sched.running) == 2, "both seated by now"
        _check(srv, settle)
        if it == 4:
            if reason == "cancel":
                ended[ra] = srv.cancel(ra)
                _check(srv, settle)
            if reason == "deadline":
                clock.advance(10.0)
        if rc is None and len(ended) == 1:
            # the only free slot is the one that request left
            freed = next(iter(ended.values())).slot
            rc = srv.submit(c, 5, seed=3)
        if len(ended) == 3:
            break
    assert set(ended) == {ra, rb, rc}
    assert ended[rc].slot == freed and len(ended[rc].tokens) == 5
    first = ended[ra] if reason != "nonfinite" else next(
        r for r in ended.values() if r.status is RequestStatus.NONFINITE)
    assert first.status is {
        "max_new": RequestStatus.OK, "eos": RequestStatus.OK,
        "cancel": RequestStatus.CANCELLED, "deadline": RequestStatus.TIMEOUT,
        "nonfinite": RequestStatus.NONFINITE}[reason]
    if reason == "eos":
        assert len(first.tokens) == 3 and first.tokens[-1] == eos
    if reason == "max_new":
        assert len(first.tokens) == 4
    # everything has ended: every row stands at 0
    _check(srv)
    assert not np.asarray(srv._state.cache.length).any()
    assert not settle or not srv.stats.registry.counter(
        "Serve/decode_steps_ahead").value
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    assert steps
    assert {e.meta["ahead"] for e in steps} == (
        {0} if settle or reason == "nonfinite" else {0, 1})
    # (the last step out, which went before the host knew that nothing was
    # left running, ran no row and says nothing of fetches)
    steps = [e for e in steps if e.meta["slots"]]
    if kind.startswith("contiguous"):
        assert [e.meta["idle_fetched"] for e in steps] == [0] * len(steps)
        # from the lengths each step left: a block a running request and
        # for no other row, in every step; with a tail of T rows the
        # request's tile in and out, and the block where its group ended
        T = TAIL_ROWS if kind == "contiguous-tail" else 0
        lens = [n for n in lens if n.any()]
        assert len(lens) == len(steps)
        for e, n in zip(steps, lens):
            live = n > 0
            moved = 128 * live.sum() if not T else \
                2 * T * live.sum() + 128 * (live & (n % T == 0)).sum()
            assert e.meta["append_moved_over_new"] == moved / e.meta["slots"]
        assert T or {e.meta["append_moved_over_new"] for e in steps} \
            == {128.0}
    # what the device cannot foresee costs one small program where it
    # happens; what it can (eos, the budget) costs none
    assert ("retire" in srv._programs) == (
        reason in ("cancel", "deadline", "nonfinite"))


def test_a_handed_off_request_leaves_its_row_at_length_0():
    """``release_request`` (the disaggregated hand-off through
    ``on_placed``) frees a slot the iteration its request was placed: the
    row is unseated at once and the payload carries the tokens left."""
    kind = "paged"
    cfg, model, params = _model(kind)
    srv = _serving(kind, _engine(kind))
    dst = _serving(kind, _engine(kind))
    moved = []

    def hand_off(req, slot):
        payload = srv.export_request(req)
        srv.release_request(req)
        moved.append((req, payload))

    srv.on_placed = hand_off
    a, = _prompts(cfg, (20,))
    srv.submit(a, 6, seed=1)
    for _ in range(4):
        srv.step()
        _check(srv)
    (req, payload), = moved
    assert int(payload["left"][0]) == 5 and int(payload["length"][0]) == 20
    assert not srv.sched.running
    assert dst.import_request(req, payload)
    _check(dst)
    while dst.sched.running:
        dst.step()
        _check(dst)
    want = np.asarray(_engine(kind).generate(
        jnp.asarray(a[None]), 6, greedy=True, request_seeds=[1],
        cache_len=S))[0]
    np.testing.assert_array_equal(req.tokens, want)


# ------------------------------ (b) a row that is not running touches nothing
def _prefilled(kind, prompt):
    """A request's batch-1 carry after its prompt, as the engine's final
    chunk leaves it."""
    cfg, model, params = _model(kind)
    cache = init_cache(cfg, 1, S)
    logits, cache = forward_with_cache(
        model, params, jnp.asarray(prompt[None]), cache,
        last_token_head=True)
    return GenCarry(tok=jnp.argmax(logits[:, -1], -1).astype(jnp.int32),
                    cache=cache, rng=jnp.zeros((1, 2), jnp.uint32),
                    done=jnp.zeros((1,), bool))


def _noise(like, seed):
    rng = np.random.default_rng(seed)
    if like.dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 127, like.shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(like.shape) * 1e3, like.dtype)


def _seated(kind, rows, idle=()):
    """A slot state of ``len(rows) + len(idle)`` slots: ``rows`` maps slot →
    prefilled carry; the slots of ``idle`` are not running but hold what a
    previous occupant left: noise in every buffer of theirs, a token, and
    (the page pool) a table row that still names a RUNNING row's pages."""
    cfg, model, params = _model(kind)
    n = len(rows) + len(idle)
    paged = kind.startswith("paged")
    if paged:
        per = S // PS
        pages = 1 + per * len(rows)
        state = init_paged_slots(cfg, n, S, PS, pages,
                                 kv_quant_bits=8 if "int8" in kind else 0)
        table = np.zeros((n, per), np.int32)
        for i, slot in enumerate(sorted(rows)):
            table[slot] = 1 + i * per + np.arange(per)
        for slot in idle:
            table[slot] = table[min(rows)]
        state = state._replace(cache=state.cache._replace(
            page_table=jnp.asarray(table)))
        for slot, pf in rows.items():
            state = insert_paged(state, jnp.int32(slot), pf,
                                 jnp.asarray(table[slot]), jnp.int32(0),
                                 np.int32(50))
    else:
        state = init_slots(cfg, n, S)
        for slot, pf in rows.items():
            state = insert_request(state, jnp.int32(slot), pf, np.int32(50))
        bufs = {name: buf for name, buf in state.cache._asdict().items()
                if name != "length" and buf is not None}
        for slot in idle:
            bufs = {name: buf.at[:, slot].set(_noise(buf[:, slot], slot))
                    for name, buf in bufs.items()}
        state = state._replace(cache=state.cache._replace(**bufs))
    for slot in idle:
        state = state._replace(tok=state.tok.at[slot].set(7 + slot))
    return state


def _row_buffers(kind, cache, slot):
    """Every buffer of one row, as arrays."""
    if kind.startswith("paged"):
        pages = np.asarray(cache.page_table)[slot]
        return [np.asarray(buf)[:, pages] for buf in (
            cache.k, cache.v, cache.k_scale, cache.v_scale)
            if buf is not None]
    return [np.asarray(buf)[:, slot] for name, buf in
            cache._asdict().items() if name != "length" and buf is not None]


@pytest.mark.parametrize("kind,T", [
    ("contiguous-kernels", 1), ("contiguous-xla", 1),
    ("contiguous-tail-kernels", 1), ("contiguous-tail-xla", 3),
    ("latent-kernels", 1),
    ("latent-xla", 1), ("paged", 1), ("paged-int8", 1),
    ("contiguous-xla", 3), ("paged", 3)],
    ids=lambda v: f"verify{v}" if isinstance(v, int) and v > 1 else
    ("step" if isinstance(v, int) else v))
def test_a_row_that_is_not_running_touches_nothing(kind, T):
    """Two running rows around one that is not (slots 0 and 2 of three, the
    middle one full of a previous occupant's leavings) against the same two
    rows alone (slots 0 and 1 of two): after a T = 1 step, and after the
    speculative verify's forward of T = 3, every buffer of every running row
    and its logits are bit-equal to the run without the idle row; the idle
    row is still at length 0; in the page pool, whose pages the idle row's
    stale table names, not one byte of the whole pool differs."""
    cfg, model, params = _model(kind)
    flash = kind.endswith("kernels")
    a, b = (_prefilled(kind, p) for p in _prompts(cfg, (20, 33), seed=4))
    with_idle = _seated(kind, {0: a, 2: b}, idle=(1,))
    without = _seated(kind, {0: a, 1: b})
    rng = np.random.default_rng(9)
    fed = rng.integers(8, cfg.vocab_size, (2, T)).astype(np.int32)

    def run(state, rows):
        ids = np.full((len(state.tok), T), 11, np.int32)
        ids[rows] = fed
        logits, cache = jax.jit(partial(
            forward_with_cache, model, flash_decode=flash))(
                params, jnp.asarray(ids), state.cache)
        return np.asarray(logits), cache

    got_logits, got = run(with_idle, [0, 2])
    want_logits, want = run(without, [0, 1])
    np.testing.assert_array_equal(np.asarray(got.length), [20 + T, 0, 33 + T])
    np.testing.assert_array_equal(np.asarray(want.length), [20 + T, 33 + T])
    np.testing.assert_array_equal(got_logits[[0, 2]], want_logits)
    for slot_got, slot_want in ((0, 0), (2, 1)):
        for g, w in zip(_row_buffers(kind, got, slot_got),
                        _row_buffers(kind, want, slot_want)):
            np.testing.assert_array_equal(g, w)
    if kind.startswith("paged"):
        for name in ("k", "v", "k_scale", "v_scale"):
            if getattr(got, name) is not None:
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, name)),
                    np.asarray(getattr(want, name)))


@pytest.mark.parametrize("kind", ["contiguous-kernels",
                                  "contiguous-tail-kernels",
                                  "latent-kernels", "paged"])
def test_the_step_counts_a_row_down_and_parks_it(kind):
    """``decode_step`` on a slot state: a running row's ``left`` falls by one
    a step; at 0 the row is ``done`` at length 0 and stays there, its
    buffers untouched by the steps that follow; ``retire_slots`` does the
    same to the rows of a mask at once."""
    cfg, model, params = _model(kind)
    a, b = (_prefilled(kind, p) for p in _prompts(cfg, (20, 33), seed=4))
    state = _seated(kind, {0: a, 2: b}, idle=(1,))
    state = state._replace(left=jnp.asarray([2, 0, 50], jnp.int32))
    step = jax.jit(partial(
        decode_step, model, flash_decode=kind.endswith("kernels"),
        sampler=partial(sample_logits, greedy=True, temperature=1.0,
                        top_k=0, top_p=1.0)))
    seen = []
    for _ in range(4):
        state = step(params, state)
        seen.append((np.asarray(state.cache.length).tolist(),
                     np.asarray(state.done).tolist(),
                     np.asarray(state.left).tolist()))
        if len(seen) == 2:
            parked = _row_buffers(kind, state.cache, 0)
    assert seen == [
        ([21, 0, 34], [False, True, False], [1, 0, 49]),
        ([0, 0, 35], [True, True, False], [0, 0, 48]),
        ([0, 0, 36], [True, True, False], [0, 0, 47]),
        ([0, 0, 37], [True, True, False], [0, 0, 46])]
    # decode_attention leaves a parked row alone (the pool: bit for bit
    # above; the latent append writes position 0 of the row's own extent,
    # which the next insert overwrites whole)
    if kind.startswith("contiguous"):
        for g, w in zip(_row_buffers(kind, state.cache, 0), parked):
            np.testing.assert_array_equal(g, w)
    state = jax.jit(retire_slots)(state, np.asarray([False, False, True]))
    assert np.asarray(state.cache.length).tolist() == [0, 0, 0]
    assert np.asarray(state.done).all()


# --------------------------- (c) served == solo with idle rows between them
@pytest.mark.parametrize("kind", ["contiguous", "contiguous-xla",
                                  "contiguous-tail", "latent", "paged"])
def test_three_of_eight_slots_running_equal_solo_generate(kind):
    """Sampled requests through an engine of 8 slots of which never more
    than 3 run (5 stand at length 0 between and around them, and a slot is
    re-used after its first occupant ended), bit-identical to solo
    ``generate()`` of each."""
    base = kind.removesuffix("-xla")
    cfg, model, params = _model(base)
    eng = ds.init_inference(
        model, params, {"dtype": "float32", "eos_token_id": None,
                        "flash_decode": kind in (
                            "contiguous", "contiguous-tail", "latent")},
        mesh=_one_device_mesh())
    conf = {"slots": 8, "max_len": S, "prefill_chunk": 16,
            "temperature": 0.9, "top_k": 30}
    if base == "paged":
        conf.update(page_size=PS)
    srv = ds.ServingEngine(eng, conf)
    prompts = _prompts(cfg, (20, 9, 33, 13, 27), seed=6)
    new, seeds = [7, 4, 9, 5, 6], [1, 2, 3, 4, 5]
    # occupy slots 0, 1, 2, then free 0 and 1 by hand so that the three
    # that run sit at 2, 3.. with idle rows before and between them
    rids = [srv.submit(p, n, seed=s)
            for p, n, s in zip(prompts[:3], new[:3], seeds[:3])]
    most = 0
    out = {}
    later = list(zip(prompts[3:], new[3:], seeds[3:]))
    for _ in range(400):
        for req in srv.step():
            out[req.rid] = req
            if later:
                p, n, s = later.pop(0)
                rids.append(srv.submit(p, n, seed=s))
        most = max(most, len(srv.sched.running))
        if len(out) == 5:
            break
    assert most == 3 and len(out) == 5
    for rid, p, n, s in zip(rids, prompts, new, seeds):
        want = np.asarray(eng.generate(
            jnp.asarray(p[None]), n, request_seeds=[s], temperature=0.9,
            top_k=30, cache_len=S))[0]
        np.testing.assert_array_equal(out[rid].tokens, want)

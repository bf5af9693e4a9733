"""Continuous-batching serving engine (serving/: slots, scheduler, engine).

Oracles:
- ragged-workload parity: every request served through the scheduler is
  BIT-identical to single-request ``generate()`` with the same seed and
  cache length — slot position, batch composition, and chunked prefill
  must all be invisible to the request;
- slot reuse: a retired slot's stale KV never leaks into its successor;
- chunked prefill == whole prefill (cache bits and first token);
- fake-clock scheduler: FIFO admission, eos/max-token retirement, slot
  accounting, Serve/* load metrics;
- the scheduling win: useful decode tokens per slot-step >= 1.5x of
  static batching on a heavy-tailed mix, counted in decode steps.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.decode import (cache_layout, forward_with_cache,
                                            init_cache, prefill_tokens)
from deepspeed_tpu.inference.sampling import (per_request_keys,
                                              sample_logits, split_keys)
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.observability.tracing import ServingStats
from deepspeed_tpu.serving import (Scheduler, ServingEngine, init_slots,
                                   plan_chunks)

M = 48          # slot capacity used across these tests
EOS = 7


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


_ENGINE_ORACLE = {}


def _solo(model, params, prompt, max_new, seed, temperature=0.8, top_k=20):
    """Reference: single-request generate() through the PUBLIC API with the
    request's seed and the serving cache length (the documented oracle)."""
    eng = _ENGINE_ORACLE.get(id(model))
    if eng is None:
        eng = _ENGINE_ORACLE[id(model)] = ds.init_inference(
            model, params, {"dtype": "float32", "eos_token_id": EOS})
    return np.asarray(eng.generate(
        jnp.asarray(prompt[None], jnp.int32), max_new,
        temperature=temperature, top_k=top_k, request_seeds=[seed],
        cache_len=M))[0]


def _check_parity(model, params, reqs, outs):
    for (p, mn, s), got in zip(reqs, outs):
        want = _solo(model, params, p, mn, s)
        n = len(got)
        assert 1 <= n <= mn
        np.testing.assert_array_equal(got, want[:n])
        # serving stops at eos; the solo row's tail must be pure eos
        assert np.all(want[n:] == EOS)
        if n < mn:
            assert got[-1] == EOS


# ------------------------------------------------------------- chunk plans
def test_plan_chunks_buckets():
    p = np.arange(1, 24, dtype=np.int32)       # P=23, chunk 8
    plans = plan_chunks(p, 8)
    assert [c.size for c in plans] == [8, 8, 8]      # 2 full + residual 7→8
    assert [c.start for c in plans] == [0, 8, 15]    # overlap rewinds to 15
    assert plans[-1].final and plans[-1].true_len == 23
    assert plans[-1].last_index == 7
    np.testing.assert_array_equal(plans[-1].ids, p[15:23])

    short = plan_chunks(np.arange(1, 6, dtype=np.int32), 8)   # P=5 → pad to 8
    assert len(short) == 1 and short[0].size == 8
    assert short[0].last_index == 4 and short[0].true_len == 5
    assert np.all(short[0].ids[5:] == 0)

    exact = plan_chunks(np.arange(1, 17, dtype=np.int32), 16)  # P == chunk
    assert len(exact) == 1 and exact[0].start == 0 and exact[0].size == 16

    with pytest.raises(ValueError, match="empty"):
        plan_chunks(np.zeros(0, np.int32), 8)


# ------------------------------------------------------------------ parity
def test_ragged_workload_parity(setup):
    """Every request's tokens == single-request generate() with the same
    seed, across prompt-length regimes (pad bucket, one chunk, overlap,
    multi-chunk) and interleaved admissions/retirements."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 3, "max_len": M, "prefill_chunk": 16,
                              "temperature": 0.8, "top_k": 20})
    rng = np.random.default_rng(0)
    shapes = [(5, 9), (16, 12), (23, 6), (37, 10), (8, 4), (30, 3),
              (12, 17), (19, 8)]
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 100 + i)
            for i, (P, N) in enumerate(shapes)]
    outs = srv.serve_batch([p for p, _, _ in reqs],
                           [n for _, n, _ in reqs],
                           [s for _, _, s in reqs])
    _check_parity(model, params, reqs, outs)

    # steady state: a different mix over the same buckets compiles nothing
    warm = srv.compiles
    outs2 = srv.serve_batch([p for p, _, _ in reqs][::-1],
                            [n for _, n, _ in reqs][::-1],
                            [s + 50 for _, _, s in reqs][::-1])
    assert srv.compiles == warm
    _check_parity(model, params,
                  [(p, n, s + 50) for p, n, s in reqs][::-1], outs2)

    snap = srv.metrics_snapshot()
    assert snap["retired"] == 16 and snap["submitted"] == 16
    assert snap["ttft_s"]["count"] == 16


@pytest.mark.parametrize("then", ["runs_on", "cancelled"])
def test_chunk_dispatched_ahead(setup, then):
    """With a slot decoding, the prefill lane's next chunk is dispatched
    behind the decode step and consumed by the next iteration, one chunk
    an iteration still; a lane cancelled with a chunk in flight leaves it
    unused. The tokens are solo ``generate()``'s either way."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 2, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20})
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 40 + i)
            for i, (P, N) in enumerate([(6, 20), (37, 5), (29, 6)])]
    first = srv.submit(reqs[0][0], reqs[0][1], seed=reqs[0][2])
    srv.step()                               # seated: a slot decodes
    long = srv.submit(reqs[1][0], reqs[1][1], seed=reqs[1][2])
    srv.step()                               # chunk 0, chunk 1 goes ahead
    assert srv._prefill[0].rid == long and srv._prefill[2] == 1
    assert srv._ahead is not None and srv._prefill[3] is None
    chunks = srv.metrics_snapshot()["prefill_chunks"]
    if then == "cancelled":
        srv.cancel(long)
        assert srv._prefill is None
    last = srv.submit(reqs[2][0], reqs[2][1], seed=reqs[2][2])
    srv.step()
    assert srv.metrics_snapshot()["prefill_chunks"] == chunks + 1
    srv.drain()
    want = [r for r in reqs if then == "runs_on" or r is not reqs[1]]
    got = [srv.pop_result(rid).tokens for rid in
           ([first, long, last] if then == "runs_on" else [first, last])]
    _check_parity(model, params, want, got)


def _chunks(srv):
    return [e for e in srv.spans.events() if e.kind == "prefill_chunk"]


def _between_the_decode_pair(srv, span):
    """``span`` lies after ``srv.decode_dispatch`` of the iteration it
    carries and before its ``srv.decode_readback`` (of the step before:
    an iteration whose step is the first out reads nothing back)."""
    pair = {e.kind: e for e in srv.spans.events() if e.step == span.step
            and e.kind in ("srv.decode_dispatch", "srv.decode_readback")}
    return pair["srv.decode_dispatch"].t1 <= span.t0 \
        and ("srv.decode_readback" not in pair
             or span.t1 <= pair["srv.decode_readback"].t0)


@pytest.mark.parametrize("prompts", [(5, 7, 6, 4, 8), (5, 21, 7, 37, 12)],
                         ids=["single_final_chunks", "mixed"])
def test_waiting_request_is_admitted_behind_the_step(setup, prompts):
    """Requests queued with a slot free: the first is admitted in front
    (nothing runs yet), every later admission and every later chunk, a
    single final one too, goes out behind a decode step (``ahead`` 1,
    between that iteration's dispatch and read-back) and is consumed by
    the next iteration, one chunk an iteration; the tokens are solo
    ``generate()``'s."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 6, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20,
                              "spans": True})
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), 10, 70 + i)
            for i, P in enumerate(prompts)]
    rids = [srv.submit(p, n, seed=s) for p, n, s in reqs]
    srv.drain()
    chunks = _chunks(srv)
    assert [c.meta["ahead"] for c in chunks] == [0] + [1] * (len(chunks) - 1)
    # one chunk an iteration: each is consumed by the iteration after the
    # one whose step it went out behind
    assert [c.step + c.meta["ahead"] for c in chunks] \
        == list(range(len(chunks)))
    assert all(_between_the_decode_pair(srv, c) for c in chunks[1:])
    admits = [e for e in srv.spans.events() if e.kind == "srv.admit"
              and e.meta["ahead"]]
    assert len(admits) >= len(reqs) - 1
    assert all(_between_the_decode_pair(srv, a) for a in admits)
    # a final chunk is read back, and its request seated, by the iteration
    # after the one that dispatched it
    reads = {e.step for e in srv.spans.events()
             if e.kind == "srv.prefill_readback"}
    assert reads == {c.step + c.meta["ahead"] for c in chunks
                     if c.meta["final"]}
    assert srv.metrics_snapshot()["prefill_chunks"] == len(chunks)
    _check_parity(model, params, reqs,
                  [srv.pop_result(r).tokens for r in rids])


def test_arrival_after_the_dispatch_is_admitted_in_front(setup):
    """A request submitted into an engine whose queue was empty when the
    last step went out is admitted, prefilled and seated by the very next
    ``step()``, in front of its decode step (``ahead`` 0): no admission
    waits longer than before."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 3, "max_len": M, "prefill_chunk": 16,
                              "temperature": 0.8, "top_k": 20,
                              "spans": True})
    rng = np.random.default_rng(12)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), 12, 80 + i)
            for i, P in enumerate([9, 13])]
    first = srv.submit(*reqs[0][:2], seed=reqs[0][2])
    srv.step()
    srv.step()                      # decoding; nothing waited at dispatch
    assert srv._prefill is None and srv._ahead is None
    late = srv.submit(*reqs[1][:2], seed=reqs[1][2])
    at = srv._iterations
    srv.step()
    chunk = _chunks(srv)[-1]
    assert chunk.rid == late and chunk.step == at
    assert chunk.meta["ahead"] == 0 and chunk.meta["final"]
    dispatch = next(e for e in srv.spans.events() if e.step == at
                    and e.kind == "srv.decode_dispatch")
    assert chunk.t1 <= dispatch.t0
    assert {r.rid for r in srv.sched.running.values()} == {first, late}
    # seated and its first token read; its step is out, read a step late
    assert len(srv.sched.running[1].tokens) == 1
    assert late in {r.rid for r in srv._inflight.rows.values()}
    srv.step()
    assert len(srv.sched.running[1].tokens) == 2
    srv.drain()
    _check_parity(model, params, reqs,
                  [srv.pop_result(r).tokens for r in (first, late)])


# ------------------------------------------- the books run one step behind
def _steps(srv):
    return [e for e in srv.spans.events() if e.kind == "decode_step"]


def test_step_dispatched_before_its_predecessor_is_read(setup, monkeypatch):
    """The plain engine's order: step k + 1 goes out, on the carry step k
    returned, BEFORE the host reads step k back (``jax.device_get`` of
    step k's own outputs), so the device never waits for a read-back; the
    first step out has nothing in front of it (``ahead`` 0), every later
    one has (``ahead`` 1, ``Serve/decode_steps_ahead``), and ``step()``
    hands a step's tokens back one call late."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 2, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20,
                              "spans": True})
    order, outs, kept = [], {}, []
    prog, get = srv._prog, jax.device_get

    def spy(key, build):
        fn = prog(key, build)

        def call(*args):
            state, read = fn(*args)
            kept.append(read[0])        # alive: its id names the step
            outs[id(read[0])] = len(outs)
            order.append(("out", outs[id(read[0])]))
            return state, read
        return call if key == "step" else fn

    def spy_get(tree):
        first = tree[0] if isinstance(tree, tuple) else None
        if id(first) in outs:
            order.append(("read", outs[id(first)]))
        return get(tree)

    srv._prog = spy
    monkeypatch.setattr(jax, "device_get", spy_get)
    rng = np.random.default_rng(21)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), 9, 60 + i)
            for i, P in enumerate([5, 7])]
    rids = [srv.submit(p, n, seed=s) for p, n, s in reqs]
    srv.step()
    assert order == [("out", 0)] and srv._inflight is not None
    # seated with its first token; the step's own is in flight
    assert [len(r.tokens) for r in srv.sched.running.values()] == [1]
    srv.step()
    assert order == [("out", 0), ("out", 1), ("read", 0)]
    assert sorted(len(r.tokens) for r in srv.sched.running.values()) == [1, 2]
    srv.drain()
    n = len(outs)
    assert n >= 9
    assert order == [("out", 0)] + [x for k in range(1, n) for x in (
        ("out", k), ("read", k - 1))] + [("read", n - 1)]
    assert [e.meta["ahead"] for e in _steps(srv)] == [0] + [1] * (n - 1)
    reg = srv.stats.registry
    assert reg.counter("Serve/decode_steps_ahead").value == n - 1
    assert reg.counter("Serve/decode_steps").value == n
    assert srv._inflight is None            # drain leaves nothing in flight
    _check_parity(model, params, reqs,
                  [srv.pop_result(r).tokens for r in rids])


def _solo_with(eng, prompt, max_new, seed):
    return np.asarray(eng.generate(
        jnp.asarray(prompt[None], jnp.int32), max_new, temperature=0.8,
        top_k=20, request_seeds=[seed], cache_len=M))[0]


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_turnover_with_the_books_a_step_behind(setup, paged):
    """A seeded mix that turns three slots over while every step goes out
    ahead: a request whose first token is eos (seated before the host has
    read that token, so the insert seats it as a row that is not running
    and the slot comes back), one that asks for one token (never seated),
    a ``cancel`` and a deadline that fall while a step is in flight. Every
    request that ran to its end has solo ``generate()``'s tokens, the two
    that were cut a prefix of them, and no slot or page is lost."""
    from _fake_clock import TickClock

    cfg, model, params, _ = setup
    rng = np.random.default_rng(22)
    shapes = [(5, 9), (9, 6), (20, 1), (7, 12), (13, 7), (6, 30), (11, 30),
              (8, 5), (17, 4)]
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 300 + i)
            for i, (P, N) in enumerate(shapes)]
    # the first token of request 1 becomes the eos
    eos = int(_solo(model, params, *reqs[1])[0])
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": eos})
    clock = TickClock()
    srv = ServingEngine(eng, {"slots": 3, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20,
                              "spans": True,
                              **({"page_size": 8} if paged else {})},
                        clock=clock)
    rids = [srv.submit(p, n, seed=s,
                       total_deadline_s=40.0 if i == 6 else None)
            for i, (p, n, s) in enumerate(reqs)]
    cancelled, ended, handed = None, {}, {}
    for it in range(400):
        for req in srv.step():
            ended[req.rid] = req
        for req in srv.sched.running.values():
            # what a streaming caller has seen of each request so far
            handed[req.rid] = list(req.tokens)
        running = {r.rid for r in srv.sched.running.values()}
        if cancelled is None and rids[5] in running \
                and len(srv.results.get(rids[5], srv._find_request(
                    rids[5])).tokens) >= 3:
            assert srv._inflight is not None        # a step is in flight
            cancelled = srv.cancel(rids[5])
            assert srv._inflight is None            # ... and was settled
            ended[cancelled.rid] = cancelled
        if rids[6] in running and len(handed[rids[6]]) >= 4 \
                and clock.t < 40.0:
            assert srv._inflight is not None
            clock.advance(60.0)
        if len(ended) == len(reqs):
            break
    assert set(ended) == set(rids)
    status = {i: ended[r].status.name for i, r in enumerate(rids)}
    assert status == {**{i: "OK" for i in range(len(reqs))},
                      5: "CANCELLED", 6: "TIMEOUT"}
    for i, ((p, n, s), rid) in enumerate(zip(reqs, rids)):
        got, want = ended[rid].tokens, _solo_with(eng, p, n, s)
        np.testing.assert_array_equal(got, want[:len(got)])
        if i in (5, 6):
            assert 3 <= len(got) < n
            # nothing a caller was handed is taken back
            assert got[:len(handed[rid])] == handed[rid]
        else:
            assert np.all(want[len(got):] == eos)
            assert len(got) == n or got[-1] == eos
    assert ended[rids[1]].tokens == [eos] and ended[rids[1]].slot == -1
    assert len(ended[rids[2]].tokens) == 1 and ended[rids[2]].slot == -1
    srv.drain()
    assert sorted(srv.sched.free) == [0, 1, 2] and srv._inflight is None
    assert not np.asarray(srv._state.cache.length).any()
    assert np.asarray(srv._state.done).all()
    steps = _steps(srv)
    assert sum(e.meta["ahead"] for e in steps) >= len(steps) - 6
    if paged:
        snap = srv.pool.snapshot()
        assert snap["free_pages"] + snap["tree_held_pages"] \
            == snap["usable_pages"]
        assert snap["live_requests"] == 0


def test_reseated_slot_is_not_given_the_step_in_flight(setup):
    """One slot, two requests: the step that went out before the host
    knew the first had ended ran its row at length 0, and by the time it
    is read the slot holds the second. That step's token is nobody's: the
    second request's tokens are solo ``generate()``'s from the first on,
    and the step's span says it ran no row."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 1, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20,
                              "spans": True})
    rng = np.random.default_rng(23)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 500 + i)
            for i, (P, N) in enumerate([(6, 4), (7, 6), (5, 3)])]
    rids = [srv.submit(p, n, seed=s) for p, n, s in reqs]
    seen = []
    while not all(r in srv.results for r in rids):
        srv.step()
        fl = srv._inflight
        if fl is not None and 0 in fl.rows:
            seen.append((fl.rows[0].rid, srv.sched.running[0].rid))
    # a step in flight is booked to the request that holds the slot now
    assert seen and all(a == b for a, b in seen)
    srv.drain()
    _check_parity(model, params, reqs,
                  [srv.pop_result(r).tokens for r in rids])
    empty = [e for e in _steps(srv) if e.meta["slots"] == 0]
    assert len(empty) == 3          # one a request: the step behind its last
    assert all(set(e.meta) == {"slots", "queue", "ahead"} for e in empty)


def test_hand_off_and_export_settle_first(setup):
    """What reads slot state from the host's books reads the step in
    flight back first: ``export_request`` (its payload is the request as
    the device has it, the token of the step that was in flight booked),
    an ``on_placed`` hand-off (the first token is read before the seat,
    as it always was, and the hook finds nothing in flight), ``drain``."""
    cfg, model, params, eng = setup
    conf = {"slots": 2, "max_len": M, "prefill_chunk": 8, "page_size": 8,
            "temperature": 0.8, "top_k": 20}
    srv, dst = ServingEngine(eng, conf), ServingEngine(eng, conf)
    rng = np.random.default_rng(24)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), 10, 600 + i)
            for i, P in enumerate([9, 6])]
    stay = srv.submit(*reqs[0][:2], seed=reqs[0][2])
    for _ in range(4):
        srv.step()
    req = srv.sched.running[0]
    assert srv._inflight is not None and len(req.tokens) == 3
    payload = srv.export_request(req)
    assert srv._inflight is None and len(req.tokens) == 4
    assert int(payload["length"][0]) == 9 + 4 - 1
    assert int(payload["left"][0]) == 10 - 4
    assert int(payload["tok"][0]) == req.tokens[-1]
    srv.release_request(req)
    assert dst.import_request(req, payload)
    # ... and a request handed off as it is placed, with another running
    moved = []

    def hand_off(r, slot):
        assert srv._inflight is None and len(r.tokens) == 1
        moved.append((r, srv.export_request(r)))
        srv.release_request(r)

    keep = srv.submit(*reqs[0][:2], seed=reqs[0][2])
    for _ in range(3):
        srv.step()
    srv.on_placed = hand_off
    srv.submit(*reqs[1][:2], seed=reqs[1][2])
    while not moved:
        srv.step()
    srv.on_placed = None
    (r2, p2), = moved
    assert int(p2["length"][0]) == 6 and int(p2["left"][0]) == 9
    assert dst.import_request(r2, p2)
    dst.drain()
    srv.drain()
    assert srv._inflight is None and dst._inflight is None
    _check_parity(model, params, [reqs[0], reqs[1], reqs[0]],
                  [req.tokens, r2.tokens, srv.pop_result(keep).tokens])
    assert stay == req.rid


@pytest.mark.parametrize("which", ["chaos", "speculation"])
def test_serial_engines_read_every_step_at_once(setup, which):
    """An engine with chaos (its poison row and its hang are chosen a
    step at a time) or speculation (the next step's drafts are made from
    this step's tokens, on the host) keeps the serial order through the
    same code: every step is read before ``step()`` returns, ``ahead`` 0,
    and a step's tokens are handed back by the call that dispatched it."""
    cfg, model, params, eng = setup
    extra = {"chaos": {"enabled": True, "seed": 0}} if which == "chaos" \
        else {"greedy": True,
              "speculation": {"enabled": True, "ngram": 2, "max_draft": 3}}
    if which == "speculation":
        eng = ds.init_inference(model, params, {
            "dtype": "float32", "eos_token_id": EOS, "flash_decode": False})
    srv = ServingEngine(eng, {"slots": 2, "max_len": M, "prefill_chunk": 8,
                              "spans": True, **extra})
    rng = np.random.default_rng(25)
    base = rng.integers(0, 256, (6,)).astype(np.int32)
    for p in (np.tile(base, 3), rng.integers(0, 256, (7,)).astype(np.int32)):
        srv.submit(p, 12, seed=3)
    for _ in range(40):
        before = {r.rid: len(r.tokens) for r in srv.sched.running.values()}
        srv.step()
        assert srv._inflight is None
        for r in srv.sched.running.values():
            assert len(r.tokens) > before.get(r.rid, 0)
        if srv.sched.idle and srv._prefill is None:
            break
    steps = _steps(srv)
    assert len(steps) >= 8 and {e.meta["ahead"] for e in steps} == {0}
    assert srv.stats.registry.counter("Serve/decode_steps_ahead").value == 0
    assert srv.metrics_snapshot()["retired"] == 2


def test_the_programs_are_the_ones_built_before(setup):
    """The order of the loop changed, the programs did not: an engine
    builds the same set under the same names, the step under ``step``
    (``jit__step_impl`` is what the benchmark's reducers find it by)."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 2, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20})
    rng = np.random.default_rng(26)
    rids = [srv.submit(rng.integers(0, 256, (P,)).astype(np.int32), 4,
                       seed=P) for P in (5, 21, 9)]
    srv.step()
    srv.cancel(rids[0])
    srv.drain()
    assert set(srv._programs) == {
        "init_slots", "init_cache", ("chunk", 8), ("final", 8), "insert",
        "step", "retire"}
    assert srv._programs["step"].__name__ == "_step_impl"
    assert srv.compiles == 7


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_lane_cleared_with_its_admission_ahead(setup, how, paged):
    """A request admitted behind the step whose chunk is in flight is
    cancelled (or runs out of time) before the next iteration: aborted
    once, the chunk's result dropped, no slot and no page leaked, and the
    next in line takes the lane in front."""
    from _fake_clock import TickClock

    cfg, model, params, eng = setup
    clock = TickClock()
    srv = ServingEngine(eng, {"slots": 3, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20,
                              **({"page_size": 8} if paged else {})},
                        clock=clock)
    rng = np.random.default_rng(13)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 90 + i)
            for i, (P, N) in enumerate([(6, 20), (7, 5), (21, 6)])]
    first = srv.submit(*reqs[0][:2], seed=reqs[0][2])
    doomed = srv.submit(*reqs[1][:2], seed=reqs[1][2],
                        ttft_deadline_s=0.0 if how == "cancel" else 50.0)
    srv.step()            # first seated; doomed admitted behind the step
    assert srv._prefill[0].rid == doomed and srv._ahead is not None
    free = list(srv.sched.free)
    last = srv.submit(*reqs[2][:2], seed=reqs[2][2])
    if how == "cancel":
        assert srv.cancel(doomed).status.name == "CANCELLED"
        assert srv.cancel(doomed) is None
    else:
        clock.advance(60.0)
    done = srv.step()
    if how == "deadline":
        assert [(r.rid, r.status.name) for r in done] == [(doomed, "TIMEOUT")]
    assert srv.results[doomed].tokens == []
    assert srv.sched.free == free               # no slot taken or lost
    assert srv._prefill[0].rid == last          # in front, then ahead
    srv.drain()
    assert srv._ahead is None and len(srv.sched.free) == 3
    if paged:
        snap = srv.pool.snapshot()
        assert snap["free_pages"] + snap["tree_held_pages"] == snap["usable_pages"]
        assert snap["live_requests"] == 0
    snap = srv.metrics_snapshot()
    assert snap["retired"] == 2 and snap["submitted"] == 3
    _check_parity(model, params, [reqs[0], reqs[2]],
                  [srv.pop_result(r).tokens for r in (first, last)])


@pytest.mark.parametrize("what", ["shared_prefix", "tiered_restore"])
def test_paged_admission_behind_the_step(setup, what):
    """On the paged pool an admission behind the step hydrates a shared
    prefix from pages a running slot holds, or restores blocks the host
    tier holds, as one in front does: same plan, same tokens."""
    cfg, model, params, eng = setup
    rng = np.random.default_rng(15)
    tiered = what == "tiered_restore"
    srv = ServingEngine(eng, {
        "slots": 3, "max_len": M, "prefill_chunk": 16, "page_size": 8,
        "temperature": 0.8, "top_k": 20, "spans": True,
        **({"pool_pages": 11, "host_pool_bytes": 8 << 20} if tiered
           else {})})

    def finish(rids):
        while not all(r in srv.results for r in rids):
            srv.step()
        return [srv.pop_result(r).tokens for r in rids]

    old = [(rng.integers(0, 256, (32,)).astype(np.int32), 8, 20 + i)
           for i in range(4)]
    if tiered:
        # ten usable pages, five a request: by the fourth every block of
        # the first prompt has been evicted to the host tier and drained
        for p, n, seed in old:
            finish([srv.submit(p, n, seed=seed)])
        assert srv.hostkv.snapshot()["pages"] >= 4
    short = (rng.integers(0, 256, (9,)).astype(np.int32), 16, 30)
    again = (old[0][0] if tiered else np.concatenate(
        [short[0][:8], rng.integers(0, 256, (13,)).astype(np.int32)]), 8, 31)
    rids = [srv.submit(p, n, seed=s) for p, n, s in (short, again)]
    srv.step()              # short seated in front; again behind the step
    req = srv._prefill[0]
    assert req.rid == rids[1] and srv._ahead is not None
    admit = [e for e in srv.spans.events() if e.kind == "srv.admit"][-1]
    assert admit.meta["ahead"] == 1
    if tiered:
        assert req.page_alloc.restored == 4
        assert srv.hostkv.snapshot()["restores"] == 1
    else:
        assert req.page_alloc.shared == 1 == req.page_alloc.hydrate_pages
    _check_parity(model, params, [short, again], finish(rids))


def test_lane_work_behind_the_step_is_not_the_steps_time(setup):
    """A tiered restore admitted behind the step that keeps the HOST busy
    far longer than the watchdog allows a decode step: the step's time
    (``_last_step_s``: the watchdog, the anomaly detector, goodput's
    ``decode_s``) is its dispatch and read-back, not the lane's work that
    stood between them, so no stall is raised."""
    from _fake_clock import TickClock

    cfg, model, params, eng = setup
    clock = TickClock()
    srv = ServingEngine(eng, {
        "slots": 3, "max_len": M, "prefill_chunk": 16, "page_size": 8,
        "pool_pages": 11, "host_pool_bytes": 8 << 20, "greedy": True,
        "spans": True, "watchdog_s": 0.5}, clock=clock)
    rng = np.random.default_rng(16)
    old = [rng.integers(0, 256, (32,)).astype(np.int32) for _ in range(4)]
    for p in old:                   # the first prompt's blocks go to the host
        srv.serve_batch([p], 8)
    assert srv.hostkv.snapshot()["pages"] >= 4
    restore = srv._restore_dispatch

    def slow(cache, alloc):
        clock.advance(5.0)
        return restore(cache, alloc)

    srv._restore_dispatch = slow
    srv.submit(rng.integers(0, 256, (9,)).astype(np.int32), 16)
    srv.submit(old[0], 8)
    srv.step()              # the first seated; the restore behind the step
    admit = [e for e in srv.spans.events() if e.kind == "srv.admit"][-1]
    assert admit.meta["ahead"] == 1 and admit.t1 - admit.t0 > 5.0
    assert srv._prefill[0].page_alloc.restored == 4
    step = [e for e in srv.spans.events() if e.kind == "decode_step"][-1]
    assert step.t1 - step.t0 > 5.0          # the span holds its children
    assert srv._last_step_s < 0.1
    srv.drain()
    assert srv.metrics_snapshot()["watchdog_stalls"] == 0


def test_one_prefill_cache_at_a_time(setup):
    """An admission behind a step that follows a seat does not take its
    batch-1 cache while the seated one still waits for its insert: the
    insert has run and its cache's buffers are gone before ``init_cache``
    is dispatched. Where nothing is admitted behind the step the seated
    cache is let go with the iteration."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 4, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20})
    seated, alive = [], []
    prog = srv._prog

    def spy(key, build):
        fn = prog(key, build)

        def call(*args):
            if key == "insert":
                seated.append(args[2].cache)
            else:
                alive.append([not x.is_deleted() for c in seated
                              for x in jax.tree_util.tree_leaves(c)])
            return fn(*args)
        return call if key in ("insert", "init_cache") else fn

    srv._prog = spy
    rng = np.random.default_rng(17)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), 10, 40 + i)
            for i, P in enumerate([5, 7, 6, 4])]
    rids = [srv.submit(p, n, seed=s) for p, n, s in reqs]
    srv.step()
    assert srv._seated is None and srv._ahead is not None
    srv.drain()
    assert len(seated) == 4 and [len(a) > 0 for a in alive] == [
        False, True, True, True]
    assert not any(x for a in alive for x in a)
    assert srv._seated is None
    _check_parity(model, params, reqs,
                  [srv.pop_result(r).tokens for r in rids])


@pytest.mark.parametrize("seed", [0, 2 ** 31 - 1, 2 ** 31 + 5,
                                  4380000011, -3])
def test_lane_key_is_the_eager_key(setup, seed):
    """The request's key comes out of the admission's one program
    (``init_cache``) and is ``per_request_keys([seed])`` bit for bit, at
    seeds beyond 32 bits and below 0 too: what solo ``generate()`` folds."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 2, "max_len": M, "prefill_chunk": 8,
                              "temperature": 0.8, "top_k": 20})
    srv.submit(np.arange(1, 20, dtype=np.int32), 4, seed=seed)
    srv.step()                       # chunk 0 of 3: the lane holds the key
    key = srv._prefill[4]
    want = per_request_keys([seed])
    assert key.dtype == want.dtype and key.shape == want.shape
    np.testing.assert_array_equal(np.asarray(key), np.asarray(want))


def test_lane_programs_take_no_device_scalars(setup):
    """What the lane hands its programs beside params, caches and the
    request's key — token ids, positions, the slot, page rows, restored
    tiles — goes in as numpy: an argument of the program, never a device
    program of its own in front of it (``jnp.int32(x)`` is a
    ``convert_element_type`` dispatch)."""
    cfg, model, params, eng = setup
    for extra in ({}, {"page_size": 8, "pool_pages": 12,
                       "host_pool_bytes": 8 << 20}):
        srv = ServingEngine(eng, {"slots": 2, "max_len": M,
                                  "prefill_chunk": 8, "greedy": True,
                                  **extra})
        seen = set()
        prog = srv._prog

        def spy(key, build):
            fn = prog(key, build)
            name = key[0] if isinstance(key, tuple) else key

            def call(*args):
                seen.add(name)
                for i, a in enumerate(args):
                    # params, caches and carries are containers; a bare
                    # array is the key (uint32) or came from the host
                    assert not isinstance(a, jax.Array) \
                        or a.dtype == np.uint32, (name, i, a)
                    if name == "restore" and isinstance(a, dict):
                        assert all(isinstance(v, np.ndarray)
                                   for v in a.values()), (name, i)
                return fn(*args)
            return call if name in ("chunk", "final", "insert", "hydrate",
                                    "restore") else fn

        srv._prog = spy
        rng = np.random.default_rng(14)
        shared = rng.integers(0, 256, (16,)).astype(np.int32)
        for r in range(3):          # the shared prefix hydrates; 12 pages
            srv.serve_batch(        # for two requests evict and restore
                [np.concatenate([shared, rng.integers(0, 256, (n,)).astype(
                    np.int32)]) for n in (5, 9)], 4, seeds=[1, 2])
            srv.serve_batch([rng.integers(0, 256, (30,)).astype(np.int32)
                             for _ in range(2)], 4, seeds=[3, 4])
        assert seen == {"chunk", "final", "insert"} | (
            {"hydrate", "restore"} if extra else set())


def test_slot_reuse_no_stale_kv(setup):
    """One slot, sequential requests: the second and third requests reuse
    the retired slot and must still match their solo runs — and the insert
    must overwrite the slot's FULL cache extent."""
    cfg, model, params, eng = setup
    srv = ServingEngine(eng, {"slots": 1, "max_len": M, "prefill_chunk": 16,
                              "temperature": 0.8, "top_k": 20})
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 7 + i)
            for i, (P, N) in enumerate([(20, 8), (6, 10), (33, 5)])]
    outs = srv.serve_batch([p for p, _, _ in reqs],
                           [n for _, n, _ in reqs],
                           [s for _, _, s in reqs])
    _check_parity(model, params, reqs, outs)

    # direct leak probe: poison the slot cache, insert a fresh prefill,
    # the slot extent must equal the prefill cache exactly
    from deepspeed_tpu.serving import insert_request

    state = init_slots(cfg, 2, M, jnp.float32)
    poison = state.cache._replace(k=jnp.full_like(state.cache.k, 1e9),
                                  v=jnp.full_like(state.cache.v, -1e9))
    state = state._replace(cache=poison)
    smp = partial(sample_logits, temperature=0.8, top_k=20)
    pf = prefill_tokens(model, params, jnp.asarray(reqs[0][0][None]),
                        per_request_keys([1]), max_new=4, sampler=smp,
                        eos_token_id=EOS, cache_len=M)
    state = insert_request(state, jnp.int32(1), pf)
    np.testing.assert_array_equal(np.asarray(state.cache.k[:, 1]),
                                  np.asarray(pf.cache.k[:, 0]))
    np.testing.assert_array_equal(np.asarray(state.cache.v[:, 1]),
                                  np.asarray(pf.cache.v[:, 0]))
    assert int(state.cache.length[1]) == 20
    # the untouched slot keeps its (poisoned) bytes — insert is slot-local
    assert float(np.asarray(state.cache.k[:, 0]).max()) == 1e9


def test_chunked_prefill_matches_whole(setup):
    """Replaying a prompt through the bucket-shaped chunk plan produces the
    same cache bits and first token as one whole-prompt prefill."""
    cfg, model, params, eng = setup
    rng = np.random.default_rng(5)
    smp = partial(sample_logits, temperature=0.8, top_k=20)
    for P in (5, 16, 23, 37):           # pad, exact, overlap, multi-chunk
        prompt = rng.integers(0, 256, (P,)).astype(np.int32)
        keys = per_request_keys([42])
        whole = prefill_tokens(model, params, jnp.asarray(prompt[None]),
                               keys, max_new=4, sampler=smp,
                               eos_token_id=EOS, cache_len=M)
        cache = init_cache(cfg, 1, M, jnp.float32)
        for ch in plan_chunks(prompt, 16):
            cache = cache._replace(length=jnp.int32(ch.start))
            ids = jnp.asarray(ch.ids[None], jnp.int32)
            if not ch.final:
                _, cache = forward_with_cache(model, params, ids, cache)
                continue
            logits, cache = forward_with_cache(
                model, params, ids, cache, last_token_head=True,
                last_index=jnp.int32(ch.last_index))
            cache = cache._replace(length=jnp.int32(ch.true_len))
            keys, sub = split_keys(keys)
            tok = smp(logits[:, -1], sub)
        # compare the LIVE extent [0, P): a right-padded bucket leaves pad
        # KV at positions >= P, which the attention mask ignores and the
        # first decode steps overwrite (the ragged-parity test proves it)
        np.testing.assert_array_equal(np.asarray(cache.k[..., :P]),
                                      np.asarray(whole.cache.k[..., :P]),
                                      err_msg=f"chunked cache drift, P={P}")
        assert int(cache.length) == P == int(whole.cache.length)
        assert int(tok[0]) == int(whole.tok[0]), f"first token drift, P={P}"


# --------------------------------------------------------------- scheduler
def test_scheduler_fake_clock():
    """Admission/retirement order and Serve/* accounting, no device."""
    t = {"now": 0.0}

    def clock():
        t["now"] += 1.0
        return t["now"]

    stats = ServingStats(clock=clock)
    sched = Scheduler(slots=2, max_len=32, prefill_chunk=8, stats=stats)
    r1 = sched.submit(np.arange(4), max_new=3, seed=1)
    r2 = sched.submit(np.arange(6), max_new=1, seed=2)
    r3 = sched.submit(np.arange(5), max_new=2, seed=3)
    assert sched.queue_depth == 3

    # FIFO admission
    assert sched.pop_next() is r1
    assert sched.place(r1, first_tok=11) == 0
    assert sched.pop_next() is r2
    sched.complete_at_prefill(r2, first_tok=9)     # max_new=1: never a slot
    assert r2.finished and r2.tokens == [9]
    assert sched.pop_next() is r3
    assert sched.place(r3, first_tok=12) == 1
    assert sched.pop_next() is None                # no slots free, queue empty

    # r3 hits max_new=2 this step and frees its slot; r1 keeps going
    fin = sched.on_step(np.array([21, 22]), np.array([False, False]))
    assert fin == [r3] and sched.free == [1]
    assert r3.tokens == [12, 22]

    # r1 emits eos (done flag) on its 3rd token → retired
    fin = sched.on_step(np.array([7, 0]), np.array([True, False]))
    assert fin == [r1] and sorted(sched.free) == [0, 1]
    assert r1.tokens == [11, 21, 7]

    snap = stats.snapshot()
    assert snap["submitted"] == 3 and snap["admitted"] == 3
    assert snap["retired"] == 3
    assert snap["completed_tokens"] == 3 + 1 + 2
    assert snap["ttft_s"]["count"] == 3
    # fake clock: every latency is a whole positive number of ticks
    assert snap["ttft_s"]["p50"] >= 1.0

    # admission guards
    with pytest.raises(ValueError, match="exceeds the slot capacity"):
        sched.submit(np.arange(30), max_new=10, seed=0)
    with pytest.raises(ValueError, match="max_new"):
        sched.submit(np.arange(3), max_new=0, seed=0)


def test_serving_config_validation(setup):
    cfg, model, params, eng = setup
    with pytest.raises(ValueError, match="power of two"):
        ServingEngine(eng, {"slots": 2, "max_len": 32, "prefill_chunk": 12})
    with pytest.raises(ValueError, match="unknown serving config"):
        ServingEngine(eng, {"slotz": 2})
    with pytest.raises(ValueError, match="learned-position"):
        ServingEngine(eng, {"slots": 2, "max_len": 128, "prefill_chunk": 16})
    # nested serving config parses through InferenceConfig.from_any
    c = ds.InferenceConfig.from_any({"serving": {"slots": 4, "max_len": 64}})
    assert c.serving.slots == 4


# --------------------------------------------------- satellite: decode_chunk
def test_decode_chunk_early_stop_parity(setup):
    """generate() with decode_chunk > 0: bit-identical tokens, and the
    host-checked chunking lets an all-eos batch stop early (observable via
    the bounded decode-program steps — here we just pin parity plus the
    eos-filled tail)."""
    cfg, model, params, eng = setup
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 8)),
                      jnp.int32)
    chunked = ds.init_inference(model, params, {
        "dtype": "float32", "eos_token_id": EOS, "decode_chunk": 4})
    want = np.asarray(eng.generate(ids, 12, greedy=True))
    got = np.asarray(chunked.generate(ids, 12, greedy=True))
    np.testing.assert_array_equal(got, want)
    # sampled path with per-request seeds, max_new == 1 edge
    a = np.asarray(chunked.generate(ids, 1, temperature=0.7,
                                    request_seeds=[4, 5]))
    b = np.asarray(eng.generate(ids, 1, temperature=0.7,
                                request_seeds=[4, 5]))
    np.testing.assert_array_equal(a, b)


def test_per_request_seeds_batch_invariant(setup):
    """Satellite: the same request samples identically alone and in a
    static batch when keyed by request_seeds."""
    cfg, model, params, eng = setup
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (3, 10)),
                      jnp.int32)
    full = np.asarray(eng.generate(ids, 6, temperature=0.8, top_k=20,
                                   request_seeds=[31, 32, 33]))
    for i, s in enumerate([31, 32, 33]):
        solo = np.asarray(eng.generate(ids[i:i + 1], 6, temperature=0.8,
                                       top_k=20, request_seeds=[s]))
        np.testing.assert_array_equal(full[i], solo[0])
    with pytest.raises(ValueError, match="request_seeds"):
        eng.generate(ids, 4, request_seeds=[1, 2])


# ------------------------------------------------------- hygiene: layout
def test_cache_layout_single_source(setup):
    """init_cache and the slot allocator agree on shape/dtype through the
    shared cache_layout helper."""
    cfg, model, params, eng = setup
    shape, dtype = cache_layout(cfg, 5, 32)
    assert shape == (cfg.n_layer, 5, cfg.kv_heads, cfg.head_dim, 32)
    one = init_cache(cfg, 5, 32)
    state = init_slots(cfg, 5, 32)
    assert one.k.shape == state.cache.k.shape == shape
    assert one.k.dtype == state.cache.k.dtype == dtype
    assert state.cache.length.shape == (5,)       # per-slot vs scalar
    assert one.length.shape == ()


# --------------------------------------------------------------- TP mesh
def test_serving_under_tensor_parallel(devices):
    """Continuous batching on a TP mesh: tokens equal the TP=1 serving run
    AND the solo TP generate — pins the jax-0.4 GSPMD regression where the
    decode scan's token concat summed each id tp_size times, and the
    per-row categorical's layout-dependent draws."""
    mcfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    base = {"dtype": "float32", "eos_token_id": EOS}
    e1 = ds.init_inference(model, params, dict(base))
    etp = ds.init_inference(model, params, {**base, "tensor_parallel": 4})
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 70 + i)
            for i, (P, N) in enumerate([(9, 6), (21, 11), (5, 3)])]
    scfg = {"slots": 2, "max_len": M, "prefill_chunk": 16,
            "temperature": 0.9, "top_k": 30}
    o1 = ServingEngine(e1, scfg).serve_batch([p for p, _, _ in reqs],
                                             [n for _, n, _ in reqs],
                                             [s for _, _, s in reqs])
    otp = ServingEngine(etp, scfg).serve_batch([p for p, _, _ in reqs],
                                               [n for _, n, _ in reqs],
                                               [s for _, _, s in reqs])
    for (p, n, s), a, b in zip(reqs, o1, otp):
        np.testing.assert_array_equal(a, b)
        want = np.asarray(etp.generate(jnp.asarray(p[None]), n,
                                       temperature=0.9, top_k=30,
                                       request_seeds=[s], cache_len=M))[0]
        np.testing.assert_array_equal(b, want[:len(b)])
        assert np.all(want[len(b):] == EOS)
        assert (want < mcfg.vocab_size).all()   # the x4 bug emitted V*tp ids


# ------------------------------------------------- the scheduling win
def test_continuous_batching_beats_static_slot_steps(setup):
    """What continuous batching is for, counted in decode steps and no
    clock: on a heavy-tailed mix of output budgets the engine retires a
    row when it finishes and admits the next, so its decode steps x slots
    are spent on live rows. Static batching, granted arrival-order groups
    of ``slots`` rows that stop when the group's longest row stops, rides
    every group's tail. Useful decode tokens per slot-step must be at
    least 1.5x better."""
    cfg, model, params, eng = setup
    slots = 4
    srv = ServingEngine(eng, {"slots": slots, "max_len": M,
                              "prefill_chunk": 16, "temperature": 0.8,
                              "top_k": 20})
    rng = np.random.default_rng(1)
    budgets = [int(rng.integers(24, 33)) if i % 4 == 0
               else int(rng.integers(2, 7)) for i in range(24)]
    outs = srv.serve_batch(
        [rng.integers(0, 256, (8,)).astype(np.int32) for _ in budgets],
        budgets, [300 + i for i in range(len(budgets))])
    # decode tokens a row needed (its first token comes from prefill);
    # eos may end a row before its budget, for both schedulers alike
    need = [len(o) - 1 for o in outs]
    static_slot_steps = sum(
        len(need[i:i + slots]) * max(need[i:i + slots])
        for i in range(0, len(need), slots))
    cont_slot_steps = srv.stats.snapshot()["decode_steps"] * slots
    assert sum(need) <= cont_slot_steps
    assert static_slot_steps >= 1.5 * cont_slot_steps, \
        (static_slot_steps, cont_slot_steps)

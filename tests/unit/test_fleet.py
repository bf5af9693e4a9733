"""Multi-replica serving fleet (serving/fleet.py).

Oracles:
- router policy: least-loaded + shed-aware admission (draining replicas
  are hard-excluded and an all-draining fleet sheds TYPED; degraded /
  pool-pressured replicas lose to healthy alternatives);
- session affinity: sticky replica wins while healthy, falls back with
  a recorded affinity-miss when pool-pressured, re-sticks after;
- failover: replica loss requeues queued + in-flight requests onto
  survivors with typed REQUEUED + attempts, keeps ORIGINAL deadlines on
  the injectable clock, loses nothing, and requeued outputs stay
  bit-identical to solo generate();
- elasticity: a joined replica warms from the shared program cache —
  zero compiles — and receives traffic;
- pop_result routes by rid fleet-wide; results evictions attribute to
  the owning replica's Serve/results_evicted;
- disaggregated prefill/decode page handoff is bit-identical to a
  single engine;
- doctor --targets fleet triage gates on down replicas;
- the whole arc at once: a decode replica killed mid-traffic on a traced
  disaggregated fleet (zero loss, parity, frozen compiles, hops sum to
  e2e, audited requeues, one valid merged trace).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.observability.export import request_record
from deepspeed_tpu.serving import (FleetEngine, QueueFullError,
                                   RequestStatus)
from _fake_clock import TickClock

M = 48          # per-replica slot capacity across these tests
EOS = 7

# Compiled-program caches shared across every fleet this module builds
# (FleetEngine(programs=...): legal because all fleets here use the same
# engine + shape config) — one dict per shape family, so the suite pays
# each program build once, not once per test.
from collections import OrderedDict  # noqa: E402

_PROGRAMS: "OrderedDict" = OrderedDict()
_PROGRAMS_PAGED: "OrderedDict" = OrderedDict()


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


def _fleet(eng, replicas=2, clock=None, **kw):
    serving = {"slots": 2, "max_len": M, "prefill_chunk": 16,
               "temperature": 0.8, "top_k": 20}
    serving.update(kw.pop("serving", {}))
    progs = _PROGRAMS_PAGED if serving.get("page_size") else _PROGRAMS
    return FleetEngine(eng, serving, replicas=replicas, clock=clock,
                       programs=progs, **kw)


def _solo(eng, prompt, max_new, seed):
    return np.asarray(eng.generate(
        jnp.asarray(np.asarray(prompt)[None], jnp.int32), max_new,
        temperature=0.8, top_k=20, request_seeds=[seed], cache_len=M))[0]


def _prompts(n, seed=0, lengths=(5, 12, 16, 23, 9, 30)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (lengths[i % len(lengths)],))
            .astype(np.int32) for i in range(n)]


def _drive(fleet, rids, max_it=50_000, collect=True):
    done = {}
    it = 0
    while len(done) < len(rids):
        for req in fleet.step():
            if req.rid in set(rids):
                done[req.rid] = req
                if collect:
                    fleet.results.pop(req.rid, None)
        it += 1
        assert it < max_it, "fleet driver stuck"
    return done


# ------------------------------------------------------------ router policy
def test_all_replicas_draining_sheds_typed(setup):
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2)
    fleet.begin_drain()
    with pytest.raises(QueueFullError):
        fleet.submit(np.arange(1, 6, dtype=np.int32), 3)
    assert int(fleet.registry.snapshot()["counters"]["Fleet/sheds"]) == 1
    # reopening restores admission
    fleet.end_drain()
    rid = fleet.submit(np.arange(1, 6, dtype=np.int32), 3, seed=5)
    done = _drive(fleet, [rid])
    assert done[rid].status is RequestStatus.OK


def test_partial_drain_routes_around(setup):
    """One draining replica is hard-excluded while the other serves."""
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2)
    fleet.replicas["r0"].begin_drain()
    rids = [fleet.submit(p, 3, seed=40 + i)
            for i, p in enumerate(_prompts(4, seed=4))]
    assert all(fleet._owner[r] == "r1" for r in rids)
    done = _drive(fleet, rids)
    assert all(done[r].status is RequestStatus.OK for r in rids)


def test_least_loaded_spread(setup):
    """With equal health, admissions spread by load, not all to r0."""
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=3)
    for i, p in enumerate(_prompts(6, seed=9)):
        fleet.submit(p, 3, seed=i)
    owners = {fleet._owner[r] for r in fleet._owner}
    assert owners == {"r0", "r1", "r2"}


def test_affinity_sticks_and_falls_back_on_pool_pressure(setup):
    _, _, _, eng = setup
    clock = TickClock()
    fleet = _fleet(eng, replicas=2, clock=clock,
                   serving={"page_size": 8})
    p = np.arange(1, 20, dtype=np.int32)
    rid0 = fleet.submit(p, 3, seed=1, session_id="chat")
    sticky = fleet._owner[rid0]
    done = _drive(fleet, [rid0])
    assert done[rid0].ok
    # same session sticks while the replica is healthy
    rid1 = fleet.submit(p, 3, seed=2, session_id="chat")
    assert fleet._owner[rid1] == sticky
    c = fleet.registry.snapshot()["counters"]
    assert int(c["Fleet/affinity_hits"]) == 1
    _drive(fleet, [rid1])
    # pool pressure on the sticky replica: affinity must fall back and
    # record the miss
    pool = fleet.replicas[sticky].pool
    saved, pool.free[:] = pool.free[:], []
    assert fleet.replicas[sticky].health()["pool_pressure"]
    rid2 = fleet.submit(p, 3, seed=3, session_id="chat")
    other = fleet._owner[rid2]
    assert other != sticky
    c = fleet.registry.snapshot()["counters"]
    assert int(c["Fleet/affinity_misses"]) == 1
    pool.free[:] = saved
    _drive(fleet, [rid2])
    # the session re-stuck to its new home
    rid3 = fleet.submit(p, 3, seed=4, session_id="chat")
    assert fleet._owner[rid3] == other
    _drive(fleet, [rid3])


# ---------------------------------------------------------------- failover
def test_remove_replica_requeues_with_status_and_attempts(setup):
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2)
    prompts = _prompts(4, seed=3)
    rids = [fleet.submit(p, 3, seed=60 + i) for i, p in enumerate(prompts)]
    fleet.step()          # some requests admitted / prefilling on both
    victim = "r0"
    requeued = fleet.remove_replica(victim)
    assert requeued, "victim held no requests — test lost its subject"
    assert victim not in fleet.replicas
    # the survivor's in-flight table shows the typed transition
    rows = {r["rid"]: r for r in fleet.requests_table()}
    for rid in requeued:
        assert rows[rid]["status"] == "requeued"
        assert rows[rid]["attempts"] == 1
    c = fleet.registry.snapshot()["counters"]
    assert int(c["Fleet/requeued"]) == len(requeued)
    surv = fleet.replicas["r1"]
    assert surv.stats.snapshot()["requeued"] == len(requeued)
    # requeued work sits at the survivor's queue HEAD oldest-first: the
    # deadline-closest request admits first
    head = [r for r in list(surv.sched.queue)[:len(requeued)]]
    assert all(r.status is RequestStatus.REQUEUED for r in head)
    stamps = [r.submit_t for r in head]
    assert stamps == sorted(stamps), stamps
    done = _drive(fleet, rids)
    # zero loss, terminal statuses, bit-parity incl. requeued requests
    for i, rid in enumerate(rids):
        assert done[rid].status is RequestStatus.OK
        want = _solo(eng, prompts[i], 3, 60 + i)
        got = np.asarray(done[rid].tokens, np.int32)
        assert np.array_equal(got, want[:len(got)])
        # the request-log record carries the attempt count
        assert request_record(done[rid])["attempts"] == \
            (1 if rid in requeued else 0)


def test_requeued_request_keeps_original_deadline(setup):
    _, _, _, eng = setup
    clock = TickClock()
    fleet = _fleet(eng, replicas=2, clock=clock)
    p = np.arange(1, 30, dtype=np.int32)
    # long prompt + big max_new: still in flight when the replica dies
    rid_dead = fleet.submit(p, 6, seed=1, total_deadline_s=5.0)
    rid_live = fleet.submit(p, 6, seed=2, total_deadline_s=10_000.0)
    dl_dead = fleet.replicas[fleet._owner[rid_dead]] \
        .sched.queue[0].deadline_total
    fleet.step()
    requeued = fleet.remove_replica("r0")
    assert set(requeued) <= {rid_dead, rid_live}
    # the absolute deadlines survived the move unchanged
    surv = fleet.replicas["r1"]
    held = {r.rid: r for r in list(surv.sched.queue)
            + list(surv.sched.running.values())}
    if surv._prefill is not None:
        held[surv._prefill[0].rid] = surv._prefill[0]
    assert held[rid_dead].deadline_total == dl_dead
    # blow past the short deadline on the injectable clock: the requeued
    # request times out against its ORIGINAL budget
    clock.advance(50.0)
    done = _drive(fleet, [rid_dead, rid_live])
    assert done[rid_dead].status is RequestStatus.TIMEOUT
    assert done[rid_dead].attempts == 1
    assert done[rid_live].status is RequestStatus.OK


def test_kill_last_replica_refused(setup):
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2)
    fleet.remove_replica("r1")
    with pytest.raises(RuntimeError, match="last replica"):
        fleet.remove_replica("r0")
    with pytest.raises(KeyError):
        fleet.remove_replica("nope")
    # a REFUSED kill is not an incident: the counter never moved
    with pytest.raises(RuntimeError):
        fleet.kill_replica("r0")
    c = fleet.registry.snapshot()["counters"]
    assert int(c.get("Fleet/replica_kills", 0)) == 0


# --------------------------------------------------------------- elasticity
def test_joined_replica_serves_without_compiles(setup):
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2)
    prompts = _prompts(4, seed=8)
    _drive(fleet, [fleet.submit(p, 3, seed=80 + i)
                   for i, p in enumerate(prompts)])
    name = fleet.add_replica()
    assert fleet.replicas[name].compiles == 0
    rids = [fleet.submit(p, 3, seed=90 + i)
            for i, p in enumerate(prompts)]
    done = _drive(fleet, rids)
    assert all(done[r].ok for r in rids)
    je = fleet.replicas[name]
    assert je.compiles == 0, "joined replica compiled under traffic"
    assert je.stats.snapshot()["retired"] >= 1
    assert int(fleet.registry.snapshot()["counters"]
               ["Fleet/replica_joins"]) == 1


# ----------------------------------------------------------- result routing
def test_pop_result_routes_by_rid(setup):
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=3)
    prompts = _prompts(4, seed=5)
    rids = [fleet.submit(p, 3, seed=70 + i)
            for i, p in enumerate(prompts)]
    _drive(fleet, rids, collect=False)
    owners = {fleet._owner[r] for r in rids}
    assert len(owners) > 1, "all requests landed on one replica"
    for rid in rids:
        req = fleet.pop_result(rid)
        assert req is not None and req.rid == rid
    assert all(fleet.pop_result(rid) is None for rid in rids)


def test_results_eviction_attributes_to_owner(setup):
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2)
    fleet._max_results = 1
    prompts = _prompts(4, seed=6)
    rids = [fleet.submit(p, 2, seed=50 + i)
            for i, p in enumerate(prompts)]
    _drive(fleet, rids, collect=False)
    assert len(fleet.results) == 1
    c = fleet.registry.snapshot()["counters"]
    assert int(c["Fleet/results_evicted"]) == 3
    per = [e.stats.snapshot()["results_evicted"]
           for e in fleet.replicas.values()]
    assert sum(per) == 3, f"evictions not attributed per replica: {per}"


# ------------------------------------------------------------ disaggregated
def test_disaggregated_parity_and_role_separation(setup):
    _, _, _, eng = setup
    fleet = FleetEngine(eng, {"slots": 2, "max_len": M,
                              "prefill_chunk": 16, "page_size": 8,
                              "temperature": 0.8, "top_k": 20},
                        replicas=3, prefill_replicas=1,
                        programs=_PROGRAMS_PAGED)
    prompts = _prompts(4, seed=12)
    rids = [fleet.submit(p, 5, seed=30 + i, session_id=f"s{i % 2}")
            for i, p in enumerate(prompts)]
    done = _drive(fleet, rids)
    for i, rid in enumerate(rids):
        want = _solo(eng, prompts[i], 5, 30 + i)
        got = np.asarray(done[rid].tokens, np.int32)
        assert np.array_equal(got, want[:len(got)]), \
            f"disaggregated rid {rid} diverged"
    c = fleet.registry.snapshot()["counters"]
    assert int(c["Fleet/handoffs"]) >= 1
    assert int(c["Fleet/handoff_imports"]) == int(c["Fleet/handoffs"])
    for n, e in fleet.replicas.items():
        s = e.stats.snapshot()
        if fleet.roles[n] == "prefill":
            assert s["decode_steps"] == 0
        else:
            assert s["prefill_chunks"] == 0
            # the import path books NO prefill savings: a decode
            # replica seating already-computed KV skipped nothing (the
            # source replica owns the savings accounting)
            ps = e.pool.snapshot()
            assert ps["prefill_tokens_saved"] == 0
            assert ps["prompt_tokens"] == 0


def test_handoff_and_decode_deadlines_enforced(setup):
    """A handed-off request is in no scheduler's sweep: the fleet must
    retire it TIMEOUT itself (and RETURN it from step() — the fleet-side
    retirement channel), and an IMPORTED request must still be swept by
    the decode replica even though that engine never saw its submit."""
    _, _, _, eng = setup
    clock = TickClock()
    fleet = FleetEngine(eng, {"slots": 2, "max_len": M,
                              "prefill_chunk": 16, "page_size": 8,
                              "temperature": 0.8, "top_k": 20},
                        replicas=3, prefill_replicas=1, clock=clock,
                        programs=_PROGRAMS_PAGED)
    p = np.arange(1, 20, dtype=np.int32)
    # (a) pending-handoff timeout: choke both decode pools so the
    # payload stays host-held, then blow the deadline
    saved = {}
    for n, e in fleet.replicas.items():
        if fleet.roles[n] == "decode":
            saved[n] = e.pool.free[:]
            e.pool.free[:] = []
    rid = fleet.submit(p, 8, seed=1, total_deadline_s=5.0)
    got = []
    for _ in range(40):
        got += fleet.step()
        if fleet._handoffs:
            break
    assert fleet._handoffs, "request never reached the handoff buffer"
    clock.advance(50.0)
    done = {}
    it = 0
    while rid not in done:
        for req in fleet.step():
            done[req.rid] = req
        it += 1
        assert it < 100, "handoff timeout never surfaced through step()"
    assert done[rid].status is RequestStatus.TIMEOUT
    for n, free in saved.items():
        fleet.replicas[n].pool.free[:] = free
    # (b) decode-side sweep after import: survives the handoff, then
    # expires mid-decode on the decode replica's own deadline sweep
    rid2 = fleet.submit(p, 8, seed=2, total_deadline_s=5.0)
    it = 0
    while not any(fleet.roles[n] == "decode"
                  and any(r.rid == rid2
                          for r in fleet.replicas[n].sched.running.values())
                  for n in fleet.replicas):
        fleet.step()
        it += 1
        assert it < 200, "request never imported into a decode replica"
    clock.advance(50.0)
    done2 = {}
    it = 0
    while rid2 not in done2:
        for req in fleet.step():
            done2[req.rid] = req
        it += 1
        assert it < 100, "imported request never swept on the decode side"
    assert done2[rid2].status is RequestStatus.TIMEOUT
    fleet.close()


def test_chaos_kill_respects_disaggregated_roles(setup):
    """A seeded chaos victim is only ever a LEGALLY removable replica —
    killing the last prefill replica must not crash the serving loop."""
    _, _, _, eng = setup
    fleet = FleetEngine(eng, {"slots": 2, "max_len": M,
                              "prefill_chunk": 16, "page_size": 8,
                              "temperature": 0.8, "top_k": 20},
                        replicas=3, prefill_replicas=1,
                        programs=_PROGRAMS_PAGED,
                        chaos={"enabled": True, "seed": 0,
                               "kill_replica_step": 2})
    prompts = _prompts(4, seed=21)
    rids = [fleet.submit(p, 4, seed=110 + i, session_id="k")
            for i, p in enumerate(prompts)]
    done = _drive(fleet, rids)        # must not raise mid-kill
    assert fleet.chaos.injected, "kill never fired"
    victim = fleet.chaos.injected[0]["replica"]
    assert victim.startswith("d"), \
        f"chaos killed {victim} — the last prefill replica is not killable"
    assert all(done[r].status is RequestStatus.OK for r in rids)
    fleet.close()


def test_fleet_defaults_to_engine_serving_config():
    """serving=None must resolve engine.config.serving (what the
    replicas actually build from), not a default-constructed config."""
    cfg = tiny_test(max_seq=32, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(
        model, params,
        {"dtype": "float32",
         "serving": {"slots": 2, "max_len": 32, "prefill_chunk": 16,
                     "page_size": 8}})
    fleet = FleetEngine(eng, None, replicas=2, prefill_replicas=1)
    assert all(e._paged for e in fleet.replicas.values())
    assert set(fleet.roles.values()) == {"prefill", "decode"}
    fleet.close()


def test_fixed_port_telemetry_refused_beyond_one_replica(setup):
    """A fixed telemetry port cannot be shared: refused at construction
    for replicas > 1 AND at a later add_replica() on a 1-replica fleet
    (the elastic-join path must not bind-crash)."""
    import socket

    _, _, _, eng = setup
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    scfg = {"slots": 2, "max_len": M, "prefill_chunk": 16,
            "temperature": 0.8, "top_k": 20,
            "telemetry": {"enabled": True, "port": port}}
    with pytest.raises(ValueError, match="fixed port"):
        FleetEngine(eng, scfg, replicas=2, programs=_PROGRAMS)
    fleet = FleetEngine(eng, scfg, replicas=1, programs=_PROGRAMS)
    try:
        with pytest.raises(ValueError, match="fixed port"):
            fleet.add_replica()
    finally:
        fleet.close()


def test_disaggregation_requires_paged():
    cfg = tiny_test(max_seq=32, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params, {"dtype": "float32"})
    with pytest.raises(ValueError, match="paged"):
        FleetEngine(eng, {"slots": 2, "max_len": 32, "prefill_chunk": 16},
                    replicas=2, prefill_replicas=1)
    with pytest.raises(ValueError, match="decode replica"):
        FleetEngine(eng, {"slots": 2, "max_len": 32, "prefill_chunk": 16,
                          "page_size": 8},
                    replicas=2, prefill_replicas=2)


# ---------------------------------------------------- distributed tracing
def test_distributed_trace_disaggregated(setup):
    """The PR-10 tentpole on a disaggregated fleet with tracing ON:
    every request's hop decomposition tiles its e2e wall exactly (fake
    clock), the handoff hops are real, Fleet/hop_* histograms aggregate
    them, the merged Chrome trace carries named replica pids + the
    cross-replica flows, and every routing decision has an audit
    entry."""
    from deepspeed_tpu.observability import validate_chrome_trace
    from deepspeed_tpu.observability import spans as S

    _, _, _, eng = setup
    clock = TickClock()
    fleet = _fleet(eng, replicas=3, clock=clock, prefill_replicas=1,
                   serving={"page_size": 8, "spans": True})
    assert fleet.spans is not None      # tracing follows serving.spans
    prompts = _prompts(4, seed=12)
    rids = [fleet.submit(p, 5, seed=130 + i, session_id=f"s{i % 2}")
            for i, p in enumerate(prompts)]
    done = _drive(fleet, rids, collect=False)
    for rid in rids:
        tr = fleet.request_trace(rid)
        assert tr is not None and tr["finished"]
        h = tr["hops"]
        # disaggregated path: every hop is real, and they TILE e2e
        for k in ("queue_wait_s", "prefill_s", "handoff_wait_s",
                  "import_s", "decode_s"):
            assert h[k] is not None and h[k] >= 0, (rid, k, h)
        assert sum(h[k] for k in ("queue_wait_s", "prefill_s",
                                  "handoff_wait_s", "import_s",
                                  "decode_s")) \
            == pytest.approx(h["e2e_s"], rel=1e-9)
        assert tr["replica"] in fleet.replicas
        # the request-log record carries the same decomposition
        rec = request_record(done[rid])
        assert rec["trace"]["import_s"] == h["import_s"]
        # ... and the router explains every decision it made for it
        audit = fleet.route_audit(rid)
        assert audit and audit[0]["event"] in ("route",
                                               "affinity_fallback")
        # the initial route lands on the prefill role (ownership moves
        # to a decode replica later, at the handoff import)
        assert audit[0]["chosen"] == "p0"
        assert all(isinstance(c["reasons"], list)
                   for c in audit[0]["candidates"])
    # hop histograms aggregate across the fleet (one sample per request
    # per hop; e2e too)
    hist = fleet.registry.snapshot()["histograms"]
    for h in ("queue_wait", "prefill", "handoff_wait", "import",
              "decode", "e2e"):
        assert hist[f"Fleet/hop_{h}_s"]["count"] == len(rids), h
    # fleet ring carries the cross-replica hop events
    kinds = {e.kind for e in fleet.spans.events()}
    assert {S.ROUTE, S.HANDOFF_EXPORT, S.HANDOFF_PENDING,
            S.HANDOFF_IMPORT} <= kinds
    # ONE merged trace: router + prefill + decode pids, flows across
    merged = fleet.merge_trace()
    assert validate_chrome_trace(merged) == []
    evs = merged["traceEvents"]
    pnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"fleet:router", "fleet:p0", "fleet:d0", "fleet:d1"} <= pnames
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert flows and len({e["pid"] for e in flows}) >= 2
    assert {e["id"] for e in flows} <= set(rids)
    fleet.close()


def test_route_audit_exclusion_reasons_and_shed(setup):
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2, serving={"spans": True})
    fleet.replicas["r0"].begin_drain()
    rid = fleet.submit(np.arange(1, 8, dtype=np.int32), 3, seed=1)
    audit = fleet.route_audit(rid)
    assert len(audit) == 1 and audit[0]["chosen"] == "r1"
    cands = {c["name"]: c for c in audit[0]["candidates"]}
    # the excluded replica's entry SAYS why it lost
    assert cands["r0"]["reasons"] == ["draining"]
    assert not cands["r0"]["healthy"] and cands["r1"]["healthy"]
    # an all-draining shed is itself an audited decision (rid-less: the
    # request never existed)
    fleet.replicas["r1"].begin_drain()
    with pytest.raises(QueueFullError):
        fleet.submit(np.arange(1, 8, dtype=np.int32), 3)
    shed = fleet.route_audit()[-1]
    assert shed["event"] == "shed" and shed["rid"] is None
    assert all(c["reasons"] == ["draining"]
               for c in shed["candidates"])
    fleet.end_drain()
    _drive(fleet, [rid])
    fleet.close()


def test_requeue_attempt_attribution(setup):
    """Satellite: per-attempt spans + the Serve/requeue_delay_s
    histogram make TTFT and failover delay separable — the requeued
    attempt's queue span starts at the REQUEUE (not the original
    submit), labeled with its attempt index."""
    from deepspeed_tpu.observability import spans as S

    _, _, _, eng = setup
    clock = TickClock()
    fleet = _fleet(eng, replicas=2, clock=clock,
                   serving={"spans": True})
    prompts = _prompts(4, seed=3)
    rids = [fleet.submit(p, 3, seed=160 + i)
            for i, p in enumerate(prompts)]
    fleet.step()
    requeued = fleet.remove_replica("r0")
    assert requeued
    kill_t = clock.t
    done = _drive(fleet, rids, collect=False)
    surv = fleet.replicas["r1"]
    # one requeue-delay observation per requeue, none for the rest
    hist = surv.stats.registry.snapshot()["histograms"]
    assert hist["Serve/requeue_delay_s"]["count"] == len(requeued)
    for rid in requeued:
        req = done[rid]
        assert req.requeue_t is not None and req.requeue_t <= kill_t
        h = request_record(req)["trace"]
        assert h["attempts"] == 1
        # requeue delay = kill -> re-admission, strictly inside the
        # (original-submit-anchored) queue wait
        assert h["requeue_delay_s"] == pytest.approx(
            req.admit_t - req.requeue_t)
        assert h["requeue_delay_s"] < h["queue_wait_s"]
        # the survivor's ring stamped the ATTEMPT's own queue span,
        # starting at the requeue instant
        qs = [e for e in surv.spans.events()
              if e.kind == S.QUEUED and e.rid == rid]
        att = [e for e in qs if e.meta.get("attempt") == 1]
        assert len(att) == 1 and att[0].t0 == req.requeue_t
        # and the fleet ring recorded the hop + the audit the reason
        rq = [e for e in fleet.spans.events()
              if e.kind == S.REQUEUE and e.rid == rid]
        assert len(rq) == 1 and rq[0].meta["replica"] == "r1"
        entries = [e for e in fleet.route_audit(rid)
                   if e["event"] == "requeue"]
        assert len(entries) == 1
        assert entries[0]["lost_replica"] == "r0"
    # non-requeued requests carry no requeue attribution
    for rid in set(rids) - set(requeued):
        h = request_record(done[rid])["trace"]
        assert h["attempts"] == 0 and h["requeue_delay_s"] is None
    fleet.close()


def test_tracing_disabled_inert_but_hops_still_stamped(setup):
    """Tracing off (the default): no fleet ring, no audit, no Fleet/hop_*
    series — but request_trace still answers from the host stamps, and
    the request-log trace dict carries null handoff hops."""
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=2)
    assert fleet.spans is None and fleet._audit is None
    assert fleet.route_audit() == []
    p = np.arange(1, 10, dtype=np.int32)
    rid = fleet.submit(p, 3, seed=2)
    done = _drive(fleet, [rid], collect=False)
    assert not any(k.startswith("Fleet/hop_")
                   for k in fleet.registry.snapshot()["histograms"])
    tr = fleet.request_trace(rid)
    h = tr["hops"]
    assert h["handoff_wait_s"] is None and h["import_s"] is None
    assert h["queue_wait_s"] + h["prefill_s"] + h["decode_s"] \
        == pytest.approx(h["e2e_s"], rel=1e-9)
    assert request_record(done[rid])["trace"]["import_s"] is None
    assert fleet.request_trace(10_000_000) is None
    fleet.close()


# ---------------------------------------------------------------- incidents
def test_incident_capture_fans_out_and_doctor_gates(setup, tmp_path,
                                                    capsys):
    """Correlated incident capture: ONE replica's flight trigger lands
    every replica's dump + the fleet artifacts + a merged trace in one
    incident dir under a shared id; the doctor reconstructs the
    cross-replica timeline and gates on an UNRECONCILED incident (fewer
    dumps than live replicas), in file mode and in ``--targets`` mode."""
    import shutil

    from deepspeed_tpu.observability import doctor, validate_chrome_trace
    from deepspeed_tpu.serving import ServingEngine

    _, _, _, eng = setup
    fdir = tmp_path / "fl"
    clock = TickClock()
    fleet = _fleet(eng, replicas=3, clock=clock,
                   serving={"spans": True, "flight_dir": str(fdir)})
    rids = [fleet.submit(p, 3, seed=170 + i)
            for i, p in enumerate(_prompts(3, seed=6))]
    _drive(fleet, rids)
    # r1's own trigger (what a watchdog stall / nonfinite halt calls)
    d = fleet.replicas["r1"].flight.dump("watchdog_stall")
    assert d is not None and d.name == "r1"
    inc = d.parent
    assert inc.name.startswith("incident_")
    import json as _json
    mf = _json.loads((inc / "incident.json").read_text())
    assert mf["incident_id"] == inc.name
    assert mf["trigger_replica"] == "r1"
    assert mf["replicas_live"] == 3
    subs = sorted(p.name for p in inc.iterdir()
                  if p.is_dir() and p.name != "fleet")
    assert subs == ["r0", "r1", "r2"]
    # every replica's dump is a full flight record in the shared dir
    for n in subs:
        assert (inc / n / "manifest.json").exists()
        assert (inc / n / "events.jsonl").exists()
    # fleet artifacts: ring + route audit + the merged trace
    assert (inc / "fleet" / "events.jsonl").exists()
    assert (inc / "fleet" / "route_audit.jsonl").exists()
    merged = _json.loads((inc / "fleet" / "trace_merged.json").read_text())
    assert validate_chrome_trace(merged) == []
    assert int(fleet.registry.snapshot()["counters"]
               ["Fleet/incidents"]) == 1
    # the manual ops entry point opens a SECOND incident of its own
    inc2 = fleet.dump_incident("manual")
    assert inc2 is not None and inc2 != inc
    assert sorted(p.name for p in inc2.iterdir()
                  if p.is_dir() and p.name != "fleet") \
        == ["r0", "r1", "r2"]
    shutil.rmtree(inc2)               # keep ONE newest incident for the
    fleet.close()                     # doctor assertions below
    # ---- doctor, file mode: reconciled incident is informational
    rc = doctor.main(["--dir", str(fdir)])
    out = capsys.readouterr().out
    assert rc == 0 and "[incident]" in out and "timeline" in out
    assert "3/3 live" in out
    # unreconciled (one replica's dump missing) trips the gate
    shutil.rmtree(inc / "r2")
    rc = doctor.main(["--dir", str(fdir)])
    out = capsys.readouterr().out
    assert rc == 1 and "unreconciled incident" in out
    assert doctor.main(["--dir", str(fdir), "--no-gate"]) == 0
    capsys.readouterr()
    # ---- doctor, fleet mode: --targets + --flight-dir runs the same
    # incident gate next to live triage (a clean target does not mask
    # an incomplete post-mortem)
    scfg = {"slots": 2, "max_len": M, "prefill_chunk": 16,
            "temperature": 0.8, "top_k": 20}
    a = ServingEngine(eng, scfg, programs=_PROGRAMS)
    try:
        pa = a.serve_telemetry(port=0)
        rc = doctor.main(["--targets", f"http://127.0.0.1:{pa}",
                          "--flight-dir", str(fdir)])
        out = capsys.readouterr().out
        assert rc == 1 and "unreconciled incident" in out
    finally:
        a.close()


# ------------------------------------------------------------ doctor fleet
def test_doctor_targets_fleet_gate(setup, capsys):
    from deepspeed_tpu.observability import doctor
    from deepspeed_tpu.serving import ServingEngine

    _, _, _, eng = setup
    # same serving config as the module's shared program cache family
    # (programs bake in the sampler — sharing needs identical config)
    scfg = {"slots": 2, "max_len": M, "prefill_chunk": 16,
            "temperature": 0.8, "top_k": 20}
    a = ServingEngine(eng, scfg, programs=_PROGRAMS)
    b = ServingEngine(eng, scfg, programs=_PROGRAMS)
    try:
        pa, pb = a.serve_telemetry(port=0), b.serve_telemetry(port=0)
        rc = doctor.main(
            ["--targets", f"http://127.0.0.1:{pa},http://127.0.0.1:{pb}"])
        out = capsys.readouterr().out
        assert rc == 0 and "[gate] clean" in out and "2/2 up" in out
        # a down replica is a gate finding (exit 1); --no-gate reports only
        rc = doctor.main(
            ["--targets", f"http://127.0.0.1:{pa},http://127.0.0.1:1"])
        out = capsys.readouterr().out
        assert rc == 1 and "DOWN" in out
        rc = doctor.main(
            ["--targets", f"http://127.0.0.1:{pa},http://127.0.0.1:1",
             "--no-gate"])
        assert rc == 0
    finally:
        a.close()
        b.close()


# ------------------------------------------- replica-scoped drain & removal
def test_draining_decode_replica_stops_receiving_handoffs(setup):
    """A decode replica whose intake is closed (``begin_drain_replica``
    — the autoscaler's drain-before-remove seam) must stop receiving
    NEW handoff imports while a non-draining sibling exists: an import
    onto the drain victim gives it fresh work exactly when the
    scale-down is waiting for it to idle."""
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=3, prefill_replicas=1,
                   serving={"page_size": 8})
    try:
        d0, d1 = [n for n, r in fleet.roles.items() if r == "decode"]
        prompts = _prompts(3, seed=21)
        # choke BOTH decode pools so every finished prefill piles up in
        # the pending-handoff buffer instead of importing
        saved = {}
        for n in (d0, d1):
            saved[n] = fleet.replicas[n].pool.free[:]
            fleet.replicas[n].pool.free[:] = []
        rids = [fleet.submit(p, 5, seed=60 + i)
                for i, p in enumerate(prompts)]
        it = 0
        while len(fleet._handoffs) < len(rids):
            fleet.step()
            it += 1
            assert it < 200, "handoffs never reached the pending buffer"
        fleet.begin_drain_replica(d0)
        for n in (d0, d1):
            fleet.replicas[n].pool.free[:] = saved[n]
        done = _drive(fleet, rids)
        assert fleet.replicas[d0].stats.snapshot()["decode_steps"] == 0, \
            "draining decode replica received a handoff import"
        for i, rid in enumerate(rids):
            want = _solo(eng, prompts[i], 5, 60 + i)
            got = np.asarray(done[rid].tokens, np.int32)
            assert np.array_equal(got, want[:len(got)])
        # the drain victim is now idle and legally removable
        e = fleet.replicas[d0]
        assert e.sched.idle and e._prefill is None
        fleet.remove_replica(d0)
        assert d0 not in fleet.replicas
    finally:
        fleet.close()


def test_remove_replica_repumps_victim_owned_handoffs(setup):
    """Removing the replica that EXPORTED a still-pending handoff must
    clear its ghost owner entry and re-pump the payload onto a survivor
    in the same call — before the victim's scheduler is gone — not
    strand it until some later step (or forever, if the fleet idles)."""
    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=3, prefill_replicas=2,
                   serving={"page_size": 8})
    try:
        dec = [n for n, r in fleet.roles.items() if r == "decode"][0]
        prompt = _prompts(1, seed=22)[0]
        saved = fleet.replicas[dec].pool.free[:]
        fleet.replicas[dec].pool.free[:] = []
        rid = fleet.submit(prompt, 5, seed=70)
        it = 0
        while not fleet._handoffs:
            fleet.step()
            it += 1
            assert it < 200, "handoff never reached the pending buffer"
        owner = fleet._owner[rid]
        assert fleet.roles[owner] == "prefill"
        # reopen the decode pool FIRST: the removal's re-pump has a
        # live destination, so the import must happen inside the call
        fleet.replicas[dec].pool.free[:] = saved
        requeued = fleet.remove_replica(owner)
        assert rid not in requeued, \
            "an exported payload survives its exporter — not a requeue"
        assert not fleet._handoffs, \
            "remove_replica left the victim-owned handoff stranded"
        assert fleet._owner.get(rid) == dec, \
            f"ghost owner entry: {fleet._owner.get(rid)!r}"
        done = _drive(fleet, [rid])
        want = _solo(eng, prompt, 5, 70)
        got = np.asarray(done[rid].tokens, np.int32)
        assert np.array_equal(got, want[:len(got)])
        assert done[rid].status is RequestStatus.OK \
            and done[rid].attempts == 0
    finally:
        fleet.close()


# ------------------------------------------------ the whole failover arc
def test_decode_kill_mid_traffic_traced_end_to_end(setup):
    """One arc, on the fake clock, that the tests above hold in pieces: a
    traced disaggregated fleet loses a decode replica while it is decoding
    handed-off requests. Nothing is lost and every output still equals solo
    generate(); the survivors compile nothing through the kill and the
    requeue; every request's hops — the requeued ones' too — sum to its
    e2e within 1%; the audit has one requeue entry per requeued request;
    and the merged trace is valid with flows across replicas and at least
    router + two replica pids."""
    from deepspeed_tpu.observability import validate_chrome_trace

    _, _, _, eng = setup
    fleet = _fleet(eng, replicas=3, clock=TickClock(), prefill_replicas=1,
                   serving={"page_size": 8, "spans": True})
    try:
        # every program the arc needs is built before the kill: the
        # second pass hits the prefix tree, as a requeued prefill will
        for _ in range(2):
            _drive(fleet, [fleet.submit(p, 5, seed=240 + i)
                           for i, p in enumerate(_prompts(6, seed=14))])
        warm = {n: e.compiles for n, e in fleet.replicas.items()}
        prompts = _prompts(6, seed=15)
        rids = [fleet.submit(p, 5, seed=250 + i, session_id=f"s{i % 3}")
                for i, p in enumerate(prompts)]
        done, it = {}, 0
        while len(done) < len(rids):
            for req in fleet.step():
                done[req.rid] = req
            if "d1" in fleet.replicas and fleet.replicas["d1"].sched.running:
                fleet.kill_replica("d1")
            it += 1
            assert it < 50_000, "fleet driver stuck"
        assert "d1" not in fleet.replicas, "d1 never decoded: no kill"
        requeued = [r for r in rids if done[r].attempts > 0]
        counters = fleet.registry.snapshot()["counters"]
        assert requeued and int(counters["Fleet/requeued"]) == len(requeued)
        for i, rid in enumerate(rids):
            assert done[rid].status is RequestStatus.OK
            got = np.asarray(done[rid].tokens, np.int32)
            np.testing.assert_array_equal(
                got, _solo(eng, prompts[i], 5, 250 + i)[:len(got)])
            hops = fleet.request_trace(rid)["hops"]
            parts = sum(hops[f"{k}_s"] or 0.0 for k in (
                "queue_wait", "prefill", "handoff_wait", "import",
                "decode"))
            assert parts == pytest.approx(hops["e2e_s"], rel=0.01), rid
            assert all(e["candidates"] for e in fleet.route_audit(rid))
        assert {n: e.compiles for n, e in fleet.replicas.items()} \
            == {n: warm[n] for n in fleet.replicas}
        moves = [e for e in fleet.route_audit()
                 if e["event"] in ("requeue", "requeue_shed")]
        assert sorted(e["rid"] for e in moves) == sorted(requeued)
        merged = fleet.merge_trace()
        assert validate_chrome_trace(merged) == []
        evs = merged["traceEvents"]
        flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
        assert len({e["pid"] for e in flows}) >= 2
        assert len({e["pid"] for e in evs if e["ph"] != "M"}) >= 3
    finally:
        fleet.close()

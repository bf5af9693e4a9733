"""Fleet bench: multi-replica serving under chaos + disaggregated roles.

Drives the :class:`~deepspeed_tpu.serving.FleetEngine` (serving/fleet.py)
through the two fleet scenarios the ROADMAP names:

- **chaos failover** — a 3-replica fleet on the injectable fake clock
  serves deterministic traffic while one replica is killed mid-stream
  (the seeded ``FleetChaosConfig`` fault). The oracle: ZERO requests
  lost — every rid retires with a terminal status, the killed replica's
  queued and in-flight requests requeue onto survivors (typed
  ``REQUEUED``, ``attempts`` bumped) and still produce bit-identical
  output to solo ``generate()`` (per-request RNG folds from the seed);
  survivors' compile counters stay FROZEN through the whole event (a
  failover must never compile-storm).
- **disaggregated prefill/decode** — dedicated prefill replicas run
  chunked prefill to completion, hand finished KV to decode replicas as
  a host-mediated page transfer (``export_slot``/``import_slot`` on the
  PR-7 pool), and the disaggregated output is bit-identical to a single
  engine's on the same seeds.

``--smoke`` is the CPU tier-1 gate (wired via tests/unit/test_fleet.py,
same pattern as bench_serving.py): asserts both oracles plus a warm
``add_replica`` join compiling NOTHING, and writes ``FLEET_BENCH.json``.
The disaggregated phase additionally runs with distributed tracing ON
and a decode-replica kill mid-traffic: it asserts the hop sum-to-e2e
invariant (every completed request's queue_wait/prefill/handoff_wait/
import/decode hops tile its e2e wall within 1% on the fake clock), a
route-audit entry for every routing decision, and a merged fleet
Chrome trace (replicas as pids, cross-replica request flows) that
passes ``validate_chrome_trace`` — written to ``FLEET_TRACE.json``.
Prints one JSON line ending in "smoke-pass"; exits nonzero on failure.
"""

import dataclasses
import json
import os
import sys

import numpy as np


class TickClock:
    """Deterministic injectable clock (+dt per read): the whole fleet —
    schedulers, watchdogs, goodput ledgers, deadline sweeps — runs on
    fake time, so the bench is bit-reproducible on any machine."""

    def __init__(self, dt=0.001):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t

    def advance(self, s):
        self.t += s


def build_engine(max_len=48):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(n_layer=2, d_model=64, d_ff=128, n_head=4,
                    max_seq=max_len, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ds.init_inference(model, params,
                             {"dtype": "float32", "eos_token_id": 510})


def build_fleet(eng, replicas=3, prefill_replicas=0, slots=2, max_len=48,
                chunk=16, clock=None, chaos=None, **serving_extra):
    from deepspeed_tpu.serving import FleetEngine

    return FleetEngine(eng, {"slots": slots, "max_len": max_len,
                             "prefill_chunk": chunk, "temperature": 0.8,
                             "top_k": 20, "goodput": True,
                             **serving_extra},
                       replicas=replicas,
                       prefill_replicas=prefill_replicas,
                       clock=clock, chaos=chaos)


def solo_oracle(eng, prompt, max_new, seed, max_len):
    """The documented parity oracle: single-request generate() with the
    request's seed and the serving cache length."""
    import jax.numpy as jnp

    return np.asarray(eng.generate(
        jnp.asarray(np.asarray(prompt)[None], jnp.int32), max_new,
        temperature=0.8, top_k=20, request_seeds=[seed],
        cache_len=max_len))[0]


def traffic(n, seed, lengths=(5, 16, 20, 30)):
    """Deterministic prompt stream over a FIXED length set — it spans
    every chunk-bucket shape (pad, exact, overlap, multi-chunk) so
    warmup covers what the main phase uses (the compile freeze is only
    meaningful if shapes repeat), and stays SMALL because every unique
    length also costs one solo-oracle generate() compile."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, (lengths[i % len(lengths)],))
             .astype(np.int32), 6, 100 + i) for i in range(n)]


def drive(fleet, reqs, kill_after=None, max_iterations=100_000):
    """Submit ``reqs`` (prompt, max_new, seed), run to completion,
    return {rid: Request}. ``kill_after`` arms the fleet chaos monkey
    ``kill_after`` fleet iterations from NOW (mid-traffic, independent
    of how many warmup iterations ran before)."""
    rids = [fleet.submit(p, mn, seed=sd) for p, mn, sd in reqs]
    if kill_after is not None:
        fleet.chaos.cfg = dataclasses.replace(
            fleet.chaos.cfg,
            kill_replica_step=fleet.chaos._iterations + kill_after)
    done = {}
    it = 0
    while len(done) < len(rids):
        for req in fleet.step():
            done[req.rid] = req
            fleet.results.pop(req.rid, None)
        it += 1
        if it > max_iterations:
            raise RuntimeError("fleet bench driver stuck")
    return rids, done


# ------------------------------------------------------------------ smoke
def smoke():
    """CPU tier-1 gate: chaos-kill zero-loss + frozen compiles + warm
    join + disaggregated parity. Everything on the fake clock."""
    from deepspeed_tpu.serving import RequestStatus

    max_len = 48
    res = {"smoke": True}
    eng = build_engine(max_len)     # ONE engine (and one solo-oracle
    # program cache) shared by both phases — fleets are views over it

    # ---- A) 3-replica chaos failover -------------------------------
    clock = TickClock()
    fleet = build_fleet(
        eng, replicas=3, clock=clock,
        chaos={"enabled": True, "seed": 1, "kill_replica": "r1"})
    # warmup: cover every chunk bucket on every replica path; no kill
    drive(fleet, traffic(6, seed=11))
    warm_compiles = {n: e.compiles for n, e in fleet.replicas.items()}
    total_warm = sum(warm_compiles.values())

    # main traffic: kill r1 four iterations in, mid-prefill/decode
    reqs = traffic(12, seed=23)
    rids, done = drive(fleet, reqs, kill_after=4)

    assert fleet.chaos.injected, "chaos kill never fired"
    assert "r1" not in fleet.replicas, "victim still in the fleet"
    # (1) zero loss: every request retired with a terminal status
    missing = [r for r in rids if r not in done]
    assert not missing, f"requests lost in failover: {missing}"
    assert all(done[r].status is RequestStatus.OK for r in rids), \
        {r: done[r].status for r in rids}
    # (2) the failover is visible: requeues counted, attempts bumped
    snap = fleet.metrics_snapshot()
    requeued = int(snap["fleet"].get("Fleet/requeued", 0))
    bumped = [r for r in rids if done[r].attempts > 0]
    assert requeued >= 1 and len(bumped) == requeued, \
        f"requeued={requeued} but {len(bumped)} requests carry attempts"
    # (3) bit-parity vs solo generate, INCLUDING the requeued requests
    for (p, mn, sd), rid in zip(reqs, rids):
        got = np.asarray(done[rid].tokens, np.int32)
        want = solo_oracle(eng, p, mn, sd, max_len)
        assert np.array_equal(got, want[:len(got)]), \
            f"rid {rid} (attempts={done[rid].attempts}) diverged"
    # (4) survivors' compile counters FROZEN through kill + requeue
    for n, e in fleet.replicas.items():
        assert e.compiles == warm_compiles[n], \
            f"replica {n} compiled {e.compiles - warm_compiles[n]} new " \
            "programs during failover"
    # (5) warm join: a replica added now compiles NOTHING and serves
    joined = fleet.add_replica()
    jr, jdone = drive(fleet, traffic(6, seed=31))
    je = fleet.replicas[joined]
    assert je.compiles == 0, \
        f"joined replica compiled {je.compiles} programs"
    assert je.stats.snapshot()["retired"] >= 1, \
        "joined replica never received traffic"
    gp = fleet.fleet_goodput()
    # (6) requeue attribution: kill → re-admission lands in its OWN
    # Serve/requeue_delay_s histogram, one observation per requeue (so
    # TTFT and failover delay stay separable in the request log)
    rq_delays = sum(int(e.stats.registry.snapshot()["histograms"]
                        .get("Serve/requeue_delay_s", {}).get("count", 0))
                    for e in fleet.replicas.values())
    assert rq_delays == requeued, \
        f"requeue_delay_s observations {rq_delays} != requeued {requeued}"
    # tracing stayed DISABLED in this phase: no fleet ring, no audit —
    # and the compile counters above already pinned the program set
    assert fleet.spans is None and fleet.route_audit() == []
    res["failover"] = {
        "replicas": 3, "requests": len(rids), "requeued": requeued,
        "kills": int(snap["fleet"].get("Fleet/replica_kills", 0)),
        "lost": 0, "warm_compiles_total": total_warm,
        "survivor_compiles_frozen": True,
        "joined_replica_compiles": je.compiles,
        "requeue_delay_observations": rq_delays,
        "fleet_goodput_frac": (round(gp["goodput_frac"], 4)
                               if gp and gp["goodput_frac"] is not None
                               else None),
    }
    fleet.close()

    # ---- B) disaggregated chaos run + distributed tracing ----------
    # prefill replica + 2 decode replicas, tracing ON, one decode
    # replica killed mid-decode: the acceptance scenario for the
    # fleet-wide trace (hops sum to e2e, merged trace w/ cross-replica
    # flows, a route-audit entry behind every decision)
    from deepspeed_tpu.observability import validate_chrome_trace

    clock2 = TickClock()
    fl2 = build_fleet(eng, replicas=3, prefill_replicas=1, clock=clock2,
                      page_size=8, spans=True)
    sys_p = np.random.default_rng(7).integers(0, 256, (16,)).astype(np.int32)
    rng = np.random.default_rng(5)
    prompts = [np.concatenate([sys_p, rng.integers(0, 256, (k,))
                               .astype(np.int32)])
               for k in (4, 7, 4, 7, 4, 7)]
    rids2 = [fl2.submit(p, 5, seed=200 + i, session_id=f"s{i % 3}")
             for i, p in enumerate(prompts)]
    done2 = {}
    killed = False
    it = 0
    while len(done2) < len(rids2):
        for req in fl2.step():
            done2[req.rid] = req
        if not killed and "d1" in fl2.replicas \
                and fl2.replicas["d1"].sched.running:
            # d1 is decoding a handed-off request: kill it NOW — its
            # residents requeue through prefill and hand off again
            fl2.kill_replica("d1")
            killed = True
        it += 1
        assert it < 100_000
    assert killed, "d1 never held a decoding request — kill never fired"
    requeued2 = int(fl2.registry.snapshot()["counters"]
                    .get("Fleet/requeued", 0))
    assert requeued2 >= 1, "the kill orphaned nothing"
    for i, (p, rid) in enumerate(zip(prompts, rids2)):
        got = np.asarray(done2[rid].tokens, np.int32)
        want = solo_oracle(eng, p, 5, 200 + i, max_len)
        assert np.array_equal(got, want[:len(got)]), \
            f"disaggregated rid {rid} diverged from solo generate " \
            f"(attempts={done2[rid].attempts})"
    snap2 = fl2.metrics_snapshot()
    handoffs = int(snap2["fleet"].get("Fleet/handoffs", 0))
    imports = int(snap2["fleet"].get("Fleet/handoff_imports", 0))
    assert handoffs >= 1 and imports == handoffs, \
        f"handoffs={handoffs} imports={imports}"
    # role separation is real: prefill replicas never decode, decode
    # replicas never prefill
    for n, e in fl2.replicas.items():
        s = e.stats.snapshot()
        if fl2.roles[n] == "prefill":
            assert s["decode_steps"] == 0, f"{n} ran decode steps"
        else:
            assert s["prefill_chunks"] == 0, f"{n} ran prefill chunks"
    saved = sum(e.pool.snapshot()["prefill_tokens_saved"]
                for n, e in fl2.replicas.items()
                if fl2.roles[n] == "prefill")
    # (t1) hop sum-to-e2e invariant: every completed request's non-null
    # hops tile [submit, finish] — within 1% on the fake clock
    worst_err = 0.0
    with_handoff = 0
    hop_keys = ("queue_wait", "prefill", "handoff_wait", "import",
                "decode")
    for rid in rids2:
        tr = fl2.request_trace(rid)
        assert tr is not None, f"request_trace({rid}) unknown"
        hops = tr["hops"]
        total = sum(hops[f"{k}_s"] or 0.0 for k in hop_keys)
        assert hops["e2e_s"] and hops["e2e_s"] > 0
        err = abs(total - hops["e2e_s"]) / hops["e2e_s"]
        assert err <= 0.01, f"rid {rid}: hops {total} vs e2e " \
            f"{hops['e2e_s']} ({err:.2%})"
        worst_err = max(worst_err, err)
        if hops["handoff_wait_s"] is not None:
            with_handoff += 1
    assert with_handoff >= 1, "no request carried handoff hops"
    # (t2) route audit: every routing decision is explained — ranked
    # candidates with per-replica exclusion reasons behind each rid
    for rid in rids2:
        audit = fl2.route_audit(rid)
        assert audit, f"rid {rid} has no route-audit entry"
        assert all(e["candidates"] for e in audit), rid
    kill_moves = [e for e in fl2.route_audit()
                  if e["event"] in ("requeue", "requeue_shed")]
    assert len(kill_moves) == requeued2
    # (t3) ONE merged Chrome trace: replicas as pids, request hops
    # stitched into cross-replica flows, schema-valid
    merged = fl2.merge_trace()
    problems = validate_chrome_trace(merged)
    assert problems == [], problems
    evs = merged["traceEvents"]
    flow = [e for e in evs if e["ph"] in ("s", "t", "f")]
    pids = sorted({e["pid"] for e in evs if e["ph"] != "M"})
    assert flow, "merged trace has no flow events"
    assert len({e["pid"] for e in flow}) >= 2, \
        "flows never crossed a replica boundary"
    assert len(pids) >= 3, f"expected router + >=2 replica pids: {pids}"
    trace_out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "FLEET_TRACE.json")
    with open(trace_out, "w") as f:
        json.dump(merged, f)
    res["disaggregated"] = {
        "replicas": 3, "prefill_replicas": 1, "requests": len(rids2),
        "handoffs": handoffs, "handoff_imports": imports,
        "parity_with_solo": True, "decode_replica_killed": True,
        "requeued": requeued2,
        "prefill_tokens_saved_at_source": int(saved),
    }
    res["tracing"] = {
        "hop_sum_worst_rel_err": round(worst_err, 6),
        "requests_with_handoff_hops": with_handoff,
        "route_audit_entries": len(fl2.route_audit()),
        "merged_trace_valid": True,
        "merged_trace_events": len(evs),
        "flow_events": len(flow), "pids": pids,
        "trace_file": "FLEET_TRACE.json",
    }
    fl2.close()

    res["verdict"] = "smoke-pass"
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "FLEET_BENCH.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


# ------------------------------------------------------------------- main
def main():
    """Fuller (still CPU-sized) run: bigger traffic, per-replica routing
    spread, goodput rollup — written to FLEET_BENCH.json."""
    clock = TickClock()
    eng = build_engine()
    fleet = build_fleet(
        eng, replicas=3, clock=clock,
        chaos={"enabled": True, "seed": 1, "kill_replica": "r1"})
    drive(fleet, traffic(9, seed=11))                       # warmup
    reqs = traffic(36, seed=23)
    rids, done = drive(fleet, reqs, kill_after=10)
    snap = fleet.metrics_snapshot()
    gp = fleet.fleet_goodput()
    res = {
        "workload": {"replicas": 3, "requests": len(rids),
                     "slots_per_replica": 2, "max_len": 48,
                     "prefill_chunk": 16},
        "completed": sum(1 for r in rids if r in done),
        "requeued": int(snap["fleet"].get("Fleet/requeued", 0)),
        "kills": int(snap["fleet"].get("Fleet/replica_kills", 0)),
        "routed": {n: int(v) for n, v in snap["fleet"].items()
                   if n.startswith("Fleet/routed_")},
        "per_replica": {n: {"compiles": r["compiles"],
                            "retired": r["retired"],
                            "decode_steps": r["decode_steps"]}
                        for n, r in snap["replicas"].items()},
        "fleet_goodput": gp,
    }
    fleet.close()
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "FLEET_BENCH.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()

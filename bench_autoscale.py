"""Elastic autoscaler chaos bench: SLO-green scale events, proven.

Drives the autoscaler control loop (``serving/autoscaler.py``) on a
fake clock through every scale event the ROADMAP demands it survive,
against oracles it cannot fake:

- **inert by default** — ``serving.autoscale=None`` attaches nothing
  (``fleet.autoscaler is None``), and turning the loop ON compiles
  ZERO extra programs on identical traffic (the shared-program-cache
  compile freeze, same oracle as ``bench_fleet.py --smoke``);
- **scale-up** — an overload trace arms the add signal through the
  hysteresis streak; the joined replica warms from the fleet program
  cache (0 compiles) and serves; the actuation's decision record
  embeds the ``scaling_report()`` inputs it fired on verbatim;
- **drain-before-remove** — a lull arms the remove signal; the victim
  drains (intake closed, backlog finishes) and is removed only when
  idle — zero requests lost, outputs bit-identical to solo
  ``generate()`` with the same request seed;
- **mid-traffic replica kill** — the incident cooldown latch holds an
  armed scale-down signal: failover is never misread as a lull;
- **flap-bait** — an oscillating trace costs at most ``flap_budget``
  direction reversals, then the loop freezes itself and alarms instead
  of oscillating;
- **SLO burn stays green** — every replica's ``Serve/slo_*_burn``
  gauges stay <= 1 and the violation counters stay 0 through every
  scale event;
- **doctor** — the ``[autoscale]`` section gates on flap-budget
  exhaustion and a frozen-stale loop, stays clean otherwise.

``--smoke`` is the CPU tier-1 gate (wired via
``tests/unit/test_autoscaler.py``); the full mode runs the same chaos
script with more traffic, replays the captured autoscaled run through
the ReplayDriver (the recorded add/drain edges co-replay), and writes
``AUTOSCALE_BENCH.json`` for the cross-PR perf ledger.
"""

import contextlib
import io
import json
import os
import sys
from collections import OrderedDict

import numpy as np

_SLOTS, _M, _CHUNK = 2, 48, 16
_PROMPT_LEN, _MAX_NEW = 9, 6

# fake-clock service calibration (the scaling_backtest seam): spans
# measure wall time, the bench runs on fake seconds — so capacity is
# DECLARED per replica and traffic rates are derived from it. One
# replica serves 20 decode tokens per fake second.
_OVR = {"slots": _SLOTS, "decode_tokens_per_slot_s": 10.0,
        "decode_tokens_per_s": 20.0, "prefill_tokens_per_s": 400.0}


def _rate(rho: float, n: int) -> float:
    """Requests/fake-second whose decode demand reads utilization
    ``rho`` on ``n`` calibrated replicas."""
    return rho * n * _OVR["decode_tokens_per_s"] / _MAX_NEW


def _build_engine():
    from bench_serving import build

    _model, _params, eng, _srv = build(
        slots=_SLOTS, max_len=_M, chunk=_CHUNK, n_layer=2, d_model=64,
        n_head=4)
    return eng


def _mk_fleet(eng, programs, clock, replicas=2, autoscale=None,
              capture=False):
    from deepspeed_tpu.serving import FleetEngine

    serving = {"slots": _SLOTS, "max_len": _M, "prefill_chunk": _CHUNK,
               "temperature": 0.8, "top_k": 20,
               "slo": {"ttft_p99_s": 30.0},
               "loadscope": {"window_s": 8.0}}
    if autoscale is not None:
        serving["autoscale"] = autoscale
    if capture:
        serving["capture"] = True
    fl = FleetEngine(eng, serving, replicas=replicas, clock=clock,
                     programs=programs)
    for e in fl.replicas.values():
        e.loadscope.service_override = dict(_OVR)
    return fl


# the autoscale knobs every scenario shares; scenarios override cadence
_ASC = {"tick_s": 1.0, "up_ticks": 2, "down_ticks": 2,
        "add_score_min": 60.0, "remove_score_min": 60.0,
        "cooldown_up_s": 3.0, "cooldown_down_s": 3.0,
        "flap_budget": 2, "flap_window_s": 1000.0,
        "drain_deadline_s": 5.0, "incident_cooldown_s": 8.0,
        "min_replicas": 2, "max_replicas": 4}


class _Run:
    """One scenario's ledger: everything submitted, everything done."""

    def __init__(self, seed=0):
        self.rng = np.random.default_rng(seed)
        self.subs: dict = {}          # rid -> (prompt, seed)
        self.done: dict = {}          # rid -> finished Request
        self.shed_submits = 0
        self.t_next = 0.0
        self.n = 0


def _drive(fl, clock, run, rate, duration_s, step_dt=0.02,
           stop_fn=None, max_iter=20_000):
    """Submit at ``rate`` req/fake-s while stepping the fleet for
    ``duration_s`` fake seconds. Joined replicas get the calibration
    override as soon as they appear (the harness plays ops: a real
    deployment's loadscope would measure from spans)."""
    t_end = clock.t + duration_s
    if run.t_next < clock.t:
        run.t_next = clock.t
    it = 0
    while clock.t < t_end:
        while rate > 0 and run.t_next <= clock.t:
            prompt = run.rng.integers(0, 256, (_PROMPT_LEN,)) \
                .astype(np.int32)
            seed = 1000 + run.n
            try:
                rid = fl.submit(prompt, _MAX_NEW, seed=seed)
                run.subs[rid] = (prompt, seed)
            except Exception:
                run.shed_submits += 1
            run.n += 1
            run.t_next += 1.0 / rate
        for req in fl.step():
            run.done[req.rid] = req
        for e in fl.replicas.values():
            if e.loadscope is not None \
                    and e.loadscope.service_override is None:
                e.loadscope.service_override = dict(_OVR)
        if stop_fn is not None and stop_fn():
            return True
        clock.advance(step_dt)
        it += 1
        assert it < max_iter, "bench driver stuck"
    return False


def _finish(fl, clock, run, max_iter=20_000):
    """Step until every submitted request reaches a terminal state."""
    it = 0
    while set(run.subs) - set(run.done):
        for req in fl.step():
            run.done[req.rid] = req
        clock.advance(0.02)
        it += 1
        assert it < max_iter, \
            f"requests never finished: {sorted(set(run.subs) - set(run.done))[:8]}"


def _assert_zero_loss(run, tag):
    from deepspeed_tpu.serving import RequestStatus

    missing = set(run.subs) - set(run.done)
    assert not missing, f"{tag}: lost rids {sorted(missing)[:8]}"
    bad = {r: run.done[r].status for r in run.subs
           if run.done[r].status is not RequestStatus.OK}
    assert not bad, f"{tag}: non-OK terminals {bad}"


def _assert_parity(eng, run, tag, sample=24):
    """Finished outputs bit-identical to solo generate() under the same
    request seed — requeued/re-imported requests included."""
    import jax.numpy as jnp

    rids = sorted(run.subs)
    pick = rids[:sample] + [r for r in rids[sample:]
                            if run.done[r].attempts > 0]
    for rid in pick:
        prompt, seed = run.subs[rid]
        want = np.asarray(eng.generate(
            jnp.asarray(prompt[None], jnp.int32), _MAX_NEW,
            temperature=0.8, top_k=20, request_seeds=[seed],
            cache_len=_M))[0]
        got = np.asarray(run.done[rid].tokens, np.int32)
        assert np.array_equal(got, want[:len(got)]), \
            f"{tag}: rid {rid} diverged from solo"


def _assert_slo_green(fl, tag):
    for n, e in fl.replicas.items():
        if e.slo is not None:
            e.slo.score()
        snap = e.stats.registry.snapshot()
        for k, v in snap["gauges"].items():
            if k.startswith("Serve/slo_") and k.endswith("_burn"):
                assert not (v > 1.0), \
                    f"{tag}: {n} {k}={v} latched through a scale event"
        viol = int(snap["counters"].get("Serve/slo_violations", 0))
        assert viol == 0, f"{tag}: {n} recorded {viol} SLO violations"


def _decisions(fl, **match):
    return [d for d in fl.autoscale_audit()
            if all(d.get(k) == v for k, v in match.items())]


def _doctor_exit(prom_text, tmp) -> int:
    from deepspeed_tpu.observability import doctor

    os.makedirs(tmp, exist_ok=True)
    with open(os.path.join(tmp, "autoscale.prom"), "w") as f:
        f.write(prom_text)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = doctor.main(["--dir", tmp])
    return rc


# ------------------------------------------------------------- scenarios
def scenario_inert(eng, progs):
    """Autoscale off attaches nothing; on compiles zero extra programs."""
    from deepspeed_tpu.observability.replay import ReplayClock

    clock = ReplayClock(dt=1e-4)
    fl = _mk_fleet(eng, progs, clock, replicas=2, autoscale=None)
    run = _Run(seed=1)
    try:
        assert fl.autoscaler is None, \
            "serving.autoscale=None must attach NO autoscaler"
        _drive(fl, clock, run, rate=_rate(0.5, 2), duration_s=2.0)
        _finish(fl, clock, run)
        gauges = fl.registry.snapshot()["gauges"]
        assert not any(k.startswith("Fleet/autoscale") for k in gauges), \
            "autoscale off must export no autoscale gauges"
    finally:
        fl.close()
    warm = len(progs)
    assert warm > 0

    clock = ReplayClock(dt=1e-4)
    fl = _mk_fleet(eng, progs, clock, replicas=2, autoscale=dict(_ASC))
    run = _Run(seed=1)
    try:
        assert fl.autoscaler is not None
        _drive(fl, clock, run, rate=_rate(0.5, 2), duration_s=2.0)
        _finish(fl, clock, run)
        assert len(progs) == warm, \
            f"autoscale on compiled {len(progs) - warm} extra programs"
        assert all(e.compiles == 0 for e in fl.replicas.values()), \
            "autoscale on must not compile anything new"
        assert fl.autoscaler.evals > 0
        _assert_zero_loss(run, "inert")
    finally:
        fl.close()
    return {"programs_warm": warm, "requests": len(run.subs)}


def scenario_scale_up_then_drain_down(eng, progs, capture=False,
                                      hi_s=25.0, down_s=45.0):
    """Overload -> warm add; lull -> drain-before-remove. One fleet
    lives through both so the audit carries the full arc."""
    from deepspeed_tpu.observability.replay import ReplayClock

    clock = ReplayClock(dt=1e-4)
    fl = _mk_fleet(eng, progs, clock, replicas=2, autoscale=dict(_ASC),
                   capture=capture)
    run = _Run(seed=2)
    out = {}
    try:
        t0 = clock.t
        scaled = _drive(fl, clock, run, rate=_rate(0.96, 2),
                        duration_s=hi_s,
                        stop_fn=lambda: len(fl.replicas) > 2)
        assert scaled, ("scale-up never actuated: "
                        + json.dumps(fl.autoscale_audit()[-3:],
                                     default=str))
        out["scale_up_latency_s"] = round(clock.t - t0, 3)
        joined = [n for n in fl.replicas if n not in ("r0", "r1")]
        assert len(joined) == 1
        assert fl.replicas[joined[0]].compiles == 0, \
            f"join was not warm: {fl.replicas[joined[0]].compiles} compiles"
        adds = _decisions(fl, action="add_replica", outcome="actuated")
        assert adds, "no actuated add decision in the audit"
        # the acceptance contract: the actuation traces to the
        # scaling_report() inputs it fired on — verbatim, not re-derived
        inp = adds[-1]["inputs"]
        assert inp["fleet"]["rho"] is not None \
            and inp["fleet"]["replica_count"] == 2 \
            and inp["what_if"]["action"] == "add_replica" \
            and inp["what_if"]["score"] >= _ASC["add_score_min"], inp
        # let the joined replica serve a little at comfortable load
        _drive(fl, clock, run, rate=_rate(0.5, 3), duration_s=2.0)
        _assert_slo_green(fl, "scale-up")

        # ---- lull: remove arms, victim drains, removal only when idle
        t1 = clock.t
        shrunk = _drive(fl, clock, run, rate=_rate(0.10, 3),
                        duration_s=down_s,
                        stop_fn=lambda: len(fl.replicas) == 2)
        assert shrunk, ("drain-down never completed: "
                        + json.dumps(fl.autoscale_audit()[-3:],
                                     default=str))
        out["drain_down_latency_s"] = round(clock.t - t1, 3)
        started = _decisions(fl, outcome="drain_started")
        assert started, "no drain_started decision"
        removed = (_decisions(fl, outcome="removed")
                   + _decisions(fl, outcome="removed_at_deadline"))
        assert removed, "no removal decision"
        out["drain_clean"] = removed[-1]["outcome"] == "removed"
        out["requeued_at_removal"] = \
            len(removed[-1]["inputs"].get("requeued_rids", []))
        _finish(fl, clock, run)
        _assert_zero_loss(run, "scale-up/drain-down")
        _assert_parity(eng, run, "scale-up/drain-down")
        _assert_slo_green(fl, "drain-down")
        out["requests"] = len(run.subs)
        out["audit_decisions"] = len(fl.autoscale_audit())
        trace = fl.capture.trace() if capture else None
    finally:
        fl.close()
    return out, trace


def scenario_kill_latch(eng, progs):
    """A mid-traffic replica kill latches an ARMED scale-down signal:
    failover is never misread as a lull."""
    from deepspeed_tpu.observability.replay import ReplayClock

    asc = {**_ASC, "down_ticks": 4, "incident_cooldown_s": 8.0}
    clock = ReplayClock(dt=1e-4)
    fl = _mk_fleet(eng, progs, clock, replicas=3, autoscale=asc)
    run = _Run(seed=3)
    try:
        # low load: the remove signal arms (score ~76 at rho 0.10) but
        # the 4-tick streak has not fired yet when the kill lands
        _drive(fl, clock, run, rate=_rate(0.10, 3), duration_s=1.5)
        victim = [n for n in fl.replicas][-1]
        t_kill = clock.t
        fl.kill_replica(victim)
        # inside the latch window the armed signal must only be
        # suppressed — never actuated
        _drive(fl, clock, run, rate=_rate(0.10, 2), duration_s=6.0)
        assert clock.t < t_kill + asc["incident_cooldown_s"]
        for d in fl.autoscale_audit():
            if d["t"] >= t_kill:
                assert d["outcome"] not in ("drain_started", "removed",
                                            "removed_at_deadline"), \
                    f"scale-down actuated during the incident latch: {d}"
        assert _decisions(fl, rule="incident"), \
            "kill did not record an incident decision"
        assert _decisions(fl, rule="incident_latch",
                          outcome="suppressed"), \
            "armed scale-down was not visibly suppressed by the latch"
        c = fl.registry.snapshot()["counters"]
        assert int(c.get("Fleet/autoscale_incidents", 0)) >= 1
        _finish(fl, clock, run)
        _assert_zero_loss(run, "kill-latch")
        _assert_parity(eng, run, "kill-latch")
        _assert_slo_green(fl, "kill-latch")
        requeued = sum(1 for r in run.done.values() if r.attempts > 0)
    finally:
        fl.close()
    return {"requests": len(run.subs), "requeued_by_kill": requeued}


def scenario_flap_bait(eng, progs):
    """An oscillating trace costs at most flap_budget reversals, then
    the loop freezes itself instead of oscillating."""
    from deepspeed_tpu.observability.replay import ReplayClock

    asc = {**_ASC, "flap_budget": 1, "cooldown_up_s": 2.0,
           "cooldown_down_s": 2.0, "drain_deadline_s": 4.0}
    clock = ReplayClock(dt=1e-4)
    fl = _mk_fleet(eng, progs, clock, replicas=2, autoscale=asc)
    run = _Run(seed=4)
    try:
        # bait 1 (up): overload until the add actuates
        assert _drive(fl, clock, run, rate=_rate(0.96, 2),
                      duration_s=25.0,
                      stop_fn=lambda: len(fl.replicas) > 2), \
            "flap bait: first add never actuated"
        # bait 2 (down): lull until drain-then-remove lands (reversal
        # #1 — inside the budget)
        assert _drive(fl, clock, run, rate=_rate(0.10, 3),
                      duration_s=45.0,
                      stop_fn=lambda: len(fl.replicas) == 2), \
            "flap bait: remove never actuated"
        # bait 3 (up again): reversal #2 would exceed the budget — the
        # loop must freeze itself and hold, not add
        _drive(fl, clock, run, rate=_rate(0.96, 2), duration_s=14.0)
        assert len(fl.replicas) == 2, \
            "loop actuated past an exhausted flap budget"
        snap = fl.registry.snapshot()
        flaps = int(snap["counters"].get("Fleet/autoscale_flaps", 0))
        assert flaps <= asc["flap_budget"], \
            f"{flaps} flaps > budget {asc['flap_budget']}"
        assert snap["gauges"]["Fleet/autoscale_frozen"] == 1.0, \
            "exhausted flap budget must freeze the loop"
        assert snap["gauges"][
            "Fleet/autoscale_flap_budget_remaining"] == 0.0
        assert _decisions(fl, rule="flap_budget"), \
            "no flap_budget decision in the audit"
        st = fl.autoscaler.status()
        assert st["frozen"] and st["frozen_by"] == "flap_budget"
        # manual unfreeze (the POST /autoscale body) re-enables the loop
        fl.autoscaler.control({"freeze": False})
        assert not fl.autoscaler.status()["frozen"]
        _finish(fl, clock, run)
        _assert_zero_loss(run, "flap-bait")
        _assert_slo_green(fl, "flap-bait")
    finally:
        fl.close()
    return {"requests": len(run.subs), "flaps": flaps,
            "froze": True}


def scenario_doctor():
    import tempfile

    base = ("dstpu_fleet_autoscale_evals 50\n"
            "dstpu_fleet_autoscale_frozen {frozen}\n"
            "dstpu_fleet_autoscale_frozen_stale_s {stale}\n"
            "dstpu_fleet_autoscale_flap_budget_remaining {rem}\n")
    with tempfile.TemporaryDirectory() as td:
        rc_flap = _doctor_exit(base.format(frozen=1, stale=12.0, rem=0),
                               td)
    with tempfile.TemporaryDirectory() as td:
        rc_stale = _doctor_exit(base.format(frozen=1, stale=4000.0,
                                            rem=2), td)
    with tempfile.TemporaryDirectory() as td:
        rc_clean = _doctor_exit(base.format(frozen=0, stale=0.0, rem=2),
                                td)
    assert rc_flap == 1, "doctor [autoscale] flap gate did not trip"
    assert rc_stale == 1, "doctor [autoscale] frozen-stale gate did not trip"
    assert rc_clean == 0, "doctor [autoscale] false-fired on a clean loop"
    return {"flap_gate": rc_flap, "stale_gate": rc_stale,
            "clean": rc_clean}


def _replay_autoscaled(eng, progs, trace):
    """The captured autoscaled run co-replays: recorded add/drain edges
    apply at their recorded positions on a matching topology; on a solo
    engine they are counted-skip, never a crash."""
    from deepspeed_tpu.observability.replay import ReplayClock, ReplayDriver

    edges = [e for e in trace.chaos_events]
    assert any(e["event"] == "add_replica" for e in edges), edges
    assert any(e["event"] == "begin_drain" and e.get("replica")
               for e in edges), edges
    clock = ReplayClock(dt=1e-4)
    fl = _mk_fleet(eng, progs, clock, replicas=2, autoscale=None)
    try:
        rep = ReplayDriver(fl, trace, clock=clock).run()
        assert rep.chaos_applied >= 3, rep.as_dict()
        assert rep.parity is True, {
            "diverged": rep.diverged[:4], "matched": rep.matched,
            "replayed": rep.replayed}
    finally:
        fl.close()
    return {"chaos_applied": rep.chaos_applied,
            "chaos_skipped": len(rep.chaos_skipped),
            "replayed": rep.replayed, "parity": rep.parity}


# ------------------------------------------------------------------ smoke
def smoke():
    progs = OrderedDict()
    eng = _build_engine()
    inert = scenario_inert(eng, progs)
    arc, _trace = scenario_scale_up_then_drain_down(eng, progs)
    kill = scenario_kill_latch(eng, progs)
    flap = scenario_flap_bait(eng, progs)
    doc = scenario_doctor()
    print(json.dumps({
        "smoke": True,
        "programs_warm": inert["programs_warm"],
        "scale_up_latency_s": arc["scale_up_latency_s"],
        "drain_down_latency_s": arc["drain_down_latency_s"],
        "drain_clean": arc["drain_clean"],
        "requeued_by_kill": kill["requeued_by_kill"],
        "flaps": flap["flaps"],
        "doctor": doc,
        "verdict": "smoke-pass",
    }))


# ------------------------------------------------------------------- full
def bench():
    progs = OrderedDict()
    eng = _build_engine()
    res = {"inert": scenario_inert(eng, progs)}
    arc, trace = scenario_scale_up_then_drain_down(
        eng, progs, capture=True, hi_s=30.0, down_s=60.0)
    res["scale_arc"] = arc
    res["kill_latch"] = scenario_kill_latch(eng, progs)
    res["flap_bait"] = scenario_flap_bait(eng, progs)
    res["doctor"] = scenario_doctor()
    res["replay"] = _replay_autoscaled(eng, progs, trace)
    # ledger rows (down is good): how long a scale event takes end to
    # end, and how much work a scale-down strands (0 = clean drain)
    res["ledger"] = {
        "scale_up_latency_s": arc["scale_up_latency_s"],
        "drain_down_latency_s": arc["drain_down_latency_s"],
        "requeued_at_removal": arc["requeued_at_removal"],
        "flaps": res["flap_bait"]["flaps"],
    }
    return res


def main():
    res = bench()
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "AUTOSCALE_BENCH.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()

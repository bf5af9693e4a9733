"""Elastic fleet autoscaler (serving/autoscaler.py) + satellites.

Oracles:
- config validation: unknown keys and out-of-rail values raise with
  the offending knob named; None/instance pass through ``from_any``;
- the control loop on a stub fleet + pinned clock (every guard exact):
  trust gate (null report, unmeasured rho, saturated forecast -> alarm,
  NEVER an actuation), per-direction hysteresis streaks (a blip resets
  the streak), post-actuation cooldowns, the incident latch (blocks
  remove, never add), flap budget exhaustion -> self-freeze, min/max
  replica rails, pin shields victims, audit dedup collapses held
  alarms;
- drain-before-remove: clean drain removes only once idle; a busy
  victim is removed at the deadline with its stragglers' rids in the
  decision record; **load reversal mid-drain reopens intake and the
  victim is NOT removed** (the satellite-3 contract), and an incident
  mid-drain aborts the drain on a foreign victim;
- every actuation's decision embeds the ``scaling_report()`` inputs it
  fired on verbatim (the acceptance contract);
- GET/POST /autoscale on the fleet ops surface: 404 when off, status
  body when on, token-gated freeze/pin, 400 on a bad body;
- replay co-replays autoscaler-recorded chaos edges: role-carrying
  add_replica and replica-scoped drain edges apply on a matching
  topology and counted-skip (never crash) on a mismatched one;
- remove_replica handoff ordering (the satellite-2 seam) is covered in
  test_fleet.py;
- the loop closed over a real fleet on a fake clock: overload -> warm
  add with the report it fired on -> lull -> clean drain-then-remove,
  zero loss, solo parity, SLO burn green; doctor's [autoscale] gates.
"""

import json
import types
import urllib.request
from collections import OrderedDict
from urllib.error import HTTPError

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.replay import (ReplayClock, ReplayDriver,
                                                TrafficTrace)
from deepspeed_tpu.serving import AutoscaleConfig, Autoscaler, FleetEngine
from deepspeed_tpu.serving.autoscaler import (ACTUATED, ALARM,
                                              DRAIN_ABORTED,
                                              DRAIN_STARTED, REMOVED,
                                              REMOVED_AT_DEADLINE,
                                              SUPPRESSED)

EOS = 7


# --------------------------------------------------------------- stub fleet
class _Clk:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class _StubEng:
    def __init__(self):
        self.sched = types.SimpleNamespace(idle=True)
        self._prefill = None
        self.draining = False

    def begin_drain(self):
        self.draining = True

    def end_drain(self):
        self.draining = False


class _StubFleet:
    """The exact surface Autoscaler consumes, with actuations ledgered
    so each guard's effect is assertable without a model."""

    def __init__(self, clock, n=2):
        self.registry = MetricsRegistry()
        self._clock = clock
        self.replicas = {f"r{i}": _StubEng() for i in range(n)}
        self._disagg = False
        self.roles = {name: "serve" for name in self.replicas}
        self.draining = False
        self.report = None
        self.added, self.removed, self.drain_calls = [], [], []
        self.requeue_on_remove: list = []
        self._next = n

    def scaling_report(self):
        return self.report

    def _killable(self):
        return list(self.replicas) if len(self.replicas) > 1 else []

    def _ranked(self, role, admission=True):
        return [{"name": n, "draining": e.draining}
                for n, e in self.replicas.items()]

    def add_replica(self, name=None, role=None):
        n = name or f"r{self._next}"
        self._next += 1
        self.replicas[n] = _StubEng()
        self.roles[n] = role or "serve"
        self.added.append((n, role))
        return n

    def begin_drain_replica(self, name):
        self.replicas[name].begin_drain()
        self.drain_calls.append(("begin", name))

    def end_drain_replica(self, name):
        self.replicas[name].end_drain()
        self.drain_calls.append(("end", name))

    def remove_replica(self, name):
        del self.replicas[name]
        self.removed.append(name)
        return list(self.requeue_on_remove)


def _rep(rho=0.5, add=0.0, rm=0.0, n=2, saturated=False):
    return {"schema": "dstpu.loadscope.v1", "replicas": {},
            "fleet": {"replica_count": n, "rho": rho,
                      "rho_prefill": None, "rho_decode": rho,
                      "arrival_rate_per_s": 1.0},
            "what_ifs": [
                {"action": "add_replica", "score": add,
                 "saturated_now": saturated},
                {"action": "remove_replica", "score": rm}]}


_CFG = {"tick_s": 1.0, "up_ticks": 2, "down_ticks": 2,
        "cooldown_up_s": 5.0, "cooldown_down_s": 5.0,
        "flap_budget": 2, "flap_window_s": 1000.0,
        "drain_deadline_s": 10.0, "incident_cooldown_s": 30.0,
        "min_replicas": 1, "max_replicas": 4}


def _mk(n=2, **over):
    clk = _Clk()
    fl = _StubFleet(clk, n=n)
    asc = Autoscaler(fl, {**_CFG, **over})
    return clk, fl, asc


def _tick(clk, asc, report, dt=1.0):
    asc.fleet.report = report
    clk.t += dt
    asc.on_step()


def _by(asc, **match):
    return [d for d in asc.audit_entries()
            if all(d.get(k) == v for k, v in match.items())]


# ------------------------------------------------------------------- config
def test_config_rejects_unknown_keys_and_bad_values():
    with pytest.raises(ValueError, match="unknown autoscale config keys"):
        AutoscaleConfig.from_any({"tick_s": 1.0, "bogus_knob": 3})
    for bad in ({"tick_s": 0}, {"add_score_min": 101.0},
                {"up_ticks": 0}, {"flap_window_s": 0},
                {"drain_deadline_s": 0}, {"min_replicas": 0},
                {"min_replicas": 4, "max_replicas": 2},
                {"audit_ring": 0}, {"cooldown_up_s": -1}):
        with pytest.raises(ValueError):
            AutoscaleConfig.from_any(bad)


def test_config_from_any_passthrough():
    assert AutoscaleConfig.from_any(None) is None
    cfg = AutoscaleConfig(tick_s=2.0)
    assert AutoscaleConfig.from_any(cfg) is cfg
    assert AutoscaleConfig.from_any({}).tick_s == 5.0


# --------------------------------------------------------------- trust gate
def test_trust_gate_null_and_unmeasured_alarm_never_actuate():
    clk, fl, asc = _mk()
    _tick(clk, asc, None)
    d = _by(asc, rule="signal_untrusted", outcome=ALARM)
    assert d and "no scaling report" in d[-1]["reason"]
    # a fresh loop (dedup collapses consecutive same-rule alarms)
    clk, fl, asc = _mk()
    rep = _rep(add=100.0)
    rep["fleet"]["rho"] = None
    rep["replicas"] = {"r0": {"unmeasured": ["arrival rate unmeasured"]}}
    for _ in range(4):
        _tick(clk, asc, rep)
    assert not fl.added and not fl.drain_calls
    d = _by(asc, rule="signal_untrusted", outcome=ALARM)
    assert any("arrival rate unmeasured" in x["reason"] for x in d)


def test_trust_gate_saturated_alarms_instead_of_acting():
    clk, fl, asc = _mk()
    for _ in range(6):
        _tick(clk, asc, _rep(rho=1.3, add=100.0, saturated=True))
    assert not fl.added, "a saturated (null) forecast must never actuate"
    d = _by(asc, rule="signal_untrusted", outcome=ALARM)
    assert d and "saturated" in d[-1]["reason"]
    # dedup: the held alarm writes ONE ring entry, not one per tick
    assert len(d) == 1


# --------------------------------------------------- hysteresis & cooldowns
def test_hysteresis_up_streak_and_blip_reset():
    clk, fl, asc = _mk()
    _tick(clk, asc, _rep(rho=0.96, add=75.0))      # armed x1
    _tick(clk, asc, _rep(rho=0.60, add=10.0))      # blip -> reset
    _tick(clk, asc, _rep(rho=0.96, add=75.0))      # armed x1 again
    assert not fl.added, "one armed tick must not actuate (up_ticks=2)"
    _tick(clk, asc, _rep(rho=0.96, add=75.0))      # armed x2 -> fire
    assert len(fl.added) == 1
    d = _by(asc, rule="hysteresis_up", outcome=ACTUATED)
    assert len(d) == 1 and d[0]["target"] == fl.added[0][0]
    # the acceptance contract: inputs are the report excerpt, verbatim
    assert d[0]["inputs"]["fleet"]["rho"] == 0.96
    assert d[0]["inputs"]["what_if"]["action"] == "add_replica"
    assert d[0]["inputs"]["what_if"]["score"] == 75.0


def test_cooldown_up_suppresses_until_horizon():
    clk, fl, asc = _mk()
    hot = _rep(rho=0.96, add=75.0)
    for _ in range(4):
        _tick(clk, asc, hot)
    assert len(fl.added) == 1
    assert _by(asc, rule="cooldown", outcome=SUPPRESSED), \
        "re-armed signal inside the cooldown must be visibly suppressed"
    clk.t += _CFG["cooldown_up_s"]
    for _ in range(2):
        _tick(clk, asc, _rep(rho=0.96, add=75.0, n=3))
    assert len(fl.added) == 2, "past the cooldown the signal actuates"


def test_rails_min_and_max_replicas():
    clk, fl, asc = _mk(n=4)
    for _ in range(3):
        _tick(clk, asc, _rep(rho=0.99, add=90.0, n=4))
    assert not fl.added
    assert _by(asc, rule="max_replicas", outcome=SUPPRESSED)
    clk2, fl2, asc2 = _mk(n=2, min_replicas=2)
    for _ in range(3):
        _tick(clk2, asc2, _rep(rho=0.05, rm=80.0))
    assert not fl2.drain_calls and not fl2.removed
    assert _by(asc2, rule="min_replicas", outcome=SUPPRESSED)


# ------------------------------------------------------ drain-before-remove
def test_drain_then_remove_only_once_idle():
    clk, fl, asc = _mk(n=3)
    lull = _rep(rho=0.05, rm=80.0, n=3)
    victim = "r0"                   # _ranked is insertion-ordered
    fl.replicas[victim].sched.idle = False       # backlog still running
    _tick(clk, asc, lull)
    _tick(clk, asc, lull)
    assert ("begin", victim) in fl.drain_calls
    assert _by(asc, outcome=DRAIN_STARTED)[0]["target"] == victim
    _tick(clk, asc, lull)
    assert not fl.removed, "a busy victim inside the deadline stays"
    fl.replicas[victim].sched.idle = True        # backlog finished
    _tick(clk, asc, lull)
    assert fl.removed == [victim]
    d = _by(asc, rule="drain_complete")
    assert d[0]["outcome"] == REMOVED \
        and d[0]["inputs"]["requeued_rids"] == []


def test_drain_deadline_removes_busy_victim_with_requeued_rids():
    clk, fl, asc = _mk(n=3, drain_deadline_s=3.0)
    lull = _rep(rho=0.05, rm=80.0, n=3)
    fl.replicas["r0"].sched.idle = False
    fl.requeue_on_remove = [41, 42]
    _tick(clk, asc, lull)
    _tick(clk, asc, lull)                        # drain starts
    _tick(clk, asc, lull, dt=5.0)                # past the deadline
    assert fl.removed == ["r0"]
    d = _by(asc, rule="drain_complete")
    assert d[0]["outcome"] == REMOVED_AT_DEADLINE
    assert d[0]["inputs"]["requeued_rids"] == [41, 42]


def test_drain_abort_on_load_reversal_keeps_the_replica():
    """Satellite 3: the add signal arming mid-drain reopens the
    victim's intake immediately — the replica is NOT removed and the
    audit explains the reversal."""
    clk, fl, asc = _mk(n=3)
    lull = _rep(rho=0.05, rm=80.0, n=3)
    fl.replicas["r0"].sched.idle = False         # drain stays in flight
    _tick(clk, asc, lull)
    _tick(clk, asc, lull)
    assert ("begin", "r0") in fl.drain_calls
    _tick(clk, asc, _rep(rho=0.97, add=80.0, n=3))   # load reverses
    assert ("end", "r0") in fl.drain_calls, "intake must reopen"
    assert "r0" in fl.replicas and not fl.removed, \
        "a reversed drain must NOT remove the replica"
    assert not fl.replicas["r0"].draining
    d = _by(asc, rule="load_reversal", outcome=DRAIN_ABORTED)
    assert d and d[0]["target"] == "r0" \
        and "load reversed mid-drain" in d[0]["reason"]
    assert asc.status()["streaks"]["remove"] == 0, \
        "the reversal must restart the scale-down hysteresis"
    # the victim stays killable later: nothing latched it out
    fl.replicas["r0"].sched.idle = True
    assert asc.status()["draining"] is None


def test_incident_mid_drain_aborts_foreign_victim():
    clk, fl, asc = _mk(n=3)
    lull = _rep(rho=0.05, rm=80.0, n=3)
    fl.replicas["r0"].sched.idle = False
    _tick(clk, asc, lull)
    _tick(clk, asc, lull)
    asc.on_incident("kill_replica", "r2")        # kill elsewhere
    assert ("end", "r0") in fl.drain_calls \
        and "r0" in fl.replicas and not fl.removed
    assert _by(asc, rule="incident", outcome=DRAIN_ABORTED)


# ------------------------------------------------------------ incident latch
def test_incident_latch_blocks_remove_never_add():
    clk, fl, asc = _mk(n=3, incident_cooldown_s=30.0)
    asc.on_incident("kill_replica", "r2")
    lull = _rep(rho=0.05, rm=80.0, n=3)
    for _ in range(4):
        _tick(clk, asc, lull)
    assert not fl.drain_calls and not fl.removed, \
        "failover must never be misread as a lull"
    assert _by(asc, rule="incident_latch", outcome=SUPPRESSED)
    # scale-UP stays allowed during the latch (capacity just dropped)
    _tick(clk, asc, _rep(rho=0.97, add=80.0, n=3))
    _tick(clk, asc, _rep(rho=0.97, add=80.0, n=3))
    assert len(fl.added) == 1
    # past the latch the armed scale-down proceeds
    clk.t += 30.0
    clk.t += _CFG["cooldown_up_s"]               # and past the up cooldown
    for _ in range(3):
        _tick(clk, asc, _rep(rho=0.05, rm=80.0, n=4))
    assert fl.drain_calls, "post-latch the remove signal must act"


# -------------------------------------------------------------- flap budget
def test_flap_budget_exhaustion_freezes_the_loop():
    clk, fl, asc = _mk(n=2, flap_budget=0, cooldown_up_s=0.0,
                       cooldown_down_s=0.0)
    hot = _rep(rho=0.97, add=80.0)
    _tick(clk, asc, hot)
    _tick(clk, asc, hot)
    assert len(fl.added) == 1                    # direction now "up"
    lull = _rep(rho=0.05, rm=80.0, n=3)
    _tick(clk, asc, lull)
    _tick(clk, asc, lull)                        # reversal, budget 0
    assert not fl.drain_calls, "reversal past the budget must not act"
    assert _by(asc, rule="flap_budget", outcome=SUPPRESSED)
    st = asc.status()
    assert st["frozen"] and st["frozen_by"] == "flap_budget"
    snap = fl.registry.snapshot()
    assert snap["gauges"]["Fleet/autoscale_frozen"] == 1.0
    assert snap["gauges"]["Fleet/autoscale_flap_budget_remaining"] == 0.0
    # frozen: even a clean signal is suppressed, evaluations continue
    _tick(clk, asc, hot)
    _tick(clk, asc, hot)
    assert len(fl.added) == 1
    assert _by(asc, rule="frozen", outcome=SUPPRESSED)
    # unfreezing is manual (the POST /autoscale path)
    asc.control({"freeze": False})
    assert not asc.status()["frozen"]


# ------------------------------------------------------------ control & pin
def test_control_freeze_pin_and_bad_bodies():
    clk, fl, asc = _mk(n=3)
    with pytest.raises(ValueError, match="unknown autoscale control"):
        asc.control({"bogus": 1})
    with pytest.raises(ValueError, match='"freeze" must be'):
        asc.control({"freeze": "yes"})
    with pytest.raises(ValueError, match='"pin" must be'):
        asc.control({"pin": "r0"})
    st = asc.control({"pin": ["r0", "r1", "r2"]})
    assert st["pinned"] == ["r0", "r1", "r2"]
    lull = _rep(rho=0.05, rm=80.0, n=3)
    for _ in range(3):
        _tick(clk, asc, lull)
    assert not fl.drain_calls
    assert _by(asc, rule="no_victim", outcome=SUPPRESSED), \
        "all victims pinned must be a visible no_victim suppression"
    asc.control({"unpin": ["r0"]})
    for _ in range(3):
        _tick(clk, asc, lull)
    assert ("begin", "r0") in fl.drain_calls, \
        "unpinned replica becomes the victim again"


def test_status_shape_and_audit_ring_bound():
    clk, fl, asc = _mk(audit_ring=4)
    for i in range(9):
        # alternate distinct alarm targets to defeat dedup
        asc.on_incident("probe", f"x{i}")
    assert len(asc.audit_entries()) == 4, "ring must stay bounded"
    st = asc.status()
    for key in ("enabled", "frozen", "pinned", "evaluations", "streaks",
                "cooldown_remaining_s", "flap_budget_remaining",
                "incident_latch_remaining_s", "draining", "decisions",
                "config"):
        assert key in st
    assert json.dumps(st)                        # JSON-clean for GET


# ----------------------------------------------------------- real fleet e2e
@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


_PROGRAMS: OrderedDict = OrderedDict()


def _fleet(eng, replicas=2, clock=None, autoscale=None, **extra):
    serving = {"slots": 2, "max_len": 48, "prefill_chunk": 16,
               "temperature": 0.8, "top_k": 20, **extra}
    if autoscale is not None:
        serving["autoscale"] = autoscale
    kw = {"clock": clock} if clock is not None else {}
    return FleetEngine(eng, serving, replicas=replicas,
                       programs=_PROGRAMS, **kw)


def _req(url, method="GET", data=None, token=None, timeout=5.0):
    headers = {}
    if data is not None:
        data = json.dumps(data).encode()
        headers["Content-Type"] = "application/json"
    if token is not None:
        headers["Authorization"] = f"Bearer {token}"
    r = urllib.request.Request(url, data=data, method=method,
                               headers=headers)
    try:
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return int(resp.status), resp.read().decode()
    except HTTPError as e:
        return int(e.code), e.read().decode()


def test_fleet_attach_inert_and_config_reject(setup):
    _, _, _, eng = setup
    fl = _fleet(eng, autoscale=None)
    try:
        assert fl.autoscaler is None, \
            "serving.autoscale unset must attach NOTHING"
    finally:
        fl.close()
    with pytest.raises(ValueError, match="unknown autoscale config"):
        _fleet(eng, autoscale={"bogus": 1}).close()
    fl = _fleet(eng, autoscale={"enabled": False, "tick_s": 1.0})
    try:
        assert fl.autoscaler is None, "enabled=False must attach nothing"
    finally:
        fl.close()


def test_autoscale_endpoint_get_post_token_gated(setup):
    _, _, _, eng = setup
    fl = _fleet(eng, autoscale={"tick_s": 1.0})
    try:
        port = fl.serve_telemetry(token="s3cret")
        u = f"http://127.0.0.1:{port}"
        code, body = _req(u + "/autoscale")
        assert code == 200
        st = json.loads(body)
        assert st["enabled"] is True and st["frozen"] is False
        code, body = _req(u + "/")
        assert json.loads(body)["endpoints"]["/autoscale"] is True
        # POST is token-gated like every other mutating endpoint
        code, _ = _req(u + "/autoscale", method="POST",
                       data={"freeze": True})
        assert code in (401, 403)
        code, body = _req(u + "/autoscale", method="POST",
                          data={"freeze": True, "pin": ["r0"]},
                          token="s3cret")
        assert code == 200
        st = json.loads(body)
        assert st["frozen"] is True and st["pinned"] == ["r0"]
        code, body = _req(u + "/autoscale", method="POST",
                          data={"bogus": 1}, token="s3cret")
        assert code == 400 and "unknown autoscale control" in body
        code, body = _req(u + "/autoscale")
        assert json.loads(body)["frozen"] is True
    finally:
        fl.close()
    off = _fleet(eng, autoscale=None)
    try:
        port = off.serve_telemetry()
        code, body = _req(f"http://127.0.0.1:{port}/autoscale")
        assert code == 404 and "no autoscaler" in body
    finally:
        off.close()


# ------------------------------------------------------- replay chaos edges
def test_replay_applies_role_add_and_replica_drain_edges(setup):
    """Satellite 1: autoscaler-recorded edges (role-carrying add,
    replica-scoped begin/end drain) co-replay deterministically."""
    _, _, _, eng = setup
    trace = TrafficTrace(meta={"source": "test"})
    trace.add_chaos("add_replica", 0.0, replica="joined")
    trace.add_chaos("begin_drain", 0.01, replica="r0")
    trace.add_chaos("end_drain", 0.02, replica="r0")
    fl = _fleet(eng, replicas=2, clock=ReplayClock(dt=1e-4))
    try:
        rep = ReplayDriver(fl, trace, clock=ReplayClock(dt=1e-4)).run()
        assert rep.chaos_applied == 3 and not rep.chaos_skipped
        assert "joined" in fl.replicas
        assert not fl.replicas["r0"].draining, "end_drain must reopen"
    finally:
        fl.close()


def test_replay_topology_mismatch_is_counted_skip(setup):
    _, _, _, eng = setup
    trace = TrafficTrace(meta={"source": "test"})
    trace.add_chaos("begin_drain", 0.0, replica="ghost")
    trace.add_chaos("end_drain", 0.01, replica="ghost")
    fl = _fleet(eng, replicas=2, clock=ReplayClock(dt=1e-4))
    try:
        rep = ReplayDriver(fl, trace, clock=ReplayClock(dt=1e-4)).run()
        assert rep.chaos_applied == 0 and len(rep.chaos_skipped) == 2
        assert all(s["replica"] == "ghost" for s in rep.chaos_skipped)
    finally:
        fl.close()
    # a solo (non-fleet) engine: replica-scoped drains counted-skip too
    srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                 "prefill_chunk": 16, "temperature": 0.8,
                                 "top_k": 20}, programs=_PROGRAMS)
    try:
        rep = ReplayDriver(srv, trace, clock=ReplayClock(dt=1e-4)).run()
        assert rep.chaos_applied == 0 and len(rep.chaos_skipped) == 2
    finally:
        srv.close()


# ------------------------------------------- the loop on a real fleet
def test_real_fleet_scales_up_warm_then_drains_down_clean(setup):
    """The control loop closed over a REAL fleet and its own
    ``scaling_report()`` (the stub tests above pin each guard): declared
    service rates on a fake clock, an overload until the add actuates,
    then a lull until the drained replica is removed. The joined replica
    compiles nothing; the add's decision record carries the report it
    fired on; the removal is a clean drain; nothing is lost, every output
    equals solo generate(), and no SLO burn gauge passes 1."""
    _, _, _, eng = setup
    clock = ReplayClock(dt=1e-4)
    asc = {"tick_s": 1.0, "up_ticks": 2, "down_ticks": 2,
           "add_score_min": 60.0, "remove_score_min": 60.0,
           "cooldown_up_s": 3.0, "cooldown_down_s": 3.0,
           "drain_deadline_s": 5.0, "min_replicas": 2, "max_replicas": 4}
    fl = _fleet(eng, clock=clock, autoscale=asc, slo={"ttft_p99_s": 30.0},
                loadscope={"window_s": 8.0})
    # one replica serves 20 decode tokens per fake second; requests want 6
    service = {"slots": 2, "decode_tokens_per_slot_s": 10.0,
               "decode_tokens_per_s": 20.0, "prefill_tokens_per_s": 400.0}
    rng = np.random.default_rng(2)
    subs, done = {}, {}

    def drive(rho, n_replicas, until, budget_s):
        """Offer ``rho`` of ``n_replicas``' declared capacity until
        ``until()`` holds; False if ``budget_s`` fake seconds pass first."""
        gap = 6 / (rho * n_replicas * 20.0)
        t_next, t_end = clock.t, clock.t + budget_s
        while clock.t < t_end:
            while t_next <= clock.t:
                prompt = rng.integers(0, 256, (9,)).astype(np.int32)
                seed = len(subs)
                subs[fl.submit(prompt, 6, seed=seed)] = (prompt, seed)
                t_next += gap
            done.update((r.rid, r) for r in fl.step())
            for e in fl.replicas.values():
                e.loadscope.service_override = service
            if until():
                return True
            clock.advance(0.02)
        return False

    try:
        assert drive(0.96, 2, lambda: len(fl.replicas) == 3, 25.0), \
            fl.autoscale_audit()[-3:]
        joined = next(n for n in fl.replicas if n not in ("r0", "r1"))
        assert fl.replicas[joined].compiles == 0
        add = [d for d in fl.autoscale_audit()
               if d["action"] == "add_replica"
               and d["outcome"] == ACTUATED][-1]["inputs"]
        assert add["fleet"]["replica_count"] == 2
        assert add["fleet"]["rho"] is not None
        assert add["what_if"]["action"] == "add_replica"
        assert add["what_if"]["score"] >= asc["add_score_min"]

        assert drive(0.10, 3, lambda: len(fl.replicas) == 2, 45.0), \
            fl.autoscale_audit()[-3:]
        outcomes = [d["outcome"] for d in fl.autoscale_audit()]
        assert DRAIN_STARTED in outcomes and REMOVED in outcomes
        assert REMOVED_AT_DEADLINE not in outcomes
        while set(subs) - set(done):
            done.update((r.rid, r) for r in fl.step())
            clock.advance(0.02)
        for rid, (prompt, seed) in subs.items():
            assert done[rid].ok, (rid, done[rid].status)
            want = np.asarray(eng.generate(
                jnp.asarray(prompt[None]), 6, temperature=0.8, top_k=20,
                request_seeds=[seed], cache_len=48))[0]
            got = np.asarray(done[rid].tokens, np.int32)
            np.testing.assert_array_equal(got, want[:len(got)])
        for e in fl.replicas.values():
            e.slo.score()
            snap = e.stats.registry.snapshot()
            assert not any(v > 1.0 for k, v in snap["gauges"].items()
                           if k.startswith("Serve/slo_")
                           and k.endswith("_burn"))
            assert int(snap["counters"].get("Serve/slo_violations", 0)) == 0
    finally:
        fl.close()


@pytest.mark.parametrize("frozen,stale_s,budget_left,rc", [
    (1, 12.0, 0, 1),        # flap budget exhausted
    (1, 4000.0, 2, 1),      # frozen by hand and forgotten
    (0, 0.0, 2, 0),         # a clean loop is no finding
])
def test_doctor_autoscale_section_gates(tmp_path, capsys, frozen, stale_s,
                                        budget_left, rc):
    from deepspeed_tpu.observability import doctor

    (tmp_path / "autoscale.prom").write_text(
        "dstpu_fleet_autoscale_evals 50\n"
        f"dstpu_fleet_autoscale_frozen {frozen}\n"
        f"dstpu_fleet_autoscale_frozen_stale_s {stale_s}\n"
        f"dstpu_fleet_autoscale_flap_budget_remaining {budget_left}\n")
    assert doctor.main(["--dir", str(tmp_path)]) == rc
    assert "[autoscale]" in capsys.readouterr().out

"""Traffic capture & deterministic replay (observability/replay.py).

Oracles:
- trace schema: round-trips through JSONL byte-stable, the validator
  catches every malformed shape, torn lines degrade (never raise);
- capture: engine hooks record admitted submits + terminal results
  (deduped), the ring bounds memory and counts drops, flight dumps
  carry the ring's tail as a standalone-replayable artifact;
- replay: fake-clock replay of a captured run is bit-identical to the
  recorded outputs; a replay under a different sampling config reports
  per-request divergence + a config-drift note instead of crashing; the
  recorded chaos script co-replays (kill applied at its position);
- request-log upgrade: v2 records (prompt/seed/session/deadline
  budgets) lift into a replayable trace; incomplete rows are skipped
  and counted;
- backtest: the advisor's prefix-sharing prediction on synthetic
  80%-overlap traffic scores within ±10 points of achieved savings;
  a captured multi-turn run backtests from the live capacity report,
  and the speculation lever scores where replies repeat and abstains,
  with its reason, where they are too short to;
- the doctor's [replay] section gates on a failed parity report and on
  an invalid trace.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.observability import doctor
from deepspeed_tpu.observability.export import request_record
from deepspeed_tpu.observability.replay import (ReplayClock, ReplayDriver,
                                                TrafficCapture,
                                                TrafficTrace,
                                                advisor_backtest,
                                                resolve_prompt,
                                                trace_from_request_log)

M = 48
EOS = 510


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


def _serving(extra=None):
    return {"slots": 2, "max_len": M, "prefill_chunk": 16,
            "temperature": 0.8, "top_k": 20, **(extra or {})}


def _reqs(n, seed=0, lengths=(5, 16, 20, 9)):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, (lengths[i % len(lengths)],))
             .astype(np.int32), 4, 700 + i) for i in range(n)]


# ------------------------------------------------------------ trace schema
def _synthetic_trace():
    tr = TrafficTrace(meta={"note": "synthetic"})
    tr.add_request(rid=0, t_rel=0.0, prompt=[1, 2, 3], max_new=4, seed=9,
                   session_id="s0", ttft_deadline_s=1.5)
    tr.add_request(rid=1, t_rel=0.5, gen={"seed": 3, "len": 8,
                                          "vocab": 32}, max_new=2, seed=10)
    tr.add_chaos("kill_replica", t_rel=0.7, replica="r1")
    tr.add_result(rid=0, t_rel=1.0, status="ok", tokens=[5, 6, 7, 8])
    tr.add_result(rid=1, t_rel=1.2, status="timeout", tokens=[3])
    return tr


def test_trace_roundtrip(tmp_path):
    tr = _synthetic_trace()
    assert tr.validate() == []
    p = tr.write(tmp_path / "t.jsonl")
    back = TrafficTrace.read(p)
    assert back.events == tr.events
    assert back.meta["schema"] == "dstpu.traffic_trace.v1"
    assert back.meta["note"] == "synthetic"
    assert back.torn_lines == 0
    # writing what was read is byte-stable (modulo the header carrying
    # the schema explicitly both times)
    assert back.as_lines() == tr.as_lines()


def test_trace_read_tolerates_torn_lines(tmp_path):
    p = _synthetic_trace().write(tmp_path / "t.jsonl")
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"kind": "result", "rid": 0, "t_re')   # torn mid-crash
    back = TrafficTrace.read(p)
    assert back.torn_lines == 1
    assert len(back.events) == 5


def test_trace_validator_negatives():
    tr = _synthetic_trace()
    tr.events[0]["max_new"] = 0
    tr.add_result(rid=99, t_rel=2.0)                   # unknown rid
    tr.events.append({"kind": "alien", "t_rel": 3.0})  # unknown kind
    tr.add_chaos("meteor", t_rel=4.0)                  # unknown chaos
    tr.events.append({"kind": "request", "t_rel": 0.1, "rid": 7,
                      "max_new": 1, "seed": 0})        # no prompt, no gen
    problems = tr.validate()
    for frag in ("max_new >= 1", "unknown rid 99", "unknown kind 'alien'",
                 "unknown chaos event 'meteor'",
                 "prompt ids or a gen{seed,len} spec",
                 "t_rel"):                             # out-of-order tail
        assert any(frag in p for p in problems), (frag, problems)
    dup = _synthetic_trace()
    dup.add_request(rid=0, t_rel=2.0, prompt=[1], max_new=1, seed=0)
    assert any("duplicate request rid 0" in p for p in dup.validate())
    alien_schema = TrafficTrace(meta={"schema": "dstpu.traffic_trace.v9"})
    assert any("unknown trace schema" in p
               for p in alien_schema.validate())


def test_gen_prompt_resolves_deterministically():
    e = {"gen": {"seed": 3, "len": 8, "vocab": 32}}
    a, b = resolve_prompt(e), resolve_prompt(e)
    assert np.array_equal(a, b) and a.dtype == np.int32 and len(a) == 8
    assert a.max() < 32
    with pytest.raises(ValueError):
        resolve_prompt({"rid": 1})


# ---------------------------------------------------------------- capture
class _Tick:
    def __init__(self, dt=0.001):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


class _Req:
    def __init__(self, rid, prompt, max_new=4, seed=0, status="ok",
                 tokens=()):
        import types

        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new = max_new
        self.seed = seed
        self.status = types.SimpleNamespace(value=status)
        self.tokens = list(tokens)
        self.attempts = 0
        self.session_id = None


def test_capture_dedupes_results_and_bounds_ring():
    cap = TrafficCapture(clock=_Tick(), ring=4)
    r = _Req(0, [1, 2], tokens=[5, 6])
    cap.on_submit(r, ttft_deadline_s=2.0)
    cap.on_result(r)
    cap.on_result(r)                       # fleet double-adoption path
    tr = cap.trace()
    assert len(tr.requests) == 1 and len(tr.results) == 1
    assert tr.requests[0]["ttft_deadline_s"] == 2.0
    assert tr.results[0]["tokens"] == [5, 6]
    for i in range(1, 8):                  # overflow the 4-event ring
        cap.on_submit(_Req(i, [1]))
    assert cap.dropped > 0
    tr2 = cap.trace()
    assert len(tr2.events) == 4
    assert tr2.meta["dropped_events"] == cap.dropped
    # the tail text is a standalone parseable trace (header + events)
    lines = cap.tail_text().strip().splitlines()
    assert json.loads(lines[0])["schema"] == "dstpu.traffic_trace.v1"
    assert len(lines) == 5


def test_overflowed_ring_tail_stays_valid():
    """Results whose request events were evicted from the ring must not
    poison the tail trace: validate() stays clean (the doctor gates on
    it) and the orphans count as dropped."""
    cap = TrafficCapture(clock=_Tick(), ring=5)
    reqs = [_Req(i, [1, 2], tokens=[4]) for i in range(4)]
    for r in reqs:
        cap.on_submit(r)
    for r in reqs:
        cap.on_result(r)     # ring tail: submit 3 + results 0..3
    tr = cap.trace()
    assert tr.validate() == []
    rids = {q["rid"] for q in tr.requests}
    assert rids == {3}
    assert all(e["rid"] in rids for e in tr.events
               if e["kind"] == "result")    # every kept result resolves
    assert tr.meta["dropped_events"] == 6   # 3 evicted + 3 orphans
    assert len(tr.events) == 2


def test_replay_reports_unhostable_request_as_failed_submit(setup):
    """A what-if replay under a SMALLER max_len cannot host a long
    recorded request — that is data (failed_submits), never a crash."""
    _, _, _, eng = setup
    tr = TrafficTrace(meta={"max_len": M})
    tr.add_request(rid=0, t_rel=0.0, prompt=list(range(1, 40)),
                   max_new=4, seed=1)
    tr.add_request(rid=1, t_rel=0.1, prompt=[1, 2, 3], max_new=4, seed=2)
    rc = ReplayClock(dt=1e-3)
    srv = ds.ServingEngine(eng, _serving({"max_len": 32}), clock=rc)
    rep = ReplayDriver(srv, tr, clock=rc).run()
    assert [f["rid"] for f in rep.failed_submits] == [0]
    assert rep.replayed == 1
    assert any("config_drift" in n for n in rep.notes)  # max_len drift

    # a recorded-OK request that never replayed must FAIL parity, not
    # silently drop out of the verdict (the gate would report PARITY
    # over requests that never ran)
    tr2 = TrafficTrace(meta={"max_len": M})
    tr2.add_request(rid=0, t_rel=0.0, prompt=list(range(1, 40)),
                    max_new=2, seed=1)
    tr2.add_request(rid=1, t_rel=0.1, prompt=[1, 2, 3], max_new=2, seed=2)
    tr2.add_result(rid=0, t_rel=1.0, status="ok", tokens=[9, 9])
    tr2.add_result(rid=1, t_rel=1.1, status="ok", tokens=[7, 7])
    rc2 = ReplayClock(dt=1e-3)
    srv2 = ds.ServingEngine(eng, _serving({"max_len": 32}), clock=rc2)
    rep2 = ReplayDriver(srv2, tr2, clock=rc2).run()
    assert rep2.parity is False
    assert any(d["rid"] == 0 and d["replayed_status"] == "not_replayed"
               for d in rep2.diverged)


def test_capture_ring_validates():
    with pytest.raises(ValueError):
        TrafficCapture(ring=0)
    from deepspeed_tpu.inference.config import ServingConfig

    with pytest.raises(ValueError):
        ServingConfig.from_any({"capture_ring": 0})


# ------------------------------------------------- engine capture + replay
def test_engine_capture_replay_parity_and_divergence(setup):
    _, _, _, eng = setup
    clock = ReplayClock(dt=1e-3)
    srv = ds.ServingEngine(eng, _serving({"capture": True}), clock=clock)
    reqs = _reqs(6, seed=1)
    srv.serve_batch([p for p, _, _ in reqs], [mn for _, mn, _ in reqs],
                    [sd for _, _, sd in reqs])
    trace = srv.capture.trace()
    assert trace.validate() == []
    assert len(trace.requests) == 6 and len(trace.results) == 6
    # deadline overrides recorded as passed (none here)
    assert all("ttft_deadline_s" not in e for e in trace.requests)
    srv.close()

    # bit-identical replay on the recorded config (fake clock)
    rc = ReplayClock(dt=1e-3)
    rep = ReplayDriver(ds.ServingEngine(eng, _serving(), clock=rc),
                       trace, clock=rc).run()
    assert rep.parity is True and rep.matched == 6
    assert rep.diverged == [] and rep.failed_submits == []

    # a different sampling config diverges PER REQUEST, with the drift
    # note explaining why — and run() returns instead of raising
    rc2 = ReplayClock(dt=1e-3)
    bad = ReplayDriver(
        ds.ServingEngine(eng, _serving({"greedy": True}), clock=rc2),
        trace, clock=rc2).run()
    assert bad.parity is False and len(bad.diverged) >= 1
    assert {"rid", "first_diff", "recorded_tokens", "replayed_tokens"} \
        <= set(bad.diverged[0])
    assert any("config_drift" in n for n in bad.notes)


def test_flight_dump_carries_traffic_trace(setup, tmp_path):
    _, _, _, eng = setup
    clock = ReplayClock(dt=1e-3)
    srv = ds.ServingEngine(
        eng, _serving({"capture": True, "spans": True,
                       "flight_dir": str(tmp_path)}), clock=clock)
    reqs = _reqs(2, seed=2)
    srv.serve_batch([p for p, _, _ in reqs], [mn for _, mn, _ in reqs],
                    [sd for _, _, sd in reqs])
    d = srv.dump_flight("manual")
    assert d is not None
    tr = TrafficTrace.read(d / "traffic_trace.jsonl")
    assert tr.validate() == []
    assert len(tr.requests) == 2 and len(tr.results) == 2
    # the artifact replays standing alone — the incident-runbook path
    rc = ReplayClock(dt=1e-3)
    rep = ReplayDriver(ds.ServingEngine(eng, _serving(), clock=rc), tr,
                       clock=rc).run()
    assert rep.parity is True and rep.matched == 2
    srv.close()


def test_fleet_capture_records_and_coreplays_kill(setup):
    from deepspeed_tpu.serving import FleetEngine

    _, _, _, eng = setup
    clock = ReplayClock(dt=1e-3)
    fleet = FleetEngine(eng, _serving({"capture": True}), replicas=2,
                        clock=clock)
    reqs = _reqs(5, seed=3)
    rids = [fleet.submit(p, mn, seed=sd, session_id="sess")
            for p, mn, sd in reqs]
    # run a bit, then kill r1 mid-traffic: the capture records the
    # chaos event at its position in the stream
    done = {}
    for _ in range(3):
        for req in fleet.step():
            done[req.rid] = req
    fleet.kill_replica("r1")
    it = 0
    while len(done) < len(rids):
        for req in fleet.step():
            done[req.rid] = req
        it += 1
        assert it < 100_000
    trace = fleet.capture.trace()
    assert trace.validate() == []
    assert [e["event"] for e in trace.chaos_events] == ["kill_replica"]
    assert trace.requests[0]["session_id"] == "sess"
    # replicas do NOT double-record: one request entry per submit
    assert len(trace.requests) == len(rids)
    assert all(e.capture is None for e in fleet.replicas.values())
    fleet.close()

    rc = ReplayClock(dt=1e-3)
    f2 = FleetEngine(eng, _serving(), replicas=2, clock=rc)
    rep = ReplayDriver(f2, trace, clock=rc).run()
    assert "r1" not in f2.replicas
    assert rep.chaos_applied == 1 and rep.chaos_skipped == []
    assert rep.parity is True and rep.matched == len(rids)
    f2.close()

    # the same trace against a SINGLE engine: the kill cannot co-replay
    # — counted as skipped, the run still completes with parity
    rc2 = ReplayClock(dt=1e-3)
    rep2 = ReplayDriver(ds.ServingEngine(eng, _serving(), clock=rc2),
                        trace, clock=rc2).run()
    assert rep2.chaos_applied == 0 and len(rep2.chaos_skipped) == 1
    assert rep2.parity is True

    # fleet replay under drifted sampling: the config_drift note must
    # come from the REPLICA config (the fleet holds no .cfg of its own)
    rc3 = ReplayClock(dt=1e-3)
    f3 = FleetEngine(eng, _serving({"greedy": True}), replicas=2,
                     clock=rc3)
    rep3 = ReplayDriver(f3, trace, clock=rc3).run()
    assert rep3.parity is False
    assert any("config_drift" in n for n in rep3.notes)
    f3.close()


def test_incident_dir_carries_fleet_traffic_trace(setup, tmp_path):
    from deepspeed_tpu.serving import FleetEngine

    _, _, _, eng = setup
    clock = ReplayClock(dt=1e-3)
    fleet = FleetEngine(
        eng, _serving({"capture": True, "spans": True,
                       "flight_dir": str(tmp_path)}),
        replicas=2, clock=clock)
    reqs = _reqs(2, seed=5)
    rids = [fleet.submit(p, mn, seed=sd) for p, mn, sd in reqs]
    done = set()
    it = 0
    while len(done) < len(rids):
        done |= {r.rid for r in fleet.step()}
        it += 1
        assert it < 100_000
    inc = fleet.dump_incident("drill")
    assert inc is not None
    tr = TrafficTrace.read(inc / "fleet" / "traffic_trace.jsonl")
    assert tr.validate() == []
    assert len(tr.requests) == 2 and len(tr.results) == 2
    fleet.close()


# ------------------------------------------------------ request-log upgrade
def test_request_record_v2_upgrades_to_trace(setup):
    _, _, _, eng = setup
    clock = ReplayClock(dt=1e-3)
    srv = ds.ServingEngine(eng, _serving(), clock=clock)
    reqs = _reqs(3, seed=4)
    rids = [srv.submit(p, mn, seed=sd, total_deadline_s=60.0)
            for p, mn, sd in reqs]
    done = {}
    it = 0
    while len(done) < len(rids):
        for req in srv.step():
            done[req.rid] = req
        it += 1
        assert it < 100_000
    rows = [request_record(done[r]) for r in rids]
    rec = rows[0]
    assert rec["schema"] == "dstpu.request_record.v3"
    assert rec["tenant_id"] == "default"    # never set → the inert value
    assert isinstance(rec["prompt"], list) and rec["seed"] >= 700
    assert rec["total_deadline_s"] == pytest.approx(60.0)
    assert rec["ttft_deadline_s"] is None
    # v3 rows + true v2 rows (no tenant_id) + one v1-ish row lacking
    # replay fields → the upgrade defaults the v2 tenants (counted in
    # meta) and skips only the v1 row — never a crash
    v2 = {k: v for k, v in rows[1].items() if k != "tenant_id"}
    v2["rid"] = 12345                       # distinct request, v2 shape
    legacy = {"rid": 99, "status": "ok", "tokens": 4}
    tr, skipped = trace_from_request_log(rows + [v2, legacy])
    assert skipped == 1
    assert len(tr.requests) == len(rows) + 1
    assert tr.meta["tenantless_rows"] == 1
    assert tr.validate() == []
    assert tr.requests[0]["total_deadline_s"] == pytest.approx(60.0)
    # default tenants are not materialized in the trace (byte-stable
    # with pre-tenant captures); replay bills them to "default"
    assert all("tenant_id" not in e for e in tr.requests)
    # no recorded outputs in a request log → the oracle degrades to None
    rc = ReplayClock(dt=1e-3)
    rep = ReplayDriver(ds.ServingEngine(eng, _serving(), clock=rc), tr,
                       clock=rc).run()
    assert rep.parity is None and rep.replayed == len(tr.requests)
    srv.close()


# ------------------------------------------------------- tenant co-fidelity
def test_capture_carries_tenants_and_replay_is_bit_identical(setup):
    """Captured traces carry tenant ids VERBATIM, a tenant-labeled
    replay is bit-identical to the recorded outputs, and the replayed
    engine re-attributes the same tenants — while tenant-free captures
    stay byte-identical to the pre-tenant layout (no tenant_id keys)."""
    _, _, _, eng = setup
    clock = ReplayClock(dt=1e-3)
    srv = ds.ServingEngine(eng, _serving({"capture": True}), clock=clock)
    reqs = _reqs(4, seed=6)
    tenants = ["acme", "umbrella", "acme", None]
    outs = srv.serve_batch([p for p, _, _ in reqs],
                           [mn for _, mn, _ in reqs],
                           [sd for _, _, sd in reqs],
                           tenant_ids=tenants)
    trace = srv.capture.trace()
    assert trace.validate() == []
    assert [e.get("tenant_id") for e in trace.requests] \
        == ["acme", "umbrella", "acme", None]   # default = unrecorded
    srv.close()

    rc = ReplayClock(dt=1e-3)
    target = ds.ServingEngine(
        eng, _serving({"tenantscope": True}), clock=rc)
    rep = ReplayDriver(target, trace, clock=rc).run()
    assert rep.parity is True and rep.matched == 4
    snap = target.tenants_snapshot()
    assert set(snap["tenants"]) == {"acme", "umbrella", "default"}
    assert snap["tenants"]["acme"]["retired_ok"] == 2
    assert sum(r["completed_tokens"] for r in snap["tenants"].values()) \
        == sum(len(t) for t in outs)
    target.close()

    # a tenant-free capture emits NO tenant_id keys at all: old traces
    # (and their byte layout) are unchanged by the v3 dimension
    clock2 = ReplayClock(dt=1e-3)
    srv2 = ds.ServingEngine(eng, _serving({"capture": True}),
                            clock=clock2)
    reqs2 = _reqs(2, seed=7)
    srv2.serve_batch([p for p, _, _ in reqs2],
                     [mn for _, mn, _ in reqs2],
                     [sd for _, _, sd in reqs2])
    assert all("tenant_id" not in e
               for e in srv2.capture.trace().events)
    srv2.close()


# ----------------------------------------------------------------- backtest
def test_advisor_backtest_scores_synthetic_overlap(setup):
    _, _, _, eng = setup
    rng = np.random.default_rng(7)
    sys_p = rng.integers(0, 256, (16,)).astype(np.int32)
    tr = TrafficTrace()
    for i in range(8):
        tail = rng.integers(0, 256, (4,)).astype(np.int32)
        tr.add_request(rid=i, t_rel=0.01 * i,
                       prompt=np.concatenate([sys_p, tail]),
                       max_new=3, seed=800 + i)
    # 16 shared of 20 tokens, first prompt cold: predicted overlap
    # (7 * 16) / (8 * 20) = 0.7 exactly on block-aligned prompts
    bt = advisor_backtest(tr, eng,
                          {"slots": 2, "max_len": M, "prefill_chunk": 16,
                           "greedy": True}, page_size=8)
    ps = bt["levers"]["prefix_sharing"]
    assert ps["source"] == "workload_estimator"
    assert ps["predicted"] == pytest.approx(0.7)
    assert ps["abs_error_pts"] <= 10.0
    assert bt["baseline"]["prefill_tokens_saved"] == 0
    assert ps["what_if"]["prefill_tokens_saved"] > 0
    kv = bt["levers"]["kv_quantization"]
    assert kv["predicted"] is not None and kv["predicted"] <= 0.5
    assert kv["achieved"] == pytest.approx(kv["predicted"], rel=0.01)
    assert bt["trace"]["requests"] == 8


def test_backtest_of_captured_sessions_scores_or_abstains(setup):
    """The advisor held to a captured run, with the live capacity report
    as the source of its predictions. On multi-turn session traffic the
    prefix-sharing lever lands within 10 points of the tokens the radix
    tree saved in replay and int8 KV at least halves the ledger's bytes
    per token. That trace's replies are 3 tokens long: no n-gram repeats
    inside them, the spec-on replay proposes no draft, and the
    speculation lever says so (``abstained``) instead of scoring nothing
    against nothing. On replies long enough to repeat, the n-gram
    estimate lands within 10 points of the live first-draft accept rate.
    Every what-if replay reproduces the recorded tokens."""
    _, _, _, eng = setup
    base = {"slots": 2, "max_len": M, "prefill_chunk": 16, "greedy": True}

    def capturing():
        return ds.ServingEngine(eng, {**base, "capture": True,
                                      "page_size": 8,
                                      "workload": {"block": 8}},
                                clock=ReplayClock(dt=1e-3))

    def captured(srv):
        out = srv.capture.trace(), srv.capacity_report(census=False)
        srv.close()
        return out

    srv = capturing()
    rng = np.random.default_rng(3)
    history = [rng.integers(0, 256, (16,)).astype(np.int32)] * 3
    for _ in range(3):
        prompts = [np.concatenate([h, rng.integers(0, 256, (5,))
                                   .astype(np.int32)]) for h in history]
        replies = srv.serve_batch(prompts, 3, [0, 1, 2])
        history = [np.concatenate([p, r]) for p, r in zip(prompts, replies)]
    trace, report = captured(srv)
    assert trace.validate() == [] and len(trace.results) == 9
    bt = advisor_backtest(trace, eng, base, capacity_report=report,
                          levers=("prefix_sharing", "kv_quantization",
                                  "speculative_decoding"), page_size=8)
    ps = bt["levers"]["prefix_sharing"]
    assert ps["source"] == "capacity_report" and ps["abs_error_pts"] <= 10
    assert bt["levers"]["kv_quantization"]["achieved"] <= 0.5
    sd = bt["levers"]["speculative_decoding"]
    assert sd["predicted"] is None and sd["achieved"] is None
    assert "never predicts" in sd["abstained"] and "abs_error_pts" not in sd
    assert sd["what_if"]["speculation"]["proposed_tokens"] == 0
    assert all(lv["parity"] is True for lv in bt["levers"].values())

    srv = capturing()
    srv.serve_batch([np.full((12,), t, np.int32) for t in range(1, 7)], 30,
                    list(range(6)))
    trace, _ = captured(srv)
    sd = advisor_backtest(trace, eng, base,
                          levers=("speculative_decoding",),
                          page_size=8)["levers"]["speculative_decoding"]
    assert sd["source"] == "ngram_estimator" and sd["parity"] is True
    assert sd["what_if"]["speculation"]["proposed_tokens"] > 0
    assert "abstained" not in sd and sd["abs_error_pts"] <= 10


# ------------------------------------------------------------------ doctor
def test_doctor_replay_section(tmp_path, capsys):
    d = tmp_path / "monitor"
    d.mkdir()
    # clean dir: notes only, no findings from the section
    assert doctor.main(["--dir", str(d)]) == 0
    # a valid trace + a parity-true report: still clean
    _synthetic_trace().write(d / "traffic_trace.jsonl")
    (d / "REPLAY_REPORT.json").write_text(json.dumps(
        {"parity": True, "requests": 2, "matched": 2, "diverged": [],
         "chaos_applied": 1}))
    assert doctor.main(["--dir", str(d)]) == 0
    out = capsys.readouterr().out
    assert "[replay]" in out and "PARITY" in out
    # parity FAILED gates; --no-gate restores report-only
    (d / "REPLAY_REPORT.json").write_text(json.dumps(
        {"parity": False, "requests": 2, "matched": 1,
         "diverged": [{"rid": 1, "first_diff": 0}], "chaos_applied": 0}))
    assert doctor.main(["--dir", str(d)]) == 1
    assert doctor.main(["--dir", str(d), "--no-gate"]) == 0
    capsys.readouterr()
    # an INVALID trace gates too
    (d / "REPLAY_REPORT.json").unlink()
    (d / "traffic_trace.jsonl").write_text(
        '{"kind": "header", "schema": "dstpu.traffic_trace.v1"}\n'
        '{"kind": "request", "t_rel": 0.0, "rid": 0, "max_new": 1, '
        '"seed": 0}\n')                       # no prompt and no gen
    assert doctor.main(["--dir", str(d)]) == 1

"""Arrival & scaling observatory (observability/loadscope.py) +
satellites.

Oracles:
- estimator math: goodput_frac piecewise-exact, Allen-Cunneen queue
  wait monotone in rho and null at saturation, time-to-violation from
  the linear rate trend (exact on hand inputs, 0 at saturation, null
  when the SLO is unarmed / the trend is flat / the crossing is beyond
  the horizon);
- arrival analytics on a fake clock: uniform traffic reads CV ~ 0,
  on/off bursts read CV > 1, an accelerating rate reads a positive
  trend; utilization rho is exact against hand-fed service rates;
- submit-path satellites: Serve/interarrival_s histogram counts and
  Serve/queue_depth sampled at submit, pinned on the injectable clock;
- degradation matrix: every unmeasured input (no arrivals, no spans,
  no SLO) degrades the dependent fields to None with a stated reason
  and an empty what-if list — never a raise, and the capacity lever
  self-demotes to score 0;
- what-if scoring: add_replica urgency monotone in rho, remove_replica
  only offered at n >= 2, never when removal would cross rho_high;
- inertness: serving.loadscope=None builds no observatory; enabling it
  compiles ZERO extra programs on identical traffic;
- GET /scaling: 200 + schema body when the observatory is on, clean
  404 when off, advertised on the endpoint index either way;
- fleet scrape rollups: dstpu_fleet_offered_load (sum),
  dstpu_fleet_utilization_max (max), dstpu_fleet_slo_ttv_min_s (min)
  across engines, absent when no engine reports them;
- FleetEngine.scaling_report(): per-replica rows + fleet aggregate
  degrade cleanly with spans off;
- replay trace generator: deterministic under a seed, rate-shaped,
  validated inputs;
- doctor [load]: sustained-overload gate trip / clean / --no-gate;
- a live engine's measured rho in the capacity lever, and
  ``scaling_backtest``: predicted goodput and queue wait within the band
  of fake-clock replays at two fleet sizes.
"""

import json
import urllib.request
from urllib.error import HTTPError

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.observability.loadscope import (LoadScope,
                                                   LoadScopeConfig,
                                                   goodput_frac,
                                                   predicted_queue_wait_s,
                                                   score_what_ifs,
                                                   time_to_violation_s)
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.replay import make_diurnal_trace
from deepspeed_tpu.observability.expfmt import (exposition_from_events,
                                                parse_prometheus_textfile)
from deepspeed_tpu.observability.fleet_scrape import FleetScraper
from deepspeed_tpu.serving import FleetEngine
from _fake_clock import TickClock

EOS = 7


class _SLO:
    """Minimal armed-SLO stand-in (only the p99 targets are read)."""

    ttft_p99_s = 0.5
    tpot_p99_s = 0.0


class _Clk:
    """Pin-able clock: returns .t verbatim (no auto-tick), so arrival
    timestamps in these tests are EXACT hand values."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


def _serving(eng, clock=None, **extra):
    cfg = {"slots": 2, "max_len": 48, "prefill_chunk": 16,
           "temperature": 0.8, "top_k": 20, **extra}
    kw = {"clock": clock} if clock is not None else {}
    return ds.ServingEngine(eng, cfg, **kw)


def _run_all(srv, n=3, max_new=6):
    rng = np.random.default_rng(0)
    for i in range(n):
        srv.submit(rng.integers(0, 256, (9,)).astype(np.int32), max_new,
                   seed=50 + i)
    it = 0
    while not srv.sched.idle or srv._prefill is not None:
        srv.step()
        it += 1
        assert it < 10_000


def _req(url, timeout=5.0):
    try:
        with urllib.request.urlopen(
                urllib.request.Request(url), timeout=timeout) as resp:
            return int(resp.status), resp.read().decode()
    except HTTPError as e:
        return int(e.code), e.read().decode()


# ---------------------------------------------------------- estimator math
def test_goodput_frac_piecewise_exact():
    assert goodput_frac(None) is None
    assert goodput_frac(0.5) == 1.0
    assert goodput_frac(1.0) == 1.0
    assert goodput_frac(2.0) == pytest.approx(0.5)


def test_queue_wait_monotone_and_null_at_saturation():
    waits = [predicted_queue_wait_s(r, 2, 1.0) for r in (0.3, 0.6, 0.9)]
    assert all(w is not None for w in waits)
    assert waits[0] < waits[1] < waits[2]
    assert predicted_queue_wait_s(0.0, 2, 1.0) == 0.0
    # saturated: steady-state wait unbounded -> None, never a number
    assert predicted_queue_wait_s(1.0, 2, 1.0) is None
    assert predicted_queue_wait_s(1.2, 2, 1.0) is None
    # unmeasured inputs -> None
    assert predicted_queue_wait_s(None, 2, 1.0) is None
    assert predicted_queue_wait_s(0.5, None, 1.0) is None
    assert predicted_queue_wait_s(0.5, 2, None) is None
    # burstier arrivals (Ca^2 scaling) wait strictly longer
    assert predicted_queue_wait_s(0.6, 2, 1.0, arrival_cv=2.0) \
        > predicted_queue_wait_s(0.6, 2, 1.0, arrival_cv=0.1)


def test_time_to_violation_hand_computed():
    # violating rate = rate/rho; ttv = (rate/rho - rate) / trend
    ttv = time_to_violation_s(rate_per_s=10.0, trend_per_s2=1.0,
                              rho=0.8, slo=_SLO())
    assert ttv == pytest.approx((10.0 / 0.8 - 10.0) / 1.0)  # 2.5
    # already saturated: violating NOW
    assert time_to_violation_s(rate_per_s=10.0, trend_per_s2=1.0,
                               rho=1.3, slo=_SLO()) == 0.0
    # no SLO armed / flat trend / beyond horizon / unmeasured -> None
    assert time_to_violation_s(rate_per_s=10.0, trend_per_s2=1.0,
                               rho=0.8, slo=None) is None
    assert time_to_violation_s(rate_per_s=10.0, trend_per_s2=0.0,
                               rho=0.8, slo=_SLO()) is None
    assert time_to_violation_s(rate_per_s=10.0, trend_per_s2=1e-6,
                               rho=0.8, slo=_SLO(),
                               horizon_s=60.0) is None
    assert time_to_violation_s(rate_per_s=None, trend_per_s2=1.0,
                               rho=0.8, slo=_SLO()) is None


def test_what_if_scores_monotone_and_guarded():
    def add_score(rho, n=1):
        wis = score_what_ifs(rho=rho, replicas=n, slots=2,
                             mean_service_s=1.0)
        return [w for w in wis if w["action"] == "add_replica"][0]["score"]

    scores = [add_score(r) for r in (0.5, 0.9, 0.97, 1.3)]
    assert scores == sorted(scores)
    assert scores[0] == 0.0 and scores[-1] == 100.0
    # rho unmeasured -> no guesses, empty list
    assert score_what_ifs(rho=None) == []
    # remove_replica only exists at n >= 2, and scores 0 whenever the
    # post-removal rho would cross rho_high
    solo = score_what_ifs(rho=0.2, replicas=1, slots=2,
                          mean_service_s=1.0)
    assert [w["action"] for w in solo] == ["add_replica"]
    duo = score_what_ifs(rho=0.2, replicas=2, slots=2,
                         mean_service_s=1.0)
    rm = [w for w in duo if w["action"] == "remove_replica"][0]
    assert rm["rho_after"] == pytest.approx(0.4) and rm["score"] > 0.0
    hot = score_what_ifs(rho=0.6, replicas=2, slots=2,
                         mean_service_s=1.0)
    rm_hot = [w for w in hot if w["action"] == "remove_replica"][0]
    assert rm_hot["score"] == 0.0  # 1.2 after removal: never suggested


def test_config_validation():
    with pytest.raises(ValueError, match="unknown loadscope"):
        LoadScopeConfig.from_any({"windw_s": 5.0})
    with pytest.raises(ValueError, match="window_s"):
        LoadScopeConfig.from_any({"window_s": 0.0})
    with pytest.raises(ValueError, match="rho_high"):
        LoadScopeConfig.from_any({"rho_high": 1.5})
    c = LoadScopeConfig.from_any({"window_s": 5.0, "rho_high": 0.7})
    assert c.window_s == 5.0 and c.rho_high == 0.7
    assert LoadScopeConfig.from_any(None) is None


# ----------------------------------------------------- arrival analytics
def test_arrival_cv_uniform_vs_bursty():
    clk = _Clk()
    ls = LoadScope({"window_s": 3600.0}, clock=clk)
    for i in range(10):
        clk.t = float(i)
        ls.on_submit(4, 8)
    arr = ls.arrival()
    assert arr["rate_per_s"] == pytest.approx(1.0)
    assert arr["interarrival_cv"] == pytest.approx(0.0, abs=1e-9)

    clk2 = _Clk()
    bursty = LoadScope({"window_s": 3600.0}, clock=clk2)
    t = 0.0
    for i in range(16):
        t += 0.1 if i % 8 else 7.3  # on/off bursts
        clk2.t = t
        bursty.on_submit(4, 8)
    assert bursty.arrival()["interarrival_cv"] > 1.0


def test_utilization_exact_and_ttv_on_fake_clock():
    clk = _Clk()
    ls = LoadScope({"window_s": 3600.0}, clock=clk)
    # accelerating arrivals: rate 0.5/s then 2/s -> positive trend
    for t in (0.0, 2.0, 4.0, 6.0, 7.0, 7.5, 8.0, 8.5, 9.0):
        clk.t = t
        ls.on_submit(4, 8)
    arr = ls.arrival()
    assert arr["rate_per_s"] == pytest.approx(8.0 / 9.0)
    # decode demand: 8 budgets over the 9 s span (last event open)
    assert arr["decode_tokens_per_s"] == pytest.approx(8 * 8 / 9.0)
    assert arr["trend_per_s2"] is not None and arr["trend_per_s2"] > 0
    service = {"slots": 2, "decode_tokens_per_slot_s": 8.0,
               "prefill_tokens_per_s": 64.0}
    rep = ls.report(service=service, slo=_SLO(), queue_depth=0)
    util = rep["utilization"]
    assert util["rho_decode"] == pytest.approx((8 * 8 / 9.0) / 16.0)
    assert util["rho"] == util["rho_decode"]  # prefill side cooler
    assert util["saturated"] is False
    assert util["predicted_queue_wait_s"] is not None
    assert rep["forecast"]["slo_armed"] is True
    ttv = rep["forecast"]["slo_ttv_s"]
    assert ttv is not None and 0.0 < ttv < 3600.0
    # gauges published for the scrape chain
    g = ls.registry.snapshot()["gauges"]
    assert g["Serve/utilization"] == pytest.approx(util["rho"])
    assert g["Serve/slo_ttv_s"] == pytest.approx(ttv)


def test_submit_satellites_pinned_on_fake_clock(setup):
    _, _, _, eng = setup
    clock = TickClock(dt=0.001)
    srv = _serving(eng, clock=clock, loadscope={"window_s": 3600.0})
    try:
        rng = np.random.default_rng(3)
        for i in range(4):
            srv.submit(rng.integers(0, 256, (7,)).astype(np.int32), 4,
                       seed=i)
        snap = srv.stats.snapshot()
        # interarrival histogram: n submits -> exactly n-1 gaps, every
        # one positive on the ticking clock
        hist = snap["interarrival_s"]
        assert hist["count"] == 3 and hist["mean"] > 0.0
        # queue depth sampled at SUBMIT time: 4 queued, none admitted
        assert snap["queue_depth"] == srv.sched.queue_depth == 4
        arr = srv.loadscope.arrival()
        assert arr["requests_in_window"] == 4
        assert arr["rate_per_s"] is not None
        while not srv.sched.idle or srv._prefill is not None:
            srv.step()
    finally:
        srv.close()


# ------------------------------------------------------------- degradation
def test_report_degrades_unmeasured_never_raises():
    ls = LoadScope()
    rep = ls.report(service=None, slo=None, queue_depth=None)
    util = rep["utilization"]
    assert util["rho"] is None and util["predicted_queue_wait_s"] is None
    assert rep["forecast"]["slo_ttv_s"] is None
    assert rep["what_ifs"] == []
    reasons = " ".join(rep["unmeasured"])
    assert len(rep["unmeasured"]) >= 3
    for frag in ("arrival rate", "decode service rate", "prefill rate",
                 "SLO"):
        assert frag in reasons
    # arrivals without spans: demand measured, capacity not -> still None
    clk = _Clk()
    ls2 = LoadScope(clock=clk)
    for t in (0.0, 1.0, 2.0):
        clk.t = t
        ls2.on_submit(4, 8)
    rep2 = ls2.report(service={"slots": 2}, slo=None)
    assert rep2["arrival"]["rate_per_s"] is not None
    assert rep2["utilization"]["rho"] is None
    assert rep2["what_ifs"] == []


def test_capacity_scaling_lever_self_demotes(setup):
    from deepspeed_tpu.observability.capacity import (LEVER_SCALING,
                                                      capacity_report)
    _, _, _, eng = setup
    srv = _serving(eng)
    try:
        rep = capacity_report(ledger=srv.hbm_ledger(),
                              loadscope=LoadScope().report())
    finally:
        srv.close()
    lever = [lv for lv in rep["advisor"]["levers"]
             if lv["name"] == LEVER_SCALING][0]
    assert lever["score"] == 0.0
    assert "unmeasured" in lever["why"]
    assert rep["loadscope"]["utilization"]["rho"] is None


# --------------------------------------------------------------- inertness
def test_inert_off_and_zero_extra_compiles(setup):
    _, _, _, eng = setup
    srv_off = _serving(eng)
    try:
        assert srv_off.loadscope is None
        assert "loadscope" not in srv_off.metrics_snapshot()
        assert srv_off.scaling_snapshot() is None
        _run_all(srv_off, n=3)
        warm = srv_off.compiles
    finally:
        srv_off.close()
    srv_on = _serving(eng, loadscope={})
    try:
        assert srv_on.loadscope is not None
        _run_all(srv_on, n=3)
        assert srv_on.compiles == warm, \
            "loadscope on must compile ZERO extra programs"
        snap = srv_on.metrics_snapshot()["loadscope"]
        assert snap["schema"] == "dstpu.loadscope.v1"
        assert snap["requests"] == 3
    finally:
        srv_on.close()


# --------------------------------------------------------- /scaling endpoint
def test_scaling_endpoint_on_and_off(setup):
    _, _, _, eng = setup
    srv = _serving(eng, loadscope={},
                   telemetry={"enabled": True, "port": 0})
    try:
        u = f"http://127.0.0.1:{srv.telemetry.port}"
        _run_all(srv, n=3)
        code, body = _req(u + "/scaling")
        assert code == 200
        obj = json.loads(body)
        assert obj["schema"] == "dstpu.loadscope.v1"
        assert obj["requests"] == 3
        assert "utilization" in obj and "what_ifs" in obj
        code, body = _req(u + "/")
        assert json.loads(body)["endpoints"]["/scaling"] is True
    finally:
        srv.close()
    off = _serving(eng, telemetry={"enabled": True, "port": 0})
    try:
        u = f"http://127.0.0.1:{off.telemetry.port}"
        code, body = _req(u + "/scaling")
        assert code == 404 and "loadscope disabled" in body
        # the index lists only live endpoints: off -> absent, not False
        code, body = _req(u + "/")
        assert "/scaling" not in json.loads(body)["endpoints"]
    finally:
        off.close()


# ------------------------------------------------------- fleet scrape rollups
def _scaling_metrics(offered, util, ttv=None):
    reg = MetricsRegistry()
    reg.gauge("Serve/goodput_frac").set(1.0)
    reg.gauge("Serve/goodput_wall_s").set(10.0)
    reg.gauge("Serve/offered_tokens_per_s").set(offered)
    reg.gauge("Serve/utilization").set(util)
    if ttv is not None:
        reg.gauge("Serve/slo_ttv_s").set(ttv)
    return exposition_from_events(reg.to_events(1))


def test_fleet_scrape_scaling_rollups():
    pages = {
        "http://a:1/metrics": _scaling_metrics(120.0, 0.4, ttv=900.0),
        "http://a:1/healthz": '{"ready": true}',
        "http://b:2/metrics": _scaling_metrics(80.0, 0.9, ttv=30.0),
        "http://b:2/healthz": '{"ready": true}',
    }

    def fetch(url, timeout):
        return pages[url]

    fs = FleetScraper(["http://a:1", "http://b:2"], labels=["a", "b"],
                      fetch=fetch, clock=TickClock())
    snap = fs.scrape()
    fl = snap["fleet"]
    assert fl["offered_load"] == pytest.approx(200.0)     # sum
    assert fl["utilization_max"] == pytest.approx(0.9)    # max
    assert fl["slo_ttv_min_s"] == pytest.approx(30.0)     # min
    vals = parse_prometheus_textfile(fs.render(snap))
    assert vals["dstpu_fleet_offered_load"] == pytest.approx(200.0)
    assert vals["dstpu_fleet_utilization_max"] == pytest.approx(0.9)
    assert vals["dstpu_fleet_slo_ttv_min_s"] == pytest.approx(30.0)
    # engines without the observatory: rollups absent, not zero
    plain = {
        "http://c:3/metrics": exposition_from_events(
            MetricsRegistry().to_events(1)),
        "http://c:3/healthz": '{"ready": true}',
    }
    fs2 = FleetScraper(["http://c:3"], labels=["c"],
                       fetch=lambda url, timeout: plain[url],
                       clock=TickClock())
    snap2 = fs2.scrape()
    assert snap2["fleet"]["offered_load"] is None
    assert "dstpu_fleet_offered_load" not in fs2.render(snap2)


# ------------------------------------------------------ fleet scaling report
def test_fleet_scaling_report_degrades_without_spans(setup):
    _, _, _, eng = setup
    serving = {"slots": 2, "max_len": 48, "prefill_chunk": 16,
               "temperature": 0.8, "top_k": 20,
               "loadscope": {"window_s": 3600.0}}
    fl = FleetEngine(eng, serving, replicas=2, clock=TickClock())
    try:
        rng = np.random.default_rng(5)
        rids = [fl.submit(rng.integers(0, 256, (7,)).astype(np.int32), 4,
                          seed=i) for i in range(4)]
        done = 0
        it = 0
        while done < len(rids):
            done += len(fl.step())
            it += 1
            assert it < 50_000
        rep = fl.scaling_report()
        assert rep["schema"] == "dstpu.loadscope.v1"
        assert set(rep["replicas"]) == {"r0", "r1"}
        fleet = rep["fleet"]
        assert fleet["arrival_rate_per_s"] is not None
        # spans off: capacity unmeasured fleet-wide -> rho None, what-ifs
        # empty, and every replica row states its reasons
        assert fleet["rho"] is None and rep["what_ifs"] == []
        for row in rep["replicas"].values():
            assert row["unmeasured"]
    finally:
        fl.close()


# ----------------------------------------------------------- replay trace
def test_make_diurnal_trace_deterministic_and_validated():
    kw = dict(duration_s=20.0, base_rate=2.0, peak_rate=6.0,
              period_s=20.0, burst_factor=2.0, burst_period_s=5.0,
              prompt_len=4, max_new=6, seed=3)
    a, b = make_diurnal_trace(**kw), make_diurnal_trace(**kw)
    ra, rb = a.events, b.events
    assert [r["t_rel"] for r in ra] == [r["t_rel"] for r in rb]
    assert len(ra) > 10
    ts = [r["t_rel"] for r in ra]
    assert ts == sorted(ts) and 0.0 <= ts[0] and ts[-1] <= 20.0
    assert all(r["max_new"] == 6 and r["gen"]["len"] == 4 for r in ra)
    assert a.meta["source"] == "make_diurnal_trace"
    with pytest.raises(ValueError):
        make_diurnal_trace(duration_s=0.0, base_rate=2.0)
    with pytest.raises(ValueError):
        make_diurnal_trace(duration_s=10.0, base_rate=-1.0)


# ----------------------------------------------------------------- doctor
def _load_prom(rate=50.0, trend=0.5, qd=12.0, util=0.97, ttv=120.0):
    return (f"dstpu_serve_arrival_rate_per_s {rate}\n"
            f"dstpu_serve_arrival_trend_per_s2 {trend}\n"
            f"dstpu_serve_queue_depth {qd}\n"
            f"dstpu_serve_utilization {util}\n"
            f"dstpu_serve_slo_ttv_s {ttv}\n")


def test_doctor_load_gate_trips_on_sustained_overload(tmp_path, capsys):
    from deepspeed_tpu.observability import doctor
    (tmp_path / "load.prom").write_text(_load_prom())
    rc = doctor.main(["--dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[load]" in out and "SUSTAINED OVERLOAD" in out
    assert doctor.main(["--dir", str(tmp_path), "--no-gate"]) == 0
    capsys.readouterr()


def test_doctor_load_gate_clean_paths(tmp_path, capsys):
    from deepspeed_tpu.observability import doctor
    # healthy utilization: no finding
    (tmp_path / "load.prom").write_text(_load_prom(rate=5.0, util=0.4))
    assert doctor.main(["--dir", str(tmp_path)]) == 0
    # hot but NO pressure and no finite TTV: watch, don't page
    (tmp_path / "load.prom").write_text(
        "dstpu_serve_utilization 0.95\n"
        "dstpu_serve_queue_depth 0\n")
    assert doctor.main(["--dir", str(tmp_path)]) == 0
    # threshold is an operator knob
    (tmp_path / "load.prom").write_text(_load_prom(util=0.92))
    assert doctor.main(["--dir", str(tmp_path),
                        "--load-rho-max", "0.95"]) == 0
    capsys.readouterr()


# ---------------------------------------- measured, then backtested
def test_measured_rho_feeds_the_lever_and_backtest_holds_at_two_sizes(setup):
    """The path the estimator tests above feed by hand, on a live engine:
    with spans on, served traffic yields a measured rho, per-slot decode
    rate and an add_replica what-if, and the capacity report's ``scaling``
    lever carries that same rho. Then the advisor is held to its own
    predictions: ``scaling_backtest`` replays a diurnal, bursty trace on a
    fake clock at fleet sizes 1 and 2, and predicted goodput and queue
    wait stay within its tolerance of what the replays achieved."""
    from deepspeed_tpu.observability.capacity import (LEVER_SCALING,
                                                      validate_capacity_report)
    from deepspeed_tpu.observability.replay import scaling_backtest

    _, _, _, eng = setup
    srv = _serving(eng, loadscope={"window_s": 3600.0}, spans=True)
    try:
        _run_all(srv, n=6, max_new=8)
        snap = srv.scaling_snapshot()
        assert snap["utilization"]["rho"] is not None, snap["unmeasured"]
        assert snap["service"]["decode_tokens_per_slot_s"] is not None
        assert any(w["action"] == "add_replica" for w in snap["what_ifs"])
        rep = srv.capacity_report(census=False)
        assert validate_capacity_report(rep) == []
        lever = [lv for lv in rep["advisor"]["levers"]
                 if lv["name"] == LEVER_SCALING][0]
        assert lever["estimate"]["rho"] == snap["utilization"]["rho"]
    finally:
        srv.close()

    bt = scaling_backtest(eng, {"slots": 2, "max_len": 32,
                                "prefill_chunk": 8, "greedy": True},
                          sizes=(1, 2), requests_target=40, prompt_len=6,
                          max_new=8, seed=5)
    assert [s["replicas"] for s in bt["sizes"]] == [1, 2]
    for s in bt["sizes"]:
        assert s["goodput_error_pts"] <= bt["tolerance_pts"], s
        assert s["wait_error_pts"] <= bt["tolerance_pts"], s
    assert bt["pass"] is True
    assert bt["runs"]["1"]["rho"] > bt["runs"]["2"]["rho"]

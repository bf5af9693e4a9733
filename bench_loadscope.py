"""Arrival & scaling observatory bench: the replay-backtested advisor.

Exercises the loadscope observatory (``observability/loadscope.py``)
end to end against ground truth it cannot fake:

- **estimator math** — goodput/queue-wait/TTV closed forms on
  hand-checkable inputs, burstiness (interarrival CV) separating a
  uniform stream from a bursty one on a fake clock, and add-replica
  urgency monotone in measured utilization;
- **degradation** — every unmeasured input (no traffic, spans off, no
  SLO) turns into ``None`` fields / a score-0 ``scaling`` lever with a
  stated reason, never an exception;
- **inertness** — loadscope on compiles ZERO extra programs (same
  compile count as the off engine on identical traffic; the
  ``bench_serving.py --smoke`` compile-freeze oracle);
- **backtest** — :func:`~deepspeed_tpu.observability.replay.scaling_backtest`
  replays a synthetic diurnal × bursty trace on the fake clock at two
  fleet sizes and gates the advisor's predicted queue-wait/goodput
  deltas against achieved within ±10 points;
- **doctor** — the ``[load]`` section gates on sustained overload and
  stays clean under normal load.

``--smoke`` is the CPU tier-1 gate (wired via
``tests/unit/test_loadscope.py``); the full mode runs a larger backtest,
writes ``LOADSCOPE_BENCH.json`` (queue_wait/ttv/utilization rows for the
cross-PR perf ledger — all down-is-good), and regenerates
``CAPACITY_REPORT.json`` with the ``scaling`` lever carrying the
backtest's ``achieved`` block.
"""

import contextlib
import io
import json
import os
import sys

import numpy as np

from bench_serving import build

_PROMPT, _MAX_NEW = 6, 8


def _mk_engine(loadscope=True, spans=True, slo=None, seed=0):
    extra = {"greedy": True, "spans": spans}
    if loadscope:
        extra["loadscope"] = {"window_s": 3600.0}
    if slo:
        extra["slo"] = slo
    _model, _params, eng, srv = build(
        slots=2, max_len=32, chunk=8, n_layer=2, d_model=64, n_head=4,
        **extra)
    return eng, srv


def _run_one(srv, prompt, seed):
    rid = srv.submit(prompt, _MAX_NEW, seed=seed)
    it = 0
    while srv.pop_result(rid) is None:
        srv.step()
        it += 1
        if it > 200_000:
            raise RuntimeError("serving stuck")


def _traffic(srv, n=8, seed=7):
    rng = np.random.default_rng(seed)
    for i in range(n):
        _run_one(srv, rng.integers(0, 256, (_PROMPT,)).astype(np.int32),
                 seed=100 + i)


def _doctor_exit(prom_text, tmp) -> int:
    from deepspeed_tpu.observability import doctor

    os.makedirs(tmp, exist_ok=True)
    with open(os.path.join(tmp, "load.prom"), "w") as f:
        f.write(prom_text)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = doctor.main(["--dir", tmp])
    return rc


_BACKTEST_SERVING = {"slots": 2, "max_len": 32, "prefill_chunk": 8,
                     "greedy": True}


# ------------------------------------------------------------------ smoke
def smoke():
    from deepspeed_tpu.observability.capacity import (
        capacity_report, validate_capacity_report)
    from deepspeed_tpu.observability.loadscope import (
        LoadScope, goodput_frac, predicted_queue_wait_s, score_what_ifs,
        time_to_violation_s)
    from deepspeed_tpu.observability.replay import scaling_backtest

    # (1) estimator math: goodput saturates at 1/rho, queue wait is
    # monotone in rho and None at saturation, TTV needs an armed SLO
    assert goodput_frac(0.5) == 1.0 and goodput_frac(2.0) == 0.5
    w_lo = predicted_queue_wait_s(0.5, 2, 1.0)
    w_hi = predicted_queue_wait_s(0.9, 2, 1.0)
    assert 0 < w_lo < w_hi, (w_lo, w_hi)
    assert predicted_queue_wait_s(1.2, 2, 1.0) is None
    assert time_to_violation_s(rate_per_s=10.0, trend_per_s2=1.0,
                               rho=0.8, slo=None) is None

    class _SLO:
        ttft_p99_s, tpot_p99_s = 0.5, 0.0

    ttv = time_to_violation_s(rate_per_s=10.0, trend_per_s2=1.0,
                              rho=0.8, slo=_SLO)
    assert ttv is not None and abs(ttv - 2.5) < 1e-9, ttv
    assert time_to_violation_s(rate_per_s=10.0, trend_per_s2=1.0,
                               rho=1.3, slo=_SLO) == 0.0

    # (1b) add-replica urgency is monotone in measured rho
    scores = [score_what_ifs(rho=r, replicas=1, slots=2,
                             mean_service_s=1.0)[0]["score"]
              for r in (0.5, 0.9, 0.97, 1.3)]
    assert scores == sorted(scores) and scores[0] == 0.0 \
        and scores[-1] == 100.0, scores

    # (2) burstiness: a bursty stream's interarrival CV beats uniform
    class _Clk:
        t = 0.0

        def __call__(self):
            return self.t

    clk = _Clk()
    uni = LoadScope({"window_s": 1e9}, clock=clk)
    for _ in range(32):
        clk.t += 1.0
        uni.on_submit(_PROMPT, _MAX_NEW)
    clk2 = _Clk()
    bur = LoadScope({"window_s": 1e9}, clock=clk2)
    for i in range(32):
        clk2.t += 0.1 if i % 8 else 7.3     # tight bursts, long gaps
        bur.on_submit(_PROMPT, _MAX_NEW)
    cv_u = uni.arrival()["interarrival_cv"]
    cv_b = bur.arrival()["interarrival_cv"]
    assert cv_u is not None and cv_u < 0.1, cv_u
    assert cv_b is not None and cv_b > 1.0 and cv_b > cv_u, (cv_u, cv_b)

    # (3) degradation: nothing measured -> None fields + stated reasons,
    # and the capacity lever self-demotes to score 0 (never raises)
    empty = LoadScope({"window_s": 60.0}).report()
    assert empty["utilization"]["rho"] is None
    assert empty["what_ifs"] == []
    assert len(empty["unmeasured"]) >= 3, empty["unmeasured"]
    _eng0, srv0 = _mk_engine(loadscope=False, spans=False)
    _traffic(srv0, n=2)
    rep0 = capacity_report(ledger=srv0.hbm_ledger(), loadscope=empty)
    sc0 = {l["name"]: l for l in rep0["advisor"]["levers"]}["scaling"]
    assert sc0["score"] == 0.0 and "unmeasured" in sc0["why"], sc0
    warm = srv0.compiles

    # (4) inertness: loadscope on compiles ZERO extra programs, and the
    # off engine holds no observatory at all
    assert srv0.loadscope is None
    _eng1, srv1 = _mk_engine(loadscope=True, spans=False)
    _traffic(srv1, n=2)
    assert srv1.compiles == warm, \
        f"loadscope on compiled {srv1.compiles} programs vs {warm} off"

    # (5) measured path: spans on -> rho/what-ifs measured, the scaling
    # lever rides the report with a measured estimate
    _eng2, srv2 = _mk_engine(loadscope=True, spans=True)
    _traffic(srv2, n=6)
    snap = srv2.scaling_snapshot()
    assert snap["utilization"]["rho"] is not None, snap["unmeasured"]
    assert snap["service"]["decode_tokens_per_slot_s"] is not None
    assert any(w["action"] == "add_replica" for w in snap["what_ifs"])
    rep2 = srv2.capacity_report(census=False)
    assert validate_capacity_report(rep2) == [], \
        validate_capacity_report(rep2)
    sc2 = {l["name"]: l for l in rep2["advisor"]["levers"]}["scaling"]
    assert sc2["estimate"]["rho"] == snap["utilization"]["rho"]

    # (6) the replay backtest: predicted vs achieved within the band at
    # BOTH fleet sizes on the self-calibrated diurnal+bursty trace
    bt = scaling_backtest(_eng2, _BACKTEST_SERVING, sizes=(1, 2),
                          requests_target=40, prompt_len=_PROMPT,
                          max_new=_MAX_NEW, seed=5)
    assert bt["pass"] is True, json.dumps(bt["sizes"], indent=2)
    assert len(bt["sizes"]) == 2
    for s in bt["sizes"]:
        assert s["goodput_error_pts"] <= bt["tolerance_pts"], s
        assert s["wait_error_pts"] <= bt["tolerance_pts"], s
    assert bt["runs"]["1"]["rho"] > bt["runs"]["2"]["rho"], bt["runs"]

    # (7) doctor [load] gate: sustained overload trips, normal load is
    # clean (--no-gate preserved by doctor.main's shared flag)
    import tempfile

    overload = ("dstpu_serve_arrival_rate_per_s 50\n"
                "dstpu_serve_arrival_trend_per_s2 0.5\n"
                "dstpu_serve_queue_depth 12\n"
                "dstpu_serve_utilization 0.97\n"
                "dstpu_serve_slo_ttv_s 120\n")
    with tempfile.TemporaryDirectory() as td:
        rc_trip = _doctor_exit(overload, td)
    with tempfile.TemporaryDirectory() as td:
        rc_clean = _doctor_exit(
            "dstpu_serve_arrival_rate_per_s 5\n"
            "dstpu_serve_utilization 0.4\n", td)
    assert rc_trip == 1, f"doctor [load] gate did not trip ({rc_trip})"
    assert rc_clean == 0, f"doctor [load] gate false-fired ({rc_clean})"

    print(json.dumps({
        "smoke": True,
        "cv_uniform": round(cv_u, 3), "cv_bursty": round(cv_b, 3),
        "rho_measured": round(snap["utilization"]["rho"], 4),
        "backtest_pass": bt["pass"],
        "backtest_errors_pts": [
            [round(s["goodput_error_pts"], 2),
             round(s["wait_error_pts"], 2)] for s in bt["sizes"]],
        "compiled_programs": warm,
        "verdict": "smoke-pass",
    }))


# ------------------------------------------------------------------- full
def bench():
    from deepspeed_tpu.observability.replay import scaling_backtest

    res = {}
    eng, srv = _mk_engine(loadscope=True, spans=True,
                          slo={"ttft_p99_s": 2.0})
    # the larger backtest: same gate, more traffic, both fleet sizes
    bt = scaling_backtest(eng, _BACKTEST_SERVING, sizes=(1, 2),
                          requests_target=96, prompt_len=_PROMPT,
                          max_new=_MAX_NEW, seed=11)
    res["scaling_backtest"] = {
        "pass": bt["pass"],
        "trace_requests": bt["trace"]["requests"],
        "serviceable_tokens_per_s": bt["serviceable_tokens_per_s"],
        "sizes": [{
            "replicas": s["replicas"],
            "goodput_error_pts": s["goodput_error_pts"],
            "wait_error_pts": s["wait_error_pts"],
        } for s in bt["sizes"]],
    }
    # live-engine observatory rows (the perf-ledger series: queue_wait /
    # ttv / utilization are all down-is-good)
    _traffic(srv, n=12)
    snap = srv.scaling_snapshot()
    res["observatory"] = {
        "utilization_rho": snap["utilization"]["rho"],
        "queue_wait_pred_s": snap["utilization"]["predicted_queue_wait_s"],
        "slo_ttv_s": snap["forecast"]["slo_ttv_s"],
        "arrival_rate_per_s": snap["arrival"]["rate_per_s"],
        "interarrival_cv": snap["arrival"]["interarrival_cv"],
    }
    # overload picture from the backtest runs, ledger-named
    r1, r2 = bt["runs"]["1"], bt["runs"]["2"]
    res["overloaded_1_replica"] = {
        "utilization_rho": r1["rho"],
        "queue_wait_mean_s": r1["queue_wait_mean_s"],
        "goodput_pts": r1["goodput_pts"],
    }
    res["scaled_2_replicas"] = {
        "utilization_rho": r2["rho"],
        "queue_wait_mean_s": r2["queue_wait_mean_s"],
        "goodput_pts": r2["goodput_pts"],
    }
    # regenerate CAPACITY_REPORT.json with the scaling lever carrying
    # the backtest's achieved block (prediction validated, not asserted)
    s0 = bt["sizes"][0]
    srv.loadscope.achieved = {
        "source": "scaling_backtest", "replicas": s0["replicas"],
        "predicted_after": s0["predicted_after"],
        "measured_after": s0["measured_after"],
        "goodput_error_pts": s0["goodput_error_pts"],
        "wait_error_pts": s0["wait_error_pts"],
        "tolerance_pts": bt["tolerance_pts"], "pass": s0["pass"],
    }
    out_dir = os.path.dirname(os.path.abspath(__file__))
    rep = srv.capacity_report(
        path=os.path.join(out_dir, "CAPACITY_REPORT.json"))
    sc = {l["name"]: l for l in rep["advisor"]["levers"]}["scaling"]
    res["advisor"] = {
        "scaling_score": sc["score"],
        "ranked": rep["advisor"]["ranked"],
        "achieved": sc["estimate"].get("achieved"),
    }
    return res


def main():
    res = bench()
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "LOADSCOPE_BENCH.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()

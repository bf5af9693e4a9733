"""KV residency & eviction-regret bench: the measured host-tier case.

Drives session traffic through a paged engine with a DELIBERATELY small
page pool so tree eviction fires, and reads the kvscope observatory
(``observability/kvscope.py``) against hand-computed ground truth:

- **forced-eviction regret exactness** — page-aligned prompts cycled
  through a pool that holds exactly one request's tree residue, so every
  resubmission re-pays its whole prefill; the ghost ledger's regret
  tokens must equal the hand-computed re-paid prefill EXACTLY;
- **advisor** — the capacity report's ``tiered_kv`` lever is scored from
  measured regret + the measured host↔device copy-bandwidth probe + the
  span ring's measured prefill throughput, ranks FIRST when regret
  dominates, and degrades to score 0 with a stated reason on no-regret
  traffic or when any input is unmeasured (never raises);
- **inertness** — kvscope on compiles ZERO extra programs (same compile
  count as the kvscope-off engine on identical traffic) and the warm
  engine's compile count freezes;
- **doctor** — the ``[kv]`` section gates on runaway regret and stays
  clean below the threshold.

``--smoke`` is the CPU tier-1 gate (wired via
``tests/unit/test_kvscope.py``); the full mode additionally runs the
multi-turn session workload and writes ``KV_RESIDENCY_BENCH.json``
(regret/session/advisor rows + per-turn resume TTFT) for the cross-PR
perf ledger (regret directions: down is good).
"""

import contextlib
import io
import json
import os
import sys
import time

import numpy as np

from bench_serving import build, make_multiturn_plan, run_multiturn, \
    ttft_by_turn

# forced-eviction geometry: 32-token page-aligned prompts over 8-token
# pages; pool_pages=6 -> 5 usable = exactly one request's worst case
# (ceil((32 + 8 - 1) / 8) = 5), so admitting the OTHER prompt must evict
# every tree-held page of the previous one.
_PS, _P, _MAX_NEW = 8, 32, 8
_POOL = 1 + (_P + _MAX_NEW - 1 + _PS - 1) // _PS


def _mk_engine(kvscope=True, pool_pages=_POOL, spans=True, seed=0):
    extra = {"page_size": _PS, "pool_pages": pool_pages, "spans": spans,
             "greedy": True}
    if kvscope:
        extra["kvscope"] = {"dead_after_s": 3600.0}
    _model, _params, eng, srv = build(
        slots=2, max_len=64, chunk=16, n_layer=2, d_model=64, n_head=4,
        **extra)
    return eng, srv


def _run_one(srv, prompt, seed, sid):
    rid = srv.submit(prompt, _MAX_NEW, seed=seed, session_id=sid)
    it = 0
    while srv.pop_result(rid) is None:
        srv.step()
        it += 1
        if it > 200_000:
            raise RuntimeError("serving stuck")


def _prompts(n=2, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (_P,)).astype(np.int32) for _ in range(n)]


def forced_eviction(srv, rounds=2):
    """A/B prompt cycling on the tiny pool: every admission after the
    first pair evicts the other prompt's tree pages, so each of the
    2*(rounds-1) resubmissions re-pays its full prefill. Hand-computed
    regret: the live tree would have skipped P-1 tokens (the final
    token always recomputes), so each resubmission's regret is P-1."""
    A, B = _prompts()
    for r in range(rounds):
        _run_one(srv, A, 1000 + r, "sess-a")
        _run_one(srv, B, 2000 + r, "sess-b")
    return 2 * (rounds - 1) * (_P - 1)


def _doctor_exit(prom_text, tmp) -> int:
    from deepspeed_tpu.observability import doctor

    os.makedirs(tmp, exist_ok=True)
    with open(os.path.join(tmp, "kv.prom"), "w") as f:
        f.write(prom_text)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = doctor.main(["--dir", tmp])
    return rc


# ------------------------------------------------------------------ smoke
def smoke():
    from deepspeed_tpu.observability.capacity import (
        capacity_report, validate_capacity_report)

    # (1) regret exactness on forced-eviction traffic
    _eng, srv = _mk_engine()
    expected = forced_eviction(srv, rounds=2)
    snap = srv.kvscope.snapshot()
    got = snap["regret"]["regret_tokens"]
    assert got == expected, \
        f"regret {got} != hand-computed re-paid prefill {expected}"
    ps = srv.pool.snapshot()
    assert ps["eviction_events"] == 3 and ps["pages_evicted"] == 12, ps
    assert snap["sessions"]["resumed"] == 2 \
        and snap["sessions"]["regret_resumes"] == 2, snap["sessions"]
    assert snap["ghosts"]["entries"] <= snap["ghosts"]["capacity"]

    # (2) advisor: tiered_kv ranks first on regret-dominated traffic,
    # scored from measured regret + copy bandwidth + prefill timings
    rep = srv.capacity_report(census=False)
    assert validate_capacity_report(rep) == [], \
        validate_capacity_report(rep)
    tk = {l["name"]: l for l in rep["advisor"]["levers"]}["tiered_kv"]
    assert tk["score"] > 0, tk
    assert rep["advisor"]["ranked"][0] == "tiered_kv", \
        rep["advisor"]["ranked"]
    assert tk["estimate"]["copy_h2d_gbps"] is not None
    assert tk["estimate"]["measured_recompute_s_per_resume"] is not None
    assert "kv_idle_resident_bytes" in rep["ledger"]

    # (2b) no-regret traffic demotes the lever to 0 with a stated reason
    _eng2, srv2 = _mk_engine(pool_pages=0)      # auto pool: no pressure
    forced_eviction(srv2, rounds=2)
    snap2 = srv2.kvscope.snapshot()
    assert snap2["regret"]["regret_tokens"] == 0, snap2["regret"]
    assert srv2.pool.snapshot()["eviction_events"] == 0
    rep2 = srv2.capacity_report(census=False)
    tk2 = {l["name"]: l for l in rep2["advisor"]["levers"]}["tiered_kv"]
    assert tk2["score"] == 0.0 and "no eviction regret" in tk2["why"], tk2

    # (2c) unmeasured inputs degrade to 0 with the reason, never raise
    ks = dict(srv.kv_residency())
    ks["copy_bandwidth"] = {"h2d_gbps": None, "d2h_gbps": None}
    rep3 = capacity_report(ledger=rep["ledger"], kvscope=ks)
    tk3 = {l["name"]: l for l in rep3["advisor"]["levers"]}["tiered_kv"]
    assert tk3["score"] == 0.0 and "copy bandwidth" in tk3["why"], tk3
    ks = dict(srv.kv_residency())
    ks["prefill"] = None
    tk4 = {l["name"]: l for l in capacity_report(
        ledger=rep["ledger"], kvscope=ks)["advisor"]["levers"]
    }["tiered_kv"]
    assert tk4["score"] == 0.0 and "prefill timings" in tk4["why"], tk4

    # (3) inertness: kvscope adds ZERO programs (same compile count as
    # the off engine on identical traffic) and the warm count freezes
    warm = srv.compiles
    forced_eviction(srv, rounds=2)
    assert srv.compiles == warm, \
        f"{srv.compiles - warm} new compiles after warmup with kvscope on"
    _eng3, srv3 = _mk_engine(kvscope=False, spans=False)
    forced_eviction(srv3, rounds=2)
    assert srv3.compiles == warm, \
        f"kvscope on compiled {warm} programs vs {srv3.compiles} off"

    # (4) doctor [kv] gate: runaway regret trips, quiet regret is clean
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        rc_trip = _doctor_exit(
            "dstpu_serve_eviction_regret_frac 0.9\n"
            "dstpu_serve_eviction_regret_tokens 900\n", td)
    with tempfile.TemporaryDirectory() as td:
        rc_clean = _doctor_exit(
            "dstpu_serve_eviction_regret_frac 0.05\n"
            "dstpu_serve_eviction_regret_tokens 5\n", td)
    assert rc_trip == 1, f"doctor [kv] gate did not trip ({rc_trip})"
    assert rc_clean == 0, f"doctor [kv] gate false-fired ({rc_clean})"

    print(json.dumps({
        "smoke": True,
        "regret_tokens": got, "hand_expected": expected,
        "eviction_events": ps["eviction_events"],
        "tiered_kv_score": round(tk["score"], 4),
        "tiered_kv_ranked_first": True,
        "no_regret_score": tk2["score"],
        "compiled_programs": warm,
        "verdict": "smoke-pass",
    }))


# ------------------------------------------------------------------- full
def bench():
    res = {}
    # forced-eviction row (same oracle as the smoke, reported)
    _eng, srv = _mk_engine()
    expected = forced_eviction(srv, rounds=3)
    snap = srv.kvscope.snapshot()
    pool = srv.pool.snapshot()
    rep = srv.capacity_report(census=False)
    tk = {l["name"]: l for l in rep["advisor"]["levers"]}["tiered_kv"]
    res["forced_eviction"] = {
        "regret_tokens": snap["regret"]["regret_tokens"],
        "hand_expected": expected,
        "regret_frac": round(snap["regret"]["regret_frac"], 4),
        "eviction_events": pool["eviction_events"],
        "pages_evicted": pool["pages_evicted"],
        "ghost_entries": snap["ghosts"]["entries"],
        "time_to_regret_s": srv.stats.registry.snapshot()["histograms"]
        .get("Serve/kv_time_to_regret_s", {}),
    }
    res["advisor"] = {
        "tiered_kv_score": tk["score"],
        "ranked": rep["advisor"]["ranked"],
        "projected_restore_s": tk["estimate"]
        ["projected_restore_s_per_resume"],
        "measured_recompute_s": tk["estimate"]
        ["measured_recompute_s_per_resume"],
        "copy_h2d_gbps": tk["estimate"]["copy_h2d_gbps"],
        "prefill_tokens_per_s": tk["estimate"]["prefill_tokens_per_s"],
        "idle_kv_bytes": rep["ledger"]["kv_idle_resident_bytes"],
    }
    # multi-turn session workload on a pressured pool: the realistic
    # regret/session picture + the per-turn resume-TTFT ledger series
    plan = make_multiturn_plan(sessions=6, turns=4, seed=3,
                               sys_tokens=32, user=(6, 12), max_new=(4, 8))
    mt_cfg = {"slots": 4, "max_len": 128, "prefill_chunk": 16,
              "greedy": True, "page_size": 16, "pool_pages": 24,
              "spans": True, "kvscope": {"dead_after_s": 3600.0}}
    _m, _p, eng2, srv2 = build(slots=4, max_len=128, chunk=16, n_layer=2,
                               d_model=64, n_head=4, greedy=True,
                               page_size=16, pool_pages=24, spans=True,
                               kvscope={"dead_after_s": 3600.0})
    run_multiturn(srv2, plan)                   # warmup (compiles)
    import deepspeed_tpu as ds

    # measure on a FRESH serving state (cold pool/tree/ghosts) over the
    # warm program LRU — the bench_serving multiturn discipline
    srv2 = ds.ServingEngine(eng2, mt_cfg)
    ttfts = {}
    t0 = time.perf_counter()
    run_multiturn(srv2, plan, ttfts=ttfts)
    wall = time.perf_counter() - t0
    s2 = srv2.kvscope.snapshot()
    res["multiturn"] = {
        "wall_s": round(wall, 3),
        "regret_tokens": s2["regret"]["regret_tokens"],
        "regret_frac": round(s2["regret"]["regret_frac"], 4),
        "sessions_resumed": s2["sessions"]["resumed"],
        "regret_resumes": s2["sessions"]["regret_resumes"],
        "idle_kv_byte_s": s2["sessions"]["idle_kv_byte_s"],
        "eviction_events": srv2.pool.snapshot()["eviction_events"],
        "resume_ttft": ttft_by_turn(ttfts, plan["turns"]),
    }
    return res


def main():
    res = bench()
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "KV_RESIDENCY_BENCH.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()

"""KV residency observatory (observability/kvscope.py) + satellites.

Oracles:
- ghost-tree regret ledger: forced-eviction traffic on a deliberately
  small pool yields regret tokens EXACTLY equal to the hand-computed
  re-paid prefill; uniform no-eviction traffic reports zero; the ghost
  list stays bounded under churn; regret attributes to the eviction
  event that caused it;
- session lifecycle: fake-clock idle/resume histograms, the HBM
  byte-seconds-held-while-idle integral, dead-session scoring, and
  per-session residency tracks in the Perfetto export;
- workload split: per-session resume overlap vs cross-request overlap
  (Serve/workload_resume_overlap beside the existing estimate);
- pages satellites: eviction EVENTS vs pages freed disaggregated,
  eviction-pressure fields (evictable pages, oldest tree-entry age) in
  snapshot()/health();
- advisor: tiered_kv scored from measured regret + measured copy
  bandwidth + measured prefill timings; ANY unmeasured input degrades
  to score 0 with a stated reason, never a raise;
- fleet: a regretted resume on the session's sticky replica counts
  Fleet/affinity_regret;
- doctor [kv]: runaway-regret gate trip/clean;
- a live paged engine: exact regret on forced-eviction traffic, the
  measured tiered_kv ranking, and no program added by the observatory.
"""

import numpy as np
import pytest

from _fake_clock import TickClock

from deepspeed_tpu.observability.kvscope import (KVScope, KVScopeConfig,
                                                 measure_copy_bandwidth)
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.workload import (WorkloadAnalyzer,
                                                  token_hash)
from deepspeed_tpu.serving.pages import PagePool


class _Req:
    """Minimal request stand-in for the host-only kvscope hooks."""

    def __init__(self, rid, prompt, session_id=None, page_alloc=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.session_id = session_id
        self.page_alloc = page_alloc


def _pool_with_scope(pages=6, page_size=8, max_len=64, clock=None,
                     cfg=None):
    clock = clock if clock is not None else TickClock()
    reg = MetricsRegistry()
    pool = PagePool(pages, page_size, max_len, registry=reg, clock=clock)
    scope = KVScope(cfg, registry=reg, clock=clock, page_size=page_size,
                    per_token_bytes=64)
    pool.on_evict = scope.on_evictions
    return pool, scope, clock, reg


def _drive(pool, scope, prompt, rid, sid=None, max_new=8):
    """One request's pool lifecycle: admit (+probe), register, release."""
    alloc = pool.try_admit(prompt, max_new, rid)
    assert alloc is not None
    req = _Req(rid, prompt, session_id=sid, page_alloc=alloc)
    out = scope.on_admit(req)
    pool.on_inserted(rid, prompt)
    pool.release(rid)
    scope.on_retire(req)
    return out


# --------------------------------------------------------- ghost ledger
def test_forced_eviction_regret_exact():
    """A/B cycling on a pool that holds exactly one request's residue:
    every resubmission re-pays its whole prefill; regret == P-1 each
    (the final token recomputes even on a live tree)."""
    pool, scope, _clk, _reg = _pool_with_scope()
    rng = np.random.default_rng(0)
    A = rng.integers(0, 256, (32,)).astype(np.int32)
    B = rng.integers(0, 256, (32,)).astype(np.int32)
    assert _drive(pool, scope, A, 1, "a")["regret_tokens"] == 0
    assert _drive(pool, scope, B, 2, "b")["regret_tokens"] == 0
    out = _drive(pool, scope, A, 3, "a")         # B's admit evicted A
    assert out["regret_tokens"] == 31 and out["resumed"]
    assert _drive(pool, scope, B, 4, "b")["regret_tokens"] == 31
    snap = scope.snapshot()
    assert snap["regret"]["regret_tokens"] == 62
    assert snap["regret"]["regret_admissions"] == 2
    # attribution: each regretted admission charged ONE eviction event
    tops = [e["regret_tokens"] for e in snap["events"]["top"]]
    assert sorted(tops, reverse=True)[:2] == [31, 31]
    # pages satellite: events vs pages freed disaggregated
    ps = pool.snapshot()
    assert ps["eviction_events"] == 3 and ps["pages_evicted"] == 12
    assert ps["evictions"] == 12          # historical meaning kept


def test_no_eviction_traffic_zero_regret():
    pool, scope, _clk, _reg = _pool_with_scope(pages=32)
    rng = np.random.default_rng(1)
    for rid in range(6):
        p = rng.integers(0, 256, (16,)).astype(np.int32)
        assert _drive(pool, scope, p, rid)["regret_tokens"] == 0
    snap = scope.snapshot()
    assert snap["regret"]["regret_tokens"] == 0
    assert pool.snapshot()["eviction_events"] == 0


def test_partial_eviction_and_stale_ghosts():
    """A ghost whose block the tree holds again (re-registered by a
    later request) is stale: dropped, no regret."""
    clock = TickClock()
    reg = MetricsRegistry()
    scope = KVScope(registry=reg, clock=clock, page_size=8)
    toks = tuple(range(8))
    scope.on_evictions([{"tokens": toks, "block": 8}])
    # the tree re-holds the block (shared=1): stale, no regret
    prompt = np.arange(16, dtype=np.int32)

    class _A:
        shared, skip = 1, 8

    out = scope.on_admit(_Req(1, prompt, page_alloc=_A()))
    assert out["regret_tokens"] == 0
    assert scope.stale_ghost_hits == 1 and not scope.ghosts


def test_ghost_ring_bounded_under_churn():
    scope = KVScope({"ghost_entries": 8}, clock=TickClock(), page_size=4)
    for i in range(50):
        scope.on_evictions([{"tokens": (i, i + 1, i + 2, i + 3),
                             "block": 4}])
    assert len(scope.ghosts) == 8
    assert scope.ghost_overflow == 42
    assert scope.snapshot()["ghosts"]["entries"] == 8


def test_regret_capped_at_repaid_prefill():
    """Ghost coverage can never claim more than the admission actually
    recomputes (P - 1 - skip)."""
    scope = KVScope(clock=TickClock(), page_size=8)
    prompt = np.arange(16, dtype=np.int32)
    scope.on_evictions([
        {"tokens": tuple(prompt[:8].tolist()), "block": 8},
        {"tokens": tuple(prompt.tolist()), "block": 8}])

    class _A:
        shared, skip = 1, 8     # first block live-shared again

    out = scope.on_admit(_Req(1, prompt, page_alloc=_A()))
    # only the second block is re-paid, and capped at P-1-skip = 7
    assert out["regret_tokens"] == 7


# ---------------------------------------------------- session lifecycle
def test_session_lifecycle_fake_clock():
    clock = TickClock(dt=1.0)
    reg = MetricsRegistry()
    scope = KVScope({"dead_after_s": 100.0}, registry=reg, clock=clock,
                    page_size=8, per_token_bytes=10)
    p = np.arange(16, dtype=np.int32)
    r1 = _Req(1, p, session_id="s")
    scope.on_admit(r1)
    scope.on_retire(r1)                  # goes idle at some t0
    clock.advance(50.0)
    r2 = _Req(2, p, session_id="s")
    scope.on_admit(r2)                   # resume after ~51s idle
    snap = scope.snapshot()
    h = reg.snapshot()["histograms"]
    assert snap["sessions"]["resumed"] == 1
    idle = h["Serve/session_idle_s"]
    assert idle["count"] == 1 and 50.0 <= idle["last"] <= 53.0
    assert h["Serve/kv_reuse_interval_s"]["count"] == 1
    # integral: held 16 tokens * 10 B/token over the idle gap
    assert snap["sessions"]["idle_kv_byte_s"] >= 16 * 10 * 50.0
    scope.on_retire(r2)
    clock.advance(200.0)                 # beyond dead_after_s
    snap = scope.snapshot()
    assert snap["sessions"]["dead"] == 1 and snap["sessions"]["idle"] == 0
    assert scope.idle_kv_bytes() == 16 * 10


def test_session_tracker_bounded_lru():
    scope = KVScope({"max_sessions": 4}, clock=TickClock(), page_size=0)
    for i in range(10):
        r = _Req(i, np.arange(8, dtype=np.int32), session_id=f"s{i}")
        scope.on_admit(r)
        scope.on_retire(r)
    assert len(scope.sessions) == 4
    assert scope.sessions_finalized == 6


def test_session_residency_tracks_in_perfetto():
    from deepspeed_tpu.observability.export import (to_chrome_trace,
                                                    validate_chrome_trace)
    from deepspeed_tpu.observability.spans import SpanRecorder

    clock = TickClock(dt=1.0)
    spans = SpanRecorder(64, clock=clock)
    scope = KVScope(clock=clock, spans=spans, page_size=8)
    p = np.arange(16, dtype=np.int32)
    r1 = _Req(1, p, session_id="chat-1")
    scope.on_admit(r1)
    scope.on_retire(r1)
    clock.advance(10.0)
    scope.on_admit(_Req(2, p, session_id="chat-1"))   # closes the idle gap
    tr = to_chrome_trace(spans.events())
    assert validate_chrome_trace(tr) == []
    names = [e["args"]["name"] for e in tr["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert "session chat-1" in names
    kinds = [e["name"] for e in tr["traceEvents"] if e.get("ph") == "X"]
    assert "active" in kinds and "idle" in kinds


# ------------------------------------------------------- workload split
def test_workload_resume_vs_cross_overlap():
    wa = WorkloadAnalyzer({"block": 8})
    sys_p = np.arange(16, dtype=np.int32)
    # session A turn 1: only the system prompt, no history anywhere
    pa1 = np.concatenate([sys_p, np.full(8, 70, np.int32)])
    out = wa.on_admit(pa1, session_id="A")
    assert out["shared_prefix_tokens"] == 0
    # session B turn 1: shares the system prompt CROSS-request
    pb1 = np.concatenate([sys_p, np.full(8, 80, np.int32)])
    out = wa.on_admit(pb1, session_id="B")
    assert out["shared_prefix_tokens"] == 16
    assert out["resume_prefix_tokens"] == 0
    # session A turn 2: replays its own turn-1 prefix — RESUME overlap
    pa2 = np.concatenate([pa1, np.full(8, 71, np.int32)])
    out = wa.on_admit(pa2, session_id="A")
    assert out["resume_prefix_tokens"] == 24
    snap = wa.snapshot()
    assert snap["resume_prefix_tokens"] == 24
    assert snap["shared_prefix_tokens"] == 40        # 16 cross + 24 resume
    assert snap["resume_overlap"] > 0
    assert snap["cross_overlap"] > 0
    g = wa.registry.snapshot()["gauges"]
    assert g["Serve/workload_resume_overlap"] == pytest.approx(
        snap["resume_overlap"])


def test_token_hash_matches_prefix_hashes():
    from deepspeed_tpu.observability.workload import prefix_hashes

    toks = np.arange(24, dtype=np.int32)
    assert prefix_hashes(toks, 8)[-1] == (24, token_hash(toks))


# ------------------------------------------------------ pages satellites
def test_pool_eviction_pressure_fields():
    clock = TickClock(dt=1.0)
    pool = PagePool(6, 8, 64, clock=clock)
    assert pool.snapshot()["oldest_tree_entry_age_s"] is None
    p = np.arange(16, dtype=np.int32)
    a = pool.try_admit(p, 8, rid=1)
    pool.on_inserted(1, p)
    pool.release(1)
    snap = pool.snapshot()
    assert snap["evictable_pages"] == snap["tree_held_pages"] == 2
    assert snap["eviction_events"] == 0
    assert snap["oldest_tree_entry_age_s"] is not None
    clock.advance(40.0)
    assert pool.snapshot()["oldest_tree_entry_age_s"] >= 40.0
    assert a is not None


# -------------------------------------------------------------- advisor
def _ledger_stub():
    return {k: None for k in (
        "weights_bytes", "weights_stream_bytes_per_step", "kv_bytes",
        "kv_per_slot_bytes", "cache_itemsize", "temp_bytes",
        "total_bytes", "limit_bytes", "headroom_bytes",
        "projected_max_slots", "projected_max_context", "kv_page_size",
        "kv_pool_pages", "kv_page_bytes", "kv_quant_bits",
        "kv_pool_used_pages", "kv_pool_free_pages")} | {
        "kv_per_token_bytes": 64, "slots": 2, "max_len": 64}


def _kvs_snap(regret=100, paid=200, cbw=10.0, prefill=1000.0):
    return {
        "per_token_bytes": 64,
        "regret": {"regret_tokens": regret, "regret_admissions": 2,
                   "prefill_tokens_paid": paid,
                   "regret_frac": regret / paid if paid else 0.0,
                   "mean_regret_tokens": regret / 2 if regret else None},
        "sessions": {"idle_kv_bytes_now": 4096, "idle_kv_byte_s": 1.0},
        "copy_bandwidth": {"h2d_gbps": cbw},
        "prefill": ({"tokens_per_s": prefill}
                    if prefill is not None else None),
    }


def test_tiered_kv_lever_measured_score():
    from deepspeed_tpu.observability.capacity import capacity_report

    rep = capacity_report(ledger=_ledger_stub(), kvscope=_kvs_snap())
    tk = {l["name"]: l for l in rep["advisor"]["levers"]}["tiered_kv"]
    # restore = 50 * 64 B / 10 GB/s = 320ns; recompute = 50/1000 = 50ms
    assert tk["score"] == pytest.approx(0.5 * (1 - 3.2e-7 / 0.05),
                                        rel=1e-6)
    assert tk["estimate"]["projected_restore_s_per_resume"] \
        == pytest.approx(3.2e-7)
    assert rep["kvscope"] is not None


@pytest.mark.parametrize("snap,reason", [
    (None, "kvscope off"),
    (_kvs_snap(regret=0), "no eviction regret"),
    (_kvs_snap(cbw=None), "copy bandwidth unmeasured"),
    (_kvs_snap(prefill=None), "prefill timings"),
])
def test_tiered_kv_lever_degrades_to_zero(snap, reason):
    from deepspeed_tpu.observability.capacity import capacity_report

    rep = capacity_report(ledger=_ledger_stub(), kvscope=snap)
    tk = {l["name"]: l for l in rep["advisor"]["levers"]}["tiered_kv"]
    assert tk["score"] == 0.0
    assert reason in tk["why"]


def test_copy_bandwidth_probe_measures_or_degrades():
    out = measure_copy_bandwidth(1 << 16, clock=TickClock(dt=0.001))
    assert set(out) >= {"bytes", "h2d_gbps", "d2h_gbps"}
    assert out["h2d_gbps"] is not None          # tick clock advances
    # a frozen clock degrades to None, never raises
    frozen = measure_copy_bandwidth(1 << 16, clock=lambda: 0.0)
    assert frozen["h2d_gbps"] is None and frozen["d2h_gbps"] is None


def test_kvscope_config_validation():
    with pytest.raises(ValueError, match="ghost_entries"):
        KVScopeConfig(ghost_entries=0)
    with pytest.raises(ValueError, match="unknown kvscope"):
        KVScopeConfig.from_any({"nope": 1})
    assert KVScopeConfig.from_any(None) is None


# ---------------------------------------------------------------- fleet
def test_fleet_affinity_regret_attribution():
    """A regretted resume on the session's sticky replica counts
    Fleet/affinity_regret; on a non-sticky replica only the fleet-wide
    counter moves."""
    from deepspeed_tpu.serving.fleet import FleetEngine

    class _FakeFleet:
        _disagg = False
        registry = MetricsRegistry()
        _session = {("serve", "sess"): "r0"}
        _on_regret_resume = FleetEngine._on_regret_resume

    f = _FakeFleet()
    f._on_regret_resume("r0", "sess", 31)      # sticky replica: affinity
    f._on_regret_resume("r1", "sess", 10)      # elsewhere: fleet-wide only
    c = f.registry.snapshot()["counters"]
    assert c["Fleet/resume_regrets"] == 2
    assert c["Fleet/resume_regret_tokens"] == 41
    assert c["Fleet/affinity_regret"] == 1
    assert c["Fleet/affinity_regret_tokens"] == 31


def test_disaggregated_handoff_moves_session_residency():
    """A handed-off request must not pin its session ACTIVE on the
    prefill replica forever: release_request ends the residency there,
    import_request takes it over on the decode side, and the decode
    retirement finds the rid in the live set."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.serving.fleet import FleetEngine

    model = build_model(tiny_test(n_layer=1, d_model=32, d_ff=64,
                                  n_head=2, max_seq=64,
                                  dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params, {"dtype": "float32"})
    fleet = FleetEngine(eng, {"slots": 2, "max_len": 64,
                              "prefill_chunk": 16, "greedy": True,
                              "page_size": 8, "kvscope": {}},
                        replicas=2, prefill_replicas=1)
    rid = fleet.submit(np.arange(16, dtype=np.int32), 4, seed=1,
                       session_id="s")
    it = 0
    while fleet.pop_result(rid) is None:
        fleet.step()
        it += 1
        assert it < 100_000
    pre = fleet.replicas["p0"].kvscope.snapshot()["sessions"]
    dec = fleet.replicas["d0"].kvscope.snapshot()["sessions"]
    assert pre["active"] == 0, pre       # handoff ended activity at p0
    assert dec["tracked"] == 1 and dec["active"] == 0, dec
    fleet.close()


def test_idle_kv_tokens_capped_at_tree_residency():
    """Per-session held sums can't exceed what the tree actually holds
    — eviction reclaims pages the session tracker can't attribute."""
    clock = TickClock()
    scope = KVScope(clock=clock, page_size=8, per_token_bytes=10,
                    tree_held_tokens=lambda: 24)
    for sid in ("a", "b"):
        r = _Req(hash(sid), np.arange(32, dtype=np.int32), session_id=sid)
        scope.on_admit(r)
        scope.on_retire(r)
    # both sessions claim 32 held tokens, but the tree only holds 24
    assert scope.idle_kv_tokens() == 24
    assert scope.idle_kv_bytes() == 240
    assert scope.snapshot()["sessions"]["idle_kv_tokens_now"] == 24


# --------------------------------------------------------------- doctor
def _write_prom(tmp_path, frac):
    (tmp_path / "kv.prom").write_text(
        f"dstpu_serve_eviction_regret_frac {frac}\n"
        f"dstpu_serve_eviction_regret_tokens 100\n"
        "dstpu_serve_sessions_idle 3\n")


def test_doctor_kv_gate_trips_on_runaway_regret(tmp_path, capsys):
    from deepspeed_tpu.observability import doctor

    _write_prom(tmp_path, 0.9)
    rc = doctor.main(["--dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "runaway eviction regret" in out
    assert "[kv]" in out
    # --no-gate restores report-only
    assert doctor.main(["--dir", str(tmp_path), "--no-gate"]) == 0
    capsys.readouterr()


def test_doctor_kv_gate_clean_and_threshold(tmp_path, capsys):
    from deepspeed_tpu.observability import doctor

    _write_prom(tmp_path, 0.2)
    assert doctor.main(["--dir", str(tmp_path)]) == 0
    # a tightened threshold trips the same file
    assert doctor.main(["--dir", str(tmp_path),
                        "--kv-regret-max", "0.1"]) == 1
    capsys.readouterr()


# --------------------------------------------------- a live engine
def test_live_engine_regret_advisor_and_no_added_program():
    """The observatory on a live paged engine (the tests above drive the
    host-only hooks). Two page-aligned sessions cycle through a pool that
    holds one request: each resubmission re-pays P - 1 prefill tokens and
    the ghost ledger says exactly that; the advisor, fed by the measured
    regret, the copy-bandwidth probe and the span ring's prefill timings,
    ranks ``tiered_kv`` first. On an unpressured pool the same traffic
    books no regret and the lever scores 0 with its reason. The engine
    with kvscope and spans on builds exactly the programs it builds with
    both off, and more traffic builds none."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.observability.capacity import validate_capacity_report

    P, ps, max_new, rounds = 32, 8, 8, 2
    model = build_model(tiny_test(max_seq=64, dtype=jnp.float32))
    eng = ds.init_inference(model, model.init(jax.random.PRNGKey(0)),
                            {"dtype": "float32"})
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, (P,)).astype(np.int32)
               for _ in range(2)]

    def cycle(pool_pages, observed):
        srv = ds.ServingEngine(eng, {
            "slots": 2, "max_len": 64, "prefill_chunk": 16, "greedy": True,
            "page_size": ps, "pool_pages": pool_pages, **(
                {"spans": True, "kvscope": {"dead_after_s": 3600.0}}
                if observed else {})})
        for r in range(rounds):
            for sid, prompt in enumerate(prompts):
                srv.serve_batch([prompt], [max_new], [1000 * sid + r],
                                session_ids=[f"s{sid}"])
        return srv

    one_request = 1 + (P + max_new - 1 + ps - 1) // ps
    srv = cycle(one_request, observed=True)
    snap = srv.kvscope.snapshot()
    assert snap["regret"]["regret_tokens"] == 2 * (rounds - 1) * (P - 1)
    assert snap["sessions"]["resumed"] == 2
    assert snap["sessions"]["regret_resumes"] == 2
    pool = srv.pool.snapshot()
    assert pool["eviction_events"] == 3 and pool["pages_evicted"] == 12
    rep = srv.capacity_report(census=False)
    assert validate_capacity_report(rep) == []
    lever = {l["name"]: l for l in rep["advisor"]["levers"]}["tiered_kv"]
    assert rep["advisor"]["ranked"][0] == "tiered_kv" and lever["score"] > 0
    assert lever["estimate"]["copy_h2d_gbps"] is not None
    assert lever["estimate"]["measured_recompute_s_per_resume"] is not None
    assert "kv_idle_resident_bytes" in rep["ledger"]

    roomy = cycle(0, observed=True)           # 0: the pool sizes itself
    assert roomy.kvscope.snapshot()["regret"]["regret_tokens"] == 0
    assert roomy.pool.snapshot()["eviction_events"] == 0
    quiet = {l["name"]: l for l in roomy.capacity_report(census=False)
             ["advisor"]["levers"]}["tiered_kv"]
    assert quiet["score"] == 0.0 and "no eviction regret" in quiet["why"]

    assert cycle(one_request, observed=False).compiles == srv.compiles
    warm = srv.compiles
    for sid, prompt in enumerate(prompts):
        srv.serve_batch([prompt], [max_new], [77 + sid],
                        session_ids=[f"s{sid}"])
    assert srv.compiles == warm

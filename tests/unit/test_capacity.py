"""Workload & capacity attribution layer (observability/{workload,capacity}).

Oracles:
- prefix-overlap estimator: synthetic traffic with CONSTRUCTED overlap is
  measured exactly at block granularity (and within ±5 points of the
  nominal figure, the bench gate's acceptance band);
- self-speculation estimator: a purely repetitive sequence scores high, a
  collision-free sequence scores zero, too-short scores None;
- HBM ledger: weight/KV totals equal hand-computed bytes; projections
  derive from the stated limit; every field PRESENT even when unknown;
- census degradation: a backend with no cost/memory analysis yields rows
  with null values — never a raise (the tier-1 pin for CPU smoke runs);
- advisor: prefix-heavy traffic ranks prefix sharing first; no workload
  data degrades levers to score 0 with a stated reason;
- satellites: time-weighted Serve/slot_occupancy_avg on a fake clock,
  Flight/write_errors counting failed dump artifacts, doctor capacity
  section;
- a live engine's report: hand-computed weight/KV bytes, the advisor's
  ranking on prefix-heavy traffic, and no program added by the analytics.
"""

import json
import math

import numpy as np
import pytest

from _fake_clock import TickClock
from deepspeed_tpu.observability.capacity import (
    LEVER_KV_QUANT, LEVER_PREFIX, ProgramCensus, capacity_report,
    hbm_ledger, kv_cache_bytes, validate_capacity_report,
    write_capacity_report)
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.tracing import ServingStats
from deepspeed_tpu.observability.workload import (WorkloadAnalyzer,
                                                  WorkloadConfig,
                                                  prefix_hashes,
                                                  selfspec_acceptance)


# ------------------------------------------------------- workload analytics
def test_prefix_overlap_estimator_exact_on_block_aligned_traffic():
    """Constructed overlap is recovered EXACTLY when the shared prefix is
    block-aligned: n prompts of 40 tokens sharing 32, first shares 0."""
    wl = WorkloadAnalyzer({"block": 8})
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 999, 32).astype(np.int32)
    n = 40
    for _ in range(n):
        wl.on_admit(np.concatenate(
            [prefix, rng.integers(1000, 2000, 8).astype(np.int32)]))
    truth = (n - 1) * 32 / (n * 40)
    assert wl.prefix_overlap == pytest.approx(truth)
    assert abs(wl.prefix_overlap * 100 - 80.0) <= 5.0
    snap = wl.snapshot()
    assert snap["dedupable_prefill_tokens"] == (n - 1) * 32
    assert snap["prompt_tokens"] == n * 40
    # per-request readout: first admit shared nothing, the rest 32 tokens
    r = wl.on_admit(np.concatenate(
        [prefix, rng.integers(1000, 2000, 8).astype(np.int32)]))
    assert r["shared_prefix_tokens"] == 32 and r["prompt_len"] == 40


def test_prefix_overlap_floors_at_block_boundaries():
    """A shared prefix that is NOT block-aligned counts only its aligned
    floor — the granularity a paged prefix cache would actually share."""
    wl = WorkloadAnalyzer({"block": 16})
    base = np.arange(100, 140, dtype=np.int32)         # 40 tokens
    wl.on_admit(base)
    # second prompt shares 39 tokens → floor(39/16)*16 = 32 creditable
    other = base.copy()
    other[-1] += 1
    r = wl.on_admit(other)
    assert r["shared_prefix_tokens"] == 32


def test_prefix_sketch_is_bounded_lru():
    """max_prefixes bounds host memory; evicted prefixes stop matching —
    overlap is measured against *recent* traffic like a finite cache."""
    wl = WorkloadAnalyzer({"block": 4, "max_prefixes": 8})
    a = np.arange(0, 16, dtype=np.int32)
    wl.on_admit(a)                                     # 4 boundary hashes
    for k in range(1, 4):                              # flood the sketch
        wl.on_admit(np.arange(k * 1000, k * 1000 + 16, dtype=np.int32))
    assert len(wl._prefixes) <= 8
    r = wl.on_admit(a)                                 # a's hashes evicted
    assert r["shared_prefix_tokens"] == 0


def test_prefix_match_survives_partial_eviction():
    """Each boundary hash covers the whole prefix from 0, so a match at
    any length stands alone. The LRU evicts a prompt's SHORTER boundaries
    first — near capacity the longest resident boundary must still score,
    not be masked by a miss at an evicted shorter one."""
    wl = WorkloadAnalyzer({"block": 4, "max_prefixes": 5})
    a = np.arange(0, 16, dtype=np.int32)
    wl.on_admit(a)                     # boundaries at 4/8/12/16
    wl.on_admit(np.arange(500, 508, dtype=np.int32))   # evicts a's len-4
    r = wl.on_admit(a)                 # len-8/12/16 hashes still resident
    assert r["shared_prefix_tokens"] == 16


def test_selfspec_acceptance_estimator():
    # pure repetition: after warmup every 3-gram predicts its successor
    rep = np.tile(np.arange(4, dtype=np.int32), 50)
    acc = selfspec_acceptance(rep, ngram=3)
    assert acc == pytest.approx((len(rep) - 3 - 4) / (len(rep) - 3), abs=0.05)
    # collision-free sequence: nothing repeats, nothing is predictable
    assert selfspec_acceptance(np.arange(64, dtype=np.int32), 3) == 0.0
    # too short to score one position
    assert selfspec_acceptance(np.arange(3, dtype=np.int32), 3) is None


def test_prefix_hashes_incremental_and_aligned():
    toks = np.arange(10, dtype=np.int32)
    hs = prefix_hashes(toks, block=4)
    assert [l for l, _ in hs] == [4, 8]
    # a prefix-extension keeps earlier boundary hashes identical
    hs2 = prefix_hashes(np.concatenate([toks, toks]), block=4)
    assert hs2[:2] == hs
    # and different contents give different hashes
    assert prefix_hashes(toks + 1, block=4) != hs


def test_workload_config_validation():
    with pytest.raises(ValueError, match="block"):
        WorkloadConfig(block=0)
    with pytest.raises(ValueError, match="ngram"):
        WorkloadConfig(ngram=0)
    with pytest.raises(ValueError, match="max_prefixes"):
        WorkloadConfig(max_prefixes=0)
    with pytest.raises(ValueError, match="unknown workload config"):
        WorkloadConfig.from_any({"blokc": 8})
    assert WorkloadConfig.from_any(None) is None
    cfg = WorkloadConfig.from_any({"block": 4})
    assert WorkloadConfig.from_any(cfg) is cfg


def test_workload_overhead_measured_on_injectable_clock():
    clk = TickClock(dt=0.25)
    wl = WorkloadAnalyzer({"block": 4}, clock=clk)
    wl.on_admit(np.arange(8, dtype=np.int32))
    h = wl.registry.snapshot()["histograms"]["Serve/workload_analysis_s"]
    assert h["count"] == 1 and h["last"] == pytest.approx(0.25)


# ------------------------------------------------------------------ ledger
class _Cfg:
    n_layer, kv_heads, head_dim = 4, 2, 8


def test_kv_cache_bytes_hand_computed():
    kv = kv_cache_bytes(_Cfg(), slots=3, max_len=32, dtype=np.float32)
    want = 2 * 4 * 3 * 2 * 32 * 8 * 4            # 2 bufs × L·B·KV·S·hd × f32
    assert kv["total_bytes"] == want
    assert kv["per_slot_bytes"] == want // 3
    assert kv["per_token_bytes"] == want // 3 // 32
    assert kv["itemsize"] == 4


def test_hbm_ledger_totals_and_projections():
    params = {"w": np.zeros((10, 10), np.float32),
              "tok_embed": np.zeros((8, 4), np.float32)}
    reg = MetricsRegistry()
    kv = kv_cache_bytes(_Cfg(), 2, 32, np.float32)
    limit = 10 * 1024 * 1024
    led = hbm_ledger(params=params, model_cfg=_Cfg(), slots=2, max_len=32,
                     cache_dtype=np.float32, temp_bytes=1000,
                     limit_bytes=limit, registry=reg)
    weights = (100 + 32) * 4
    assert led["weights_bytes"] == weights
    assert led["kv_bytes"] == kv["total_bytes"]
    assert led["total_bytes"] == weights + kv["total_bytes"] + 1000
    assert led["headroom_bytes"] == limit - led["total_bytes"]
    free = limit - weights - 1000
    assert led["projected_max_slots"] == free // kv["per_slot_bytes"]
    assert led["projected_max_context"] == \
        free // (kv["per_token_bytes"] * 2)
    g = reg.snapshot()["gauges"]
    assert g["Memory/ledger_weights_bytes"] == weights
    assert g["Memory/ledger_kv_bytes"] == kv["total_bytes"]


def test_hbm_ledger_degrades_fields_present_values_null():
    """No limit (CPU smoke): headroom/projections are PRESENT and None —
    the degradation contract the capacity report validator pins."""
    led = hbm_ledger(params={"w": np.zeros((4, 4), np.float32)},
                     model_cfg=_Cfg(), slots=1, max_len=16,
                     cache_dtype=np.float32, limit_bytes=None)
    for k in ("headroom_bytes", "projected_max_slots",
              "projected_max_context", "temp_bytes"):
        assert k in led and led[k] is None


# ------------------------------------------------------------------ census
class _NoAnalysisCompiled:
    """A 'compiled' object from a backend that implements none of the
    analyses — every probe raises, like old jax/exotic backends."""

    def cost_analysis(self):
        raise NotImplementedError("no cost analysis on this backend")

    def memory_analysis(self):
        raise NotImplementedError("no memory analysis on this backend")

    def as_text(self):
        raise NotImplementedError("no HLO text on this backend")


def test_census_degrades_to_null_rows_never_raises():
    census = ProgramCensus()
    row = census.measure("step", _NoAnalysisCompiled())
    for k in ("flops", "bytes_accessed", "collective_mbytes",
              "collective_count", "temp_bytes", "peak_bytes"):
        assert k in row and row[k] is None
    rep = census.report()
    assert set(rep["programs"]) == {"step"}
    assert rep["programs"]["step"]["mfu"] is None
    assert rep["programs"]["step"]["mbu"] is None
    # the degraded census still joins wall times (achieved side intact)
    census.observe_wall("step", 0.5)
    rep = census.report()
    assert rep["programs"]["step"]["wall_s_p50"] == 0.5
    assert rep["programs"]["step"]["calls"] == 1


def test_census_lowering_failure_keeps_null_row():
    def explodes(*a):
        raise RuntimeError("nope")

    class _Unlowerable:
        lower = staticmethod(explodes)

    census = ProgramCensus()
    row = census.measure("broken", _Unlowerable())
    assert row["flops"] is None         # row kept, fields present, no raise


def test_census_real_program_on_cpu():
    """Where the backend DOES support the analyses (jax CPU), the census
    records static costs and roofline joins against observed wall."""
    import jax
    import jax.numpy as jnp

    census = ProgramCensus(peak_flops=1e12, peak_bw=1e11)
    fn = jax.jit(lambda x: x @ x)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    row = census.measure("mm", fn, x)
    assert row["flops"] and row["flops"] >= 2 * 64**3 * 0.9
    census.observe_wall("mm", 1e-4)
    r = census.report()["programs"]["mm"]
    assert r["mfu"] == pytest.approx(r["achieved_tflops"] * 1e12 / 1e12)
    assert r["achieved_gbps"] is not None


# ----------------------------------------------------------------- advisor
def _workload_snap(overlap=0.8, accept=0.1, prompt_mean=40.0,
                   decode_mean=8.0, tokens=4000):
    return {"prefix_overlap": overlap,
            "dedupable_prefill_tokens": int(tokens * overlap),
            "prompt_tokens": tokens,
            "selfspec_accept": {"mean": accept},
            "prompt_len": {"mean": prompt_mean},
            "decode_len": {"mean": decode_mean}}


def _ledger(itemsize=2):
    kv = kv_cache_bytes(_Cfg(), 4, 128,
                        np.float16 if itemsize == 2 else np.float32)
    return hbm_ledger(params={"w": np.zeros((64, 64), np.float32)},
                      model_cfg=_Cfg(), slots=4, max_len=128,
                      cache_dtype=np.float16 if itemsize == 2
                      else np.float32, limit_bytes=1 << 24) | {
        "kv_per_token_bytes": kv["per_token_bytes"]}


def test_advisor_ranks_prefix_on_prefix_heavy_traffic(tmp_path):
    rep = capacity_report(ledger=_ledger(), workload=_workload_snap(0.8),
                          occupancy_avg=0.9, meta={"job": "t"})
    assert validate_capacity_report(rep) == []
    ranked = rep["advisor"]["ranked"]
    assert ranked[0] == LEVER_PREFIX
    assert ranked.index(LEVER_PREFIX) < ranked.index(LEVER_KV_QUANT)
    # round-trips through the atomic writer
    p = write_capacity_report(rep, tmp_path / "CAPACITY_REPORT.json")
    assert validate_capacity_report(json.loads(p.read_text())) == []


def test_mean_context_time_averages_decode():
    """decode_len records the FINAL generated count at retirement; a
    slot's time-averaged live context is prompt + ~decode/2 (context
    grows linearly over residency) — matching the max_len/2 fallback's
    average-over-lifetime semantics."""
    from deepspeed_tpu.observability.capacity import _mean_context

    wl = {"prompt_len": {"mean": 50.0}, "decode_len": {"mean": 400.0}}
    assert _mean_context(wl, {}) == pytest.approx(50.0 + 200.0)
    assert _mean_context({"prompt_len": {"mean": 50.0}}, {}) == 50.0
    assert _mean_context(None, {"max_len": 48}) == 24.0


def test_advisor_degrades_without_workload_data():
    rep = capacity_report(ledger=_ledger(), workload=None, census=None)
    assert validate_capacity_report(rep) == []
    levers = {d["name"]: d for d in rep["advisor"]["levers"]}
    assert levers[LEVER_PREFIX]["score"] == 0.0
    assert "off" in levers[LEVER_PREFIX]["why"]
    # the KV lever still scores from the ledger alone (context falls back
    # to half the slot capacity), never inventing workload numbers
    assert levers[LEVER_KV_QUANT]["estimate"][
        "decode_step_speedup_bound"] is not None


def test_validate_capacity_report_negatives():
    rep = capacity_report(ledger=_ledger(), workload=None)
    assert validate_capacity_report("nope") != []
    bad = dict(rep, schema="wrong/v0")
    assert any("schema" in e for e in validate_capacity_report(bad))
    bad = dict(rep, ledger={k: v for k, v in rep["ledger"].items()
                            if k != "kv_bytes"})
    assert any("kv_bytes" in e for e in validate_capacity_report(bad))
    bad = dict(rep, advisor={"levers": rep["advisor"]["levers"],
                             "ranked": []})
    assert any("ranked" in e for e in validate_capacity_report(bad))


# -------------------------------------------------------------- satellites
def test_slot_occupancy_avg_time_weighted_fake_clock():
    clk = TickClock(dt=0.0)              # manual advance only
    st = ServingStats(clock=clk)
    # 100% occupancy held for 3s, then 0% for 1s → avg 0.75
    st.on_iteration(0, 4, 4, False)      # sample at t=0: frac 1.0
    clk.advance(3.0)
    st.on_iteration(0, 0, 4, False)      # 1.0 held over [0, 3]
    clk.advance(1.0)
    st.on_iteration(0, 0, 4, False)      # 0.0 held over [3, 4]
    g = st.registry.snapshot()["gauges"]
    assert g["Serve/slot_occupancy_avg"] == pytest.approx(0.75)
    assert g["Serve/slot_occupancy"] == 0.0          # point-in-time differs
    assert st.snapshot()["slot_occupancy_avg"] == pytest.approx(0.75)
    st.reset()
    assert "Serve/slot_occupancy_avg" not in \
        st.registry.snapshot()["gauges"]


def test_flight_write_errors_counted(tmp_path, monkeypatch):
    from deepspeed_tpu.observability import flight as F

    reg = MetricsRegistry()
    # unwritable dump dir: the directory path is a FILE
    blocked = tmp_path / "blocked"
    blocked.write_text("not a dir")
    fr = F.FlightRecorder(blocked / "dumps", registry=reg, clock=TickClock())
    assert fr.dump("stall") is None
    assert reg.snapshot()["counters"]["Flight/write_errors"] == 1
    # one failing artifact writer: counted, .error breadcrumb written,
    # the rest of the post-mortem still lands
    fr2 = F.FlightRecorder(tmp_path / "ok", registry=reg, clock=TickClock())
    from deepspeed_tpu.observability import export as E
    monkeypatch.setattr(E, "write_chrome_trace",
                        lambda *a, **k: (_ for _ in ()).throw(OSError("x")))
    d = fr2.dump("stall")
    assert d is not None
    assert reg.snapshot()["counters"]["Flight/write_errors"] == 2
    assert (d / "trace.json.error").exists()
    assert (d / "manifest.json").exists() and (d / "metrics.json").exists()
    # the name lands in the .prom as dstpu_flight_write_errors
    from deepspeed_tpu.observability.sinks import prometheus_name
    assert prometheus_name("Flight/write_errors") == \
        "dstpu_flight_write_errors"


def test_doctor_capacity_section(tmp_path, capsys):
    from deepspeed_tpu.observability import doctor

    rep = capacity_report(ledger=_ledger(), workload=_workload_snap(0.8),
                          occupancy_avg=0.5)
    write_capacity_report(rep, tmp_path / "CAPACITY_REPORT.json")
    assert doctor.main(["--dir", str(tmp_path)]) == 0   # nothing fired
    out = capsys.readouterr().out
    assert "[capacity]" in out and "INVALID" not in out
    assert "#1 prefix_sharing" in out
    assert "weights_bytes" in out and "[gate] clean" in out
    # an invalid report is flagged but never crashes the triage
    (tmp_path / "CAPACITY_REPORT.json").write_text('{"schema": "x"}')
    assert doctor.main(["--dir", str(tmp_path)]) == 0
    assert "INVALID" in capsys.readouterr().out
    # hand-edited / torn-but-parseable shapes: wrong-typed census, a
    # non-dict lever, null lever fields, a non-dict report — flagged by
    # the validator, printed field-by-field, never a traceback
    for torn in ('{"schema": "x", "census": [], "advisor":'
                 ' {"levers": [{}, null, {"score": null}]}}',
                 '[1, 2]'):
        (tmp_path / "CAPACITY_REPORT.json").write_text(torn)
        assert doctor.main(["--dir", str(tmp_path)]) == 0
        assert "INVALID" in capsys.readouterr().out


# ----------------------------------------------------- serving integration
def test_serving_workload_wiring():
    """The admission hook feeds the analyzer; disabled (default) builds
    nothing. Program-count parity between the two is
    ``test_live_engine_report_hand_bytes_ranking_and_no_added_program``."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params, {"dtype": "float32"})
    srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                 "prefill_chunk": 16, "greedy": True})
    assert srv.workload is None                         # default: none built
    wl_srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                    "prefill_chunk": 16, "greedy": True,
                                    "workload": {"block": 4}})
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 99, 12).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(100, 200, 4).astype(np.int32)])
               for _ in range(4)]
    wl_srv.serve_batch(prompts, max_new_tokens=3)
    snap = wl_srv.metrics_snapshot()
    assert snap["workload"]["requests"] == 4
    assert snap["workload"]["prefix_overlap"] == pytest.approx(
        3 * 12 / (4 * 16))
    # decode-side shape histogram fed at retirement
    assert snap["workload"]["decode_len"]["count"] == 4
    # the ledger/census/advisor composition runs on CPU (degraded fields
    # allowed, schema complete)
    rep = wl_srv.capacity_report()
    assert validate_capacity_report(rep) == []
    assert rep["census"]["programs"].get("step") is not None
    assert rep["meta"]["job"] == "serving"
    # the census never BUILDS programs: an idle engine reports an empty
    # census (no phantom compile in the freeze gates / storm detector)
    idle = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                  "prefill_chunk": 16, "greedy": True})
    before = idle.compiles
    idle_rep = idle.capacity_report()
    assert idle.compiles == before
    assert idle_rep["census"]["programs"] == {}
    assert validate_capacity_report(idle_rep) == []


def test_train_step_cost_census(devices):
    """The training row of the capacity census: compile_train_step's AOT
    memory summary survives its refactor through
    ``compiled_memory_analysis``, and ``Engine.cost_census`` joins the
    train step's static costs with achieved span wall times."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  random_token_dataset)

    model = build_model(tiny_test())
    engine = ds.initialize({
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "steps_per_print": 100,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "observability": {"spans": True},
    }, model)
    data = random_token_dataset(32, seq_len=16, vocab_size=256, seed=0,
                                learnable=True)
    loader = DataLoader(data, local_batch_size=engine.train_batch_size,
                        shuffle=False, seed=0)
    batch = next(iter(loader))
    engine.train_batch(batch)
    ma = engine.compile_train_step(batch)
    assert isinstance(ma, dict)         # *_in_bytes fields where supported
    rep = engine.cost_census(batch)
    row = rep["programs"]["train_step"]
    for k in ("flops", "bytes_accessed", "collective_mbytes", "temp_bytes",
              "mfu", "mbu"):
        assert k in row                 # present even when degraded to null
    assert row["calls"] >= 1            # the span ring joined achieved wall
    assert row["wall_s_p50"] is not None
    engine.close()


def test_live_engine_report_hand_bytes_ranking_and_no_added_program(
        tmp_path):
    """The capacity layer measuring a live engine, not a synthetic
    snapshot: with ``workload`` and ``spans`` on, the same traffic builds
    exactly the programs the plain engine builds and more traffic builds
    none; the written report's weight and KV bytes equal the sum of the
    parameter leaves and K + V of ``cache_layout``; on 80%-shared prompts
    the advisor ranks prefix sharing above KV quantization; and the
    analyzer's own cost per admission is in the report."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.decode import cache_layout
    from deepspeed_tpu.models import build_model, tiny_test

    slots, max_len, n = 4, 64, 40
    model = build_model(tiny_test(n_layer=2, d_model=64, d_ff=128, n_head=2,
                                  max_seq=max_len))
    eng = ds.init_inference(model, model.init(jax.random.PRNGKey(0)),
                            {"dtype": "float32"})
    base = {"slots": slots, "max_len": max_len, "prefill_chunk": 16,
            "greedy": True}
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 256, 32).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(0, 256, 8)
                               .astype(np.int32)]) for _ in range(n + 12)]
    plain = ds.ServingEngine(eng, base)
    plain.serve_batch(prompts[:n], max_new_tokens=2)
    srv = ds.ServingEngine(eng, {**base, "spans": True,
                                 "workload": {"block": 8}})
    srv.serve_batch(prompts[:n], max_new_tokens=2)
    assert srv.compiles == plain.compiles
    srv.serve_batch(prompts[n:], max_new_tokens=2)
    assert srv.compiles == plain.compiles

    path = tmp_path / "CAPACITY_REPORT.json"
    srv.capacity_report(path=str(path))
    rep = json.loads(path.read_text())
    assert validate_capacity_report(rep) == []
    shape, dt = cache_layout(model.cfg, slots, max_len, eng.compute_dtype)
    assert rep["ledger"]["weights_bytes"] == sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(eng.params))
    assert rep["ledger"]["kv_bytes"] == \
        2 * math.prod(shape) * jnp.dtype(dt).itemsize
    ranked = rep["advisor"]["ranked"]
    assert ranked.index(LEVER_PREFIX) < ranked.index(LEVER_KV_QUANT)
    assert rep["workload"]["analysis_s"]["count"] >= n
    assert rep["workload"]["analysis_s"]["mean"] >= 0.0

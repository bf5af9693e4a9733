"""Communication observatory (deepspeed_tpu/observability/commscope.py).

What is pinned here:

- the interval algebra and the step-anatomy TILING invariant — compute +
  exposed-collective + other sums to the step wall exactly;
- ``comm.hlo_analysis`` classifies EVERY collective kind from hand-built
  HLO text, counts tuple-form variadic payloads as their SUM (the
  all-to-all undercount fix) while async ``-start`` tuples keep the
  max-member rule, and skips ``-done`` halves;
- the achieved-bandwidth ledger carries the census bytes verbatim and
  derives algbw/busbw with the NCCL-convention ring factors, degrading
  to nulls when either side is unmeasured;
- the straggler detector: a single slow device is flagged with the right
  id, a UNIFORM slowdown never flags, the episode closes after the
  device heals, and the flight why-marker is written exactly once per
  episode — all on synthetic stamp streams with the injectable clock;
- the Perfetto export renders ``comm_op``/``comm_exposed`` spans as the
  ``comm``/``comm-exposed`` tracks beside the train pid and the result
  passes the trace validator;
- the capacity advisor's quantize/overlap-collectives lever upgrades to
  the MEASURED exposed fraction when an observatory report is attached;
- the doctor's ``[comm]`` section gates on a burning straggler gauge;
- a sharded training engine with the observatory on: the same programs
  and bit-identical losses as with it off, and a CPU capture that
  degrades to a null anatomy without raising.
"""

import gzip
import json

import pytest

from deepspeed_tpu.observability import commscope as C
from deepspeed_tpu.observability import spans as S

from _fake_clock import TickClock


# ---------------------------------------------------------- interval math
def test_interval_merge_and_subtract():
    assert C.merge_intervals([(5, 6), (0, 2), (1, 3), (3, 3)]) == \
        [(0, 3), (5, 6)]
    assert C.subtract_intervals([(0, 10)], [(2, 4), (6, 7)]) == \
        [(0, 2), (4, 6), (7, 10)]
    assert C.subtract_intervals([(0, 5)], [(0, 5)]) == []
    assert C.subtract_intervals([(0, 5)], []) == [(0, 5)]
    assert C.clip_intervals([(0, 10), (20, 30)], 5, 25) == \
        [(5, 10), (20, 25)]


def _ops():
    return [
        C.OpSpan("fusion.1", 0.000, 0.040, "d0"),
        C.OpSpan("all-reduce.1", 0.035, 0.055, "d0", "all-reduce"),
        C.OpSpan("fusion.2", 0.050, 0.070, "d0"),
        C.OpSpan("reduce-scatter.3", 0.080, 0.090, "d0",
                 "reduce-scatter"),
    ]


def test_step_anatomy_tiles_the_wall():
    a = C.step_anatomy(_ops(), 0.0, 0.100)
    assert a["compute_s"] == pytest.approx(0.060)
    assert a["collective_s"] == pytest.approx(0.030)
    # all-reduce [35,55) overlaps compute [35,40)+[50,55): 10ms exposed;
    # the reduce-scatter is fully exposed
    assert a["exposed_collective_s"] == pytest.approx(0.020)
    assert a["other_s"] == pytest.approx(0.020)
    tile = a["compute_s"] + a["exposed_collective_s"] + a["other_s"]
    assert tile == pytest.approx(a["wall_s"], abs=1e-12)
    assert a["exposed_comm_frac"] == pytest.approx(0.2)
    assert a["overlap_frac"] == pytest.approx(1 - 0.020 / 0.030)
    assert a["by_kind"]["all-reduce"]["exposed_s"] == pytest.approx(0.010)


@pytest.mark.parametrize("spelling,ops,collective_ms,exposed_frac", [
    # one flat all-reduce serialized after the backward
    ("fused", [("fusion.bwd", 0, 60, None),
               ("all-reduce.grads", 60, 90, "all-reduce")], 30, 0.3),
    # 35 ms of collectives, more than fused, in buckets riding the
    # backward: the first hidden whole, the second exposed for [60, 65),
    # the gather bare
    ("bucketed", [("fusion.bwd", 0, 60, None),
                  ("fusion.bwd.tail", 65, 95, None),
                  ("all-to-all.b0", 20, 35, "all-to-all"),
                  ("all-to-all.b1", 55, 70, "all-to-all"),
                  ("all-gather.b1", 95, 100, "all-gather")], 35, 0.1),
])
def test_anatomy_prices_what_bucketed_overlap_buys(spelling, ops,
                                                   collective_ms,
                                                   exposed_frac):
    a = C.step_anatomy([C.OpSpan(n, t0 * 1e-3, t1 * 1e-3, "d0", k)
                        for n, t0, t1, k in ops], 0.0, 0.100)
    assert a["collective_s"] == pytest.approx(collective_ms * 1e-3)
    assert a["exposed_comm_frac"] == pytest.approx(exposed_frac)
    assert a["compute_s"] + a["exposed_collective_s"] + a["other_s"] \
        == pytest.approx(a["wall_s"], abs=1e-12)


def test_decompose_multi_device_and_window():
    # two devices with identical timelines, two step windows
    ops = _ops() + [C.OpSpan(o.name, o.t0 + 0.1, o.t1 + 0.1, "d0",
                             o.kind) for o in _ops()]
    tl = {"d0": ops, "d1": list(ops)}
    d = C.decompose(tl, windows=[(0.0, 0.1), (0.1, 0.2)])
    assert d["n_devices"] == 2 and d["n_windows"] == 2
    tile = d["compute_s"] + d["exposed_collective_s"] + d["other_s"]
    assert tile == pytest.approx(d["wall_s"], rel=1e-9)
    assert d["wall_s"] == pytest.approx(0.2)       # 2 windows summed
    assert d["by_kind"]["all-reduce"]["count"] == 2


def test_decompose_empty_is_all_null():
    d = C.decompose({})
    assert d["exposed_comm_frac"] is None
    assert d["overlap_frac"] is None
    assert d["n_devices"] == 0


def test_classify_op():
    assert C.classify_op("all-reduce-start.7") == "all-reduce"
    assert C.classify_op("psum.3") == "all-reduce"
    assert C.classify_op("loop_reduce_scatter_fusion.1") == \
        "reduce-scatter"
    assert C.classify_op("all-gather.2") == "all-gather"
    assert C.classify_op("ppermute") == "collective-permute"
    # ragged keeps its OWN kind: the ledger joins trace kinds against
    # the HLO census kinds by key, and the census counts it separately
    assert C.classify_op("ragged-all-to-all.4") == "ragged-all-to-all"
    assert C.classify_op("all-to-all.4") == "all-to-all"
    assert C.classify_op("fusion.77") is None
    assert C.classify_op("copy-done.1") is None


# --------------------------------------------------- hlo_analysis (kinds)
_EVERY_KIND_HLO = """
ENTRY main {
  %ar = f32[8,128]{1,0} all-reduce(%p0), to_apply=%add
  %rs = f32[2,128]{1,0} reduce-scatter(%p0), dimensions={0}, to_apply=%add
  %ag = bf16[16,128]{1,0} all-gather(%p0), dimensions={0}
  %a2a = (f32[1,16]{1,0}, f32[1,16]{1,0}, f32[1,16]{1,0}, f32[1,16]{1,0}) all-to-all(%a, %b, %c, %d), replica_groups={{0,1,2,3}}
  %cp = f32[64]{0} collective-permute(%p0), source_target_pairs={{0,1}}
  %cb = f32[32]{0} collective-broadcast(%p0), replica_groups={{0,1}}
  %ra = f32[128]{0} ragged-all-to-all(%p0, %o, %i, %os, %rz, %ss), replica_groups={{0,1}}
  %ars = (f32[8,128]{1,0}, f32[8,128]{1,0}) all-reduce-start(%p0), to_apply=%add
  %ard = f32[8,128]{1,0} all-reduce-done(%ars)
  %cps = (f32[64]{0}, f32[64]{0}, u32[], u32[]) collective-permute-start(%p0), source_target_pairs={{0,1}}
  %cpd = f32[64]{0} collective-permute-done(%cps)
}
"""


def test_collective_summary_classifies_every_kind():
    from deepspeed_tpu.comm.hlo_analysis import (collective_summary,
                                                 collective_totals)

    s = collective_summary(_EVERY_KIND_HLO)
    assert set(s) == {"all-reduce", "reduce-scatter", "all-gather",
                      "all-to-all", "collective-permute",
                      "collective-broadcast", "ragged-all-to-all"}
    # sync + async start; -done halves never counted
    assert s["all-reduce"]["count"] == 2
    assert s["collective-permute"]["count"] == 2
    t = collective_totals(_EVERY_KIND_HLO)
    assert t["count"] == sum(d["count"] for d in s.values())
    assert t["by_kind"] == s


def test_collective_bytes_variadic_sum_vs_start_max():
    from deepspeed_tpu.comm.hlo_analysis import collective_summary

    s = collective_summary(_EVERY_KIND_HLO)
    # tuple-form all-to-all: 4 independent f32[1,16] payloads — the SUM
    # (the old max-member rule undercounted this 4x)
    assert s["all-to-all"]["mbytes"] == pytest.approx(4 * 16 * 4 / 1e6)
    # async -start tuples alias (operand, result): max member only, so
    # sync f32[8,128] + async f32[8,128] = exactly two payloads
    assert s["all-reduce"]["mbytes"] == pytest.approx(2 * 8 * 128 * 4 / 1e6)
    # permute contexts (u32[] pair) don't count toward payload
    assert s["collective-permute"]["mbytes"] == pytest.approx(
        2 * 64 * 4 / 1e6)


# ------------------------------------------------------- bandwidth ledger
def test_bandwidth_ledger_exact_bytes_and_factors():
    anatomy = C.decompose({"d0": _ops()}, windows=[(0.0, 0.1)])
    by_kind = {"all-reduce": {"count": 1, "mbytes": 20.0},
               "reduce-scatter": {"count": 1, "mbytes": 8.0}}
    led = C.bandwidth_ledger(by_kind, anatomy, n_steps=1, n_devices=8,
                             peak_ici_gbps=300.0)
    ar = led["by_kind"]["all-reduce"]
    assert ar["mbytes_per_step"] == 20.0          # census bytes verbatim
    assert ar["algbw_gbps"] == pytest.approx(20e6 / 0.020 / 1e9)
    assert ar["busbw_gbps"] == pytest.approx(
        ar["algbw_gbps"] * 2 * 7 / 8)             # 2(n-1)/n
    assert ar["roofline_ratio"] == pytest.approx(ar["busbw_gbps"] / 300.0)
    rs = led["by_kind"]["reduce-scatter"]
    assert rs["busbw_gbps"] == pytest.approx(
        rs["algbw_gbps"] * 7 / 8)                 # (n-1)/n


def test_bandwidth_ledger_null_degradation():
    # bytes with no measurement: time/bw null, bytes kept
    led = C.bandwidth_ledger({"all-reduce": {"count": 1, "mbytes": 5.0}},
                             None, n_devices=4)
    row = led["by_kind"]["all-reduce"]
    assert row["mbytes_per_step"] == 5.0
    assert row["time_s_per_step"] is None and row["algbw_gbps"] is None
    # measurement with no bytes: time kept, bw null
    anatomy = C.decompose({"d0": _ops()}, windows=[(0.0, 0.1)])
    led2 = C.bandwidth_ledger(None, anatomy, n_devices=4)
    row2 = led2["by_kind"]["all-reduce"]
    assert row2["time_s_per_step"] is not None
    assert row2["mbytes_per_step"] is None and row2["algbw_gbps"] is None
    # no peak: roofline null even when bw is measured
    led3 = C.bandwidth_ledger({"all-reduce": {"count": 1, "mbytes": 5.0}},
                              anatomy, n_devices=4, peak_ici_gbps=None)
    assert led3["by_kind"]["all-reduce"]["busbw_gbps"] is not None
    assert led3["by_kind"]["all-reduce"]["roofline_ratio"] is None


def test_busbw_factor_single_device_is_identity():
    assert C.busbw_factor("all-reduce", 1) == 1.0
    assert C.busbw_factor("all-gather", 1) == 1.0


def test_ragged_all_to_all_census_and_trace_kinds_join():
    """The census kind and the trace-classified kind must be the SAME
    key, or the ledger row never joins bytes with time."""
    from deepspeed_tpu.comm.hlo_analysis import collective_totals

    by_kind = collective_totals(_EVERY_KIND_HLO)["by_kind"]
    ops = [C.OpSpan("ragged-all-to-all.1", 0.01, 0.03, "d0",
                    C.classify_op("ragged-all-to-all.1"))]
    anatomy = C.decompose({"d0": ops}, windows=[(0.0, 0.1)])
    led = C.bandwidth_ledger(by_kind, anatomy, n_devices=4)
    row = led["by_kind"]["ragged-all-to-all"]
    assert row["mbytes_per_step"] is not None
    assert row["time_s_per_step"] is not None
    assert row["algbw_gbps"] is not None      # the join happened
    assert row["busbw_gbps"] == pytest.approx(
        row["algbw_gbps"] * 3 / 4)            # (n-1)/n like a2a


# ------------------------------------------------------ straggler detector
def _stamps(step, n=8, slow=None, skew=0.4, uniform=1.0):
    return {i: float(step) * uniform
            + (skew if i == slow else 0.0) for i in range(n)}


def test_straggler_flags_the_right_device():
    det = C.StragglerDetector(k=4.0, confirm=3, clear=3, min_skew_s=1e-3)
    edges = []
    for step in range(8):
        edges += det.observe(step, _stamps(step,
                                           slow=5 if step >= 2 else None))
    opens = [e for e in edges if e[0] == "open"]
    assert len(opens) == 1 and opens[0][1] == 5
    assert det.burning == {5}
    assert det.episodes == 1


def test_straggler_uniform_slowdown_never_flags():
    det = C.StragglerDetector(k=4.0, confirm=2)
    for step in range(12):
        # every device slows down together 5x at step 6 — relative skew
        # within the step is unchanged, so nothing may flag
        factor = 5.0 if step >= 6 else 1.0
        assert det.observe(step, _stamps(step, uniform=factor)) == []
    assert det.episodes == 0 and not det.burning


def test_straggler_recovers_after_heal():
    det = C.StragglerDetector(k=4.0, confirm=2, clear=3)
    edges = []
    for step in range(20):
        slow = 2 if 3 <= step < 8 else None
        edges += det.observe(step, _stamps(step, slow=slow))
    kinds = [(e[0], e[1]) for e in edges]
    assert kinds == [("open", 2), ("close", 2)]
    assert not det.burning and det.episodes == 1


def test_straggler_needs_a_quorum():
    det = C.StragglerDetector(k=4.0, confirm=1)
    # 1 and 2 stamps: the median IS a sample — detection must stay inert
    assert det.observe(0, {0: 5.0}) == []
    assert det.observe(1, {0: 0.0, 1: 99.0}) == []
    assert det.episodes == 0


class _FakeFlight:
    def __init__(self):
        self.notes = []

    def note(self, name, **meta):
        self.notes.append((name, meta))


def test_flight_marker_exactly_once_per_episode():
    from deepspeed_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    fl = _FakeFlight()
    cs = C.CommScope(C.CommScopeConfig(
        enabled=True, straggler_confirm=2, straggler_clear=2),
        registry=reg, flight=fl, clock=TickClock())
    for step in range(30):
        slow = 4 if (3 <= step < 10 or 18 <= step < 24) else None
        cs.observe_stamps(step, _stamps(step, slow=slow))
    marks = [n for n, _ in fl.notes if n == "straggler"]
    assert len(marks) == 2, fl.notes       # two episodes, two markers
    assert cs.detector.episodes == 2
    snap = reg.snapshot()
    assert snap["counters"]["Train/straggler_episodes"] == 2
    assert snap["gauges"]["Train/straggler_active"] == 0.0  # healed
    # per-device skew gauges exist for the doctor table
    assert "Train/straggler_skew_s_d4" in snap["gauges"]
    # the marker names the device and the skew
    assert fl.notes[0][1]["device"] == "4"
    assert fl.notes[0][1]["skew_s"] == pytest.approx(0.4, abs=0.05)


# ----------------------------------------------------------- trace parsing
def _fake_trace(device="/device:TPU:0"):
    return {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": device}},
        {"ph": "M", "name": "process_name", "pid": 8,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 40000.0,
         "name": "fusion.1"},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 35000.0, "dur": 20000.0,
         "name": "all-reduce.1"},
        {"ph": "X", "pid": 8, "tid": 1, "ts": 0.0, "dur": 90000.0,
         "name": "$python host stuff"},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 50000.0, "dur": 20000.0,
         "name": "fusion.2"},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 80000.0, "dur": 10000.0,
         "name": "reduce-scatter.3"},
    ]}


def test_parse_trace_filters_host_and_converts_units():
    tl = C.parse_trace_events(_fake_trace())
    assert list(tl) == ["/device:TPU:0"]     # host pid dropped
    ops = tl["/device:TPU:0"]
    assert len(ops) == 4
    assert ops[0].t0 == pytest.approx(0.0)
    assert ops[0].t1 == pytest.approx(0.040)  # us → s
    kinds = {o.name: o.kind for o in ops}
    assert kinds["all-reduce.1"] == "all-reduce"
    assert kinds["fusion.1"] is None


def test_load_trace_gz_roundtrip(tmp_path):
    p = tmp_path / "t.trace.json.gz"
    p.write_bytes(gzip.compress(json.dumps(_fake_trace()).encode()))
    tr = C.load_trace(p)
    assert tr is not None and len(C.parse_trace_events(tr)) == 1
    # profiler-layout dir discovery
    d = tmp_path / "logdir" / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.trace.json.gz").write_bytes(
        gzip.compress(json.dumps(_fake_trace()).encode()))
    assert C.load_trace(tmp_path / "logdir") is not None
    assert C.load_trace(tmp_path / "absent") is None


def test_analyze_degrades_to_nulls_never_raises(tmp_path):
    cs = C.CommScope(C.CommScopeConfig(enabled=True), clock=TickClock())
    for src in ({}, {"traceEvents": []}, str(tmp_path / "missing")):
        rep = cs.analyze(src)
        assert rep["anatomy"]["exposed_comm_frac"] is None
        assert rep["ledger"]["by_kind"] == {}


def test_rebase_anchors_to_the_traced_window():
    """Comm spans must land on the TRACED steps' host windows: steps
    stamped before the TraceWindow opened must not drag the anchor
    earlier (the export would overlay comm ops on the wrong steps)."""
    ring = S.SpanRecorder(64, clock=TickClock())
    cs = C.CommScope(C.CommScopeConfig(enabled=True), spans=ring,
                     clock=TickClock())
    cs.on_step(0, 10.0, 10.5)                  # pre-window step
    cs.on_step(1, 11.0, 11.5, traced=True)     # first traced step
    cs.on_step(2, 12.0, 12.5, traced=True)
    cs.analyze(_fake_trace(), windows=[(0.0, 0.1)])
    comm = [e for e in ring.events() if e.kind == S.COMM_OP]
    assert comm, "comm spans expected"
    # the capture's first op (profiler t=0) maps to the traced window's
    # start (11.0), not the pre-window step's 10.0
    assert min(e.t0 for e in comm) >= 11.0


def test_analyze_emits_comm_gauges_and_spans():
    from deepspeed_tpu.observability.export import (to_chrome_trace,
                                                    validate_chrome_trace)
    from deepspeed_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    ring = S.SpanRecorder(256, clock=TickClock())
    cs = C.CommScope(C.CommScopeConfig(enabled=True), registry=reg,
                     spans=ring, n_devices=8, clock=TickClock())
    cs.set_collective_bytes({"all-reduce": {"count": 1, "mbytes": 10.0},
                             "reduce-scatter": {"count": 1, "mbytes": 4.0}})
    rep = cs.analyze(_fake_trace(), windows=[(0.0, 0.1)],
                     peak_ici_gbps=300.0)
    assert rep["anatomy"]["exposed_comm_frac"] == pytest.approx(0.2)
    g = reg.snapshot()["gauges"]
    assert g["Comm/exposed_frac"] == pytest.approx(0.2)
    assert g["Comm/overlap_frac"] == pytest.approx(1 - 2 / 3)
    assert "Comm/all-reduce/busbw_gbps" in g
    # the ring carries comm_op + comm_exposed spans → the comm tracks
    kinds = [e.kind for e in ring.events()]
    assert S.COMM_OP in kinds and S.COMM_EXPOSED in kinds
    trace = to_chrome_trace(ring.events())
    assert validate_chrome_trace(trace) == []
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "comm" in names and "comm-exposed" in names


# ------------------------------------------------------------ capacity tie
def test_capacity_lever_uses_measured_exposed_fraction():
    from deepspeed_tpu.observability.capacity import (
        LEVER_COLLECTIVES, capacity_report, validate_capacity_report)

    ledger = {k: None for k in (
        "weights_bytes", "weights_stream_bytes_per_step", "kv_bytes",
        "kv_per_slot_bytes", "kv_per_token_bytes", "cache_itemsize",
        "temp_bytes", "total_bytes", "limit_bytes", "headroom_bytes",
        "projected_max_slots", "projected_max_context", "kv_page_size",
        "kv_pool_pages", "kv_page_bytes", "kv_quant_bits",
        "kv_pool_used_pages", "kv_pool_free_pages", "kv_scale_bytes",
        "slots", "max_len")}
    cs_report = {
        "anatomy": {"exposed_comm_frac": 0.31, "overlap_frac": 0.5,
                    "exposed_collective_s": 0.12},
        "ledger": {"by_kind": {"all-reduce": {"busbw_gbps": 41.0,
                                              "roofline_ratio": 0.14}}},
    }
    rep = capacity_report(ledger=ledger, commscope=cs_report)
    assert validate_capacity_report(rep) == []
    assert rep["commscope"] is cs_report
    lever = next(lv for lv in rep["advisor"]["levers"]
                 if lv["name"] == LEVER_COLLECTIVES)
    assert lever["score"] == pytest.approx(0.31)
    assert "MEASURED" in lever["why"]
    assert lever["estimate"]["measured"]["achieved_busbw_gbps"][
        "all-reduce"] == 41.0
    # without a commscope report the lever keeps its projection stance
    rep2 = capacity_report(ledger=ledger)
    lever2 = next(lv for lv in rep2["advisor"]["levers"]
                  if lv["name"] == LEVER_COLLECTIVES)
    assert lever2["score"] == 0.0
    assert "MEASURED" not in lever2["why"]


# ------------------------------------------------------------- doctor gate
def test_doctor_comm_gate(tmp_path, capsys):
    from deepspeed_tpu.observability import doctor

    prom = tmp_path / "m.prom"
    prom.write_text("dstpu_comm_exposed_frac 0.3\n"
                    "dstpu_train_straggler_active 1\n"
                    "dstpu_train_straggler_device 3\n"
                    "dstpu_train_straggler_skew_s_d3 0.4\n")
    assert doctor.main(["--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "[comm]" in out and "STRAGGLER burning" in out
    assert "device 3" in out
    assert doctor.main(["--dir", str(tmp_path), "--no-gate"]) == 0
    prom.write_text("dstpu_comm_exposed_frac 0.3\n"
                    "dstpu_train_straggler_active 0\n")
    assert doctor.main(["--dir", str(tmp_path)]) == 0


# ----------------------------------------------------------- config + engine
def test_commscope_config_validation():
    with pytest.raises(ValueError, match="unknown commscope"):
        C.CommScopeConfig.from_any({"enabled": True, "typo_knob": 1})
    with pytest.raises(ValueError, match="straggler_mad_k"):
        C.CommScopeConfig(straggler_mad_k=-1)
    with pytest.raises(ValueError, match="straggler_confirm"):
        C.CommScopeConfig(straggler_confirm=0)
    assert C.CommScopeConfig.from_any(None) is None
    cfg = C.CommScopeConfig.from_any({"enabled": True,
                                      "straggler_mad_k": 2.0})
    assert cfg.enabled and cfg.straggler_mad_k == 2.0


def test_engine_commscope_off_by_default():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    import jax

    eng = ds.initialize({
        "train_batch_size": 2 * len(jax.devices()),
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    }, build_model(tiny_test(max_seq=16)))
    assert eng.commscope is None
    assert eng.observe_device_stamps(0, {0: 1.0, 1: 1.0, 2: 1.0}) == []
    with pytest.raises(RuntimeError, match="commscope is not enabled"):
        eng.comm_observatory()
    eng.close()


def test_engine_observatory_on_adds_no_program_and_moves_no_loss(tmp_path):
    """A sharded training engine with the observatory, spans and a
    profiler window on takes the same number of train-step programs as one
    without, and its losses are bit-identical. The CPU capture has no
    device timeline: ``comm_observatory()`` answers with a null anatomy, not
    a raise, while the ledger still carries the compiled step's collective
    bytes."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  random_token_dataset)

    def run(observability):
        eng = ds.initialize({
            "train_batch_size": 8, "seed": 0,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": 4, "model": 2},
            "observability": observability,
        }, build_model(tiny_test(max_seq=32)))
        data = random_token_dataset(8, seq_len=32, vocab_size=256)
        batch = DataLoader(data, local_batch_size=8,
                           shuffle=False).collate_fn(data)
        return eng, [float(eng.train_batch(batch)["loss"])
                     for _ in range(5)]

    on, losses_on = run({"commscope": {"enabled": True}, "spans": True,
                         "trace_steps": [1, 3],
                         "trace_dir": str(tmp_path)})
    off, losses_off = run({})
    try:
        assert losses_on == losses_off
        assert on._train_step._cache_size() == \
            off._train_step._cache_size()
        rep = on.comm_observatory()
        assert rep["anatomy"]["exposed_comm_frac"] is None
        rows = rep["ledger"]["by_kind"]
        assert rows and all(r["mbytes_per_step"] > 0 and
                            r["busbw_gbps"] is None for r in rows.values())
    finally:
        on.close()
        off.close()

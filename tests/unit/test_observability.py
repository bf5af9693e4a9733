"""Observability layer: metrics core, request tracing, sinks, engine wiring.

Oracles:
- reservoir percentiles are exact nearest-rank over a known window;
- TTFT/TPOT/MBU accounting reproduces hand-computed numbers from a fake
  clock's phase times;
- the JSONL and Prometheus sinks emit files that parse back to the events
  written (machine-readable is the whole point — assert by parsing);
- ``InferenceEngine.metrics_snapshot()`` on the CPU smoke path returns
  TTFT / per-token-latency percentiles / tokens/s / decode MBU, and the
  traced two-program path generates bit-identical tokens to the fused
  zero-sync path;
- one train step + one generate() with ALL sinks enabled produces
  well-formed output (the tier-1 smoke for the whole subsystem).
"""

import json
import math

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.observability import (CompileStormDetector, FlightRecorder,
                                         JsonlSink, MedianMADDetector,
                                         MetricsRegistry,
                                         PrometheusTextfileSink,
                                         RequestLogSink, RequestTracer,
                                         Reservoir, SLOConfig, SLOScorer,
                                         SpanRecorder, TraceWindow,
                                         newest_flight_record,
                                         parse_prometheus_textfile,
                                         prometheus_name, read_flight_record,
                                         merge_fleet_trace, sample_memory,
                                         to_chrome_trace,
                                         validate_chrome_trace)
from deepspeed_tpu.observability import spans as spans_mod
from deepspeed_tpu.models import build_model, tiny_test


# ------------------------------------------------------------- metrics core
def test_reservoir_percentiles_exact():
    r = Reservoir(size=200)
    for v in range(1, 101):          # 1..100, well under capacity
        r.add(v)
    assert r.percentile(50) == 50
    assert r.percentile(90) == 90
    assert r.percentile(99) == 99
    assert r.percentile(100) == 100
    ps = r.percentiles((50, 90, 99))
    assert ps == {"p50": 50, "p90": 90, "p99": 99}


def test_reservoir_rolls_window():
    r = Reservoir(size=10)
    for v in range(100):             # only 90..99 survive
        r.add(v)
    assert len(r) == 10
    assert min(r.values()) == 90
    # nearest-rank p50 over [90..99]: ceil(0.5 * 10) = 5th sorted value
    assert r.percentile(50) == 94


def test_reservoir_empty_and_bad_size():
    assert math.isnan(Reservoir(4).percentile(50))
    with pytest.raises(ValueError):
        Reservoir(0)


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("requests").inc()
    reg.counter("requests").inc(2)
    reg.gauge("loss").set(1.5)
    h = reg.histogram("lat_s")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["requests"] == 3
    assert snap["gauges"]["loss"] == 1.5
    assert snap["histograms"]["lat_s"]["count"] == 3
    assert snap["histograms"]["lat_s"]["p50"] == pytest.approx(0.2)
    assert snap["histograms"]["lat_s"]["mean"] == pytest.approx(0.2)
    # same-name accessors return the same object (no silent forking)
    assert reg.histogram("lat_s") is h


def test_registry_thread_safe_increments():
    import threading

    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("v")

    def work():
        for i in range(1000):
            c.inc()
            h.observe(float(i))

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 4000       # no lost read-modify-writes
    assert snap["histograms"]["v"]["count"] == 4000


def test_registry_to_events_drops_nans():
    reg = MetricsRegistry()
    reg.gauge("good").set(1.0)
    reg.gauge("touched_nan").set(float("nan"))
    reg.histogram("empty")           # created but never observed
    events = reg.to_events(step=7)
    names = [e[0] for e in events]
    assert ("good", 1.0, 7) in events
    assert "touched_nan" not in names
    assert not any(n.startswith("empty/p") for n in names)
    # histogram count=0 is a legitimate (non-NaN) value
    assert ("empty/count", 0, 7) in events


# --------------------------------------------------------- request tracing
class FakeClock:
    """Deterministic clock: each call returns the next scripted instant."""

    def __init__(self, *ticks):
        self.ticks = list(ticks)

    def __call__(self):
        return self.ticks.pop(0)


def test_tracer_ttft_tpot_accounting_fake_clock():
    t = RequestTracer(ring_size=8, bytes_per_step=1_000_000_000,
                      peak_bw=100e9, clock=FakeClock())
    # 4 new tokens: prefill 10 ms, decode 3 steps in 30 ms → TPOT 10 ms
    rec = t.observe(batch=2, prompt_len=16, new_tokens=4,
                    prefill_s=0.010, decode_s=0.030)
    assert rec.tpot_s == pytest.approx(0.010)
    assert rec.prefill_s == pytest.approx(0.010)
    # tokens/s: 2 * 4 tokens / 40 ms
    assert rec.tokens_per_sec == pytest.approx(200.0)
    # 1 GB per step / 10 ms = 100 GB/s achieved = exactly the 100 GB/s peak
    assert rec.achieved_gbps == pytest.approx(100.0)
    assert rec.mbu == pytest.approx(1.0)
    snap = t.snapshot()
    assert snap["requests"] == 1
    assert snap["ttft_s"]["p50"] == pytest.approx(0.010)
    assert snap["tpot_s"]["p99"] == pytest.approx(0.010)
    assert snap["decode_mbu"] == pytest.approx(1.0)


def test_tracer_cold_requests_kept_out_of_percentiles():
    t = RequestTracer(ring_size=8)
    t.observe(batch=1, prompt_len=8, new_tokens=4, prefill_s=30.0,
              decode_s=30.0, cold=True)           # compile included: huge
    t.observe(batch=1, prompt_len=8, new_tokens=4, prefill_s=0.01,
              decode_s=0.03)
    snap = t.snapshot()
    assert snap["requests"] == 2 and snap["cold_starts"] == 1
    assert snap["ttft_s"]["count"] == 1           # only the warm one
    assert snap["ttft_s"]["p99"] == pytest.approx(0.01)
    # but the ring keeps the cold record for forensics
    assert [r["cold"] for r in snap["recent"]] == [True, False]


def test_tracer_single_token_request_has_no_tpot():
    t = RequestTracer()
    rec = t.observe(batch=1, prompt_len=8, new_tokens=1, prefill_s=0.01,
                    decode_s=0.0)
    assert rec.tpot_s is None and rec.mbu is None
    assert t.snapshot()["tpot_s"] == {}           # histogram never created


# ------------------------------------------------------------------- sinks
def test_jsonl_sink_parseable(tmp_path):
    sink = JsonlSink({"output_path": str(tmp_path), "job_name": "job",
                      "flush_every": 1})
    sink.write_events([("Train/loss", 1.25, 3), ("Serve/ttft_s/p50", 0.01, 3)])
    sink.write_events([("Train/loss", 1.20, 4)])
    sink.close()
    lines = (tmp_path / "job.jsonl").read_text().splitlines()
    recs = [json.loads(ln) for ln in lines]
    assert len(recs) == 3
    assert recs[0] == {"name": "Train/loss", "value": 1.25, "step": 3,
                       "time": recs[0]["time"]}
    assert recs[0]["time"] > 0
    assert recs[2]["value"] == 1.20 and recs[2]["step"] == 4


def test_prometheus_sink_latest_value_wins(tmp_path):
    sink = PrometheusTextfileSink({"output_path": str(tmp_path),
                                   "job_name": "job"})
    sink.write_events([("Train/loss", 2.0, 1), ("Serve/decode_mbu", 0.5, 1)])
    sink.write_events([("Train/loss", 1.0, 2)])   # supersedes
    sink.close()
    parsed = parse_prometheus_textfile((tmp_path / "job.prom").read_text())
    assert parsed["dstpu_train_loss"] == 1.0
    assert parsed["dstpu_serve_decode_mbu"] == 0.5
    text = (tmp_path / "job.prom").read_text()
    assert "# TYPE dstpu_train_loss gauge" in text


def test_prometheus_name_sanitization():
    assert prometheus_name("Serve/ttft_s/p99") == "dstpu_serve_ttft_s_p99"
    assert prometheus_name("Comm/all-reduce@model/mbytes") == \
        "dstpu_comm_all_reduce_model_mbytes"


def test_monitor_master_all_sinks_flush_close(tmp_path):
    from deepspeed_tpu.config import Config
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    cfg = Config(**{"monitor": {
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path / "csv")},
        "jsonl": {"enabled": True, "output_path": str(tmp_path)},
        "prometheus": {"enabled": True, "output_path": str(tmp_path)},
    }}).monitor
    assert cfg.any_enabled()
    mon = MonitorMaster(cfg)
    assert len(mon.writers) == 3
    mon.write_events([("Train/loss", 3.0, 1)])
    mon.write_events([("Train/loss", 2.5, 2)])
    mon.flush()
    # csv: the handle stays OPEN across events (the satellite fix) …
    csvw = mon.writers[0]
    assert csvw._files and not next(iter(csvw._files.values())).closed
    rows = (tmp_path / "csv" / "Train_loss.csv").read_text().splitlines()
    assert rows[0] == "step,Train/loss" and len(rows) == 3
    mon.close()
    # … and close() really closes everything
    assert not csvw._files
    assert len((tmp_path / "DeepSpeedTpuJob.jsonl").read_text()
               .splitlines()) == 2


# ------------------------------------------------------------- comms ledger
def test_comms_logger_summary_returned_and_exportable():
    import jax.numpy as jnp

    from deepspeed_tpu.comm.comm import CommsLogger

    cl = CommsLogger(enabled=True)
    cl.record("all_reduce", "model", jnp.zeros((4, 4), jnp.float32))
    cl.record("all_reduce", "model", jnp.zeros((4, 4), jnp.float32))
    cl.record("all_gather", "data", jnp.zeros((8,), jnp.float32))
    out = cl.log_summary()                        # satellite: returns dict
    assert out["all_reduce@model"]["count"] == 2
    assert out["all_reduce@model"]["mbytes"] == pytest.approx(2 * 64 / 1e6)
    events = cl.as_monitor_events(step=5)
    assert ("Comm/all_reduce@model/count", 2.0, 5) in events
    assert ("Comm/all_gather@data/mbytes", pytest.approx(32 / 1e6), 5) in \
        [(n, pytest.approx(v), s) for n, v, s in events]
    cl.reset()
    assert cl.log_summary() == {}


# ------------------------------------------------------------- trace window
def test_trace_window_start_stop(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    w = TraceWindow((2, 3), "/tmp/xla_trace_test")
    for step in range(6):
        w.on_step(step)
    assert calls == [("start", "/tmp/xla_trace_test"), ("stop",)]
    assert w.done
    w.on_step(2)                                  # idempotent after close
    assert len(calls) == 2


def test_trace_window_close_mid_window(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    w = TraceWindow((0, 100), "/tmp/xla_trace_test")
    w.on_step(0)
    w.close()                                     # training ended early
    assert calls == ["start", "stop"]
    with pytest.raises(ValueError):
        TraceWindow((5, 2), "/tmp/x")


def test_sample_memory_gauges():
    reg = MetricsRegistry()
    stats = sample_memory(reg)                    # CPU: zeros, but present
    snap = reg.snapshot()["gauges"]
    for key in ("Memory/bytes_in_use", "Memory/peak_bytes_in_use",
                "Memory/bytes_limit"):
        assert key in snap
    assert set(stats) >= {"bytes_in_use", "bytes_limit"}


# ------------------------------------------- inference engine CPU smoke path
def _tiny_engine(**icfg):
    cfg = tiny_test(max_seq=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, ds.init_inference(
        model, params, {"dtype": "float32", **icfg})


def _prompt(B=2, S=8):
    rng = np.random.default_rng(0)
    return np.asarray(rng.integers(0, 256, (B, S)), np.int32)


def test_metrics_snapshot_cpu_smoke_and_parity(monkeypatch):
    # the peak table knows no CPU: name a bandwidth (the documented
    # override) so the MBU plumbing is exercised
    monkeypatch.setenv("DSTPU_PEAK_HBM_BW", "50e9")
    ids = _prompt()
    _, _, fused = _tiny_engine()
    _, _, traced = _tiny_engine(observability=True)
    want = np.asarray(fused.generate(ids, 6, greedy=True))
    got_cold = np.asarray(traced.generate(ids, 6, greedy=True))
    got_warm = np.asarray(traced.generate(ids, 6, greedy=True))
    # the two-program traced path samples the exact same token chain
    np.testing.assert_array_equal(want, got_cold)
    np.testing.assert_array_equal(want, got_warm)

    snap = traced.metrics_snapshot()
    assert snap["tracing"] is True
    assert snap["requests"] == 2 and snap["cold_starts"] == 1
    # acceptance: TTFT, per-token latency p50/p99, tokens/s, decode MBU
    assert snap["ttft_s"]["p50"] > 0 and snap["ttft_s"]["p99"] > 0
    assert snap["tpot_s"]["p50"] > 0 and snap["tpot_s"]["p99"] > 0
    assert snap["tokens_per_sec"] > 0
    assert snap["decode_mbu"] is not None and snap["decode_mbu"] > 0
    assert snap["weight_bytes_per_step"] > 0
    rec = snap["recent"][-1]
    assert rec["batch"] == 2 and rec["prompt_len"] == 8 \
        and rec["new_tokens"] == 6 and not rec["cold"]


def test_disabled_observability_keeps_fused_zero_sync_path():
    ids = _prompt()
    _, _, eng = _tiny_engine()
    out = np.asarray(eng.generate(ids, 4, greedy=True))
    assert out.shape == (2, 4)
    assert eng.tracer is None
    # no split prefill/decode programs were built — generation stayed one
    # fused jit call with no mid-request host sync (the split caches exist
    # for the tracer and the decode_chunk path, but stay empty here)
    assert len(eng._prefill_cache) == 0 and len(eng._decode_cache) == 0
    assert len(eng._gen_cache) == 1
    assert eng.metrics_snapshot() == {"tracing": False, "requests": 0}


def test_quantized_engine_traces_quantized_bytes():
    ids = _prompt()
    _, _, dense = _tiny_engine(observability=True)
    _, _, q8 = _tiny_engine(observability=True, quantize=True, quant_bits=8,
                            quant_group_size=16)
    np.asarray(q8.generate(ids, 4, greedy=True))
    # the MBU denominator reflects int8 streaming, not a bf16 shadow copy
    assert q8.tracer.bytes_per_step < dense.tracer.bytes_per_step


# ------------------------------------------------------- spans + export
from _fake_clock import TickClock    # noqa: E402  (shared test helper)


def test_span_recorder_ring_and_threading():
    import threading

    sp = SpanRecorder(capacity=100, clock=TickClock())
    with pytest.raises(ValueError):
        SpanRecorder(capacity=0)

    def work(k):
        for i in range(200):
            sp.emit(spans_mod.DECODE_STEP, float(i), float(i) + 0.5,
                    step=i, worker=k)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(sp) == 100                 # bounded
    assert sp.emitted == 800              # nothing lost before eviction
    ev = sp.events()[-1]
    assert ev.duration == pytest.approx(0.5)
    m = sp.marker("why", cause="test")
    assert m.instant and m.meta["name"] == "why"


def _lifecycle_ring():
    sp = SpanRecorder(64, clock=TickClock())
    sp.emit(spans_mod.QUEUED, 0.0, 1.0, rid=7)
    sp.emit(spans_mod.PREFILL_CHUNK, 1.0, 1.2, rid=7, chunk=0, size=16,
            final=True)
    sp.emit(spans_mod.PLACED, 1.2, rid=7, slot=3)
    sp.emit(spans_mod.DECODE_STEP, 1.2, 1.3, step=0, slots=1)
    sp.counter(t=1.3, queue_depth=2, occupancy=1)
    sp.emit(spans_mod.DECODE_RESIDENCY, 1.2, 2.0, rid=7, slot=3, tokens=9)
    sp.emit(spans_mod.RETIRED, 2.0, rid=7, slot=3, status="ok", tokens=9)
    sp.marker("slo_ttft_breach", t=2.0, burn=1.5)
    sp.emit(spans_mod.TRAIN_STEP, 0.0, 0.5, step=1)
    sp.emit(spans_mod.TRAIN_PHASE, 0.0, 0.2, step=1, phase="step_dispatch")
    return sp


def test_chrome_trace_export_schema_valid():
    sp = _lifecycle_ring()
    trace = to_chrome_trace(sp.events(), job_name="t")
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    names = [e["name"] for e in evs]
    # slots are tracks: the slot-3 thread is named, request span rides it
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               and e["args"]["name"] == "slot 3" for e in evs)
    assert any(n == "decode rid=7" for n in names)
    # counters became counter tracks
    assert any(e["ph"] == "C" and e["name"] == "queue_depth" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "occupancy" for e in evs)
    # markers are instants; train spans land under the train pid
    assert any(e["ph"] == "i" and "slo_ttft_breach" in e["name"]
               for e in evs)
    from deepspeed_tpu.observability.export import PID_TRAIN

    assert any(e["pid"] == PID_TRAIN and e["name"] == "step_dispatch"
               for e in evs)
    # ts is relative µs, sorted among non-metadata events
    tss = [e["ts"] for e in evs if e["ph"] != "M"]
    assert tss == sorted(tss) and tss[0] == 0.0
    assert json.loads(json.dumps(trace)) == trace      # JSON-serializable


def test_chrome_trace_validator_catches_malformed():
    assert validate_chrome_trace({}) == ["missing or non-list traceEvents"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 2.0, "dur": 1.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 1.0, "dur": 1.0},
    ]}
    assert any("sorted" in p for p in validate_chrome_trace(bad))
    assert any("dur" in p for p in validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "tid": 1,
                          "ts": 0.0}]}))
    assert any("unknown phase" in p for p in validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "Z", "pid": 1, "tid": 1,
                          "ts": 0.0}]}))
    assert any("missing keys" in p for p in validate_chrome_trace(
        {"traceEvents": [{"ph": "i", "ts": 0.0}]}))
    assert any("without matching B" in p for p in validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "E", "pid": 1, "tid": 1,
                          "ts": 0.0}]}))
    assert any("unclosed B" in p for p in validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "B", "pid": 1, "tid": 1,
                          "ts": 0.0}]}))


# ------------------------------------------------------ merged fleet trace
def _replica_ring(rid, t0, clock=None, slot=0):
    """One replica's serving lifecycle for ``rid`` starting at ``t0``."""
    sp = SpanRecorder(64, clock=clock if clock is not None else TickClock())
    sp.emit(spans_mod.QUEUED, t0, t0 + 0.5, rid=rid)
    sp.emit(spans_mod.PREFILL_CHUNK, t0 + 0.5, t0 + 0.8, rid=rid, chunk=0,
            size=16, final=True)
    sp.emit(spans_mod.PLACED, t0 + 0.8, rid=rid, slot=slot)
    sp.emit(spans_mod.DECODE_RESIDENCY, t0 + 0.8, t0 + 2.0, rid=rid,
            slot=slot, tokens=5)
    sp.emit(spans_mod.RETIRED, t0 + 2.0, rid=rid, slot=slot, status="ok",
            tokens=5)
    return sp


def test_merge_fleet_trace_pids_flows_and_naming():
    """The fleet merge: replicas as named pids on ONE time axis, fleet
    ring as the router pid, cross-replica requests stitched into flows
    — and the result passes the validator."""
    # rid 7 prefills on p0 (only QUEUED+PREFILL there), hands off, and
    # decodes on d0; rid 9 lives entirely on d0 (no flow for it)
    p0 = SpanRecorder(64, clock=TickClock())
    p0.emit(spans_mod.QUEUED, 0.0, 0.5, rid=7)
    p0.emit(spans_mod.PREFILL_CHUNK, 0.5, 1.0, rid=7, chunk=0, size=16,
            final=True)
    d0 = _replica_ring(9, t0=0.2)
    d0.emit(spans_mod.DECODE_RESIDENCY, 1.6, 3.0, rid=7, slot=1, tokens=4)
    fleet = SpanRecorder(64, clock=TickClock())
    fleet.emit(spans_mod.ROUTE, 0.0, rid=7, replica="p0")
    fleet.emit(spans_mod.HANDOFF_EXPORT, 1.0, 1.1, rid=7, replica="p0")
    fleet.emit(spans_mod.HANDOFF_PENDING, 1.1, 1.4, rid=7)
    fleet.emit(spans_mod.HANDOFF_IMPORT, 1.4, 1.5, rid=7, replica="d0")
    tr = merge_fleet_trace({"p0": p0.events(), "d0": d0.events()},
                           fleet.events(), job_name="fleet")
    assert validate_chrome_trace(tr) == []
    evs = tr["traceEvents"]
    # multi-pid track naming: every replica is a named process, the
    # fleet ring fronts as the router process
    pnames = {e["pid"]: e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert set(pnames.values()) == {"fleet:router", "fleet:p0",
                                    "fleet:d0"}
    # one shared origin: d0's first event (t0=0.2) is NOT at ts 0
    d0_pid = next(p for p, n in pnames.items() if n == "fleet:d0")
    d0_ts = [e["ts"] for e in evs if e["ph"] == "X"
             and e["pid"] == d0_pid]
    assert min(d0_ts) > 0
    # rid 7 crossed pids -> one flow chain s ... f, id = rid; rid 9
    # stayed on d0 -> no flow
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert flows and {e["id"] for e in flows} == {7}
    seq = [e["ph"] for e in flows]
    assert seq[0] == "s" and seq[-1] == "f" \
        and all(p == "t" for p in seq[1:-1])
    assert len({e["pid"] for e in flows}) >= 2   # the arrow crosses
    # handoff hops render as X slices on the router pid's handoff track
    router_pid = next(p for p, n in pnames.items()
                      if n == "fleet:router")
    hand = [e["name"] for e in evs if e["ph"] == "X"
            and e["pid"] == router_pid]
    assert {"export rid=7", "pending rid=7", "import rid=7"} \
        <= set(hand)
    # slices carry their replica label
    assert all(e["args"].get("replica") == "d0" for e in evs
               if e["ph"] == "X" and e["pid"] == d0_pid)
    json.loads(json.dumps(tr))       # JSON-serializable


def test_merge_fleet_trace_empty_and_single_pid():
    assert merge_fleet_trace({}, None)["traceEvents"] == []
    # one replica, no fleet ring: valid, named, and flow-free
    tr = merge_fleet_trace({"r0": _replica_ring(3, 0.0).events()})
    assert validate_chrome_trace(tr) == []
    assert not [e for e in tr["traceEvents"] if e["ph"] in ("s", "t", "f")]


def test_chrome_trace_validator_flow_and_pid_negatives():
    """Satellite: the validator catches the fleet-merge failure modes —
    dangling flow ids and events under an unnamed pid."""
    ok = {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
          "dur": 1.0}
    # dangling flow: s without f
    bad = {"traceEvents": [ok, {"name": "f1", "ph": "s", "id": 7,
                                "pid": 1, "tid": 1, "ts": 0.0}]}
    assert any("dangling flow id 7" in p for p in
               validate_chrome_trace(bad))
    # f/t without a preceding s
    bad = {"traceEvents": [ok, {"name": "f1", "ph": "f", "id": 7,
                                "pid": 1, "tid": 1, "ts": 0.0,
                                "bp": "e"}]}
    assert any("without a preceding s" in p for p in
               validate_chrome_trace(bad))
    # flow event with no id at all
    bad = {"traceEvents": [{"name": "f1", "ph": "s", "pid": 1, "tid": 1,
                            "ts": 0.0}]}
    assert any("without id" in p for p in validate_chrome_trace(bad))
    # complete s->f chain: clean
    good = {"traceEvents": [
        ok,
        {"name": "f1", "ph": "s", "id": 7, "pid": 1, "tid": 1, "ts": 0.0},
        {"name": "f1", "ph": "f", "id": 7, "pid": 1, "tid": 1, "ts": 0.5,
         "bp": "e"}]}
    assert validate_chrome_trace(good) == []
    # unknown pid: only fires when the trace names processes at all
    unnamed = {"traceEvents": [ok]}
    assert validate_chrome_trace(unnamed) == []
    named = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0, "ts": 0.0,
         "args": {"name": "r0"}},
        ok,
        {"name": "b", "ph": "X", "pid": 99, "tid": 1, "ts": 1.0,
         "dur": 0.5}]}
    assert any("unknown pid 99" in p for p in validate_chrome_trace(named))


# ------------------------------------------------------- flight recorder
def test_flight_recorder_dump_and_readback(tmp_path):
    clk = TickClock()
    sp = _lifecycle_ring()
    reg = MetricsRegistry()
    reg.gauge("Serve/queue_depth").set(2.0)
    fr = FlightRecorder(tmp_path, spans=sp,
                        snapshots={"serving": reg.snapshot}, clock=clk,
                        job_name="t")
    fr.note("watchdog_stall", step_s=0.5, threshold_s=0.05)
    fr.on_request({"rid": 7, "status": "ok", "tokens": 9})
    d = fr.dump("watchdog_stall")
    rec = read_flight_record(d)
    assert rec["manifest"]["reason"] == "watchdog_stall"
    assert rec["manifest"]["events"] == len(sp.events())
    assert rec["metrics"]["serving"]["gauges"]["Serve/queue_depth"] == 2.0
    assert rec["requests"] == [{"rid": 7, "status": "ok", "tokens": 9}]
    # the marker went into the SPAN ring (timeline shows the why in place)
    assert any(e["kind"] == "marker"
               and e["meta"]["name"] == "watchdog_stall"
               for e in rec["events"])
    assert validate_chrome_trace(rec["trace"]) == []
    assert newest_flight_record(tmp_path) == d
    assert newest_flight_record(tmp_path / "nope") is None


def test_flight_recorder_dump_cap_and_no_spans(tmp_path):
    fr = FlightRecorder(tmp_path, spans=None, max_dumps=2,
                        clock=TickClock())
    fr.note("manual_marker", k=1)          # lands in the internal ring
    assert fr.dump("a") is not None
    assert fr.dump("b") is not None
    assert fr.dump("c") is None            # capped
    dirs = [p for p in tmp_path.iterdir() if p.is_dir()]
    assert len(dirs) == 2
    rec = read_flight_record(fr.dumps[0])
    assert [e["meta"]["name"] for e in rec["events"]] == ["manual_marker"]
    # a broken snapshot provider degrades to an error entry, not a lost dump
    fr2 = FlightRecorder(tmp_path / "p2", clock=TickClock(),
                         snapshots={"boom": lambda: 1 / 0})
    rec2 = read_flight_record(fr2.dump("x"))
    assert "error" in rec2["metrics"]["boom"]
    # numpy values in a snapshot must not crash the dump (it runs on the
    # failure path): scalars via .item(), ARRAYS via .tolist() — .item()
    # raises on size != 1
    fr3 = FlightRecorder(tmp_path / "p3", clock=TickClock(),
                         snapshots={"dev": lambda: {
                             "per_device": np.array([1.5, 2.5]),
                             "one": np.float32(3.5)}})
    rec3 = read_flight_record(fr3.dump("np"))
    assert rec3["metrics"]["dev"] == {"per_device": [1.5, 2.5], "one": 3.5}
    # an unwritable dump dir (full/read-only disk) loses the dump, NOT the
    # failure path that asked for it: no OSError out of the watchdog /
    # nonfinite halt / SIGTERM handler
    blocker = tmp_path / "blocked"
    blocker.write_text("a file where the dump dir should go")
    fr4 = FlightRecorder(blocker / "sub", clock=TickClock())
    assert fr4.dump("stall") is None
    assert fr4.dumps == []                     # budget not consumed either


# ---------------------------------------------------------- SLO / anomaly
def test_slo_scorer_burn_rates_and_edge_trigger(tmp_path):
    reg = MetricsRegistry()
    fr = FlightRecorder(tmp_path, clock=TickClock())
    cfg = SLOConfig(ttft_p99_s=0.1, tpot_p99_s=0.01, error_rate=0.05)
    scorer = SLOScorer(cfg, reg, flight=fr)
    empty = scorer.score()                 # empty window: NaN burns, no
    assert set(empty) == {"ttft", "tpot", "error"}        # violations
    assert all(math.isnan(v) for v in empty.values())
    assert "Serve/slo_violations" not in reg.snapshot()["counters"]
    for _ in range(20):
        reg.histogram("Serve/ttft_s").observe(0.05)     # within budget
        reg.histogram("Serve/tpot_s").observe(0.02)     # 2x over
    reg.counter("Serve/retired").inc(98)
    reg.counter("Serve/timeout").inc(1)
    reg.counter("Serve/nonfinite").inc(1)
    burns = scorer.score()
    assert burns["ttft"] == pytest.approx(0.5)
    assert burns["tpot"] == pytest.approx(2.0)
    assert burns["error"] == pytest.approx(0.02 / 0.05)
    snap = reg.snapshot()
    assert snap["gauges"]["Serve/slo_tpot_burn"] == pytest.approx(2.0)
    assert snap["counters"]["Serve/slo_violations"] == 1   # tpot only
    scorer.score()                                         # still breached
    assert reg.snapshot()["counters"]["Serve/slo_violations"] == 1
    # the breach left a why-marker for the flight dump
    rec = read_flight_record(fr.dump("t"))
    assert any(e["meta"].get("name") == "slo_tpot_breach"
               for e in rec["events"])
    # error burn is windowed over recent score() passes: once the bad
    # passes age out, healthy traffic brings the rate back to zero —
    # lifetime counters would pin the burn above zero forever
    for _ in range(SLOScorer.ERROR_WINDOW_SCORES):
        reg.counter("Serve/retired").inc(10)
        burns = scorer.score()
    assert burns["error"] == 0.0
    with pytest.raises(ValueError, match="unknown slo"):
        SLOConfig.from_any({"ttft_p99": 1.0})
    with pytest.raises(ValueError, match="error_rate"):
        SLOConfig(error_rate=1.5)


def test_median_mad_detector():
    det = MedianMADDetector(k=6.0, window=32, min_samples=8)
    assert det.enabled
    fired = [det.observe(v) for v in [0.1] * 16]
    assert not any(fired)                      # steady baseline
    assert det.observe(1.0)                    # 10x: regression
    # the outlier did NOT poison the window — the next normal step is fine
    assert not det.observe(0.1)
    assert det.observe(1.0)
    assert det.fired == 2
    med, mad = det.stats()
    assert med == pytest.approx(0.1)
    assert not MedianMADDetector(k=0.0).observe(100.0)     # disabled
    # a PERSISTENT shift is adopted as the new regime instead of firing
    # one marker per step forever
    det = MedianMADDetector(k=6.0, window=32, min_samples=8)
    for v in [0.1] * 16:
        det.observe(v)
    fired = [det.observe(1.0) for _ in range(det.REGIME_SHIFT_FIRES + 16)]
    assert sum(fired) == det.REGIME_SHIFT_FIRES
    assert not any(fired[det.REGIME_SHIFT_FIRES:])
    assert not det.observe(1.0)                # new baseline adopted


def test_compile_storm_detector():
    det = CompileStormDetector(threshold=2, window=8, grace=10)
    # warmup grace: early compiles never fire
    assert det.update(0, 3) == 0 and det.update(5, 6) == 0
    for i in range(10, 20):
        assert det.update(i, 6) == 0           # steady: no new programs
    assert det.update(20, 10) == 4             # 4 new inside the window
    assert det.update(21, 10) == 0             # edge-triggered
    assert det.fired == 1
    assert not CompileStormDetector(threshold=0).enabled
    # warmup compiles just BEFORE the grace boundary must not leak into
    # the first post-grace trailing window as a false storm
    det = CompileStormDetector(threshold=3, window=32, grace=64)
    for i in range(0, 61, 5):
        det.update(i, i // 5)                  # 12 legit warmup compiles
    assert det.update(64, 13) == 0 and det.fired == 0
    assert det.update(70, 13) == 0             # steady after grace
    assert det.update(75, 20) == 7             # a REAL post-grace storm


# ------------------------------------------------ sink satellites (PR 5)
def test_jsonl_sink_rotation(tmp_path):
    sink = JsonlSink({"output_path": str(tmp_path), "job_name": "job",
                      "flush_every": 1, "rotate_mb": 0.0005},   # ~524 bytes
                     clock=lambda: 1.25)
    for step in range(40):
        sink.write_events([("Train/loss", 1.0, step)])
    sink.close()
    rolled = tmp_path / "job.jsonl.1"
    assert rolled.exists() and sink.rotations >= 2
    # every line in both kept generations parses; no torn records (the
    # roll happens at flush boundaries only), and the retained window is
    # the most recent — older generations age out by design (one backup)
    recs = [json.loads(ln) for p in (rolled, tmp_path / "job.jsonl")
            for ln in p.read_text().splitlines()]
    assert 0 < len(recs) < 40
    assert all(r["name"] == "Train/loss" and r["time"] == 1.25
               for r in recs)
    assert [r["step"] for r in recs] == \
        list(range(40 - len(recs), 40))        # contiguous newest window
    assert (tmp_path / "job.jsonl").stat().st_size <= 524 + 60
    # default: no rotation (unbounded append, the pre-satellite behavior)
    sink2 = JsonlSink({"output_path": str(tmp_path), "job_name": "j2",
                       "flush_every": 1})
    for step in range(40):
        sink2.write_events([("Train/loss", 1.0, step)])
    sink2.close()
    assert not (tmp_path / "j2.jsonl.1").exists()
    # flush_every=0 ("rely on close()") must not defeat rotate_mb: the
    # size check triggers the flush-and-roll even when nothing else
    # flushes, so a standalone sink stays bounded
    sink3 = JsonlSink({"output_path": str(tmp_path), "job_name": "j3",
                       "flush_every": 0, "rotate_mb": 0.0005},
                      clock=lambda: 1.25)
    for step in range(40):
        sink3.write_events([("Train/loss", 1.0, step)])
    sink3.close()
    assert (tmp_path / "j3.jsonl.1").exists() and sink3.rotations >= 1
    assert (tmp_path / "j3.jsonl").stat().st_size <= 524 + 60


def test_prometheus_sink_help_lines_and_nonfinite(tmp_path):
    sink = PrometheusTextfileSink({"output_path": str(tmp_path),
                                   "job_name": "job"})
    sink.write_events([("Train/loss", float("nan"), 1),
                       ("Serve/burn", float("inf"), 1),
                       ("Serve/floor", float("-inf"), 1),
                       ("Serve/ok", 0.5, 1)])
    sink.close()
    text = (tmp_path / "job.prom").read_text()
    # exposition format: HELP before TYPE, non-finite spelled exactly
    assert "# HELP dstpu_train_loss" in text
    assert text.index("# HELP dstpu_serve_ok") \
        < text.index("# TYPE dstpu_serve_ok")
    assert "dstpu_train_loss NaN" in text
    assert "dstpu_serve_burn +Inf" in text
    assert "dstpu_serve_floor -Inf" in text
    assert "nan" not in text.split("NaN")[0]   # no lowercase leakage
    parsed = parse_prometheus_textfile(text)   # round-trips
    assert math.isnan(parsed["dstpu_train_loss"])
    assert parsed["dstpu_serve_burn"] == math.inf
    assert parsed["dstpu_serve_floor"] == -math.inf
    assert parsed["dstpu_serve_ok"] == 0.5


def test_serving_stats_queue_wait_histogram():
    from deepspeed_tpu.observability import ServingStats

    clk = TickClock(dt=1.0)
    stats = ServingStats(clock=clk)
    t_submit = stats.on_submit(queue_depth=1)      # t=1
    stats.on_admit(queue_depth=0, submit_t=t_submit)   # t=2: wait 1s
    snap = stats.snapshot()
    assert snap["queue_wait_s"]["count"] == 1
    assert snap["queue_wait_s"]["p50"] == pytest.approx(1.0)
    # admit without submit_t (legacy callers) records no wait sample
    stats.on_admit(queue_depth=0)
    assert stats.snapshot()["queue_wait_s"]["count"] == 1


def test_request_log_sink(tmp_path):
    sink = RequestLogSink({"output_path": str(tmp_path), "job_name": "s",
                           "flush_every": 1})
    sink.write_events([("Serve/x", 1.0, 1)])       # scalar events: dropped
    sink.log_request({"rid": 3, "status": "ok", "tokens": 5})
    sink.close()
    rows = [json.loads(ln) for ln in
            (tmp_path / "s.requests.jsonl").read_text().splitlines()]
    assert rows == [{"rid": 3, "status": "ok", "tokens": 5}]
    # it IS a JsonlSink: rotate_mb bounds the per-request log the same
    # way it bounds the event log ("same config shape" means it)
    sink = RequestLogSink({"output_path": str(tmp_path), "job_name": "r",
                           "flush_every": 1, "rotate_mb": 0.0005})
    for rid in range(40):
        sink.log_request({"rid": rid, "status": "ok", "tokens": 5})
    sink.close()
    assert (tmp_path / "r.requests.jsonl.1").exists()
    assert sink.rotations >= 1
    kept = [json.loads(ln)["rid"]
            for p in (tmp_path / "r.requests.jsonl.1",
                      tmp_path / "r.requests.jsonl")
            for ln in p.read_text().splitlines()]
    assert kept == list(range(40 - len(kept), 40))   # newest window, no tears


# -------------------------------------------- serving spans: cost parity
def test_serving_spans_add_no_programs_and_keep_outputs():
    """Spans enabled = the same compiled-program set and bit-identical
    tokens as spans disabled (the ring is host-side bookkeeping only);
    the ring carries the full lifecycle for the requests served."""
    import jax.numpy as jnp

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params, {"dtype": "float32"})
    scfg = {"slots": 2, "max_len": 48, "prefill_chunk": 16,
            "temperature": 0.8, "top_k": 20}
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (9,)).astype(np.int32)
               for _ in range(4)]
    from deepspeed_tpu.observability import get_registry

    backend = get_registry().counter("Compile/programs")
    b0 = backend.value
    plain = ds.ServingEngine(eng, scfg)
    base = plain.serve_batch(prompts, 6, seeds=list(range(4)))
    b1 = backend.value
    spanned = ds.ServingEngine(eng, {**scfg, "spans": True})
    got = spanned.serve_batch(prompts, 6, seeds=list(range(4)))
    assert spanned.compiles == plain.compiles      # zero new programs
    # nor executables: JAX's own backend events, which the lifecycle ring
    # counts whether or not an engine has a ring of its own
    assert backend.value - b1 == b1 - b0 > 0
    for w, g in zip(base, got):
        np.testing.assert_array_equal(w, g)        # bit-identical tokens
    kinds = {e.kind for e in spanned.spans.events()}
    assert {"queued", "prefill_chunk", "placed", "decode", "retired",
            "decode_step", "occupancy"} <= kinds
    rids = {e.rid for e in spanned.spans.events() if e.rid is not None}
    assert rids == {0, 1, 2, 3}
    trace = to_chrome_trace(spanned.spans.events())
    assert validate_chrome_trace(trace) == []
    loop = [e["name"] for e in trace["traceEvents"]
            if e.get("ph") == "X" and e["tid"] == 5]
    assert {"step", "admit", "decode_readback", "tail"} <= set(loop)
    # off path: no ``spans``, no capture. Twenty more iterations with work
    # in them leave no ring, record nothing for the capture's reader and
    # build no program
    assert plain.spans is None and not spans_mod.TraceAnnotation.is_enabled()
    held = len(spans_mod.captured())
    life, traced = spans_mod._LIFECYCLE.emitted, spans_mod.traces()
    programs, executables = plain.compiles, backend.value
    for p in prompts:
        plain.submit(p, 6)
    for _ in range(20):
        plain.step()
    assert len(spans_mod.captured()) == held
    assert plain.compiles == programs and backend.value == executables
    # the lifecycle ring is always on and has nothing to say of a warm loop
    assert spans_mod._LIFECYCLE.emitted == life
    assert spans_mod.traces() == traced


@pytest.fixture(scope="module")
def tiny_server():
    """engine, prompts: a two-slot server on a counting clock."""
    import jax.numpy as jnp

    model = build_model(tiny_test(max_seq=64, dtype=jnp.float32))
    eng = ds.init_inference(model, model.init(jax.random.PRNGKey(0)),
                            {"dtype": "float32"})
    rng = np.random.default_rng(5)
    # 5 and 9 tokens: one final chunk; 21 and 33: chunks before it
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (5, 21, 9, 33)]
    return eng, prompts


@pytest.fixture(scope="module")
def iteration_spans(tiny_server):
    """The ring of a server that served four requests with ``spans`` on,
    by iteration: {step: (the srv.step span, [its children])}."""
    eng, prompts = tiny_server
    srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                 "prefill_chunk": 16, "spans": True},
                           clock=TickClock())
    srv.serve_batch(prompts, 5, seeds=[1, 2, 3, 4])
    evs = srv.spans.events()
    steps = {e.step: (e, []) for e in evs if e.kind == spans_mod.SRV_STEP}
    for e in evs:
        if e.step is not None and e.t1 is not None \
                and e.kind not in (spans_mod.SRV_STEP, spans_mod.DECODE_STEP):
            steps[e.step][1].append(e)
    return evs, steps


# the plain engine's order (docs/SERVING.md, "The host loop"): the seat goes
# out behind its chunk and the step behind that; then the step BEFORE is
# read back and booked, and last the seat's first token
PHASE_ORDER = ["srv.deadlines", "srv.admit", "prefill_chunk", "srv.place",
               "srv.decode_dispatch", "srv.admit.ahead",
               "prefill_chunk.ahead", "srv.decode_readback", "srv.retire",
               "srv.prefill_readback", "srv.tail"]


def _phase(e):
    """An admission and a chunk dispatched behind the decode step for the
    next iteration (``_lane_dispatch(ahead=1)``) are phases of their
    own."""
    return e.kind + (".ahead" if e.meta.get("ahead") else "")


@pytest.mark.parametrize("check", [
    "children_inside_in_order", "self_time", "readback_on_final_chunks",
    "decode_step_is_the_parent_of_the_decode_pair", "readers_kinds_and_meta",
    "submit_carries_the_rid"])
def test_serving_iteration_spans(iteration_spans, check):
    """One serving iteration through the seam: ``srv.step`` and its
    phases, disjoint, in the order the loop runs them, each carrying the
    iteration that caused it."""
    evs, steps = iteration_spans
    assert len(steps) >= 8
    if check == "children_inside_in_order":
        for step, (parent, kids) in steps.items():
            assert kids, step
            kinds = [_phase(k) for k in kids]
            assert kinds == sorted(kinds, key=PHASE_ORDER.index), kinds
            assert len(set(kinds)) == len(kinds)
            edges = [parent.t0] + [t for k in kids for t in (k.t0, k.t1)] \
                + [parent.t1]
            assert edges == sorted(edges), (step, kinds)
            assert all(k.step == step for k in kids)
    elif check == "self_time":
        for parent, kids in steps.values():
            own = parent.duration - sum(k.duration for k in kids)
            assert own >= 0
            # the counting clock: two reads a span, one more between
            # neighbours, so the loop's own time is never nothing
            assert own >= 0.001 * (len(kids) + 1) - 1e-9
    elif check == "readback_on_final_chunks":
        # a final chunk is read back in the iteration it belongs to: its
        # own, or the one after where it was dispatched ahead
        final_before = False
        for step in sorted(steps):
            kids = steps[step][1]
            chunk = [k for k in kids if _phase(k) == "prefill_chunk"]
            ahead = [k for k in kids if _phase(k) == "prefill_chunk.ahead"]
            read = [k for k in kids if k.kind == "srv.prefill_readback"]
            assert len(chunk) <= 1 and len(ahead) <= 1
            assert not (chunk and final_before)
            assert bool(read) == bool(
                final_before or (chunk and chunk[0].meta["final"]))
            final_before = bool(ahead and ahead[0].meta["final"])
        assert any(_phase(e) == "prefill_chunk.ahead" for e in evs)
        finals = [e.meta["final"] for e in evs if e.kind == "prefill_chunk"]
        assert finals.count(True) == 4 and finals.count(False) == 3
    elif check == "decode_step_is_the_parent_of_the_decode_pair":
        # a step is dispatched in one iteration and read back in the
        # next: its span runs from the read-back before it (from its own
        # dispatch where nothing was in flight) to its own, so the spans
        # tile the time the device spent on each
        decode = sorted((e for e in evs if e.kind == "decode_step"),
                        key=lambda e: e.t1)
        reads = sorted((e for e in evs if e.kind == "srv.decode_readback"),
                       key=lambda e: e.t1)
        assert decode and len(decode) == len(reads)
        before = None
        for d, read in zip(decode, reads):
            (dispatch,) = [k for k in steps[d.step][1]
                           if k.kind == "srv.decode_dispatch"]
            assert read.step == d.step + 1 and read.t1 < d.t1
            if d.meta["ahead"]:
                assert d.t0 == before.t1 and dispatch.t1 < before.t1
            else:
                assert d.t0 < dispatch.t0
                assert before is None or before.t1 < d.t0
            assert 0 <= d.meta["slots"] <= 2 and d.meta["queue"] >= 0
            before = d
        assert [d.meta["ahead"] for d in decode].count(1) >= len(decode) - 2
        assert [d.step for d in decode] == sorted(d.step for d in decode)
    elif check == "readers_kinds_and_meta":
        # what export.py, flight.py, capacity.py, _decode_rate and
        # _prefill_rate read, under the names they read it by
        kinds = {e.kind for e in evs}
        assert {"queued", "prefill_chunk", "placed", "decode", "retired",
                "decode_step", "occupancy"} <= kinds
        chunk = next(e for e in evs if e.kind == "prefill_chunk")
        assert {"chunk", "size", "final"} <= set(chunk.meta)
        assert chunk.rid is not None
        occ = next(e for e in evs if e.kind == "occupancy")
        assert set(occ.meta) == {"queue_depth", "occupancy"}
    else:
        subs = [e for e in evs if e.kind == "srv.submit"]
        assert [e.rid for e in subs] == [0, 1, 2, 3]
        assert all(e.step is None for e in subs)


def _host_annotations(trace_dir, prefix):
    from jax.profiler import ProfileData

    (path,) = (trace_dir / "plugins" / "profile").glob("*/*.xplane.pb")
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            mine = [e.name for e in line.events if e.name.startswith(prefix)]
            if mine:
                found.append((plane.name, line.name, mine))
    return found


def test_seam_records_in_a_live_capture_and_only_there(tiny_server,
                                                       tmp_path):
    """With ``spans`` unset a profiler capture switches the seam on: the
    same spans are annotations on the capture's host line, under
    ``ds.<name>``, and events behind ``captured()``; after ``stop_trace``
    neither grows."""
    eng, prompts = tiny_server
    srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                 "prefill_chunk": 16})
    srv.serve_batch(prompts[:1], 3)            # warm, no capture
    assert srv.spans is None
    first = srv._iterations
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.serve_batch(prompts, 4, seeds=[1, 2, 3, 4])
    finally:
        jax.profiler.stop_trace()
    iterations = srv._iterations - first
    got = spans_mod.captured()
    srv.serve_batch(prompts[:2], 3)            # after the capture
    assert len(spans_mod.captured()) == len(got)
    assert srv.spans is None
    timed = [e for e in got if e.kind.startswith("srv.")
             or e.kind == "prefill_chunk"]
    ((plane, line, names),) = _host_annotations(tmp_path, "ds.")
    assert plane == "/host:CPU" and line.startswith("python")
    assert sorted(names) == sorted(
        "ds.srv.prefill_chunk" if e.kind == "prefill_chunk"
        else "ds." + e.kind for e in timed)
    assert names.count("ds.srv.step") == iterations >= 8
    # the lifecycle and the counts come along, from stamps: ring only
    kinds = {e.kind for e in got}
    assert {"queued", "placed", "decode", "retired", "decode_step",
            "occupancy"} <= kinds
    assert {e.rid for e in got if e.kind == "queued"} == {1, 2, 3, 4}


@pytest.mark.parametrize("second, counted", [("same", 0), ("other", 1)])
def test_retraces_counts_new_signatures_of_a_built_program(tiny_server,
                                                           second, counted):
    """``Serve/retraces``: a built program called with another argument
    signature traces again inside its ``jax.jit`` (``compiles`` cannot
    see that); the same signature again does not."""
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    eng, _ = tiny_server
    srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                 "prefill_chunk": 16, "spans": True})
    everywhere = get_registry().counter("Serve/retraces")
    before, built = everywhere.value, srv.compiles
    prog = srv._prog("probe", lambda: jax.jit(lambda x: x + 1))
    prog(jnp.zeros(3, jnp.float32))
    prog(jnp.zeros(3, jnp.float32 if second == "same" else jnp.int32))
    srv.step()
    assert srv.compiles == built + 1
    assert everywhere.value - before == counted
    mine = srv.stats.registry.snapshot()["counters"].get(
        "Serve/retraces", 0)
    assert mine == counted
    marks = [e.meta for e in srv.spans.events() if e.kind == "retrace"]
    assert [m["program"] for m in marks] == ["probe"] * counted


# ------------------------------------------------- the lifecycle ring
def _compiles_of(program):
    return [e for e in spans_mod.lifecycle()
            if e.kind == "compile" and e.meta["program"] == program]


def _life_mark():
    """Events the lifecycle ring has ever taken: its length stops growing
    once a long test process has filled it."""
    return spans_mod._LIFECYCLE.emitted


def _life_since(mark):
    n = _life_mark() - mark
    return spans_mod.lifecycle()[-n:] if n else []


def test_compile_spans_lie_on_perf_counter():
    """A fresh ``jax.jit`` leaves its trace, its lowering and its backend
    compile in ``lifecycle()`` under the module's name, with ``spans``
    unset and no capture; JAX stamps them with ``time.time()`` and they
    come out on ``time.perf_counter()``, inside a bracket of it taken
    around the call."""
    import time

    import jax.numpy as jnp

    def lifecycle_probe(x):
        return jnp.tanh(x) * 3 + 1      # jnp.tanh: a trace inside the trace

    assert not spans_mod.TraceAnnotation.is_enabled()
    x = jnp.ones(7)
    reg = ds.observability.get_registry().snapshot()["counters"]
    traced = spans_mod.traces()
    a = time.perf_counter()
    jax.jit(lifecycle_probe)(x)
    b = time.perf_counter()
    mine = _compiles_of("jit_lifecycle_probe")
    # one span a stage: what the function traces for its own callees is
    # inside its trace, not beside it
    assert [e.meta["stage"] for e in mine] == ["trace", "lower", "backend"]
    assert spans_mod.traces() > traced
    stamps = [t for e in mine for t in (e.t0, e.t1)]
    assert stamps == sorted(stamps) and a <= stamps[0] and stamps[-1] <= b
    now = ds.observability.get_registry().snapshot()["counters"]
    assert now["Compile/programs"] - reg["Compile/programs"] == 1
    lowered = sum(e.duration for e in mine[:2])
    assert now["Compile/trace_lower_s"] - reg["Compile/trace_lower_s"] \
        == pytest.approx(lowered)
    assert now["Compile/backend_s"] - reg["Compile/backend_s"] \
        == pytest.approx(mine[2].duration)
    # the same call again is no event
    held = _life_mark()
    jax.jit(lifecycle_probe)(x)
    assert _life_mark() == held


@pytest.mark.parametrize("said, meta", [
    ("hit", {"cache_hit": True, "retrieval_s": 0.25}),
    ("miss", {"cache_hit": False}),
    ("nothing", {})])
def test_what_the_cache_said_lands_on_the_backend_span(said, meta):
    """A hit, a miss and the retrieval's seconds fire inside a backend
    event with no name of their own: they go onto that span, and a miss
    onto ``Compile/cache_misses``."""
    import time

    from jax import monitoring

    backend = "/jax/core/compile/backend_compile_duration"
    misses = ds.observability.get_registry().counter("Compile/cache_misses")
    m0 = misses.value
    t = time.time()
    monitoring.record_scalar(backend, t, fun_name="jit(cache_probe)")
    if said == "hit":
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    elif said == "miss":
        monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event_time_span(backend, t, t + 0.5,
                                      fun_name="jit(cache_probe)")
    ev = _compiles_of("jit_cache_probe")[-1]
    assert ev.meta == {"program": "jit_cache_probe", "stage": "backend",
                       **meta}
    assert ev.duration == pytest.approx(0.5)
    assert misses.value - m0 == (said == "miss")


def test_a_retrace_is_in_the_lifecycle_ring_and_a_warm_loop_walks_nothing(
        tiny_server):
    """With ``spans`` unset a second signature of a built program leaves a
    RETRACE in ``lifecycle()`` that says what it cost, and moves
    ``Serve/retraces`` by one. After that, 50 iterations with work in them
    append nothing and never look at a program's cache: the walk waits for
    the process's trace count to move."""
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    eng, prompts = tiny_server
    srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 48,
                                 "prefill_chunk": 16})
    assert srv.spans is None
    for _ in range(2):                   # every program and signature met
        srv.serve_batch(prompts, 4, seeds=[1, 2, 3, 4])
    counter = get_registry().counter("Serve/retraces")
    before = counter.value

    def retrace_probe(x):
        return x + 1

    prog = srv._prog("probe", lambda: jax.jit(retrace_probe))
    prog(jnp.zeros(3, jnp.float32))
    srv.step()
    prog(jnp.zeros(3, jnp.int32))
    srv.step()
    assert counter.value - before == 1
    (mark,) = [e for e in spans_mod.lifecycle() if e.kind == "retrace"
               and e.meta["module"] == "jit_retrace_probe"]
    assert mark.meta["program"] == "probe" and mark.meta["new"] == 1
    assert mark.meta["signatures"] == 2
    # another type: traced, lowered and compiled again, each with seconds
    assert [w.split()[0] for w in mark.meta["why"].split(", ")] \
        == ["trace", "lower", "backend"]

    class Watched:
        looked = 0

        def _cache_size(self):
            Watched.looked += 1
            return 1

    srv._programs["watched"] = Watched()
    held, traced = _life_mark(), spans_mod.traces()
    for p in prompts:
        srv.submit(p, 12)
    for _ in range(50):
        srv.step()
    assert srv.sched.idle
    assert spans_mod.traces() == traced
    assert _life_mark() == held and Watched.looked == 0
    # a trace anywhere in the process and the next iteration looks again
    jax.jit(lambda x: x - 1)(jnp.zeros(2))
    srv.step()
    assert Watched.looked == 1


def test_an_init_span_covers_the_compiles_of_its_engine_s_build(tiny_server):
    """``ServingEngine.__init__`` is an INIT span of phase ``serving``, and
    the ``init_slots`` program it builds lies inside it by time, on the one
    clock both are stamped with, fake engine clock or not."""
    eng, _ = tiny_server
    ticks = iter(range(10 ** 6))
    held = _life_mark()
    ds.ServingEngine(eng, {"slots": 5, "max_len": 40, "prefill_chunk": 8},
                     clock=lambda: float(next(ticks)))
    new = _life_since(held)
    (init,) = [e for e in new if e.kind == "init"]
    assert init.meta == {"phase": "serving"} and init.duration > 0
    inside = [e for e in new if e.kind == "compile"]
    assert {e.meta["stage"] for e in inside} == {"trace", "lower", "backend"}
    assert all(init.t0 <= e.t0 and e.t1 <= init.t1 for e in inside)
    assert next(ticks) == 0              # the engine's clock was not read
    # an operator reads it through the exporter every ring goes through:
    # the build and, nested in it, its programs' stages, on one track
    trace = to_chrome_trace(new)
    assert validate_chrome_trace(trace) == []
    track = [(e["name"], e["ts"], e["ts"] + e["dur"])
             for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert track[0][0] == "init.serving"
    assert {n.split(":")[0] for n, _, _ in track[1:]} \
        == {"trace", "lower", "backend"}
    # Perfetto's microseconds are rounded to a thousandth
    assert all(track[0][1] <= a and b <= track[0][2] + 2e-3
               for _, a, b in track[1:])
    # and an inference engine's build is one of phase ``inference``
    held = _life_mark()
    ds.init_inference(eng.model, eng.model.init(jax.random.PRNGKey(1)),
                      {"dtype": "float32"})
    assert [e.meta["phase"] for e in _life_since(held)
            if e.kind == "init"] == ["inference"]


def test_the_lifecycle_ring_is_bounded(monkeypatch):
    small = SpanRecorder(capacity=8)
    monkeypatch.setattr(spans_mod, "_LIFECYCLE", small)
    for i in range(20):
        spans_mod.emit(None, spans_mod.INIT, float(i), i + 0.5, phase=str(i))
    assert [e.meta["phase"] for e in spans_mod.lifecycle()] \
        == [str(i) for i in range(12, 20)]
    assert small.emitted == 20 and spans_mod._LIFECYCLE.capacity == 8
    # a kind that is not the process's stays out
    spans_mod.emit(None, spans_mod.QUEUED, 0.0, 1.0, rid=1)
    assert len(spans_mod.lifecycle()) == 8 and small.emitted == 20


# ----------------------------------------------------------- doctor CLI
def test_doctor_cli_reports_from_files(tmp_path, capsys):
    """The triage CLI reads files alone: latest .prom, request log, and
    newest flight record — no engine, no device."""
    from deepspeed_tpu.observability import doctor

    sink = PrometheusTextfileSink({"output_path": str(tmp_path),
                                   "job_name": "job"})
    sink.write_events([("Serve/goodput_tps", 123.0, 9),
                       ("Serve/slo_ttft_burn", float("inf"), 9)])
    sink.close()
    rlog = RequestLogSink({"output_path": str(tmp_path), "job_name": "job",
                           "flush_every": 1})
    rlog.log_request({"rid": 1, "status": "ok", "tokens": 5,
                      "ttft_s": 0.01, "queue_wait_s": 0.002})
    rlog.log_request({"rid": 2, "status": "timeout", "tokens": 1,
                      "ttft_s": None, "queue_wait_s": None,
                      "error": "ttft deadline expired in queue"})
    rlog.close()
    fr = FlightRecorder(tmp_path, spans=_lifecycle_ring(),
                        clock=TickClock())
    fr.note("watchdog_stall", step_s=0.7)
    fr.dump("watchdog_stall")
    # a burning SLO gauge + a why-marker in the record: the gate trips
    # (nonzero exit, so CI/cron can alert on this command), --no-gate
    # restores report-only
    assert doctor.main(["--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "dstpu_serve_goodput_tps" in out and "123" in out
    assert "+Inf" in out
    assert "ok=1" in out and "timeout=1" in out
    assert "rid=2" in out and "ttft deadline expired" in out
    assert "reason=watchdog_stall" in out
    assert "marker" in out and "slowest spans" in out
    assert "perfetto" in out
    assert "[gate]" in out and "slo_ttft_burn" in out
    assert "why-marker" in out and "watchdog_stall" in out
    assert doctor.main(["--dir", str(tmp_path), "--no-gate"]) == 0
    capsys.readouterr()
    # empty directory: nothing fired, exits 0
    assert doctor.main(["--dir", str(tmp_path / "empty")]) == 0
    out = capsys.readouterr().out
    assert "no *.prom" in out and "no flight_*" in out
    assert "[gate] clean" in out
    # torn artifacts — the state an UNCLEAN death leaves (os._exit mid
    # write, SIGKILL before flush) — must degrade, not crash the triage:
    # a half-written trailing request record and a torn flight events line
    with open(tmp_path / "job.requests.jsonl", "a", encoding="utf-8") as f:
        f.write('{"rid": 3, "status": "o')            # no newline: torn
    fdir = newest_flight_record(tmp_path)
    with open(fdir / "events.jsonl", "a", encoding="utf-8") as f:
        f.write('{"kind": "marker", "t0"')
    assert doctor.main(["--dir", str(tmp_path)]) == 1   # markers still gate
    out = capsys.readouterr().out
    assert "1 torn line(s) skipped" in out
    assert "ok=1" in out                               # intact rows kept
    assert read_flight_record(fdir)["torn_lines"] == 1


# --------------------------------------------------- tier-1 subsystem smoke
def test_train_and_generate_all_sinks_smoke(tmp_path, monkeypatch):
    """One train step + one generate() with every machine-readable sink
    enabled: JSONL parses, the Prometheus textfile parses, CSV has rows,
    and both engines' snapshots are well-formed."""
    monkeypatch.setenv("DSTPU_PEAK_HBM_BW", "50e9")   # no CPU peak in the table
    engine = ds.initialize({
        "train_batch_size": 8,
        "steps_per_print": 1,
        "wall_clock_breakdown": True,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "observability": {"hbm_watermark": True, "spans": True},
        "monitor": {
            "csv_monitor": {"enabled": True,
                            "output_path": str(tmp_path / "csv")},
            "jsonl": {"enabled": True, "output_path": str(tmp_path),
                      "flush_every": 1},
            "prometheus": {"enabled": True, "output_path": str(tmp_path)},
        },
    }, build_model(tiny_test(n_layer=2)))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (8, 32)).astype(np.int32)
    engine.train_batch({"input_ids": ids, "labels": ids})
    engine.close()

    snap = engine.metrics_snapshot()
    assert snap["gauges"]["Train/loss"] > 0
    assert "Train/samples_per_sec" in snap["gauges"]
    assert "Memory/bytes_in_use" in snap["gauges"]
    assert snap["histograms"]["Train/step_time_s"]["count"] == 1

    # training spans: one train_step span + the wall-clock-breakdown
    # timer windows re-emitted as phase spans, export schema-valid
    evs = engine.spans.events()
    assert [e.step for e in evs if e.kind == "train_step"] == [1]
    phases = {e.meta["phase"] for e in evs if e.kind == "train_phase"}
    assert {"batch_prep", "step_dispatch", "step_sync"} <= phases
    assert all(e.duration >= 0 for e in evs)
    assert validate_chrome_trace(to_chrome_trace(evs)) == []

    recs = [json.loads(ln) for ln in
            (tmp_path / "DeepSpeedTpuJob.jsonl").read_text().splitlines()]
    names = {r["name"] for r in recs}
    assert {"Train/loss", "Train/lr", "Train/samples_per_sec",
            "Memory/bytes_in_use"} <= names
    assert all(isinstance(r["value"], float) and r["step"] >= 1
               for r in recs)

    prom = parse_prometheus_textfile(
        (tmp_path / "DeepSpeedTpuJob.prom").read_text())
    assert prom["dstpu_train_loss"] == pytest.approx(
        snap["gauges"]["Train/loss"], rel=1e-6)
    assert "dstpu_train_mfu" in prom or "dstpu_train_tflops" in prom

    assert (tmp_path / "csv" / "Train_loss.csv").exists()

    # serving half of the namespace: record, then export Serve/* through
    # the same sink machinery on the serving loop's cadence
    from deepspeed_tpu.config import Config
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    _, _, eng = _tiny_engine(observability=True)
    np.asarray(eng.generate(_prompt(), 4, greedy=True))
    np.asarray(eng.generate(_prompt(), 4, greedy=True))   # one warm request
    ssnap = eng.metrics_snapshot()
    assert ssnap["requests"] == 2
    json.dumps(ssnap)                 # machine-readable end to end
    mon = MonitorMaster(Config(**{"monitor": {"prometheus": {
        "enabled": True, "output_path": str(tmp_path),
        "job_name": "serve"}}}).monitor)
    wrote = eng.publish_metrics(mon)
    assert wrote > 0
    sprom = parse_prometheus_textfile((tmp_path / "serve.prom").read_text())
    assert sprom["dstpu_serve_requests"] == 2.0
    assert sprom["dstpu_serve_ttft_s_p99"] > 0
    assert "dstpu_serve_decode_mbu" in sprom
    mon.close()
    # untraced engine: publish is a no-op, not an error
    _, _, plain = _tiny_engine()
    assert plain.publish_metrics(mon) == 0


@pytest.mark.parametrize("breakdown", [False, True])
def test_train_step_spans_come_from_the_seam(breakdown):
    """``train_step`` and its parts are recorded whenever a ring records,
    not only under ``wall_clock_breakdown``; the parts lie inside the
    step, in order, numbered as the step that caused them."""
    engine = ds.initialize({
        "train_batch_size": 8, "steps_per_print": 2,
        "wall_clock_breakdown": breakdown,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "observability": {"spans": True},
    }, build_model(tiny_test(n_layer=2)))
    ids = np.random.default_rng(0).integers(0, 256, (8, 32)).astype(np.int32)
    for _ in range(2):
        engine.train_batch({"input_ids": ids, "labels": ids})
    evs = engine.spans.events()
    engine.close()
    steps = [e for e in evs if e.kind == "train_step"]
    assert [e.step for e in steps] == [1, 2]
    for parent in steps:
        parts = [e for e in evs if e.kind == "train_phase"
                 and e.step == parent.step]
        want = ["batch_prep", "step_dispatch"]
        if breakdown or parent.step == 2:      # a sync: asked for, or due
            want.append("step_sync")
        assert [e.meta["phase"] for e in parts] == want
        edges = [parent.t0] + [t for e in parts for t in (e.t0, e.t1)] \
            + [parent.t1]
        assert edges == sorted(edges)

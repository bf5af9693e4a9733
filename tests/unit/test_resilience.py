"""Resilience layer (resilience/ + the guards it proves).

Oracles:
- typed failure taxonomy: QueueFullError (status SHED) on a full queue /
  draining engine, RequestStatus on every terminal request, cancel() from
  queue and slots, deadline expiry under a FAKE clock;
- checkpoint integrity: manifest-written-last commit protocol, load-time
  verification with newest-verified-tag fallback, keep-last-K pruning,
  and the chaos-kill crash between the orbax state write and the
  ``latest`` flip (subprocess — a dead process can't assert in-process);
- simulated SIGTERM preemption: the PreemptionGuard awaits the in-flight
  async save, flips ``latest``, and exits 143 with a loadable checkpoint;
- resume="auto" wires all of the above into engine construction;
- the non-finite sentinel halts a collapsed run with a typed error;
- elastic restart visibility: DSTPU_ELASTIC_RESTART / _LAST_RC land in
  Train/* metrics;
- a live serving engine under injected faults: a poisoned slot retires
  NONFINITE alone while its neighbours' tokens stay bit-identical, a
  submit flood is shed and the rest served, a drain refuses new work and
  an uncollected results store evicts at its cap.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.observability.tracing import ServingStats
from deepspeed_tpu.resilience import (ChaosConfig, chaos, newest_verified_tag,
                                      prune_tags, verify_tag, write_manifest)
from deepspeed_tpu.resilience.guards import (CheckpointIntegrityError,
                                             NonFiniteLossError,
                                             QueueFullError, RequestStatus)
from deepspeed_tpu.serving import Scheduler

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _fake_clock():
    t = {"now": 0.0}

    def clock():
        t["now"] += 1.0
        return t["now"]

    return t, clock


# ------------------------------------------------------------ typed guards
def test_queue_full_is_typed_and_counted():
    t, clock = _fake_clock()
    stats = ServingStats(clock=clock)
    sched = Scheduler(slots=1, max_len=32, prefill_chunk=8, max_queue=2,
                      stats=stats)
    sched.submit(np.arange(3), 2)
    sched.submit(np.arange(3), 2)
    with pytest.raises(QueueFullError) as ei:
        sched.submit(np.arange(3), 2)
    # typed: status + depth ride the exception; RuntimeError compat kept
    assert ei.value.status is RequestStatus.SHED
    assert ei.value.queue_depth == 2 and ei.value.max_queue == 2
    assert isinstance(ei.value, RuntimeError)
    assert stats.snapshot()["shed"] == 1


def test_deadlines_fire_under_fake_clock():
    t, clock = _fake_clock()
    stats = ServingStats(clock=clock)
    sched = Scheduler(slots=1, max_len=64, prefill_chunk=8, stats=stats,
                      ttft_deadline_s=10.0, total_deadline_s=50.0)
    runner = sched.submit(np.arange(4), max_new=8, seed=1)
    waiter = sched.submit(np.arange(4), max_new=8, seed=2)
    # per-request overrides beat the config defaults
    vip = sched.submit(np.arange(4), max_new=4, seed=3,
                       ttft_deadline_s=500.0, total_deadline_s=500.0)
    assert vip.deadline_ttft == pytest.approx(vip.submit_t + 500.0)
    assert vip.deadline_total == pytest.approx(vip.submit_t + 500.0)
    assert sched.pop_next() is runner
    sched.place(runner, first_tok=11)
    assert sched.expire_deadlines(now=t["now"]) == []     # nothing due yet

    expired = sched.expire_deadlines(now=waiter.submit_t + 15.0)
    assert expired == [waiter]                 # TTFT blown while queued
    assert waiter.status is RequestStatus.TIMEOUT and waiter.finished
    assert "ttft" in waiter.error

    expired = sched.expire_deadlines(now=runner.submit_t + 60.0)
    assert expired == [runner]                 # total wall blown mid-decode
    assert runner.status is RequestStatus.TIMEOUT
    assert sched.free == [0]                   # the slot came back
    assert [r.rid for r in sched.queue] == [vip.rid]   # vip survives
    snap = stats.snapshot()
    assert snap["timeout"] == 2 and snap["aborted"] == 2


def test_cancel_from_queue_and_slot():
    t, clock = _fake_clock()
    sched = Scheduler(slots=1, max_len=32, prefill_chunk=8,
                      stats=ServingStats(clock=clock))
    a = sched.submit(np.arange(3), 4, seed=1)
    b = sched.submit(np.arange(3), 4, seed=2)
    sched.pop_next()
    sched.place(a, first_tok=5)
    got = sched.cancel(b.rid)                  # queued
    assert got is b and b.status is RequestStatus.CANCELLED
    got = sched.cancel(a.rid)                  # running: slot must free
    assert got is a and a.status is RequestStatus.CANCELLED
    assert sched.free == [0] and sched.idle
    assert sched.cancel(999) is None           # unknown rid
    # normal retirement still lands status OK
    c = sched.submit(np.arange(3), 1, seed=3)
    sched.pop_next()
    sched.complete_at_prefill(c, first_tok=2)
    assert c.status is RequestStatus.OK and c.ok


# ------------------------------------------------------------------- chaos
def test_chaos_config_validation():
    with pytest.raises(ValueError, match="unknown chaos config"):
        ChaosConfig.from_any({"enabled": True, "nonfinte_step": 3})
    with pytest.raises(ValueError, match="hang_seconds"):
        ChaosConfig(hang_seconds=-1.0)
    cfg = ds.ServingConfig.from_any(
        {"slots": 2, "max_len": 32,
         "chaos": {"enabled": True, "nonfinite_decode_step": 2}})
    assert isinstance(cfg.chaos, ChaosConfig)
    with pytest.raises(ValueError, match="watchdog_s"):
        ds.ServingConfig.from_any({"slots": 2, "max_len": 32,
                                   "watchdog_s": -0.5})


def test_kill_point_parsing(monkeypatch):
    fired = []
    monkeypatch.setattr(chaos.os, "_exit", lambda code: fired.append(code))
    monkeypatch.setattr(chaos, "_kill_hits", {})
    monkeypatch.delenv(chaos.KILL_ENV, raising=False)
    chaos.kill_point("ckpt:after-state-write")          # inert when unset
    assert fired == []
    # point names contain ':' — only a numeric tail is an occurrence index
    monkeypatch.setenv(chaos.KILL_ENV, "ckpt:after-state-write")
    chaos.kill_point("ckpt:before-latest-flip")         # different point
    assert fired == []
    chaos.kill_point("ckpt:after-state-write")
    assert fired == [137]
    monkeypatch.setattr(chaos, "_kill_hits", {})
    monkeypatch.setenv(chaos.KILL_ENV, "ckpt:after-state-write:1")
    chaos.kill_point("ckpt:after-state-write")          # hit 0: survives
    chaos.kill_point("ckpt:after-state-write")          # hit 1: dies
    assert fired == [137, 137]


# ----------------------------------------------------- checkpoint integrity
def _fake_tag(base, name, step, payload=b"0123456789abcdef"):
    tag = base / name
    (tag / "state").mkdir(parents=True)
    (tag / "state" / "leaf0").write_bytes(payload)
    (tag / "state" / "leaf1").write_bytes(payload * 2)
    (tag / "meta.json").write_text(json.dumps({"global_steps": step}))
    return tag


def test_manifest_roundtrip_and_verification(tmp_path):
    tag = _fake_tag(tmp_path, "global_step3", 3)
    assert verify_tag(tag, "size")[0] == "legacy"      # no manifest yet
    mf = write_manifest(tag, "checksum")
    assert set(mf["files"]) == {"state/leaf0", "state/leaf1"}
    assert verify_tag(tag, "checksum") == ("verified", "")
    # torn write: size mismatch caught at "size" already
    (tag / "state" / "leaf1").write_bytes(b"short")
    status, reason = verify_tag(tag, "size")
    assert status == "corrupt" and "leaf1" in reason
    # bit rot at unchanged size: only "checksum" catches it
    (tag / "state" / "leaf0").write_bytes(b"X123456789abcdef")
    assert verify_tag(tag, "size")[0] == "corrupt"      # leaf1 still torn
    (tag / "state" / "leaf1").write_bytes(b"0123456789abcdef" * 2)
    assert verify_tag(tag, "size")[0] == "verified"
    assert verify_tag(tag, "checksum")[0] == "corrupt"
    # missing file
    (tag / "state" / "leaf0").unlink()
    status, reason = verify_tag(tag, "size")
    assert status == "corrupt" and "missing" in reason
    assert verify_tag(tag, "off")[0] == "verified"      # trust mode


def test_newest_verified_fallback_and_prune(tmp_path):
    for i in (1, 2, 3, 4):
        write_manifest(_fake_tag(tmp_path, f"global_step{i}", i), "size")
    # corrupt the newest → fallback picks the next one down
    (tmp_path / "global_step4" / "state" / "leaf0").write_bytes(b"xx")
    assert newest_verified_tag(tmp_path, "size") == "global_step3"
    assert newest_verified_tag(tmp_path, "size",
                               exclude={"global_step3"}) == "global_step2"
    # a manifest-less tag is most likely a save that died mid-state-write:
    # the fallback scan must skip it (accept_legacy opts back in)
    _fake_tag(tmp_path, "global_step9", 9)
    assert newest_verified_tag(tmp_path, "size") == "global_step3"
    assert newest_verified_tag(tmp_path, "size",
                               accept_legacy=True) == "global_step9"
    import shutil
    shutil.rmtree(tmp_path / "global_step9")
    deleted = prune_tags(tmp_path, keep_last=2, protect={"global_step1"})
    # keeps the newest 2 plus anything protected
    assert deleted == ["global_step2"]
    assert sorted(d.name for d in tmp_path.iterdir() if d.is_dir()) == \
        ["global_step1", "global_step3", "global_step4"]
    assert prune_tags(tmp_path, keep_last=0) == []      # 0 = disabled


# --------------------------------------------- engine-level (one tiny build)
@pytest.fixture(scope="module")
def train_engine():
    """ONE tiny training engine for the in-process resilience tests (init
    compile only — train_batch is never called, keeping tier-1 cheap).
    Built under elastic-agent env vars so _post_init's restart plumbing is
    covered by the same build."""
    from deepspeed_tpu.models import build_model, tiny_test

    os.environ["DSTPU_ELASTIC_RESTART"] = "2"
    os.environ["DSTPU_ELASTIC_LAST_RC"] = "17"
    try:
        eng = ds.initialize({
            "train_batch_size": 8,     # divisible by the suite's virtual
                                       # 8-device mesh AND a single device
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "checkpoint": {"verify": "checksum", "keep_last": 2},
            "seed": 3,
        }, build_model(tiny_test()))
    finally:
        del os.environ["DSTPU_ELASTIC_RESTART"]
        del os.environ["DSTPU_ELASTIC_LAST_RC"]
    return eng


def test_elastic_restarts_in_registry(train_engine):
    """Satellite: incarnation index + last exit cause are Train/* metrics,
    so the Prometheus textfile shows them from the first report boundary."""
    snap = train_engine.metrics.snapshot()
    assert snap["counters"]["Train/restarts"] == 2
    assert snap["gauges"]["Train/last_exit_code"] == 17.0
    names = [n for n, _, _ in train_engine.metrics.to_events(step=0)]
    assert "Train/restarts" in names and "Train/last_exit_code" in names


def test_save_load_verified_fallback_and_prune(tmp_path, train_engine):
    """End-to-end commit protocol on a real engine: manifests written
    last, keep_last pruning, corrupt-tag fallback on load, and the
    refusal to silently substitute an explicitly pinned tag."""
    eng = train_engine
    for step in (1, 2, 3):
        eng.global_steps = step
        eng.save_checkpoint(tmp_path)
    tags = sorted(d.name for d in tmp_path.iterdir() if d.is_dir())
    assert tags == ["global_step2", "global_step3"]      # keep_last=2
    for t in tags:
        assert verify_tag(tmp_path / t, "checksum")[0] == "verified"
    # corrupt the tag 'latest' names: truncate one state file
    for p in sorted((tmp_path / "global_step3" / "state").rglob("*")):
        if p.is_file() and p.stat().st_size > 8:
            p.write_bytes(p.read_bytes()[:-4])
            break
    eng.load_checkpoint(tmp_path)            # falls back, loudly
    assert eng.global_steps == 2
    with pytest.raises(CheckpointIntegrityError) as ei:
        eng.load_checkpoint(tmp_path, tag="global_step3")
    assert ei.value.tag == "global_step3" and ei.value.reason


def test_nonfinite_sentinel_halts(train_engine):
    """K consecutive bad steps raise the typed halt; any good step resets
    the streak. (The counting windows — exact on offload, per report
    boundary in-device — are exercised through _note_bad_steps, the one
    hook both paths call.)"""
    eng = train_engine
    prev = eng._max_bad_steps, eng._bad_step_streak
    try:
        eng._max_bad_steps, eng._bad_step_streak = 4, 0
        eng._note_bad_steps(True, 2, float("nan"))
        eng._note_bad_steps(False, 2, 1.5)               # reset
        assert eng._bad_step_streak == 0
        eng._note_bad_steps(True, 2, float("nan"))
        with pytest.raises(NonFiniteLossError) as ei:
            eng._note_bad_steps(True, 2, float("inf"))
        assert ei.value.streak == 4
        assert math.isinf(ei.value.last_loss)
        # the boundary hook: a finite loss with no skips is not bad
        eng._bad_step_streak = 0
        eng._max_bad_steps = 1000
        eng._sentinel_at_boundary(1.25)
        assert eng._bad_step_streak == 0
        eng._sentinel_at_boundary(float("nan"))
        assert eng._bad_step_streak == int(eng.config.steps_per_print)
    finally:
        eng._max_bad_steps, eng._bad_step_streak = prev


def test_resume_auto_requires_dir():
    from deepspeed_tpu.models import build_model, tiny_test

    with pytest.raises(ValueError, match="resume_dir"):
        ds.initialize({
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "resilience": {"resume": "auto"},
        }, build_model(tiny_test()))


# --------------------------------------------------- crash / preempt (e2e)
_CKPT_SCRIPT = """
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test

phase, ckpt = sys.argv[1], sys.argv[2]
engine = ds.initialize({
    "train_batch_size": 8,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    "checkpoint": {"verify": "checksum", "async_save": phase == "preempt"},
    "resilience": {"resume": "auto", "resume_dir": ckpt},
    "observability": {"flight_dir": os.path.join(ckpt, "flight")},
    "seed": 3,
}, build_model(tiny_test()))
print(f"PHASE={phase} resumed_step={engine.global_steps}", flush=True)
if phase == "crash":
    engine.global_steps = 1
    engine.save_checkpoint(ckpt)        # commits clean: manifest + latest
    engine.global_steps = 2
    os.environ["DSTPU_CHAOS_KILL"] = "ckpt:after-state-write"
    engine.save_checkpoint(ckpt)        # dies between state write and flip
    print("UNREACHABLE", flush=True)
elif phase == "preempt":
    assert engine.global_steps == 1, engine.global_steps
    guard = ds.PreemptionGuard(engine).install()
    engine.global_steps = 5
    engine.save_checkpoint(ckpt)        # async: commit in flight
    from deepspeed_tpu.resilience import chaos
    chaos.deliver_preemption()          # SIGTERM -> guard commits, exits 143
    print("UNREACHABLE", flush=True)
elif phase == "verify":
    assert engine.global_steps == 5, engine.global_steps
    print("VERIFY_OK", flush=True)
"""


def test_crash_mid_commit_then_preempt_then_resume(tmp_path):
    """The checkpoint chaos chain, each phase its own process:

    1. *crash*: save step1 clean, then chaos-kill between the orbax state
       write and the ``latest`` flip of step2 → rc 137, step2 left
       WITHOUT a commit marker, ``latest`` still naming step1;
    2. *preempt*: auto-resume must land on step1 (the previous VERIFIED
       tag); an async save of step5 is mid-flight when chaos delivers
       SIGTERM — the PreemptionGuard awaits the commit, writes the
       manifest, flips ``latest``, exits 143;
    3. *verify*: auto-resume loads the preemption checkpoint (step5).
    """
    script = tmp_path / "ckpt_chaos.py"
    script.write_text(_CKPT_SCRIPT)
    ckpt = tmp_path / "ckpt"
    # (the suite's persistent compile cache comes with os.environ: the
    # three phases build the same tiny init program)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("DSTPU_CHAOS_KILL", None)
    env.pop("DSTPU_CHAOS_PREEMPT", None)

    def run(phase):
        return subprocess.run(
            [sys.executable, str(script), phase, str(ckpt)],
            env=env, capture_output=True, text=True, timeout=300)

    p = run("crash")
    assert p.returncode == 137, (p.stdout[-2000:], p.stderr[-2000:])
    assert "kill_point 'ckpt:after-state-write'" in p.stderr, p.stderr
    assert "UNREACHABLE" not in p.stdout
    assert (ckpt / "latest").read_text().strip() == "global_step1"
    assert (ckpt / "global_step2" / "state").exists()
    assert verify_tag(ckpt / "global_step2", "checksum")[0] == "legacy"

    p = run("preempt")
    assert p.returncode == 143, (p.stdout[-2000:], p.stderr[-2000:])
    assert "PHASE=preempt resumed_step=1" in p.stdout, p.stdout
    assert "UNREACHABLE" not in p.stdout
    assert (ckpt / "latest").read_text().strip() == "global_step5"
    assert verify_tag(ckpt / "global_step5", "checksum")[0] == "verified"
    # the PreemptionGuard left the black box next to the checkpoint
    from deepspeed_tpu.observability import (newest_flight_record,
                                             read_flight_record)

    fdir = newest_flight_record(ckpt / "flight")
    assert fdir is not None and fdir.name.endswith("preemption")
    frec = read_flight_record(fdir)
    assert frec["manifest"]["reason"] == "preemption"
    assert any(e["meta"].get("name") == "preemption_sigterm"
               for e in frec["events"])

    p = run("verify")
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert "VERIFY_OK" in p.stdout


# --------------------------------------- flight recorder (PR 5 tentpole)
from _fake_clock import TickClock    # noqa: E402  (shared test helper)


def test_chaos_hung_step_produces_flight_record(tmp_path):
    """The acceptance chain, fully fake-clocked: submit → chaos-hung step
    → watchdog → flight dump → the exported Perfetto timeline is
    schema-valid and SHOWS the stall gap (a decode_step span as long as
    the injected hang, plus the watchdog why-marker)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.observability import (newest_flight_record,
                                             read_flight_record,
                                             validate_chrome_trace)

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": 7})
    clk = TickClock()
    hang_s = 0.5
    srv = ds.ServingEngine(eng, {
        "slots": 2, "max_len": 48, "prefill_chunk": 16,
        "temperature": 0.8, "top_k": 20,
        "spans": True, "flight_dir": str(tmp_path / "flight"),
        "watchdog_s": 0.05,
        "chaos": {"enabled": True, "seed": 1, "hang_iteration": 3,
                  "hang_seconds": hang_s},
    }, clock=clk)
    # fake time end-to-end: the chaos hang advances the SAME clock the
    # watchdog and the spans read — no real sleeping, no wall-clock race
    srv.chaos.sleep = clk.advance
    rng = np.random.default_rng(0)
    for i in range(4):
        srv.submit(rng.integers(0, 256, (9,)).astype(np.int32), 6,
                   seed=100 + i)
    srv.drain()
    assert [i for i in srv.chaos.injected if i["point"] == "hang"]
    snap = srv.metrics_snapshot()
    assert snap["watchdog_stalls"] >= 1 and snap["retired"] == 4

    d = newest_flight_record(tmp_path / "flight")
    assert d is not None, "watchdog stall did not dump a flight record"
    rec = read_flight_record(d)
    assert rec["manifest"]["reason"] == "watchdog_stall"
    # the why-marker carries the measured stall
    stall_markers = [e for e in rec["events"] if e["kind"] == "marker"
                     and e["meta"].get("name") == "watchdog_stall"]
    assert stall_markers and \
        stall_markers[0]["meta"]["step_s"] >= hang_s
    # the export is schema-valid Perfetto input…
    assert validate_chrome_trace(rec["trace"]) == []
    # …and the timeline shows the stall gap: one decode_step span at
    # least as long as the injected hang (µs in the trace)
    step_spans = [e for e in rec["trace"]["traceEvents"]
                  if e.get("name") == "decode_step"]
    assert step_spans, "no decode_step spans in the exported timeline"
    assert max(e["dur"] for e in step_spans) >= hang_s * 1e6
    # the engine ring kept serving after the dump: full lifecycle present
    kinds = {e.kind for e in srv.spans.events()}
    assert {"queued", "prefill_chunk", "placed", "decode", "retired",
            "decode_step", "occupancy", "marker"} <= kinds


def test_watchdog_stall_storm_dumps_once_per_episode(tmp_path):
    """A stall STORM (threshold set below every step's duration) takes ONE
    flight dump for the whole episode — per-iteration dumps would burn the
    max_dumps budget the terminal post-mortem (SIGTERM, nonfinite halt)
    needs, and pay dump I/O inside an already-stalling loop. Every stall
    still writes its why-marker and bumps the stall counter."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": 7})
    clk = TickClock()
    srv = ds.ServingEngine(eng, {
        "slots": 2, "max_len": 48, "prefill_chunk": 16,
        "spans": True, "flight_dir": str(tmp_path / "flight"),
        # below one TickClock dt: EVERY decode step "stalls"
        "watchdog_s": 1e-5,
    }, clock=clk)
    rng = np.random.default_rng(0)
    for i in range(3):
        srv.submit(rng.integers(0, 256, (9,)).astype(np.int32), 6,
                   seed=100 + i)
    srv.drain()
    snap = srv.metrics_snapshot()
    assert snap["watchdog_stalls"] > 1          # a real storm…
    assert len(srv.flight.dumps) == 1           # …one dump (rising edge)


def test_nonfinite_halt_dumps_flight_record(tmp_path, train_engine):
    """The training sentinel's halt freezes the black box before raising
    (wired in _note_bad_steps) — the dump names the collapse."""
    from deepspeed_tpu.observability import (FlightRecorder,
                                             read_flight_record)

    eng = train_engine
    prev = eng._max_bad_steps, eng._bad_step_streak, eng.flight
    try:
        eng._max_bad_steps, eng._bad_step_streak = 2, 0
        eng.flight = FlightRecorder(tmp_path, spans=eng.spans,
                                    snapshots={"train": eng.metrics_snapshot})
        with pytest.raises(NonFiniteLossError):
            eng._note_bad_steps(True, 2, float("nan"))
        assert len(eng.flight.dumps) == 1
        rec = read_flight_record(eng.flight.dumps[0])
        assert rec["manifest"]["reason"] == "nonfinite_halt"
        halt = [e for e in rec["events"]
                if e["meta"].get("name") == "nonfinite_halt"]
        assert halt and halt[0]["meta"]["streak"] == 2
        assert "train" in rec["metrics"]
    finally:
        eng._max_bad_steps, eng._bad_step_streak, eng.flight = prev


def test_serving_request_log_and_flight_requests(tmp_path):
    """attach_monitor wires the MonitorMaster request-log sink: every
    retired request lands as one JSON record (status + timing attribution
    included), and the flight recorder keeps the recent ones."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.config import Config
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": 7})
    mon = MonitorMaster(Config(**{"monitor": {
        "request_log": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "serve", "flush_every": 1},
        "prometheus": {"enabled": True, "output_path": str(tmp_path),
                       "job_name": "serve"},
    }}).monitor)
    srv = ds.ServingEngine(eng, {
        "slots": 2, "max_len": 48, "prefill_chunk": 16,
        "temperature": 0.8, "top_k": 20,
        "flight_dir": str(tmp_path / "flight"),
        "slo": {"ttft_p99_s": 1e-9},       # impossibly tight: must burn
    }, clock=TickClock())
    srv.attach_monitor(mon)
    rng = np.random.default_rng(1)
    rids = [srv.submit(rng.integers(0, 256, (9,)).astype(np.int32), 5,
                       seed=i) for i in range(3)]
    srv.drain()
    srv.publish_metrics(mon)               # scores SLO + flushes sinks
    mon.close()
    rows = [json.loads(ln) for ln in
            (tmp_path / "serve.requests.jsonl").read_text().splitlines()]
    assert sorted(r["rid"] for r in rows) == sorted(rids)
    for r in rows:
        assert r["status"] == "ok" and r["tokens"] == 5
        assert r["ttft_s"] > 0 and r["queue_wait_s"] is not None
    # SLO burn gauges rode the same flush into the textfile
    from deepspeed_tpu.observability import parse_prometheus_textfile

    prom = parse_prometheus_textfile(
        (tmp_path / "serve.prom").read_text())
    assert prom["dstpu_serve_slo_ttft_burn"] > 1.0
    assert prom["dstpu_serve_slo_violations"] == 1.0
    assert prom["dstpu_serve_queue_wait_s_p50"] > 0
    # the flight black box kept the same records
    d = srv.dump_flight("unit")
    from deepspeed_tpu.observability import read_flight_record

    assert len(read_flight_record(d)["requests"]) == 3


# ------------------------------------------- guards on a live engine
def test_serving_guards_react_to_injected_faults():
    """Three faults through the chaos harness into a live engine, each
    held to the guard's exact reaction. NaN logits in one occupied slot at
    one decode step: exactly that request retires NONFINITE, cut at the
    poisoned step, and every other request's tokens are bit-identical to
    a clean run. Sixteen submits slammed into a queue of four: the
    overflow is shed (typed, counted) and everything admitted is served.
    A drain: new submits are refused and readiness drops while the
    backlog finishes, and a results store nobody collects evicts at its
    cap and counts it."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import build_model, tiny_test

    model = build_model(tiny_test(max_seq=64, dtype=jnp.float32))
    eng = ds.init_inference(model, model.init(jax.random.PRNGKey(0)),
                            {"dtype": "float32", "eos_token_id": 7})
    scfg = {"slots": 3, "max_len": 48, "prefill_chunk": 16,
            "temperature": 0.8, "top_k": 20}
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 256, (int(rng.choice([5, 9, 16, 23])),))
             .astype(np.int32), int(rng.integers(4, 12)), 500 + i)
            for i in range(8)]

    def run(srv):
        rids = [srv.submit(p, n, seed=s) for p, n, s in reqs]
        srv.drain()
        return [srv.results[r] for r in rids]

    clean = run(ds.ServingEngine(eng, scfg))
    chaotic = ds.ServingEngine(eng, {**scfg, "chaos": {
        "enabled": True, "seed": 1, "nonfinite_decode_step": 5}})
    out = run(chaotic)
    assert chaotic.chaos.injected
    poisoned = [i for i, r in enumerate(out)
                if r.status is RequestStatus.NONFINITE]
    assert len(poisoned) == 1
    for i, (got, want) in enumerate(zip(out, clean)):
        if i in poisoned:
            assert len(got.tokens) < len(want.tokens)
        assert got.tokens == want.tokens[:len(got.tokens)]
    assert chaotic.metrics_snapshot()["nonfinite"] == 1

    flooded = ds.ServingEngine(eng, {**scfg, "max_queue": 4, "chaos": {
        "enabled": True, "seed": 2, "flood_submits": 16}})
    flooded.step()                 # iteration 0 floods through chaos
    assert flooded.metrics_snapshot()["shed"] >= 10
    assert flooded.sched.queue_depth <= 4
    flooded.drain()
    snap = flooded.metrics_snapshot()
    assert snap["retired"] == snap["admitted"] > 0

    srv = ds.ServingEngine(eng, scfg)
    srv._max_results = 2
    for p, n, s in reqs[:5]:
        srv.submit(p, n, seed=s)
    srv.begin_drain()
    with pytest.raises(QueueFullError):
        srv.submit(reqs[0][0], 2, seed=9)
    assert not srv.health()["ready"]
    srv.drain()
    snap = srv.metrics_snapshot()
    assert snap["retired"] == 5 and snap["results_evicted"] >= 3
    assert len(srv.results) <= 2

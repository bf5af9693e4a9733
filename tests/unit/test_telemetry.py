"""Live telemetry & control plane (observability/server.py), goodput
ledger (observability/goodput.py), fleet aggregator (fleet_scrape.py),
and the shared exposition formatter (expfmt.py).

Oracles:
- byte-compat: the Prometheus textfile sink and ``GET /metrics`` render
  IDENTICAL bytes for the same registry events (shared expfmt renderer,
  regression-pinned here);
- probe contract: /readyz answers 503 while draining, 200 otherwise;
  control POSTs are token-gated (403 without/with the wrong token);
- goodput invariant: productive + badput buckets == wall time (exact on
  the fake clock; the chaos hung-step's excess lands in the stall
  bucket, the cold engine's compile window in the compile bucket);
- fleet degradation: a dead target becomes ``dstpu_scrape_up 0``, never
  an exception, and drops out of the weighted rollups;
- no program added: telemetry and goodput on compile what the plain
  engine compiles, and a live scrape equals the sink's file.
"""

import json
import math
import os
import urllib.request
from urllib.error import HTTPError, URLError

import numpy as np
import pytest
from _fake_clock import TickClock

from deepspeed_tpu.observability.expfmt import (exposition_from_events,
                                                parse_prometheus_textfile,
                                                render_exposition)
from deepspeed_tpu.observability.fleet_scrape import (FleetScraper,
                                                      engine_label)
from deepspeed_tpu.observability.goodput import (BADPUT_BUCKETS,
                                                 GoodputLedger)
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.server import (TelemetryConfig,
                                                TelemetryHooks,
                                                TelemetryServer)
from deepspeed_tpu.observability.sinks import PrometheusTextfileSink

EOS = 7


def _req(url, method="GET", data=None, token=None, timeout=5.0):
    """(status, content_type, body) — 4xx/5xx return their status
    instead of raising."""
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
            return (int(resp.status), resp.headers.get("Content-Type", ""),
                    resp.read().decode())
    except HTTPError as e:
        return int(e.code), e.headers.get("Content-Type", ""), \
            e.read().decode()


# ------------------------------------------------------- expfmt byte-compat
def _demo_registry():
    reg = MetricsRegistry()
    reg.counter("Serve/retired").inc(3)
    reg.gauge("Serve/goodput_tps").set(12.5)
    reg.gauge("Serve/weird name!").set(float("inf"))
    h = reg.histogram("Serve/ttft_s")
    for v in (0.1, 0.2, 0.4):
        h.observe(v)
    return reg


def test_sink_and_exposition_are_byte_identical(tmp_path):
    """The satellite regression pin: one renderer, two transports."""
    reg = _demo_registry()
    events = reg.to_events(17)
    sink = PrometheusTextfileSink({"output_path": str(tmp_path),
                                   "job_name": "t"})
    sink.write_events(events)
    sink.flush()
    file_text = (tmp_path / "t.prom").read_text()
    assert file_text == exposition_from_events(events)
    # and the existing parse helper round-trips both
    a = parse_prometheus_textfile(file_text)
    b = parse_prometheus_textfile(exposition_from_events(events))
    assert a == b and a["dstpu_serve_retired"] == 3.0
    assert a["dstpu_serve_weird_name"] == float("inf")
    assert a["dstpu_step"] == 17.0


def test_render_exposition_step_first_and_sorted():
    text = render_exposition({"dstpu_b": 2.0, "dstpu_a": 1.0},
                             step=5, prefix="dstpu")
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
    assert lines == ["dstpu_step 5", "dstpu_a 1", "dstpu_b 2"]


def test_parse_keeps_labeled_series_distinct():
    text = ('dstpu_scrape_up{engine="a"} 1\n'
            'dstpu_scrape_up{engine="b"} 0\n'
            "dstpu_fleet_up 1\n")
    p = parse_prometheus_textfile(text)
    assert p['dstpu_scrape_up{engine="a"}'] == 1.0
    assert p['dstpu_scrape_up{engine="b"}'] == 0.0
    assert p["dstpu_fleet_up"] == 1.0


def test_telemetry_config_validation():
    assert TelemetryConfig.from_any(None) is None
    c = TelemetryConfig.from_any({"enabled": True, "port": 0})
    assert c.host == "127.0.0.1" and not c.token
    with pytest.raises(ValueError, match="unknown telemetry"):
        TelemetryConfig.from_any({"prot": 99})
    with pytest.raises(ValueError, match="port"):
        TelemetryConfig.from_any({"port": 70000})


# ------------------------------------------------- server over fake hooks
@pytest.fixture()
def fake_server():
    """Ephemeral-port server over plain-Python hooks — every endpoint
    exercised without a device or an engine."""
    reg = _demo_registry()
    state = {"ready": True, "drained": [], "dumps": 0}

    def drain(end):
        state["drained"].append(end)
        state["ready"] = bool(end)
        return {"draining": not end}

    def dump():
        state["dumps"] += 1
        return "/tmp/flight_x" if state["dumps"] < 3 else None

    hooks = TelemetryHooks(
        registry=reg, step_fn=lambda: 9,
        health_fn=lambda: {"ready": state["ready"], "state": "serving"},
        requests_fn=lambda: [{"rid": 0, "state": "queued"}],
        goodput_fn=lambda: {"wall_s": 1.0, "productive_s": 0.9},
        drain_fn=drain, dump_fn=dump,
        slo_reload_fn=lambda cfg: {"reloaded": True, "got": cfg})
    srv = TelemetryServer(hooks, port=0, token="s3cret")
    srv.start()
    try:
        yield srv, state
    finally:
        srv.close()


def test_endpoints_status_codes_and_content_types(fake_server):
    srv, state = fake_server
    u = srv.url
    code, ctype, body = _req(u + "/metrics")
    assert code == 200 and ctype.startswith("text/plain")
    assert "version=0.0.4" in ctype
    assert parse_prometheus_textfile(body)["dstpu_step"] == 9.0
    code, ctype, body = _req(u + "/healthz")
    assert code == 200 and ctype.startswith("application/json")
    assert json.loads(body)["alive"] is True
    code, _, body = _req(u + "/readyz")
    assert code == 200 and json.loads(body)["ready"] is True
    code, _, body = _req(u + "/requests")
    assert code == 200 and json.loads(body)["in_flight"] == 1
    code, _, body = _req(u + "/goodput")
    assert code == 200 and json.loads(body)["wall_s"] == 1.0
    code, _, _ = _req(u + "/capacity")        # hook absent -> clean 404
    assert code == 404
    code, _, _ = _req(u + "/flight")
    assert code == 404
    code, _, _ = _req(u + "/nope")
    assert code == 404
    code, _, body = _req(u + "/")             # index lists live endpoints
    assert code == 200 and "/metrics" in json.loads(body)["endpoints"]


def test_readyz_flips_503_and_post_token_gating(fake_server):
    srv, state = fake_server
    u = srv.url
    # control POST without a token: 403, nothing executed
    code, _, _ = _req(u + "/drain", method="POST", data={})
    assert code == 403 and state["drained"] == []
    code, _, _ = _req(u + "/drain", method="POST", data={},
                      token="wrong")
    assert code == 403 and state["drained"] == []
    # right token: drain begins, /readyz flips to 503
    code, _, body = _req(u + "/drain", method="POST", data={},
                         token="s3cret")
    assert code == 200 and json.loads(body)["draining"] is True
    assert state["drained"] == [False]
    code, _, _ = _req(u + "/readyz")
    assert code == 503
    # end the drain: ready again
    code, _, _ = _req(u + "/drain", method="POST", data={"end": True},
                      token="s3cret")
    assert code == 200
    assert _req(u + "/readyz")[0] == 200
    # GETs never need the token
    assert _req(u + "/metrics")[0] == 200


def test_post_flight_dump_and_slo_reload(fake_server):
    srv, state = fake_server
    u = srv.url
    code, _, body = _req(u + "/flight/dump", method="POST", data={},
                         token="s3cret")
    assert code == 200 and json.loads(body)["dumped"] is True
    state["dumps"] = 5          # recorder at its cap: dump() -> None
    code, _, body = _req(u + "/flight/dump", method="POST", data={},
                         token="s3cret")
    assert code == 409 and json.loads(body)["dumped"] is False
    code, _, body = _req(u + "/slo/reload", method="POST",
                         data={"ttft_p99_s": 0.5}, token="s3cret")
    assert code == 200 and json.loads(body)["got"] == {"ttft_p99_s": 0.5}
    # unknown POST path 404s even with the token
    assert _req(u + "/evil", method="POST", data={},
                token="s3cret")[0] == 404


def test_post_garbled_body_is_400_not_silent_default(fake_server):
    """A JSON typo in /slo/reload must NOT read as 'disable SLOs' (nor a
    garbled /drain body as 'begin'): non-empty unparseable bodies 400."""
    srv, state = fake_server
    r = urllib.request.Request(
        srv.url + "/slo/reload", data=b'{"ttft_p99_s": 0.5,}',
        method="POST", headers={"Authorization": "Bearer s3cret"})
    with pytest.raises(HTTPError) as ei:
        urllib.request.urlopen(r, timeout=5)
    assert ei.value.code == 400
    r = urllib.request.Request(
        srv.url + "/drain", data=b'not json', method="POST",
        headers={"Authorization": "Bearer s3cret"})
    with pytest.raises(HTTPError) as ei:
        urllib.request.urlopen(r, timeout=5)
    assert ei.value.code == 400 and state["drained"] == []
    # an EMPTY body stays a valid bare POST
    r = urllib.request.Request(
        srv.url + "/drain", method="POST",
        headers={"Authorization": "Bearer s3cret"})
    with urllib.request.urlopen(r, timeout=5) as resp:
        assert resp.status == 200
    assert state["drained"] == [False]


def test_slo_reload_maps_value_error_to_400():
    reg = MetricsRegistry()

    def reload(cfg):
        raise ValueError("unknown slo config keys: ['nope']")

    srv = TelemetryServer(TelemetryHooks(registry=reg,
                                         slo_reload_fn=reload), port=0)
    srv.start()
    try:
        code, _, body = _req(srv.url + "/slo/reload", method="POST",
                             data={"nope": 1})
        assert code == 400 and "unknown slo" in json.loads(body)["error"]
    finally:
        srv.close()


# ------------------------------------------------------- goodput ledger
def test_goodput_ledger_sums_to_wall_exactly():
    clk = TickClock(dt=0.0)           # manual time control
    gp = GoodputLedger(clock=clk)
    # training-shaped day: compile, steps, idle gaps, a checkpoint, a
    # preemption window
    gp.on_train_step(0.0, 5.0, compiled=True)     # cold compile
    gp.on_train_step(6.0, 7.0)                    # gap 5→6 = queue_empty
    clk.t = 7.0
    with gp.window("checkpoint"):
        clk.advance(2.0)                          # 7→9 checkpoint
    gp.on_train_step(9.5, 10.5)                   # gap 9→9.5 idle
    gp.account("preempt", 10.5, 11.0)
    s = gp.snapshot()
    assert s["wall_s"] == pytest.approx(11.0)
    assert s["productive_s"] == pytest.approx(2.0)
    b = s["badput_s"]
    assert b["compile"] == pytest.approx(5.0)
    assert b["queue_empty"] == pytest.approx(1.5)
    assert b["checkpoint"] == pytest.approx(2.0)
    assert b["preempt"] == pytest.approx(0.5)
    total = s["productive_s"] + s["badput_total_s"]
    assert total == pytest.approx(s["wall_s"], rel=1e-9)
    assert s["unattributed_s"] == pytest.approx(0.0)
    assert s["goodput_frac"] == pytest.approx(2.0 / 11.0)


def test_goodput_ledger_drain_idle_and_export():
    gp = GoodputLedger(registry=MetricsRegistry(), prefix="Serve")
    gp.on_serving_iteration(0.0, 1.0, decode_s=0.8, ran_decode=True)
    gp.set_idle_reason(draining=True)
    gp.on_serving_iteration(2.0, 2.1, draining=True, idle=True)
    snap = gp.export()
    b = snap["badput_s"]
    assert b["drain"] == pytest.approx(1.0 + 0.1)   # gap + empty iter
    assert snap["productive_s"] == pytest.approx(0.8)
    assert b["other"] == pytest.approx(0.2)
    g = gp.registry.snapshot()["gauges"]
    assert g["Serve/goodput_frac"] == pytest.approx(snap["goodput_frac"])
    for bucket in BADPUT_BUCKETS:
        assert f"Serve/goodput_badput_{bucket}_s" in g
    with pytest.raises(ValueError, match="unknown goodput bucket"):
        gp.account("nope", 0.0, 1.0)


def test_goodput_stall_excess_attribution():
    gp = GoodputLedger()
    gp.on_serving_iteration(0.0, 1.0, decode_s=0.9, ran_decode=True,
                            stall_excess_s=0.6)
    s = gp.snapshot()
    assert s["badput_s"]["stall"] == pytest.approx(0.6)
    assert s["productive_s"] == pytest.approx(0.3)   # 0.9 - 0.6
    assert s["badput_s"]["other"] == pytest.approx(0.1)


def test_goodput_compiled_iteration_is_all_compile_never_stall():
    """A cold decode step compiles INSIDE the decode window and trips
    the watchdog; the whole iteration must land in compile — booking it
    as productive + a phantom stall would tell the router a merely-cold
    replica is degraded."""
    gp = GoodputLedger()
    gp.on_serving_iteration(0.0, 3.0, decode_s=2.8, ran_decode=True,
                            compiled=True, stall_excess_s=2.5)
    s = gp.snapshot()
    assert s["badput_s"]["compile"] == pytest.approx(3.0)
    assert s["badput_s"]["stall"] == 0.0
    assert s["productive_s"] == 0.0
    assert s["badput_total_s"] == pytest.approx(s["wall_s"])


# ------------------------------------------------ engine-level integration
@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return model, params, eng


def _serving(eng, clock=None, **extra):
    import deepspeed_tpu as ds

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


def test_serving_engine_telemetry_end_to_end(setup, tmp_path, capsys):
    _, _, eng = setup
    srv = _serving(eng, goodput=True, spans=True,
                   flight_dir=str(tmp_path / "fl"),
                   telemetry={"enabled": True, "port": 0})
    try:
        port = srv.telemetry.port
        assert port > 0
        # idempotent: a second call returns the same bound port
        assert srv.serve_telemetry() == port
        u = f"http://127.0.0.1:{port}"
        # in-flight table BEFORE any step: all requests queued
        rng = np.random.default_rng(1)
        for i in range(3):
            srv.submit(rng.integers(0, 256, (9,)).astype(np.int32), 6,
                       seed=i)
        code, _, body = _req(u + "/requests")
        rows = json.loads(body)["requests"]
        assert code == 200 and len(rows) == 3
        assert all(r["state"] == "queued" for r in rows)
        while not srv.sched.idle or srv._prefill is not None:
            srv.step()
        # /metrics: parses, carries serve + goodput series, and is
        # byte-compatible with the sink for the same registry snapshot
        code, ctype, body = _req(u + "/metrics")
        assert code == 200 and "version=0.0.4" in ctype
        vals = parse_prometheus_textfile(body)
        assert vals["dstpu_serve_retired"] == 3.0
        assert "dstpu_serve_goodput_frac" in vals
        assert vals["dstpu_serve_results_held"] == 3.0
        body2 = _req(u + "/metrics")[2]
        reg = srv.stats.registry
        step = int(reg.counter("Serve/iterations").value)
        assert body2 == exposition_from_events(reg.to_events(step))
        # goodput endpoint: buckets sum to wall within 1%
        g = json.loads(_req(u + "/goodput")[2])
        tot = g["productive_s"] + g["badput_total_s"]
        assert abs(tot - g["wall_s"]) <= 0.01 * max(g["wall_s"], 1e-9)
        assert g["badput_s"]["compile"] > 0        # cold engine compiled
        # probes + drain round-trip (loopback POST, no token configured)
        assert _req(u + "/readyz")[0] == 200
        code, _, _ = _req(u + "/drain", method="POST", data={})
        assert code == 200 and srv.draining
        assert _req(u + "/readyz")[0] == 503       # the k8s contract
        assert json.loads(_req(u + "/healthz")[2])["state"] == "draining"
        code, _, _ = _req(u + "/drain", method="POST",
                          data={"end": True})
        assert code == 200 and not srv.draining
        # manual flight dump through the control plane
        code, _, body = _req(u + "/flight/dump", method="POST", data={})
        assert code == 200
        d = json.loads(body)["dir"]
        assert d is not None and os.path.isdir(d)
        fl = json.loads(_req(u + "/flight")[2])
        assert fl["newest"]["manifest"]["reason"] == "manual"
        # /trace: the span ring as a Perfetto-loadable trace, plus the
        # per-request hop decomposition by rid
        from deepspeed_tpu.observability import validate_chrome_trace

        code, _, body = _req(u + "/trace")
        assert code == 200 and validate_chrome_trace(json.loads(body)) == []
        code, _, body = _req(u + "/trace?rid=0")
        hops = json.loads(body)["hops"]
        assert code == 200 and hops["e2e_s"] > 0
        # single engine, no handoff: those hops are null, the rest tile
        assert hops["handoff_wait_s"] is None and hops["import_s"] is None
        assert (hops["queue_wait_s"] + hops["prefill_s"] + hops["decode_s"]
                ) == pytest.approx(hops["e2e_s"], rel=1e-9)
        assert _req(u + "/trace?rid=999999")[0] == 404
        assert _req(u + "/trace?rid=bogus")[0] == 400
        # live doctor triage over the same plane: clean gate
        from deepspeed_tpu.observability import doctor

        rc = doctor.main(["--url", u])
        out = capsys.readouterr().out
        assert rc == 0 and "[gate] clean" in out and "[goodput]" in out
        # SLO live reload: bad keys 400 and nothing half-applies
        code, _, _ = _req(u + "/slo/reload", method="POST",
                          data={"bogus": 1})
        assert code == 400 and srv.slo is None
        code, _, body = _req(u + "/slo/reload", method="POST",
                             data={"ttft_p99_s": 10.0})
        assert code == 200 and srv.slo is not None
        assert srv.cfg.slo.ttft_p99_s == 10.0
    finally:
        srv.close()
    assert srv.telemetry is None       # close() is idempotent teardown
    srv.close()


def test_serve_telemetry_failed_bind_leaves_engine_retryable(setup):
    """A bind failure (port in use) must raise AND leave the engine
    retryable — not jam the idempotency guard on a dead server whose
    unbound port every later call returns."""
    _, _, eng = setup
    blocker = TelemetryServer(TelemetryHooks(registry=MetricsRegistry()),
                              port=0)
    busy = blocker.start()
    srv = _serving(eng)
    try:
        with pytest.raises(OSError):
            srv.serve_telemetry(port=busy)
        assert srv.telemetry is None
        port = srv.serve_telemetry(port=0)
        assert port > 0 and port != busy
        assert _req(f"http://127.0.0.1:{port}/healthz")[0] == 200
    finally:
        srv.close()
        blocker.close()


def test_health_mirrors_pool_and_results(setup):
    _, _, eng = setup
    srv = _serving(eng, page_size=16, prefix_sharing=True)
    _run_all(srv, n=3)
    h = srv.health()
    assert h["results_held"] == 3 and h["pool_pressure"] is False
    assert "pages" in h and h["pages"]["usable_pages"] > 0
    assert h["pages"]["free_pages"] + h["pages"]["used_pages"] \
        == h["pages"]["usable_pages"]
    g = srv.stats.registry.snapshot()["gauges"]
    assert g["Serve/results_held"] == 3.0
    assert g["Serve/page_pool_pressure"] == 0.0
    assert g["Serve/page_pool_free"] == float(h["pages"]["free_pages"])
    # the contiguous engine reports the same shape minus the pool block
    srv2 = _serving(eng)
    _run_all(srv2, n=1)
    h2 = srv2.health()
    assert h2["pool_pressure"] is False and "pages" not in h2
    assert srv2.stats.registry.snapshot()["gauges"][
        "Serve/results_held"] == 1.0


def test_goodput_serving_fake_clock_sums(setup):
    _, _, eng = setup
    clk = TickClock()
    srv = _serving(eng, clock=clk, goodput=True)
    _run_all(srv, n=3)
    for _ in range(5):                 # idle iterations: queue_empty
        srv.step()
    s = srv.goodput.snapshot()
    total = s["productive_s"] + s["badput_total_s"]
    assert total == pytest.approx(s["wall_s"], rel=1e-6)
    assert s["productive_s"] > 0
    assert s["badput_s"]["compile"] > 0
    assert s["badput_s"]["queue_empty"] > 0
    snap = srv.metrics_snapshot()
    assert snap["goodput"]["wall_s"] == pytest.approx(s["wall_s"])


def test_goodput_chaos_hung_step_lands_in_stall_bucket(setup):
    """The acceptance chain: chaos-hung decode step → watchdog fires →
    the hang's excess is STALL badput, fully fake-clocked."""
    _, _, eng = setup
    clk = TickClock()
    hang_s, wd = 0.5, 0.05
    srv = _serving(eng, clock=clk, goodput=True, watchdog_s=wd,
                   chaos={"enabled": True, "seed": 1, "hang_iteration": 3,
                          "hang_seconds": hang_s})
    srv.chaos.sleep = clk.advance      # the hang advances the fake clock
    _run_all(srv, n=4)
    assert [i for i in srv.chaos.injected if i["point"] == "hang"]
    s = srv.goodput.snapshot()
    assert srv.metrics_snapshot()["watchdog_stalls"] >= 1
    # the injected hang minus the watchdog budget is stall badput
    assert s["badput_s"]["stall"] == pytest.approx(hang_s - wd, rel=0.2)
    total = s["productive_s"] + s["badput_total_s"]
    assert total == pytest.approx(s["wall_s"], rel=1e-6)


def test_training_engine_telemetry_and_goodput(tmp_path):
    """The training half of the tentpole: config-gated server +
    Train/goodput_* attribution (first-call compile window, checkpoint
    commit bucket), serving-only endpoints 404 cleanly."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  random_token_dataset)

    model = build_model(tiny_test())
    engine = ds.initialize({
        # tb omitted: resolved to micro * gas * dp for whatever device
        # count this session's mesh has
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 100,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "observability": {"goodput": True,
                          "telemetry": {"enabled": True, "port": 0}},
    }, model)
    try:
        port = engine.telemetry.port
        assert port > 0 and engine.serve_telemetry() == port
        u = f"http://127.0.0.1:{port}"
        data = random_token_dataset(8 * engine.train_batch_size,
                                    seq_len=32, vocab_size=256,
                                    seed=0, learnable=True)
        loader = DataLoader(data, local_batch_size=engine.train_batch_size,
                            shuffle=True, seed=0)
        for i, batch in enumerate(loader):
            if i >= 3:
                break
            engine.train_batch(batch)
        engine.save_checkpoint(str(tmp_path / "ckpt"))
        code, _, body = _req(u + "/metrics")
        vals = parse_prometheus_textfile(body)
        assert code == 200 and "dstpu_train_goodput_frac" in vals
        assert vals["dstpu_step"] == 3.0        # step_fn = global_steps
        h = json.loads(_req(u + "/healthz")[2])
        assert h["state"] == "training" and h["ready"] is True
        assert h["global_steps"] == 3
        assert _req(u + "/readyz")[0] == 200
        assert _req(u + "/requests")[0] == 404   # serving-only: clean 404
        assert _req(u + "/drain", method="POST", data={})[0] == 404
        g = json.loads(_req(u + "/goodput")[2])
        total = g["productive_s"] + g["badput_total_s"]
        assert abs(total - g["wall_s"]) <= 0.01 * max(g["wall_s"], 1e-9)
        assert g["badput_s"]["compile"] > 0       # first train_batch
        assert g["badput_s"]["checkpoint"] > 0    # the save window
        assert g["productive_s"] > 0              # warm steps
    finally:
        engine.close()
    assert engine.telemetry is None


# ------------------------------------------------------- fleet aggregator
def _fake_fleet(pages):
    """fetch(url, timeout) over a canned {url: text-or-exception} map."""

    def fetch(url, timeout):
        v = pages[url]
        if isinstance(v, Exception):
            raise v
        return v

    return fetch


def _engine_metrics(frac, wall, burn=None, ready=1):
    reg = MetricsRegistry()
    reg.gauge("Serve/goodput_frac").set(frac)
    reg.gauge("Serve/goodput_wall_s").set(wall)
    reg.gauge("Serve/ready").set(ready)
    if burn is not None:
        reg.gauge("Serve/slo_ttft_burn").set(burn)
    return exposition_from_events(reg.to_events(1))


def test_fleet_scraper_merge_relabel_and_rollups():
    pages = {
        "http://a:1/metrics": _engine_metrics(1.0, 10.0),
        "http://a:1/healthz": '{"ready": true}',
        "http://b:2/metrics": _engine_metrics(0.5, 90.0, burn=2.5),
        "http://b:2/healthz": '{"ready": false}',
        "http://c:3/metrics": ConnectionRefusedError("dead"),
        "http://c:3/healthz": ConnectionRefusedError("dead"),
    }
    fs = FleetScraper(["http://a:1", "http://b:2", "http://c:3"],
                      labels=["a", "b", "c"],
                      fetch=_fake_fleet(pages), clock=TickClock())
    snap = fs.scrape()
    fl = snap["fleet"]
    assert fl["engines"] == 3 and fl["up"] == 2 and fl["ready"] == 1
    # wall-weighted: (1.0*10 + 0.5*90) / 100
    assert fl["goodput_frac"] == pytest.approx(0.55)
    assert fl["slo_burn_max"] == pytest.approx(2.5)
    dead = [e for e in snap["engines"] if e["engine"] == "c"][0]
    assert dead["up"] is False and dead["error"] is not None
    text = fs.render(snap)
    p = parse_prometheus_textfile(text)
    assert p['dstpu_scrape_up{engine="a"}'] == 1.0
    assert p['dstpu_scrape_up{engine="c"}'] == 0.0
    assert p['dstpu_serve_goodput_frac{engine="b"}'] == 0.5
    assert p["dstpu_fleet_up"] == 2.0
    assert p["dstpu_fleet_goodput_frac"] == pytest.approx(0.55)
    assert p["dstpu_fleet_slo_burn_max"] == pytest.approx(2.5)


def test_fleet_scraper_all_dead_never_raises(tmp_path):
    fs = FleetScraper(["http://x:1"], fetch=_fake_fleet(
        {"http://x:1/metrics": URLError("nope"),
         "http://x:1/healthz": URLError("nope")}), clock=TickClock())
    snap = fs.scrape()
    assert snap["fleet"]["up"] == 0
    assert snap["fleet"]["goodput_frac"] is None
    out = fs.write(tmp_path / "fleet.prom", snap)
    p = parse_prometheus_textfile(out.read_text())
    assert p['dstpu_scrape_up{engine="x_1"}'] == 0.0


def test_fleet_scraper_validation_and_labels():
    assert engine_label("http://host:8080/") == "host_8080"
    with pytest.raises(ValueError, match="at least one"):
        FleetScraper([])
    with pytest.raises(ValueError, match="labels"):
        FleetScraper(["http://a", "http://b"], labels=["x"])
    with pytest.raises(ValueError, match="duplicate"):
        FleetScraper(["http://a:1", "http://a:1"])
    # explicit labels are sanitized like derived ones: a quote or
    # backslash must not invalidate the merged exposition
    fs = FleetScraper(["http://a:1"], labels=['us-"east"\\'])
    assert fs.labels == ["us-_east__"]


def test_fleet_healthz_falls_back_to_mirrored_gauge():
    """metrics answers, healthz doesn't: ready comes from the
    Serve/ready gauge health() mirrors into the exposition."""
    pages = {"http://a:1/metrics": _engine_metrics(0.9, 5.0, ready=1),
             "http://a:1/healthz": ConnectionRefusedError("nope")}
    fs = FleetScraper(["http://a:1"], labels=["a"],
                      fetch=_fake_fleet(pages), clock=TickClock())
    snap = fs.scrape()
    assert snap["engines"][0]["up"] is True
    assert snap["engines"][0]["ready"] is True
    assert snap["fleet"]["ready"] == 1


# ------------------------------------------------------------- doctor live
def test_doctor_url_gates_on_burning_slo(capsys):
    reg = MetricsRegistry()
    reg.gauge("Serve/slo_ttft_burn").set(3.0)
    srv = TelemetryServer(TelemetryHooks(registry=reg), port=0)
    srv.start()
    try:
        from deepspeed_tpu.observability import doctor

        rc = doctor.main(["--url", srv.url])
        out = capsys.readouterr().out
        assert rc == 1 and "slo_ttft_burn" in out
        assert "endpoint absent" in out        # goodput/flight degrade
        rc = doctor.main(["--url", srv.url, "--no-gate"])
        assert rc == 0
    finally:
        srv.close()


def test_doctor_url_unreachable_is_a_finding(capsys):
    from deepspeed_tpu.observability import doctor

    rc = doctor.main(["--url", "http://127.0.0.1:1", "--timeout", "0.5"])
    out = capsys.readouterr().out
    assert rc == 1 and "unreachable" in out


# ------------------------------------------------- no program added
def test_plane_adds_no_program_and_scrape_equals_the_sink_file(setup,
                                                               tmp_path):
    """The ops surface adds threads and clock reads, never programs: the
    same requests compile the same number of programs with the listener
    and the goodput ledger on as with both off. And one renderer, two
    transports, on a live engine: the ``/metrics`` body equals, byte for
    byte, the file the Prometheus sink writes from the same registry."""
    _, _, eng = setup
    off = _serving(eng)
    _run_all(off, n=6)
    srv = _serving(eng, goodput=True,
                   telemetry={"enabled": True, "port": 0})
    try:
        _run_all(srv, n=6)
        assert srv.compiles == off.compiles
        body = _req(f"http://127.0.0.1:{srv.telemetry.port}/metrics")[2]
        reg = srv.stats.registry
        sink = PrometheusTextfileSink({"output_path": str(tmp_path),
                                       "job_name": "live"})
        sink.write_events(reg.to_events(
            int(reg.counter("Serve/iterations").value)))
        sink.flush()
        assert (tmp_path / "live.prom").read_text() == body
    finally:
        srv.close()

"""Per-tenant cost attribution, fairness & noisy-neighbor observatory
(observability/tenantscope.py) + satellites.

Oracles:
- conservation by construction, pinned on a fake clock: per-tenant
  completed tokens sum EXACTLY to the fleet's Serve/completed_tokens
  counter; per-tenant page-second integrals sum EXACTLY to the pool's
  own integral (same clock reads, hand-computed values);
- bounded cardinality: tenants beyond max_tenants fold into
  "(overflow)" and the fold still conserves totals;
- config validation: from_any matrix + every bad knob raises;
- jain_index: 1.0 when equal, exact hand value when skewed, None when
  nothing was allocated;
- expfmt labeled series: labeled_name composes (merge + same-key
  override + sorted keys + escaping), render emits HELP/TYPE once per
  BASE name, and parse_prometheus_textfile round-trips labeled samples
  as ``name{labels}`` keys;
- fleet scrape relabeling COMPOSES: a tenant-labeled series gains the
  engine label merged into its block (never nested), and a sample that
  already carries engine= keeps its own attribution;
- engine e2e: serve_batch(tenant_ids=...) bills the right tenants,
  conserves the fleet counter, and shows up in metrics_snapshot();
- inertness: tenantscope off builds nothing, mints no Serve/tenant_*
  series, and enabling it compiles ZERO extra programs;
- GET /tenants: 200 + schema body when on, clean 404 when off;
- noisy-neighbor detector: edge-triggered open/close on the injectable
  clock, flight why-marker + incident dump on open, cooldown gates the
  re-trigger;
- doctor [tenants]: fairness floor gate trip / clean / absent;
- a live paged, tiered engine on an exact clock: page-seconds and host
  tier bytes conserve across tenants, and a bursting tenant under SLO
  burn is named in a real flight dump's ``tenant_breakdown.json``.
"""

import json
import urllib.request
from types import SimpleNamespace
from urllib.error import HTTPError

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.observability.doctor import report_tenants
from deepspeed_tpu.observability.expfmt import (exposition_from_events,
                                                labeled_name,
                                                parse_labels,
                                                parse_prometheus_textfile,
                                                prometheus_series,
                                                split_series)
from deepspeed_tpu.observability.fleet_scrape import FleetScraper
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.tenantscope import (OVERFLOW_TENANT,
                                                     TenantScope,
                                                     TenantScopeConfig,
                                                     jain_index)
from deepspeed_tpu.serving import FleetEngine
from _fake_clock import TickClock

EOS = 7


class _Clk:
    """Pin-able clock: returns .t verbatim, so every page-second
    interval in these tests is EXACT hand arithmetic."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Flight:
    """Note/dump recorder standing in for the flight ring."""

    def __init__(self):
        self.notes = []
        self.dumps = []

    def note(self, name, t=None, **meta):
        self.notes.append((name, meta))

    def dump(self, reason):
        self.dumps.append(reason)


def _r(rid, tenant, tokens=(1, 2, 3), prompt_len=4, status="ok",
       submit_t=0.0, admit_t=None, first_token_t=None, finish_t=None):
    """Minimal Request stand-in: exactly the attributes the ledger
    reads (rid/tenant_id/prompt_len/tokens/status/timestamps)."""
    return SimpleNamespace(
        rid=rid, tenant_id=tenant, prompt_len=prompt_len,
        tokens=list(tokens), status=SimpleNamespace(value=status),
        submit_t=submit_t, admit_t=admit_t, first_token_t=first_token_t,
        finish_t=finish_t, prompt=np.arange(prompt_len, dtype=np.int32))


def _scope(clk=None, flight=None, **cfg):
    clk = clk if clk is not None else _Clk()
    reg = MetricsRegistry()
    ts = TenantScope(TenantScopeConfig(**cfg), reg, clk, flight=flight)
    return ts, reg, clk


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


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (9,)).astype(np.int32)
            for _ in range(n)]


def _req(url, timeout=5.0):
    try:
        with urllib.request.urlopen(
                urllib.request.Request(url), timeout=timeout) as resp:
            return int(resp.status), resp.read().decode()
    except HTTPError as e:
        return int(e.code), e.read().decode()


# ------------------------------------------------------------ config matrix
def test_config_from_any_matrix_and_validation():
    assert TenantScopeConfig.from_any(None).enabled
    assert TenantScopeConfig.from_any(True).max_tenants == 64
    inst = TenantScopeConfig(max_tenants=4)
    assert TenantScopeConfig.from_any(inst) is inst
    assert TenantScopeConfig.from_any({"max_tenants": 4}).max_tenants == 4
    with pytest.raises(ValueError, match="unknown tenantscope"):
        TenantScopeConfig.from_any({"max_tenant": 4})
    with pytest.raises(ValueError, match="max_tenants"):
        TenantScopeConfig(max_tenants=0)
    with pytest.raises(ValueError, match="reservoir"):
        TenantScopeConfig(reservoir=0)
    with pytest.raises(ValueError, match="burst_share"):
        TenantScopeConfig(burst_share=0.0)
    with pytest.raises(ValueError, match="window_s"):
        TenantScopeConfig(window_s=-1.0)


def test_jain_index_hand_values():
    assert jain_index([1, 1, 1, 1]) == 1.0
    assert jain_index([3, 1]) == pytest.approx(16.0 / 20.0)
    # zero allocations don't count as tenants in the index
    assert jain_index([5, 0, 0]) == 1.0
    assert jain_index([]) is None
    assert jain_index([0, 0]) is None


# ------------------------------------------------------ exact conservation
def test_token_conservation_exact_against_labeled_counters():
    ts, reg, _ = _scope()
    plan = [("acme", (1, 2, 3, 4)), ("umbrella", (9, 9)),
            ("acme", (5, 6, 7))]
    for i, (tid, toks) in enumerate(plan):
        req = _r(rid=i, tenant=tid, tokens=toks)
        ts.on_submit(req)
        ts.on_admit(req, workload={"shared_prefix_tokens": 2})
        ts.on_retire(req)
    snap = ts.report()
    rows = snap["tenants"]
    assert rows["acme"]["completed_tokens"] == 7
    assert rows["umbrella"]["completed_tokens"] == 2
    total = sum(len(t) for _, t in plan)
    assert snap["totals"]["completed_tokens"] == total
    # the labeled counters carry the same exact integers
    acme = reg.counter(labeled_name("Serve/tenant_completed_tokens",
                                    tenant="acme"))
    assert acme.value == 7
    # goodput shares partition 1.0
    assert sum(r["goodput_share"] for r in rows.values()) \
        == pytest.approx(1.0)
    # prefix overlap partitions by tenant: 2 shared of 4 prompt per req
    assert rows["acme"]["shared_prefix_tokens"] == 4
    assert rows["acme"]["prefix_overlap"] == pytest.approx(4 / 8)


def test_page_second_integrals_agree_interval_by_interval():
    """Per-tenant integrals vs the pool's own integral, same clock
    reads, EXACT equality on hand-pinned event times."""
    ts, _, clk = _scope()
    ts.on_adopt(_r(rid=1, tenant="a"))
    ts.on_adopt(_r(rid=2, tenant="b"))
    clk.t = 1.0
    ts.on_pages(1, +2)
    clk.t = 2.0
    ts.on_pages(2, +3)
    clk.t = 4.0
    ts.on_pages(1, -2)
    clk.t = 6.0
    ts.on_pages(2, -3)
    snap = ts.report()
    # hand math: a held 2 pages over [1,4] = 6; b held 3 over [2,6] = 12
    assert snap["tenants"]["a"]["page_seconds"] == 6.0
    assert snap["tenants"]["b"]["page_seconds"] == 12.0
    # pool integral: 2*[1,2] + 5*[2,4] + 3*[4,6] = 2 + 10 + 6 = 18
    assert snap["totals"]["pool_page_seconds"] == 18.0
    assert snap["totals"]["page_seconds"] \
        == snap["totals"]["pool_page_seconds"]
    # deltas netted to zero: nothing held, nothing still integrating
    assert snap["tenants"]["a"]["pages_held"] == 0
    assert ts.pool_pages_held == 0


def test_overflow_folding_bounds_cardinality_and_conserves():
    ts, _, _ = _scope(max_tenants=2)
    for i, tid in enumerate(["a", "b", "c", "d"]):
        req = _r(rid=i, tenant=tid, tokens=(1,) * (i + 1))
        ts.on_submit(req)
        ts.on_retire(req)
    snap = ts.report()
    # c and d fold into the overflow cell — never a 4th label value
    assert set(snap["tenants"]) == {"a", "b", OVERFLOW_TENANT}
    assert snap["tenants"][OVERFLOW_TENANT]["completed_tokens"] == 3 + 4
    # the fold conserves: totals still equal the sum of ALL retirements
    assert snap["totals"]["completed_tokens"] == 1 + 2 + 3 + 4
    assert snap["fairness"]["n_tenants"] == 3


# -------------------------------------------------------- labeled exposition
def test_labeled_name_composes_merges_and_escapes():
    assert labeled_name("Serve/x", tenant="acme") \
        == 'Serve/x{tenant="acme"}'
    # merge: new keys compose into the existing block, keys sorted
    assert labeled_name('Serve/x{tenant="acme"}', engine="e0") \
        == 'Serve/x{engine="e0",tenant="acme"}'
    # same key passed again OVERRIDES (the relabeler's compose rule)
    assert labeled_name('Serve/x{a="1"}', a="2") == 'Serve/x{a="2"}'
    # escaping round-trips through split/parse
    nasty = labeled_name("Serve/x", t='he said "hi"\\')
    base, block = split_series(nasty)
    assert base == "Serve/x"
    assert parse_labels(block)["t"] == 'he said \\"hi\\"\\\\'
    # the canonical series identity is stable under re-canonicalization
    assert prometheus_series(nasty) == prometheus_series(
        prometheus_series(nasty), prefix="")


def test_exposition_help_once_per_base_and_parse_roundtrip():
    reg = MetricsRegistry()
    reg.counter(labeled_name("Serve/tenant_completed_tokens",
                             tenant="acme")).inc(5)
    reg.counter(labeled_name("Serve/tenant_completed_tokens",
                             tenant="b")).inc(7)
    reg.gauge("Serve/tenant_fairness_jain").set(0.9)
    text = exposition_from_events(reg.to_events(3))
    # HELP/TYPE once per BASE name even with two labeled children
    assert text.count(
        "# TYPE dstpu_serve_tenant_completed_tokens gauge") == 1
    vals = parse_prometheus_textfile(text)
    assert vals[
        'dstpu_serve_tenant_completed_tokens{tenant="acme"}'] == 5.0
    assert vals['dstpu_serve_tenant_completed_tokens{tenant="b"}'] == 7.0
    assert vals["dstpu_serve_tenant_fairness_jain"] \
        == pytest.approx(0.9)


def test_fleet_scrape_composes_engine_label_into_tenant_series():
    page = ("# fake engine exposition\n"
            'dstpu_serve_tenant_completed_tokens{tenant="acme"} 5\n'
            'dstpu_proxied{engine="z"} 1\n'
            "dstpu_serve_completed_tokens 5\n")
    pages = {"http://a:1/metrics": page,
             "http://a:1/healthz": '{"ready": true}'}
    fs = FleetScraper(["http://a:1"], labels=["a"],
                      fetch=lambda url, timeout: pages[url],
                      clock=TickClock())
    text = fs.render(fs.scrape())
    vals = parse_prometheus_textfile(text)
    # COMPOSED, not nested: engine merges INTO the tenant block
    assert vals["dstpu_serve_tenant_completed_tokens"
                '{engine="a",tenant="acme"}'] == 5.0
    # an already-attributed sample keeps its own engine label
    assert vals['dstpu_proxied{engine="z"}'] == 1.0
    assert vals['dstpu_serve_completed_tokens{engine="a"}'] == 5.0


# ----------------------------------------------------------- engine e2e
def test_engine_bills_tenants_and_stays_compile_frozen(setup):
    _, _, _, eng = setup
    prompts = _prompts(4)
    seeds = [50 + i for i in range(4)]
    srv_off = _serving(eng)
    try:
        outs_off = srv_off.serve_batch(prompts, 6, seeds=seeds)
        warm = srv_off.compiles
        assert srv_off.tenantscope is None
        assert srv_off.tenants_snapshot() is None
        assert "tenants" not in srv_off.metrics_snapshot()
        # off mints no tenant series at all
        assert not any(n.startswith("Serve/tenant_")
                       for n, _, _ in srv_off.stats.registry.to_events(1))
    finally:
        srv_off.close()
    srv = _serving(eng, tenantscope=True)
    try:
        outs = srv.serve_batch(
            prompts, 6, seeds=seeds,
            tenant_ids=["acme", "umbrella", "acme", None])
        assert srv.compiles == warm, \
            "tenantscope on must compile ZERO extra programs"
        # identical sampling: attribution must not perturb the tokens
        for a, b in zip(outs, outs_off):
            assert np.array_equal(a, b)
        snap = srv.tenants_snapshot()
        assert snap["schema"] == "dstpu.tenantscope.v1"
        assert set(snap["tenants"]) == {"acme", "umbrella", "default"}
        assert snap["tenants"]["acme"]["retired_ok"] == 2
        # conservation against the fleet's own counter, exactly
        fleet_total = srv.stats.registry.counter(
            "Serve/completed_tokens").value
        assert snap["totals"]["completed_tokens"] == fleet_total
        assert fleet_total == sum(len(t) for t in outs)
        assert srv.metrics_snapshot()["tenants"]["totals"][
            "completed_tokens"] == fleet_total
    finally:
        srv.close()


def test_tenants_endpoint_on_and_off(setup):
    _, _, _, eng = setup
    srv = _serving(eng, tenantscope={},
                   telemetry={"enabled": True, "port": 0})
    try:
        u = f"http://127.0.0.1:{srv.telemetry.port}"
        srv.serve_batch(_prompts(2), 4, seeds=[1, 2],
                        tenant_ids=["acme", "umbrella"])
        code, body = _req(u + "/tenants")
        assert code == 200
        obj = json.loads(body)
        assert obj["schema"] == "dstpu.tenantscope.v1"
        assert set(obj["tenants"]) == {"acme", "umbrella"}
        code, body = _req(u + "/")
        assert json.loads(body)["endpoints"]["/tenants"] is True
    finally:
        srv.close()
    off = _serving(eng, telemetry={"enabled": True, "port": 0})
    try:
        u = f"http://127.0.0.1:{off.telemetry.port}"
        code, body = _req(u + "/tenants")
        assert code == 404 and "tenantscope disabled" in body
        code, body = _req(u + "/")
        assert "/tenants" not in json.loads(body)["endpoints"]
    finally:
        off.close()


def test_fleet_routes_carry_tenants_and_replicas_bill_them(setup):
    _, _, _, eng = setup
    serving = {"slots": 2, "max_len": 48, "prefill_chunk": 16,
               "temperature": 0.8, "top_k": 20, "spans": True,
               "tenantscope": True}
    fl = FleetEngine(eng, serving, replicas=2, clock=TickClock())
    try:
        rids = [fl.submit(p, 4, seed=i, tenant_id="acme")
                for i, p in enumerate(_prompts(3, seed=5))]
        done = {}
        it = 0
        while len(done) < len(rids):
            for req in fl.step():
                if req.rid in set(rids):
                    done[req.rid] = req
                    fl.results.pop(req.rid, None)
            it += 1
            assert it < 50_000
        # every routing decision names the tenant it routed for
        for rid in rids:
            audit = fl.route_audit(rid)
            assert audit and audit[0]["tenant_id"] == "acme"
        # the replicas' ledgers jointly conserve the fleet's tokens
        total = sum(len(done[r].tokens) for r in rids)
        billed = 0
        for name in fl.replicas:
            snap = fl.replicas[name].tenants_snapshot()
            if snap and "acme" in snap["tenants"]:
                billed += snap["tenants"]["acme"]["completed_tokens"]
        assert billed == total
    finally:
        fl.close()


# -------------------------------------------------------- noisy neighbor
def test_noisy_neighbor_edge_triggered_with_cooldown():
    flight = _Flight()
    ts, reg, clk = _scope(
        flight=flight, min_burst_arrivals=3, burst_share=0.6,
        burn_threshold=1.0, check_interval_s=0.0, cooldown_s=5.0,
        window_s=100.0)
    rid = iter(range(1000))
    # quiet two-tenant traffic, no burn: never fires
    for tid in ("a", "b", "a", "b"):
        clk.t += 0.01
        ts.on_submit(_r(next(rid), tid))
    assert ts.episodes == 0 and ts.active_episode is None
    # fleet starts burning while "a" bursts: ONE episode opens
    reg.gauge("Serve/slo_ttft_burn").set(2.0)
    for _ in range(6):
        clk.t += 0.01
        ts.on_submit(_r(next(rid), "a"))
    assert ts.episodes == 1
    assert ts.active_episode["tenant"] == "a"
    assert ts.active_episode["share"] >= 0.6
    assert reg.gauge("Serve/tenant_noisy_active").value == 1.0
    # the why-marker + incident dump fired exactly once, at the edge
    assert [n for n, _ in flight.notes] == ["noisy_neighbor"]
    assert flight.notes[0][1]["tenant"] == "a"
    assert flight.dumps == ["noisy_neighbor"]
    # burn clears: the episode CLOSES (edge-triggered, not latched)
    reg.gauge("Serve/slo_ttft_burn").set(0.0)
    clk.t += 0.01
    ts.on_submit(_r(next(rid), "b"))
    assert ts.active_episode is None
    assert ts.last_episode["tenant"] == "a"
    assert ts.last_episode["duration_s"] > 0
    assert reg.gauge("Serve/tenant_noisy_active").value == 0.0
    # re-burst inside the cooldown: suppressed
    reg.gauge("Serve/slo_ttft_burn").set(2.0)
    clk.t += 1.0
    ts.on_submit(_r(next(rid), "a"))
    assert ts.episodes == 1 and ts.active_episode is None
    # ... and past it: a second episode
    clk.t += 10.0
    ts.on_submit(_r(next(rid), "a"))
    assert ts.episodes == 2 and ts.active_episode["tenant"] == "a"
    assert flight.dumps == ["noisy_neighbor"] * 2


# ------------------------------------------------------------ doctor gate
_SKEWED_PROM = """\
dstpu_serve_tenant_completed_tokens{tenant="a"} 90
dstpu_serve_tenant_completed_tokens{tenant="b"} 10
dstpu_serve_tenant_goodput_share{tenant="a"} 0.9
dstpu_serve_tenant_goodput_share{tenant="b"} 0.1
dstpu_serve_tenant_fairness_jain 0.6098
dstpu_serve_tenant_noisy_episodes 1
dstpu_serve_tenant_noisy_active 0
"""


def test_doctor_tenants_fairness_gate(tmp_path, capsys):
    # no .prom at all: no section, no gate
    assert report_tenants(tmp_path, fairness_min=0.8) == []
    (tmp_path / "metrics.prom").write_text(_SKEWED_PROM)
    findings = report_tenants(tmp_path, fairness_min=0.8)
    out = capsys.readouterr().out
    assert len(findings) == 1
    assert "fairness floor breached" in findings[0]
    assert "FAIRNESS FLOOR BREACHED" in out
    assert "noisy_neighbor" in out
    # floor disabled (the default): same picture, no finding
    assert report_tenants(tmp_path, fairness_min=0.0) == []
    # a tenant-free exposition: section absent entirely
    other = tmp_path / "later"
    other.mkdir()
    (other / "metrics.prom").write_text("dstpu_serve_ready 1\n")
    assert report_tenants(other, fairness_min=0.8) == []


# ------------------------------------------- a live paged engine
def test_tier_owner_bytes_move_with_bytes_used():
    """``owner_bytes`` is ``bytes_used`` split by first writer, through
    put, a replace that re-owns the entry, and the LRU prunes a full
    store forces."""
    from deepspeed_tpu.serving.hostkv import HostKVTier

    store = HostKVTier(1000, page_size=8)
    keys = [tuple(range(i, i + 8)) for i in range(6)]
    owners = ["t0", "t1", "t0", "big", "big", "big"]
    for key, owner in zip(keys[:3], owners):
        store.put(key, {"k": np.zeros(250, np.int8)}, owner=owner)
    store.put(keys[0], {"k": np.zeros(250, np.int8)}, owner="t9")
    assert sum(store.owner_bytes.values()) == store.bytes_used
    for key, owner in zip(keys[3:], owners[3:]):    # each put prunes
        store.put(key, {"k": np.zeros(250, np.int8)}, owner=owner)
        assert sum(store.owner_bytes.values()) == store.bytes_used


def test_live_engine_conserves_pages_and_names_the_noisy_tenant(setup,
                                                                tmp_path):
    """Conservation on a live paged, tiered engine whose clock ticks in
    exact binary fractions: the tenants' page-second integrals sum to the
    pool's own integral as equal floats, and the host tier's bytes split
    by tenant sum to what its entries say they own. Then the detector
    end to end: a tenant that bursts while the fleet burns its SLO opens
    an episode in its name, the flight recorder dumps once with
    ``tenant_breakdown.json`` beside the trace, and the episode closes
    when the burn clears."""
    _, _, _, eng = setup
    srv = _serving(
        eng, clock=TickClock(dt=2.0 ** -10), greedy=True, page_size=8,
        pool_pages=12, host_pool_bytes=1 << 20,
        flight_dir=str(tmp_path),
        tenantscope={"min_burst_arrivals": 6, "burst_share": 0.6,
                     "burn_threshold": 0.5, "check_interval_s": 0.0,
                     "cooldown_s": 0.0, "window_s": 1e9})
    rng = np.random.default_rng(7)
    prefix = {t: rng.integers(0, 256, (32,)).astype(np.int32)
              for t in ("acme", "umbrella", "chatty")}

    def serve(tenant, n):
        for i in range(n):
            prompt = prefix[tenant].copy()
            prompt[-1] = i
            srv.serve_batch([prompt], 8, [1000 + i], tenant_ids=[tenant])

    try:
        serve("acme", 3)
        serve("umbrella", 2)
        snap = srv.tenants_snapshot()
        assert set(snap["tenants"]) == {"acme", "umbrella"}
        assert snap["totals"]["page_seconds"] \
            == snap["totals"]["pool_page_seconds"] > 0.0
        owned = sum(srv.hostkv.owner_bytes.values())
        assert 0 < owned <= srv.hostkv.bytes_used
        assert sum(r["tier_bytes"].get("host_tier", 0)
                   for r in snap["tenants"].values()) == owned
        assert srv.tenantscope.active_episode is None

        srv.stats.registry.gauge("Serve/slo_ttft_burn").set(2.0)
        serve("chatty", 8)
        assert srv.tenantscope.active_episode["tenant"] == "chatty"
        dumps = [d for d in tmp_path.iterdir()
                 if "noisy_neighbor" in d.name]
        assert len(dumps) == 1
        breakdown = json.loads(
            (dumps[0] / "tenant_breakdown.json").read_text())
        assert breakdown["noisy"]["active"]["tenant"] == "chatty"
        assert {"acme", "umbrella", "chatty"} <= set(breakdown["tenants"])
        srv.stats.registry.gauge("Serve/slo_ttft_burn").set(0.0)
        serve("acme", 1)
        assert srv.tenantscope.active_episode is None
        assert srv.tenantscope.last_episode["tenant"] == "chatty"
    finally:
        srv.close()

"""Per-tenant cost attribution bench: conservation, fairness, noisy
neighbors.

Exercises the tenantscope observatory
(``observability/tenantscope.py``) end to end against ground truth it
cannot fake:

- **conservation** — on a binary-exact fake clock, the per-tenant sums
  equal the fleet's own meters EXACTLY: completed tokens vs the
  ``Serve/completed_tokens`` counter, Σ goodput shares == 1, the
  per-tenant page-second integrals vs the pool-wide integral updated at
  the same clock reads, and ``TierStore.owner_bytes`` moving with
  ``bytes_used`` through put / replace / prune / pop;
- **inertness** — tenantscope on compiles ZERO extra programs (same
  compile count as the off engine on identical traffic; the
  ``bench_serving.py --smoke`` compile-freeze oracle), and the off
  engine holds no observatory at all;
- **noisy neighbor** — an injected burst tenant under fleet SLO burn is
  identified by name, the episode marks the flight ring
  (``noisy_neighbor`` why-marker) and the dump carries the per-tenant
  breakdown artifact (``tenant_breakdown.json``);
- **doctor** — the ``[tenants]`` section gates on a breached fairness
  floor (``--tenant-fairness-min``) and stays clean without one.

``--smoke`` is the CPU tier-1 gate (wired via
``tests/unit/test_tenantscope.py``); the full mode serves skewed vs
even multi-tenant traffic and writes ``TENANT_BENCH.json`` (the
fairness-index rows are up-is-good in the cross-PR perf ledger).
"""

import contextlib
import io
import json
import os
import sys
import tempfile

import numpy as np

from bench_serving import build

_PROMPT, _MAX_NEW = 6, 8
_PS, _M = 8, 64


class _Clk:
    """Binary-exact tick clock (dt = 2^-10 s): every timestamp and every
    pages*dt product is exactly representable, so the conservation
    asserts below can demand float EQUALITY, not tolerance."""

    def __init__(self, dt=2.0 ** -10):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _mk_engine(tenantscope=True, paged=False, clock=None, flight=None,
               **extra):
    cfg = {"greedy": True, **extra}
    if tenantscope:
        cfg["tenantscope"] = tenantscope
    if paged:
        cfg.update(page_size=_PS,
                   pool_pages=2 * ((_PROMPT * 4 + _MAX_NEW) // _PS + 2),
                   host_pool_bytes=1 << 20)
    if flight is not None:
        cfg["flight_dir"] = flight
    _model, _params, eng, srv = build(
        slots=2, max_len=_M, chunk=_PS, n_layer=2, d_model=64, n_head=4,
        clock=clock, **cfg)
    return srv


def _drive(srv, rid):
    for _ in range(200_000):
        req = srv.pop_result(rid)
        if req is not None:
            return req
        srv.step()
    raise RuntimeError("serving stuck")


def _traffic(srv, plan, seed=7):
    """``plan`` = [(tenant_id, n_requests)]: serve them interleaved,
    per-tenant prompts sharing a per-tenant prefix (so prefix overlap
    and block ownership split by tenant)."""
    rng = np.random.default_rng(seed)
    base = {t: rng.integers(0, 256, (4 * _PS,)).astype(np.int32)
            for t, _ in plan}
    reqs = [(t, i) for t, n in plan for i in range(n)]
    for t, i in reqs:
        prompt = base[t].copy()
        prompt[-1] = i                       # unique tail per request
        rid = srv.submit(prompt, _MAX_NEW, seed=1000 + i, tenant_id=t)
        _drive(srv, rid)


def _doctor_exit(prom_text, tmp, argv=()) -> int:
    from deepspeed_tpu.observability import doctor

    os.makedirs(tmp, exist_ok=True)
    with open(os.path.join(tmp, "tenants.prom"), "w") as f:
        f.write(prom_text)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = doctor.main(["--dir", tmp, *argv])
    return rc


# ------------------------------------------------------------------ smoke
def smoke():
    from deepspeed_tpu.observability.tenantscope import (
        TenantScopeConfig, jain_index)
    from deepspeed_tpu.serving.hostkv import HostKVTier

    # (1) math + config: Jain hand values, unknown keys refused
    assert jain_index([1, 1, 1, 1]) == 1.0
    assert abs(jain_index([4, 0, 0, 0]) - 1.0) < 1e-12   # zeros drop
    assert abs(jain_index([3, 1]) - (16 / (2 * 10))) < 1e-12
    assert jain_index([]) is None
    try:
        TenantScopeConfig.from_any({"max_tenant": 4})
        raise AssertionError("unknown tenantscope key accepted")
    except ValueError:
        pass

    # (2) tier-store owner conservation: owner_bytes moves with
    # bytes_used through put / replace / prune / pop
    st = HostKVTier(1000, page_size=_PS)
    tiles = {"k": np.zeros(250, np.int8)}    # 250 B per entry
    toks = [tuple(range(i, i + _PS)) for i in range(6)]
    for i, tk in enumerate(toks[:3]):
        st.put(tk, dict(tiles), owner=f"t{i % 2}")
    assert sum(st.owner_bytes.values()) == st.bytes_used
    st.put(toks[0], dict(tiles), owner="t9")          # replace: re-owned
    assert sum(st.owner_bytes.values()) == st.bytes_used
    for tk in toks[3:]:                               # prune LRU victims
        st.put(tk, dict(tiles), owner="big")
        assert sum(st.owner_bytes.values()) == st.bytes_used

    # (3) conservation, end to end on the exact clock: tokens, shares,
    # page-seconds, and the host tier's owned bytes
    srv = _mk_engine(tenantscope=True, paged=True, clock=_Clk())
    _traffic(srv, [("acme", 3), ("umbrella", 2)])
    snap = srv.tenants_snapshot()
    rows = snap["tenants"]
    assert set(rows) == {"acme", "umbrella"}, sorted(rows)
    fleet_tokens = int(
        srv.stats.registry.counter("Serve/completed_tokens").value)
    assert fleet_tokens > 0
    assert sum(r["completed_tokens"] for r in rows.values()) \
        == fleet_tokens, (snap["totals"], fleet_tokens)
    assert abs(sum(r["goodput_share"] for r in rows.values()) - 1.0) \
        < 1e-9
    # the two page-second integrals were updated at the SAME binary-
    # exact clock reads: sum-of-tenants == pool, as floats, exactly
    assert snap["totals"]["page_seconds"] \
        == snap["totals"]["pool_page_seconds"] > 0.0, snap["totals"]
    hk = srv.hostkv
    assert hk is not None and hk.bytes_used > 0
    owned = sum(hk.owner_bytes.values())
    assert 0 < owned <= hk.bytes_used
    assert set(hk.owner_bytes) <= {"acme", "umbrella"}, hk.owner_bytes
    # prompt-prefix demotions bill their first writer; blocks past the
    # prompt (generated tokens) stay (unowned) — visible in the report
    t_bytes = {t: r["tier_bytes"].get("host_tier", 0)
               for t, r in rows.items()}
    assert sum(t_bytes.values()) == owned, (t_bytes, hk.owner_bytes)

    # (4) inertness: off engine holds no observatory; on engine compiles
    # ZERO extra programs on identical traffic
    srv0 = _mk_engine(tenantscope=False)
    _traffic(srv0, [("acme", 1), ("umbrella", 1)])
    assert srv0.tenantscope is None
    assert srv0.tenants_snapshot() is None
    warm = srv0.compiles
    srv1 = _mk_engine(tenantscope=True)
    _traffic(srv1, [("acme", 1), ("umbrella", 1)])
    assert srv1.compiles == warm, \
        f"tenantscope on compiled {srv1.compiles} programs vs {warm} off"

    # (5) the injected noisy tenant: burst + SLO burn -> the episode
    # names the tenant, marks the flight ring, and the dump carries
    # tenant_breakdown.json
    with tempfile.TemporaryDirectory() as td:
        srv2 = _mk_engine(
            tenantscope={"min_burst_arrivals": 6, "burst_share": 0.6,
                         "burn_threshold": 0.5, "check_interval_s": 0.0,
                         "cooldown_s": 0.0, "window_s": 1e9},
            clock=_Clk(), flight=td)
        _traffic(srv2, [("quiet", 2)])
        assert srv2.tenantscope.active_episode is None
        srv2.stats.registry.gauge("Serve/slo_ttft_burn").set(2.0)
        _traffic(srv2, [("chatty", 8)])
        ep = srv2.tenantscope.active_episode
        assert ep is not None and ep["tenant"] == "chatty", ep
        dumps = [d for d in os.listdir(td) if "noisy_neighbor" in d]
        assert dumps, os.listdir(td)
        art = os.path.join(td, dumps[0], "tenant_breakdown.json")
        assert os.path.exists(art), os.listdir(os.path.join(td, dumps[0]))
        bd = json.loads(open(art).read())
        assert bd["noisy"]["active"]["tenant"] == "chatty"
        assert "chatty" in bd["tenants"] and "quiet" in bd["tenants"]
        # episode closes when the burn clears (edge-triggered)
        srv2.stats.registry.gauge("Serve/slo_ttft_burn").set(0.0)
        _traffic(srv2, [("quiet", 1)])
        assert srv2.tenantscope.active_episode is None
        assert srv2.tenantscope.last_episode["tenant"] == "chatty"

    # (6) doctor [tenants]: the fairness floor gates; clean without it
    skewed = (
        'dstpu_serve_tenant_completed_tokens{tenant="acme"} 900\n'
        'dstpu_serve_tenant_completed_tokens{tenant="umbrella"} 100\n'
        'dstpu_serve_tenant_goodput_share{tenant="acme"} 0.9\n'
        'dstpu_serve_tenant_goodput_share{tenant="umbrella"} 0.1\n'
        "dstpu_serve_tenant_fairness_jain 0.61\n"
        "dstpu_serve_tenant_noisy_episodes 1\n"
        "dstpu_serve_tenant_noisy_active 0\n")
    with tempfile.TemporaryDirectory() as td:
        rc_trip = _doctor_exit(skewed, td,
                               ["--tenant-fairness-min", "0.8"])
    with tempfile.TemporaryDirectory() as td:
        rc_clean = _doctor_exit(skewed, td)
    assert rc_trip == 1, f"fairness floor did not gate ({rc_trip})"
    assert rc_clean == 0, f"[tenants] false-fired ({rc_clean})"

    print(json.dumps({
        "smoke": True,
        "fleet_tokens": fleet_tokens,
        "page_seconds": round(snap["totals"]["page_seconds"], 4),
        "host_owned_bytes": owned,
        "fairness_jain": round(snap["fairness"]["jain"], 4),
        "noisy_tenant": "chatty",
        "compiled_programs": warm,
        "verdict": "smoke-pass",
    }))


# ------------------------------------------------------------------- full
def bench():
    res = {}
    # even vs skewed multi-tenant traffic: the fairness index must rank
    # them (up-is-good in the perf ledger)
    srv_e = _mk_engine(tenantscope=True, paged=True, clock=_Clk())
    _traffic(srv_e, [("a", 3), ("b", 3), ("c", 3)])
    even = srv_e.tenants_snapshot()
    srv_s = _mk_engine(tenantscope=True, paged=True, clock=_Clk())
    _traffic(srv_s, [("a", 7), ("b", 1), ("c", 1)])
    skew = srv_s.tenants_snapshot()
    res["fairness_jain_even"] = even["fairness"]["jain"]
    res["fairness_jain_skewed"] = skew["fairness"]["jain"]
    res["attribution"] = {
        "tenants": len(even["tenants"]),
        "completed_tokens": even["totals"]["completed_tokens"],
        "page_seconds": even["totals"]["page_seconds"],
        "host_owned_bytes": sum(
            (srv_e.hostkv.owner_bytes if srv_e.hostkv is not None
             else {}).values()),
    }
    res["dominant_share_max_even"] = max(
        even["fairness"]["dominant_shares"].values())
    res["dominant_share_max_skewed"] = max(
        skew["fairness"]["dominant_shares"].values())
    return res


def main():
    res = bench()
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "TENANT_BENCH.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()

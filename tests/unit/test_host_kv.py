"""Tiered host KV store (serving/hostkv.py + the pages/engine wiring).

Oracles:
- fp host-restore serving output is BIT-identical to prefill-recompute
  (a tierless engine on identical traffic) and to solo ``generate()``,
  incl. TP=4; int8 restore keeps greedy short-context parity;
- the forced-evict→restore A/B: a demoted-then-restored prefix pops
  its ghost WITHOUT booking regret tokens (restore paid copy bytes,
  not prefill), and the fleet books no ``Fleet/affinity_regret`` for a
  resume the sticky replica restored from its host tier;
- degradation: corrupt host copies fail CRC verification and fall back
  to recompute (counted in ``Serve/host_tier_fallbacks``); a pruned
  tier recomputes; a deferred allocation releases its pins;
- allocator hygiene: 10x session oversubscription churn on a fake
  clock leaks nothing (refcount audit: no live allocs, free list +
  tree-held = usable, tier bytes = sum of entries <= budget);
- inert-by-default: ``host_pool_bytes=0`` compiles exactly the plain
  paged program set; config validation refuses a tier without paging;
- the warm tier end to end: a bounded added program set and no compile
  under further restores, the advisor's achieved block and the ledger's
  tier bytes, corrupt/lost disk copies counted and recomputed; doctor's
  [kv] tier verdicts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _fake_clock import TickClock

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.serving import FleetEngine
from deepspeed_tpu.serving.hostkv import HostKVTier

PS = 8          # page size
P = 32          # prompt length (page-aligned: 4 full blocks)
MAX_NEW = 8
M = 64          # slot capacity
POOL = 1 + (P + MAX_NEW - 1 + PS - 1) // PS   # one request's worst case
HOST = 8 << 20
EOS = 7


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


def _scfg(host=True, kvscope=False, pool_pages=POOL, **extra):
    cfg = {"slots": 2, "max_len": M, "prefill_chunk": 16, "greedy": True,
           "page_size": PS, "pool_pages": pool_pages, **extra}
    if host:
        cfg["host_pool_bytes"] = HOST
    if kvscope:
        cfg["kvscope"] = {"dead_after_s": 3600.0}
    return cfg


def _prompts(n=2, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (P,)).astype(np.int32) for _ in range(n)]


def _run_one(srv, prompt, seed, sid, max_new=MAX_NEW):
    rid = srv.submit(prompt, max_new, seed=seed, session_id=sid)
    for _ in range(200_000):
        req = srv.pop_result(rid)
        if req is not None:
            return req
        srv.step()
    raise RuntimeError("serving stuck")


def _cycle(srv, rounds=2, max_new=MAX_NEW):
    """A/B forced-eviction cycling on the one-request pool; every
    resume finds its tree pages evicted (and, tiered, demoted)."""
    A, B = _prompts()
    toks = []
    for r in range(rounds):
        toks.append(_run_one(srv, A, 1000 + r, "sa", max_new).tokens)
        toks.append(_run_one(srv, B, 2000 + r, "sb", max_new).tokens)
    return toks


# ---------------------------------------------------------------- parity
def test_fp_restore_bit_parity_vs_recompute_and_solo(setup):
    _cfg, _model, _params, eng = setup
    srv_on = ds.ServingEngine(eng, _scfg(host=True))
    srv_off = ds.ServingEngine(eng, _scfg(host=False))
    on = _cycle(srv_on, rounds=3)
    off = _cycle(srv_off, rounds=3)
    assert on == off
    hs = srv_on.hostkv.snapshot()
    assert hs["restores"] >= 4 and hs["restored_pages"] >= 4, hs
    assert srv_off.hostkv is None
    # solo oracle through the public API: same seed, same cache width
    A, _B = _prompts()
    solo = np.asarray(eng.generate(
        A[None], MAX_NEW, greedy=True, request_seeds=[1002],
        cache_len=M))[0].tolist()
    assert solo[:len(on[4])] == on[4]     # round-2 A resume (restored)


def test_restore_parity_under_tensor_parallel(devices):
    """TP=4: the demote gather and restore scatter must be
    sharding-transparent under GSPMD — tiered TP output equals the
    tiered TP=1 run and the tierless TP run bit-for-bit."""
    mcfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    base = {"dtype": "float32", "eos_token_id": EOS}
    e1 = ds.init_inference(model, params, dict(base))
    etp = ds.init_inference(model, params, {**base, "tensor_parallel": 4})
    o1 = _cycle(ds.ServingEngine(e1, _scfg(host=True)), rounds=2)
    otp = ds.ServingEngine(etp, _scfg(host=True))
    otp_toks = _cycle(otp, rounds=2)
    ooff = _cycle(ds.ServingEngine(etp, _scfg(host=False)), rounds=2)
    assert o1 == otp_toks == ooff
    assert otp.hostkv.snapshot()["restores"] >= 2


def test_int8_restore_greedy_parity(setup):
    """int8 pool: demoted tiles carry the scale planes; a restore is
    byte-exact vs the quantize-on-append path, so greedy short-context
    tokens match the tierless int8 engine exactly."""
    _cfg, _model, _params, eng = setup
    on = _cycle(ds.ServingEngine(eng, _scfg(host=True, kv_quant_bits=8)),
                rounds=3, max_new=6)
    off = _cycle(ds.ServingEngine(eng, _scfg(host=False, kv_quant_bits=8)),
                 rounds=3, max_new=6)
    assert on == off


# ------------------------------------------------- ghost fix (regret A/B)
def test_restored_resume_books_no_regret(setup):
    """The forced-evict→restore A/B pin: identical traffic books the
    hand-computed regret without the tier and EXACTLY zero with it —
    the restored prefix pops its ghosts without regret tokens."""
    _cfg, _model, _params, eng = setup
    srv_off = ds.ServingEngine(eng, _scfg(host=False, kvscope=True))
    _cycle(srv_off, rounds=3)
    off_reg = srv_off.kvscope.snapshot()["regret"]
    assert off_reg["regret_tokens"] == 2 * 2 * (P - 1), off_reg

    srv_on = ds.ServingEngine(eng, _scfg(host=True, kvscope=True))
    _cycle(srv_on, rounds=3)
    snap = srv_on.kvscope.snapshot()
    assert snap["regret"]["regret_tokens"] == 0, snap["regret"]
    assert snap["regret"]["restored_ghost_hits"] >= 4, snap["regret"]
    assert snap["sessions"]["regret_resumes"] == 0, snap["sessions"]
    assert snap["sessions"]["host_restored_resumes"] == 4, \
        snap["sessions"]
    # ghosts of restored blocks were consumed, not left to rot
    reg = srv_on.stats.registry.snapshot()["counters"]
    assert reg.get("Serve/eviction_regret_tokens", 0) == 0


# ----------------------------------------------------------- degradation
def test_corrupt_host_copy_falls_back_to_recompute(setup):
    _cfg, _model, _params, eng = setup
    srv = ds.ServingEngine(eng, _scfg(host=True))
    srv_ref = ds.ServingEngine(eng, _scfg(host=False))
    A, B = _prompts()
    for s, (prompt, sid) in enumerate([(A, "sa"), (B, "sb")]):
        _run_one(srv, prompt, 1000 + s, sid)
        _run_one(srv_ref, prompt, 1000 + s, sid)
    # A's 4 full blocks are demoted now; corrupt its FIRST block so the
    # whole restore run breaks at the gap and recomputes
    key = min((k for k in srv.hostkv.entries), key=lambda k: k[0])
    srv.hostkv.entries[key]["tiles"]["k"].flat[0] += 1
    got = _run_one(srv, A, 2000, "sa")
    ref = _run_one(srv_ref, A, 2000, "sa")
    assert got.tokens == ref.tokens
    hs = srv.hostkv.snapshot()
    assert hs["fallbacks"] == 1, hs
    assert srv.stats.registry.snapshot()["counters"][
        "Serve/host_tier_fallbacks"] == 1
    # the corrupt entry was dropped; serving continues
    assert key not in srv.hostkv.entries


def test_pruned_tier_recomputes(setup):
    """A tier too small to hold one page keeps nothing; every resume
    recomputes — bit-identically, with demote skips counted."""
    _cfg, _model, _params, eng = setup
    srv = ds.ServingEngine(eng, {**_scfg(host=False), "host_pool_bytes": 64})
    toks = _cycle(srv, rounds=2)
    ref = _cycle(ds.ServingEngine(eng, _scfg(host=False)), rounds=2)
    assert toks == ref
    hs = srv.hostkv.snapshot()
    assert hs["pages"] == 0 and hs["restores"] == 0, hs
    assert hs["demote_skips"] > 0, hs


# ---------------------------------------------------- churn / leak audit
def test_oversubscription_churn_zero_leaks(setup):
    """10x oversubscription on a fake clock: 10 sessions' worst-case
    pages vs a pool that holds one, cycled for rounds — after the drain
    nothing leaks: no live allocations, every page accounted for (free
    list + tree-held = usable), tier bytes = sum of its entries and
    within budget."""
    _cfg, _model, _params, eng = setup
    clock = TickClock(dt=0.25)
    srv = ds.ServingEngine(
        eng, _scfg(host=True, kvscope=True, host_pool_bytes=HOST),
        clock=clock)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (P,)).astype(np.int32)
               for _ in range(10)]
    for r in range(3):
        for s, p in enumerate(prompts):
            _run_one(srv, p, 9000 + 31 * s + r, f"s{s}")
    srv.drain()
    pool = srv.pool
    assert not pool._alloc, pool._alloc
    assert np.all(pool.slot_refs == 0), pool.slot_refs
    assert len(pool.free) + pool.tree_held == pool.usable, \
        (len(pool.free), pool.tree_held, pool.usable)
    tier = srv.hostkv
    assert tier.bytes_used == sum(e["nbytes"]
                                  for e in tier.entries.values())
    assert tier.bytes_used <= tier.capacity_bytes
    assert all(not e["pinned"] for e in tier.entries.values())
    hs = tier.snapshot()
    assert hs["restores"] > 0 and hs["fallbacks"] == 0, hs
    # the ghost fix held under churn too: restored resumes booked none
    snap = srv.kvscope.snapshot()
    assert snap["sessions"]["host_restored_resumes"] > 0


# ------------------------------------------------------------- inertness
def test_host_off_is_plain_paged_engine(setup):
    _cfg, _model, _params, eng = setup
    a = ds.ServingEngine(eng, _scfg(host=False))
    b = ds.ServingEngine(eng, _scfg(host=False))
    _cycle(a, rounds=2)
    _cycle(b, rounds=2)
    assert a.compiles == b.compiles
    assert a.hostkv is None and a.pool.host is None \
        and a.pool.on_demote is None
    assert "demote" not in a._programs and "restore" not in a._programs


def test_config_validation():
    from deepspeed_tpu.inference.config import ServingConfig

    with pytest.raises(ValueError, match="host_pool_bytes"):
        ServingConfig.from_any({"host_pool_bytes": 1 << 20})
    with pytest.raises(ValueError, match="host_pool_bytes"):
        ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                "prefill_chunk": 16,
                                "host_pool_bytes": -1})
    cfg = ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                  "prefill_chunk": 16,
                                  "host_pool_bytes": 1 << 20})
    assert cfg.host_pool_bytes == 1 << 20


# ------------------------------------------------------- tier unit tests
def _tiles(seed=0, nbytes=256):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(-4, 4, (nbytes // 2,)).astype(np.int8),
            "v": rng.integers(-4, 4, (nbytes // 2,)).astype(np.int8)}


def test_tier_put_match_consume_release():
    tier = HostKVTier(4096, page_size=4, clock=TickClock())
    p = np.arange(12, dtype=np.int32)
    tier.put(p[:4], _tiles(1))
    tier.put(p[:8], _tiles(2))
    # block 2 (tokens 8..11) missing: the run stops there
    keys = tier.match(p, start_block=0)
    assert len(keys) == 2
    assert all(tier.entries[k]["pinned"] for k in keys)
    # a pinned entry survives pruning pressure
    tier.release(keys)
    keys = tier.match(p, start_block=1)
    assert len(keys) == 1
    tiles, nbytes, toks = tier.consume(keys)
    assert toks == 4 and nbytes > 0
    assert tiles["k"].shape[1] == 1
    assert tier.bytes_used == sum(e["nbytes"]
                                  for e in tier.entries.values())


def test_tier_lru_prune_and_pin():
    tier = HostKVTier(600, page_size=4, clock=TickClock())
    p = np.arange(16, dtype=np.int32)
    tier.put(p[:4], _tiles(1))       # 256 B
    tier.put(p[:8], _tiles(2))       # 512 B total -> fits
    keys = tier.match(p[:4], start_block=0)   # pin the OLDER entry
    tier.put(p[:12], _tiles(3))      # over budget: prunes LRU UNPINNED
    assert keys[0] in tier.entries           # pinned survived
    assert tier.prunes >= 1
    assert tier.bytes_used <= 600
    tier.release(keys)


def test_tier_collision_and_peek():
    tier = HostKVTier(4096, page_size=4, clock=TickClock())
    p = np.arange(8, dtype=np.int32)
    tier.put(p[:4], _tiles(1))
    # same key-length different tokens: exact verification rejects
    q = p.copy()
    q[3] += 1
    ent = tier.entries[next(iter(tier.entries))]
    ent["tokens"] = tuple(int(t) for t in q[:4])   # simulate collision
    assert tier.match(p, start_block=0) == []
    assert tier.misses == 1
    tier2 = HostKVTier(4096, page_size=4, clock=TickClock())
    tier2.put(p[:4], _tiles(1))
    assert tier2.peek_blocks(p, 0) == 1
    assert tier2.peek_blocks(p, 1) == 0
    assert all(not e["pinned"] for e in tier2.entries.values())


# ------------------------------------------------------------------ fleet
def _fleet_run(fleet, prompt, seed, sid, max_new=MAX_NEW):
    rid = fleet.submit(prompt, max_new, seed=seed, session_id=sid)
    for _ in range(200_000):
        req = fleet.pop_result(rid)
        if req is not None:
            return rid, req
        fleet.step()
    raise RuntimeError("fleet stuck")


def test_fleet_host_restore_is_not_affinity_regret(setup):
    """A resume the sticky replica restores from its host tier is a
    HIT: Fleet/affinity_regret stays zero (tierless, the same traffic
    books it), and the router's residency ranking prefers the replica
    holding the cold copy over a colder, less-loaded one."""
    _cfg, _model, _params, eng = setup
    A, B = _prompts()

    def run_fleet(host):
        fleet = FleetEngine(eng, _scfg(host=host, kvscope=True),
                            replicas=2)
        # both sessions land on r0 (least-loaded, name order) — sb's
        # admission evicts sa's pages there (one-request pool)
        _fleet_run(fleet, A, 1, "sa")
        _fleet_run(fleet, B, 2, "sb")
        # resume sa on its sticky replica: tierless this re-pays prefill
        # (affinity regret); tiered it restores from r0's host tier
        _fleet_run(fleet, A, 3, "sa")
        c = fleet.registry.snapshot()["counters"]
        return fleet, c

    fleet_off, c_off = run_fleet(host=False)
    assert c_off.get("Fleet/affinity_regret", 0) >= 1, c_off
    fleet_on, c_on = run_fleet(host=True)
    assert c_on.get("Fleet/affinity_regret", 0) == 0, c_on
    kv = fleet_on.kv_residency()
    assert kv["totals"]["host_restored_resumes"] >= 1, kv["totals"]
    assert kv["totals"]["host_tier_restores"] >= 1, kv["totals"]
    fleet_on.close()
    fleet_off.close()


def test_router_ranks_host_tier_residency(setup):
    """Router affinity ranks host-tier residency between tree hit and
    cold miss: a session whose prefix was evicted-but-demoted on r1
    routes there, even though load and name-order policy alone would
    pick r0 — and WITHOUT the tier the identical sequence picks r0."""
    _cfg, _model, _params, eng = setup
    A, B = _prompts()

    def seed_r1(host):
        fleet = FleetEngine(eng, _scfg(host=host, kvscope=True),
                            replicas=2)
        # park r0 so the seeding traffic lands on r1; B's admission
        # there evicts A's tree pages (demoting them when tiered)
        fleet.replicas["r0"].begin_drain()
        _fleet_run(fleet, A, 1, "x1")
        _fleet_run(fleet, B, 2, "x2")
        fleet.replicas["r0"].end_drain()
        rid = fleet.submit(A, MAX_NEW, seed=3, session_id="fresh")
        return fleet, rid

    fleet_on, rid = seed_r1(host=True)
    assert fleet_on.replicas["r1"].prefix_residency(A) == (0, 4)
    assert fleet_on.replicas["r0"].prefix_residency(A) == (0, 0)
    assert fleet_on._owner[rid] == "r1", fleet_on.route_audit(rid)
    # the tierless control: both replicas are cold for A, so policy
    # (equal load, name order) picks r0 — the flip IS the ranking
    fleet_off, rid_off = seed_r1(host=False)
    assert fleet_off._owner[rid_off] == "r0"
    fleet_on.drain()
    fleet_off.drain()
    fleet_on.close()
    fleet_off.close()


# ------------------------------------- the warm tier, end to end
def test_warm_tier_program_set_advisor_and_corrupt_disk(setup, tmp_path):
    """What the tests above leave open, on one pair of engines. The tier
    adds a bounded program set (``demote``, ``restore`` and the short final
    bucket a near-whole restore plans) and a warm tiered engine compiles
    nothing under further restores. The capacity report shows what the
    tier achieved beside its projection, and its bytes in the HBM ledger.
    With the disk rung below a host rung too small for one request,
    truncated, overwritten and unlinked files become counted fallbacks:
    the resume recomputes and its tokens equal the tierless engine's."""
    _cfg, _model, _params, eng = setup
    plain = ds.ServingEngine(eng, _scfg(host=False))
    _cycle(plain, rounds=2)
    srv = ds.ServingEngine(eng, _scfg(host=True))
    _cycle(srv, rounds=3)
    assert set(srv._programs) - set(plain._programs) == \
        {"demote", "restore", ("final", 8)}
    warm = srv.compiles
    _cycle(srv, rounds=2)
    assert srv.compiles == warm

    hs = srv.hostkv.snapshot()
    rep = srv.capacity_report(census=False)
    lever = {l["name"]: l for l in rep["advisor"]["levers"]}["tiered_kv"]
    achieved = lever["estimate"]["achieved"]
    assert achieved["restores"] == hs["restores"] > 0
    assert achieved["restored_tokens"] == hs["restored_tokens"]
    assert "host tier ACTIVE" in lever["why"]
    assert rep["ledger"]["kv_host_tier_bytes"] == hs["bytes"]

    page_bytes = hs["bytes"] // hs["pages"]
    nv = ds.ServingEngine(eng, {
        **_scfg(host=False), "host_pool_bytes": page_bytes,
        "nvme_pool_bytes": 64 << 20, "nvme_path": str(tmp_path)})
    try:
        assert _cycle(nv, rounds=2) == _cycle(
            ds.ServingEngine(eng, _scfg(host=False)), rounds=2)
        assert nv.nvmekv.snapshot()["fallbacks"] == 0
        nv.nvmekv.flush()                   # settle the write-behind
        files = sorted(tmp_path.rglob("*.bin"))
        assert len(files) >= 3, files
        for n, f in enumerate(files):
            if n % 2:
                with open(f, "r+b") as fh:   # a torn write
                    fh.truncate(max(1, f.stat().st_size // 2))
            else:
                with open(f, "r+b") as fh:   # bit rot
                    fh.write(b"\xff" * 64)
        # and one lost file, through the store so that its cached
        # descriptor cannot serve the dead inode
        nv.nvmekv.store.unlink(nv.nvmekv._file(next(iter(
            nv.nvmekv.entries))))
        A, _B = _prompts()
        assert _run_one(nv, A, 1003, "sa").tokens == \
            _run_one(plain, A, 1003, "sa").tokens
        assert nv.nvmekv.snapshot()["fallbacks"] >= 1
    finally:
        nv.nvmekv.close()


@pytest.mark.parametrize("prom,rc", [
    ("dstpu_serve_host_tier_pages 4\ndstpu_serve_host_tier_fallbacks 3\n",
     1),
    ("dstpu_serve_host_tier_pages 4\ndstpu_serve_host_tier_fallbacks 0\n"
     "dstpu_serve_host_tier_restores 12\n", 0),
    ("dstpu_serve_nvme_tier_pages 6\ndstpu_serve_nvme_tier_fallbacks 2\n",
     1),
    ("dstpu_serve_nvme_tier_pages 6\ndstpu_serve_nvme_aio_errors 1\n", 1),
    ("dstpu_serve_nvme_tier_pages 6\ndstpu_serve_nvme_tier_promotions 9\n"
     "dstpu_serve_nvme_tier_fallbacks 0\n", 0),
], ids=["host-fallbacks", "host-clean", "nvme-fallbacks", "nvme-aio-errors",
        "nvme-clean"])
def test_doctor_kv_tier_verdicts(tmp_path, capsys, prom, rc):
    """Lost or corrupt tier copies and AIO transport errors trip
    ``doctor``'s [kv] section; a tier that spills and restores cleanly
    does not."""
    from deepspeed_tpu.observability import doctor

    (tmp_path / "tier.prom").write_text(prom)
    assert doctor.main(["--dir", str(tmp_path)]) == rc
    assert "tier verdict" in capsys.readouterr().out

"""Demote-ahead background lane (engine tick + pages candidate walk).

Fake-clock (TickClock) pins, no wall time anywhere:

- idle-threshold triggering: a session's tree-held pages stage into
  the host tier only once it has sat idle past
  ``serving.demote_ahead_idle_s`` — an engine whose sessions stay
  busy stages nothing;
- cancel-on-resume: resuming a session whose pages were already
  staged keeps serving off the tree (no tier restore, no regret); the
  waste is bounded at the staged copies themselves, which stay valid
  in the tier (same tokens → same bits) and fast-free the eventual
  eviction;
- pressure-path fast-free: an eviction of pre-staged pages is a pure
  refcount drop — ``Serve/demote_ahead_fastfrees`` counts it and the
  admission-path demote-wait meter stays EXACTLY zero;
- hygiene: x12-session churn with the lane on leaks nothing (no live
  allocations, free list + tree-held = usable, no pinned tier
  entries, staged-key set consistent with the tier);
- config: the knob refuses to stand without the host tier under it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _fake_clock import TickClock

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test

PS = 8
P = 32
MAX_NEW = 8
M = 64
POOL = 1 + (P + MAX_NEW - 1 + PS - 1) // PS
HOST = 8 << 20
EOS = 7
IDLE = 10.0


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return eng


def _mk(eng, idle_s=IDLE, dt=0.001, **extra):
    clock = TickClock(dt=dt)
    srv = ds.ServingEngine(eng, {
        "slots": 2, "max_len": M, "prefill_chunk": 16, "greedy": True,
        "page_size": PS, "pool_pages": POOL, "host_pool_bytes": HOST,
        "kvscope": {"dead_after_s": 3600.0},
        "demote_ahead_idle_s": idle_s, **extra}, clock=clock)
    return srv, clock


def _prompts(n=2, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (P,)).astype(np.int32) for _ in range(n)]


def _run_one(srv, prompt, seed, sid):
    rid = srv.submit(prompt, MAX_NEW, seed=seed, session_id=sid)
    for _ in range(200_000):
        req = srv.pop_result(rid)
        if req is not None:
            return req
        srv.step()
    raise RuntimeError("serving stuck")


def _counters(srv):
    return srv.stats.registry.snapshot()["counters"]


# ----------------------------------------------------- idle threshold
def test_stages_only_past_idle_threshold(setup):
    srv, clock = _mk(setup)
    A, _B = _prompts()
    _run_one(srv, A, 1, "sa")
    held = srv.pool.tree_held
    assert held > 0
    # busy-adjacent: idle but under the threshold — nothing staged
    srv.step()
    assert not srv.hostkv.entries
    assert _counters(srv).get("Serve/demote_ahead_staged", 0) == 0
    # cross the threshold: the next tick stages the whole idle chain
    clock.advance(IDLE + 1.0)
    srv.step()
    assert _counters(srv)["Serve/demote_ahead_staged"] == held
    assert len(srv.hostkv.entries) == held
    assert srv._staged_ahead == set(srv.hostkv.entries)
    # staging is a COPY: pages stay tree-held, nothing was freed
    assert srv.pool.tree_held == held
    # and it is idempotent — an already-held prefix is not re-staged
    clock.advance(IDLE + 1.0)
    srv.step()
    assert _counters(srv)["Serve/demote_ahead_staged"] == held


def test_busy_sessions_do_not_stage(setup):
    """A session resumed before the threshold never stages: its tree
    tstamps refresh on every touch."""
    srv, clock = _mk(setup)
    A, _B = _prompts()
    for r in range(3):
        _run_one(srv, A, 1 + r, "sa")
        clock.advance(IDLE / 4)     # active well under the threshold
        srv.step()
    assert not srv.hostkv.entries
    assert _counters(srv).get("Serve/demote_ahead_staged", 0) == 0


# --------------------------------------------------- cancel-on-resume
def test_resume_after_staging_keeps_tree_pages(setup):
    """Resume of a staged-but-never-evicted session serves from the
    TREE (prefix hit, no tier restore, no regret); the staged copies
    are the bounded waste and stay valid for the later eviction."""
    srv, clock = _mk(setup)
    A, _B = _prompts()
    r0 = _run_one(srv, A, 1, "sa")
    clock.advance(IDLE + 1.0)
    srv.step()                       # stage A's idle chain
    staged = len(srv.hostkv.entries)
    assert staged > 0
    restores0 = srv.hostkv.restores
    req = _run_one(srv, A, 2, "sa")  # resume: tree pages still there
    assert req.tokens[:len(r0.tokens)] == r0.tokens[:len(req.tokens)]
    assert srv.hostkv.restores == restores0       # no tier restore
    snap = srv.kvscope.snapshot()
    assert snap["regret"]["regret_tokens"] == 0, snap["regret"]
    # waste bound: the tier still holds at most the one staged copy
    # per block — no duplicate entries, nothing pinned after serving
    assert len(srv.hostkv.entries) >= staged
    assert all(not e["pinned"] for e in srv.hostkv.entries.values())
    assert srv.hostkv.fallbacks == 0


# ------------------------------------------------ pressure fast-free
def test_eviction_of_staged_pages_is_pure_free(setup):
    """B's admission against a one-request pool evicts A's pre-staged
    pages: every one is a fast-free (refcount drop), the pressure
    demote-wait meter stays exactly 0.0, and A still restores from the
    tier with zero regret."""
    srv, clock = _mk(setup)
    A, B = _prompts()
    ra = _run_one(srv, A, 1, "sa")
    clock.advance(IDLE + 1.0)
    srv.step()
    staged = _counters(srv)["Serve/demote_ahead_staged"]
    assert staged > 0
    _run_one(srv, B, 2, "sb")        # forces A's pages out
    c = _counters(srv)
    assert c["Serve/demote_ahead_fastfrees"] >= staged - 1, c
    assert srv.demote_wait_s == 0.0
    ra2 = _run_one(srv, A, 3, "sa")  # restore path, not recompute
    assert ra2.tokens[:P] == ra.tokens[:P]
    assert srv.hostkv.restores > 0
    snap = srv.kvscope.snapshot()
    assert snap["regret"]["regret_tokens"] == 0, snap["regret"]
    assert snap["sessions"]["host_restored_resumes"] >= 1


# -------------------------------------------------------- leak audit
def test_churn_zero_leaks(setup):
    """x12-session churn with aggressive staging (every gap crosses the
    threshold): after the drain nothing leaks and the staged-key
    bookkeeping is consistent with the tier."""
    srv, clock = _mk(setup, idle_s=0.1, dt=0.5)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (P,)).astype(np.int32)
               for _ in range(12)]
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
    # staged-key set never outgrows reality: every tracked key is an
    # actual tier entry (fast-free discards are removed on eviction)
    assert srv._staged_ahead <= set(tier.entries), \
        srv._staged_ahead - set(tier.entries)
    assert srv.demote_wait_s == 0.0
    c = _counters(srv)
    assert c["Serve/demote_ahead_fastfrees"] > 0
    assert tier.fallbacks == 0
    snap = srv.kvscope.snapshot()
    assert snap["regret"]["regret_tokens"] == 0, snap["regret"]


# ------------------------------------------------------------- config
def test_demote_ahead_requires_host_tier():
    from deepspeed_tpu.inference.config import ServingConfig

    with pytest.raises(ValueError, match="demote_ahead_idle_s"):
        ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                "prefill_chunk": 16,
                                "demote_ahead_idle_s": 5.0})
    with pytest.raises(ValueError, match="demote_ahead_idle_s"):
        ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                "prefill_chunk": 16,
                                "host_pool_bytes": 1 << 20,
                                "demote_ahead_idle_s": -1.0})
    cfg = ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                  "prefill_chunk": 16,
                                  "host_pool_bytes": 1 << 20,
                                  "demote_ahead_idle_s": 5.0})
    assert cfg.demote_ahead_idle_s == 5.0

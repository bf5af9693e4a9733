"""Self-speculative decoding (inference/speculation.py + the serving
engine's draft/verify/commit lane + PagePool.truncate rollback).

Oracles:
- greedy spec-on serving is BIT-identical to greedy spec-off — the
  acceptance chain re-derives exactly the plain lane's argmax stream —
  across contiguous and paged layouts, multi-turn paged sessions,
  host-KV demote/restore cycling, and TP=4;
- the n-gram drafter is a pure read of the slot's own history; the
  shared helper reproduces the PR-6 workload estimator bit-for-bit;
- PagePool.truncate frees exactly the whole pages past the committed
  extent, never below the shared-prefix floor, with exact refcounts and
  a clean free-list round-trip;
- the verify step is fixed-shape: new acceptance patterns compile
  nothing.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference.speculation import (NGramTable,
                                                 SpeculationConfig,
                                                 acceptance_stats)
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.serving import PagePool
from deepspeed_tpu.serving.pages import _SCRATCH

M = 64          # slot capacity
PS = 8          # page size
EOS = 7
SPEC = {"ngram": 3, "max_draft": 4}


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


def _serve(eng, reqs, extra=None, slots=3):
    srv = ds.ServingEngine(eng, {
        "slots": slots, "max_len": M, "prefill_chunk": 16,
        "greedy": True, **(extra or {})})
    outs = srv.serve_batch([p for p, _, _ in reqs],
                           [n for _, n, _ in reqs],
                           [s for _, _, s in reqs])
    return srv, outs


def _traffic(seed=0, n=6, repetitive=True):
    """Half motif-tiled (n-gram-predictable) prompts, half random —
    the parity oracle must hold whether drafts are mostly accepted or
    mostly rejected."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        if repetitive and i % 2 == 0:
            # motif-tiled prompt + enough output budget for the table to
            # learn the model's own output loop (the drafter predicts
            # from history; prompt n-grams rarely cover the first output
            # tokens, so short decodes never draft)
            p = np.tile(rng.integers(0, 32, (4,)).astype(np.int32), 5)
            mn = int(rng.integers(10, 16))
        else:
            p = rng.integers(0, 256,
                             (int(rng.integers(5, 24)),)).astype(np.int32)
            mn = int(rng.integers(4, 12))
        reqs.append((p, mn, 100 + i))
    return reqs


# ------------------------------------------------------------ n-gram table
def test_ngram_table_extend_predict_draft():
    tab = NGramTable(2)
    assert tab.predict() is None            # context not yet full
    tab.extend([1, 2, 3, 1, 2])
    assert tab.predict() == 3               # (1,2) -> 3
    tab.extend([9])                         # (2,9): unseen context
    assert tab.predict() is None
    # latest occurrence wins
    tab2 = NGramTable(2)
    tab2.extend([1, 2, 3, 1, 2, 4, 1, 2])
    assert tab2.predict() == 4


def test_ngram_draft_chains_and_is_pure():
    tab = NGramTable(2)
    tab.extend([5, 6, 7, 5, 6, 7, 5, 6])
    d = tab.draft(4)
    assert d == [7, 5, 6, 7]                # chained period-3 loop
    assert tab.draft(2) == [7, 5]           # cap respected
    assert tab.draft(4) == d                # pure read: no state moved
    assert tab.predict() == 7               # context untouched
    # the chain stops at the first miss (final context has no successor)
    tab3 = NGramTable(2)
    tab3.extend([1, 2, 3])
    assert tab3.draft(4) == []              # (2,3) unseen -> no draft


def test_acceptance_stats_matches_legacy_estimator():
    from deepspeed_tpu.observability.workload import selfspec_acceptance

    rng = np.random.default_rng(11)
    for _ in range(50):
        toks = rng.integers(0, 12, (int(rng.integers(2, 60)),)).tolist()
        st = acceptance_stats(toks, 3)
        legacy = selfspec_acceptance(toks, 3)
        if st is None:
            assert legacy is None
        else:
            assert legacy == st["rate"]
            assert st["scored"] == len(toks) - 3
            assert 0 <= st["hits"] <= st["predicted"] <= st["scored"]
    assert acceptance_stats([1, 2, 3], 3) is None       # nothing to score


def test_speculation_config_validation():
    cfg = SpeculationConfig.from_any({"ngram": 2, "max_draft": 6})
    assert cfg.ngram == 2 and cfg.max_draft == 6 and cfg.enabled
    with pytest.raises(ValueError, match="ngram"):
        SpeculationConfig.from_any({"ngram": 0})
    with pytest.raises(ValueError, match="max_draft"):
        SpeculationConfig.from_any({"max_draft": 0})
    with pytest.raises(ValueError):
        SpeculationConfig.from_any({"ngrams": 3})       # unknown key


def test_spec_requires_greedy_and_dense_attention(setup):
    _cfg, model, params, eng = setup
    with pytest.raises(ValueError, match="greedy"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": M,
                               "prefill_chunk": 16, "temperature": 0.8,
                               "speculation": SPEC})
    mcfg = tiny_test(max_seq=128, dtype=jnp.float32)
    mfl = build_model(mcfg)
    efl = ds.init_inference(mfl, mfl.init(jax.random.PRNGKey(0)),
                            {"dtype": "float32", "eos_token_id": EOS,
                             "flash_decode": True})
    with pytest.raises(ValueError, match="flash"):
        ds.ServingEngine(efl, {"slots": 2, "max_len": 128,
                               "prefill_chunk": 16, "greedy": True,
                               "speculation": SPEC})


# ------------------------------------------------------------------ parity
def test_spec_greedy_parity_contiguous(setup):
    *_, eng = setup
    reqs = _traffic(seed=1)
    _, base = _serve(eng, reqs)
    srv, outs = _serve(eng, reqs, {"speculation": SPEC})
    for i, (a, b) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")
    snap = srv.spec_snapshot()
    assert snap["verify_steps"] > 0
    assert snap["accepted_tokens_per_step"] >= 1.0
    assert srv.metrics_snapshot()["speculation"] == snap


def test_spec_greedy_parity_paged(setup):
    *_, eng = setup
    reqs = _traffic(seed=2)
    _, base = _serve(eng, reqs, {"page_size": PS})
    srv, outs = _serve(eng, reqs, {"page_size": PS, "speculation": SPEC})
    for i, (a, b) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")
    assert srv.spec_snapshot()["accepted_tokens"] > 0
    # every retirement rolled its pages back and released them: nothing
    # stays slot-referenced (the prefix tree legitimately holds retired
    # prefixes) — rejected-draft KV cannot leak pages
    ps = srv.pool.snapshot()
    assert ps["free_pages"] + ps["tree_held_pages"] == ps["usable_pages"]


def test_spec_multiturn_paged_sessions_parity(setup):
    """Turn t+1 replays turn t's whole conversation (prompt grows by the
    engine's own greedy reply) — the drafter's table must track the
    ADOPTED prefix correctly and rollback must keep the prefix tree
    reusable. Spec-on tokens equal spec-off bit-for-bit every turn."""
    *_, eng = setup
    rng = np.random.default_rng(4)

    def run(extra):
        srv = ds.ServingEngine(eng, {"slots": 2, "max_len": M,
                                     "prefill_chunk": 16, "greedy": True,
                                     "page_size": PS, **extra})
        toks = []
        for s in range(2):                          # two sessions
            hist = np.tile(rng.integers(0, 32, (4,)).astype(np.int32), 3) \
                if s == 0 else rng.integers(0, 256, (9,)).astype(np.int32)
            for t in range(3):                      # three turns each
                rid = srv.submit(hist, 8, seed=10 * s + t,
                                 session_id=f"s{s}")
                out = None
                for _ in range(100_000):
                    out = srv.pop_result(rid)
                    if out is not None:
                        break
                    srv.step()
                toks.append(list(out.tokens))
                hist = np.concatenate(
                    [hist, np.asarray(out.tokens, np.int32)])
        return srv, toks

    rng_state = rng.bit_generator.state
    _, base = run({})
    rng.bit_generator.state = rng_state             # identical traffic
    srv, outs = run({"speculation": SPEC})
    assert base == outs
    assert srv.spec_snapshot()["verify_steps"] > 0
    ps = srv.pool.snapshot()
    assert ps["free_pages"] + ps["tree_held_pages"] == ps["usable_pages"]


def test_spec_parity_with_host_kv_restore(setup):
    """PR-14 composition: A/B forced-eviction cycling on a one-request
    pool demotes retired prefixes to the host tier; every resume
    restores from it. Speculative rollback must preserve the demotion
    invariants — spec-on tokens equal spec-off across the whole cycle,
    and restores actually happened. This traffic is rejection-heavy
    (2-gram drafts off a barely-repetitive stream) — the harshest case
    for the rollback/demote composition: nearly every verify
    truncates."""
    *_, eng = setup
    pool = 1 + (20 + 10 - 1 + PS - 1) // PS

    def cycle(extra):
        srv = ds.ServingEngine(eng, {
            "slots": 2, "max_len": M, "prefill_chunk": 16,
            "greedy": True, "page_size": PS, "pool_pages": pool,
            "host_pool_bytes": 8 << 20, **extra})
        rng = np.random.default_rng(6)
        A = np.tile(rng.integers(0, 32, (4,)).astype(np.int32), 5)
        B = rng.integers(0, 256, (20,)).astype(np.int32)
        toks = []
        for r in range(3):
            for sid, p in (("sa", A), ("sb", B)):
                rid = srv.submit(p, 10, seed=hash((sid, r)) % 1000,
                                 session_id=sid)
                out = None
                for _ in range(100_000):
                    out = srv.pop_result(rid)
                    if out is not None:
                        break
                    srv.step()
                toks.append(list(out.tokens))
        return srv, toks

    _, base = cycle({})
    srv, outs = cycle({"speculation": {"ngram": 2, "max_draft": 4}})
    assert base == outs
    assert srv.hostkv.snapshot()["restores"] >= 2
    assert srv.spec_snapshot()["proposed_tokens"] > 0


def test_spec_under_tensor_parallel(devices):
    """TP=4 parity: the fixed-shape verify forward must be
    sharding-transparent — TP spec-on tokens equal the TP spec-off and
    TP=1 spec-on runs bit-for-bit."""
    mcfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    base = {"dtype": "float32", "eos_token_id": EOS}
    e1 = ds.init_inference(model, params, dict(base))
    etp = ds.init_inference(model, params, {**base, "tensor_parallel": 4})
    reqs = _traffic(seed=9, n=4)
    scfg = {"page_size": PS, "speculation": SPEC}
    _, o1 = _serve(e1, reqs, scfg, slots=2)
    srv, otp = _serve(etp, reqs, scfg, slots=2)
    _, off = _serve(etp, reqs, {"page_size": PS}, slots=2)
    for a, b, c in zip(o1, otp, off):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, c)
    assert srv.spec_snapshot()["verify_steps"] > 0


# -------------------------------------------------------- paged rollback
def test_truncate_frees_whole_pages_and_keeps_mid_block_tail():
    pool = PagePool(pages=16, page_size=4, max_len=32)
    a = pool.try_admit(np.arange(12, dtype=np.int32), 9, rid=1)
    assert a.pages == 5                     # ceil((12 + 9 - 1) / 4)
    gen = pool.generation
    freed = pool.truncate(1, 8)             # exact page boundary
    assert freed == 3 and a.pages == 2
    assert all(int(p) == _SCRATCH for p in a.row[2:5])
    assert pool.generation == gen + 1
    # mid-block tail: 7 tokens keep ceil(7/4)=2 pages — nothing to free
    assert pool.truncate(1, 7) == 0 and a.pages == 2
    pool.release(1)
    assert len(pool.free) + int(np.sum(pool.tree_refs)) == pool.usable


def test_truncate_never_drops_shared_prefix_pages():
    pool = PagePool(pages=16, page_size=4, max_len=32)
    p = np.arange(8, dtype=np.int32)
    pool.try_admit(p, 5, rid=1)
    pool.on_inserted(1, p)
    pool.release(1)                         # 2 full blocks into the tree
    a2 = pool.try_admit(p, 5, rid=2)
    assert a2.shared == 2 and a2.pages == 3
    shared_pages = [int(x) for x in a2.row[:2]]
    assert pool.truncate(2, 0) == 1         # only the private page frees
    assert a2.pages == 2
    for pg in shared_pages:
        assert pool.slot_refs[pg] == 1      # rid=2 still references them
        assert pool.tree_refs[pg] == 1      # tree reference intact
    pool.release(2)
    assert len(pool.free) + int(np.sum(pool.tree_refs)) == pool.usable


def test_truncate_then_append_round_trip_refcounts():
    """Rollback then regrow: truncated rows reacquire pages through the
    normal admission path with exact refcounts — the spec lane's
    reject-heavy steady state."""
    pool = PagePool(pages=16, page_size=4, max_len=32)
    for r in range(3):
        a = pool.try_admit(np.arange(10, dtype=np.int32), 7, rid=r)
        assert a is not None
        pool.truncate(r, 10 - r)            # varying committed extents
        pool.release(r)
        assert len(pool.free) + int(np.sum(pool.tree_refs)) == pool.usable
    assert pool.truncate(99, 4) == 0        # unknown rid: no-op


# ------------------------------------------------- accounting / tier-1 gate
def test_spec_off_engine_reports_no_speculation(setup):
    *_, eng = setup
    srv, _ = _serve(eng, _traffic(seed=3, n=2))
    assert srv.spec_snapshot() is None
    assert "speculation" not in srv.metrics_snapshot()


def test_workload_analyzer_spec_live_export():
    from deepspeed_tpu.observability.workload import WorkloadAnalyzer

    wl = WorkloadAnalyzer({"block": 8})
    assert wl.spec_accept_rate is None
    wl.on_spec(proposed=8, accepted=5, emitted=9, first_scored=3,
               first_hits=2)
    wl.on_spec(proposed=4, accepted=1, emitted=3, first_scored=1,
               first_hits=0)
    snap = wl.snapshot()["spec_live"]
    assert snap["steps"] == 2 and snap["proposed_tokens"] == 12
    assert snap["accept_rate"] == 6 / 12
    assert snap["first_accept_rate"] == 2 / 4
    assert snap["emitted_tokens"] == 12


def test_new_acceptance_patterns_compile_nothing(setup):
    """The verify forward has one shape, whatever a step accepts: after a
    first batch has drafted, accepted and rejected, a second batch with
    other motifs, and so other accept counts per step, builds no program
    (``compiles`` counts builds; a built program traced again for a carry
    that another program produced is ``Serve/retraces``' to count).
    Every slot-step commits at least its one verified token."""
    cfg, model, params, eng = setup
    srv, _ = _serve(eng, _traffic(seed=7),
                    {"page_size": PS, "speculation": SPEC})
    snap = srv.spec_snapshot()
    assert snap["verify_steps"] > 0 and snap["proposed_tokens"] > 0
    assert 0 < snap["accepted_tokens"] < snap["proposed_tokens"]
    assert snap["accepted_tokens_per_step"] >= 1.0
    warm = srv.compiles
    reqs = _traffic(seed=8)
    srv.serve_batch([p for p, _, _ in reqs], [n for _, n, _ in reqs],
                    [s for _, _, s in reqs])
    assert srv.spec_snapshot()["verify_steps"] > snap["verify_steps"]
    assert srv.compiles == warm

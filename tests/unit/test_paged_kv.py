"""Paged KV cache (serving/pages.py + the decode.py paged read/append).

Oracles:
- paged fp serving is BIT-identical to the contiguous engine (and
  transitively to solo ``generate()`` — test_serving.py pins that edge),
  across slot churn, prefix sharing, and copy-on-write, incl. TP=4;
- int8 KV: per-element dequant error bounded by half a quantization
  step, quantize∘dequantize idempotent (what re-inserting a hydrated
  prefix relies on), greedy short-context token parity;
- allocator/tree invariants: refcounts, LRU eviction, COW pinning,
  typed PagePoolExhausted at submit, defer-then-admit-after-retirement
  on a fake clock — the OOM-shaped mid-decode crash is unreachable;
- multi-turn sessions: sharing at least halves the prefill tokens paid,
  within 5 points of the workload estimator's prediction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.decode import (PagedKVCache, cache_layout,
                                            dequantize_kv,
                                            forward_with_cache, init_cache,
                                            quantize_kv)
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.serving import (PagePool, PagePoolExhausted,
                                   RadixPrefixTree, RequestStatus,
                                   plan_chunks)
from deepspeed_tpu.serving.pages import init_paged_slots

M = 48          # slot capacity used across these tests
PS = 8          # page size
EOS = 7


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    return cfg, model, params, eng


def _serve(eng, reqs, extra=None, slots=3):
    srv = ds.ServingEngine(eng, {
        "slots": slots, "max_len": M, "prefill_chunk": 16,
        "temperature": 0.8, "top_k": 20, **(extra or {})})
    outs = srv.serve_batch([p for p, _, _ in reqs],
                           [n for _, n, _ in reqs],
                           [s for _, _, s in reqs])
    return srv, outs


# ------------------------------------------------------------ device layout
def test_paged_cache_layout_and_init(setup):
    cfg, *_ = setup
    shape, dtype = cache_layout(cfg, 4, M, page_size=PS, pages=10)
    assert shape == (cfg.n_layer, 10, cfg.kv_heads, PS, cfg.head_dim)
    state = init_paged_slots(cfg, 4, M, PS, 10, jnp.float32)
    assert isinstance(state.cache, PagedKVCache)
    assert state.cache.k.shape == shape
    assert state.cache.k_scale is None
    assert state.cache.page_table.shape == (4, M // PS)
    assert state.cache.length.shape == (4,)
    q = init_paged_slots(cfg, 4, M, PS, 10, jnp.float32, kv_quant_bits=8)
    assert q.cache.k.dtype == jnp.int8
    assert q.cache.k_scale.shape == shape[:-1]


def test_quantize_kv_bound_and_idempotent():
    """Dequant error <= half a step per element; re-quantizing a
    dequantized value is exact — the property that lets a hydrated
    shared prefix re-insert into the pool without drift."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(scale=3.0, size=(5, 4, 64)), jnp.float32)
    q, s = quantize_kv(x)
    dq = q.astype(jnp.float32) * s[..., None]
    step = np.asarray(s)[..., None]
    assert np.all(np.abs(np.asarray(dq - x)) <= step / 2 + 1e-7)
    q2, s2 = quantize_kv(dq)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), rtol=1e-6)
    # all-zero rows stay representable (no divide-by-zero scale)
    qz, sz = quantize_kv(jnp.zeros((2, 3, 8)))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) > 0)


# ------------------------------------------------------------- chunk plans
def test_plan_chunks_skip():
    p = np.arange(1, 40, dtype=np.int32)            # P=39
    base = plan_chunks(p, 16)
    skipped = plan_chunks(p, 16, skip=16)           # one shared page pair
    assert skipped[0].start == 16
    # final overlap bucket identical to the no-skip plan (may rewind
    # into the hydrated region; rewrites bit-identical KV)
    assert skipped[-1].final and base[-1].final
    assert skipped[-1].start == base[-1].start
    np.testing.assert_array_equal(skipped[-1].ids, base[-1].ids)
    # every chunk stays in the bucket set regardless of skip
    assert all(c.size in (8, 16) for c in skipped)
    # a near-total skip still plans the final-token replay
    tail = plan_chunks(p, 16, skip=38)
    assert tail[-1].final and tail[-1].true_len == 39
    with pytest.raises(ValueError, match="skip"):
        plan_chunks(p, 16, skip=39)


# ---------------------------------------------------------- radix tree/pool
def test_radix_tree_match_register_cow():
    tree = RadixPrefixTree(4)
    a = np.arange(10, dtype=np.int32)               # 2 full blocks + tail 2
    ids, cow = tree.match(a)
    assert ids == [] and cow is None
    taken = tree.register(a, np.asarray([5, 6, 7, 0], np.int32))
    assert taken == [5, 6, 7]                       # 2 blocks + tail page
    ids, cow = tree.match(a)
    assert ids == [5, 6] and cow == (7, 2)          # tail is the COW source
    # an extending prompt matches blocks + the partial tail
    b = np.concatenate([a, np.arange(100, 104, dtype=np.int32)])
    ids, cow = tree.match(b)
    assert ids == [5, 6] and cow == (7, 2)
    # divergence after one block: only the first block matches
    c = np.concatenate([a[:4], np.full(6, 99, np.int32)])
    ids, cow = tree.match(c)
    assert ids == [5] and cow is None


def test_page_pool_refcounts_eviction_and_release():
    pool = PagePool(pages=8, page_size=4, max_len=32)   # 7 usable, 8/slot
    a1 = pool.try_admit(np.arange(8, dtype=np.int32), 5, rid=1)   # 3 pages
    assert a1 is not None and a1.shared == 0 and a1.pages == 3
    pool.on_inserted(1, np.arange(8, dtype=np.int32))
    # identical prompt: both full blocks shared, no private prefill pages
    a2 = pool.try_admit(np.arange(8, dtype=np.int32), 5, rid=2)
    assert a2.shared == 2 and a2.skip == 7              # capped at P-1
    assert list(a2.row[:2]) == list(a1.row[:2])
    # shared pages survive the donor's retirement (tree reference)
    pool.release(1)
    assert pool.slot_refs[a1.row[0]] == 1               # rid=2 still on it
    pool.release(2)
    assert pool.tree_held == 2
    # pressure: a big request evicts the tree-held pages LRU
    a3 = pool.try_admit(np.arange(100, 124, dtype=np.int32), 5, rid=3)
    assert a3 is not None and pool.evictions == 2
    # transient full: next request defers (None), then admits after free
    a4 = pool.try_admit(np.arange(20, dtype=np.int32), 8, rid=4)
    assert a4 is None and pool.defers == 1
    pool.release(3)
    a4 = pool.try_admit(np.arange(20, dtype=np.int32), 8, rid=4)
    assert a4 is not None
    # never-fits: typed shed at submit
    with pytest.raises(PagePoolExhausted, match="pool holds"):
        pool.check_submit(28, 5)                        # 8 pages > 7 usable
    # direct misuse beyond the slot extent is a bug, not backpressure
    with pytest.raises(ValueError, match="pages_per_slot"):
        pool.try_admit(np.arange(40, dtype=np.int32), 8, rid=9)
    snap = pool.snapshot()
    assert snap["pages"] == 8 and snap["prefix_sharing"]
    assert snap["prefill_tokens_saved"] >= 7


def test_page_pool_cow_pin_released_on_abort():
    """A request aborted between admission and insert must release its
    copy-on-write source pin (and all refs) — no page leaks."""
    pool = PagePool(pages=16, page_size=4, max_len=32)
    a = np.arange(10, dtype=np.int32)
    a1 = pool.try_admit(a, 4, rid=1)
    pool.on_inserted(1, a)
    pool.release(1)
    b = np.concatenate([a, np.arange(50, 58, dtype=np.int32)])
    a2 = pool.try_admit(b, 4, rid=2)
    assert a2.cow and a2.cow_src is not None
    assert pool.slot_refs[a2.cow_src] == 1              # pinned
    pool.release(2)                                     # abort pre-insert
    assert pool.slot_refs[a2.cow_src if a2.cow_src is not None
                          else a2.hydrate_row[a2.shared]] == 0
    free_and_tree = len(pool.free) + int(np.sum(pool.tree_refs))
    assert free_and_tree == pool.usable                 # nothing leaked


# ------------------------------------------------------------------ parity
def test_paged_serving_parity_and_slot_churn(setup):
    """Paged fp serving == contiguous serving, bit for bit, across a
    ragged mix with slot reuse; a second identical workload rides the
    prefix tree (tokens saved) and still matches; compile set frozen."""
    cfg, model, params, eng = setup
    rng = np.random.default_rng(0)
    shapes = [(5, 9), (16, 12), (23, 6), (37, 10), (8, 4), (30, 3)]
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 100 + i)
            for i, (P, N) in enumerate(shapes)]
    _, base = _serve(eng, reqs)
    srv, outs = _serve(eng, reqs, {"page_size": PS, "pool_pages": 64})
    for i, (a, b) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")

    def replay():
        return srv.serve_batch([p for p, _, _ in reqs],
                               [n for _, n, _ in reqs],
                               [s for _, _, s in reqs])

    # first SHARED pass may compile the one hydrate program (part of the
    # bounded set); after that the compile count must freeze
    outs2 = replay()
    warm = srv.compiles
    outs3 = replay()
    assert srv.compiles == warm, "sharing must not keep compiling"
    for i, (a, b, c) in enumerate(zip(base, outs2, outs3)):
        np.testing.assert_array_equal(a, b, err_msg=f"shared req {i}")
        np.testing.assert_array_equal(a, c, err_msg=f"re-shared req {i}")
    snap = srv.pool.snapshot()
    assert snap["prefill_tokens_saved"] > 0
    assert snap["prefix_hit_rate"] > 0
    g = srv.stats.registry.snapshot()["gauges"]
    assert g["Serve/page_pool_free"] >= 0
    assert g["Serve/page_prefix_hit_rate"] > 0


def test_paged_cow_multiturn_parity(setup):
    """Turn 2 extends turn 1's prompt past a partial tail block: the COW
    path copies the donor page into a fresh private page and outputs
    stay bit-identical to the contiguous engine."""
    cfg, model, params, eng = setup
    rng = np.random.default_rng(3)
    t1 = rng.integers(0, 256, (21,)).astype(np.int32)
    t2 = np.concatenate([t1, rng.integers(0, 256, (9,)).astype(np.int32)])
    reqs = [(t1, 6, 11), (t2, 6, 12)]
    srv, outs = _serve(eng, reqs, {"page_size": PS, "pool_pages": 64},
                       slots=1)
    _, base = _serve(eng, reqs, slots=1)
    np.testing.assert_array_equal(outs[0], base[0])
    np.testing.assert_array_equal(outs[1], base[1])
    assert srv.pool.snapshot()["cow_copies"] == 1


def test_paged_int8_greedy_short_context_parity(setup):
    """The int8-KV oracle: greedy tokens equal the fp engine's on short
    contexts, and the ledger's per-token KV cost at least halves.

    Equality is the claim wherever the argmax has a margin. On a random
    tiny model it sometimes has none: logits span about +-0.5 and two
    tokens can stand 5e-4 apart, inside what rounding K and V to int8
    moves them. A first difference is excused only there: the fp logits
    at that position must put the fp token and the int8 token closer
    together than the error of an int8 cache measured at that position,
    by running the same context over the fp cache and over that cache
    rounded through ``quantize_kv``. That measurement never touches the
    paged engine, so a defect in its int8 path cannot excuse itself;
    after a first difference the two runs have different contexts and
    are not compared further."""
    cfg, model, params, eng = setup
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 0)
            for P, N in [(9, 4), (12, 5), (20, 4), (6, 3)]]
    srv_c, base = _serve(eng, reqs, {"greedy": True})
    srv_q, outs = _serve(eng, reqs, {"greedy": True, "page_size": PS,
                                     "kv_quant_bits": 8})

    def through_int8(x):
        q, scale = quantize_kv(x, axis=-2)
        return dequantize_kv(q, scale, x.dtype, axis=-2)

    for i, (fp, q8) in enumerate(zip(base, outs)):
        assert len(fp) == len(q8), f"req {i}"
        if np.array_equal(fp, q8):
            continue
        pos = int(np.nonzero(fp != q8)[0][0])
        ctx = jnp.asarray(np.concatenate([reqs[i][0], fp[:pos]])[None])
        _, cache = forward_with_cache(model, params, ctx[:, :-1],
                                      init_cache(cfg, 1, M, jnp.float32))
        cache = cache._replace(length=jnp.int32(ctx.shape[1] - 1))
        exact = np.asarray(forward_with_cache(
            model, params, ctx[:, -1:], cache)[0][0, -1])
        rounded = np.asarray(forward_with_cache(
            model, params, ctx[:, -1:], cache._replace(
                k=through_int8(cache.k), v=through_int8(cache.v)))[0][0, -1])
        assert int(exact.argmax()) == fp[pos], f"req {i}"
        margin = exact[fp[pos]] - exact[q8[pos]]
        noise = np.abs(rounded - exact).max()
        assert margin <= noise, (
            f"req {i} position {pos}: int8 chose {q8[pos]} over {fp[pos]} "
            f"across a margin of {margin:.2e}, int8 cache error {noise:.2e}"
            ": not a near-tie")
    led_q, led_c = srv_q.hbm_ledger(), srv_c.hbm_ledger()
    assert 2 * led_q["kv_per_token_bytes"] <= led_c["kv_per_token_bytes"]
    assert led_q["kv_quant_bits"] == 8
    assert led_q["kv_pool_used_pages"] is not None


def test_paged_under_tensor_parallel(devices):
    """Paged serving on a TP mesh: tokens equal the TP=1 paged run and
    the contiguous TP run — the page gather/scatter must be
    sharding-transparent under GSPMD."""
    mcfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    base = {"dtype": "float32", "eos_token_id": EOS}
    e1 = ds.init_inference(model, params, dict(base))
    etp = ds.init_inference(model, params, {**base, "tensor_parallel": 4})
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, 256, (P,)).astype(np.int32), N, 70 + i)
            for i, (P, N) in enumerate([(9, 6), (21, 11), (5, 3)])]
    scfg = {"slots": 2, "max_len": M, "prefill_chunk": 16,
            "temperature": 0.9, "top_k": 30, "page_size": PS}
    args = ([p for p, _, _ in reqs], [n for _, n, _ in reqs],
            [s for _, _, s in reqs])
    o1 = ds.ServingEngine(e1, scfg).serve_batch(*args)
    otp = ds.ServingEngine(etp, scfg).serve_batch(*args)
    octp = ds.ServingEngine(etp, {k: v for k, v in scfg.items()
                                  if k != "page_size"}).serve_batch(*args)
    for a, b, c in zip(o1, otp, octp):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, c)


# --------------------------------------------------------- admission guard
def test_pool_exhaustion_shed_and_defer_fake_clock(setup):
    """The OOM-shaped failure mode: a request the pool can never hold
    sheds typed at submit (PagePoolExhausted, status SHED); a transient
    shortage defers at the queue head and admits after a retirement
    frees pages — never a mid-decode crash. Fake clock drives the
    deadline-free scheduler deterministically."""
    cfg, model, params, eng = setup
    t = {"now": 0.0}

    def clock():
        t["now"] += 0.01
        return t["now"]

    srv = ds.ServingEngine(eng, {
        "slots": 2, "max_len": M, "prefill_chunk": 16, "greedy": True,
        "page_size": PS, "pool_pages": 6, "prefix_sharing": False},
        clock=clock)
    rng = np.random.default_rng(1)
    r1 = srv.submit(rng.integers(0, 256, (20,)).astype(np.int32), 12,
                    seed=1)                              # 4 pages
    r2 = srv.submit(rng.integers(0, 256, (18,)).astype(np.int32), 8,
                    seed=2)                              # 4 pages: defers
    with pytest.raises(PagePoolExhausted) as ei:
        srv.submit(rng.integers(0, 256, (41,)).astype(np.int32), 7)
    assert ei.value.status is RequestStatus.SHED
    assert ei.value.pages_needed == 6 and ei.value.pages_usable == 5
    seen = {}
    for _ in range(400):
        for req in srv.step():
            seen[req.rid] = req
        if len(seen) == 2:
            break
    assert seen[r1].ok and seen[r2].ok
    assert srv.pool.defers > 0
    assert srv.pool.snapshot()["free_pages"] == srv.pool.usable
    snap = srv.stats.registry.snapshot()
    assert snap["counters"]["Serve/page_defers"] >= 1
    assert snap["counters"]["Serve/shed"] == 1


def test_paged_config_validation(setup):
    cfg, model, params, eng = setup
    with pytest.raises(ValueError, match="page_size"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": M,
                               "prefill_chunk": 16, "page_size": 7})
    with pytest.raises(ValueError, match="pool_pages"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": M,
                               "prefill_chunk": 16, "page_size": 8,
                               "pool_pages": 1})
    with pytest.raises(ValueError, match="kv_quant_bits"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": M,
                               "prefill_chunk": 16, "page_size": 8,
                               "kv_quant_bits": 4})
    with pytest.raises(ValueError, match="paged"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": M,
                               "prefill_chunk": 16, "kv_quant_bits": 8})


# ------------------------------------------------------------ observability
def test_paged_flight_snapshot_and_capacity_report(setup, tmp_path):
    """The flight recorder carries a pages snapshot provider; the
    capacity report closes the loop — achieved savings next to the
    estimator's projection, pool decomposition in the ledger."""
    import json

    from deepspeed_tpu.observability.capacity import (
        LEVER_KV_QUANT, LEVER_PREFIX, validate_capacity_report)

    cfg, model, params, eng = setup
    srv = ds.ServingEngine(eng, {
        "slots": 2, "max_len": M, "prefill_chunk": 16, "greedy": True,
        "page_size": PS, "flight_dir": str(tmp_path / "flight"),
        "workload": {"block": PS}})
    assert "pages" in srv.flight.snapshots
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, (18,)).astype(np.int32)] * 3
    srv.serve_batch(prompts, max_new_tokens=3)
    d = srv.dump_flight("test")
    dumped = json.loads((d / "metrics.json").read_text())
    assert "pages" in dumped and dumped["pages"]["prompt_tokens"] > 0
    rep = srv.capacity_report(path=tmp_path / "cap.json", census=False)
    assert validate_capacity_report(rep) == []
    assert rep["pages"]["prefill_tokens_saved"] > 0
    prefix = next(lv for lv in rep["advisor"]["levers"]
                  if lv["name"] == LEVER_PREFIX)
    ach = prefix["estimate"]["achieved"]
    assert ach["prefill_tokens_saved"] == \
        rep["pages"]["prefill_tokens_saved"]
    assert rep["ledger"]["kv_pool_used_pages"] is not None
    # int8 mode: the kv lever reports achieved instead of projecting
    srv8 = ds.ServingEngine(eng, {
        "slots": 2, "max_len": M, "prefill_chunk": 16, "greedy": True,
        "page_size": PS, "kv_quant_bits": 8})
    srv8.serve_batch(prompts[:1], max_new_tokens=3)
    rep8 = srv8.capacity_report(census=False)
    kv = next(lv for lv in rep8["advisor"]["levers"]
              if lv["name"] == LEVER_KV_QUANT)
    assert kv["estimate"]["achieved"]["kv_quant_bits"] == 8
    assert kv["score"] == 0.0


# ------------------------------------------- what sharing is worth
def test_multiturn_sharing_halves_prefill_as_the_estimator_predicts(setup):
    """Chat-shaped traffic, counted: four sessions open on one system
    prompt and every turn replays its conversation so far. The paged
    engine pays for at most half the prompt tokens the unshared engine
    pays for, and the share it saves is within 5 points of what the
    workload estimator predicts from the same admission stream (the
    capacity advisor's prefix-sharing lever, closed against the thing it
    advises)."""
    cfg, model, params, eng = setup
    srv = ds.ServingEngine(eng, {
        "slots": 3, "max_len": M, "prefill_chunk": 16, "temperature": 0.8,
        "top_k": 20, "page_size": PS, "pool_pages": 64,
        "workload": {"block": PS}})
    rng = np.random.default_rng(3)
    system = rng.integers(0, 256, (16,)).astype(np.int32)
    history = [system] * 4
    prompt_tokens = 0
    for turn, user in enumerate((8, 5, 5)):     # prompts of 24, 32, 40
        prompts = [np.concatenate([h, rng.integers(0, 256, (user,))
                                   .astype(np.int32)]) for h in history]
        prompt_tokens += sum(len(p) for p in prompts)
        replies = srv.serve_batch(prompts, 3,
                                  [10 * turn + s for s in range(4)])
        history = [np.concatenate([p, r])
                   for p, r in zip(prompts, replies)]
    saved = srv.pool.snapshot()["prefill_tokens_saved"]
    assert prompt_tokens >= 2 * (prompt_tokens - saved), \
        (prompt_tokens, saved)
    assert saved / prompt_tokens == pytest.approx(
        srv.workload.prefix_overlap, abs=0.05)

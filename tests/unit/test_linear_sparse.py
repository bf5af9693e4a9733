"""GLM-5.3-Flash's trunk (``model_type: glm5_next_text``): KDA mixers beside
latent attention over pooled indexer keys, four residual streams, clamped
FFNs — the system against ``benchmark/reference/glm5_next.py`` on seeded
weights (the full forward; chunked prefill then decode through the kind, its
kernels off and on), and its parts against what defines them: the chunkwise
delta rule against the recurrence, the state step's kernel against the plain
step, the residual function at one stream against ``x + f(x)``, the pooled
selection against a recount, the eight shares of an expert layer against
the whole layer. One configuration and one set of weights for the file
(ROADMAP D23)."""

import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.models import glm5_next as fam  # noqa: E402
from benchmark.reference import glm5_next as ref  # noqa: E402
from deepspeed_tpu.inference.decode import (forward_with_cache,  # noqa: E402
                                            init_cache)
from deepspeed_tpu.models import (build_model, dsa, glm5_next, kda,  # noqa: E402
                                  mhc)
from deepspeed_tpu.ops.kda_step import kda_state_step  # noqa: E402

F32 = jnp.float32
S, PROMPT, CHUNK, MAX_LEN = 126, 101, 32, 512


def published(**over):
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "glm-5.3-flash-l5-e36.json")) as f:
        conf = json.load(f)
    return {**conf["config"], **conf["rehearsal"], **over}


@pytest.fixture(scope="module")
def small():
    """The rehearsal's configuration in float32, seeded weights, one
    sequence longer than its selection (48 of 126 positions) and the
    reference's logits of it."""
    cfg, model = fam.build(published(), "float32", False)
    params = model.init(jax.random.PRNGKey(3))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, S)), jnp.int32)
    want = np.asarray(ref.run_highest(ref.logits, params, ids))
    return cfg, model, params, ids, want


def worst(got, want):
    return float((np.abs(np.asarray(got) - want).max(-1)
                  / np.abs(want).max(-1)).max())


def through_the_cache(cfg, model, params, ids, flash, size=CHUNK):
    """The prompt in chunks of ``size``, the last right-padded to its bucket,
    then one token a step: the logit rows from the prompt's last on."""
    @partial(jax.jit, donate_argnums=(0,))
    def chunk(cache, blk, start, last):
        lg, cache = forward_with_cache(
            model, params, blk, cache._replace(length=start),
            flash_decode=flash, last_token_head=True, last_index=last)
        return lg[0, 0], cache._replace(length=start + last + 1)

    @partial(jax.jit, donate_argnums=(0,))
    def step(cache, tok):
        lg, cache = forward_with_cache(model, params, tok, cache,
                                       flash_decode=flash)
        return lg[0, 0], cache

    cache = init_cache(cfg, 1, MAX_LEN, F32)
    start, rows = 0, []
    while start < PROMPT:
        n = min(size, PROMPT - start)
        bucket = size if n == size else max(8, 1 << (n - 1).bit_length())
        blk = np.zeros((1, bucket), np.int32)
        blk[0, :n] = np.asarray(ids[0, start:start + n])
        row, cache = chunk(cache, jnp.asarray(blk), jnp.int32(start),
                           jnp.int32(n - 1))
        start += n
    rows.append(row)
    cache = cache._replace(length=jnp.full((1,), PROMPT, jnp.int32))
    for t in range(PROMPT, S):
        row, cache = step(cache, ids[:, t:t + 1])
        rows.append(row)
    return np.stack([np.asarray(r) for r in rows])


@pytest.mark.parametrize("path", ["forward", "cache", "cache, kernels on",
                                  "cache, chunks of 64, kernels on"])
def test_the_trunk_matches_the_plain_reference(small, path):
    """The full forward; and prefill in chunks (a padded final one: 101 = 3 x
    32 + 5 in a bucket of 8, ending mid-group; or 64 + 37 in a bucket of 64)
    then 25 decode steps over six group edges, with XLA's updates and with
    the kernels (interpreted here: the state step, the pooled keys' append,
    the score, the selected read, the chunk's attention and — at chunks of
    64 — the chunk's scan, ``kda_chunk_scan``, behind ``valid`` in the
    padded one)."""
    cfg, model, params, ids, want = small
    with jax.default_matmul_precision("highest"):
        if path == "forward":
            assert worst(model.apply(params, ids), want) < 2e-4
        else:
            got = through_the_cache(cfg, model, params, ids,
                                    path.endswith("on"),
                                    64 if "chunks of 64" in path else CHUNK)
            assert worst(got, want[0, PROMPT - 1:]) < 2e-4


@pytest.mark.parametrize("control", ["sinkhorn-once", "open-group-unread",
                                     "max-for-mean", "clamp-dropped",
                                     "gate-unbounded"])
def test_a_wrong_reading_of_the_config_is_another_model(small, control):
    """Each deviation a control of the chip's comparison switches on
    (``benchmark/kinds/backlog_linear.py``) moves the reference's logits by
    far more than the system differs from the sound one."""
    _, _, params, ids, want = small
    ref.CONTROL.add(control)
    try:
        other = np.asarray(ref.run_highest(ref.logits, params, ids))
    finally:
        ref.CONTROL.clear()
    assert worst(other, want) > 2e-2


# ------------------------------------------------------------------- KDA
def _kda_inputs(T, H=3, D=16, B=2, seed=0):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q, kk = (unit(jax.random.normal(next(k), (B, T, H, D))) for _ in "qk")
    v = jax.random.normal(next(k), (B, T, H, D))
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(next(k), (B, T, H, D)))
    beta = jax.nn.sigmoid(jax.random.normal(next(k), (B, T, H)))
    S0 = jax.random.normal(next(k), (B, H, D, D))
    return q, kk, v, g, beta, S0


def _recurrence(q, k, v, g, beta, S0):
    live = jnp.ones((q.shape[0],), bool)

    def token(St, t):
        o, St = kda.state_step(St, *t, live)
        return St, o

    St, o = jax.lax.scan(token, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), St


@pytest.mark.parametrize("T,real", [(150, 150), (64, 37), (8, 5)])
def test_the_chunkwise_delta_rule_is_the_recurrence(T, real):
    """Blocks of 64 with sub-blocks of 16 at decays down to e^-5 a token
    (the floor: e^-320 a block), a state handed in and handed on; and a
    padded tail (``beta = 0``, ``g = 0``: what ``valid`` sets) leaves the
    state where the last real token left it."""
    q, k, v, g, beta, S0 = _kda_inputs(T)
    real_t = jnp.arange(T)[None, :, None] < real
    beta_p = jnp.where(real_t, beta, 0.0)
    g_p = jnp.where(real_t[..., None], g, 0.0)
    with jax.default_matmul_precision("highest"):
        o, St = kda.scan_chunked(q, k, v, g_p, beta_p, S0)
        o_want, S_want = _recurrence(*(a[:, :real] for a in (q, k, v, g,
                                                             beta)), S0)
    np.testing.assert_allclose(o[:, :real], o_want, atol=2e-5)
    np.testing.assert_allclose(St, S_want, atol=2e-5)


def test_the_state_step_s_kernel_is_the_plain_step_and_spares_the_idle():
    """``kda_state_step`` (interpreted) at heads of 128 x 128, layer 1 of 2:
    the running slots' outputs and states as ``kda.state_step``'s, the slot
    at length 0 and the other layer bit-equal."""
    B, H, D = 3, 4, 128
    q, k, v, g, beta, _ = _kda_inputs(1, H=H, D=D, B=B, seed=1)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    S = jax.random.normal(jax.random.PRNGKey(5), (2, B, H, D, D))
    length = jnp.asarray([7, 0, 3], jnp.int32)
    o, new = kda_state_step(S, jnp.int32(1), q, k, v, g, beta, length)
    o_want, S_want = kda.state_step(S[1], q, k, v, g, beta, length > 0)
    np.testing.assert_allclose(o[jnp.asarray([0, 2])],
                               o_want[jnp.asarray([0, 2])], atol=1e-5)
    np.testing.assert_allclose(new[1], S_want, atol=1e-5)
    assert jnp.array_equal(new[1, 1], S[1, 1]) \
        and jnp.array_equal(new[0], S[0])


# ------------------------------------------------------------------- mHC
def test_one_stream_with_its_maps_at_one_is_the_plain_residual():
    """``mhc.sublayer`` at n = 1 with H_pre = H_post = H_res = 1 gives the
    bits of ``x + f(x)``, in bfloat16 and in float32: the residual of every
    other trunk is this function's special case."""
    f = lambda x: jnp.tanh(x * 1.7) * 0.3  # noqa: E731
    for dtype in (jnp.bfloat16, F32):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 64)).astype(dtype)
        one = (jnp.ones((2, 5, 1)), jnp.ones((2, 5, 1)),
               jnp.ones((2, 5, 1, 1)))
        got = mhc.sublayer(x[..., None, :], f, one)[..., 0, :]
        assert got.dtype == dtype and jnp.array_equal(got, x + f(x))
        assert jnp.array_equal(mhc.sublayer(x, f), x + f(x))


def test_the_residual_map_is_doubly_stochastic_after_twenty_passes():
    cfg = glm5_next("tiny")
    m0 = jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (64, 4, 4)))

    def off(m):
        return float(jnp.maximum(jnp.abs(m.sum(-1) - 1).max(),
                                 jnp.abs(m.sum(-2) - 1).max()))

    assert off(mhc.sinkhorn(m0, cfg.hc_sinkhorn_iters, cfg.hc_eps)) < 1e-5
    assert off(mhc.sinkhorn(m0, 1, cfg.hc_eps)) > 1e-2
    # the drawn maps are far from the identity (a missing pass would show)
    p = jax.tree.map(lambda a: a[0], mhc.init_params(
        cfg, jax.random.PRNGKey(1), 1))
    X = jax.random.normal(jax.random.PRNGKey(6), (8, 4, cfg.d_model))
    res = mhc.maps(cfg, X, p, 0)[2]
    assert off(res) < 1e-5 and float(jnp.abs(res - jnp.eye(4)).max()) > 0.3


# ------------------------------------------------------- the pooled choice
def test_the_pooled_selection_is_a_recount_over_all_keys():
    """Scores over 12 groups of 4, a budget of 3 groups: a query takes the 3
    best closed groups before its own, whole, and its own up to itself —
    positions, their count and the mask agree with a count by hand, across
    group edges and while fewer groups are closed than the budget holds."""
    pool, topk, G = 4, 12, 12
    score = jax.random.normal(jax.random.PRNGKey(7), (1, G * pool, G))
    q_pos = jnp.arange(G * pool, dtype=jnp.int32)[None]
    idx, n, mask = dsa.select_pooled(score, q_pos, topk, pool)
    sc = np.asarray(score[0])
    for t in range(G * pool):
        own = t // pool
        best = sorted(np.argsort(-sc[t, :own], kind="stable")[:topk // pool])
        want = [g * pool + j for g in best for j in range(pool)] \
            + list(range(own * pool, t + 1))
        assert int(n[0, t]) == len(want)
        assert sorted(np.asarray(idx[0, t, :len(want)]).tolist()) == want
        assert np.flatnonzero(np.asarray(mask[0, t])).tolist() == want


# --------------------------------------- the step's read of live blocks
def _pooled_step(dtype, lens, S, topk, pool, D, seed=0, L=2, H=4):
    """A step's operands at ``lens`` live positions a slot: a cache of rows
    with no rope part, the pooled selection of random scores (and of their
    negatives: another selection with the same own group)."""
    from deepspeed_tpu.ops import sparse_mla_attention as sparse

    B = len(lens)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    lat = jax.random.normal(next(keys), (L, B, S, D), F32).astype(dtype)
    q = jax.random.normal(next(keys), (B, H, D), F32).astype(dtype)
    new = jax.random.normal(next(keys), (B, D), F32).astype(dtype)
    score = jax.random.normal(next(keys), (B, 1, S // pool), F32)
    length = jnp.asarray(lens, jnp.int32)
    pos = jnp.maximum(length - 1, 0)[:, None]
    picks = [dsa.select_pooled(sc, pos, topk, pool) for sc in (score, -score)]
    after = np.asarray(lat, np.float32).copy()
    for b, n in enumerate(lens):
        if n:
            after[1, b, n - 1] = np.asarray(new[b], np.float32)
    return (lat, sparse.pack_rows(lat, dtype), q, new, length, after,
            [(idx[:, 0], n[:, 0], mask) for idx, n, mask in picks])


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2), (F32, 1e-5)],
                         ids=["bf16", "f32"])
def test_the_dense_read_of_a_pooled_selection_is_the_selected_read(dtype,
                                                                   tol):
    """``run`` 4, ``n`` given, a row that is the latent alone: with the
    selection's mask every slot here walks its live blocks whole and gives
    what ``attend_selected`` gives over ``select_pooled``'s positions — at
    lengths 0 and 1, inside and at the edges of a group, of the budget (48 +
    the open group) and of a block of 128, at the cache's end; the appended
    position is the open group's last and always selected; every other
    position of the cache stays bit-untouched."""
    from deepspeed_tpu.ops import sparse_mla_attention as sparse

    pool, topk, S, D = 4, 48, 512, 32
    lens = [0, 1, 3, 4, 5, 47, 48, 49, 52, 53, 127, 128, 129, 300, 512]
    lat, cache, q, new, length, after, picks = _pooled_step(
        dtype, lens, S, topk, pool, D)
    idx, n, mask = picks[0]
    assert bool(np.all(sparse.reads_dense(
        np.asarray(lens), np.asarray(n) * (np.asarray(lens) > 0), pool,
        cache.shape[-1] * cache.dtype.itemsize)))
    o, got = sparse.sparse_mla_decode_attention(
        q, cache, new, idx, length, layer=jnp.int32(1), rank=D, scale=0.3,
        group=16, n=n, run=pool, mask=sparse.step_mask(mask), block=128,
        interpret=True)
    assert np.array_equal(
        np.asarray(sparse.unpack_rows(got, D, dtype), np.float32), after)
    want = sparse.attend_selected(
        q, jnp.asarray(after[1]).astype(dtype), idx, length, rank=D,
        scale=0.3, n=jnp.where(length > 0, n, 0))
    assert float(jnp.abs(o.astype(F32) - want.astype(F32)).max()) <= tol
    assert float(jnp.abs(o[0]).max()) == 0.0        # length 0: nothing read
    for b, live in enumerate(lens[1:], 1):
        assert live - 1 in np.asarray(idx[b, :int(n[b])])


def test_the_kind_s_host_count_is_what_the_kernel_took(small):
    """Slots on both sides of the crossover at ``run`` 4, the kernel handed
    a mask that holds ANOTHER selection than ``idx``: a slot's result is the
    mask's where ``reads_dense`` says dense and ``idx``'s elsewhere, which is
    what the kind's ``decode_step`` meta counts (``dsa_dense_share``,
    ``dsa_rows_read_over_selected`` by hand); a row is the latent alone
    (``dsa_fetched_over_selected`` 1.0)."""
    from deepspeed_tpu.inference.kinds import kind_of
    from deepspeed_tpu.ops import sparse_mla_attention as sparse

    cfg = small[0]
    pool, topk, D, S = cfg.index_kpool, cfg.index_topk, cfg.latent_dim, 2048
    assert (pool, topk, D, cfg.kv_lora_rank) == (4, 48, 32, 32)
    kind = kind_of(cfg, 5, F32)
    kind.flash, kind.max_len = True, S
    row_bytes = sparse.row_layout(D, F32)[0] * 4
    most = topk + pool                      # 12 closed groups and the open
    edge = int(sparse.crossover(pool, row_bytes) * 16) * most // 16 \
        // pool * pool
    assert 128 < edge < S - pool
    lens = np.asarray([30, 0, edge, edge + pool, S], np.int32)
    lat, cache, q, new, length, after, picks = _pooled_step(
        F32, lens.tolist(), S, topk, pool, D, seed=5)
    (idx, n, _), (other, n2, mask) = picks
    assert np.array_equal(np.asarray(n), np.asarray(n2))
    chosen = np.asarray(n) * (lens > 0)
    assert np.array_equal(chosen[lens > 0], kind._chosen(lens[lens > 0]))
    o = sparse.sparse_mla_decode_attention(
        q, cache, new, idx, length, layer=jnp.int32(1), rank=D, scale=0.3,
        group=16, n=n, run=pool, mask=sparse.step_mask(mask), block=128,
        interpret=True)[0]
    by_idx, by_mask = (sparse.attend_selected(
        q, jnp.asarray(after[1]), sel, length, rank=D, scale=0.3,
        n=jnp.asarray(chosen)) for sel in (idx, other))
    dense = sparse.reads_dense(lens, chosen, pool, row_bytes)
    assert dense.tolist() == [True, True, True, False, False]
    for b in range(len(lens)):
        took, left = (by_mask, by_idx) if dense[b] else (by_idx, by_mask)
        assert float(jnp.abs(o[b] - took[b]).max()) <= 1e-5, b
        assert lens[b] <= most \
            or float(jnp.abs(o[b] - left[b]).max()) > 1e-3, b
    meta = kind.step_meta([], [], lens, {})
    assert meta["dsa_dense_share"] == 2 / 4
    blk = min(sparse.DENSE_BLOCK, S)
    walked = -(-30 // blk) * blk + -(-edge // blk) * blk
    assert meta["dsa_rows_read_over_selected"] \
        == (walked + 2 * most) / chosen.sum()
    assert meta["dsa_selected"] == chosen.sum() == 30 + 3 * most
    kind.flash = False                  # off the kernels nothing reads so
    assert "dsa_dense_share" not in kind.step_meta([], [], lens, {})
    # at the published widths (512 values in 256 words: nothing beside the
    # latent) a slot of the cell's ~2700 reads its blocks whole, ~1.5 rows
    # a selected one
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "glm-5.3-flash-l5-e36.json")) as f:
        wide = kind_of(fam.model_config(json.load(f)["config"], "bfloat16"),
                       2, jnp.bfloat16)
    wide.flash, wide.max_len = True, 8192
    meta = wide.step_meta([], [], np.asarray([2700, 0], np.int32), {})
    walked = -(-2700 // sparse.DENSE_BLOCK) * sparse.DENSE_BLOCK
    assert meta["dsa_fetched_over_selected"] == 1.0
    assert meta["dsa_selected"] == 2052 and meta["dsa_dense_share"] == 1.0
    assert meta["dsa_rows_read_over_selected"] == walked / 2052 < 1.6


@pytest.mark.parametrize("T,flash,max_len,scan", [
    (64, True, 512, True), (32, True, 512, False), (8, True, 512, False),
    (64, False, 512, False), (64, True, 500, False)])
def test_a_chunk_s_span_says_which_scan_ran(small, T, flash, max_len, scan):
    """``scan_kernel`` beside ``attn_kernel`` on a ``prefill_chunk`` span:
    ``kda_chunk_scan`` with the kernels on (a cache of whole lane blocks:
    the attention kernel's rule) and a chunk of whole blocks of 64."""
    from deepspeed_tpu.inference.kinds import kind_of
    from deepspeed_tpu.serving.scheduler import ChunkPlan

    kind = kind_of(small[0], 2, F32)
    kind.flash, kind.max_len = flash, max_len
    meta = kind.chunk_meta(ChunkPlan(start=64, ids=np.zeros(T, np.int32)))
    assert meta["scan_kernel"] is scan
    assert meta["attn_kernel"] is (flash and max_len % 128 == 0)


# ------------------------------------------------------- the chip's share
def test_eight_shares_of_an_expert_layer_sum_to_the_whole_layer():
    """Each of 8 chips holds 2 of the router's 16 experts; every one routes
    over all 16, takes its top 4, adds its own experts' part and the shared
    expert. Their parts, the shared expert counted once, add up to the
    reference's layer with all 16 held."""
    pub = published(n_routed_experts=16, router_experts=16)
    ref.configure(pub)
    cfg = fam.model_config(pub, "float32")
    whole = build_model(cfg)
    seg = whole.init(jax.random.PRNGKey(9))["layers"][1]
    w = jax.tree.map(lambda a: a[0], seg)
    y = jax.random.normal(jax.random.PRNGKey(10), (1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(y[0], w, ref.PUBLISHED)
        shared = want - ref.experts(y[0], w, ref.PUBLISHED, shared=False)[0]
        total = 0.0
        for chip in range(8):
            share = build_model(fam.model_config(published(
                n_routed_experts=2, router_experts=16,
                first_expert_held=2 * chip), "float32"))
            mine = {**w, **{k: w[k][2 * chip:2 * chip + 2]
                            for k in ref.BANKS}}
            total = total + share.experts(y, mine)[0][0] - shared
        np.testing.assert_allclose(total + shared, want, atol=2e-5)


# ------------------------------------------------------------ the config
def test_the_published_config_counts_the_card_s_parameters():
    big = glm5_next("5.3-flash")
    assert round(big.param_count() / 1e9, 1) == 313.3
    assert round(big.param_count(active_only=True) / 1e9, 1) == 17.4
    assert big.mixer_pattern.count("K") == 34 \
        and big.index_pattern.count("F") == 11


@pytest.mark.parametrize("over,why", [
    (dict(index_pattern="-s---"), "the first that attends an 'F'"),
    (dict(index_pattern="FF---"), "exactly where mixer_pattern"),
    (dict(mixer_pattern=""), "only beside the mixers of a mixer_pattern"),
    (dict(mixer_pattern="", pos_embedding="rope", qk_rope_head_dim=8,
          index_pattern="FFFFF"), "hc_mult and index_kpool"),
    (dict(kda_gate_floor=1.0), "negative .the bounded gate. or 0"),
    (dict(index_topk=18), "a multiple of index_kpool"),
    (dict(pos_embedding="rope", qk_rope_head_dim=8), "no position code"),
    (dict(index_pattern=""), "glm5_next_text\n?.*block|glm5_next_text"),
])
def test_what_the_trunk_does_not_run_is_refused_with_why(over, why):
    with pytest.raises(ValueError, match=why):
        build_model(glm5_next("tiny", **over))


def test_an_index_pattern_refuses_what_is_still_not_run():
    """``dsa.check_config``, narrowed: '-' only beside a mixer_pattern, no
    looped trunk, no window rings; GLM-5.2's own trunk still builds."""
    from deepspeed_tpu.models import glm_moe_dsa

    build_model(glm_moe_dsa("tiny"))
    for over, why in ((dict(index_pattern="F-ssFss"), "only beside a "
                       "mixer_pattern"),
                      (dict(attn_pattern="GSGSGSG", window=8),
                       "stands beside neither")):
        with pytest.raises(ValueError, match=why):
            build_model(glm_moe_dsa("tiny", **over))

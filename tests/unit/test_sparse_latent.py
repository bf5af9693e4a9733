"""Latent attention that reads an indexer's selection (``models/dsa.py``,
``inference/kinds/sparse_latent.py``, ``ops/sparse_mla_attention.py``), at a
tiny size on the CPU against the plain reference
(``benchmark/reference/glm_moe_dsa.py``): the full forward, chunked prefill
and decode through the cache under, at and over ``index_topk``, the shared
selection, the chip's share, the controls that have to fail, a slot at
length 0, and the sparse kernel in interpret mode."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kinds.backlog_sparse import CONTROLS, control
from benchmark.reference import glm_moe_dsa as ref
from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                            forward_with_cache, init_cache)
from deepspeed_tpu.inference.kinds import Latent, SparseLatent, kind_of
from deepspeed_tpu.models import build_model, dsa, glm_moe_dsa, mla
from deepspeed_tpu.ops import sparse_mla_attention as sparse
from deepspeed_tpu.serving.slots import init_slots

F32 = jnp.float32


TOPK = 16
HELD = dict(moe_experts_held=2, moe_first_held=2)


def published(cfg) -> dict:
    """The published keys the reference reads, of a native config."""
    L = cfg.n_layer
    return {
        "num_attention_heads": cfg.n_head, "rms_norm_eps": cfg.norm_eps,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank, "index_topk": cfg.index_topk,
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim,
        "indexer_types": ["full" if k == "F" else "shared"
                          for k in cfg.index_pattern],
        "mlp_layer_types": ["dense"] * cfg.moe_first_dense
        + ["sparse"] * (L - cfg.moe_first_dense),
        "num_hidden_layers": L, "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "first_expert_held": cfg.moe_first_held,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc"}


@pytest.fixture(scope="module")
def tiny():
    cfg = glm_moe_dsa("tiny", dtype=F32, **HELD)
    assert (cfg.index_pattern, cfg.index_topk, cfg.index_heads) \
        == ("FsssFss", TOPK, 3)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ref.configure(published(cfg))
    return cfg, model, params


def ids_of(cfg, n, rows=1, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, n), 0,
                              cfg.vocab_size)


def close(a, b, tol=2e-5):
    return float(jnp.abs(a - b).max()) <= tol * float(jnp.abs(b).max())


# -------------------------------------------------------- the full forward
def test_the_forward_is_the_reference_s(tiny):
    cfg, model, params = tiny
    ids = ids_of(cfg, 70, rows=2)
    got, routing = model.apply(params, ids, return_aux=True)
    assert routing.shape == (6, 2, 70, cfg.moe_top_k)
    assert close(got, ref.run_highest(ref.logits, params, ids))


def through_the_cache(model, params, ids, cuts, flash, max_len=128):
    """Prefill ``ids`` in chunks ending at ``cuts`` and decode the rest a
    token at a time: (logits of every position, the last read-backs)."""
    cfg = model.cfg
    cache = init_cache(cfg, ids.shape[0], max_len, F32)
    # one program a chunk shape and one for the step (run op by op, a test
    # is a thousand one-op programs in a worker that holds them all)
    fwd = jax.jit(lambda p, ids, cache: forward_with_cache(
        model, p, ids, cache, flash_decode=flash, with_routing=True))
    out, at, chose = [], 0, None
    for cut in (*cuts, *range(cuts[-1] + 1, ids.shape[1] + 1)):
        lg, cache, chose = fwd(params, ids[:, at:cut], cache)
        out.append(lg)
        at = cut
    return jnp.concatenate(out, 1), chose, cache


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("cuts,n", [
    ((8,), 14),              # under index_topk everywhere
    ((16,), 20),             # a chunk ending AT index_topk, steps over it
    ((13, 29), 36),          # chunks 2-3 tokens behind 16 and 32, steps over
    ((32, 64), 70),          # aligned chunks, several times index_topk
])
def test_chunks_then_steps_through_the_cache_are_the_reference_s(
        tiny, cuts, n, flash):
    cfg, model, params = tiny
    ids = ids_of(cfg, n, seed=n)
    got, chose, _ = through_the_cache(model, params, ids, cuts, flash)
    assert close(got, ref.run_highest(ref.logits, params, ids))
    routing, picks = chose
    assert routing.shape == (6, 1, 1, cfg.moe_top_k)
    # the selection comes back for the F layers alone, whole past index_topk
    assert picks.shape == (2, 1, 1, TOPK)
    assert int((picks >= 0).sum(-1).min()) == min(n, TOPK)


def test_under_index_topk_it_is_the_latent_kind_on_the_same_weights(tiny):
    """Everything is selected while ``length <= index_topk``: the latent
    kind (the same trunk with no index_pattern) reads the same."""
    cfg, model, params = tiny
    plain = dataclasses.replace(cfg, index_pattern="", index_topk=0,
                                index_heads=0, index_head_dim=0)
    assert type(kind_of(plain)) is Latent \
        and type(kind_of(cfg)) is SparseLatent
    other = build_model(plain)
    theirs = {k: v for k, v in params.items() if k != "indexer"}
    ids = ids_of(cfg, TOPK, seed=5)
    for flash in (False, True):
        got, _, _ = through_the_cache(model, params, ids, (7,), flash)
        want, _, _ = through_the_cache(other, theirs, ids, (7,), flash)
        assert close(got, want, 1e-5)
    # ... and past it they part
    ids = ids_of(cfg, 3 * TOPK, seed=6)
    got, _, _ = through_the_cache(model, params, ids, (40,), False)
    want, _, _ = through_the_cache(other, theirs, ids, (40,), False)
    assert not close(got, want, 1e-2)


# ------------------------------------------------------ the shared selection
def test_a_shared_layer_reads_the_full_layer_s_selection(tiny, monkeypatch):
    """Eagerly, the trunk's own loop: the mask a shared layer hands its
    attention IS the object the full layer before it made; the cache holds
    an indexer key for the two full layers alone."""
    cfg, model, params = tiny
    seen = []
    real = mla.attend_expanded

    def spy(*a, selected=None, **kw):
        seen.append(selected)
        return real(*a, selected=selected, **kw)

    monkeypatch.setattr(mla, "attend_expanded", spy)
    x, pos = model._embed(params, ids_of(cfg, 40))
    _, _, picks = dsa.trunk(model, params, x, pos, with_selection=True)
    assert len(seen) == 7 and picks.shape == (2, 1, 40, TOPK)
    assert all(seen[i] is seen[0] for i in (1, 2, 3))
    assert all(seen[i] is seen[4] for i in (5, 6))
    assert seen[4] is not seen[0] \
        and not np.array_equal(np.asarray(seen[4]), np.asarray(seen[0]))
    # bit-equal indices: the mask holds exactly the positions picked
    for full, mask in ((0, seen[0]), (1, seen[4])):
        for t in (3, TOPK - 1, TOPK, 39):
            idx = np.asarray(picks[full, 0, t])
            assert sorted(idx[idx >= 0]) == list(
                np.nonzero(np.asarray(mask[0, t]))[0])
    kind = kind_of(cfg)
    assert kind.buffers(2, 128, F32)["ik"][0] == (2, 2, 16, 128)
    assert kind.buffers(2, 128, F32)["c"][0][0] == 7
    assert "wq_b" in params["indexer"] \
        and params["indexer"]["wk"].shape[0] == 2
    assert not any("weights_proj" in seg
                   for seg in model.segment_params(params["layers"]))


# ------------------------------------------------- the selection, no sort
def sorted_select(score, q_pos, topk):
    """``dsa.select`` as it stood until PR 52, kept as the oracle: one
    ``lax.top_k`` (a sort) and the mask from its K-th value."""
    S = score.shape[-1]
    causal = jnp.arange(S, dtype=jnp.int32)[None, None] <= q_pos[..., None]
    masked = jnp.where(causal, score, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, min(topk, S))
    idx = jnp.where(vals > -jnp.inf, idx, -1).astype(jnp.int32)
    thr = vals[..., -1:]
    above, tied = masked > thr, (masked == thr) & causal
    room = min(topk, S) - jnp.sum(above, axis=-1, keepdims=True)
    return idx, above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


def _scores(rng, B, T, S):
    return rng.standard_normal((B, T, S)).astype(np.float32)


def _relu(zeros):               # what an indexer gives: many exact zeros
    return lambda rng, *s: np.where(rng.random(s) < zeros, 0.0,
                                    np.abs(_scores(rng, *s)))


def _signed_zeros(rng, B, T, S):
    x = np.where(rng.random((B, T, S)) < 0.5, -0.0, 0.0).astype(np.float32)
    return np.where(rng.random((B, T, S)) < 0.1, _scores(rng, B, T, S), x)


# name: (scores, S, topk, the queries' positions (None: any), n_keys)
SELECTIONS = {
    "fewer candidates than K": (_scores, 300, 64, (0, 40), None),
    "K over S": (_scores, 70, 100, None, None),
    "K is S": (_scores, 128, 128, None, None),
    "ties at the threshold": (_relu(0.95), 300, 32, (200, 300), None),
    "rows of zeros": (lambda rng, *s: np.zeros(s, np.float32), 300, 16,
                      None, None),
    "-0.0 beside +0.0": (_signed_zeros, 260, 24, (100, 260), None),
    "negative scores": (lambda rng, *s: -np.abs(_scores(rng, *s)) - 1.0, 300,
                        16, None, None),
    "all candidates equal": (lambda rng, *s: np.full(s, -2.5, np.float32),
                             256, 16, None, None),
    "a walk over the live blocks": (_relu(0.999), 4 * dsa.SELECT_BLOCK, 48,
                                    (dsa.SELECT_BLOCK + 7,
                                     2 * dsa.SELECT_BLOCK + 100),
                                    2 * dsa.SELECT_BLOCK + 100),
    "a walk that ends under K": (_scores, 2 * dsa.SELECT_BLOCK, 64, (0, 50),
                                 50),
}


@pytest.mark.parametrize("T", [1, 5], ids=["a step", "a chunk"])
@pytest.mark.parametrize("case", SELECTIONS)
def test_the_selection_is_the_sort_s(case, T):
    """The threshold found by bisection gives the mask one ``lax.top_k`` and
    the tie rule gave, bit for bit; ``idx`` holds the mask's positions and
    no other, ascending, -1 behind them: min(K, candidates) a row."""
    make, S, topk, where, n_keys = SELECTIONS[case]
    B = 2
    rng = np.random.default_rng(len(case) + T)
    score = jnp.asarray(make(rng, B, T, S))
    lo, hi = where or (0, S)
    q_pos = jnp.asarray(rng.integers(lo, hi, (B, T)), jnp.int32)
    K = min(topk, S)
    idx, mask = jax.jit(lambda s, p: dsa.select(
        s, p, topk, n_keys=None if n_keys is None else jnp.int32(n_keys)))(
            score, q_pos)
    _, want = sorted_select(score, q_pos, topk)
    assert idx.shape == (B, T, K) and idx.dtype == jnp.int32
    assert np.array_equal(np.asarray(mask), np.asarray(want))
    for row, m, at in zip(np.asarray(idx).reshape(-1, K),
                          np.asarray(mask).reshape(-1, S),
                          np.asarray(q_pos).reshape(-1)):
        held = np.flatnonzero(m)
        assert len(held) == min(K, at + 1)
        assert np.array_equal(row[:len(held)], held) \
            and (row[len(held):] == -1).all()


def test_a_step_wants_no_mask_and_gets_the_same_indices():
    """``want_mask=False`` (the T == 1 step, whose kernel fetches by index):
    the same path, the same ``idx``, no mask handed back."""
    rng = np.random.default_rng(7)
    score = jnp.asarray(_relu(0.6)(rng, 3, 1, 300) + 0.5)
    q_pos = jnp.asarray([[10], [150], [299]], jnp.int32)
    idx, none = dsa.select(score, q_pos, 32, want_mask=False)
    want, _ = dsa.select(score, q_pos, 32)
    assert none is None and np.array_equal(np.asarray(idx), np.asarray(want))
    by_sort, _ = sorted_select(score, q_pos, 32)
    assert np.array_equal(np.sort(np.asarray(by_sort), -1),
                          np.sort(np.asarray(idx), -1))


# -------------------------------------------------------------- the controls
@pytest.mark.parametrize("name", CONTROLS)
def test_every_control_fails(tiny, name):
    """What a wrong system would compute reads far from the system: the
    reference under each control, following the system at its near-ties as
    the benchmark's comparison does, against the system's own logits."""
    cfg, model, params = tiny
    ids = ids_of(cfg, 48)
    got, routing, picks = jax.jit(lambda p, ids: (lambda x, pos: (
        lambda xs, r, s: (model._head(p, xs), r, s))(*dsa.trunk(
            model, p, x, pos, with_selection=True)))(*model._embed(p, ids)))(
                params, ids)

    def compare():
        want, took = ref.run_highest(
            lambda p, i, r, s: ref.logits(p, i, follow=(r, s), gap=1e-4,
                                          select_gap=1e-4),
            params, ids, routing, picks)
        return float(jnp.abs(got - want).max() / jnp.abs(want).max()), took

    sound, took = compare()
    assert sound < 2e-5 and float(took[2]) <= 1e-4
    with control(name, ref):
        wrong, _ = compare()
    assert not ref.CONTROL and ref.ROUND is None
    # by a wide margin: thousands of times the sound reading
    assert wrong > (0.02 if name == "weights-8bit" else 0.2), (name, wrong)


def test_the_reference_follows_a_selection_at_its_near_ties_only():
    """The near-tie rule on a hand case: scores 5, 4, 3.001, 3, 1 with K = 3.
    The system took {0, 1, 3} where the reference takes {0, 1, 2}: the sets
    differ in positions 2 and 3, 0.001 and 0 from the threshold 3.001. A
    system that took position 4 (2.001 away) is not followed."""
    score = jnp.asarray([[5.0, 4.0, 3.001, 3.0, 1.0]])
    own, thr = ref.top_mask(score, 3)
    assert own.tolist() == [[True, True, True, False, False]]
    assert float(thr[0, 0]) == pytest.approx(3.001)
    tied, _ = ref.top_mask(jnp.asarray([[2.0, 1.0, 1.0, 1.0, 0.5]]), 2)
    assert tied.tolist() == [[True, True, False, False, False]]  # the lowest

    def far(theirs):
        sys = np.zeros(5, bool)
        sys[theirs] = True
        differ = sys != np.asarray(own[0])
        return float(np.abs(np.asarray(score[0]) - 3.001)[differ].max())

    assert far([0, 1, 3]) == pytest.approx(0.001, abs=1e-5)   # followed
    assert far([0, 1, 4]) == pytest.approx(2.001, abs=1e-5)   # never


# ------------------------------------------------------------ the chip's share
def test_the_shares_sum_to_the_uncut_layer(tiny):
    """The parts all four two-expert shares of an expert layer give, the
    shared expert counted once, add up to what the uncut reference gives for
    the whole layer (the model-configs guide's test of the cut)."""
    cfg, _, _ = tiny
    whole_cfg = dataclasses.replace(cfg, moe_experts_held=0, moe_first_held=0)
    whole = build_model(whole_cfg)
    params = whole.init(jax.random.PRNGKey(3))
    layer = jax.tree.map(lambda a: a[1],
                         whole.segment_params(params["layers"])[1])
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.d_model), F32)
    c = dict(published(whole_cfg), first_held=0)
    want, _ = ref.run_highest(lambda y, w: ref.experts(y, w, c), y[0], layer)
    shared = whole._with_shared(jnp.zeros((24, cfg.d_model), F32), y[0],
                                layer)
    parts = []
    for first in range(0, cfg.num_experts, 2):
        held = build_model(dataclasses.replace(cfg, moe_first_held=first))
        mine = {k: v[first:first + 2] if k in held.BANKS else v
                for k, v in layer.items()}
        out, stats, idx = held.experts(y, mine)
        parts.append(out[0] - shared)
        assert idx.max() < cfg.num_experts and stats[3] <= 24 * cfg.moe_top_k
    assert close(sum(parts) + shared, want)
    # and attention is whole on every share: nothing of it is cut
    assert cache_bytes_per_token(cfg, F32) \
        == cache_bytes_per_token(whole_cfg, F32)


# ------------------------------------------------------- a slot at length 0
def test_a_slot_at_length_0_keeps_its_rows_and_keys(tiny):
    cfg, model, params = tiny
    state = init_slots(cfg, 3, 128, F32)
    key = jax.random.PRNGKey(7)
    cache = state.cache._replace(
        c=jax.random.normal(key, state.cache.c.shape, F32),
        ik=jax.random.normal(key, state.cache.ik.shape, F32),
        length=jnp.asarray([20, 0, 5], jnp.int32))
    lg, new = forward_with_cache(model, params, ids_of(cfg, 1, rows=3),
                                 cache, flash_decode=True)
    assert new.length.tolist() == [21, 0, 6]
    for name in ("c", "ik"):
        a, b = np.asarray(getattr(cache, name)), np.asarray(getattr(new, name))
        assert np.array_equal(a[:, 1], b[:, 1]), name       # bit-equal
        assert not np.array_equal(a[:, 0], b[:, 0]), name
    assert bool(jnp.isfinite(lg).all())


def test_the_bytes_are_the_arrays_own(tiny):
    cfg, _, _ = tiny
    for dtype, per_token in ((jnp.bfloat16, 7 * 512 + 2 * 32),
                             (F32, 7 * 512 + 2 * 64)):
        cache = jax.eval_shape(lambda: init_cache(cfg, 2, 128, dtype))
        held = sum(np.prod(b.shape) * b.dtype.itemsize
                   for b in (cache.c, cache.ik))
        assert cache_bytes_per_token(cfg, dtype) == per_token
        assert per_token * 2 * 128 == held
    # at the published widths: a row of 384 words a layer, 128 values a key
    big = glm_moe_dsa("5.2")
    assert sparse.row_layout(big.latent_dim, jnp.bfloat16)[0] == 384
    assert cache_bytes_per_token(
        dataclasses.replace(big, n_layer=7, index_pattern="FsssFss"),
        jnp.bfloat16) == 7 * 1536 + 2 * 256


# ------------------------------------------------------------- the kernels
@pytest.mark.parametrize("dtype,tol", [(F32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_the_sparse_kernel_reads_what_plain_jnp_reads(dtype, tol):
    """Interpret mode against ``attend_selected`` on ragged lengths, indices
    that repeat and rows padded with -1; the append lands where it should
    and nowhere else."""
    L, B, S, D, H, K, rank = 2, 4, 256, 40, 4, 48, 32
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    lat = jax.random.normal(next(keys), (L, B, S, D), F32).astype(dtype)
    cache = sparse.pack_rows(lat, dtype)
    assert np.array_equal(
        np.asarray(sparse.unpack_rows(cache, D, dtype), np.float32),
        np.asarray(lat, np.float32))
    q = jax.random.normal(next(keys), (B, H, D), F32).astype(dtype)
    new = jax.random.normal(next(keys), (B, D), F32).astype(dtype)
    length = jnp.asarray([0, 30, 200, 256], jnp.int32)
    rows = []
    for b, n in enumerate(length.tolist()):
        if n >= K:      # any K live positions, some of them twice
            row = jax.random.randint(jax.random.PRNGKey(9 + b), (K,), 0, n)
        else:           # all of them, the rest padding
            row = jnp.pad(jnp.arange(n), (0, K - n), constant_values=-1)
        rows.append(row)
    idx = jnp.stack(rows).astype(jnp.int32)
    o, after = sparse.sparse_mla_decode_attention(
        q, cache, new, idx, length, layer=jnp.int32(1), rank=rank, scale=0.3,
        group=16, interpret=True)
    want = np.asarray(lat, np.float32).copy()
    for b, n in enumerate(length.tolist()):
        if n:
            want[1, b, n - 1] = np.asarray(new[b], np.float32)
    assert np.array_equal(
        np.asarray(sparse.unpack_rows(after, D, dtype), np.float32), want)
    ref_o = sparse.attend_selected(q, jnp.asarray(want[1]).astype(dtype), idx,
                                   length, rank=rank, scale=0.3)
    assert float(jnp.abs(o.astype(F32) - ref_o.astype(F32)).max()) <= tol
    assert float(jnp.abs(o[0]).max()) == 0.0        # length 0: nothing read


def test_the_score_kernel_scores_the_live_keys_alone():
    B, H, D, S = 3, 3, 16, 256
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 4))
    q = jax.random.normal(next(keys), (B, H, D), F32)
    w = jax.random.normal(next(keys), (B, H), F32)
    ik = jax.random.normal(next(keys), (2, B, D, S), F32)
    length = jnp.asarray([0, 77, 256], jnp.int32)
    got = sparse.index_scores(q, w, ik, length, layer=jnp.int32(1), block=128,
                              interpret=True)
    want = dsa.scores(q[:, None], w[:, None], ik[1])[:, 0]
    live = jnp.arange(S)[None] < length[:, None]
    assert bool((got == -jnp.inf)[~live].all())
    assert float(jnp.abs(jnp.where(live, got - want, 0.0)).max()) < 1e-5


# --------------------------------------------------- a chunk's attention
WIDTHS = {"tiny": (4, 16, 8, 16, 32), "glm": (2, 192, 64, 256, 512)}


def _mask(case, rng, T, S, pos):
    causal = np.arange(S)[None] <= np.asarray(pos)[:, None]
    if case == "all":           # fewer live keys than index_topk: every one
        return causal
    mask = causal & (rng.random((T, S)) < 0.5)
    if case == "a block out":   # keys 128..255 hidden from half the queries,
        mask[::2, 128:256] = False
        mask[1] = False         # ... and a query that may see nothing
    return mask


# (T, the chunk's first position, max_len, the mask): blocks of 128 keys
CHUNKS = {
    "a live length inside a block": (32, 150, 384, "half"),
    "a chunk that starts at 0": (64, 0, 256, "half"),
    "under index_topk": (32, 0, 256, "all"),
    "a final bucket of 8": (8, 300, 384, "half"),
    "a final bucket of 16": (16, 300, 384, "half"),
    "13 queries": (13, 243, 256, "half"),
    "a final bucket of 64": (64, 200, 384, "half"),
    "a key block left out": (32, 352, 384, "a block out"),
    "the cache's last block": (48, 464, 512, "half"),
}


@pytest.mark.parametrize("dtype,tol", [(F32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["a float32 cache", "packed rows"])
@pytest.mark.parametrize("case,widths", [(case, "tiny") for case in CHUNKS] + [
    ("a live length inside a block", "glm"), ("a final bucket of 16", "glm")])
def test_the_chunk_kernel_attends_what_the_walk_attends(case, widths, dtype,
                                                        tol):
    """``sparse_mla_chunk_attention`` in interpret mode against
    ``mla.attend_expanded(selected=)`` reading the same rows: two heads a
    program, blocks of 128 keys, the layer read the second of two; at the
    test's widths and, two cases, at the published ones (``k_rope`` beside
    the last 64 of ``k_nope``'s 192 columns)."""
    T, start, S, kind = CHUNKS[case]
    H, nope, rope, vd, rank = WIDTHS[widths]
    cfg = dataclasses.replace(
        glm_moe_dsa("tiny"), n_head=H, qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=vd, kv_lora_rank=rank)
    rng = np.random.default_rng(len(case))
    lat = jnp.asarray(rng.standard_normal((1, S, rank + rope)), dtype)
    cache = jnp.concatenate([sparse.pack_rows(lat * 0, dtype)[None],
                             sparse.pack_rows(lat, dtype)[None]])
    p = {"wkv_b": jnp.asarray(rng.standard_normal(
        (rank, H * (nope + vd))) / np.sqrt(rank), dtype)}
    qn = jnp.asarray(rng.standard_normal((1, T, H, nope)), dtype)
    qr = jnp.asarray(rng.standard_normal((1, T, H, rope)), dtype)
    pos = start + jnp.arange(T, dtype=jnp.int32)
    mask = jnp.asarray(_mask(kind, rng, T, S, pos))[None]

    def read(j, blk):
        rows = jax.lax.dynamic_slice(cache, (1, 0, j * blk, 0, 0),
                                     (1, 1, blk) + cache.shape[3:])[0]
        return sparse.unpack_rows(rows, rank + rope, dtype).transpose(0, 2, 1)

    want = mla.attend_expanded(cfg, p, qn, qr, (read, S), pos[None],
                               start + T, block=128, selected=mask)
    got = jax.jit(lambda n: sparse.sparse_mla_chunk_attention(
        qn, qr, mla._wkv_b(cfg, p, dtype), cache, mask.astype(jnp.int8), n,
        layer=jnp.int32(1), rank=rank, scale=mla.softmax_scale(cfg), heads=2,
        block=128, interpret=True))(jnp.int32(start + T))
    assert got.shape == want.shape == (1, T, H, vd)
    err = jnp.abs(got.astype(F32) - want.astype(F32)).max()
    assert float(err) <= tol * float(jnp.abs(want.astype(F32)).max())
    if kind == "a block out":
        assert float(jnp.abs(got[0, 1]).max()) == 0.0   # nothing to see: 0


def _chunks(model, params, ids, cuts, flash, dtype, max_len=128):
    """``ids`` prefilled in chunks ending at ``cuts``: (logits, the cache
    before the last chunk, the cache behind it)."""
    cache = init_cache(model.cfg, 1, max_len, dtype)
    fwd = jax.jit(lambda p, ids, cache: forward_with_cache(
        model, p, ids, cache, flash_decode=flash))
    out, at = [], 0
    for cut in cuts:
        before = cache
        lg, cache = fwd(params, ids[:, at:cut], cache)
        out.append(lg)
        at = cut
    return jnp.concatenate(out, 1), before, cache


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_chunked_prefill_on_the_kernel_is_the_walk_s(tiny, dtype):
    """Chunks of 32, 32 and a bucket of 16 through ``forward_with_cache``
    with the kernels on: the walk's logits, the first layer's rows and the
    first indexer's keys bit-equal (nothing attended stands in front of
    them), nothing outside the chunk's positions touched in any layer. In
    float32 to 2e-5 of the largest logit; in bf16 (packed rows; a trunk of
    dense layers, so that no expert is chosen otherwise at a near-tie) a
    position's worst logit within 2.5e-2 in the median — the second
    indexer picks another key for a few positions, whose logits move by a
    tenth."""
    cfg, model, params = tiny
    if dtype != F32:
        cfg = glm_moe_dsa("tiny", dtype=dtype, moe_first_dense=cfg.n_layer)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
    ids = ids_of(cfg, 80, seed=3)
    cuts = (32, 64, 80)
    walk, _, c0 = _chunks(model, params, ids, cuts, False, dtype)
    got, before, c1 = _chunks(model, params, ids, cuts, True, dtype)
    assert c1.c.dtype == (jnp.uint32 if dtype != F32 else F32)
    if dtype == F32:
        assert close(got, walk)
    else:
        worst = jnp.abs(got - walk).max(-1) / jnp.abs(walk).max()
        assert float(jnp.median(worst)) <= 2.5e-2 and float(worst.max()) < 0.2
    assert np.array_equal(np.asarray(c0.c[0]), np.asarray(c1.c[0]))
    assert np.array_equal(np.asarray(c0.ik[0]), np.asarray(c1.ik[0]))
    lo, hi = cuts[-2], cuts[-1]
    for name, axis in (("c", 2), ("ik", 3)):
        a = np.moveaxis(np.asarray(getattr(before, name)), axis, 0)
        b = np.moveaxis(np.asarray(getattr(c1, name)), axis, 0)
        assert np.array_equal(a[:lo], b[:lo]), name
        assert np.array_equal(a[hi:], b[hi:]), name
        assert not np.array_equal(a[lo:hi], b[lo:hi]), name


@pytest.mark.parametrize("what,T,max_len,flash,walks", [
    ("the kernel", 32, 128, True, 0),
    ("a bucket of 8", 8, 128, True, 0),
    ("a cache of no whole lane block", 32, 96, True, 1),
    ("the kernels off", 32, 128, False, 0),
])
def test_a_chunk_traced_onto_the_walk_is_counted(tiny, what, T, max_len,
                                                 flash, walks):
    """``Serve/chunk_attention_fallback_builds``: one for every chunk program
    traced onto ``mla.attend_expanded`` while the kernels are on; the
    kind's own answer (what the ``prefill_chunk`` span says) agrees."""
    from deepspeed_tpu.observability.metrics import get_registry

    cfg, model, params = tiny
    counter = get_registry().counter("Serve/chunk_attention_fallback_builds")
    before = counter.value
    cache = init_cache(cfg, 1, max_len, F32)
    text = str(jax.make_jaxpr(lambda p, ids, cache: forward_with_cache(
        model, p, ids, cache, flash_decode=flash))(
            params, ids_of(cfg, T), cache))
    assert counter.value - before == walks
    kind = kind_of(cfg, 1, F32)
    kind.flash, kind.max_len = flash, max_len
    took = flash and not walks
    assert kind.chunk_kernel(flash, T, max_len, F32, F32) == took
    assert ("sparse_mla_chunk_attention" in text) == took


def test_a_chunk_s_span_says_what_attended_and_over_how_many_keys(tiny):
    from deepspeed_tpu.serving.scheduler import ChunkPlan

    cfg, _, _ = tiny
    kind = kind_of(cfg, 2, F32)
    chunk = ChunkPlan(start=64, ids=np.zeros(32, np.int32))
    assert kind.chunk_meta(chunk)["attn_live_keys"] == 96
    assert kind.chunk_meta(chunk)["attn_kernel"] is False   # no engine's
    kind.flash, kind.max_len = True, 128
    assert kind.chunk_meta(chunk)["attn_kernel"] is True
    kind.max_len = 96
    assert kind.chunk_meta(chunk)["attn_kernel"] is False


# ------------------------------------------- the step's read of live blocks
def _lengths_and_the_rest(dtype, D, B, S, seed=0, L=2, H=4):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    lat = jax.random.normal(next(keys), (L, B, S, D), F32).astype(dtype)
    q = jax.random.normal(next(keys), (B, H, D), F32).astype(dtype)
    new = jax.random.normal(next(keys), (B, D), F32).astype(dtype)
    score = jax.random.normal(next(keys), (B, 1, S), F32)
    return lat, sparse.pack_rows(lat, dtype), q, new, score


def _appended(lat, new, length, layer=1):
    want = np.asarray(lat, np.float32).copy()
    for b, n in enumerate(np.asarray(length).tolist()):
        if n:
            want[layer, b, n - 1] = np.asarray(new[b], np.float32)
    return want


@pytest.mark.parametrize("dtype,D,tol", [
    (jnp.bfloat16, 40, 2e-2), (F32, 40, 1e-5), (jnp.bfloat16, 300, 2e-2)],
    ids=["bf16 packed with a rope part", "f32", "bf16 into the high halves"])
def test_the_dense_read_is_the_selected_read(dtype, D, tol):
    """With the selection's mask the kernel walks a slot's live blocks whole
    (every slot here stands on that side of the rule) and gives what
    ``attend_selected`` gives over ``dsa.select``'s positions: lengths 0 and
    1, under, at and over ``index_topk`` and a block's edge, the cache's
    end; the appended position among the selected (it is scored highest);
    every other position of the cache bit-untouched; length 0 exactly 0."""
    K, S, rank, block = 48, 512, 32, 128
    length = jnp.asarray([0, 1, 47, 48, 49, 127, 128, 129, 300, 512],
                         jnp.int32)
    B = len(length)
    lat, cache, q, new, score = _lengths_and_the_rest(dtype, D, B, S)
    pos = jnp.maximum(length - 1, 0)[:, None]
    score = jnp.where(jnp.arange(S)[None, None] == pos[..., None], 9.0,
                      score)
    idx, mask = dsa.select(score, pos, K)
    idx = idx[:, 0]
    assert all(n - 1 in row for n, row in zip(length.tolist()[1:],
                                              np.asarray(idx)[1:]))
    assert bool(np.all(sparse.reads_dense(
        np.asarray(length), np.minimum(np.asarray(length), K), 1,
        cache.shape[-1] * cache.dtype.itemsize)))
    o, after = sparse.sparse_mla_decode_attention(
        q, cache, new, idx, length, layer=jnp.int32(1), rank=rank, scale=0.3,
        group=16, mask=sparse.step_mask(mask), block=block, interpret=True)
    want = _appended(lat, new, length)
    assert np.array_equal(
        np.asarray(sparse.unpack_rows(after, D, dtype), np.float32), want)
    ref_o = sparse.attend_selected(q, jnp.asarray(want[1]).astype(dtype), idx,
                                   length, rank=rank, scale=0.3)
    assert float(jnp.abs(o.astype(F32) - ref_o.astype(F32)).max()) <= tol
    assert float(jnp.abs(o[0]).max()) == 0.0        # length 0: nothing read
    # ... and it is not the read of everything live: the mask decides
    everything = sparse.attend_selected(
        q, jnp.asarray(want[1]).astype(dtype),
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), length,
        rank=rank, scale=0.3)
    assert float(jnp.abs(o[-1].astype(F32)
                         - everything[-1].astype(F32)).max()) > 10 * tol


def test_a_slot_reads_as_the_rule_says_and_as_it_would_alone(tiny):
    """One batch with slots on both sides of the crossover. The kernel is
    handed a mask that holds ANOTHER selection than ``idx``, so a slot's
    result says which fetch it took: the mask's positions where
    ``reads_dense`` says dense, ``idx``'s elsewhere — the rule the host's
    mirror counts by (``dsa_dense_share``, ``dsa_rows_read_over_selected``
    of the ``decode_step`` span), at the kind's own widths. Every slot
    equals the slot alone in a batch of one."""
    cfg, _, _ = tiny
    D, rank, K, S = cfg.latent_dim, cfg.kv_lora_rank, cfg.index_topk, 2048
    kind = kind_of(cfg, 4, F32)
    kind.flash, kind.max_len = True, S
    row_bytes = sparse.row_layout(D, F32)[0] * 4
    edge = int(sparse.crossover(1, row_bytes) * 16) * K // 16
    assert 128 < edge < S - 1
    lens = np.asarray([20, 0, edge, edge + 1, S], np.int32)
    length, B = jnp.asarray(lens), len(lens)
    lat, cache, q, new, score = _lengths_and_the_rest(F32, D, B, S, seed=3)
    pos = jnp.maximum(length - 1, 0)[:, None]
    idx = dsa.select(score, pos, K, want_mask=False)[0][:, 0]
    other, mask = dsa.select(-score, pos, K)

    def read(rows, mask_rows):
        return sparse.sparse_mla_decode_attention(
            q[rows], cache[:, rows], new[rows], idx[rows], length[rows],
            layer=jnp.int32(1), rank=rank, scale=0.3, group=8,
            mask=sparse.step_mask(mask_rows), block=128, interpret=True)[0]

    o = read(slice(None), mask)
    after = jnp.asarray(_appended(lat, new, length)[1])
    by_idx, by_mask = (sparse.attend_selected(
        q, after, sel, length, rank=rank, scale=0.3)
        for sel in (idx, other[:, 0]))
    dense = sparse.reads_dense(lens, np.minimum(lens, K), 1, row_bytes)
    assert dense.tolist() == [True, True, True, False, False]
    for b in range(B):
        took, left = (by_mask, by_idx) if dense[b] else (by_idx, by_mask)
        assert float(jnp.abs(o[b] - took[b]).max()) <= 1e-5, b
        assert lens[b] < K or float(jnp.abs(o[b] - left[b]).max()) > 1e-3, b
        alone = read(slice(b, b + 1), mask[b:b + 1])
        assert np.array_equal(np.asarray(alone[0]), np.asarray(o[b])), b
    # the host's count is the kernel's: two of the four running slots
    # dense; rows by hand: each of the two walks its live rows in whole
    # blocks of DENSE_BLOCK, the two others fetch their 16 selected
    meta = kind.step_meta([], [], lens, {})
    assert meta["dsa_dense_share"] == 2 / 4 == float(dense[lens > 0].mean())
    blk = min(sparse.DENSE_BLOCK, S)
    walked = -(-20 // blk) * blk + -(-edge // blk) * blk
    assert meta["dsa_rows_read_over_selected"] == (walked + 2 * K) / (4 * K)
    assert meta["dsa_selected"] == 4 * K
    # ... and off the kernels nothing reads by the rule
    kind.flash = False
    assert "dsa_dense_share" not in kind.step_meta([], [], lens, {})


def test_the_rows_a_read_brings_in_by_hand():
    """GLM-5.2's widths (rows of 1536 B, 2048 selected): a slot of 17 700
    reads dense — whole blocks of DENSE_BLOCK, 9 rows a selected one — and
    a slot of 131 072 gathers its 2048; the layout's cost stays 1536 /
    1152."""
    from deepspeed_tpu.inference.kinds.sparse_latent import read_meta

    kind = kind_of(glm_moe_dsa("5.2"), 2, jnp.bfloat16)
    kind.flash, kind.max_len = True, 131072
    assert 16 < sparse.crossover(1, 1536) < 32
    walked = -(-17700 // sparse.DENSE_BLOCK) * sparse.DENSE_BLOCK
    one = read_meta(kind, np.asarray([17700]), np.asarray([2048]), 1)
    assert one == {"dsa_dense_share": 1.0,
                   "dsa_rows_read_over_selected": walked / 2048}
    assert 8.6 < walked / 2048 <= 9.0
    far = read_meta(kind, np.asarray([131072]), np.asarray([2048]), 1)
    assert far == {"dsa_dense_share": 0.0,
                   "dsa_rows_read_over_selected": 1.0}
    both = kind.step_meta([], [], np.asarray([17700, 0, 131072]), {})
    assert both["dsa_dense_share"] == 0.5
    assert both["dsa_rows_read_over_selected"] == (walked + 2048) / 4096
    assert both["dsa_fetched_over_selected"] == 1536 / 1152

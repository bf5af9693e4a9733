"""Latent attention (MLA), sigmoid-routed experts and block kinds in the
scanned trunk: the system against the plain reference
(``benchmark/reference/deepseek_v3.py``) at tiny sizes, float32, seeded
random weights; and GPT-2's cache and decode outputs as the parent commit
gave them."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v3 as ref
from deepspeed_tpu.inference.decode import (GenCarry, LatentCache,
                                            cache_bytes_per_token,
                                            cache_layout, forward_with_cache,
                                            init_cache)
from deepspeed_tpu.models import build_model, deepseek_v3, gpt2, tiny_test
from deepspeed_tpu.models import mla
from deepspeed_tpu.serving.slots import init_slots, insert_request

PUBLISHED = {
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "q_lora_rank": None, "rope_scaling": None}


@pytest.fixture(scope="module")
def tiny():
    cfg = deepseek_v3("tiny", dtype=jnp.float32, moe_routed_scale=2.448)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    ref.configure(PUBLISHED)
    return cfg, model, params


def one_device_mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def ref_logits(params, ids):
    return np.asarray(ref.run_highest(ref.logits, params, jnp.asarray(ids)))


def test_full_forward_against_the_reference(tiny):
    cfg, model, params = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 37))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(ids, jnp.int32)))
    np.testing.assert_allclose(got, ref_logits(params, ids), atol=2e-4)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_chunked_prefill_then_slot_decode_against_the_reference(tiny, flash):
    """A 41-token prompt prefilled in chunks of 16 into a batch-1 cache,
    seated in slot 1 of a 3-slot cache, then 6 teacher-forced decode steps
    through the slot batch: the logits at every decoded position equal the
    reference's full forward over the whole sequence."""
    cfg, model, params = tiny
    S, P, n = 128, 41, 6
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (1, P + n))
    want = ref_logits(params, ids)[0]
    with jax.default_matmul_precision("highest"):
        cache = init_cache(cfg, 1, S)
        assert isinstance(cache, LatentCache)
        for a in range(0, P, 16):
            chunk = jnp.asarray(ids[:, a:min(a + 16, P)], jnp.int32)
            lg, cache = forward_with_cache(
                model, params, chunk, cache._replace(length=jnp.int32(a)))
        np.testing.assert_allclose(np.asarray(lg[0, -1]), want[P - 1],
                                   atol=2e-4)
        state = init_slots(cfg, 3, S)
        pf = GenCarry(tok=jnp.asarray(ids[:, P], jnp.int32), cache=cache,
                      rng=jnp.zeros((1, 2), jnp.uint32),
                      done=jnp.zeros((1,), bool))
        state = insert_request(state, jnp.int32(1), pf)
        assert state.cache.c.shape == (cfg.n_layer, 3, cfg.latent_dim, S)
        cache = state.cache
        for t in range(n):
            tok = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(int(ids[0, P + t]))
            lg, cache, stats = forward_with_cache(
                model, params, tok, cache, flash_decode=flash,
                with_stats=True)
            np.testing.assert_allclose(np.asarray(lg[1, 0]), want[P + t],
                                       atol=2e-4)
        assert stats.shape == (2, 4) and float(stats[:, 2].min()) >= 6
        # every expert is held here: the held rows are the rows routed
        assert float(stats[:, 3].min()) == 3 * cfg.moe_top_k


def test_absorbed_step_equals_the_expanded_path_on_the_same_cache(tiny):
    """One layer's T = 1 read three ways over the same cached latents:
    expanded K and V (as prefill attends), absorbed in XLA, absorbed in the
    Pallas kernel after its in-place append."""
    from deepspeed_tpu.ops.mla_attention import (latent_append,
                                                 mla_decode_attention)

    cfg, model, params = tiny
    p = jax.tree.map(lambda a: a[1], model.segment_params(params["layers"])[1])
    rng = np.random.default_rng(2)
    B, S, D = 3, 256, cfg.latent_dim
    lengths = jnp.asarray([200, 1, 77], jnp.int32)       # AFTER the append
    cache = jnp.asarray(rng.normal(size=(2, B, D, S)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    pos = (lengths - 1)[:, None]
    with jax.default_matmul_precision("highest"):
        q_nope, q_rope, new = mla.project(cfg, y, p, pos)
        appended = latent_append(cache, new[:, 0], lengths, layer=1)
        slab = appended[1]
        for b in range(B):      # only position length-1 of layer 1 changed
            at = int(lengths[b]) - 1
            np.testing.assert_array_equal(np.asarray(slab[b, :, at]),
                                          np.asarray(new[b, 0]))
        untouched = np.asarray(appended).copy()
        for b in range(B):
            untouched[1, b, :, int(lengths[b]) - 1] = np.asarray(
                cache[1, b, :, int(lengths[b]) - 1])
        np.testing.assert_array_equal(untouched, np.asarray(cache))
        expanded = mla.attend_expanded(cfg, p, q_nope, q_rope, slab, pos,
                                       jnp.max(lengths))
        q = mla.absorb_q(cfg, p, q_nope, q_rope)
        absorbed = mla.absorb_o(cfg, p, mla.attend_absorbed(cfg, q, slab,
                                                            lengths))
        kernel = mla.absorb_o(cfg, p, mla_decode_attention(
            q, appended, lengths, layer=1, rank=cfg.kv_lora_rank,
            scale=mla.softmax_scale(cfg)))
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(expanded),
                               atol=1e-5)


# (dtype, H, rank, rope, blocks of 128 in the cache, blocks a turn): two
# blocks a turn over a cache of seven (the width does not divide it), in
# both dtypes; heads that fill no sublane tile; three blocks a turn over
# eleven; one a turn (no lane is ever stale, nothing is zeroed); and the
# published widths under the module's own byte target
WALKS = {
    "float32": (jnp.float32, 4, 32, 8, 7, 2),
    "bfloat16": (jnp.bfloat16, 4, 32, 8, 7, 2),
    "five heads": (jnp.float32, 5, 32, 8, 7, 2),
    "three a turn": (jnp.float32, 4, 32, 8, 11, 3),
    "one a turn": (jnp.float32, 4, 32, 8, 3, 1),
    "published widths": (jnp.bfloat16, 32, 512, 64, 10, None),
}


@pytest.mark.parametrize("check", ["reference", "neighbours", "idle"])
@pytest.mark.parametrize("walk", WALKS)
def test_the_step_s_kernel_walks_each_slot_s_live_latents(monkeypatch, walk,
                                                          check):
    """``mla_decode_attention`` against ``mla.attend_absorbed`` on one cache
    with ragged lengths a slot, at a layer other than 0: nothing, one
    position, around a block's edge, one turn exactly, one turn and a
    position, several turns, the whole cache and a length past it (clamped).
    ``neighbours``: a slot's output is bit-equal whatever stands beside it;
    ``idle``: a slot at length 0 gives zeros, and costs its neighbours
    nothing (behind it a program fetches for itself)."""
    from types import SimpleNamespace

    from deepspeed_tpu.ops import mla_attention

    dtype, H, rank, rope, blocks, W = WALKS[walk]
    D, S = rank + rope, blocks * 128
    if W is None:
        W = mla_attention.turn_blocks(D, S, dtype)
        assert 1 < W < blocks
    else:
        monkeypatch.setattr(mla_attention, "_TURN_BYTES",
                            W * D * 128 * jnp.dtype(dtype).itemsize)
        assert mla_attention.turn_blocks(D, S, dtype) == W
    turn = W * 128
    cfg = SimpleNamespace(kv_lora_rank=rank, qk_nope_head_dim=24,
                          qk_rope_head_dim=rope)
    lengths = [0, 1, 127, 128, 129, turn, min(turn + 1, S),
               min(2 * turn + 77, S - 5), S, S + 300]
    B = len(lengths)
    rng = np.random.default_rng(5)
    cache = jnp.asarray(rng.normal(size=(2, B, D, S)), dtype)
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)

    def kernel(q, cache, n):
        return np.asarray(mla_attention.mla_decode_attention(
            q, cache, jnp.asarray(n, jnp.int32), layer=1, rank=rank,
            scale=mla.softmax_scale(cfg)).astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        got = kernel(q, cache, lengths)
        if check == "reference":
            # (in float32 over the same values: in bf16 the reference
            # rounds its scores, the kernel only ``p`` and the result)
            want = np.asarray(mla.attend_absorbed(
                cfg, q.astype(jnp.float32), cache[1].astype(jnp.float32),
                jnp.asarray(lengths)))
            tol = dict(atol=1e-5) if dtype == jnp.float32 \
                else dict(atol=2e-2, rtol=2e-2)
            np.testing.assert_allclose(got[1:], want[1:], **tol)
        elif check == "neighbours":
            for b in (2, 6, 7, 9):
                alone = kernel(q[b:b + 1], cache[:, b:b + 1], lengths[b:b + 1])
                np.testing.assert_array_equal(alone[0], got[b])
            # the same slots behind a slot that is not running, which fetches
            # nothing ahead, and in front of one
            idle = kernel(q, cache, [0 if b % 2 else n
                                     for b, n in enumerate(lengths)])
            np.testing.assert_array_equal(idle[::2], got[::2])
        else:
            np.testing.assert_array_equal(got[0], np.zeros((H, rank)))
            np.testing.assert_array_equal(
                kernel(q, cache, [0] * B), np.zeros((B, H, rank)))


def expert_layer(model, params):
    return jax.tree.map(lambda a: a[0],
                        model.segment_params(params["layers"])[1])


def plain_experts(cfg, p, yt, idx, w):
    """Per token: sum of weight x SwiGLU of each chosen expert."""
    out = np.zeros_like(yt)
    for t in range(yt.shape[0]):
        for e, g in zip(idx[t], w[t]):
            h = jax.nn.silu(yt[t] @ p["w_gate"][e]) * (yt[t] @ p["w_in"][e])
            out[t] += g * np.asarray(h @ p["w_out"][e])
    return out


@pytest.mark.parametrize("case", ["bias", "normalised", "shared", "crowded"])
def test_router_and_expert_layer(tiny, case):
    cfg, model, params = tiny
    p = dict(expert_layer(model, params))
    rng = np.random.default_rng(3)
    yt = jnp.asarray(rng.normal(size=(24, cfg.d_model)), jnp.float32)
    score = np.asarray(jax.nn.sigmoid(yt @ p["router"]))
    with jax.default_matmul_precision("highest"):
        if case == "bias":
            # a bias that lifts expert 5 into every choice changes WHO is
            # chosen; the weight is still the unbiased score
            idx0, _, _ = model.route(yt, dict(p, router_bias=jnp.zeros(8)))
            p["router_bias"] = jnp.zeros(8).at[5].set(10.0)
            idx, w, _ = model.route(yt, p)
            assert (np.asarray(idx) == 5).any(axis=1).all()
            assert not (np.asarray(idx0) == 5).any(axis=1).all()
            chosen = np.take_along_axis(score, np.asarray(idx), 1)
            np.testing.assert_allclose(
                np.asarray(w), 2.448 * chosen / chosen.sum(1, keepdims=True),
                rtol=1e-5)
        elif case == "normalised":
            idx, w, _ = model.route(yt, p)
            np.testing.assert_allclose(np.asarray(w).sum(1), 2.448, rtol=1e-5)
            g, biased, _ = ref.router(yt, jax.tree.map(
                lambda a: jnp.asarray(a, jnp.float32), p), PUBLISHED)
            top = np.argsort(-np.asarray(biased), axis=1)[:, :2]
            assert (np.sort(top, 1) == np.sort(np.asarray(idx), 1)).all()
            np.testing.assert_allclose(
                np.take_along_axis(np.asarray(g), np.asarray(idx), 1),
                np.asarray(w), rtol=1e-5)
        elif case == "shared":
            y = yt.reshape(2, 12, -1)
            full, _, _ = model.experts(y, p)
            zeroed, _, _ = model.experts(y, dict(p, ws_out=0 * p["ws_out"]))
            shared = (jax.nn.silu(yt @ p["ws_gate"]) * (yt @ p["ws_in"])) \
                @ p["ws_out"]
            np.testing.assert_allclose(
                np.asarray(full - zeroed).reshape(24, -1), np.asarray(shared),
                atol=1e-5)
            idx, w, _ = model.route(yt, p)
            np.testing.assert_allclose(
                np.asarray(zeroed).reshape(24, -1),
                plain_experts(cfg, p, np.asarray(yt), np.asarray(idx),
                              np.asarray(w)), atol=1e-4)
        else:
            # every token chooses expert 0: no capacity, so no row drops
            p["router_bias"] = jnp.zeros(8).at[0].set(10.0)
            p["ws_out"] = 0 * p["ws_out"]
            out, stats, chosen = model.experts(yt[None], p)
            idx, w, _ = model.route(yt, p)
            assert (np.asarray(idx)[:, 0] == 0).all()
            assert float(stats[0]) == 24            # expert 0 got every token
            np.testing.assert_allclose(
                np.asarray(out)[0],
                plain_experts(cfg, p, np.asarray(yt), np.asarray(idx),
                              np.asarray(w)), atol=1e-4)
            # rows multiplied stay within padding of the 48 rows routed
            assert 48 <= float(stats[2]) <= 48 + float(stats[1]) * 7


def test_dense_then_expert_segments_against_a_python_loop(tiny):
    cfg, model, params = tiny
    assert cfg.segments == (("dense", 1), ("moe", 2))
    ids = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 19)), jnp.int32)
    x, positions = model._embed(params, ids)
    for seg in model.segment_params(params["layers"]):
        for i in range(jax.tree.leaves(seg)[0].shape[0]):
            x, _ = model._layer(x, jax.tree.map(lambda a: a[i], seg),
                                positions, None)
    np.testing.assert_allclose(np.asarray(model._head(params, x)),
                               np.asarray(model.apply(params, ids)),
                               atol=1e-5)


@pytest.mark.parametrize("dtype, itemsize", [(jnp.bfloat16, 2),
                                             (jnp.float32, 4)])
def test_cache_layout_of_the_latent_kind(dtype, itemsize):
    cfg = deepseek_v3("kanana-2-30b-a3b", n_layer=7, dtype=dtype)
    shape, dt = cache_layout(cfg, 48, 8192)
    assert shape == (7, 48, 576, 8192) and dt == dtype
    assert cache_bytes_per_token(cfg) == 576 * itemsize * 7
    with pytest.raises(NotImplementedError):
        cache_layout(cfg, 48, 8192, page_size=16, pages=64)
    # K and V stored expanded would cost 32 x (192 + 128) values a layer
    assert cache_bytes_per_token(gpt2("774m")) == 2 * 36 * 1280 * 2


@pytest.mark.parametrize("what, millions", [
    ("expert layer", 640.0), ("routed", 603.98), ("beside", 36.05),
    ("dense layer", 64.1), ("embedding and head", 525.3)])
def test_param_count_at_the_published_sizes(what, millions):
    cfg = deepseek_v3("kanana-2-30b-a3b")
    assert cfg.head_dim == 192 and cfg.v_dim == 128 and cfg.latent_dim == 576
    attn = cfg._attn_params_per_layer()
    got = {
        "expert layer": attn + cfg._ffn_params_per_layer(kind="moe"),
        "routed": 128 * 3 * 2048 * 768,
        "beside": attn + cfg._ffn_params_per_layer(kind="moe")
        - 128 * 3 * 2048 * 768,
        "dense layer": attn + cfg._ffn_params_per_layer(kind="dense"),
        "embedding and head": cfg.param_count() - cfg.param_count(
            non_embedding=True)}[what]
    assert round(got / 1e6, 2 if millions % 1 else 1) == pytest.approx(
        millions, abs=0.051)
    seven = deepseek_v3("kanana-2-30b-a3b", n_layer=7)
    assert seven.param_count() == 525_336_576 + 64_094_208 + 6 * 640_024_576
    # and the count is what the tree holds, norms and the bias left out
    built = build_model(deepseek_v3("tiny"))
    shapes = jax.eval_shape(built.init, jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    held = sum(int(np.prod(a.shape)) for path, a in flat
               if not any(str(getattr(k, "key", "")).endswith(
                   ("_scale", "router_bias")) for k in path))
    assert built.cfg.param_count() == held


@pytest.mark.parametrize("flash, digest, total", [
    (False, "494fc2649712fcfe", -0.1365962028503418),
    (True, "47b0756470363a88", -0.13659536838531494)],
    ids=["xla", "kernels"])
def test_gpt2_cache_and_decode_as_the_parent_gave_them(flash, digest, total):
    """The K/V path after the latent cache landed: layout, greedy tokens and
    logits of a tiny GPT-2 through prefill and four cached steps, recorded on
    the parent commit (PR 28) with this same code."""
    cfg = tiny_test(max_seq=256, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    assert cache_layout(cfg, 3, 128) == ((2, 3, 4, 16, 128), jnp.float32)
    ids = np.random.default_rng(5).integers(0, 256, (2, 9)).astype(np.int32)
    cache = init_cache(cfg, 2, 128)
    lg, cache = forward_with_cache(model, params, jnp.asarray(ids), cache,
                                   flash_decode=flash)
    outs, tok = [np.asarray(lg[:, -1])], jnp.argmax(lg[:, -1], -1)
    toks = [np.asarray(tok)]
    for _ in range(4):
        lg, cache = forward_with_cache(model, params,
                                       tok[:, None].astype(jnp.int32), cache,
                                       flash_decode=flash)
        tok = jnp.argmax(lg[:, 0], -1)
        toks.append(np.asarray(tok))
        outs.append(np.asarray(lg[:, 0]))
    assert np.stack(toks).T.tolist() == [[25, 190, 156, 94, 193],
                                         [30, 61, 30, 215, 121]]
    a = np.stack(outs)
    np.testing.assert_allclose(float(a.sum()), total, rtol=1e-5)
    if hashlib.sha256(a.tobytes()).hexdigest()[:16] != digest:
        # another CPU may round a fused multiply differently; the sum
        # above and the tokens have to hold on any
        pytest.skip("logits equal the parent's to 1e-5, not bit for bit, "
                    "on this CPU")


def test_serving_refuses_what_does_not_compose_with_a_latent_cache(tiny):
    import deepspeed_tpu as ds

    cfg, model, params = tiny
    eng = ds.init_inference(model, params, {"dtype": "float32"},
                            mesh=one_device_mesh())
    with pytest.raises(ValueError, match="a mesh of several devices"):
        ds.ServingEngine(ds.init_inference(model, params,
                                           {"dtype": "float32"}),
                         {"slots": 2, "max_len": 128, "prefill_chunk": 16})
    for bad in ({"page_size": 8, "pool_pages": 64}, {"kv_quant_bits": 8,
                "page_size": 8, "pool_pages": 64}):
        with pytest.raises(ValueError, match="do not yet compose"):
            ds.ServingEngine(eng, {"slots": 2, "max_len": 128,
                                   "prefill_chunk": 16, **bad})


def test_served_requests_equal_solo_generate_and_spans_carry_the_counts(tiny):
    import deepspeed_tpu as ds

    cfg, model, params = tiny
    eng = ds.init_inference(model, params, {"dtype": "float32"},
                            mesh=one_device_mesh())
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 128,
                                 "prefill_chunk": 16, "temperature": 0.9,
                                 "top_k": 30, "spans": True})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 21, 33)]
    outs = srv.serve_batch(prompts, [6, 9, 4], seeds=[1, 2, 3])
    for p, o, n, s in zip(prompts, outs, [6, 9, 4], [1, 2, 3]):
        solo = eng.generate(p[None], n, request_seeds=[s], temperature=0.9,
                            top_k=30, cache_len=128)
        assert o.tolist() == np.asarray(solo)[0].tolist()
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    chunks = [e for e in srv.spans.events() if e.kind == "prefill_chunk"]
    assert steps and all(e.meta["cache_bytes_per_token"] == 3 * 40 * 4
                         for e in steps)
    assert all(1.0 <= e.meta["moe_rows_over_routed"] <= 8.0 for e in steps)
    assert all(1.0 <= e.meta["experts_touched"] <= 8.0 for e in steps)
    assert all(e.meta["moe_load_max_over_mean"] >= 1.0 for e in steps)
    assert all(1.0 <= e.meta["moe_rows_over_routed"] <= 8.0 for e in chunks)


def test_apply_hands_back_its_routing_and_the_reference_can_follow_it(tiny):
    """``apply(return_aux=True)`` of a sigmoid-routed trunk gives the chosen
    experts (expert layers, B, S, k) from the same program as the logits.
    The reference takes them only for tokens whose own k-th and (k+1)-th
    scores lie within the gap: following a WRONG routing everywhere changes
    its logits, following it nowhere (gap 0) does not."""
    cfg, model, params = tiny
    ids = jnp.asarray(np.random.default_rng(6).integers(
        0, cfg.vocab_size, (1, 23)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, routing = model.apply(params, ids, return_aux=True)
    assert routing.shape == (2, 1, 23, 2) and routing.dtype == jnp.int32
    own = ref_logits(params, ids)
    np.testing.assert_allclose(np.asarray(logits), own, atol=2e-4)

    def following(theirs, gap):
        out, n = ref.run_highest(
            lambda p, i, t: ref.logits(p, i, follow=t, gap=gap), params, ids,
            theirs)
        return np.asarray(out), int(n)

    same, n = following(routing, 1e9)     # the system's routing IS its own
    np.testing.assert_allclose(same, own, atol=1e-5)
    assert n == 0
    wrong = (routing + 3) % cfg.num_experts
    kept, n = following(wrong, 0.0)
    np.testing.assert_array_equal(kept, own)
    assert n == 0
    led, n = following(wrong, 1e9)
    assert n == 2 * 23 and np.abs(led - own).max() > 1e-2
    some, n = following(wrong, 0.02)      # only the near-ties follow
    assert 0 < n < 2 * 23

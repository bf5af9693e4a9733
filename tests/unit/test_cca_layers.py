"""Compressed convolutional attention behind the zaya router (ZAYA1) at a
small size, seeded, against the plain reference (``benchmark/reference/
zaya.py``): the full forward, prefill in chunks then decode through the
slots' cache, the tails across chunk boundaries, re-seating, the router's
carried state, the decode kernel under its own name, the controls (each of
which has to fail), the counts at the uncut sizes, the spans and what is
refused."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.kinds import backlog_cca
from benchmark.reference import zaya as ref
from deepspeed_tpu.inference.decode import (CCACache, GenCarry,
                                            cache_bytes_per_token,
                                            forward_with_cache, init_cache,
                                            state_bytes_per_slot)
from deepspeed_tpu.models import build_model, cca, mimo_v2_flash, zaya
from deepspeed_tpu.ops.decode_attention import decode_attention
from deepspeed_tpu.serving.scheduler import plan_chunks
from deepspeed_tpu.serving.slots import init_slots, insert_request

PUB = dict(model_type="zaya", num_hidden_layers=3, layer_types=["hybrid"] * 3,
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           cca_time0=2, cca_time1=2, rms_norm_eps=1e-5, num_experts=4,
           num_experts_per_tok=1, router_hidden_size=16,
           tie_word_embeddings=True,
           rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                       "rope_theta": 5e6}})
F32 = jnp.float32
TOL = 2e-5
CHUNK = 64


def one_device_mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def engine(model, params, **conf):
    return ds.init_inference(model, params, {"dtype": "float32", **conf},
                             mesh=one_device_mesh())


def tiny(**over):
    return zaya("tiny", dtype=F32, **over)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """As ``test_window_layers.py``: the module's programs stay out of the
    persistent compilation cache (a worker that read an entry while another
    wrote it aborted inside the cache's reader)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def served():
    cfg = tiny()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ref.configure(PUB)
    return cfg, model, params


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def applied(model, params, ids, aux=False):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, i: model.apply(p, i, return_aux=aux))(
            params, ids)


def referred(params, ids, **kw):
    return ref.run_highest(ref.logits, params, jnp.asarray(ids), **kw)


# ---------------------------------------------------- the whole model
def test_apply_equals_the_reference(served):
    cfg, model, params = served
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 300))
    assert rel(applied(model, params, ids), referred(params, ids)) < TOL


def test_the_trunk_is_one_segment_carrying_the_routers_state(served):
    cfg, model, params = served
    assert cfg.segments == (("moe", 3),) and isinstance(params["layers"], dict)
    ids = np.random.default_rng(6).integers(0, cfg.vocab_size, (2, 40))
    logits, routing = applied(model, params, ids, aux=True)
    assert routing.shape == (3, 2, 40, 1) and routing.dtype == jnp.int32
    # away from ties the system's choices are the reference's: told to follow
    # wherever they differ (a gap no p + bias exceeds), it follows nowhere
    _, followed = referred(params, ids, follow=routing, gap=10.0)
    assert int(followed) == 0


def through_the_slots(cfg, model, params, prompts, given, chunk, slots,
                      max_len, flash, seats=None, between=None,
                      seated=None):
    """Per prompt (1 + steps, V) logits: prefill in ``chunk``s into a batch-1
    cache, seated in a slot, ``given`` tokens decoded by the slots' step
    (``test_window_layers.py``'s). ``between(cache)``: what a control does to
    the request's cache between two chunks; ``seated(cache)``: to the slots'
    cache before the first step."""
    seats = seats or [1 + 2 * i for i in range(len(prompts))]

    @jax.jit
    def prefill(p, cache, ids, start, last):
        lg, cache = forward_with_cache(
            model, p, ids, cache._replace(length=start),
            last_token_head=True, last_index=last)
        return lg[0, 0], cache

    @jax.jit
    def step(p, cache, toks):
        lg, cache = forward_with_cache(model, p, toks[:, None], cache,
                                       flash_decode=flash)
        return lg[:, 0], cache

    seat = jax.jit(insert_request)
    state = init_slots(cfg, slots, max_len, F32)
    rows = [[] for _ in prompts]
    for i, prompt in enumerate(prompts):
        cache = init_cache(cfg, 1, max_len, F32)
        for ch in plan_chunks(prompt, chunk, overlap=False):
            if between is not None and ch.start:
                cache = between(cache)
            lg, cache = prefill(params, cache, jnp.asarray(ch.ids[None]),
                                jnp.int32(ch.start),
                                jnp.int32(ch.last_index if ch.final
                                          else ch.size - 1))
        cache = cache._replace(length=jnp.int32(len(prompt)))
        rows[i].append(lg)
        state = seat(state, jnp.int32(seats[i]), GenCarry(
            tok=jnp.zeros((1,), jnp.int32), cache=cache,
            rng=jnp.zeros((1, 2), jnp.uint32), done=jnp.zeros((1,), bool)))
    cache = state.cache if seated is None else seated(state.cache)
    for t in range(len(given[0])):
        toks = np.zeros(slots, np.int32)
        toks[seats] = [g[t] for g in given]
        lg, cache = step(params, cache, jnp.asarray(toks))
        for i, s in enumerate(seats):
            rows[i].append(lg[s])
    return [jnp.stack(r) for r in rows], cache


def cache_case(cfg, lengths, steps):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    given = [rng.integers(0, cfg.vocab_size, steps).astype(np.int32)
             for _ in lengths]
    return prompts, given


def reference_rows(params, prompts, given):
    out = []
    for prompt, toks in zip(prompts, given):
        n = len(prompt)
        ids = np.concatenate([prompt, toks])[None]
        out.append(np.asarray(referred(
            params, ids, rows=tuple(range(n - 1, n + len(toks)))))[0])
    return out


# prompts: one token (h_{-1} = 0, the convs' left zeros); short; 1 and 2
# behind a chunk boundary in a padded bucket; a last chunk that fills its
# bucket; a last chunk padded by 3
LENGTHS = (1, 24, 65, 66, 80, 125)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_prefill_in_chunks_then_the_slots_step_equal_the_reference(served,
                                                                   flash):
    """Chunks of 64 into planes and tails, seated between slots at length 0,
    then 5 given tokens through the slots' step: every row against the
    reference's one full forward."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, LENGTHS, 5)
    with jax.default_matmul_precision("highest"):
        got, cache = through_the_slots(cfg, model, params, prompts, given,
                                       CHUNK, 13, 256, flash)
    for g, w in zip(got, reference_rows(params, prompts, given)):
        assert rel(g, w) < TOL
    assert (np.asarray(cache.length)[1::2]
            == [n + 5 for n in LENGTHS]).all()


def test_a_reseated_slot_reads_nothing_of_its_predecessors_tail(served):
    """A request of 40 tokens seated where one of 150 stood: the same rows
    as in a fresh slot, bit for bit; and through the serving engine, equal
    to solo ``generate()``."""
    cfg, model, params = served
    (long, short), given = cache_case(cfg, (150, 40), 4)
    with jax.default_matmul_precision("highest"):
        fresh, _ = through_the_slots(cfg, model, params, [short], given[1:],
                                     CHUNK, 2, 256, True, seats=[1])
        after, _ = through_the_slots(cfg, model, params, [long, short], given,
                                     CHUNK, 2, 256, True, seats=[1, 1])
    assert (np.asarray(after[1][1:]) == np.asarray(fresh[0][1:])).all()
    eng = engine(model, params)
    conf = {"slots": 1, "max_len": 256, "prefill_chunk": CHUNK, "greedy": True}
    served_after = ds.ServingEngine(eng, conf).serve_batch(
        [long, short], [6, 6], seeds=[1, 2])[1]
    solo = np.asarray(eng.generate(short[None], 6, request_seeds=[2],
                                   greedy=True, cache_len=256))[0]
    assert list(served_after) == list(solo)


def test_a_row_at_length_0_touches_nothing(served):
    """Whatever an idle slot's planes and tails hold, the running rows come
    out bit-equal and the idle slot's buffers stay as they were."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, (70, 30), 3)
    _, clean = through_the_slots(cfg, model, params, prompts,
                                 [g[:0] for g in given], CHUNK, 4, 128, True)
    idle, run = np.array([0, 2]), np.array([1, 3])
    dirty = clean._replace(**{
        n: getattr(clean, n).at[:, idle].set(1.5)
        for n in ("k", "v", "tail")})
    step = jax.jit(lambda p, c, t: forward_with_cache(
        model, p, t[:, None], c, flash_decode=True))
    outs = []
    for cache in (clean, dirty):
        for t in range(3):
            toks = np.zeros(4, np.int32)
            toks[run] = [g[t] for g in given]
            lg, cache = step(params, cache, jnp.asarray(toks))
        outs.append((lg, cache))
    (lg_a, a), (lg_b, b) = outs
    assert (np.asarray(lg_a)[run] == np.asarray(lg_b)[run]).all()
    for name in ("k", "v", "tail"):
        assert (np.asarray(getattr(a, name))[:, run]
                == np.asarray(getattr(b, name))[:, run]).all(), name
        assert (np.asarray(getattr(b, name))[:, idle] == 1.5).all(), name
    assert (np.asarray(b.length) == [0, 73, 0, 33]).all()


# ------------------------------------------------------------- controls
def zero_tail(cache):
    return cache._replace(tail=jnp.zeros_like(cache.tail))


def test_the_tail_zeroed_at_a_chunk_boundary_fails(served):
    """Through the cache: the second chunk's first position reads the first
    chunk's last two through the tail; zeros there are another model."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, (100,), 2)
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, CHUNK,
                                   2, 128, True, between=zero_tail)
    assert rel(got[0], reference_rows(params, prompts, given)[0]) > 1e-2


def test_the_first_decoded_token_uses_the_chunks_tail(served):
    """The tail a prompt's last chunk left is what its first step reads:
    zeroed in the slots before the steps, the first decoded row is wrong,
    where with it every row is right (the test above this block)."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, (30,), 2)
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, CHUNK,
                                   2, 128, True, seated=zero_tail)
    want = reference_rows(params, prompts, given)[0]
    assert rel(got[0][0], want[0]) < TOL           # the prefill's own row
    assert rel(got[0][1], want[1]) > 1e-2          # the first step's


@pytest.mark.parametrize("control", [c for c in backlog_cca.CONTROLS
                                     if c != "weights-8bit"])
def test_controls_of_the_reference_fail(served, control):
    """Each control of the kind (``benchmark/kinds/backlog_cca.py``: a
    piece of the reference dropped or swapped) leaves the system, which is
    2e-5 from the sound reference, hundreds of times further away."""
    cfg, model, params = served
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (1, 150))
    got = applied(model, params, ids)
    with backlog_cca.control(control, ref, params, chunk=CHUNK) as theirs:
        assert rel(got, referred(theirs, ids)) > 5e-3, control
    assert rel(got, referred(params, ids)) < TOL        # and put back


def test_the_8_bit_reference_is_told_apart(served):
    """The nearest precision below bf16 (3 mantissa bits) on every matrix of
    the reference but the router's."""
    cfg, model, params = served
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (1, 150))
    routing = applied(model, params, ids, aux=True)[1]
    with backlog_cca.control("weights-8bit", ref, params) as theirs:
        want, _ = referred(theirs, ids, follow=routing, gap=10.0)
    assert rel(applied(model, params, ids), want) > 1e-2


# ---------------------------------------------------- the router's state
def test_a_layers_choice_reads_the_state_of_the_layer_before(served):
    """``route`` on the same tokens with another state from the layer before
    chooses differently for some of them, and hands its own state on."""
    cfg, model, params = served
    p1 = jax.tree.map(lambda a: a[1], params["layers"])
    rng = np.random.default_rng(7)
    yt = jnp.asarray(rng.standard_normal((200, cfg.d_model)), F32)
    s = jnp.asarray(rng.standard_normal((200, cfg.router_hidden)), F32)
    idx, w, out = model.route(yt, p1, s)
    idx2, _, out2 = model.route(yt, p1, s + 2.0)
    assert idx.shape == (200, 1) and out.shape == s.shape
    assert 0 < int((idx != idx2).sum()) < 200
    assert np.allclose(np.asarray(out2 - out), 2.0 * float(
        p1["router_gamma"][0]), atol=1e-5)
    assert ((0 < np.asarray(w)) & (np.asarray(w) <= 1)).all()
    # gamma = 0: the state of the layer before is not read
    p0 = dict(p1, router_gamma=jnp.zeros_like(p1["router_gamma"]))
    assert (model.route(yt, p0, s)[0] == model.route(yt, p0, s + 2.0)[0]).all()


# ------------------------------------------------------------ the kernel
def test_the_decode_kernel_at_8_to_2_heads_under_its_own_name(monkeypatch):
    """``decode_attention`` interpreted, at 8 query heads over 2 KV heads of
    128, appending in place by layer, under the name the cell's roofline
    reads; against the dense expression."""
    from deepspeed_tpu.ops import decode_attention as da

    seen, real = [], da.pl.pallas_call
    monkeypatch.setattr(da.pl, "pallas_call", lambda *a, **k: (
        seen.append(k.get("name")), real(*a, **k))[1])
    B, H, KV, hd, S, L = 3, 8, 2, 128, 256, 2
    rng = np.random.default_rng(1)
    ck, cv = (jnp.asarray(rng.standard_normal((L, B, KV, hd, S)), F32)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), F32)
    k, v = (jnp.asarray(rng.standard_normal((B, 1, KV, hd)), F32)
            for _ in range(2))
    lengths = jnp.asarray([130, 0, 17], jnp.int32)      # after the append
    out, nk, nv = decode_attention(q, ck, cv, lengths, k=k, v=v,
                                   layer=jnp.int32(1),
                                   name="cca_decode_attention")
    assert "cca_decode_attention" in seen
    live = np.asarray(lengths) > 0
    for b in np.nonzero(live)[0]:
        n = int(lengths[b])
        keys = np.array(ck[1, b])
        vals = np.array(cv[1, b])
        keys[:, :, n - 1], vals[:, :, n - 1] = k[b, 0], v[b, 0]
        s = np.einsum("kgd,kds->kgs", np.asarray(q[b, 0]).reshape(KV, 4, hd),
                      keys[:, :, :n]) / np.sqrt(hd)
        pr = np.exp(s - s.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        want = np.einsum("kgs,kds->kgd", pr, vals[:, :, :n]).reshape(H, hd)
        assert rel(out[b, 0], want) < 1e-5
        assert (np.asarray(nk[1, b, :, :, n - 1]) == np.asarray(k[b, 0])).all()
    assert (np.asarray(nk[0]) == np.asarray(ck[0])).all()     # layer 0 as was
    assert (np.asarray(nk[1, 1]) == np.asarray(ck[1, 1])).all()   # length 0


def test_the_step_calls_the_kernel_under_its_name_and_gpt2_its_own(
        served, monkeypatch):
    from deepspeed_tpu.models import tiny_test
    from deepspeed_tpu.ops import decode_attention as da

    seen, real = [], da.pl.pallas_call
    monkeypatch.setattr(da.pl, "pallas_call", lambda *a, **k: (
        seen.append(k.get("name")) if "attention" in k.get("name", "")
        else None, real(*a, **k))[1])
    cfg, model, params = served
    for m, p in ((model, params), (build_model(tiny_test(max_seq=256,
                                                         dtype=F32)), None)):
        p = p or jax.eval_shape(m.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: init_slots(m.cfg, 2, 256, F32).cache)
        jax.jit(lambda p, c, t: forward_with_cache(
            m, p, t, c, flash_decode=True)).lower(
                p, cache, jnp.zeros((2, 1), jnp.int32))
        seen.append("|")
    assert seen == ["cca_decode_attention", "|", "decode_attention", "|"]


# ------------------------------------------------------------ the sizes
def cell_config():
    return zaya("8b", n_layer=20)


def test_the_cache_is_planes_beside_a_tail_a_slot():
    cfg = cell_config()
    assert cache_bytes_per_token(cfg) == 20480      # 20 x 2 x (128 + 128) x 2
    assert cca.tail_width(cfg) == 2688              # z, z1: 1280 each; u: 128
    assert state_bytes_per_slot(cfg) == 107520      # 20 x 2688 x 2
    shapes = jax.eval_shape(lambda: init_cache(cfg, 2, 1024))
    assert isinstance(shapes, CCACache)
    assert shapes.k.shape == shapes.v.shape == (20, 2, 2, 128, 1024)
    assert shapes.tail.shape == (20, 2, 2688)


def test_param_count_is_the_models_name():
    """8.30 B outside the embedding (published 8.3 B) and 0.75 B active
    (published 0.76 B), by hand: the latent's projections, the grouped conv,
    the router's MLP, 16 SwiGLU experts of which one works."""
    cfg = zaya("8b")
    attn = 2 * 2048 * 1024 + 2 * 2048 * 256 + 2 * 10 * 128 * 128
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    expert = 3 * 2048 * 2048
    assert cfg.param_count(non_embedding=True) \
        == 40 * (attn + router + 16 * expert)
    assert cfg.param_count(non_embedding=True, active_only=True) \
        == 40 * (attn + router + expert)
    assert abs(cfg.param_count(non_embedding=True) / 8.30e9 - 1) < 0.01
    assert abs(cfg.param_count(non_embedding=True, active_only=True)
               / 0.75e9 - 1) < 0.02
    # tied: the table once
    assert cfg.param_count() - cfg.param_count(non_embedding=True) \
        == 262272 * 2048
    assert round(cell_config().param_count() * 2 / 1e9, 2) == 9.38


def test_flops_count_the_latents_heads():
    """6 a parameter that works + 6 H (hd + hd) a key seen, in the latent's
    8 heads of 128 (not d_model / n_head = 256), + the tied head."""
    cfg = zaya("8b", max_seq=4096)
    assert (cfg.head_dim, cfg.v_dim) == (128, 128)
    assert cfg.flops_per_token() == 6 * cfg.param_count(
        non_embedding=True, active_only=True) \
        + 6 * 8 * 256 * 40 * 4096 + 6 * 2048 * 262272


# (parameters, active parameters, flops_per_token) at the parent commit:
# the MiMo presets here, the other families in test_window_layers.py
PARENT_COUNTS = {
    "mimo-v2-flash": (308778369024, 15445524480, 379447148544),
    "mimo-v2-flash-l7-e16": (3429892096, 2221932544, 77366034432),
}
PRESETS = {
    "mimo-v2-flash": lambda: mimo_v2_flash("flash"),
    "mimo-v2-flash-l7-e16": lambda: mimo_v2_flash(
        "flash", attn_pattern="GSSSSGS", n_layer=7, moe_experts_held=16,
        vocab_size=19072),
}


@pytest.mark.parametrize("name", list(PARENT_COUNTS))
def test_the_mimo_presets_count_what_they_counted(name):
    c = PRESETS[name]()
    assert (c.param_count(), c.param_count(active_only=True),
            c.flops_per_token()) == PARENT_COUNTS[name]


# ------------------------------------------------------------- the spans
def test_the_spans_carry_the_cache_the_tail_and_the_routers_counts(served):
    cfg, model, params = served
    eng = engine(model, params, flash_decode=True)
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 256,
                                 "prefill_chunk": CHUNK, "greedy": True,
                                 "spans": True})
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (20, 70, 150)]
    srv.serve_batch(prompts, [4, 4, 4], seeds=[1, 2, 3])
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    chunks = [e for e in srv.spans.events() if e.kind == "prefill_chunk"]
    meta = steps[-1].meta
    assert meta["cache_bytes_per_token"] == cache_bytes_per_token(cfg, F32)
    assert meta["state_bytes_per_slot"] == state_bytes_per_slot(cfg, F32) \
        == 3 * cca.tail_width(cfg) * 4
    assert meta["moe_rows_routed"] == 3                 # slots x 1
    assert 1.0 / cfg.num_experts <= meta["router_top_p"] <= 1.0
    assert 1.0 <= meta["experts_touched"] <= 3.0
    assert meta["moe_rows_over_routed"] >= 1.0
    assert meta["moe_load_max_over_mean"] >= 1.0
    assert meta["live_positions"] > 0 and meta["slots"] >= 1
    assert meta["attn_fetched_over_live"] >= 1.0
    assert all(c.meta["state_bytes_per_slot"] == meta["state_bytes_per_slot"]
               for c in chunks)
    assert any("router_top_p" in c.meta for c in chunks)


# ------------------------------------------------------------- refused
@pytest.mark.parametrize("serving,why", [
    ({"page_size": 16}, "paged pool"),
    ({"page_size": 16, "kv_quant_bits": 8}, "int8 KV"),
    ({"greedy": True, "speculation": {"enabled": True}}, "speculation"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_beside_a_conv_tail(served, serving, why):
    cfg, model, params = served
    eng = engine(model, params, flash_decode=False)
    with pytest.raises(ValueError, match="compressed convolutional"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": 256,
                               "prefill_chunk": CHUNK, **serving})


def test_weight_quantization_is_refused(served):
    cfg, model, params = served
    with pytest.raises(ValueError, match="compressed convolutional"):
        ds.ServingEngine(engine(model, params, quantize=True),
                         {"slots": 2, "max_len": 256, "prefill_chunk": CHUNK})


def test_a_mesh_of_several_devices_is_refused(served):
    cfg, model, params = served
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    with pytest.raises(ValueError, match="compressed convolutional"):
        ds.ServingEngine(ds.init_inference(model, params,
                                           {"dtype": "float32"}),
                         {"slots": 2, "max_len": 256, "prefill_chunk": CHUNK})


def test_training_is_refused(served):
    cfg, model, _ = served
    with pytest.raises(ValueError, match="served, not trained"):
        ds.initialize({"train_batch_size": 8,
                       "optimizer": {"type": "adamw",
                                     "params": {"lr": 1e-3}}}, model)


def test_a_verify_forward_of_many_tokens_a_slot_is_refused(served):
    cfg, model, params = served
    cache = init_slots(cfg, 2, 128, F32).cache
    with pytest.raises(NotImplementedError, match="conv tail"):
        forward_with_cache(model, params, jnp.zeros((2, 3), jnp.int32), cache)


@pytest.mark.parametrize("over,match", [
    (dict(n_kv_head=1, n_head=4), "even number of KV heads"),
    (dict(qk_head_dim=0), "qk_head_dim"),
    (dict(moe_router="sigmoid"), "ZAYA1 block"),
    (dict(use_bias=True), "ZAYA1 block"),
    (dict(moe_top_k=2), "ZAYA1 expert sub-layer"),
    (dict(router_hidden=0), "ZAYA1 expert sub-layer"),
    (dict(cca_conv=(2, 0)), "conv kernels"),
], ids=lambda v: "" if isinstance(v, str) else ",".join(v))
def test_a_configuration_the_trunk_does_not_run_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        build_model(tiny(**over))


def test_a_wider_kernel_keeps_a_longer_tail(served):
    """Kernels of 3 and 1: two rows of z, none of z1; chunks still equal the
    whole sequence."""
    cfg = tiny(cca_conv=(3, 1))
    assert cca.tail_width(cfg) == 2 * cca.channels(cfg) + 8
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, (1, 50))
    whole = applied(model, params, ids)
    with jax.default_matmul_precision("highest"):
        cache, rows = init_cache(cfg, 1, 64, F32), []
        for a, b in ((0, 16), (16, 48), (48, 49), (49, 50)):
            lg, cache = forward_with_cache(model, params, ids[:, a:b], cache)
            rows.append(lg)
    assert rel(jnp.concatenate(rows, 1), whole) < TOL


def test_the_importer_maps_the_configuration_and_refuses_the_tensors():
    """``config.json`` of the catalog's row gives the "8b" preset; the
    checkpoint's tensors are refused with the reason."""
    import json
    import os

    from deepspeed_tpu.models import config_from_hf, import_state_dict

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        hf = next(r for r in map(json.loads, f)
                  if r["name"] == "ZAYA1-8B")["config"]
    assert config_from_hf(hf) == zaya("8b")
    with pytest.raises(NotImplementedError, match="names and layouts"):
        import_state_dict({}, hf_config=hf)
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        config_from_hf(dict(hf, num_experts_per_tok=2))


def test_residual_scale_is_a_trunk_option_of_the_plain_block():
    """On a GPT-2-like trunk: the full forward and the cache path agree, and
    the option changes the result."""
    from deepspeed_tpu.models import tiny_test

    cfg = tiny_test(max_seq=64, dtype=F32, residual_scale=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert params["layers"]["res_scale"].shape == (2, 2, 4, 64)
    ids = np.random.default_rng(0).integers(0, 256, (2, 20))
    whole = applied(model, params, ids)
    with jax.default_matmul_precision("highest"):
        cache = init_cache(cfg, 2, 32, F32)
        first, cache = forward_with_cache(model, params, ids[:, :12], cache)
        rest, _ = forward_with_cache(model, params, ids[:, 12:], cache)
    assert rel(jnp.concatenate([first, rest], 1), whole) < TOL
    plain = {**params, "layers": {k: v for k, v in params["layers"].items()
                                  if k != "res_scale"}}
    off = build_model(dataclasses.replace(cfg, residual_scale=False))
    assert rel(applied(off, plain, ids), whole) > 1e-2

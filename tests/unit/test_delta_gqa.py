"""Solar-Open2's trunk (``model_type: solar_open2``): KDA mixers behind a gate
with no floor and a beta up to 2 beside gated softmax GQA with no position
code, one residual stream — the system against
``benchmark/reference/solar_open2.py`` on seeded weights (the full forward;
chunked prefill, seating and decode through the kind, its kernels off and
on), and its parts against what defines them: the chunkwise delta rule
against the recurrence where a channel forgets within a token, ``mix_chunk``
and ``mix_step`` across a chunk's edge, the gated attention layer alone, the
four shares of an expert layer against the whole layer. One configuration
and one set of weights for the file (ROADMAP D23)."""

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

import deepspeed_tpu as ds  # noqa: E402
from benchmark.models import solar_open2 as fam  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from deepspeed_tpu.inference.decode import (GenCarry,  # noqa: E402
                                            forward_with_cache, init_cache)
from deepspeed_tpu.inference.kinds import DeltaGQA, kind_of  # noqa: E402
from deepspeed_tpu.models import build_model, kda, solar_open2  # noqa: E402
from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh  # noqa: E402
from deepspeed_tpu.serving.slots import (init_slots, insert_request,  # noqa: E402
                                         retire_slots)

F32 = jnp.float32
S, PROMPT, CHUNK, MAX_LEN = 114, 107, 32, 256


def published(**over):
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "solar-open2-250b-l4-e40.json")) as f:
        conf = json.load(f)
    return {**conf["config"], **conf["rehearsal"], **over}


@pytest.fixture(scope="module")
def small():
    """The rehearsal's configuration in float32, seeded weights, one
    sequence and the reference's logits of it."""
    cfg, model = fam.build(published(), "float32", False)
    params = model.init(jax.random.PRNGKey(3))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, S)), jnp.int32)
    want = np.asarray(ref.run_highest(ref.logits, params, ids))
    return cfg, model, params, ids, want


def worst(got, want):
    return float((np.abs(np.asarray(got) - want).max(-1)
                  / np.abs(want).max(-1)).max())


def through_the_kind(cfg, model, params, ids, flash, size=CHUNK):
    """The prompt in chunks of ``size``, the last right-padded to its bucket
    (107 = 3 x 32 + 11 in a bucket of 16; 64 + 43 in a bucket of 64, where
    the KDA layers scan in ``kda_chunk_scan`` with the kernels on, behind
    ``valid`` in the second), seated in slots 0 and 2 of three,
    slot 2 then retired; 7 steps, one token a slot. Returns (slot 0's logit
    rows from the prompt's last on, the buffers of slots 1 and 2 before the
    steps, and after)."""
    @partial(jax.jit, donate_argnums=(0,))
    def chunk(cache, blk, start, last):
        lg, cache = forward_with_cache(
            model, params, blk, cache._replace(length=start),
            flash_decode=flash, last_token_head=True, last_index=last)
        return lg[0, 0], cache._replace(length=start + last + 1)

    @partial(jax.jit, donate_argnums=(0,))
    def step(cache, toks):
        lg, cache = forward_with_cache(model, params, toks[:, None], cache,
                                       flash_decode=flash)
        return lg[:, 0], cache

    cache = init_cache(cfg, 1, MAX_LEN, F32)
    start = 0
    while start < PROMPT:
        n = min(size, PROMPT - start)
        bucket = size if n == size else max(8, 1 << (n - 1).bit_length())
        blk = np.zeros((1, bucket), np.int32)
        blk[0, :n] = np.asarray(ids[0, start:start + n])
        row, cache = chunk(cache, jnp.asarray(blk), jnp.int32(start),
                           jnp.int32(n - 1))
        start += n
    rows = [row]
    carry = GenCarry(tok=jnp.zeros((1,), jnp.int32), cache=cache,
                     rng=jnp.zeros((1, 2), jnp.uint32),
                     done=jnp.zeros((1,), bool))
    state = init_slots(cfg, 3, MAX_LEN, F32)
    for slot in (0, 2):
        state = insert_request(state, jnp.int32(slot), carry)
    state = retire_slots(state, jnp.asarray([False, False, True]))
    slots = state.cache
    names = ("k", "v", "kda", "conv")
    before = [np.asarray(getattr(slots, n)[:, 1:]) for n in names]
    for t in range(PROMPT, S):
        lg, slots = step(slots, jnp.broadcast_to(ids[0, t], (3,)))
        rows.append(lg[0])
    after = [np.asarray(getattr(slots, n)[:, 1:]) for n in names]
    assert np.asarray(slots.length).tolist() == [S, 0, 0]
    return np.stack([np.asarray(r) for r in rows]), before, after


@pytest.mark.parametrize("path", ["forward", "kind", "kind, kernels on",
                                  "kind, chunks of 64, kernels on"])
def test_the_trunk_matches_the_plain_reference(small, path):
    """The full forward; and prefill in chunks (a padded final one), seating
    and 7 decode steps, every logit row, with XLA's updates and with the
    kernels (interpreted here: the state step, the appending decode
    attention under its own name, the chunk's attention and — at chunks of
    64 — the chunk's scan). A slot that is not running — retired with a
    prompt's state in it, or never seated — keeps every buffer bit-equal
    with the kernels on."""
    cfg, model, params, ids, want = small
    with jax.default_matmul_precision("highest"):
        if path == "forward":
            assert worst(model.apply(params, ids), want) < 2e-4
            return
        got, before, after = through_the_kind(
            cfg, model, params, ids, path.endswith("on"),
            64 if "chunks of 64" in path else CHUNK)
    assert worst(got, want[0, PROMPT - 1:]) < 2e-4
    if path.endswith("on"):
        assert all(np.array_equal(a, b) for a, b in zip(before, after))
        assert any(a.any() for a in before)     # the retired slot held a state


@pytest.mark.parametrize("control", [
    "gate-floored", "beta-sigmoid", "out-gate-dropped", "out-gate-per-head",
    "rope-on-attention", "softmax-router"])
def test_a_wrong_reading_of_the_config_is_another_model(small, control):
    """Each reading the configuration's ``assumed`` excludes (a control of
    the chip's comparison, ``benchmark/kinds/backlog_delta.py``) moves the
    reference's logits by far more than the system differs from the sound
    one."""
    _, _, params, ids, want = small
    ref.CONTROL.add(control)
    try:
        other = np.asarray(ref.run_highest(ref.logits, params, ids))
    finally:
        ref.CONTROL.clear()
    assert worst(other, want) > 2e-2


# ------------------------------------------------------------------- KDA
def _kda_inputs(T, draw, H=3, D=16, B=2, seed=0):
    """``draw`` "unbounded": g down to -60 a token in one channel in four
    (and near 0 in the others), beta up to 2; "bounded": GLM-5.3's, g in
    (-5, 0) and beta in (0, 1)."""
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q, kk = (unit(jax.random.normal(next(k), (B, T, H, D))) for _ in "qk")
    v = jax.random.normal(next(k), (B, T, H, D))
    z = jax.random.normal(next(k), (B, T, H, D))
    beta = jax.nn.sigmoid(jax.random.normal(next(k), (B, T, H)))
    if draw == "bounded":
        g = -5.0 * jax.nn.sigmoid(3.0 * z)
    else:
        steep = (jnp.arange(D) % 4 == 3)
        g = -jnp.where(steep, 60.0 * jax.nn.sigmoid(3.0 * z),
                       jax.nn.softplus(3.0 * z - 4.0))
        beta = 2.0 * beta
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


@pytest.mark.parametrize("T,real,draw", [
    (150, 150, "unbounded"), (64, 37, "unbounded"), (8, 5, "unbounded"),
    (150, 150, "bounded")])
def test_the_chunkwise_form_holds_for_any_decay(T, real, draw):
    """Blocks of 64 with sub-blocks of 16 at decays down to e^-60 a token
    (e^-3840 a block: a factor about a sub-block's start would be e^960) and
    beta up to 2, a state handed in and handed on, a padded tail: finite
    everywhere and the recurrence's to 2e-5 — and the same with the bounded
    draw GLM-5.3 runs."""
    q, k, v, g, beta, S0 = _kda_inputs(T, draw)
    if draw == "unbounded" and T >= 64:
        assert float(g.min()) < -55 and float(beta.max()) > 1.9
    real_t = jnp.arange(T)[None, :, None] < real
    beta_p = jnp.where(real_t, beta, 0.0)
    g_p = jnp.where(real_t[..., None], g, 0.0)
    with jax.default_matmul_precision("highest"):
        o, St = kda.scan_chunked(q, k, v, g_p, beta_p, S0)
        o_want, S_want = _recurrence(*(a[:, :real] for a in (q, k, v, g,
                                                             beta)), S0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(St).all())
    np.testing.assert_allclose(o[:, :real], o_want, atol=2e-5)
    np.testing.assert_allclose(St, S_want, atol=2e-5)


def test_a_chunk_then_steps_are_the_reference_s_mixer(small):
    """``mix_chunk`` over a full chunk and a padded one (``valid`` 11 of a
    bucket of 16) then ``mix_step`` token by token: the reference's KDA
    branch over the whole sequence, across both edges; the seeded gate
    forgets within a token in one head in eight and beta passes 1.5."""
    cfg, _, params, _, _ = small
    c = ref.PUBLISHED
    p = jax.tree.map(lambda a: a[0], params["layers"][1])
    T = 32 + 11 + 5
    y = jax.random.normal(jax.random.PRNGKey(11), (1, T, cfg.d_model))
    shapes = kda.state_shapes(cfg, 1)
    St = jnp.zeros((1,) + shapes["kda"], F32)
    W = jnp.zeros((1,) + shapes["conv"], F32)
    with jax.default_matmul_precision("highest"):
        want, S_want = ref.kda(y[0], p, c)
        beta, g = ref.kda_gates(y[0], p, c)
        out = []
        o, s, w = kda.mix_chunk(cfg, p, y[:, :32], St[0], W[0])
        out.append(o)
        pad = jnp.pad(y[:, 32:43], ((0, 0), (0, 5), (0, 0)))
        o, s, w = kda.mix_chunk(cfg, p, pad, s, w, valid=jnp.int32(11))
        out.append(o[:, :11])
        St, W = s[None], w[None]
        for t in range(43, T):
            o, St, W = kda.mix_step(cfg, p, y[:, t:t + 1], St, W,
                                    jnp.int32(0), jnp.asarray([t + 1]), False)
            out.append(o)
    assert float(g.min()) < -6 and float(beta.max()) > 1.5
    np.testing.assert_allclose(jnp.concatenate(out, 1)[0], want, atol=2e-5)
    np.testing.assert_allclose(St[0, 0], S_want, atol=2e-5)


def test_the_gated_attention_layer_alone_is_the_reference_s():
    """A trunk of ONE layer, the gated GQA layer (4 query heads over 2 KV
    heads, no position code) and its experts: the full forward against the
    reference; without the gate, or with one value a head, another model."""
    pub = published(num_hidden_layers=1)
    cfg, model = fam.build(pub, "float32", False)
    assert cfg.mixer_pattern == "A" and cfg.attn_out_gate
    params = model.init(jax.random.PRNGKey(5))
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 70)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, ids)
    want = np.asarray(ref.run_highest(ref.logits, params, ids))
    assert worst(got, want) < 2e-4
    for control in ("out-gate-dropped", "out-gate-per-head"):
        ref.CONTROL.add(control)
        try:
            other = np.asarray(ref.run_highest(ref.logits, params, ids))
        finally:
            ref.CONTROL.clear()
        assert worst(other, want) > 2e-2, control
    ref.configure(published())       # (the module's configuration, back)


# ------------------------------------------------------- the chip's share
def test_four_shares_of_an_expert_layer_sum_to_the_whole_layer():
    """Each of 4 chips holds 4 of the router's 16 experts; every one routes
    over all 16, takes its top 4, adds its own experts' part and the shared
    expert. Their parts, the shared expert counted once, add up to the
    reference's layer with all 16 held."""
    pub = published(n_routed_experts=16, router_experts=16)
    ref.configure(pub)
    cfg = fam.model_config(pub, "float32")
    seg = build_model(cfg).init(jax.random.PRNGKey(9))["layers"][1]
    w = jax.tree.map(lambda a: a[0], seg)
    y = jax.random.normal(jax.random.PRNGKey(10), (1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(y[0], w, ref.PUBLISHED)
        shared = want - ref.experts(y[0], w, ref.PUBLISHED, shared=False)[0]
        total = 0.0
        for chip in range(4):
            share = build_model(fam.model_config(published(
                n_routed_experts=4, router_experts=16,
                first_expert_held=4 * chip), "float32"))
            mine = {**w, **{k: w[k][4 * chip:4 * chip + 4]
                            for k in ref.BANKS}}
            total = total + share.experts(y, mine)[0][0] - shared
        np.testing.assert_allclose(total + shared, want, atol=2e-5)
    ref.configure(published())


# ------------------------------------------------------------ the config
def test_the_published_config_counts_the_card_s_parameters():
    big = solar_open2("250b")
    assert round(big.param_count() / 1e9, 1) == 250.3
    assert round(big.param_count(active_only=True) / 1e9, 1) == 14.7
    assert big.mixer_pattern.count("K") == 36 \
        and big.mixer_pattern[:4] == "AKKK"
    # the cell's share: what a slot and a cached position cost
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "solar-open2-250b-l4-e40.json")) as f:
        share = fam.model_config(json.load(f)["config"], "bfloat16")
    kind = kind_of(share, 24, jnp.bfloat16)
    assert isinstance(kind, DeltaGQA) and kind.recurrent
    assert kind.state_bytes_per_slot() == 13025280
    assert kind.bytes_per_token() == 4096
    assert {n: s for n, (s, _) in {**kind.buffers(24, 65536),
                                   **kind.state(24)}.items()} == {
        "k": (1, 24, 8, 128, 65536), "v": (1, 24, 8, 128, 65536),
        "kda": (3, 24, 64, 128, 128), "conv": (3, 24, 3, 24576)}


@pytest.mark.parametrize("over,why", [
    (dict(index_pattern="F---"), "latent"),
    (dict(hc_mult=4), "solar_open2 block"),
    (dict(mixer_pattern="AKK"), "has to name each"),
    (dict(kda_gate_floor=1.0), "negative .the bounded gate. or 0"),
    (dict(pos_embedding="rope"), "no position code"),
    (dict(mixer_pattern="", attn_out_gate=True), "attn_out_gate is the "
                                                "solar_open2 block's"),
    (dict(attention="cca"), "compressed|cca|no other attention"),
])
def test_what_the_trunk_does_not_run_is_refused_with_why(over, why):
    with pytest.raises(ValueError, match=why):
        build_model(solar_open2("tiny", **over))


# ----------------------------------------------------------- the serving
@pytest.fixture(scope="module")
def served(small):
    cfg, model, params, _, _ = small
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    eng = ds.init_inference(model, params, {"dtype": "float32"}, mesh=mesh)
    return cfg, eng


def test_served_requests_are_solo_generate_s_and_the_spans_say_what_moved(
        served):
    """Three prompts on both sides of a chunk's edge through
    ``ServingEngine`` (chunks of 16, three slots): the tokens of solo
    ``generate()``; the ``decode_step`` spans carry what the step has to
    move from the mirror of the slots' lengths, the ``prefill_chunk`` spans
    their real and padded tokens and the keys their walk reads."""
    cfg, eng = served
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 128,
                                 "prefill_chunk": 16, "temperature": 0.9,
                                 "top_k": 30, "spans": True})
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (P,)).astype(np.int32)
               for P in (5, 33, 46)]
    outs = srv.serve_batch(prompts, [6, 5, 4], seeds=[1, 2, 3])
    for p, n, seed, got in zip(prompts, (6, 5, 4), (1, 2, 3), outs):
        want = eng.generate(p[None], n, request_seeds=[seed],
                            temperature=0.9, top_k=30, cache_len=128)
        assert np.asarray(got).tolist() == np.asarray(want)[0].tolist()
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    chunks = [e for e in srv.spans.events() if e.kind == "prefill_chunk"]
    meta = steps[-1].meta
    for key in ("slots", "live_positions", "state_bytes_per_slot",
                "cache_bytes_per_token", "state_bytes_step", "kv_bytes_step",
                "weight_bytes_step", "expert_bytes_step", "head_bytes_step",
                "state_share_of_step_bytes", "kv_share_of_step_bytes",
                "experts_touched", "held_rows", "held_rows_share"):
        assert key in meta, key
    kind = srv.kind
    assert meta["state_bytes_step"] == 2 * meta["slots"] * kind.slot_bytes
    assert meta["kv_bytes_step"] == meta["live_positions"] * kind.token_bytes
    total = sum(meta[k] for k in ("state_bytes_step", "kv_bytes_step",
                                  "weight_bytes_step", "expert_bytes_step",
                                  "head_bytes_step"))
    assert meta["kv_share_of_step_bytes"] == meta["kv_bytes_step"] / total
    assert kind.layer_bytes > 0 and kind.expert_bytes > 0
    last = [e.meta for e in chunks if e.meta["final"]]
    assert {(m["tokens_real"], m["tokens_padded"]) for m in last} \
        == {(5, 3), (1, 7), (14, 2)}
    assert all(m["attn_live_keys"] == m["size"] + 16 * m["chunk"]
               for m in (e.meta for e in chunks))
    srv.close()


def test_the_kind_refuses_what_it_does_not_compose_with(served):
    _, eng = served
    base = {"slots": 2, "max_len": 128, "prefill_chunk": 16}
    for over, why in ((dict(page_size=8, pool_pages=40), "has no pages"),
                      (dict(speculation={"enabled": True}),
                       "roll the delta-rule state back"),
                      (dict(host_pool_bytes=1 << 20, page_size=8,
                            pool_pages=40), "has no pages")):
        with pytest.raises(ValueError, match=why):
            ds.ServingEngine(eng, {**base, **over})
    kind = kind_of(eng.model.cfg)
    assert set(kind.refuses) == {"paged", "kv_quant", "speculation",
                                 "host_kv", "quantize", "mesh"}


# ------------------------------------------------- the chunk's attention
def _chunks(model, params, ids, cuts, flash, dtype, max_len=MAX_LEN):
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


@pytest.mark.parametrize("dtype,tol", [(F32, 2e-5), (jnp.bfloat16, 4e-2)],
                         ids=["f32", "bf16"])
def test_a_chunked_prefill_on_the_kernel_is_the_walk_s(small, dtype, tol):
    """Chunks of 32, 32, 32 and a bucket of 16 through ``forward_with_cache``
    with the kernels on (``nope_gqa_chunk_attention``, interpreted): the
    walk's logits — to float32 rounding, and in bf16 to what two roundings
    of ``p`` under another blocking differ by — the attention layer's planes
    bit-equal in float32 (the kernel writes none and the one attention layer
    stands first: nothing attended stands in front of its K/V), nothing
    outside the last chunk's positions touched."""
    cfg, model, params, ids, _ = small
    if dtype != F32:
        cfg, model = fam.build(published(), "bfloat16", False)
        params = model.init(jax.random.PRNGKey(3))
    cuts = (32, 64, 96, 112)
    walk, _, c0 = _chunks(model, params, ids[:, :112], cuts, False, dtype)
    got, before, c1 = _chunks(model, params, ids[:, :112], cuts, True, dtype)
    scale = float(jnp.abs(walk.astype(F32)).max())
    assert float(jnp.abs(got.astype(F32) - walk.astype(F32)).max()) \
        <= tol * scale
    if dtype == F32:
        assert np.array_equal(np.asarray(c0.k), np.asarray(c1.k))
        assert np.array_equal(np.asarray(c0.v), np.asarray(c1.v))
    lo, hi = cuts[-2], cuts[-1]
    for name in ("k", "v"):
        a, b = (np.asarray(getattr(c, name).astype(F32))
                for c in (before, c1))
        assert np.array_equal(a[..., :lo], b[..., :lo]), name
        assert np.array_equal(a[..., hi:], b[..., hi:]), name
        assert not np.array_equal(a[..., lo:hi], b[..., lo:hi]), name


@pytest.mark.parametrize("what,T,max_len,flash,walks", [
    ("the kernel", 32, 128, True, 0),
    ("a bucket of 8", 8, 128, True, 0),
    ("a cache of no whole lane block", 32, 96, True, 1),
    ("queries nothing tiles", 12, 128, True, 1),
    ("the kernels off", 32, 128, False, 0),
])
def test_a_chunk_traced_onto_the_walk_is_counted(small, what, T, max_len,
                                                 flash, walks):
    """``Serve/chunk_attention_fallback_builds``: one for every chunk program
    traced onto ``windowed.attend_blocks`` while the kernels are on; the
    kind's own answer (what the ``prefill_chunk`` span says) agrees."""
    from deepspeed_tpu.observability.metrics import get_registry

    cfg, model, params, ids, _ = small
    counter = get_registry().counter("Serve/chunk_attention_fallback_builds")
    before = counter.value
    cache = init_cache(cfg, 1, max_len, F32)
    text = str(jax.make_jaxpr(lambda p, ids, cache: forward_with_cache(
        model, p, ids, cache, flash_decode=flash))(params, ids[:, :T], cache))
    assert counter.value - before == walks
    kind = kind_of(cfg, 1, F32)
    took = flash and not walks
    assert kind.chunk_kernel(flash, T, max_len, F32, F32) == took
    assert ("nope_gqa_chunk_attention" in text) == took


def test_a_chunk_s_span_says_what_attended_and_over_how_many_keys(small):
    from deepspeed_tpu.serving.scheduler import ChunkPlan

    cfg = small[0]
    kind = kind_of(cfg, 2, F32)
    chunk = ChunkPlan(start=64, ids=np.zeros(32, np.int32))
    assert kind.chunk_meta(chunk)["attn_live_keys"] == 96
    assert kind.chunk_meta(chunk)["attn_kernel"] is False   # no engine's
    assert kind.chunk_meta(chunk)["scan_kernel"] is False
    kind.flash, kind.max_len = True, 128
    assert kind.chunk_meta(chunk)["attn_kernel"] is True
    assert kind.chunk_meta(chunk)["scan_kernel"] is False   # a bucket of 32
    whole = ChunkPlan(start=64, ids=np.zeros(64, np.int32))
    assert kind.chunk_meta(whole)["scan_kernel"] is True
    kind.max_len = 96
    assert kind.chunk_meta(chunk)["attn_kernel"] is False
    assert kind.chunk_meta(whole)["scan_kernel"] is False


def test_served_with_the_kernels_every_chunk_attends_in_the_kernel(small):
    """``ServingEngine`` with ``flash_decode`` on (interpreted): every
    ``prefill_chunk`` span says ``attn_kernel`` and its live keys, no chunk
    program was traced onto the walk, and the tokens are solo
    ``generate()``'s (whose prefill is the walk's)."""
    from deepspeed_tpu.observability.metrics import get_registry

    cfg, model, params, _, _ = small
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    eng = ds.init_inference(model, params, {"dtype": "float32",
                                            "flash_decode": True}, mesh=mesh)
    counter = get_registry().counter("Serve/chunk_attention_fallback_builds")
    before = counter.value
    srv = ds.ServingEngine(eng, {"slots": 2, "max_len": 128,
                                 "prefill_chunk": 16, "greedy": True,
                                 "spans": True})
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (P,)).astype(np.int32)
               for P in (9, 37)]
    outs = srv.serve_batch(prompts, [3, 3])
    for p, got in zip(prompts, outs):
        want = eng.generate(p[None], 3, greedy=True, cache_len=128)
        assert np.asarray(got).tolist() == np.asarray(want)[0].tolist()
    chunks = [e.meta for e in srv.spans.events() if e.kind == "prefill_chunk"]
    assert len(chunks) >= 4 and all(m["attn_kernel"] is True for m in chunks)
    assert all(m["attn_live_keys"] == m["size"] + 16 * m["chunk"]
               for m in chunks)
    assert counter.value == before
    srv.close()

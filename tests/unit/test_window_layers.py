"""Window layers beside full ones (``models/windowed.py``, MiMo-V2-Flash)
against the plain reference (``benchmark/reference/mimo_v2_flash.py``):
``apply()``, prefill in chunks + decode through the slots' planes and rings,
the re-seated slot, the experts' shares, the decode kernel's ring, sink and
two widths, the counts, what is refused — and the controls, each of which has
to FAIL the comparison."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.reference import mimo_v2_flash as ref
from deepspeed_tpu.inference.decode import (GenCarry, WindowedCache,
                                            cache_bytes_per_token,
                                            forward_with_cache, init_cache,
                                            state_bytes_per_slot)
from deepspeed_tpu.models import (build_model, deepseek_v3, gpt2, llama2,
                                  mimo_v2_flash, mixtral, nemotron_h, ouro,
                                  windowed)
from deepspeed_tpu.models import moe as moe_module
from deepspeed_tpu.ops.decode_attention import decode_attention
from deepspeed_tpu.serving.scheduler import plan_chunks
from deepspeed_tpu.serving.slots import init_slots, insert_request

PUB = dict(model_type="mimo_v2_flash", num_hidden_layers=5,
           hybrid_layer_pattern=[0, 1, 1, 0, 1],
           moe_layer_freq=[0, 1, 1, 1, 1], num_attention_heads=4,
           num_key_value_heads=1, swa_num_key_value_heads=2, head_dim=24,
           v_head_dim=16, partial_rotary_factor=0.334, rope_theta=5e6,
           swa_rope_theta=1e4, sliding_window=128,
           attention_value_scale=0.707, layernorm_epsilon=1e-5,
           num_experts_per_tok=2)
F32 = jnp.float32
TOL = 2e-5


def one_device_mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def engine(model, params, **conf):
    return ds.init_inference(model, params, {"dtype": "float32", **conf},
                             mesh=one_device_mesh())


def tiny(**over):
    return mimo_v2_flash("tiny", dtype=F32, **over)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """The module's programs stay out of the persistent compilation cache:
    many of its tests run the same eager programs (one chunk of 64 into a
    cache of 512, ...) on several workers at once, and a worker that read
    an entry while another wrote it aborted inside the cache's reader
    (twice in four whole runs, at ``compilation_cache.get_executable_and_
    time``). As ``test_chip_compile.py`` keeps its compiles out of it."""
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


def ids_of(cfg, seed, shape):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, shape)


def applied(model, params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, i: model.apply(p, i))(params, ids)


def referred(params, ids, **kw):
    return ref.run_highest(ref.logits, params, jnp.asarray(ids), **kw)


# ---------------------------------------------------- the whole model
def test_the_trunk_is_runs_of_equal_attention_and_ffn_kind(served):
    cfg, _, params = served
    assert cfg.segments == (("dense", 1), ("moe", 2), ("moe", 1), ("moe", 1))
    assert cfg.segment_attn == ("G", "S", "G", "S")
    assert ["sink" in seg for seg in params["layers"]] \
        == [False, True, False, True]
    flash = mimo_v2_flash("flash")
    assert flash.attn_pattern.count("S") == 39 and len(flash.segments) == 17
    assert flash.attn_pattern[:7] == "GSSSSGS"


def test_apply_equals_the_reference(served):
    """300 tokens: past the window, and past one block of 128 queries."""
    cfg, model, params = served
    ids = ids_of(cfg, 0, (2, 300))
    assert rel(applied(model, params, ids), referred(params, ids)) < TOL


def through_the_slots(cfg, model, params, prompts, given, chunk, slots,
                      max_len, flash, seats=None):
    """Per prompt (1 + steps, V) logits: prefill in ``chunk``s into a batch-1
    cache, seated in a slot, ``given`` tokens decoded by the slots' step.
    The three programs are jitted, as the serving engine builds them (and
    one compile a program: run op by op, the XLA:CPU compiler was seen to
    fall over under six workers)."""
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
            # (a chunk that is not the last is whole: its last index its end)
            lg, cache = prefill(params, cache, jnp.asarray(ch.ids[None]),
                                jnp.int32(ch.start),
                                jnp.int32(ch.last_index if ch.final
                                          else ch.size - 1))
        cache = cache._replace(length=jnp.int32(len(prompt)))
        rows[i].append(lg)
        state = seat(state, jnp.int32(seats[i]), GenCarry(
            tok=jnp.zeros((1,), jnp.int32), cache=cache,
            rng=jnp.zeros((1, 2), jnp.uint32), done=jnp.zeros((1,), bool)))
    cache = state.cache
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


# prompts: inside the window; 3 past it; 2 behind a chunk boundary in a
# padded bucket; whole chunks (no padding) whose steps cross the block and
# ring edge at 256; the ring wrapped, the steps crossing the block edge at 384
LENGTHS = (24, 131, 66, 254, 381)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_prefill_in_chunks_then_the_slots_step_equal_the_reference(served,
                                                                   flash):
    """Chunks of 64 into planes and rings, seated between slots at length
    0, then 5 given tokens through the slots' step: every row against the
    reference's one full forward."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, LENGTHS, 5)
    with jax.default_matmul_precision("highest"):
        got, cache = through_the_slots(cfg, model, params, prompts, given, 64,
                                       11, 512, flash)
    for g, w in zip(got, reference_rows(params, prompts, given)):
        assert rel(g, w) < TOL
    assert (np.asarray(cache.length)[1::2][:5]
            == [n + 5 for n in LENGTHS]).all()


def test_a_chunk_longer_than_the_ring_leaves_the_last_positions_in_it(served):
    """One chunk of 512 over a ring of 256 (the cell's proportions: a chunk
    holds two rings): the steps behind it read what the chunk left."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, (700,), 3)
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, 512,
                                   2, 1024, True)
    assert rel(got[0], reference_rows(params, prompts, given)[0]) < TOL


def test_a_reseated_slot_reads_nothing_of_its_predecessors_ring(served):
    """A request of 40 tokens seated where one of 300 stood (its ring full,
    wrapped): the same rows as in a fresh slot, bit for bit; and through the
    serving engine, equal to solo ``generate()``."""
    cfg, model, params = served
    (long, short), given = cache_case(cfg, (300, 40), 4)
    with jax.default_matmul_precision("highest"):
        fresh, _ = through_the_slots(cfg, model, params, [short], given[1:],
                                     64, 2, 512, True, seats=[1])
        after, _ = through_the_slots(cfg, model, params, [long, short], given,
                                     64, 2, 512, True, seats=[1, 1])
    assert (np.asarray(after[1][1:]) == np.asarray(fresh[0][1:])).all()
    eng = engine(model, params)
    conf = {"slots": 1, "max_len": 512, "prefill_chunk": 64, "greedy": True}
    served_after = ds.ServingEngine(eng, conf).serve_batch(
        [long, short], [6, 6], seeds=[1, 2])[1]
    solo = np.asarray(eng.generate(short[None], 6, request_seeds=[2],
                                   greedy=True, cache_len=512))[0]
    assert list(served_after) == list(solo)


def test_a_row_at_length_0_touches_nothing(served):
    """Whatever an idle slot's ring and planes hold, the running rows come
    out bit-equal and the idle slot's buffers stay as they were."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, (140, 30), 3)
    _, clean = through_the_slots(cfg, model, params, prompts,
                                 [g[:0] for g in given], 64, 4, 256, True)
    idle, run = np.array([0, 2]), np.array([1, 3])
    dirty = clean._replace(**{
        n: getattr(clean, n).at[:, idle].set(1.5)
        for n in ("k", "v", "wk", "wv")})
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
    for name in ("k", "v", "wk", "wv"):
        assert (np.asarray(getattr(a, name))[:, run]
                == np.asarray(getattr(b, name))[:, run]).all(), name
        assert (np.asarray(getattr(b, name))[:, idle] == 1.5).all(), name
    assert (np.asarray(b.length) == [0, 143, 0, 33]).all()


# ------------------------------------------------------------- controls
def _swap(cfg):
    return dataclasses.replace(cfg, rope_theta=cfg.window_rope_theta,
                               window_rope_theta=cfg.rope_theta)


CONTROLS = {
    "the sink dropped": lambda c: dataclasses.replace(c, attn_sink=False),
    "a window of 127": lambda c: dataclasses.replace(c, window=127),
    "no window": lambda c: dataclasses.replace(c, window=1024),
    "the two thetas swapped": _swap,
    "0.707 dropped": lambda c: dataclasses.replace(c, attn_value_scale=1.0),
    "rope on all 24 dims": lambda c: dataclasses.replace(c, rotary_dim=24),
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_controls_of_the_forward_fail(served, control):
    """Each leaves the reference a thousand times further than the system
    itself is from it."""
    cfg, model, params = served
    ids = ids_of(cfg, 5, (1, 300))
    broken = build_model(CONTROLS[control](cfg))
    if control == "the sink dropped":
        params = {**params, "layers": tuple(
            {k: v for k, v in seg.items() if k != "sink"}
            for seg in params["layers"])}
    assert rel(applied(broken, params, ids), referred(served[2], ids)) > 2e-2


@pytest.mark.parametrize("control", ["a window of 127", "the sink dropped"])
def test_controls_of_the_cache_path_fail(served, control, monkeypatch):
    """Through the rings and the decode kernel too: the step's window one
    position short; the kernel's sum started without the sink."""
    cfg, model, params = served
    if control == "the sink dropped":
        params = {**params, "layers": tuple(
            {k: v for k, v in seg.items() if k != "sink"}
            for seg in params["layers"])}
    else:
        model = build_model(dataclasses.replace(cfg, window=127))
    prompts, given = cache_case(cfg, (200,), 3)
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, 64, 2,
                                   256, True)
    want = reference_rows(served[2], prompts, given)[0]
    assert rel(got[0][1:], want[1:]) > 2e-2


# ----------------------------------------------------------- the shares
def share_of(cfg, p, j, held):
    share = dataclasses.replace(cfg, moe_experts_held=held,
                                moe_first_held=held * j)
    pj = dict(p, **{n: p[n][held * j:held * (j + 1)]
                    for n in ("w_gate", "w_in", "w_out")})
    return build_model(share), pj


def test_the_shares_add_up_to_the_whole_layer(served):
    """Four shares of 2 experts each equal the uncut layer of 8 — in the
    program and in the reference — and each share's rows are the pairs that
    chose one of its experts."""
    cfg, model, params = served
    p = jax.tree.map(lambda a: a[0], params["layers"][1])
    y = jax.random.normal(jax.random.PRNGKey(7), (2, 9, cfg.d_model), F32)
    yt = y.reshape(-1, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        whole, stats, idx = model.experts(y, p)
        want, _ = ref.experts(yt, p, ref.PUBLISHED)
        assert rel(whole.reshape(-1, cfg.d_model), want) < TOL
        parts, held_rows = [], 0.0
        for j in range(4):
            m, pj = share_of(cfg, p, j, 2)
            out, st, idx_j = m.experts(y, pj)
            assert (idx_j == idx).all()          # every share routes alike
            parts.append(out.reshape(-1, cfg.d_model))
            held_rows += float(st[3])
            assert float(st[3]) == float(((idx // 2) == j).sum())
            ref.configure(PUB, first_held=2 * j)
            want_j, _ = ref.experts(yt, pj, ref.PUBLISHED)
            assert rel(parts[-1], want_j) < TOL
        ref.configure(PUB)
    assert held_rows == yt.shape[0] * cfg.moe_top_k == float(stats[3])
    assert rel(sum(parts), whole.reshape(-1, cfg.d_model)) < TOL


def test_an_absent_experts_rows_not_dropped_fails(served, monkeypatch):
    """The control of the held rule: a pair that chose an expert held
    elsewhere reads some row of the sorted layout instead of adding 0."""
    cfg, model, params = served
    p = jax.tree.map(lambda a: a[0], params["layers"][1])
    y = jax.random.normal(jax.random.PRNGKey(8), (2, 9, cfg.d_model), F32)
    m, pj = share_of(cfg, p, 1, 2)
    ref.configure(PUB, first_held=2)
    want, _ = ref.experts(y.reshape(-1, cfg.d_model), pj, ref.PUBLISHED)
    ref.configure(PUB)
    layout = moe_module.held_layout

    def kept(*a):
        out = list(layout(*a))
        out[2] = jnp.ones_like(out[2])
        return tuple(out)

    with jax.default_matmul_precision("highest"):
        good = m.experts(y, pj)[0].reshape(-1, cfg.d_model)
        monkeypatch.setattr(moe_module, "held_layout", kept)
        bad = m.experts(y, pj)[0].reshape(-1, cfg.d_model)
    assert rel(good, want) < TOL < 1e-2 < rel(bad, want)


def test_a_share_of_the_model_equals_the_reference_given_the_same(served):
    """The whole trunk with experts 4..7 of 8 held, through ``apply()``."""
    cfg, _, params = served
    held = dataclasses.replace(cfg, moe_experts_held=4, moe_first_held=4)
    model = build_model(held)
    shared = model.init(jax.random.PRNGKey(0))
    assert shared["layers"][1]["w_in"].shape[1] == 4
    assert shared["layers"][1]["router"].shape[-1] == 8
    ids = ids_of(cfg, 9, (1, 150))
    ref.configure(PUB, first_held=4)
    try:
        assert rel(applied(model, shared, ids), referred(shared, ids)) < TOL
    finally:
        ref.configure(PUB)


# ----------------------------------------------------------- the kernel
def dense_window(q, kc, vc, length, window, sink):
    """The dense expression of one ring read: q (B, 1, H, hd), the rings
    (B, KV, ., R), the slot at ``length`` after the append."""
    B, _, H, hd = q.shape
    KV, R = kc.shape[1], kc.shape[3]
    out = np.zeros((B, 1, H, vc.shape[2]))
    for b in range(B):
        n = int(length[b])
        if not n:
            continue
        pos = [p for p in range(max(n - window, 0), n)] if window \
            else list(range(min(n, R)))
        for h in range(H):
            kv = h // (H // KV)
            s = np.array([np.dot(q[b, 0, h], kc[b, kv, :, p % R])
                          for p in pos]) / np.sqrt(hd)
            m = max(s.max(), sink[h]) if sink is not None else s.max()
            e = np.exp(s - m)
            den = e.sum() + (np.exp(sink[h] - m) if sink is not None else 0)
            out[b, 0, h] = sum(e[i] * vc[b, kv, :, p % R]
                               for i, p in enumerate(pos)) / den
    return out


@pytest.mark.parametrize("window,sink", [(0, False), (128, True),
                                         (128, False), (100, True)],
                         ids=["planes", "ring+sink", "ring", "window 100"])
def test_the_kernel_with_keys_wider_than_values(window, sink):
    """K 192 / V 128 at the published head widths, 8 query heads over 2 KV
    heads: lengths inside the first block, 3 past the window, on a block
    edge, past the ring's wrap (a FIRST live position as well as a last),
    and an idle slot; appended in place, only the slot's own column
    written."""
    rng = np.random.default_rng(11)
    B, H, KV, hd, vd, R = 6, 8, 2, 192, 128, 256 if window else 512
    lengths = np.array([5, 131, 256, 257, 0, 385 if window else 300],
                       np.int32)
    q = rng.normal(size=(B, 1, H, hd)).astype(np.float32)
    kc = rng.normal(size=(3, B, KV, hd, R)).astype(np.float32)
    vc = rng.normal(size=(3, B, KV, vd, R)).astype(np.float32)
    k = rng.normal(size=(B, 1, KV, hd)).astype(np.float32)
    v = rng.normal(size=(B, 1, KV, vd)).astype(np.float32)
    sk = rng.normal(size=(H,)).astype(np.float32) + 2.0 if sink else None
    with jax.default_matmul_precision("highest"):
        out, k2, v2 = jax.jit(lambda *a: decode_attention(
            a[0], a[1], a[2], jnp.asarray(lengths), k=a[3], v=a[4],
            layer=jnp.int32(1), window=window,
            sink=None if sk is None else jnp.asarray(sk),
            name="window_decode_attention" if window
            else "full_decode_attention", interpret=True))(q, kc, vc, k, v)
    want_k, want_v = kc.copy(), vc.copy()
    for b, n in enumerate(lengths):
        if n:
            want_k[1, b, :, :, (n - 1) % R] = k[b, 0]
            want_v[1, b, :, :, (n - 1) % R] = v[b, 0]
    assert (np.asarray(k2) == want_k).all() and (np.asarray(v2) == want_v).all()
    want = dense_window(q, want_k[1], want_v[1], lengths, window, sk)
    live = lengths > 0
    assert out.shape == (B, 1, H, vd)
    assert rel(np.asarray(out)[live], want[live]) < 1e-5


def test_the_two_uses_have_names_of_their_own(served, monkeypatch):
    """The step's program calls ``full_decode_attention`` for its full
    layers' runs and ``window_decode_attention`` for its window layers'; a
    GPT-2 step still calls ``decode_attention``."""
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
    # one call a run of layers: G, SS, G, S; then GPT-2's one loop
    assert seen == ["full_decode_attention", "window_decode_attention",
                    "full_decode_attention", "window_decode_attention", "|",
                    "decode_attention", "|"]


# ------------------------------------------------------------ the sizes
def cell_config():
    return mimo_v2_flash("flash", attn_pattern="GSSSSGS", n_layer=7,
                         moe_experts_held=16, vocab_size=19072)


def test_the_cache_is_planes_for_full_layers_and_a_ring_a_slot():
    cfg = cell_config()
    assert cache_bytes_per_token(cfg) == 5120        # 2 planes x 4 x 320 x 2
    assert state_bytes_per_slot(cfg) == 6_553_600    # 5 rings x 8 x 320 x 256
    assert windowed.ring_len(cfg) == 256
    shapes = jax.eval_shape(lambda: init_cache(cfg, 2, 1024))
    assert isinstance(shapes, WindowedCache)
    assert shapes.k.shape == (2, 2, 4, 192, 1024)
    assert shapes.v.shape == (2, 2, 4, 128, 1024)
    assert shapes.wk.shape == (5, 2, 8, 192, 256)
    assert shapes.wv.shape == (5, 2, 8, 128, 256)


def test_param_count_is_the_models_name():
    cfg = mimo_v2_flash("flash")
    assert abs(cfg.param_count() / 309e9 - 1) < 0.01
    # "A15B": 15.45 B with the embedding's table, 14.82 B multiplied
    active = cfg.param_count(active_only=True)
    assert abs(active / 15e9 - 1) < 0.03
    assert abs((active - 152576 * 4096) / 15e9 - 1) < 0.0125
    # by hand: q + o, k + v by kind, 256 experts and a router, the dense
    # layer, embedding and head
    qo = 4096 * 64 * 192 + 64 * 128 * 4096
    full, win = qo + 4096 * 4 * 320, qo + 4096 * 8 * 320
    ffn = 4096 * 256 + 256 * 3 * 4096 * 2048
    want = 9 * full + 39 * win + 47 * ffn + 3 * 4096 * 16384 \
        + 2 * 152576 * 4096
    assert cfg.param_count() == want
    cut = cell_config()
    assert round(cut.param_count() * 2 / 1e9, 2) == 6.86      # bf16 GB held


def test_flops_count_scores_by_kind_and_window():
    """6 a parameter + 6 H (qk + v) a key seen: every position for the 9
    full layers, at most 128 for the 39 window ones."""
    cfg = mimo_v2_flash("flash", max_seq=8192)
    scores = 6 * 64 * (192 + 128) * (9 * 8192 + 39 * 128)
    assert cfg.flops_per_token() == 6 * cfg.param_count(
        non_embedding=True, active_only=True) + scores + 6 * 4096 * 152576
    short = dataclasses.replace(cfg, max_seq=64)
    assert short.flops_per_token() - 6 * short.param_count(
        non_embedding=True, active_only=True) - 6 * 4096 * 152576 \
        == 6 * 64 * 320 * 48 * 64


# (parameters, active parameters, flops_per_token) at the parent commit
PARENT_COUNTS = {
    "gpt2-774m": (772117760, 772117760, 5198937600),
    "gpt2-1.5b": (1554971200, 1554971200, 10273545600),
    "kanana-2-30b-a3b": (30670585856, 3614179328, 116745830400),
    "ouro-2.6b": (2667577344, 2667577344, 369031643136),
    "nemotron-3-super": (120665931776, 12767461376, 176462757888),
    "nemotron-3-super-l11-e128": (4647813120, 1730150400, 22460497920),
    "mixtral-8x7b": (46702526464, 12879659008, 82933972992),
    "llama2-70b": (68975329280, 68975329280, 444491366400),
}
PRESETS = {
    "gpt2-774m": lambda: gpt2("774m"), "gpt2-1.5b": lambda: gpt2("1.5b"),
    "kanana-2-30b-a3b": lambda: deepseek_v3("kanana-2-30b-a3b"),
    "ouro-2.6b": lambda: ouro("2.6b"), "nemotron-3-super": nemotron_h,
    "nemotron-3-super-l11-e128": lambda: dataclasses.replace(
        nemotron_h(), block_pattern="MEMEMEM*EME", n_layer=11,
        moe_experts_held=128, vocab_size=32768),
    "mixtral-8x7b": lambda: mixtral("8x7b"),
    "llama2-70b": lambda: llama2("70b"),
}


@pytest.mark.parametrize("name", list(PARENT_COUNTS))
def test_the_other_families_count_what_they_counted(name):
    """``_attn_params_per_layer`` / ``flops_per_token`` by kind leave every
    configuration without an ``attn_pattern`` where it was."""
    c = PRESETS[name]()
    assert not c.attn_pattern and c.segment_attn == ("",) * len(c.segments)
    assert (c.param_count(), c.param_count(active_only=True),
            c.flops_per_token()) == PARENT_COUNTS[name]


# ------------------------------------------------------------- the spans
def test_the_spans_carry_the_cache_ring_and_expert_counts(served):
    cfg, model, params = served
    held = dataclasses.replace(cfg, moe_experts_held=4)
    m = build_model(held)
    eng = engine(m, m.init(jax.random.PRNGKey(0)), flash_decode=True)
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 512,
                                 "prefill_chunk": 64, "greedy": True,
                                 "spans": True})
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (20, 150, 300)]
    srv.serve_batch(prompts, [4, 4, 4], seeds=[1, 2, 3])
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    chunks = [e for e in srv.spans.events() if e.kind == "prefill_chunk"]
    meta = steps[-1].meta
    assert meta["cache_bytes_per_token"] == cache_bytes_per_token(held, F32)
    assert meta["window_bytes_per_slot"] == state_bytes_per_slot(held, F32)
    assert 1.0 <= meta["window_fetched_over_live"] <= 2.0
    for key in ("held_rows", "held_rows_share", "experts_touched",
                "moe_load_max_over_mean"):
        assert key in meta, key
    assert 0.0 < meta["held_rows_share"] < 1.0
    assert all(c.meta["key_blocks_walked_over_live"] >= 1.0
               and c.meta["window_bytes_per_slot"]
               == meta["window_bytes_per_slot"] for c in chunks)
    assert any("held_rows" in c.meta for c in chunks)


# ------------------------------------------------------------- refused
@pytest.mark.parametrize("serving,why", [
    ({"page_size": 16}, "paged pool"),
    ({"page_size": 16, "kv_quant_bits": 8}, "int8 KV"),
    ({"greedy": True, "speculation": {"enabled": True}}, "speculation"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_beside_window_layers(served, serving, why):
    cfg, model, params = served
    eng = engine(model, params, flash_decode=False)
    with pytest.raises(ValueError, match="window layers beside full ones"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": 256,
                               "prefill_chunk": 64, **serving})


def test_weight_quantization_is_refused(served):
    cfg, model, params = served
    with pytest.raises(ValueError, match="window layers beside full ones"):
        ds.ServingEngine(engine(model, params, quantize=True),
                         {"slots": 2, "max_len": 256, "prefill_chunk": 64})


def test_a_mesh_of_several_devices_is_refused(served):
    cfg, model, params = served
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    with pytest.raises(ValueError, match="window layers beside full ones"):
        ds.ServingEngine(ds.init_inference(model, params,
                                           {"dtype": "float32"}),
                         {"slots": 2, "max_len": 256, "prefill_chunk": 64})


def test_training_is_refused(served):
    cfg, model, _ = served
    with pytest.raises(ValueError, match="served, not trained"):
        ds.initialize({"train_batch_size": 8,
                       "optimizer": {"type": "adamw",
                                     "params": {"lr": 1e-3}}}, model)


@pytest.mark.parametrize("over,match", [
    (dict(attn_pattern="GSX", n_layer=3), "attn_pattern"),
    (dict(attn_pattern="GS", n_layer=3), "attn_pattern"),
    (dict(window=0), "window"),
    (dict(window_kv_heads=3), "KV heads"),
    (dict(moe_experts_held=16), "moe_experts_held"),
    (dict(moe_experts_held=4, moe_first_held=2), "moe_experts_held"),
    (dict(use_bias=True), "MiMo-V2 block"),
], ids=lambda v: "" if isinstance(v, str) else ",".join(v))
def test_a_configuration_the_trunk_does_not_run_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        build_model(tiny(**over))


def test_the_importer_maps_the_configuration_and_refuses_the_tensors():
    """``config.json`` of the catalog's row gives the "flash" preset; the
    checkpoint's tensors are refused with the reason."""
    import json
    import os

    from deepspeed_tpu.models import config_from_hf, import_state_dict

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        hf = next(r for r in map(json.loads, f)
                  if r["name"] == "MiMo-V2-Flash")["config"]
    assert config_from_hf(hf) == mimo_v2_flash("flash")
    with pytest.raises(NotImplementedError, match="names and layouts"):
        import_state_dict({}, hf_config=hf)
    with pytest.raises(ValueError, match="add_full_attention_sink_bias"):
        config_from_hf(dict(hf, add_full_attention_sink_bias=True))

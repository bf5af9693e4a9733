"""Pallas decode attention vs the XLA cache-attention path (interpret mode).

Reference analog: the ``softmax_context`` inference-kernel tests under
``tests/unit/ops/transformer/inference/``."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.decode import _cache_attend
from deepspeed_tpu.ops.decode_attention import decode_attention, tail_rows


def _setup(B=2, S=128, H=4, KV=2, hd=32, length=77, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, KV, hd, S)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((B, KV, hd, S)), jnp.float32)
    return q, ck, cv, jnp.int32(length)


@pytest.mark.parametrize("kv", [4, 2, 1])          # MHA, GQA, MQA
@pytest.mark.parametrize("length", [1, 64, 77, 128])
def test_decode_matches_xla(kv, length):
    q, ck, cv, L = _setup(KV=kv, length=length)
    want = _cache_attend(q, ck, cv, L)              # XLA score-materializing
    got = decode_attention(q, ck, cv, L, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_per_batch_lengths():
    q, ck, cv, _ = _setup()
    lengths = jnp.asarray([30, 100], jnp.int32)
    got = decode_attention(q, ck, cv, lengths, interpret=True)
    for b in range(2):
        want_b = _cache_attend(q[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                               lengths[b])
        np.testing.assert_allclose(np.asarray(got[b:b + 1]),
                                   np.asarray(want_b), rtol=2e-5, atol=2e-5)


def test_decode_bf16():
    q, ck, cv, L = _setup(length=100)
    q, ck, cv = (x.astype(jnp.bfloat16) for x in (q, ck, cv))
    want = _cache_attend(q, ck, cv, L).astype(jnp.float32)
    got = decode_attention(q, ck, cv, L, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=3e-2)


def test_generate_with_flash_decode_matches():
    """End-to-end: generation with the Pallas decode path must produce the
    same tokens as the XLA path (greedy sampling, fp32)."""
    from deepspeed_tpu.inference.decode import generate_tokens
    from deepspeed_tpu.inference.sampling import sample_logits
    from deepspeed_tpu.models import build_model, tiny_test
    from functools import partial

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 8)),
                      jnp.int32)
    sampler = partial(sample_logits, greedy=True, temperature=1.0,
                      top_k=0, top_p=1.0)
    base = generate_tokens(model, params, ids, jax.random.PRNGKey(1),
                           max_new=8, sampler=sampler, flash_decode=False)
    flash = generate_tokens(model, params, ids, jax.random.PRNGKey(1),
                            max_new=8, sampler=sampler, flash_decode=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(flash))


def test_alibi_slopes_in_kernel_match_dense():
    """ALiBi decode stays on the streaming kernel (round 4): the in-kernel
    distance ramp (slope·(s - (L-1)) from the live length) must equal the
    dense path's materialized bias — including under GQA (slopes index by
    QUERY head, the cache by KV group) and per-batch live lengths."""
    from deepspeed_tpu.inference.decode import _cache_attend
    from deepspeed_tpu.models.transformer import alibi_slopes
    from deepspeed_tpu.ops.decode_attention import decode_attention

    B, S, H, hd = 2, 64, 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    slopes = alibi_slopes(H)
    for KV in (H, 2):
        ck = jnp.asarray(rng.standard_normal((B, KV, hd, S)), jnp.float32)
        cv = jnp.asarray(rng.standard_normal((B, KV, hd, S)), jnp.float32)
        for length in (jnp.int32(17), jnp.int32(64),
                       jnp.asarray([13, 49], jnp.int32)):
            got = decode_attention(q, ck, cv, length, alibi_slopes=slopes,
                                   block=16, interpret=True)
            if getattr(length, "ndim", 0):   # dense path takes a scalar:
                want = jnp.concatenate([      # run it per batch row
                    _cache_attend(q[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                                  length[b], flash_decode=False,
                                  alibi=slopes) for b in range(B)])
            else:
                want = _cache_attend(q, ck, cv, length, flash_decode=False,
                                     alibi=slopes)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
                err_msg=f"KV={KV} length={length}")


def test_bloom_generation_flash_vs_dense_decode():
    """End to end: an ALiBi model generates identically with the streaming
    decode kernel and the dense fallback."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import bloom, build_model

    cfg = bloom("tiny", n_layer=2, n_head=4, d_model=64, vocab_size=256,
                max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 8)),
                      jnp.int32)
    dense = ds.init_inference(model, params, {"dtype": "float32",
                                              "flash_decode": False})
    flash = ds.init_inference(model, params, {"dtype": "float32",
                                              "flash_decode": True})
    np.testing.assert_array_equal(
        np.asarray(flash.generate(ids, 6, greedy=True)),
        np.asarray(dense.generate(ids, 6, greedy=True)))


# ------------------------------------------------- the carried-cache step
# One layer loop for every contiguous cache (inference/decode.py): T == 1
# under the gate appends and attends with one kernel on the cache the loop
# carries; the dense path is the oracle, solo generate() the contract.
S = 256                                       # two lane tiles per slot
EDGES = [1, 127, 128, 129, S]                 # live lengths AFTER the append
FAMILIES = {
    "mha-hd64": lambda: _tiny(n_head=2, d_model=128),
    "gqa-hd64": lambda: _tiny(n_head=4, n_kv_head=2, d_model=256),
    "mha-hd128": lambda: _tiny(n_head=2, d_model=256),
    "mha-hd16": lambda: _tiny(n_head=4, d_model=64),
    "alibi-hd64": lambda: _tiny(n_head=2, d_model=128, pos_embedding="alibi"),
    "rope-gqa-hd64": lambda: _tiny(n_head=4, n_kv_head=2, d_model=256,
                                   pos_embedding="rope"),
}


def _tiny(**kw):
    from deepspeed_tpu.models import tiny_test

    return tiny_test(n_layer=2, vocab_size=256, max_seq=S, d_ff=128,
                     dtype=jnp.float32, **kw)


_BUILT = {}


def _family(name):
    if name not in _BUILT:
        from deepspeed_tpu.models import build_model

        cfg = FAMILIES[name]()
        model = build_model(cfg)
        _BUILT[name] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _BUILT[name]


def _slot_cache(cfg, lens, seed=0, stale=0.0):
    """A slot cache whose rows hold ``lens`` live positions of noise and
    ``stale`` behind them (what a retired occupant leaves), as the T == 1
    step takes it over: the kind's tail, where it keeps one, filled from
    the planes."""
    from deepspeed_tpu.inference.decode import KVCache, cache_layout
    from deepspeed_tpu.inference.kinds import kind_of

    lens = np.asarray(lens, np.int32)
    shape, _ = cache_layout(cfg, len(lens), S)
    rng = np.random.default_rng(seed)
    live = np.arange(S) < lens.reshape(-1, 1, 1, 1)           # (B,1,1,S)
    k, v = (np.where(live, rng.standard_normal(shape), sign * stale)
            for sign in (1, -1))
    tail = kind_of(cfg).state(len(lens), jnp.float32).get("tail")
    return kind_of(cfg).rewound(KVCache(
        k=jnp.asarray(k, jnp.float32), v=jnp.asarray(v, jnp.float32),
        length=jnp.asarray(lens),
        tail=tail and jnp.zeros(*tail)))


@pytest.mark.parametrize("lengths", ["ragged", "scalar-127", "scalar-128"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_step_kernels_match_dense_path(family, lengths):
    """The T == 1 forward on the kernels against the same forward on the
    dense path: same logits; the append lands at ``length - 1`` of every
    running slot and nowhere else — a slot that is not running (length 0)
    stays at 0: the kernel leaves its row alone, the dense path writes
    one position of it, the row's own; a neighbour's bits never move; K/V a
    previous occupant left past the live length is never read (1e9 there
    would swamp any sum it entered)."""
    from deepspeed_tpu.inference.decode import forward_with_cache
    from deepspeed_tpu.inference.kinds import kind_of

    cfg, model, params = _family(family)
    if lengths == "ragged":
        length = before = np.asarray([n - 1 for n in EDGES] + [0])  # + idle
    else:                                       # generate()'s: all rows at one
        length = np.int32(int(lengths.split("-")[1]) - 1)
        before = np.full((2,), length)
    B = len(before)
    cache = _slot_cache(cfg, before, stale=1e9)._replace(
        length=jnp.asarray(length))
    tok = jnp.asarray(np.random.default_rng(1).integers(0, 256, (B, 1)),
                      jnp.int32)
    fwd = jax.jit(partial(forward_with_cache, model),
                  static_argnames=("flash_decode",))
    want, dense = fwd(params, tok, cache, flash_decode=False)
    got, fused = fwd(params, tok, cache, flash_decode=True)
    # (where the kind defers the block's write, the planes once settled)
    fused = kind_of(cfg).settled(fused)
    run = before > 0        # a row that is not running: its logits are nobody's
    np.testing.assert_allclose(np.asarray(got)[run], np.asarray(want)[run],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(fused.length),
                                  np.asarray(dense.length))
    np.testing.assert_array_equal(np.asarray(fused.length),
                                  np.where(before > 0, before + 1, 0))
    written = np.arange(S) == before.reshape(-1, 1, 1, 1)
    # (the dense path's update of a row at length 0 starts at -1, which
    # dynamic_update_slice wraps to the row's last position)
    idle = np.broadcast_to((before == 0).reshape(-1, 1, 1, 1), written.shape)
    for new, ref, old in ((fused.k, dense.k, cache.k),
                          (fused.v, dense.v, cache.v)):
        new, ref, old = (np.asarray(a) for a in (new, ref, old))
        np.testing.assert_array_equal(np.where(written & ~idle, 0, new),
                                      np.where(written & ~idle, 0, old))
        np.testing.assert_array_equal(np.where(written | idle, 0, ref),
                                      np.where(written | idle, 0, old))
        # layer 0's new K/V do not pass through attention: bit-equal
        np.testing.assert_array_equal(np.where(idle, 0, new[0]),
                                      np.where(idle, 0, ref[0]))
        np.testing.assert_allclose(np.where(idle, 0, new),
                                   np.where(idle, 0, ref),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lens", [EDGES + [0], 129, 0],
                         ids=["ragged", "scalar", "untouched"])
def test_appended_caches_are_bit_equal_to_set(lens, dtype):
    """The caches the appending call hands back against ``.at[].set``, bit
    for bit, at tile edges, for a layer in the middle of the cache; a slot
    of length 0 writes nothing and its row keeps every bit."""
    L, B, KV, hd = 3, 6, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, 1, KV, hd)), dtype)
    ck, cv = (jnp.asarray(rng.standard_normal((L, B, KV, hd, S)), dtype)
              for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((B, 1, KV, hd)), dtype)
            for _ in range(2))
    n = jnp.asarray(lens, jnp.int32)
    _, got_k, got_v = jax.jit(partial(decode_attention, layer=1,
                                      interpret=True))(q, ck, cv, n, k=k, v=v)
    after = np.broadcast_to(np.asarray(lens), (B,))
    rows, = np.nonzero(after > 0)
    for got, old, new in ((got_k, ck, k), (got_v, cv, v)):
        want = old.at[1, rows, :, :, after[rows] - 1].set(new[rows, 0])
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("family,tp", [(f, 1) for f in FAMILIES]
                         + [("gqa-hd64", 2)])
def test_served_on_the_kernels_equals_solo_generate(family, tp):
    """Four requests through three slots on the kernel path, bit-identical
    to solo ``generate()`` (scalar length, the same kernels): a slot idle
    while its neighbours decode, lengths that walk over the 127/128/129
    tile edge, and a slot re-used after ``insert_request`` while a
    neighbour is mid-answer. Greedy tokens equal the dense engine's too.
    Under a ``model`` axis both kernels run per shard of the KV heads."""
    import deepspeed_tpu as ds

    cfg, model, params = _family(family)
    conf = {"dtype": "float32", "eos_token_id": 7, "tensor_parallel": tp}
    eng = ds.init_inference(model, params, {**conf, "flash_decode": True})
    rng = np.random.default_rng(3)
    prompts = [rng.integers(8, 256, (P,)).astype(np.int32)
               for P in (125, 9, 60, 126)]
    new, seeds = [6, 3, 4, 5], [1, 2, 3, 4]
    serve_cfg = {"slots": 3, "max_len": S, "prefill_chunk": 64,
                 "greedy": True}
    got = ds.ServingEngine(eng, serve_cfg).serve_batch(prompts, new, seeds)
    dense = ds.ServingEngine(
        ds.init_inference(model, params, {**conf, "flash_decode": False}),
        serve_cfg).serve_batch(prompts, new, seeds)
    for p, n, seed, g, d in zip(prompts, new, seeds, got, dense):
        want = np.asarray(eng.generate(
            jnp.asarray(p[None]), n, greedy=True, request_seeds=[seed],
            cache_len=S))[0]
        np.testing.assert_array_equal(g, want[:len(g)])
        np.testing.assert_array_equal(g, d)


def test_step_consumes_its_cache():
    """The step donates its carry: the cache handed in is gone afterwards
    and the one handed back lives where it lived."""
    from deepspeed_tpu.inference.decode import GenCarry, decode_step
    from deepspeed_tpu.inference.sampling import sample_logits

    cfg, model, params = _family("mha-hd64")
    cache = _slot_cache(cfg, [5, 0, 128])
    carry = GenCarry(tok=jnp.zeros((3,), jnp.int32), cache=cache,
                     rng=jnp.zeros((3, 2), jnp.uint32),
                     done=jnp.zeros((3,), bool))
    step = jax.jit(partial(
        decode_step, model, flash_decode=True, logit_guard=True,
        sampler=partial(sample_logits, greedy=True, temperature=1.0,
                        top_k=0, top_p=1.0)), donate_argnums=(1,))
    where = (cache.k.unsafe_buffer_pointer(), cache.v.unsafe_buffer_pointer())
    out, ok = step(params, carry)
    assert cache.k.is_deleted() and cache.v.is_deleted()
    assert (out.cache.k.unsafe_buffer_pointer(),
            out.cache.v.unsafe_buffer_pointer()) == where
    assert bool(np.all(np.asarray(ok)))
    # the slot at length 0 is not running: it stays there
    np.testing.assert_array_equal(np.asarray(out.cache.length), [6, 0, 129])


# ------------------------------------- a slot's heads, live blocks only
# The kernel takes a slot's KV heads in one program and copies that slot's
# live blocks out of the cache itself (PR 30): edge lengths in one batch,
# the head counts and sizes its tile rule has to hold for, and what the
# serving cells' ``correct`` rests on: a slot's bits do not depend on its
# neighbours, and nothing behind the live length is read.
S3 = 384                                      # three lane tiles per slot
MIXED = [0, 1, 127, 128, 129, S3, S3 + 77, 300]   # past S3: clamped
SHAPES = {                                    # B, H, KV, hd
    "mha-20x64-48slots": (48, 20, 20, 64),
    "gqa-group4": (8, 8, 2, 64),
    "tp-shard-5heads": (8, 5, 5, 64),
    "mha-hd128": (8, 4, 4, 128),
    "mqa-group16": (8, 16, 1, 64),
}


def _case(B, H, KV, hd, S=S3, dtype=jnp.float32, seed=0, layers=None):
    rng = np.random.default_rng(seed)
    lead = () if layers is None else (layers,)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), dtype)
    ck, cv = (jnp.asarray(rng.standard_normal(lead + (B, KV, hd, S)), dtype)
              for _ in range(2))
    n = jnp.asarray((MIXED * (B // len(MIXED) + 1))[:B], jnp.int32)
    return q, ck, cv, n


def _dense_rows(q, ck, cv, n, alibi=None):
    """The dense path a row at a time (it takes one length), the length
    clamped into the cache as the kernel clamps it."""
    S = ck.shape[-1]
    return jnp.concatenate([
        _cache_attend(q[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                      jnp.minimum(n[b], S), alibi=alibi)
        for b in range(q.shape[0])])


@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_mixed_lengths_in_one_batch_match_dense(shape, alibi):
    """Lengths 0, 1, 127, 128, 129, max_len and past max_len (clamped) in
    one batch against the dense path, for 20 x 64 at 48 slots, a GQA group
    of 4, a TP shard's 5 heads, ``hd`` 128 and MQA; with ALiBi the slopes
    index by QUERY head while the cache is read by KV head."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    B, H, KV, hd = SHAPES[shape]
    q, ck, cv, n = _case(B, H, KV, hd)
    slopes = alibi_slopes(H) if alibi else None
    got = np.asarray(decode_attention(q, ck, cv, n, alibi_slopes=slopes,
                                      interpret=True))
    want = np.asarray(_dense_rows(q, ck, cv, n, alibi=slopes))
    live = np.asarray(n) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    # a slot that holds nothing attends to nothing: zeros, not NaN
    np.testing.assert_array_equal(got[~live], 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_slab_entry_equals_cache_entry_with_traced_layer(dtype):
    """The two ways in: a layer's 4-D slab (``_cache_attend``, the paged
    view) and the whole 5-D cache with a traced ``layer``, bit for bit."""
    q, ck, cv, n = _case(8, 4, 2, 64, dtype=dtype, layers=3)
    whole = jax.jit(lambda q, ck, cv, n, layer: decode_attention(
        q, ck, cv, n, layer=layer, interpret=True))
    for layer in range(3):
        slab = decode_attention(q, ck[layer], cv[layer], n, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(whole(q, ck, cv, n, jnp.int32(layer)), np.float32),
            np.asarray(slab, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("slot", [0, 17, 28, 47])
def test_slot_of_48_is_bit_equal_to_the_row_alone(slot, dtype):
    """Slot ``i`` of a batch of 48 (20 heads x 64) against the same row run
    alone at batch 1 with a scalar length, as solo ``generate()`` runs it:
    the same bits. The tile and the order of accumulation over a slot's
    blocks follow from the slot's own shapes and length."""
    q, ck, cv, n = _case(48, 20, 20, 64, dtype=dtype, layers=2)
    batch = decode_attention(q, ck, cv, n, layer=1, interpret=True)
    alone = decode_attention(q[slot:slot + 1], ck[:, slot:slot + 1],
                             cv[:, slot:slot + 1], n[slot], layer=1,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(batch[slot], np.float32),
                                  np.asarray(alone[0], np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length", [1, 100, 128, 129, 300])
def test_garbage_behind_the_live_length_is_never_read(length, dtype):
    """Large finite garbage (1e4) behind the live length, in the live
    length's own block and in the later ones, leaves the output
    bit-unchanged (``0 x NaN`` would poison ``p . v`` in any kernel that
    multiplies a whole block, so NaN cannot be the probe)."""
    q, ck, cv, _ = _case(4, 20, 20, 64, dtype=dtype)
    n = jnp.asarray([length, 5, length, 0], jnp.int32)
    clean = decode_attention(q, ck, cv, n, interpret=True)
    behind = np.arange(S3) >= np.asarray(n).reshape(-1, 1, 1, 1)
    dirty = decode_attention(q, jnp.where(behind, 1e4, ck).astype(dtype),
                             jnp.where(behind, -1e4, cv).astype(dtype), n,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(clean, np.float32),
                                  np.asarray(dirty, np.float32))


# ------------------------------------------- the step appends as it attends
# With the step's new K/V as operands the kernel patches them into the last
# live block it has fetched, attends from the patched buffers and writes the
# block back once (PR 32). The oracle is what the step did before: the
# append alone (``append_in_place``, kept for the latent cache), then the
# read-only call.
FUSED = {**SHAPES, "64heads-two-programs": (6, 64, 64, 128)}


def _append_then_attend(q, ck, cv, k, v, n, layer, alibi=None):
    """(o, cache_k, cache_v) by the two kernels of the parent; a slot of
    length 0, which the parent's append wrote at position 0, keeps its row."""
    from deepspeed_tpu.ops.decode_attention import append_in_place

    ak, av = append_in_place((ck, cv), (k, v), n, jnp.int32(layer).reshape(1),
                             name="cache_append", interpret=True)
    idle = (np.asarray(n) == 0).reshape(1, -1, 1, 1, 1)
    ak, av = jnp.where(idle, ck, ak), jnp.where(idle, cv, av)
    return (decode_attention(q, ak, av, n, layer=layer, alibi_slopes=alibi,
                             interpret=True), ak, av)


def _new(B, KV, hd, dtype=jnp.float32):
    """A step's new K and V, (B, 1, KV, hd) each."""
    rng = np.random.default_rng(1)
    return tuple(jnp.asarray(rng.standard_normal((B, 1, KV, hd)), dtype)
                 for _ in range(2))


def _bits(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
@pytest.mark.parametrize("shape", list(FUSED))
def test_appending_call_equals_append_then_attend(shape, alibi):
    """Lengths 0, 1, 127, 128, 129, 300, max_len and past it (clamped) in
    one batch: the output and both caches of the one call, bit for bit
    those of the append followed by the read-only call. With 64 heads of
    128 in bfloat16 two programs take a slot, each its own heads' block."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    B, H, KV, hd = FUSED[shape]
    dtype = jnp.bfloat16 if shape == "64heads-two-programs" else jnp.float32
    q, ck, cv, n = _case(B, H, KV, hd, dtype=dtype, layers=2)
    k, v = _new(B, KV, hd, dtype)
    slopes = alibi_slopes(H) if alibi else None
    want = _append_then_attend(q, ck, cv, k, v, n, 1, slopes)
    got = decode_attention(q, ck, cv, n, k=k, v=v, layer=1,
                           alibi_slopes=slopes, interpret=True)
    for g, w, what in zip(got, want, ("o", "cache_k", "cache_v")):
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=what)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_short_slot_behind_a_long_one_is_patched_by_its_owner(dtype):
    """A slot of at most 128 positions behind a long one: its only block is
    the one the program before it fetched ahead, unpatched; the slot's own
    program patches it (and a long slot behind a short one, and an empty
    one between)."""
    lens = [300, 5, S3, 128, 200, 1, 0, 2, 257, 127]
    q, ck, cv, _ = _case(len(lens), 4, 4, 64, dtype=dtype, layers=2)
    k, v = _new(len(lens), 4, 64, dtype)
    n = jnp.asarray(lens, jnp.int32)
    want = _append_then_attend(q, ck, cv, k, v, n, 0)
    got = decode_attention(q, ck, cv, n, k=k, v=v, layer=0, interpret=True)
    for g, w, what in zip(got, want, ("o", "cache_k", "cache_v")):
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=what)
    rows = np.arange(len(lens))[np.asarray(lens) > 0]
    at = np.asarray(lens)[rows] - 1
    np.testing.assert_array_equal(_bits(got[1])[0, rows, :, :, at],
                                  _bits(k)[rows, 0])


@pytest.mark.parametrize("slot,length", [(0, 1), (3, 128), (5, 129),
                                         (7, S3 + 9)])
def test_append_touches_one_column_of_one_slot_of_one_layer(slot, length):
    """One slot appends, its neighbours hold nothing: of the three layers
    of both caches exactly ``KV x hd`` values change, those at
    ``length - 1`` (clamped) of that slot in that layer."""
    B, KV, hd = 8, 5, 64
    q, ck, cv, _ = _case(B, KV, KV, hd, layers=3)
    k, v = 100.0 + q, -100.0 - q                  # no value of the cache
    n = jnp.zeros((B,), jnp.int32).at[slot].set(length)
    _, got_k, got_v = decode_attention(q, ck, cv, n, k=k, v=v, layer=1,
                                       interpret=True)
    for got, old, new in ((got_k, ck, k), (got_v, cv, v)):
        moved = np.argwhere(_bits(got) != _bits(old))
        assert len(moved) == KV * hd
        assert {tuple(m[[0, 1, 4]]) for m in moved} \
            == {(1, slot, min(length, S3) - 1)}
        np.testing.assert_array_equal(
            _bits(got)[1, slot, :, :, min(length, S3) - 1], _bits(new)[slot, 0])


def test_read_only_call_is_the_appended_caches_reader():
    """Without new values the call reads and hands back the output alone:
    over the caches the appending call returned it gives that call's
    output, bit for bit (the paged view's slab, a caller that appended)."""
    q, ck, cv, n = _case(8, 8, 2, 64, layers=2)
    k, v = _new(8, 2, 64)
    o, ak, av = decode_attention(q, ck, cv, n, k=k, v=v, layer=1,
                                 interpret=True)
    again = decode_attention(q, ak, av, n, layer=1, interpret=True)
    assert isinstance(again, jax.Array)
    np.testing.assert_array_equal(_bits(again), _bits(o))
    slab = decode_attention(q, ck[1], cv[1], n, k=k, v=v, interpret=True)
    for g, w in zip(slab, (o, ak[1], av[1])):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def test_heads_per_program_follow_the_shapes():
    """No knob: the KV heads a program takes are the largest divisor of
    ``KV`` whose K block fits the budget, whatever the batch."""
    from deepspeed_tpu.ops.decode_attention import _heads_per_program

    assert _heads_per_program(20, 64, 128, jnp.bfloat16) == 20   # GPT-2 774M
    assert _heads_per_program(5, 64, 128, jnp.bfloat16) == 5     # TP shard
    assert _heads_per_program(32, 128, 128, jnp.bfloat16) == 32  # 7B, hd 128
    assert _heads_per_program(64, 128, 128, jnp.bfloat16) == 32  # over: halve
    assert _heads_per_program(64, 128, 128, jnp.float32) == 16
    assert _heads_per_program(7, 4096, 128, jnp.float32) == 1    # never 0


def test_decode_step_span_says_fetched_over_live():
    """``attn_fetched_over_live`` on the ``decode_step`` span: host
    arithmetic on a mirror of the slots' lengths, which tracks the
    device's vector through placements, steps and retirements; a slot
    that is not running stands at length 0 and nothing is fetched for it
    (``idle_fetched``)."""
    import deepspeed_tpu as ds

    cfg, model, params = _family("mha-hd64")
    eng = ds.init_inference(model, params, {"dtype": "float32",
                                            "eos_token_id": 7,
                                            "flash_decode": True})
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": S, "greedy": True,
                                 "prefill_chunk": 64, "spans": True})
    rng = np.random.default_rng(5)
    prompts = [rng.integers(8, 256, (P,)).astype(np.int32)
               for P in (120, 9, 60, 130)]
    srv.serve_batch(prompts, [12, 3, 4, 5], [1, 2, 3, 4])
    np.testing.assert_array_equal(srv._slot_len,
                                  np.asarray(srv._state.cache.length))
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    ratios = [e.meta["attn_fetched_over_live"] for e in steps]
    assert len(ratios) == len(steps) > 0
    assert min(ratios) >= 1.0
    # the first step: one request of 120 tokens seated, attending 121
    # positions of its one block; two slots not running: nothing
    assert ratios[0] == pytest.approx(128 / 121)
    assert [e.meta["idle_fetched"] for e in steps] == [0] * len(steps)


def _moved_over_new(lens, ran, T):
    """What a step moves to append over the positions it appends, from
    the lengths it leaves: a block of 128 a live slot where the cache has
    no tail; with a tail of T rows the slot's tile in and out, and the block
    for the slots whose group of T the step completed."""
    live = lens > 0
    if not T:
        return 128 * live.sum() / ran
    return (2 * T * live.sum() + 128 * (live & (lens % T == 0)).sum()) / ran


@pytest.mark.parametrize("family,T", [("mha-hd64", 8), ("mha-hd128", 8),
                                      ("mha-hd16", 0)])
def test_decode_step_span_says_append_moved_over_new(family, T):
    """``append_moved_over_new`` on the ``decode_step`` span: over the one
    new position of each running request, what the kernel moves to append
    it. Where the cache keeps a deferred tail (K beside V fill whole lane
    tiles: heads of 64 and of 128; T = 8 rows of float32) a tile of T
    positions in and out for every slot whose length is over 0 (the
    running ones alone: a row that is not running stands at 0) and the
    block of 128 for those whose group the step completed; with heads of
    16 there is no tail and the block goes back every step: 128."""
    import deepspeed_tpu as ds

    cfg, model, params = _family(family)
    eng = ds.init_inference(model, params, {"dtype": "float32",
                                            "eos_token_id": 7,
                                            "flash_decode": True})
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": S, "greedy": True,
                                 "prefill_chunk": 64, "spans": True})
    assert srv.kind.deferred_rows(jnp.float32) == T
    assert (srv._state.cache.tail is not None) == bool(T)
    seen, counts = [], srv._attn_counts
    srv._attn_counts = lambda fl: seen.append(fl) or counts(fl)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(8, 256, (P,)).astype(np.int32)
               for P in (120, 9, 60, 130)]
    srv.serve_batch(prompts, [12, 3, 4, 5], [1, 2, 3, 4])
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"
             and e.meta["slots"]]
    seen = [fl for fl in seen if fl.rows]
    assert len(steps) == len(seen) > 0
    running = {e.meta["slots"] for e in steps}
    assert running >= {1, 2, 3}
    # whatever runs: a tile (or a block) for each running request and for
    # no other slot, a block more where a group of T ended
    for e, fl in zip(steps, seen):
        assert e.meta["append_moved_over_new"] == pytest.approx(
            _moved_over_new(fl.lens, len(fl.rows), T))
    moved = {e.meta["append_moved_over_new"] for e in steps}
    if T:
        # between a tile in and out, and that with a block a slot
        assert min(moved) == 2 * T and 2 * T < max(moved) <= 2 * T + 128
    else:
        assert moved == {128.0}


# ------------------------------------------- several live blocks a loop turn
# Where the heads are few a turn takes W consecutive live blocks (PR 46):
# W copies of one block each into adjacent lane ranges of the turn's buffer,
# one chain of products over them. W follows from (KV, hd, vd, max_len,
# dtype) as the heads a program takes do; the cases below are float32, whose
# blocks are twice bfloat16's: 2 KV heads of 64 are 64 KB a block (W = 8, as
# 2 of 128 in bfloat16), 4 of 128 are 256 KB (W = 2).
def test_blocks_per_turn_follow_the_shapes():
    """No knob: the blocks a turn takes are those of the program's heads
    (the wider of K and V) that fit the per-turn target, whatever the
    batch; never more than the cache has, never 0. The serving cells whose
    heads fill a turn keep one block a turn: the parent's program."""
    from deepspeed_tpu.ops.decode_attention import blocks_per_turn

    bf16 = jnp.bfloat16
    assert blocks_per_turn(2, 128, 128, 4096, bf16) == 8    # ZAYA, Nemotron
    assert blocks_per_turn(4, 192, 128, 4096, bf16) == 2    # MiMo, full
    assert blocks_per_turn(8, 192, 128, 256, bf16) == 1     # MiMo, window
    assert blocks_per_turn(20, 64, 64, 1024, bf16) == 1     # GPT-2 774M
    assert blocks_per_turn(16, 128, 128, 2048, bf16) == 1   # Ouro-2.6B
    assert blocks_per_turn(16, 128, 128, 4096, bf16) == 1   # 7B, a TP-2 shard
    assert blocks_per_turn(32, 128, 128, 4096, bf16) == 1   # 7B whole: 1 MiB
    assert blocks_per_turn(5, 64, 64, 1024, bf16) == 6      # GPT-2, TP-4 shard
    assert blocks_per_turn(2, 128, 128, 128, bf16) == 1     # the cache's own
    assert blocks_per_turn(2, 128, 128, 256, bf16) == 2
    assert blocks_per_turn(2, 64, 64, 4096, jnp.float32) == 8
    assert blocks_per_turn(7, 4096, 4096, 1024, jnp.float32) == 1  # never 0
    # the wider of K and V: values of 256 beside keys of 128 halve the turn
    assert blocks_per_turn(2, 128, 256, 4096, bf16) == 4


def _dense(q, ck, cv, n, window=0, sink=None):
    """The plain expression in float64: q (B, 1, H, hd) over a layer's
    caches (B, KV, ., S) at lengths ``n`` (after the append); a ring where
    ``window``; zeros for a slot at 0."""
    q, ck, cv = (np.asarray(x, np.float64) for x in (q, ck, cv))
    B, _, H, hd = q.shape
    KV, S = ck.shape[1], ck.shape[3]
    out = np.zeros((B, 1, H, cv.shape[2]))
    for b, L in enumerate(np.asarray(n)):
        L = int(L) if window else min(int(L), S)
        if not L:
            continue
        pos = np.arange(max(L - window, 0) if window else 0, L) % S
        for h in range(H):
            kv = h // (H // KV)
            s = q[b, 0, h] @ ck[b, kv][:, pos] / np.sqrt(hd)
            m = s.max() if sink is None else max(s.max(), sink[h])
            e = np.exp(s - m)
            den = e.sum() + (0 if sink is None else np.exp(sink[h] - m))
            out[b, 0, h] = cv[b, kv][:, pos] @ e / den
    return out


# H, KV, hd, vd, max_len: what the width rule gives is asserted in the test
TURNS = {
    "gqa-group4-w8": (8, 2, 64, 64, 2048, 8),
    "group1-w8": (2, 2, 64, 64, 2048, 8),
    "keys-wider-w5": (8, 2, 96, 64, 2048, 5),
    "4x128-w2": (4, 4, 128, 128, 512, 2),
}


def _turn_case(shape, layers=2, seed=0):
    """Lengths 0, 1, 127, 128, 129, W 128 - 1, W 128, W 128 + 1, a length
    inside the second turn and ``max_len`` in one batch, idle slots among
    them."""
    from deepspeed_tpu.ops.decode_attention import blocks_per_turn

    H, KV, hd, vd, S, W = TURNS[shape]
    assert blocks_per_turn(KV, hd, vd, S, jnp.float32) == W
    lens = [0, 1, 127, 128, 129, W * 128 - 1, W * 128, W * 128 + 1, 0,
            min(W * 128 + 300, S), S]
    rng = np.random.default_rng(seed)
    B = len(lens)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((layers, B, KV, hd, S)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((layers, B, KV, vd, S)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, 1, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, 1, KV, vd)), jnp.float32)
    return q, ck, cv, k, v, np.asarray(lens, np.int32)


def _set(cache, layer, new, lens):
    """``cache`` with ``new`` at position ``length - 1`` of every running
    slot of ``layer``, as numpy."""
    out = np.array(cache)
    S = out.shape[-1]
    for b, n in enumerate(lens):
        if n:
            out[layer, b, :, :, (n - 1) % S] = np.asarray(new)[b, 0]
    return out


@pytest.mark.parametrize("append", [True, False], ids=["appending", "reading"])
@pytest.mark.parametrize("shape", list(TURNS))
def test_wide_turns_match_the_dense_reference(shape, append):
    """Turns of 8, 5 and 2 blocks against the plain expression at every
    edge of a block and of a turn: GQA groups of 4 and of 1, keys wider
    than values; appending (the caches bit-equal to ``.at[].set``, the new
    column attended to) and read-only."""
    q, ck, cv, k, v, lens = _turn_case(shape)
    n = jnp.asarray(lens)
    with jax.default_matmul_precision("highest"):
        if append:
            out, gk, gv = jax.jit(partial(decode_attention, layer=1,
                                          interpret=True))(q, ck, cv, n,
                                                           k=k, v=v)
            want_k, want_v = _set(ck, 1, k, lens), _set(cv, 1, v, lens)
            np.testing.assert_array_equal(np.asarray(gk), want_k)
            np.testing.assert_array_equal(np.asarray(gv), want_v)
        else:
            out = decode_attention(q, ck, cv, n, layer=1, interpret=True)
            want_k, want_v = np.asarray(ck), np.asarray(cv)
    want = _dense(q, want_k[1], want_v[1], lens)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[lens == 0], 0.0)


@pytest.mark.parametrize("sink", [False, True], ids=["ring", "ring+sink"])
@pytest.mark.parametrize("window", [384, 300])
def test_wide_turns_over_a_ring_that_wraps(window, sink):
    """A ring of four blocks under turns of two: windows that lie inside
    the first blocks, that end on a block's edge, whose first turn crosses
    the ring's wrap (blocks 3, 0) and whose second does (2, 3 | 0, 1), with
    and without a sink; every block folded into the ring on its own, only
    the slot's own column written."""
    from deepspeed_tpu.ops.decode_attention import blocks_per_turn

    H, KV, hd, R = 8, 4, 128, 512
    assert blocks_per_turn(KV, hd, hd, R, jnp.float32) == 2
    lens = np.asarray([5, 300, 384, 0, 512, 513, 641, 770, 900, 1025, 1408],
                      np.int32)
    rng = np.random.default_rng(7)
    B = len(lens)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    ck, cv = (jnp.asarray(rng.standard_normal((2, B, KV, hd, R)), jnp.float32)
              for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((B, 1, KV, hd)), jnp.float32)
            for _ in range(2))
    sk = rng.standard_normal((H,)).astype(np.float32) + 2.0 if sink else None
    with jax.default_matmul_precision("highest"):
        out, gk, gv = jax.jit(lambda *a: decode_attention(
            a[0], a[1], a[2], jnp.asarray(lens), k=a[3], v=a[4],
            layer=jnp.int32(0), window=window,
            sink=None if sk is None else jnp.asarray(sk),
            interpret=True))(q, ck, cv, k, v)
    want_k, want_v = _set(ck, 0, k, lens), _set(cv, 0, v, lens)
    np.testing.assert_array_equal(np.asarray(gk), want_k)
    np.testing.assert_array_equal(np.asarray(gv), want_v)
    want = _dense(q, want_k[0], want_v[0], lens, window, sk)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "-inf"])
@pytest.mark.parametrize("shape", ["gqa-group4-w8", "4x128-w2"])
def test_blocks_behind_the_live_length_are_never_fetched(shape, poison):
    """NaN and Inf in every block behind a slot's last live one (and in
    the whole row of a slot at 0): a turn starts copies for its live blocks
    alone and the lanes of the rest hold what the zeroed scratch or an
    earlier turn's real K/V left, so the outputs are finite, bit-equal to
    a clean cache's, and every position of the returned caches but the
    appended column keeps its bits. (Inside the last live block the kernel
    multiplies whole lanes, so there the probe is large and finite, as in
    ``test_garbage_behind_the_live_length_is_never_read``.)"""
    q, ck, cv, k, v, lens = _turn_case(shape)
    S = ck.shape[-1]
    at = np.arange(S)
    edge = (-(-lens // 128) * 128).reshape(-1, 1, 1, 1)
    tail = (at >= lens.reshape(-1, 1, 1, 1)) & (at < edge)
    dk = jnp.where(at >= edge, poison, jnp.where(tail, 1e4, ck))
    dv = jnp.where(at >= edge, -poison, jnp.where(tail, -1e4, cv))
    run = jax.jit(partial(decode_attention, layer=1, interpret=True))
    clean = run(q, ck, cv, jnp.asarray(lens), k=k, v=v)
    dirty = run(q, dk, dv, jnp.asarray(lens), k=k, v=v)
    assert np.isfinite(np.asarray(dirty[0])).all()
    np.testing.assert_array_equal(np.asarray(dirty[0]), np.asarray(clean[0]))
    for got, old, new in ((dirty[1], dk, k), (dirty[2], dv, v)):
        np.testing.assert_array_equal(np.asarray(got), _set(old, 1, new, lens))


@pytest.mark.parametrize("append", [True, False], ids=["appending", "reading"])
@pytest.mark.parametrize("slot", [1, 5, 7, 9, 10])
def test_a_slot_of_wide_turns_is_bit_equal_whatever_its_neighbours(slot,
                                                                   append):
    """A slot's output among eleven slots of every length against the same
    row alone at batch 1 with a scalar length, and against the same row
    among neighbours at other lengths (so its first turn is fetched ahead
    into the other buffer, by a program whose own turns differ): the same
    bits. Turns count from the slot's own first live block."""
    q, ck, cv, k, v, lens = _turn_case("gqa-group4-w8")
    new = dict(k=k, v=v) if append else {}

    def one(x):
        return x[slot:slot + 1]

    def run(q, ck, cv, n, **new):
        out = decode_attention(q, ck, cv, n, layer=1, interpret=True, **new)
        return np.asarray(out[0] if new else out)

    batch = run(q, ck, cv, jnp.asarray(lens), **new)
    alone = run(one(q), ck[:, slot:slot + 1], cv[:, slot:slot + 1],
                jnp.int32(lens[slot]), **{a: one(x) for a, x in new.items()})
    np.testing.assert_array_equal(batch[slot], alone[0])
    others = np.where(np.arange(len(lens)) == slot, lens,
                      np.roll(lens, 3) // 2 + 64)
    moved = run(q, ck, cv, jnp.asarray(others.astype(np.int32)), **new)
    np.testing.assert_array_equal(batch[slot], moved[slot])


def test_decode_step_span_says_blocks_per_turn():
    """``attn_blocks_per_turn`` on the ``decode_step`` span: the live
    blocks the step's slots fetch over the loop turns the kernel takes for
    them, with the kernel's own width at the cache's shapes (2 KV heads of
    64 in float32 over a cache of two blocks: W = 2). 1.0 while every
    running slot holds one block; 2.0 when the one slot running holds two,
    fetched in one turn; between them when slots of one block run beside
    it."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.ops.decode_attention import blocks_per_turn

    cfg, model, params = _family("mha-hd64")
    assert blocks_per_turn(2, 64, 64, S, jnp.float32) == 2
    eng = ds.init_inference(model, params, {"dtype": "float32",
                                            "eos_token_id": 7,
                                            "flash_decode": True})
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": S, "greedy": True,
                                 "prefill_chunk": 64, "spans": True})
    rng = np.random.default_rng(5)
    prompts = [rng.integers(8, 256, (P,)).astype(np.int32)
               for P in (120, 9, 60, 130)]
    srv.serve_batch(prompts, [12, 3, 4, 5], [1, 2, 3, 4])
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    ratios = [e.meta["attn_blocks_per_turn"] for e in steps]
    assert len(ratios) == len(steps) > 0
    assert ratios[0] == 1.0                  # 121 positions: a block, a turn
    # one or two blocks a slot, a turn each: 3/2 with one slot of each
    assert set(ratios) <= {1.0, 4 / 3, 1.5, 5 / 3, 2.0}
    assert 2.0 in ratios and min(ratios) == 1.0


# ------------------------------------------------------- the deferred tail
# A dense cache's newest positions stand in a tail of T rows a slot and KV
# head, K beside V on the lanes (PR 49): the step's kernel writes the new
# position's ROW and the slot's tile, takes the current group's columns
# from the tile, and writes the block of 128 back only where the step
# completed the group. The kernel without a tail, on a cache that holds the
# same positions in its blocks, is the oracle: bit for bit.
TAILED = {                                    # B, H, KV, hd, dtype
    "mha-hd64": (9, 4, 4, 64, jnp.float32),
    "mha-hd64-bf16": (9, 4, 4, 64, jnp.bfloat16),
    "gqa-group4-hd64-w3": (9, 8, 2, 64, jnp.float32),
    "gqa-group2-hd128": (9, 4, 2, 128, jnp.bfloat16),
    "mha-hd128": (10, 2, 2, 128, jnp.float32),
}


def _tail_lengths(B, T):
    """Lengths AFTER the append: 0, 1, T - 1, T, T + 1, 127, 128, 129 and
    max_len in one batch (then a few in the middle of groups)."""
    return ([0, 1, T - 1, T, T + 1, 127, 128, 129, S3] + [300, 77])[:B]


def _group_start(n, T):
    return max(int(n) - 1, 0) // T * T


def _np_tail(ck, cv, before, T):
    """The tail a cache at lengths ``before`` stands with, from planes that
    hold every position: the group of position ``before - 1`` a slot."""
    ck, cv = np.asarray(ck), np.asarray(cv)
    L, B, KV, hd, _ = ck.shape
    tail = np.zeros((L, B, KV, T, hd + cv.shape[3]), ck.dtype)
    for b in range(B):
        g = _group_start(before[b], T)
        tail[:, b, :, :, :hd] = ck[:, b, :, :, g:g + T].swapaxes(-1, -2)
        tail[:, b, :, :, hd:] = cv[:, b, :, :, g:g + T].swapaxes(-1, -2)
    return tail


def _np_settled(ck, cv, tail, n, T, layer):
    """Layer ``layer``'s planes with the current group's live rows taken
    from the tail (what ``Dense.settled`` does, by hand)."""
    ck, cv, tail = (np.array(a) for a in (ck, cv, tail))
    hd = ck.shape[3]
    for b, m in enumerate(np.minimum(np.asarray(n), ck.shape[-1])):
        g = _group_start(m, T)
        for r in range(m - g):
            ck[layer, b, :, :, g + r] = tail[layer, b, :, r, :hd]
            cv[layer, b, :, :, g + r] = tail[layer, b, :, r, hd:]
    return ck, cv


def _tail_case(shape, before=None, seed=0, layers=2, B=None):
    """(q, planes that hold everything, the same planes with what the
    blocks hold of each slot's current group overwritten with noise — the
    tail is the truth there —, the tail, the lengths before the step)."""
    slots, H, KV, hd, dtype = TAILED[shape]
    B = B or slots
    T = tail_rows(dtype)
    if before is None:
        before = [max(n - 1, 0) for n in _tail_lengths(B, T)]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), dtype)
    ck, cv = (jnp.asarray(rng.standard_normal((layers, B, KV, hd, S3)), dtype)
              for _ in range(2))
    tail = _np_tail(ck, cv, before, T)
    holes = [np.array(ck), np.array(cv)]
    for b, m in enumerate(before):
        if m % T:          # an incomplete group: the blocks may hold anything
            for plane in holes:
                plane[:, b, :, :, _group_start(m, T):m] = 99.0
    return (q, (ck, cv), tuple(jnp.asarray(p) for p in holes),
            jnp.asarray(tail), np.asarray(before), T)


def _step_lengths(before):
    return jnp.asarray(np.where(before > 0, np.minimum(before + 1, S3), 0),
                       jnp.int32)


def _raw(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("shape", list(TAILED))
def test_tailed_step_is_bit_equal_to_the_step_without_a_tail(shape):
    """Lengths 0, 1, T - 1, T, T + 1, 127, 128, 129 and max_len in one
    batch, MHA and GQA, heads of 64 and 128, one and three blocks a turn:
    the output bit for bit that of the kernel without a tail over planes
    that hold every position; the planes, once the tail's live rows are
    settled into them, bit for bit its planes; and nothing written but the
    slot's own tile (its one new row) and, where the step completed a
    group of T, the group's block."""
    B, H, KV, hd, dtype = TAILED[shape]
    q, full, holes, tail, before, T = _tail_case(shape)
    k, v = _new(B, KV, hd, dtype)
    n = _step_lengths(before)
    want_o, want_k, want_v = decode_attention(
        q, *full, n, k=k, v=v, layer=1, interpret=True)
    got_o, got_k, got_v, got_t = decode_attention(
        q, *holes, n, k=k, v=v, tail=tail, layer=1, interpret=True)
    np.testing.assert_array_equal(_raw(got_o), _raw(want_o))
    # against the dense path too, a row at a time
    ref = _dense_rows(q, want_k[1], want_v[1], n)
    live = np.asarray(n) > 0
    np.testing.assert_allclose(
        _bits(got_o)[live], _bits(ref)[live],
        **(dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16
           else dict(rtol=2e-5, atol=2e-5)))
    sk, sv = _np_settled(got_k, got_v, got_t, n, T, 1)
    # (the noise in the blocks lies inside the groups the tail settles)
    np.testing.assert_array_equal(_raw(sk[1]), _raw(want_k[1]))
    np.testing.assert_array_equal(_raw(sv[1]), _raw(want_v[1]))
    n = np.asarray(n)
    for got, old in ((got_k, holes[0]), (got_v, holes[1])):
        changed = (_raw(got) != _raw(old)).any(axis=(2, 3))     # (L, B, S)
        assert not changed[0].any(), "another layer's planes moved"
        for b in range(B):
            at, = np.nonzero(changed[1, b])
            if n[b] == 0 or n[b] % T:
                assert not len(at), (b, n[b], at)     # the block stayed
            else:           # the group's columns, in the block that went
                assert set(at) <= set(range(n[b] - T, n[b])), (b, n[b], at)
    moved = (_raw(got_t) != _raw(tail)).any(axis=(2, 4))        # (L, B, T)
    assert not moved[0].any(), "another layer's tail moved"
    for b in range(B):
        rows, = np.nonzero(moved[1, b])
        assert set(rows) <= ({(n[b] - 1) % T} if n[b] else set()), (b, rows)
        if n[b]:
            hd_ = k.shape[-1]
            np.testing.assert_array_equal(
                _raw(got_t[1, b, :, (n[b] - 1) % T]),
                _raw(jnp.concatenate([k[b, 0], v[b, 0]], -1).astype(dtype)))


@pytest.mark.parametrize("slot", [1, 3, 4, 8])
@pytest.mark.parametrize("shape", ["mha-hd64-bf16", "gqa-group4-hd64-w3"])
def test_a_tailed_slot_is_bit_equal_whatever_its_neighbours(shape, slot):
    """A slot's output, tile and planes out of a batch of nine equal the
    same slot stepped alone: the tile a program takes, and which of the two
    tile buffers, follow the grid; the bits do not."""
    B, H, KV, hd, dtype = TAILED[shape]
    q, _, holes, tail, before, T = _tail_case(shape, seed=slot)
    k, v = _new(B, KV, hd, dtype)
    n = _step_lengths(before)
    many = decode_attention(q, *holes, n, k=k, v=v, tail=tail, layer=1,
                            interpret=True)
    one = slice(slot, slot + 1)
    alone = decode_attention(
        q[one], holes[0][:, one], holes[1][:, one], n[one], k=k[one],
        v=v[one], tail=tail[:, one], layer=1, interpret=True)
    np.testing.assert_array_equal(_raw(many[0][one]), _raw(alone[0]))
    for got, want in zip(many[1:], alone[1:]):
        np.testing.assert_array_equal(_raw(got[:, one]), _raw(want))


@pytest.mark.parametrize("start", [0, 5, 127 - 16])
@pytest.mark.parametrize("shape", ["mha-hd64", "gqa-group2-hd128"])
def test_tailed_steps_in_a_row_across_a_groups_end(shape, start):
    """T + 3 steps in a row from the middle of a group (``start`` rows of
    it live; the last case crosses a block's end too): every step's output
    and the settled planes bit-equal to the kernel's without a tail, the
    block written exactly once, by the step that completed the group."""
    B, (_, H, KV, hd, dtype) = 3, TAILED[shape]
    T = tail_rows(dtype)
    before = np.asarray([T + start, 2 * T + start + 1, 0])
    q, full, holes, tail, before, _ = _tail_case(shape, before=before, B=B)
    plain = jax.jit(partial(decode_attention, layer=1, interpret=True))
    a, b = full, (*holes, tail)
    n = jnp.asarray(before, jnp.int32)
    rng = np.random.default_rng(3)
    writes = np.zeros(B, int)
    for _ in range(T + 3):
        q, k, v = (jnp.asarray(rng.standard_normal(s), dtype) for s in (
            (B, 1, H, hd), (B, 1, KV, hd), (B, 1, KV, hd)))
        n = jnp.where(n > 0, n + 1, 0)
        o1, *a = plain(q, *a, n, k=k, v=v)
        was = b[0]
        o2, *b = plain(q, b[0], b[1], n, k=k, v=v, tail=b[2])
        np.testing.assert_array_equal(_raw(o1), _raw(o2))
        wrote = (_raw(b[0]) != _raw(was)).any(axis=(0, 2, 3, 4))
        np.testing.assert_array_equal(wrote, (np.asarray(n) > 0)
                                      & (np.asarray(n) % T == 0))
        writes += wrote
        sk, sv = _np_settled(b[0], b[1], b[2], n, T, 1)
        np.testing.assert_array_equal(_raw(sk[1]), _raw(a[0][1]))
        np.testing.assert_array_equal(_raw(sv[1]), _raw(a[1][1]))
    # (the groups that ended in the lengths the steps passed)
    np.testing.assert_array_equal(
        writes, np.where(before > 0, (before + T + 3) // T - before // T, 0))


def test_tail_rows_are_one_sublane_tile():
    assert [tail_rows(d) for d in (jnp.float32, jnp.bfloat16)] \
        == [8, 16]


@pytest.mark.parametrize("bad", ["no new K/V", "a ring", "rows of 24",
                                 "another width", "another dtype"])
def test_a_tail_the_kernel_cannot_take_is_refused(bad):
    q, (ck, cv), _, tail, before, T = _tail_case("mha-hd64")
    k, v = _new(9, 4, 64)
    kw = dict(k=k, v=v, tail=tail, layer=1, interpret=True)
    if bad == "no new K/V":
        kw.update(k=None, v=None)
    elif bad == "a ring":
        kw.update(window=128)
    elif bad == "rows of 24":
        kw.update(tail=jnp.zeros(tail.shape[:3] + (24, 128), tail.dtype))
    elif bad == "another width":
        kw.update(tail=tail[..., :64])
    else:
        kw.update(tail=tail.astype(jnp.bfloat16))
    with pytest.raises(ValueError, match="a tail"):
        decode_attention(q, ck, cv, _step_lengths(before), **kw)


def _tail_state(family, lens, seed=0):
    """A slot state of ``family`` whose rows hold ``lens`` positions."""
    from deepspeed_tpu.inference.decode import GenCarry

    cfg, model, params = _family(family)
    B = len(lens)
    return cfg, model, params, GenCarry(
        tok=jnp.asarray(np.random.default_rng(seed).integers(8, 256, (B,)),
                        jnp.int32),
        cache=_slot_cache(cfg, lens, seed=seed),
        rng=jnp.zeros((B, 2), jnp.uint32), done=jnp.asarray(lens) == 0,
        left=jnp.full((B,), 1000, jnp.int32))


def _greedy_step(model, flash):
    from deepspeed_tpu.inference.decode import decode_step
    from deepspeed_tpu.inference.sampling import sample_logits

    return jax.jit(partial(
        decode_step, model, flash_decode=flash,
        sampler=partial(sample_logits, greedy=True, temperature=1.0,
                        top_k=0, top_p=1.0)))


@pytest.mark.parametrize("family", ["mha-hd64", "gqa-hd64", "mha-hd128",
                                    "mha-hd16"])
def test_kind_settles_and_refills_its_tail(family):
    """The kind's two helpers around the step (``Dense.settled``,
    ``Dense.rewound``): T + 2 steps on the kernels leave the planes behind
    the tail by up to T - 1 positions; settled, they hold what the dense
    path's steps leave (the same tokens chosen on the way), and a tail
    filled again from the settled planes is the tail the steps left, row for
    live row. Heads of 16: no tail, and both helpers hand the cache back."""
    from deepspeed_tpu.inference.kinds import kind_of

    lens = [5, 0, 127, 200]
    cfg, model, params, carry = _tail_state(family, lens)
    kind = kind_of(cfg)
    T = kind.deferred_rows(jnp.float32)
    assert T == (0 if family == "mha-hd16" else 8)
    assert (carry.cache.tail is None) == (T == 0)
    fused, dense = carry, carry
    for _ in range((T or 8) + 2):
        fused = _greedy_step(model, True)(params, fused)
        dense = _greedy_step(model, False)(params, dense)
        # (the row that is not running: its token is nobody's)
        np.testing.assert_array_equal(np.asarray(fused.tok)[[0, 2, 3]],
                                      np.asarray(dense.tok)[[0, 2, 3]])
    n = np.asarray(fused.cache.length)
    np.testing.assert_array_equal(n, [5 + T + 2 if T else 15, 0,
                                      127 + (T or 8) + 2, 200 + (T or 8) + 2])
    settled = kind.settled(fused.cache)
    live = np.arange(S) < n.reshape(-1, 1, 1, 1)
    if T:
        behind = np.asarray(fused.cache.k) != np.asarray(settled.k)
        assert behind.any(), "the planes were not behind their tail"
        assert not (behind & ~live).any()
    else:
        assert settled is fused.cache and kind.rewound(settled) is settled
    for got, want in ((settled.k, dense.cache.k), (settled.v, dense.cache.v)):
        np.testing.assert_allclose(np.where(live, got, 0),
                                   np.where(live, want, 0),
                                   rtol=2e-4, atol=2e-4)
    if T:
        again = kind.rewound(settled).tail
        rows = np.arange(T).reshape(-1, 1) <= (
            (n - 1) % T).reshape(-1, 1, 1, 1)         # (B, 1, T, 1)
        rows = rows & (n > 0).reshape(-1, 1, 1, 1)
        np.testing.assert_array_equal(
            np.where(rows, again, 0), np.where(rows, fused.cache.tail, 0))


@pytest.mark.parametrize("prompt", [3, 8, 21, 125, 128, 131])
def test_a_seat_in_the_middle_of_a_group_steps_like_the_dense_path(prompt):
    """A request prefilled by T > 1 forwards (the second right-padded, as a
    final chunk is) and seated beside two running rows: its tail is the
    prompt's partial group (none live at a group's start, all T at its
    end), and the steps on the kernels choose the dense path's tokens."""
    from deepspeed_tpu.inference.decode import (GenCarry, forward_with_cache,
                                                init_cache)
    from deepspeed_tpu.inference.kinds import kind_of
    from deepspeed_tpu.serving.slots import insert_request

    cfg, model, params, state = _tail_state("mha-hd64", [40, 0, 77], seed=2)
    kind = kind_of(cfg)
    ids = jnp.asarray(np.random.default_rng(prompt).integers(
        8, 256, (1, prompt + 5)), jnp.int32)
    cache = init_cache(cfg, 1, S)
    head = prompt // 2
    if head:
        _, cache = forward_with_cache(model, params, ids[:, :head], cache)
    # the rest in a padded bucket: five tokens behind the last real one
    logits, cache = forward_with_cache(
        model, params, ids[:, head:], cache, last_token_head=True,
        last_index=jnp.int32(prompt - head - 1))
    pf = GenCarry(tok=jnp.argmax(logits[:, -1], -1).astype(jnp.int32),
                  cache=kind.rewound(cache, jnp.int32(prompt)),
                  rng=jnp.zeros((1, 2), jnp.uint32),
                  done=jnp.zeros((1,), bool))
    T, g = 8, (prompt - 1) // 8 * 8
    np.testing.assert_array_equal(
        np.asarray(pf.cache.tail[:, 0, :, :prompt - g, :64]),
        np.asarray(pf.cache.k[:, 0, :, :, g:prompt]).swapaxes(-1, -2))
    fused = dense = insert_request(state, jnp.int32(1), pf, jnp.int32(50))
    for _ in range(T + 1):
        fused = _greedy_step(model, True)(params, fused)
        dense = _greedy_step(model, False)(params, dense)
        np.testing.assert_array_equal(np.asarray(fused.tok),
                                      np.asarray(dense.tok))
    np.testing.assert_array_equal(np.asarray(fused.cache.length),
                                  [40 + T + 1, prompt + T + 1, 77 + T + 1])
    np.testing.assert_array_equal(np.asarray(dense.cache.length),
                                  np.asarray(fused.cache.length))


@pytest.mark.parametrize("steps", [1, 5, 8])
def test_export_import_step(steps):
    """What reads a slot's K/V outside the step goes through
    ``Dense.settled``: a slot stepped ``steps`` times on the kernels, its
    settled planes and length taken out (one slot's extent, as an export
    gathers it), seated in another engine's state at another slot with the
    tail filled from them (``Dense.rewound``), steps on there bit for bit
    as it does where it was."""
    from deepspeed_tpu.inference.decode import GenCarry
    from deepspeed_tpu.inference.kinds import kind_of
    from deepspeed_tpu.serving.slots import insert_request

    cfg, model, params, here = _tail_state("mha-hd64", [0, 61, 130], seed=4)
    _, _, _, there = _tail_state("mha-hd64", [9, 0, 0, 33], seed=5)
    kind = kind_of(cfg)
    step = _greedy_step(model, True)
    for _ in range(steps):
        here = step(params, here)
    out = kind.settled(here.cache)
    one = slice(1, 2)
    payload = GenCarry(
        tok=here.tok[one], rng=here.rng[one], done=here.done[one],
        cache=kind.rewound(out._replace(
            k=out.k[:, one], v=out.v[:, one], tail=out.tail[:, one] * 0,
            length=out.length[one])))
    there = insert_request(there, jnp.int32(2), payload, jnp.int32(100))
    for _ in range(8 + 2):
        here, there = step(params, here), step(params, there)
        assert int(here.tok[1]) == int(there.tok[2])
    a, b = kind.settled(here.cache), kind.settled(there.cache)
    n = int(a.length[1])
    assert n == int(b.length[2]) == 61 + steps + 10
    np.testing.assert_array_equal(_raw(a.k[:, 1, :, :, :n]),
                                  _raw(b.k[:, 2, :, :, :n]))
    np.testing.assert_array_equal(_raw(a.v[:, 1, :, :, :n]),
                                  _raw(b.v[:, 2, :, :, :n]))


@pytest.mark.parametrize("preset,names,tailed", [
    ("gpt2-hd64", {"decode_attention"}, True),
    ("ouro", {"decode_attention"}, False),              # heads of 16
    ("nemotron_h", {"decode_attention"}, False),
    ("mimo_v2_flash", {"full_decode_attention",
                       "window_decode_attention"}, False),
    ("zaya", {"cca_decode_attention"}, False),
    ("falcon_h1", {"gqa_decode_attention"}, False),
])
def test_only_the_dense_kind_hands_the_kernel_a_tail(monkeypatch, preset,
                                                     names, tailed):
    """The tail is an operand a kind passes or does not: tracing the T == 1
    step of each kind's unit-test preset, every call of the kernel comes
    without one (``tail=None``: the parent's program op for op) except the
    ``Dense`` kind's over heads that fill whole lane tiles."""
    from deepspeed_tpu import models
    from deepspeed_tpu.inference.decode import decode_step
    from deepspeed_tpu.inference.sampling import sample_logits
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.ops import decode_attention as da
    from deepspeed_tpu.serving.slots import init_slots

    cfg = _tiny(n_head=2, d_model=128) if preset == "gpt2-hd64" else \
        getattr(models, preset)("tiny", dtype=jnp.float32, max_seq=S)
    model = build_model(cfg)
    seen, real = [], da.decode_attention

    def spy(*a, tail=None, name="decode_attention", **kw):
        seen.append((name, tail))
        return real(*a, tail=tail, name=name, **kw)

    monkeypatch.setattr(da, "decode_attention", spy)
    jax.eval_shape(
        lambda p, c: decode_step(
            model, p, c, flash_decode=True,
            sampler=partial(sample_logits, greedy=True, temperature=1.0,
                            top_k=0, top_p=1.0)),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: init_slots(cfg, 3, S)))
    assert {name for name, _ in seen} == names
    assert all((tail is not None) == tailed for _, tail in seen), seen
    if tailed:
        assert seen[0][1].shape == (cfg.n_layer, 3, cfg.kv_heads, 8,
                                    2 * cfg.head_dim)


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
def test_the_kinds_helpers_read_nothing_behind_the_live_length(poison):
    """``Dense.rewound`` fills the tail and ``Dense.settled`` the planes
    through one-hot products, and 0 x NaN is NaN: what lies behind a
    slot's live length — in the planes, in the tail's rows — is zeroed
    before it is multiplied, so none of it reaches a live position. A cache
    of 200 positions (no whole lane tiles) keeps its groups in place too."""
    from deepspeed_tpu.inference.decode import KVCache
    from deepspeed_tpu.inference.kinds import kind_of

    cfg, _, _ = _family("mha-hd64")
    kind = kind_of(cfg)
    L, B, KV, hd, S_, T = 2, 4, 2, 64, 200, 8
    lens = np.asarray([5, 0, 197, 200])
    rng = np.random.default_rng(0)
    live = np.arange(S_) < lens.reshape(-1, 1, 1, 1)
    k, v = (np.where(live, rng.standard_normal((L, B, KV, hd, S_)),
                     poison).astype(np.float32) for _ in range(2))
    cache = kind.rewound(KVCache(
        k=jnp.asarray(k), v=jnp.asarray(v), length=jnp.asarray(lens),
        tail=jnp.full((L, B, KV, T, 2 * hd), poison, jnp.float32)))
    tail = np.asarray(cache.tail)
    assert np.isfinite(tail).all()
    for b, n in enumerate(lens):
        g = _group_start(n, T)
        np.testing.assert_array_equal(
            tail[:, b, :, :n - g, :hd], k[:, b, :, :, g:n].swapaxes(-1, -2))
        np.testing.assert_array_equal(
            tail[:, b, :, :n - g, hd:], v[:, b, :, :, g:n].swapaxes(-1, -2))
        assert not tail[:, b, :, n - g:].any()
    # the planes behind their tail, and poison in the tail's dead rows
    dead = np.arange(T).reshape(-1, 1) >= (lens - [
        _group_start(n, T) for n in lens]).reshape(-1, 1, 1, 1)
    holes = [a.copy() for a in (k, v)]
    for b, n in enumerate(lens):
        for plane in holes:
            plane[:, b, :, :, _group_start(n, T):n] = 77.0
    out = kind.settled(cache._replace(
        k=jnp.asarray(holes[0]), v=jnp.asarray(holes[1]),
        tail=jnp.asarray(np.where(dead, poison, tail))))
    np.testing.assert_array_equal(np.asarray(out.k), k)
    np.testing.assert_array_equal(np.asarray(out.v), v)

"""Pallas flash attention vs the plain XLA attention (interpret mode on CPU).

Oracle: allclose fwd + grads against ``causal_attention`` — the same
equivalence style the reference uses for its fused transformer kernel tests
(``tests/unit/ops/transformer/``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import causal_attention
from deepspeed_tpu.ops.flash_attention import flash_attention


def _qkv(B=2, S=64, H=4, KV=None, hd=32, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    KV = KV or H
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), dtype)
    return q, k, v


@pytest.mark.parametrize("kv_heads", [None, 2, 1])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_forward_matches(kv_heads, block):
    q, k, v = _qkv(KV=kv_heads)
    want = causal_attention(q, k, v)
    got = flash_attention(q, k, v, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_heads", [None, 2])
def test_grads_match(kv_heads):
    q, k, v = _qkv(S=32, KV=kv_heads)

    def loss(f):
        def inner(qq, kk, vv):
            return jnp.sum(jnp.square(f(qq, kk, vv)))
        return inner

    want = jax.grad(loss(causal_attention), argnums=(0, 1, 2))(q, k, v)
    flash = lambda a, b, c: flash_attention(a, b, c, block=16, interpret=True)
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-5, atol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_masked_int_mask_matches():
    """Round 1 fell back to XLA for any mask; masks now run in-kernel (int
    masks included) with identical results on valid rows."""
    q, k, v = _qkv(S=32)
    mask = jnp.ones((2, 32), jnp.int32).at[:, 20:].set(0)
    want = causal_attention(q, k, v, mask=mask)
    got = flash_attention(q, k, v, mask=mask, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:, :20],
                               np.asarray(want)[:, :20], rtol=2e-5, atol=2e-5)


def test_bf16_close():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    want = causal_attention(q, k, v).astype(jnp.float32)
    got = flash_attention(q, k, v, block=32, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_model_with_flash_attention():
    """TransformerLM trains with the flash kernel as attention_fn."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.ops.flash_attention import make_flash_attention
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset

    model = build_model(tiny_test(max_seq=32),
                        attention_fn=make_flash_attention(block=16, interpret=True))
    engine = ds.initialize({"train_batch_size": 8,
                            "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
                            "zero_optimization": {"stage": 1}}, model)
    data = random_token_dataset(16, seq_len=32, vocab_size=256, learnable=True)
    batch = DataLoader(data, local_batch_size=8, shuffle=False).collate_fn(data[:8])
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(3)]
    assert losses[-1] < losses[0]


# ----------------------------------------------------- padding-mask in-kernel
def _padded_mask(B, S, lengths):
    m = np.zeros((B, S), np.float32)
    for b, L in enumerate(lengths):
        m[b, :L] = 1.0
    return jnp.asarray(m)


# ------------------------------------------- the tile schedule (PR 43)
# The resident kernels split their loop at the diagonal: tiles under it take
# no mask arithmetic, the forward holds its tiles keys first (row statistics
# on the lanes), the backward kernels cut the diagonal tile into strips of
# 128 queries. A strip needs a block over 128, so these run at real widths.
_SCHEDULES = {"two-q-blocks": (1024, 512), "four-q-blocks": (1024, 256),
              "clamped-768-to-256": (768, 512)}
_FEATURES = ("plain", "key_mask", "alibi", "bias_dbias", "gqa")
_SCHEDULE_CASES = [(sched, feat, "f32", 64) for sched in _SCHEDULES
                   for feat in _FEATURES] + [
    # bf16 as the train cells run it; hd 80 / 96: 1/sqrt(hd) is no power of
    # two, the scale stays a float32 multiply on the product
    ("two-q-blocks", "plain", "bf16", 64),
    ("two-q-blocks", "key_mask", "bf16", 64),
    ("clamped-768-to-256", "gqa", "bf16", 64),
    ("two-q-blocks", "plain", "f32", 80),
    ("four-q-blocks", "alibi", "bf16", 80),
    ("clamped-768-to-256", "bias_dbias", "f32", 96),
    ("two-q-blocks", "key_mask", "f32", 96),
]


@pytest.mark.parametrize("sched,feature,dtype,hd", _SCHEDULE_CASES)
def test_tile_schedule_matches_dense(sched, feature, dtype, hd):
    """Forward and q/k/v (and bias) gradients against the dense reference
    through the split loop, the keys-first forward and the strips."""
    from deepspeed_tpu.models.transformer import alibi_slopes
    from deepspeed_tpu.ops.flash_attention import _diag_chunk

    S, block = _SCHEDULES[sched]
    B, H = 1, 2
    dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    q, k, v = _qkv(B=B, S=S, H=H, KV=1 if feature == "gqa" else None, hd=hd,
                   seed=S + hd, dtype=dt)
    clamped = block if S % block == 0 else 256
    assert _diag_chunk(clamped) == 128 and S // clamped >= 2
    kw, valid, bias = {}, S, None
    if feature == "key_mask":
        valid = S - 200                       # right padding: rows stay live
        kw["mask"] = _padded_mask(B, S, [valid])
    if feature == "alibi":
        kw["alibi_slopes"] = alibi_slopes(H)
        rel = jnp.arange(S)[None, :] - jnp.arange(S)[:, None]
        bias = (kw["alibi_slopes"][:, None, None]
                * rel[None].astype(jnp.float32))[None]
    if feature == "bias_dbias":
        bias = jnp.asarray(np.random.default_rng(5).standard_normal(
            (B, H, S, S)), jnp.float32)
    learned = feature == "bias_dbias"
    live = jnp.asarray(np.arange(S) < valid, jnp.float32)[None, :, None, None]

    def dense(qq, kk, vv, bb):
        f32 = [x.astype(jnp.float32) for x in (qq, kk, vv)]
        if bb is None:
            return causal_attention(*f32, mask=kw.get("mask"))
        return _dense_biased(*f32, bb, mask=kw.get("mask"))

    def flash(qq, kk, vv, bb):
        extra = dict(kw, bias=bb) if learned else kw
        return flash_attention(qq, kk, vv, block=block, interpret=True,
                               **extra).astype(jnp.float32)

    def loss(f):
        return lambda *a: jnp.sum(jnp.square(f(*a) * live))

    argnums = (0, 1, 2, 3) if learned else (0, 1, 2)
    want_o = dense(q, k, v, bias)
    got_o, got_g = jax.jit(lambda *a: (
        flash(*a), jax.grad(loss(flash), argnums=argnums)(*a)))(
            q, k, v, bias if learned else None)
    want_g = jax.grad(loss(dense), argnums=argnums)(q, k, v, bias)
    tol = 3e-2 if dtype == "bf16" else 2e-4
    np.testing.assert_allclose(np.asarray(got_o * live),
                               np.asarray(want_o * live), rtol=tol, atol=tol)
    for g, w, name in zip(got_g, want_g, ("q", "k", "v", "bias")):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max(),
                                   err_msg=f"d{name} mismatch")


def test_scores_computed_over_needed():
    """The schedule's static figure: score elements computed over the pairs
    a causal mask needs. Whole diagonal tiles (the forward, and every kernel
    before PR 43) 1.50 at S 1024 / block 512; strips of 128 (both backward
    kernels) 1.12."""
    from deepspeed_tpu.ops.flash_attention import (
        _diag_chunk, scores_computed_over_needed)

    assert scores_computed_over_needed(1024, 512) == pytest.approx(1.50, abs=2e-3)
    assert scores_computed_over_needed(1024, 512, _diag_chunk(512)) \
        == pytest.approx(1.124, abs=2e-3)
    assert scores_computed_over_needed(1024, 512, 256) \
        == pytest.approx(1.249, abs=2e-3)
    assert scores_computed_over_needed(1024, 128, _diag_chunk(128)) \
        == pytest.approx(1.124, abs=2e-3)
    assert scores_computed_over_needed(1024, 512, causal=False) == 1.0
    assert (_diag_chunk(512), _diag_chunk(256), _diag_chunk(128),
            _diag_chunk(96), _diag_chunk(16)) == (128, 128, 128, 96, 16)


@pytest.mark.parametrize("block", [16, 32])
def test_masked_forward_matches_and_stays_fused(block, monkeypatch):
    """Padding masks must run IN the kernel — the round-1 silent fallback to
    the O(S^2) XLA path is the bug this guards against."""
    import deepspeed_tpu.models.transformer as tr

    def _boom(*a, **k):
        raise AssertionError("flash_attention fell back to XLA attention")

    monkeypatch.setattr(tr, "causal_attention", _boom)
    q, k, v = _qkv(S=64)
    mask = _padded_mask(2, 64, [64, 40])
    want = causal_attention(q, k, v, mask=mask)          # the saved original
    got = flash_attention(q, k, v, mask=mask, block=block, interpret=True)
    # compare only non-pad rows (padded queries are garbage-but-finite)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got)[1, :40], np.asarray(want)[1, :40],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(got)))


def test_masked_grads_match():
    q, k, v = _qkv(S=32, KV=2)
    mask = _padded_mask(2, 32, [32, 20])
    lm = np.zeros((2, 32, 1, 1), np.float32)
    lm[0, :, 0, 0] = 1.0
    lm[1, :20, 0, 0] = 1.0
    lmask = jnp.asarray(lm)  # loss over non-pad rows only (like real training)

    def loss(f):
        def fn(q, k, v):
            return jnp.sum((f(q, k, v) * lmask) ** 2)
        return fn

    want = jax.grad(loss(lambda q, k, v: causal_attention(q, k, v, mask=mask)),
                    argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, mask=mask, block=16, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=3e-5, atol=3e-5)


def test_fully_masked_row_is_finite():
    """Left-padded rows (query with zero visible keys) must yield zeros, not
    NaN/inf, in both fwd and bwd."""
    q, k, v = _qkv(S=32)
    m = np.ones((2, 32), np.float32)
    m[1, :16] = 0.0   # left padding: queries 0..15 of row 1 see no keys
    mask = jnp.asarray(m)
    out = flash_attention(q, k, v, mask=mask, block=16, interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, mask=mask, block=16, interpret=True) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g)))


# ----------------------------------------------------------- bias operand
def _dense_biased(q, k, v, bias, mask=None, causal=True):
    from deepspeed_tpu.ops.evoformer import dense_biased_attention

    return dense_biased_attention(q, k, v, bias, mask=mask, causal=causal)


@pytest.mark.parametrize("bias_shape", ["hss", "bhss", "b1ss", "ss"])
@pytest.mark.parametrize("causal", [True, False])
def test_biased_forward_matches(bias_shape, causal):
    """The bias operand (round-4: evoformer/ALiBi streaming) matches the
    dense path for every broadcast layout the kernel index maps support."""
    B, S, H, hd = 2, 64, 4, 32
    q, k, v = _qkv(B=B, S=S, H=H, hd=hd)
    rng = np.random.default_rng(7)
    shapes = {"hss": (H, S, S), "bhss": (B, H, S, S),
              "b1ss": (B, 1, S, S), "ss": (S, S)}
    bias = jnp.asarray(rng.standard_normal(shapes[bias_shape]), jnp.float32)
    bias4 = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    want = _dense_biased(q, k, v, bias4, causal=causal)
    got = flash_attention(q, k, v, bias=bias, causal=causal, block=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_biased_forward_with_mask():
    q, k, v = _qkv(S=32)
    bias = jnp.asarray(np.random.default_rng(3).standard_normal((4, 32, 32)),
                       jnp.float32)
    mask = jnp.ones((2, 32), jnp.float32).at[:, 24:].set(0.0)
    want = _dense_biased(q, k, v, bias[None], mask=mask, causal=True)
    got = flash_attention(q, k, v, bias=bias, mask=mask, block=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got[:, :24]),
                               np.asarray(want[:, :24]), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_full_shape_bias_grad_matches(causal):
    """A full-shape (B, H, S, S) bias is DIFFERENTIABLE through the kernel
    (dbias = ds tiles from the dq kernel) — the evoformer pair-bias
    gradient the reference's CUTLASS kernels exist for."""
    B, S, H, hd = 2, 32, 2, 16
    q, k, v = _qkv(B=B, S=S, H=H, hd=hd)
    bias = jnp.asarray(np.random.default_rng(5).standard_normal((B, H, S, S)),
                       jnp.float32)

    def loss(f):
        return lambda qq, kk, vv, bb: jnp.sum(jnp.square(f(qq, kk, vv, bb)))

    dense = lambda qq, kk, vv, bb: _dense_biased(qq, kk, vv, bb, causal=causal)
    flash = lambda qq, kk, vv, bb: flash_attention(
        qq, kk, vv, bias=bb, causal=causal, block=16, interpret=True)
    want = jax.grad(loss(dense), argnums=(0, 1, 2, 3))(q, k, v, bias)
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2, 3)))(q, k, v, bias)
    for g, w, name in zip(got, want, ("q", "k", "v", "bias")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_broadcast_bias_qkv_grads_match():
    """Broadcast (H, S, S) biases: q/k/v grads must match the dense path
    on BOTH bias modes. A learned shared bias (default) gets the true
    summed cotangent (review r4 finding: the old zero-grad contract was a
    silent regression vs the dense path); bias_is_constant=True (ALiBi)
    opts into the zero-cost stream with an explicit stop_gradient."""
    B, S, H, hd = 2, 32, 4, 16
    q, k, v = _qkv(B=B, S=S, H=H, hd=hd)
    bias = jnp.asarray(np.random.default_rng(9).standard_normal((H, S, S)),
                       jnp.float32)

    def loss(f):
        return lambda qq, kk, vv: jnp.sum(jnp.square(f(qq, kk, vv)))

    dense = lambda qq, kk, vv: _dense_biased(qq, kk, vv, bias[None])
    for const in (False, True):
        flash = lambda qq, kk, vv: flash_attention(
            qq, kk, vv, bias=bias, bias_is_constant=const, block=16,
            interpret=True)
        want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
        got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name} mismatch ({const})")
    # learned mode: dbias equals the dense path's summed cotangent
    dwant = jax.grad(lambda bb: jnp.sum(jnp.square(
        _dense_biased(q, k, v, bb[None]))))(bias)
    dgot = jax.grad(lambda bb: jnp.sum(jnp.square(flash_attention(
        q, k, v, bias=bb, block=16, interpret=True))))(bias)
    np.testing.assert_allclose(np.asarray(dgot), np.asarray(dwant),
                               rtol=1e-4, atol=1e-4)
    # constant mode: explicitly zero
    dzero = jax.grad(lambda bb: jnp.sum(flash_attention(
        q, k, v, bias=bb, bias_is_constant=True, block=16,
        interpret=True)))(bias)
    assert float(jnp.max(jnp.abs(dzero))) == 0.0


def test_biased_flash_memory_ceiling_s4k():
    """VERDICT r4 #5 'done' check: at S=4096 the streamed-bias kernel
    compiles under a device-temp budget the dense path cannot meet — the
    dense path materializes (B, H, S, S) fp32 scores+probs (>=256 MB here)
    while the flash path's temps stay at block granularity. Compile-only
    (AOT buffer assignment), nothing is executed."""
    B, S, H, hd = 1, 4096, 2, 32
    q, k, v = _qkv(B=B, S=S, H=H, hd=hd, dtype=jnp.bfloat16)
    bias = jnp.zeros((H, S, S), jnp.bfloat16)

    def temp_bytes(fn, *args):
        return jax.jit(fn).lower(*args).compile() \
            .memory_analysis().temp_size_in_bytes

    dense = temp_bytes(
        lambda qq, kk, vv, bb: _dense_biased(qq, kk, vv, bb[None]),
        q, k, v, bias)
    flash = temp_bytes(
        lambda qq, kk, vv, bb: flash_attention(qq, kk, vv, bias=bb,
                                               interpret=True),
        q, k, v, bias)
    # dense: >= 2 x (B*H*S*S) fp32-ish buffers (261 MB measured). The
    # interpret-mode emulation inflates the flash path's temps (the python
    # interpreter materializes per-grid buffers: 132 MB measured where the
    # real TPU kernel holds block-granular VMEM tiles), so the CPU bound is
    # conservative; the TPU-side buffer assignment is checked by
    # bench_act_offload-style AOT probes on hardware.
    assert dense > 1.8 * flash, (dense, flash)


def test_alibi_model_routes_through_flash():
    """ALiBi models can now use the flash attention_fn (the constructor
    rejected them before the bias operand existed): logits match the
    default XLA attention path."""
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.ops.flash_attention import make_flash_attention

    cfg = tiny_test(n_layer=2, pos_embedding="alibi", max_seq=32,
                    dtype=jnp.float32)
    base = build_model(cfg)
    params = base.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32)),
                      jnp.int32)
    want = base.apply(params, ids)
    flash_model = build_model(cfg, attention_fn=make_flash_attention(
        block=16, interpret=True))
    got = flash_model.apply(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_alibi_slopes_in_kernel_match_dense_bias():
    """The in-kernel ALiBi ramp (slopes operand; no (H, S, S) bias ever
    materialized) must equal the dense-bias path in fwd AND grads — the
    long-context ALiBi mechanism."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    B, S, H, hd = 2, 64, 4, 16
    q, k, v = _qkv(B=B, S=S, H=H, hd=hd)
    slopes = alibi_slopes(H)
    rel = (jnp.arange(S)[None, :] - jnp.arange(S)[:, None])
    bias = slopes[:, None, None] * rel[None].astype(jnp.float32)

    def loss(f):
        return lambda qq, kk, vv: jnp.sum(jnp.square(f(qq, kk, vv)))

    dense = lambda qq, kk, vv: _dense_biased(qq, kk, vv, bias[None])
    flash = lambda qq, kk, vv: flash_attention(
        qq, kk, vv, alibi_slopes=slopes, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=3e-5, atol=3e-5)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name} mismatch")
    # with a padding mask too
    mask = jnp.ones((B, S), jnp.float32).at[:, 48:].set(0.0)
    got_m = flash_attention(q, k, v, mask=mask, alibi_slopes=slopes,
                            block=16, interpret=True)
    want_m = _dense_biased(q, k, v, bias[None], mask=mask)
    np.testing.assert_allclose(np.asarray(got_m[:, :48]),
                               np.asarray(want_m[:, :48]),
                               rtol=3e-5, atol=3e-5)


# ------------------------------------------------- streamed long-seq kernels
def _force_streamed(monkeypatch):
    """Route through the 4D-grid streamed kernels at test-size shapes."""
    import deepspeed_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_STREAM_VMEM_BYTES", 0)


@pytest.mark.parametrize("causal", [True, False])
def test_streamed_matches_baseline_fwd(causal, monkeypatch):
    """The streamed (constant-VMEM) kernels must be numerically identical
    to the staged baseline — same math, different blocking."""
    q, k, v = _qkv(S=64)
    base = flash_attention(q, k, v, causal=causal, block=16, interpret=True)
    _force_streamed(monkeypatch)
    got = flash_attention(q, k, v, causal=causal, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-6, atol=1e-6)


def test_streamed_grads_match_baseline(monkeypatch):
    q, k, v = _qkv(S=64)

    def loss(f):
        return lambda qq, kk, vv: jnp.sum(jnp.square(f(qq, kk, vv)))

    flash = lambda a, b, c: flash_attention(a, b, c, block=16, interpret=True)
    want = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    _force_streamed(monkeypatch)
    jax.clear_caches()          # drop the baseline-path compiled grads
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"d{name} mismatch (streamed)")


def test_streamed_masked_and_alibi_match(monkeypatch):
    from deepspeed_tpu.models.transformer import alibi_slopes

    B, S, H = 2, 64, 4
    q, k, v = _qkv(B=B, S=S, H=H)
    mask = jnp.ones((B, S), jnp.float32).at[:, 48:].set(0.0)
    slopes = alibi_slopes(H)
    base = flash_attention(q, k, v, mask=mask, alibi_slopes=slopes,
                           block=16, interpret=True)
    base_m = flash_attention(q, k, v, mask=mask, block=16, interpret=True)
    _force_streamed(monkeypatch)
    got = flash_attention(q, k, v, mask=mask, alibi_slopes=slopes,
                          block=16, interpret=True)
    got_m = flash_attention(q, k, v, mask=mask, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got[:, :48]),
                               np.asarray(base[:, :48]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_m[:, :48]),
                               np.asarray(base_m[:, :48]),
                               rtol=1e-6, atol=1e-6)


def test_streamed_masked_grads_match(monkeypatch):
    B, S = 2, 64
    q, k, v = _qkv(B=B, S=S)
    mask = jnp.ones((B, S), jnp.float32).at[:, 40:].set(0.0)

    def loss(f):
        return lambda qq, kk, vv: jnp.sum(jnp.square(f(qq, kk, vv)))

    flash = lambda a, b, c: flash_attention(a, b, c, mask=mask, block=16,
                                            interpret=True)
    want = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    _force_streamed(monkeypatch)
    jax.clear_caches()
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"d{name} mismatch (streamed+mask)")


def test_default_block_clamps_to_short_sequences():
    """block=512 default (round-5): shorter sequences clamp the block to
    S (single tile) and must stay ON the kernel path, not fall back."""
    import deepspeed_tpu.models.transformer as tr

    q, k, v = _qkv(S=96, hd=32)
    want = causal_attention(q, k, v)
    orig = tr.causal_attention

    def _boom(*a, **kw):
        raise AssertionError("fell back to dense at S=96")

    tr.causal_attention = _boom
    try:
        got = flash_attention(q, k, v, interpret=True)   # default block
    finally:
        tr.causal_attention = orig
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

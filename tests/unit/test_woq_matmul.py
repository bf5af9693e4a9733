"""Fused WOQ GEMM: interpret-mode parity, TP sharding, consumption-side
dispatch, and the satellite regressions that rode this PR (flash-attention
divisor fallback, f16 decode gating, xent tile floor).

Oracle for every kernel case: the reference dequantize-then-matmul in
fp32 — the kernel must match it to fp32-matmul rounding (the quantization
error itself cancels out because both sides consume the same int values).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.quantization import (QuantizedTensor,
                                                  dequant_rows, dequantize,
                                                  matmul_any, quantize,
                                                  quantize_params, woq_dot,
                                                  woq_dot_t)
from deepspeed_tpu.ops.woq_matmul import woq_matmul, woq_matmul_t


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


# ------------------------------------------------------------ kernel parity
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("K,N,gs", [
    (256, 384, 64),      # multi-group
    (256, 384, 128),
    (256, 384, 256),     # one group == K
    (96, 200, 128),      # degraded group (96 % 128 != 0) + ragged N
    (192, 256, 48),      # non-power-of-two group
])
def test_matmul_parity(bits, K, N, gs):
    w = _rand((K, N))
    qt = quantize(w, group_size=gs, bits=bits)
    x = _rand((8, K), seed=1)
    want = x @ dequantize(qt, jnp.float32)
    got = woq_matmul(x, qt.q, qt.scale, group_size=qt.group_size,
                     bits=qt.bits, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("V,d,gs", [
    (512, 128, 128),     # grouped vocab
    (512, 128, 64),
    (250, 128, 128),     # odd vocab -> degraded single group
    (256, 192, 256),
])
def test_matmul_t_parity(bits, V, d, gs):
    """Transposed consumption — the tied-embedding head reads (V, d)."""
    w = _rand((V, d))
    qt = quantize(w, group_size=gs, bits=bits)
    x = _rand((4, d), seed=2)
    want = x @ dequantize(qt, jnp.float32).T
    got = woq_matmul_t(x, qt.q, qt.scale, group_size=qt.group_size,
                       bits=qt.bits, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_matmul_parity_bf16_activations():
    """bf16 activations (the serving dtype): int8 -> bf16 conversion is
    exact for |q| <= 127, so the kernel matches the dequant reference to
    bf16-matmul rounding."""
    w = _rand((256, 256))
    qt = quantize(w, group_size=128, bits=8)
    x = _rand((8, 256), jnp.bfloat16, seed=3)
    # fp32 oracle; both sides then differ from it only by bf16 matmul
    # rounding, which scales with the output magnitude — compare in
    # absolute terms against the output scale, not elementwise rtol
    # (near-zero entries make rtol meaningless under bf16)
    want = np.asarray(x.astype(jnp.float32)
                      @ dequantize(qt, jnp.float32))
    got = np.asarray(woq_matmul(x, qt.q, qt.scale,
                                group_size=qt.group_size, bits=qt.bits,
                                interpret=True).astype(jnp.float32))
    tol = 0.05 * float(np.abs(want).max())
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


def test_dequant_rows_matches_dense_gather():
    """Embedding-path row gather: int8 bytes for exactly the batch's
    tokens, equal to gathering the dense dequantized table."""
    w = _rand((250, 64))
    ids = jnp.asarray([[0, 3, 249], [7, 100, 8]], jnp.int32)
    for bits in (8, 4):
        qt = quantize(w, group_size=50, bits=bits)
        want = dequantize(qt, jnp.float32)[ids]
        got = dequant_rows(qt, ids, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


# ------------------------------------------------------------- dispatchers
def test_woq_dot_kernel_matches_xla_path():
    """The two consumption paths (fused kernel / per-use XLA dequant) are
    numerically interchangeable — kernel accumulates fp32, so it is at
    least as accurate as the dense reference."""
    w = _rand((256, 384))
    x = _rand((2, 3, 256), seed=4)          # leading dims flattened inside
    for bits in (8, 4):
        qt = quantize(w, group_size=128, bits=bits)
        a = woq_dot(x, qt, use_kernel=False)
        b = woq_dot(x, qt, use_kernel=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)
        assert a.shape == (2, 3, 384)


def test_woq_dot_t_out_dtype_fp32():
    """The decode head asks for fp32 logits straight out of the GEMM — no
    bf16 round-trip before the sampler."""
    w = _rand((512, 128))
    qt = quantize(w, group_size=128, bits=8)
    x = _rand((2, 128), jnp.bfloat16, seed=5)
    for use_kernel in (False, True):
        out = woq_dot_t(x, qt, use_kernel=use_kernel,
                        out_dtype=jnp.float32)
        assert out.dtype == jnp.float32 and out.shape == (2, 512)


def test_matmul_any_dense_passthrough():
    x = _rand((4, 64))
    w = _rand((64, 32), seed=6)
    np.testing.assert_allclose(np.asarray(matmul_any(x, w)),
                               np.asarray(x @ w), atol=1e-6)


# ------------------------------------------------------------------ TP/specs
def test_quantize_params_stamps_pspec():
    from jax.sharding import PartitionSpec as P

    params = {"layers": {"wqkv": _rand((2, 64, 192)),
                         "ln1_scale": jnp.ones((2, 64))}}
    specs = {"layers": {"wqkv": P(None, None, "model"),
                        "ln1_scale": P(None, None)}}
    q = quantize_params(params, group_size=32, min_size=1, specs=specs)
    assert isinstance(q["layers"]["wqkv"], QuantizedTensor)
    assert q["layers"]["wqkv"].pspec == P(None, None, "model")


def test_woq_dot_tp_sharded_matches_unsharded(devices):
    """Kernel + shard_map under a model-axis mesh: column-sharded and
    row-sharded weights both reproduce the unsharded kernel result (the
    scales travel with their shards, reference GroupQuantizer-over-mp)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, model=4))
    x = _rand((8, 256), seed=7)
    w = _rand((256, 512), seed=8)
    for bits in (8, 4):
        qt = quantize(w, group_size=64, bits=bits)
        want = woq_dot(x, qt, use_kernel=True)
        col = QuantizedTensor(qt.q, qt.scale, qt.group_size, qt.bits,
                              pspec=P(None, "model"))
        row = QuantizedTensor(qt.q, qt.scale, qt.group_size, qt.bits,
                              pspec=P("model", None))
        with mesh:
            got_col = jax.jit(partial(woq_dot, use_kernel=True))(x, col)
            got_row = jax.jit(partial(woq_dot, use_kernel=True))(x, row)
        np.testing.assert_allclose(np.asarray(got_col), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(got_row), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_woq_dot_t_tp_vocab_sharded(devices):
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, model=4))
    x = _rand((4, 128), seed=9)
    w = _rand((512, 128), seed=10)
    qt = quantize(w, group_size=64, bits=8)
    want = woq_dot_t(x, qt, use_kernel=True)
    sharded = QuantizedTensor(qt.q, qt.scale, qt.group_size, qt.bits,
                              pspec=P("model", None))
    with mesh:
        got = jax.jit(partial(woq_dot_t, use_kernel=True))(x, sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_woq_dot_tp_degraded_single_group(devices):
    """G == 1 (vocab/width not group-divisible — GPT-2's tied table is the
    real-world case) must STAY on the kernel under TP: the one scale row
    replicates and each shard's local slice becomes its group. A fallback
    to whole-table dequant here would silently forfeit the bandwidth win
    on the single largest per-step weight read."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, model=4))
    # mode A, row-sharded degraded group (gs degrades to K)
    x = _rand((8, 256), seed=11)
    w = _rand((256, 512), seed=12)
    qt = quantize(w, group_size=1000, bits=8)
    assert qt.scale.shape[-2] == 1
    want = woq_dot(x, qt, use_kernel=True)
    row = QuantizedTensor(qt.q, qt.scale, qt.group_size, qt.bits,
                          pspec=P("model", None))
    with mesh:
        got = jax.jit(partial(woq_dot, use_kernel=True))(x, row)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    # mode B, vocab-sharded degraded group (500 % 128 != 0, 500 % tp == 0)
    xv = _rand((4, 128), seed=13)
    wv = _rand((500, 128), seed=14)
    qv = quantize(wv, group_size=128, bits=8)
    assert qv.scale.shape[-2] == 1 and qv.group_size == 500
    wantv = woq_dot_t(xv, qv, use_kernel=True)
    sh = QuantizedTensor(qv.q, qv.scale, qv.group_size, qv.bits,
                         pspec=P("model", None))
    with mesh:
        gotv = jax.jit(partial(woq_dot_t, use_kernel=True))(xv, sh)
    np.testing.assert_allclose(np.asarray(gotv), np.asarray(wantv),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------------------ engine-level
def test_engine_woq_kernel_generation_matches_xla_path():
    """End to end: a quantized engine serving through the fused kernel
    (forced on; interpret mode on CPU) produces the same greedy tokens as
    the XLA-dequant consumption path — the serving-path analog of the
    kernel parity tests."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 8)),
                      jnp.int32)
    base = {"dtype": "float32", "quantize": True, "quant_group_size": 32}
    xla = ds.init_inference(model, params, {**base, "woq_kernel": False})
    ker = ds.init_inference(model, params, {**base, "woq_kernel": True})
    out_x = np.asarray(xla.generate(ids, 6, greedy=True))
    out_k = np.asarray(ker.generate(ids, 6, greedy=True))
    np.testing.assert_array_equal(out_x, out_k)


def test_engine_fused_qkv_forward_matches_generate_prefill():
    """The serving tree stores [wq|wk|wv] fused; forward() unfuses for
    model.apply and must equal the unfused model's logits exactly."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 8)),
                      jnp.int32)
    eng = ds.init_inference(model, params, {"dtype": "float32"})
    assert "wqkv" in eng.params["layers"] and "wq" not in eng.params["layers"]
    want = np.asarray(model.apply(params, ids))
    got = np.asarray(eng.forward(ids))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ------------------------------------------- satellite regressions (PR 1)
def test_flash_block_shrinks_to_divisor_not_dense():
    """S = 768 with the default 512 block must stay on the fused kernel by
    shrinking to 256 — the dense fallback (which materializes (B, H, S, S)
    scores) must NOT be taken."""
    import deepspeed_tpu.models.transformer as tr
    from deepspeed_tpu.ops.flash_attention import flash_attention

    q = _rand((1, 768, 2, 32))
    want = tr.causal_attention(q, q, q)
    orig = tr.causal_attention
    try:
        def boom(*a, **k):
            raise AssertionError("dense fallback taken for S=768")
        tr.causal_attention = boom
        got = flash_attention(q, q, q, block=512, interpret=True)
    finally:
        tr.causal_attention = orig
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_no_divisor_still_falls_back():
    """A truly indivisible S takes the dense path and matches it. S must
    exceed the block for the shrink search to run and fail: 576 % 512,
    576 % 256 and 576 % 128 are all nonzero (S < block just clamps to a
    single full-S tile and stays fused)."""
    import deepspeed_tpu.models.transformer as tr
    from deepspeed_tpu.ops.flash_attention import flash_attention

    q = _rand((1, 576, 2, 16))
    want = tr.causal_attention(q, q, q)
    seen = []
    orig = tr.causal_attention
    try:
        def spy(*a, **k):
            seen.append(True)
            return orig(*a, **k)
        tr.causal_attention = spy
        got = flash_attention(q, q, q, block=512, interpret=True)
    finally:
        tr.causal_attention = orig
    assert seen, "dense fallback was not taken for S=576"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_f16_decode_routes_dense_on_tpu(monkeypatch):
    """float16 q/KV on (fake) TPU must take the dense cache attention, not
    the Mosaic kernel — the round-5 ADVICE decode gate."""
    import deepspeed_tpu.ops.decode_attention as da
    from deepspeed_tpu.inference.decode import _cache_attend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def boom(*a, **k):
        raise AssertionError("f16 reached the Pallas decode kernel")
    monkeypatch.setattr(da, "decode_attention", boom)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 32)), jnp.float16)
    ck = jnp.asarray(rng.standard_normal((2, 2, 32, 128)), jnp.float16)
    cv = jnp.asarray(rng.standard_normal((2, 2, 32, 128)), jnp.float16)
    out = _cache_attend(q, ck, cv, jnp.int32(77), flash_decode=True)
    assert out.shape == (2, 1, 4, 32)
    # bf16 inputs still go to the kernel (gate is f16-specific)
    with pytest.raises(AssertionError, match="Pallas decode kernel"):
        _cache_attend(q.astype(jnp.bfloat16), ck.astype(jnp.bfloat16),
                      cv.astype(jnp.bfloat16), jnp.int32(77),
                      flash_decode=True)


def test_f16_sparse_routes_dense_on_tpu(monkeypatch):
    from deepspeed_tpu.models.transformer import causal_attention
    from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                    sparse_attention)

    cfg = FixedSparsityConfig(block=16, num_local_blocks=4)
    q = _rand((1, 64, 2, 16)).astype(jnp.float16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = np.asarray(sparse_attention(q, q, q, cfg)).astype(np.float32)
    # dense-layout Fixed(4 local of 4 total) == full causal here
    assert got.shape == (1, 64, 2, 16) and np.isfinite(got).all()
    want = np.asarray(causal_attention(
        q.astype(jnp.float32), q.astype(jnp.float32),
        q.astype(jnp.float32)))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_xent_blocks_clamp_at_min_tile():
    """A non-power-of-two user block (192) must land on the 128 floor
    during the VMEM shrink, never on a 96-lane tile."""
    from deepspeed_tpu.ops.xent import _MIN_TILE, _blocks

    bt, bv = _blocks(1024, 50257, 192, 192, d=8192)
    assert bt >= _MIN_TILE and bv >= _MIN_TILE
    # a 192 block must normalize to a lane-aligned 128 even when the VMEM
    # budget never forces the shrink loop to run (small d)
    bt, bv = _blocks(1024, 50257, 192, 192, d=512)
    assert (bt, bv) == (_MIN_TILE, _MIN_TILE)
    # huge d: both tiles pinned exactly AT the floor, not below
    bt, bv = _blocks(4096, 50257, 192, 384, d=6144)
    assert (bt, bv) == (_MIN_TILE, _MIN_TILE)

"""Sequence/context parallelism: Ulysses + ring attention.

Oracle: exact-math agreement with the single-device causal attention
(reference test strategy — allclose equivalence against the unsharded op).
The reference has NO Ulysses unit test (SURVEY §4 notes the gap); this adds
the coverage the reference was missing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deepspeed_tpu.models.transformer import causal_attention
from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh
from deepspeed_tpu.sequence import make_ring_attention, make_ulysses_attention


def _qkv(B=2, S=32, H=4, KV=None, hd=16, seed=0):
    rng = np.random.default_rng(seed)
    KV = KV or H
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    return q, k, v


@pytest.fixture()
def seq_mesh(devices):
    return build_mesh(MeshSpec(data=2, seq=4))


@pytest.mark.parametrize("maker", [make_ring_attention, make_ulysses_attention])
@pytest.mark.parametrize("kv_heads", [None, 2])
def test_matches_plain_attention(seq_mesh, maker, kv_heads):
    q, k, v = _qkv(KV=kv_heads)
    want = causal_attention(q, k, v)
    attn = maker(seq_mesh)
    with seq_mesh:
        got = jax.jit(lambda a, b, c: attn(a, b, c))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("maker", [make_ring_attention, make_ulysses_attention])
def test_with_padding_mask(seq_mesh, maker):
    q, k, v = _qkv()
    mask = jnp.asarray(np.random.default_rng(1).integers(0, 2, (2, 32)),
                       jnp.int32).at[:, :8].set(1)  # keep early keys valid
    want = causal_attention(q, k, v, mask=mask)
    attn = maker(seq_mesh)
    with seq_mesh:
        got = jax.jit(lambda a, b, c, m: attn(a, b, c, mask=m))(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("maker", [make_ring_attention, make_ulysses_attention])
def test_grads_match(seq_mesh, maker):
    """Backward pass through the collective attention must match too (the
    reference's all-to-all pair is autograd-transparent; shard_map is)."""
    q, k, v = _qkv(S=16)

    def loss(f):
        def inner(qq, kk, vv):
            return jnp.sum(jnp.square(f(qq, kk, vv)))
        return inner

    want = jax.grad(loss(causal_attention), argnums=(0, 1, 2))(q, k, v)
    attn = maker(seq_mesh)
    with seq_mesh:
        got = jax.jit(jax.grad(loss(attn), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-5, atol=5e-5)


def test_train_step_with_ring_attention(seq_mesh):
    """End-to-end: a TransformerLM trained with ring attention on a
    data x seq mesh takes a finite step."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset

    attn = make_ring_attention(seq_mesh)
    model = build_model(tiny_test(max_seq=32), attention_fn=attn)
    cfg = {
        "train_batch_size": 2,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "mesh": {"data": 2, "seq": 4},
    }
    engine = ds.initialize(cfg, model)
    data = random_token_dataset(4, seq_len=32, vocab_size=256)
    batch = DataLoader(data, local_batch_size=2, shuffle=False).collate_fn(data[:2])
    metrics = engine.train_batch(batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("maker", [make_ring_attention, make_ulysses_attention])
@pytest.mark.parametrize("kv_heads", [None, 2])
def test_composes_with_tensor_parallel(devices, maker, kv_heads):
    """SP wrappers on a data x model x seq mesh: heads shard over the model
    axis (no cross-model collectives) and results still match."""
    mesh = build_mesh(MeshSpec(data=2, model=2, seq=2))
    q, k, v = _qkv(KV=kv_heads)
    want = causal_attention(q, k, v)
    attn = maker(mesh)
    with mesh:
        got = jax.jit(lambda a, b, c: attn(a, b, c))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_alibi_matches_dense(devices):
    """Long-context ALiBi: the ring rebuilds the distance ramp from its
    global per-step positions; output must match the dense biased path."""
    from deepspeed_tpu.models.transformer import alibi_slopes, causal_attention
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh
    from deepspeed_tpu.sequence.layer import make_ring_attention

    B, S, H, hd = 2, 32, 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    slopes = alibi_slopes(H)
    rel = (jnp.arange(S)[None, :] - jnp.arange(S)[:, None])
    bias = slopes[:, None, None] * rel[None].astype(jnp.float32)
    want = causal_attention(q, k, v, bias=bias)

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    with jax.set_mesh(mesh):
        ring = make_ring_attention(mesh)
        got = jax.jit(lambda a, b, c: ring(a, b, c,
                                           alibi_slopes=slopes))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_bloom_model_with_ring_attention(devices):
    """ALiBi model end to end on a data x seq mesh with ring attention:
    logits match the default dense path."""
    from deepspeed_tpu.models import bloom, build_model
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh
    from deepspeed_tpu.sequence.layer import make_ring_attention

    cfg = bloom("tiny", n_layer=2, n_head=4, d_model=64, vocab_size=256,
                max_seq=32, dtype=jnp.float32)
    base = build_model(cfg)
    params = base.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32)),
                      jnp.int32)
    want = base.apply(params, ids)
    mesh = build_mesh(MeshSpec(data=2, seq=4))
    with jax.set_mesh(mesh):
        ring_model = build_model(cfg, attention_fn=make_ring_attention(mesh))
        got = jax.jit(lambda p, i: ring_model.apply(p, i))(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_rolled_ring_matches_unrolled(devices):
    """Rings past RING_UNROLL_MAX compile to a fori_loop; forcing the
    rolled form (unroll_max=1) on a ring-4 mesh must reproduce the dense
    reference exactly — forward, with mask, with ALiBi, and grads."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    q, k, v = _qkv()
    mask = jnp.asarray(np.random.default_rng(1).integers(0, 2, (2, 32)),
                       jnp.int32).at[:, :8].set(1)
    slopes = alibi_slopes(4)
    rolled = make_ring_attention(mesh, unroll_max=1)
    with mesh:
        got = jax.jit(lambda a, b, c: rolled(a, b, c))(q, k, v)
        got_m = jax.jit(lambda a, b, c, m: rolled(a, b, c, mask=m))(q, k, v, mask)
        got_a = jax.jit(lambda a, b, c: rolled(a, b, c,
                                               alibi_slopes=slopes))(q, k, v)
        grads = jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(jnp.square(rolled(a, b, c))),
            argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(causal_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_m),
                               np.asarray(causal_attention(q, k, v, mask=mask)),
                               rtol=2e-5, atol=2e-5)
    rel = (jnp.arange(32)[None, :] - jnp.arange(32)[:, None])
    bias = slopes[:, None, None] * rel[None].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got_a),
                               np.asarray(causal_attention(q, k, v, bias=bias)),
                               rtol=3e-5, atol=3e-5)
    want_g = jax.grad(lambda a, b, c: jnp.sum(jnp.square(
        causal_attention(a, b, c))), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-5, atol=5e-5)


def test_ring64_compiles_bounded():
    """A 64-ring must compile in bounded time/size (VERDICT r4 weak #8: the
    unrolled form grew linearly). Runs in a 64-virtual-device subprocess:
    asserts the rolled program lowers with a while loop, compiles fast, and
    matches the dense reference numerically."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os, time
        import jax, jax.numpy as jnp, numpy as np
        from deepspeed_tpu.models.transformer import causal_attention
        from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh
        from deepspeed_tpu.sequence.layer import make_ring_attention

        mesh = build_mesh(MeshSpec(data=1, seq=64))
        rng = np.random.default_rng(0)
        B, S, H, hd = 1, 128, 2, 8
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, hd)),
                               jnp.float32) for _ in range(3))
        ring = make_ring_attention(mesh)
        with mesh:
            f = jax.jit(lambda a, b, c: ring(a, b, c))
            t0 = time.monotonic()
            hlo = f.lower(q, k, v)
            compiled = hlo.compile()
            dt = time.monotonic() - t0
            got = np.asarray(f(q, k, v))
        assert "while" in hlo.as_text(), "ring-64 did not roll into a loop"
        np.testing.assert_allclose(
            got, np.asarray(causal_attention(q, k, v)), rtol=3e-5, atol=3e-5)
        print(f"OK compile_s={dt:.1f}")
    """)
    env = dict(**__import__("os").environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=64")
    p = subprocess.run([sys.executable, "-c", code], env=env, timeout=600,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "OK" in p.stdout, p.stdout


def test_ring_attention_alibi_with_tp_sharded_heads(devices):
    """ALiBi slopes under ring + TP head sharding: each model shard must
    apply ITS heads' slice of the slope vector (review r4: a closed-over
    full (H,) vector would shape-error — or worse, mis-slope — when
    shard_map splits H)."""
    from deepspeed_tpu.models.transformer import (alibi_bias, alibi_slopes,
                                                  causal_attention)
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh
    from deepspeed_tpu.sequence.layer import make_ring_attention

    B, S, H, hd = 2, 32, 4, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    slopes = alibi_slopes(H)
    want = causal_attention(q, k, v, bias=alibi_bias(slopes, S))
    mesh = build_mesh(MeshSpec(data=2, seq=2, model=2))
    with jax.set_mesh(mesh):
        ring = make_ring_attention(mesh)
        got = jax.jit(lambda a, b, c: ring(a, b, c,
                                           alibi_slopes=slopes))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)

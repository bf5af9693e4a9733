"""``gqa_chunk_attention`` (``ops/chunk_attention.py``), interpreted here,
against the walk it replaces (``windowed.attend_blocks``): the same softmax
over the same keys at every bucket, context and head grouping, out of the
layer asked for, nothing behind the live length read into a sum; the shape
rule that decides who takes it, and that MiMo's full layers (keys of 192)
do not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.decode import forward_with_cache, init_cache
from deepspeed_tpu.inference.kinds import kind_of
from deepspeed_tpu.models import build_model, mimo_v2_flash, windowed
from deepspeed_tpu.ops import chunk_attention as ca

F32, BF16 = jnp.float32, jnp.bfloat16

# (what, T, G, KV, max_len, start, how the kernel is called, (hd, vd))
CASES = [
    ("a first chunk", 64, 8, 1, 2048, 0, {}, (128, 128)),
    ("the live length inside a block", 16, 8, 2, 2048, 1324, {}, (128, 128)),
    ("the live length at a block's edge", 64, 1, 8, 2048, 960, {},
     (128, 128)),
    ("the cache's last block", 64, 8, 1, 2048, 1984, {}, (128, 128)),
    ("a bucket of 8", 8, 8, 1, 1024, 515, {}, (128, 128)),
    ("a bucket of 8 of one head", 8, 1, 1, 1024, 515, {}, (128, 128)),
    ("a bucket of 512", 512, 8, 1, 2048, 512, {}, (128, 128)),
    ("the published widths", 16, 8, 8, 2048, 1100, {}, (128, 128)),
    ("tiles within the queries, a program a tile", 256, 2, 1, 1024, 640,
     dict(tile=64, rows=64, block=256), (128, 128)),
    ("tiles of whole heads, programs of two", 32, 8, 1, 1024, 200,
     dict(tile=64, rows=128, block=128), (128, 128)),
    ("a small trunk's widths", 32, 2, 2, 256, 96, {}, (32, 16)),
    ("a cache of no whole kilo-block", 64, 2, 1, 640, 400, {}, (128, 128)),
]


def _inputs(T, G, KV, S, dims, dtype, seed=0):
    hd, vd = dims
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((1, T, G * KV, hd)), dtype)
    ck = jnp.asarray(rng.standard_normal((2, 1, KV, hd, S)), dtype)
    cv = jnp.asarray(rng.standard_normal((2, 1, KV, vd, S)), dtype)
    return q, ck, cv


@pytest.mark.parametrize("dtype,tol", [(F32, 2e-6), (BF16, 1e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_kernel_attends_what_the_walk_attends(case, dtype, tol):
    """Layer 1 of two planes — layer 0 is all ``nan``, and so is every
    block of layer 1 behind the live length (the kernel's blocks, which are
    the walk's or wider): the output is finite and the walk's, to float32
    rounding in a float32 cache and to the walk's own (``p`` in bf16 under
    a running maximum that moves by blocks) in bf16."""
    _, T, G, KV, S, start, how, dims = case
    q, ck, cv = _inputs(T, G, KV, S, dims, dtype)
    blk = max(ca._key_block(S, how.get("block", ca.KEY_BLOCK)),
              windowed.KEY_BLOCK if S % windowed.KEY_BLOCK == 0 else S)
    dead = -(-(start + T) // blk) * blk
    ck, cv = (c.at[0].set(jnp.nan).at[1, ..., dead:].set(jnp.nan)
              for c in (ck, cv))
    pos = (start + jnp.arange(T, dtype=jnp.int32))[None]
    want = windowed.attend_blocks(q, ck, cv, pos, jnp.int32(start + T),
                                  layer=jnp.int32(1)).astype(F32)
    got = jax.jit(lambda q, ck, cv, start: ca.gqa_chunk_attention(
        q, ck, cv, start, layer=jnp.int32(1), interpret=True, **how))(
            q, ck, cv, jnp.int32(start))
    assert got.shape == (1, T, G * KV, dims[1]) and got.dtype == dtype
    got = got.astype(F32)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(want).all())
    assert float(jnp.abs(got - want).max()) <= tol * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("G,T,tile,want", [
    (8, 512, 1024, 1024),      # two heads' worth of a chunk of 512
    (8, 512, 512, 512), (8, 512, 256, 256),
    (8, 8, 1024, 64), (1, 8, 1024, 8), (8, 64, 1024, 512),
    (8, 256, 1024, 1024), (1, 2048, 1024, 1024),
    (8, 520, 512, None),       # 8 x 65: nothing under 512 tiles it
    (2, 24, 1024, 48), (8, 72, 512, 288),
])
def test_a_product_s_rows_are_whole_runs_of_the_queries(G, T, tile, want):
    assert ca.row_tile(G, T, tile) == want


def test_the_rule_is_the_shapes(monkeypatch):
    """Whole lane blocks of positions, a bucket whose rows tile and, on the
    chip, keys and values of whole lane tiles: MiMo's 192 / 128 stay on the
    walk there, by the shape."""
    assert ca.chunk_kernel_fits(512, 8, 65536, 128, 128)
    assert ca.chunk_kernel_fits(8, 8, 65536, 128, 128)
    assert ca.chunk_kernel_fits(32, 2, 256, 32, 32)       # interpreted
    assert not ca.chunk_kernel_fits(512, 8, 65536 + 64, 128, 128)
    assert not ca.chunk_kernel_fits(12, 8, 65536, 128, 128)
    assert not ca.chunk_kernel_fits(520, 8, 65536, 128, 128)  # 8 x 65
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ca.chunk_kernel_fits(512, 8, 65536, 128, 128)
    assert ca.chunk_kernel_fits(512, 4, 32768, 256, 128)
    assert not ca.chunk_kernel_fits(512, 16, 32768, 192, 128)
    assert not ca.chunk_kernel_fits(32, 2, 256, 32, 32)


def test_mimo_s_full_layers_keep_the_walk():
    """The windowed kind answers ``Kind``'s False for a chunk: the small
    MiMo trunk's chunk program, kernels on, holds no call of the kernel and
    counts no fallback (it has no kernel to fall back from)."""
    from deepspeed_tpu.observability.metrics import get_registry

    cfg = mimo_v2_flash("tiny", dtype=F32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kind = kind_of(cfg, 1, F32)
    assert kind.chunk_fused(True, 32, 256, F32, F32) is False
    counter = get_registry().counter("Serve/chunk_attention_fallback_builds")
    before = counter.value
    ids = jnp.zeros((1, 32), jnp.int32)
    text = jax.jit(lambda p, ids, cache: forward_with_cache(
        model, p, ids, cache, flash_decode=True)).lower(
            params, ids, init_cache(cfg, 1, 256, F32)).as_text()
    assert "chunk_attention" not in text
    assert counter.value == before

"""Pallas decode attention: one query token against the KV cache.

TPU-native answer to the reference's ``softmax_context`` inference kernel
(``csrc/transformer/inference/csrc/softmax_context_cuda.cu`` via
``pt_binding.cpp``): fused attention of the current token over the cached
keys/values, masking cache slots past the live length.  The XLA fallback in
``inference/decode.py`` materializes the full (B, H, 1, max_len) score tensor
in HBM each step; this kernel streams the cache through VMEM with an online
softmax instead — the decode hot loop is bandwidth-bound, so not spilling
scores is the win.

Layout notes:
- the cache is ``(L, B, KV, hd, max_len)``: POSITIONS ON THE LANES. HBM
  tiles the last two dims (8 x 128 words): ``max_len`` is a multiple of
  128 here, so no lane is padding, whatever ``hd`` is. With ``hd`` last, a
  head of 64 fills half of every tile — the kernel's operand is then twice
  the cache's bytes, and the compiler keeps the cache compact by storing it
  the other way round and re-laying every slab out around each call (what
  PERF.md F10 measured: more time moving K/V than attending to it).
- both kernels take the WHOLE cache and a scalar-prefetched layer index,
  and block it through the index map: the layer loop carries one buffer
  and nothing slices a layer's slab out of it or writes one back.
- ``decode_attention``: grid (B, H); a program's K/V block is one
  (slot, kv-head)'s ``(hd, max_len)``. The GQA head group mapping happens
  in the index map (h // group), so there is no repeated-KV
  materialization at all (the training kernel pays a ``jnp.repeat``;
  decode can't afford it). ``s = q @ k`` is a plain (8, hd) @ (hd, block);
  ``p . v`` contracts the lane dims of both (the MXU's NT form).
- the single query row is broadcast to the 8-sublane tile (q_sub trick) so
  the matmuls are MXU/VPU shaped.
- the live length is a scalar-prefetch operand (SMEM), letting the kernel
  bound its streaming loop at ceil(length / block) instead of max_len.
- ``cache_append``: grid (B, kv-blocks); writes the step's new K/V at
  position ``length - 1`` of every slot as a read-modify-write of the one
  128-lane tile that holds it, with the cache aliased to the output. An
  XLA-level update of one position lets the compiler pick a layout FOR THE
  UPDATE and convert the whole cache to it; inside an aliased kernel
  nothing can.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

BIG_NEG = -2.0 ** 30
SUBLANES = 8
LANES = 128
# cache_append's tile is (kv-block, hd, 128): the most KV heads a program
# takes, so in, out and their double buffers stay far inside scoped VMEM
_APPEND_TILE_BYTES = 512 * 1024


def _decode_kernel(*refs, block: int, scale: float, alibi: bool):
    if alibi:
        len_ref, _, slopes_ref, q_ref, k_ref, v_ref, o_ref = refs
    else:
        len_ref, _, q_ref, k_ref, v_ref, o_ref = refs
        slopes_ref = None
    b = pl.program_id(0)
    h = pl.program_id(1)
    # an idle slot's length keeps counting past the cache: never past the
    # block (the append clamps the same way)
    L = jnp.minimum(len_ref[b], k_ref.shape[1])
    q = q_ref[...].astype(jnp.float32) * scale          # (SUBLANES, hd)

    def body(j, carry):
        m, l, acc = carry
        at = pl.ds(pl.multiple_of(j * block, block), block)
        k = k_ref[:, at].astype(jnp.float32)             # (hd, blk)
        v = v_ref[:, at].astype(jnp.float32)
        s = jnp.dot(q, k, preferred_element_type=jnp.float32)  # (SUB, blk)
        col = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (SUBLANES, block), 1)
        if slopes_ref is not None:
            # ALiBi is a pure function of (slot, live length): slope·(s -
            # t) with the query at global position t = L-1 — no (H, S)
            # bias tensor ever exists (the dense fallback builds one per
            # step; Bloom's positional signal costs one SMEM scalar here)
            s = s + slopes_ref[h] * (col - (L - 1)).astype(jnp.float32)
        keep = col < L
        s = jnp.where(keep, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(          # p (SUB, blk) . vT
            p, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    nb = (L + block - 1) // block                        # only live blocks
    m0 = jnp.full((SUBLANES, 1), BIG_NEG, jnp.float32)
    l0 = jnp.zeros((SUBLANES, 1), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _shard_axes(ck, H):
    from ..platform.mesh import attention_shard_axes

    axes = attention_shard_axes(ck.shape[1], H, ck.shape[2])
    if axes is None:
        return None
    mesh, b_ax, h_ax = axes
    return mesh, b_ax, h_ax, P(None, b_ax, h_ax, None, None)


def decode_attention(q, ck, cv, length, *, layer=None, alibi_slopes=None,
                     block: int = LANES, interpret: Optional[bool] = None):
    """q: (B, 1, H, hd) current-token queries; ck/cv: the cache
    ``(L, B, KV, hd, max_len)`` with ``layer`` (traced i32) the layer to
    attend over, or one layer's ``(B, KV, hd, max_len)``; ``length`` scalar
    or (B,) live lengths (positions < length attended).
    ``alibi_slopes``: optional (H,) per-head slopes — the ALiBi distance
    bias is reconstructed in-kernel from the live length (Bloom decode
    stays on the streaming kernel instead of the dense fallback).

    Returns (B, 1, H, hd)."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, hd = q.shape
    assert T == 1, "decode kernel is single-token; use flash_attention for prefill"
    if ck.ndim == 4:            # a layer's slab: a cache of that one layer
        ck, cv, layer = ck[None], cv[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    KV, S = ck.shape[2], ck.shape[4]
    blk = min(block, S)
    if S % blk != 0:
        raise ValueError(f"cache length {S} not divisible by block {blk}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    group = H // KV
    scale = 1.0 / math.sqrt(hd)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    alibi = alibi_slopes is not None
    slopes = (jnp.asarray(alibi_slopes, jnp.float32),) if alibi else ()

    axes = _shard_axes(ck, H)
    if axes is not None:
        # GSPMD cannot partition a Mosaic kernel: run it per shard, slots
        # over the example-parallel axes and heads over model/seq (inside
        # the body the axes are manual, so the recursion lands below)
        mesh, b_ax, h_ax, cache = axes

        def per_shard(q, ck, cv, n, layer, *slopes):
            return decode_attention(q, ck, cv, n, layer=layer[0], block=block,
                                    interpret=interpret,
                                    alibi_slopes=slopes[0] if slopes else None)

        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(b_ax, None, h_ax, None), cache, cache, P(b_ax), P())
            + ((P(h_ax),) if alibi else ()),
            out_specs=P(b_ax, None, h_ax, None), check_vma=False)(
                q, ck, cv, lengths, layer, *slopes)

    # (B, 1, H, hd) → (B, H, SUBLANES, hd): sublane-replicated single query
    qs = jnp.broadcast_to(q.swapaxes(1, 2), (B, H, SUBLANES, hd))

    def kv_block(b, h, n, layer, *_):
        return (layer[0], b, h // group, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(slopes),
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((None, None, SUBLANES, hd),
                         lambda b, h, *pre: (b, h, 0, 0)),
            pl.BlockSpec((None, None, None, hd, S), kv_block),
            pl.BlockSpec((None, None, None, hd, S), kv_block),
        ],
        out_specs=pl.BlockSpec((None, None, SUBLANES, hd),
                               lambda b, h, *pre: (b, h, 0, 0)),
    )
    out = pl.pallas_call(
        partial(_decode_kernel, block=blk, scale=scale, alibi=alibi),
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, SUBLANES, hd), q.dtype),
        interpret=interpret,
    )(lengths, layer, *slopes, qs, ck, cv)
    return out[:, :, :1, :].swapaxes(1, 2)               # (B, 1, H, hd)


def _append_kernel(pos_ref, _, *refs):
    """``refs``: n new-value blocks, n cache tiles, n output tiles."""
    from jax.experimental.pallas import tpu as pltpu

    n = len(refs) // 3
    b = pl.program_id(0)
    r = pos_ref[b] % LANES                  # the position's lane in its tile
    col = jax.lax.broadcasted_iota(jnp.int32, refs[n].shape, 2)
    # the new values lie slots-on-lanes: slot b's column turns onto lane r
    turn = (r - b % LANES) % LANES
    for new_ref, old_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                         refs[2 * n:]):
        new = pltpu.roll(new_ref[...].astype(jnp.float32), turn, 2)
        out_ref[...] = jnp.where(col == r, new.astype(out_ref.dtype),
                                 old_ref[...])


def cache_append(ck, cv, k, v, length, *, layer,
                 interpret: Optional[bool] = None):
    """Write this step's K/V into layer ``layer`` (traced i32) of the cache
    ``(L, B, KV, hd, max_len)``, in place: ``k``/``v`` (B, 1, KV, hd) go to
    position ``length - 1`` of every slot (``length`` scalar or (B,), the
    lengths AFTER the append; clamped into the cache as
    ``dynamic_update_slice`` clamps). Returns the cache with the outputs
    aliased to the inputs, every other position bit-untouched."""
    from jax.experimental.pallas import tpu as pltpu

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    _, B, KV, hd, S = ck.shape
    if S % LANES != 0:
        raise ValueError(f"cache length {S} not a multiple of {LANES}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))

    axes = _shard_axes(ck, KV)
    if axes is not None:
        mesh, b_ax, h_ax, cache = axes
        new = P(b_ax, None, h_ax, None)

        def per_shard(ck, cv, k, v, n, layer):
            return cache_append(ck, cv, k, v, n, layer=layer[0],
                                interpret=interpret)

        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(cache, cache, new, new, P(b_ax), P()),
            out_specs=(cache, cache), check_vma=False)(
                ck, cv, k, v, lengths, layer)

    return append_in_place((ck, cv), (k, v), lengths, layer,
                           name="cache_append", interpret=interpret)


def append_in_place(caches: tuple, news: tuple, lengths, layer, *, name: str,
                    interpret: bool):
    """The kernel behind :func:`cache_append`, for any number of buffers
    ``(L, B, KV, hd, max_len)`` written at the same positions (K and V; the
    one buffer of a latent cache, ``ops/mla_attention.py``): ``news``
    (B, 1, KV, hd) each, ``lengths`` (B,) AFTER the append, ``layer`` (1,)
    i32. Returns the caches, outputs aliased to the inputs."""
    from jax.experimental.pallas import tpu as pltpu

    ck = caches[0]
    _, B, KV, hd, S = ck.shape
    n = len(caches)
    # (B, 1, KV, hd) → (KV, hd, slots): hd on the sublanes as in the cache,
    # the slots on the lanes (a few KiB; the kernel turns its slot's column
    # onto the position's lane)
    pos = jnp.clip(lengths - 1, 0, S - 1)
    pad = (-B) % LANES
    news = tuple(jnp.pad(x[:, 0].transpose(1, 2, 0).astype(c.dtype),
                         ((0, 0), (0, 0), (0, pad)))
                 for x, c in zip(news, caches))
    kvb = max(d for d in range(1, KV + 1) if KV % d == 0 and (
        d == 1 or d * hd * LANES * ck.dtype.itemsize <= _APPEND_TILE_BYTES))

    def new_block(b, g, pos, layer):
        return (g, 0, b // LANES)

    def tile(b, g, pos, layer):
        return (layer[0], b, g, 0, pos[b] // LANES)

    new_spec = pl.BlockSpec((kvb, hd, LANES), new_block)
    tile_spec = pl.BlockSpec((None, None, kvb, hd, LANES), tile)
    return pl.pallas_call(
        _append_kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV // kvb),
            in_specs=[new_spec] * n + [tile_spec] * n,
            out_specs=[tile_spec] * n),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
    )(pos, layer, *news, *caches)

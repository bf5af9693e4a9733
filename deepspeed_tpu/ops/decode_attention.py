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
- both kernels take the WHOLE cache and a scalar-prefetched layer index:
  the layer loop carries one buffer and nothing slices a layer's slab out
  of it or writes one back.
- ``decode_attention``: grid (B, KV / hb); a program's work is a slot's
  ``hb`` KV heads over that slot's LIVE blocks of ``block`` (128)
  positions. ``hb`` is the largest divisor of ``KV`` whose K block
  ``(hb, hd, block)`` fits ``_ATTEND_BLOCK_BYTES`` in the cache's dtype
  (``_heads_per_program``: all 20 heads of GPT-2 774M, a TP shard's 5, 32
  of 64 at ``hd`` 128): derived from the shapes, never from the batch, so
  a slot's bits do not depend on its neighbours. The cache stays in HBM
  (``pl.ANY``); the kernel copies ``ceil(length / block)`` blocks of K and
  of V into two VMEM buffers each with ``make_async_copy``, the next block
  in flight behind the current one's products, and after a slot's last
  block the first block of the NEXT program (which buffer, and whether
  that copy was started, ride in SMEM scratch: the grid runs in order).
  Positions behind the live length are neither fetched nor multiplied.
- the GQA head group mapping is a reshape of q to ``(B, KV, group, hd)``:
  a KV head's query rows are real rows of one product, padded to the 8
  sublanes, and there is no repeated-KV materialization at all
  (the training kernel pays a ``jnp.repeat``; decode can't afford it).
  K and V enter the MXU as stored: ``s = q @ k`` is a batch over heads of
  (rows, hd) @ (hd, block), ``p . v`` contracts the lane dims of both (the
  MXU's NT form) with ``p`` in the cache's dtype; scores, the scale, the
  running max, the sum and the accumulator are float32 (the loop's carry).
- the live length is a scalar-prefetch operand (SMEM): it bounds the
  kernel's loop and its copies at ceil(length / block) instead of max_len.
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
# decode_attention's K (and V) block is (kv-block, hd, block): two buffers
# of each, the scores and the accumulator stay under a third of the 16 MiB
# of scoped VMEM
_ATTEND_BLOCK_BYTES = 1024 * 1024


def _decode_kernel(*refs, block: int, scale: float, alibi: bool, group: int):
    """One program: a slot's ``hb`` KV heads (``q_ref`` (hb, rows, hd), the
    group's query rows padded to the 8 sublanes) over that slot's live
    blocks, copied out of the cache in HBM by the kernel itself."""
    from jax.experimental.pallas import tpu as pltpu

    len_ref, layer_ref, *refs = refs
    slopes_ref = refs.pop(0) if alibi else None
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, ahead = refs
    b, g = pl.program_id(0), pl.program_id(1)
    n_slots, n_groups = pl.num_programs(0), pl.num_programs(1)
    hb, rows, _ = q_ref.shape
    S = k_hbm.shape[4]
    # an idle slot's length keeps counting past the cache: never past it
    # (the append clamps the same way)
    L = jnp.minimum(len_ref[b], S)
    nb = (L + block - 1) // block                        # only live blocks

    def copies(buf, slot, heads, j):
        at = (layer_ref[0], slot, pl.ds(heads * hb, hb), slice(None),
              pl.ds(pl.multiple_of(j * block, block), block))
        return (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[buf],
                                      sem.at[1, buf]))

    def fetch(buf, slot, heads, j):
        for copy in copies(buf, slot, heads, j):
            copy.start()

    # ``ahead``: which buffer this program's first block goes to, and
    # whether the program before already started that copy
    @pl.when((b == 0) & (g == 0))
    def _():
        ahead[0] = 0
        ahead[1] = 0

    first = ahead[0]

    @pl.when((nb > 0) & (ahead[1] == 0))
    def _():
        fetch(first, b, g, 0)

    # the program after this one, and whether it has a block to fetch
    wraps = g == n_groups - 1
    b_next = jnp.where(wraps, b + 1, b)
    g_next = jnp.where(wraps, 0, g + 1)
    next_live = (b_next < n_slots) & (
        len_ref[jnp.minimum(b_next, n_slots - 1)] > 0)

    q = q_ref[...]
    slope = None
    if alibi:
        # (hb, rows, 1) of per-query-head slopes, from SMEM scalars
        head = jax.lax.broadcasted_iota(jnp.int32, (hb, rows, 1), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (hb, rows, 1), 1)
        slope = jnp.zeros((hb, rows, 1), jnp.float32)
        for i in range(hb):
            for r in range(group):
                slope = jnp.where((head == i) & (row == r),
                                  slopes_ref[(g * hb + i) * group + r], slope)

    def body(j, carry):
        m, l, acc = carry
        buf = (first + j) % 2

        # behind this block's products: the slot's next block, or after
        # its last the first block of the next program
        @pl.when(j + 1 < nb)
        def _():
            fetch(1 - buf, b, g, j + 1)

        @pl.when((j + 1 == nb) & next_live)
        def _():
            fetch(1 - buf, b_next, g_next, 0)

        for copy in copies(buf, b, g, j):
            copy.wait()
        k, v = k_buf[buf], v_buf[buf]                    # (hb, hd, blk)
        s = jax.lax.dot_general(                         # (hb, rows, blk)
            q, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        col = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if slope is not None:
            # ALiBi is a pure function of (slot, live length): slope·(s -
            # t) with the query at global position t = L-1 — no (H, S)
            # bias tensor ever exists (the dense fallback builds one per
            # step; Bloom's positional signal costs SMEM scalars here)
            s = s + slope * (col - (L - 1)).astype(jnp.float32)
        keep = col < L
        s = jnp.where(keep, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(          # p . vT per head
            p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((hb, rows, 1), BIG_NEG, jnp.float32)
    l0 = jnp.zeros((hb, rows, 1), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, acc0))
    ahead[0] = (first + nb) % 2
    ahead[1] = ((nb > 0) & next_live).astype(jnp.int32)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _shard_axes(ck, H):
    from ..platform.mesh import attention_shard_axes

    axes = attention_shard_axes(ck.shape[1], H, ck.shape[2])
    if axes is None:
        return None
    mesh, b_ax, h_ax = axes
    return mesh, b_ax, h_ax, P(None, b_ax, h_ax, None, None)


def _heads_per_program(KV: int, hd: int, blk: int, dtype) -> int:
    """The most KV heads (a divisor of ``KV``) whose K block fits
    ``_ATTEND_BLOCK_BYTES``: all 20 of GPT-2 774M, a TP shard's 5, all 32
    of a 7B model at ``hd`` 128."""
    return max(d for d in range(1, KV + 1) if KV % d == 0 and (
        d == 1 or d * hd * blk * jnp.dtype(dtype).itemsize
        <= _ATTEND_BLOCK_BYTES))


def decode_attention(q, ck, cv, length, *, layer=None, alibi_slopes=None,
                     block: int = LANES, interpret: Optional[bool] = None):
    """q: (B, 1, H, hd) current-token queries; ck/cv: the cache
    ``(L, B, KV, hd, max_len)`` with ``layer`` (traced i32) the layer to
    attend over, or one layer's ``(B, KV, hd, max_len)``; ``length`` scalar
    or (B,) live lengths (positions < length attended).
    ``alibi_slopes``: optional (H,) per-head slopes — the ALiBi distance
    bias is reconstructed in-kernel from the live length (Bloom decode
    stays on the streaming kernel instead of the dense fallback).

    A slot's result depends on that slot's row and length alone: the block
    and the heads a program takes follow from ``(KV, hd, max_len, dtype)``,
    never from ``B``.

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

    hb = _heads_per_program(KV, hd, blk, ck.dtype)
    # (B, 1, H, hd) → (B, KV, rows, hd): a KV head's ``group`` query rows,
    # as the cache is stored, padded to the sublane tile — the GQA mapping
    # is this reshape, K/V are never repeated
    rows = -(-group // SUBLANES) * SUBLANES
    qs = jnp.pad(q.reshape(B, KV, group, hd).astype(ck.dtype),
                 ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    q_spec = pl.BlockSpec((None, hb, rows, hd),
                          lambda b, g, *pre: (b, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(slopes),
        grid=(B, KV // hb),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((2, hb, hd, blk), ck.dtype),
                        pltpu.VMEM((2, hb, hd, blk), cv.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    out = pl.pallas_call(
        partial(_decode_kernel, block=blk, scale=scale, alibi=alibi,
                group=group),
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), q.dtype),
        # a program starts the next one's first copy: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths, layer, *slopes, qs, ck, cv)
    return out[:, :, :group].reshape(B, 1, H, hd)


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

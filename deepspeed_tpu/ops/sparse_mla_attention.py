"""The latent (MLA) decode step over SELECTED positions: one absorbed query
per head against the latents of the positions a learned indexer chose
(``models/dsa.py``) — fetched one position at a time or, where a slot's live
positions are few enough a selected one, its live blocks read whole under
the selection's mask — and the in-place append of the step's own latents, in
one Pallas kernel.

**The layout, and why.** ``ops/mla_attention.py`` keeps positions on the
lanes (``(L, B, rank + rope, max_len)``): a block of 128 neighbours is one
tile column, and one position alone is the worst thing to fetch. Here ONE
position has to come without its neighbours, so the cache is ``(L, B,
max_len, 1, words)``: a position is a leading index and its latents the
whole of the two minor dimensions, which XLA tiles ``(1, 128)`` — no
sublane padding, and a DMA of ``cache[layer, slot, position]`` is a whole
number of tiles (Mosaic refuses a slice that is not: one row of an ``(8,
128)``-tiled ``(max_len, 576)`` plane cannot be copied alone). The minor
dimension is a whole number of 128-word tiles for the same reason. A 2-byte
cache packs two values a 32-bit word (``uint32``: value ``i`` in the low
half beside value ``i + words`` in the high half, so that both halves
unpack, by a shift and a mask, into lane-aligned operands): 576 bf16 values
are 288 words in a row of 384, 1536 B a position a layer where 1152 are
used (``fetched_over_selected`` 1.33: the price of the tile). A 4-byte cache
holds its values as they are, padded likewise.

``sparse_mla_decode_attention``: grid (slots,). A program writes its slot's
new latents at ``length - 1`` (DMA, waited for: the position may be among
the selected), then brings the slot's latents into VMEM one of two ways,
chosen from the scalars it prefetches (:func:`reads_dense`: ``length`` over
``n`` against the ratio of the two measured costs). **Gathered:** it walks
its ``n = min(length, K)`` selected positions in groups of ``group``: the
group's DMAs — a descriptor a position, 56 ns each whatever it carries — go
out while the group before is multiplied (two buffers). **Dense:** it walks
its ``ceil(length / block)`` live blocks whole (a contiguous copy a block,
re-tiled by the DMA, at 600-700 B/ns; two buffers) with the selection as a
row added to the scores (:func:`step_mask`: 0 or ``BIG_NEG``), rows behind
the live length zeroed. Behind either fetch ``s = q . lat^T`` for all heads
at once, the running softmax, ``o += p . c``: the same softmax over the same
positions, the order of addition apart. A slot at length 0 writes, fetches
and multiplies nothing.

``index_scores`` (kernel ``dsa_index_score``): the indexer's weighted ReLU
score of a slot's live keys for the step, block by block, nothing behind the
live length fetched. (In XLA the same product re-laid the whole key buffer
out batch-minor, 2 GB of padding a step.)

``sparse_mla_chunk_attention``: T > 1 queries (a chunk, a final bucket) over
the live blocks of :data:`KEY_BLOCK` positions with the selection as a mask:
the published, expanded form (``mla.attend_expanded(selected=)``), dense work
under a mask. Grid (rows, groups of :data:`HEADS` heads). A program walks the
live blocks in a loop of its own (two buffers: the next block's rows and its
block of the mask come while this one is multiplied), and for each of its
heads expands the block's ``k_nope`` and ``v`` with the head's slice of
``wkv_b``, ``s = q . k`` in float32, the running softmax, ``acc += p . v``:
scores, probabilities, the expanded block and the accumulators never leave
VMEM, where XLA's walk carried ``acc`` (33 MB a layer), ``p`` and the
expanded block through HBM every block. ``k_rope`` rides in the lanes that
pad ``k_nope``'s last tile (the query's columns permuted to match), so one
product of a whole number of tiles gives ``q_nope . k_nope + q_rope .
k_rope``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .decode_attention import BIG_NEG, LANES
from .mla_attention import _lengths, _refuse_mesh

GROUP = 256          # positions a buffer holds (two of them in VMEM)
DENSE_BLOCK = 1024   # live positions a turn of the step's dense walk
# What decides how a slot's latents come into VMEM: two measured costs
# (examples/sparse_mla_decode_attention_microbench.py, PR 56). A descriptor
# costs what it costs to issue whatever it carries: 56 ns one row, 78 ns a
# run of four. A row read among its neighbours costs its bytes at the rate
# the dense walk holds over a long slot: 707 B/ns in rows of 1536 B, 597 in
# rows of 1024 B (the products, not the HBM, bound the narrower row); the
# lower of the two, rounded, so that a slot near the edge gathers.
DESCRIPTOR_NS = (56.0, 78.0)
DENSE_BYTES_PER_NS = 600.0
KEY_BLOCK = 512      # keys a turn of a chunk's walk (a divisor of max_len)
HEADS = 4            # heads a program of the chunk's kernel
MASK_TILE = 32       # queries its int8 mask's sublane tile holds
VMEM_LIMIT = 64 * 2 ** 20   # of a core's 128 MiB; the chunk's kernel holds ~20
IDX_PREFETCH_BYTES = 256 * 2 ** 10   # of SMEM for a step's selected positions
FLOOR = -2.0 ** 20   # under every score, over BIG_NEG: exp(BIG_NEG - FLOOR) = 0


def crossover(run: int, row_bytes: int) -> float:
    """Live rows a selected one at which fetching a slot's live blocks whole
    costs what a descriptor a selected position (a run of ``run``) costs."""
    return DESCRIPTOR_NS[run > 1] / run * DENSE_BYTES_PER_NS / row_bytes


def reads_dense(length, n, run: int, row_bytes: int):
    """Whether a slot of ``length`` live positions of which ``n`` are
    selected reads its live blocks whole, the selection a mask, and not a
    descriptor a selected row: ``length <= crossover * n``, in sixteenths so
    that the kernel's scalar core, the wrapper and the host's mirror
    (``step_meta``) count with the same integers. Python ints, numpy arrays
    or traced int32."""
    return length * 16 <= int(16 * crossover(run, row_bytes)) * n


def dense_rows(length, max_len: int, block: int = DENSE_BLOCK):
    """The rows a dense walk over ``length`` live positions of a cache of
    ``max_len`` brings in: whole blocks."""
    blk = _key_block(max_len, block)
    return -(-length // blk) * blk


def step_mask(mask):
    """A step's selection ``mask`` (B, 1, S) bool as the kernel takes it: a
    float32 row a slot, 0 where selected and ``BIG_NEG`` elsewhere — added
    to the scores, and a row of ONE query in whole (1, 128) tiles, which an
    int8 row is not."""
    return jnp.where(mask, 0.0, BIG_NEG).astype(jnp.float32)


def einsum_f32(spec: str, a, b):
    """``einsum`` of two arrays of the compute type into float32: the
    MXU's own accumulation on the chip; on the CPU, whose runtime has no
    bf16 x bf16 -> f32 batched product, the operands widened first."""
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def row_layout(values: int, dtype) -> tuple:
    """(words in a position's row, the words' dtype, values a word) of a
    cache of ``dtype`` holding ``values`` a position."""
    packed = 2 if jnp.dtype(dtype).itemsize == 2 else 1
    words = -(-values // (packed * LANES)) * LANES
    return words, (jnp.uint32 if packed == 2 else jnp.dtype(dtype)), packed


def pack_rows(lat, dtype):
    """``lat`` (..., values) as rows of the cache ``(..., 1, words)``."""
    words, _, packed = row_layout(lat.shape[-1], dtype)
    lat = lat.astype(dtype)
    lat = jnp.pad(lat, [(0, 0)] * (lat.ndim - 1)
                  + [(0, packed * words - lat.shape[-1])])
    if packed == 1:
        return lat[..., None, :]
    bits = lax.bitcast_convert_type(lat, jnp.uint16).astype(jnp.uint32)
    return (bits[..., :words] | (bits[..., words:] << 16))[..., None, :]


def unpack_rows(rows, values: int, dtype):
    """The inverse: ``rows`` (..., 1, words) -> (..., values) of ``dtype``."""
    rows = rows[..., 0, :]
    if rows.dtype != jnp.uint32:
        return rows[..., :values].astype(dtype)
    lo = lax.bitcast_convert_type((rows & 0xffff).astype(jnp.uint16), dtype)
    hi = lax.bitcast_convert_type((rows >> 16).astype(jnp.uint16), dtype)
    return jnp.concatenate([lo, hi], axis=-1)[..., :values]


def _halves(w, dtype):
    """A loaded (G, words) block as the operands of its products: the low
    and the high halves of packed words, or the block itself."""
    from jax.experimental.pallas import tpu as pltpu

    if w.dtype != jnp.uint32:
        return (w,)
    return (pltpu.bitcast(w << 16, jnp.float32).astype(dtype),
            pltpu.bitcast(w & jnp.uint32(0xffff0000),
                          jnp.float32).astype(dtype))


def _kernel(*refs, group: int, rank: int, values: int, scale: float, dtype,
            idx_block: bool, run: int, block: int):
    from jax.experimental.pallas import tpu as pltpu

    # the selection: the whole batch's by scalar prefetch, or (too many
    # slots for SMEM) this slot's row as a block of its own
    if idx_block:
        n_ref, len_ref, layer_ref, idx_ref = refs[:4]
    else:
        idx_ref, n_ref, len_ref, layer_ref = refs[:4]
    if block:       # the selection as a mask too: a slot may read dense
        (q_ref, new_ref, mask_ref, cache_ref, o_ref, out_cache_ref, buf, sem,
         wsem, m_ref, l_ref, acc_ref, rows, bias, dsem) = refs[4:]
    else:
        (q_ref, new_ref, cache_ref, o_ref, out_cache_ref, buf, sem, wsem,
         m_ref, l_ref, acc_ref) = refs[4:]
    del cache_ref                       # aliased: out_cache_ref is the cache
    b = pl.program_id(0)
    row = 0 if idx_block else b
    n, layer = n_ref[b], layer_ref[0]
    G, W = group, buf.shape[-1]
    ng = (n + G - 1) // G
    parts = 2 if buf.dtype == jnp.uint32 else 1
    lowest = BIG_NEG
    if block:
        # one slot, one fetch: its live blocks whole where the live rows
        # are few enough a selected one, else a descriptor a selected row
        dense = reads_dense(len_ref[b], n, run,
                            W * jnp.dtype(buf.dtype).itemsize)
        nb = jnp.where(dense, (len_ref[b] + block - 1) // block, 0)
        ng = jnp.where(dense, 0, ng)
        # (the dense walk adds its mask to the scores: as the chunk's)
        lowest = jnp.where(dense, FLOOR, BIG_NEG)

    m_ref[...] = jnp.full(m_ref.shape, lowest, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(len_ref[b] > 0)
    def _():
        write = pltpu.make_async_copy(
            new_ref, out_cache_ref.at[layer, b, pl.ds(len_ref[b] - 1, 1)], wsem)
        write.start()
        write.wait()

    def copy(pos, slot, i):
        # ``run`` consecutive positions a descriptor (a selection of whole
        # aligned groups: models/dsa.py select_pooled), one where run is 1
        if run == 1:
            return pltpu.make_async_copy(out_cache_ref.at[layer, b, pos],
                                         buf.at[slot, i], sem.at[slot])
        return pltpu.make_async_copy(
            out_cache_ref.at[layer, b, pl.ds(pos, run)],
            buf.at[slot, pl.ds(i * run, run)], sem.at[slot])

    per = G // run                      # descriptors a group of G positions

    def each(g, fn):
        def one(i, _):
            @pl.when(g * G + (i if run == 1 else i * run) < n)
            def _():
                fn(i)
            return 0
        lax.fori_loop(0, per, one, 0)

    def fetch(g, slot):
        each(g, lambda i: copy(idx_ref[row, g * per + i], slot, i).start())

    @pl.when(ng > 0)
    def _():
        fetch(0, 0)

    def body(g, _):
        slot = g % 2

        @pl.when(g + 1 < ng)
        def _():
            fetch(g + 1, 1 - slot)

        each(g, lambda i: copy(0, slot, i).wait())
        lat = _halves(buf[slot].reshape(G, W), dtype)
        q = q_ref[...]                                   # (H, parts * W)
        nt = (((1,), (1,)), ((), ()))
        s = sum(lax.dot_general(q[:, i * W:(i + 1) * W], lat[i], nt,
                                preferred_element_type=jnp.float32)
                for i in range(parts)) * scale
        keep = g * G + lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
        s = jnp.where(keep, s, BIG_NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # a row no DMA wrote holds whatever the buffer held: 0 x NaN is NaN
        live = g * G + lax.broadcasted_iota(jnp.int32, (G, 1), 0) < n
        for i in range(parts):
            acc_ref[:, i * W:(i + 1) * W] = \
                acc_ref[:, i * W:(i + 1) * W] * corr + jnp.dot(
                    p.astype(dtype), jnp.where(live, lat[i], 0),
                    preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return 0

    lax.fori_loop(0, ng, body, 0)
    if block:
        _dense_walk(nb, len_ref[b], out_cache_ref.at[layer, b],
                    mask_ref.at[b], q_ref, rows, bias, dsem, m_ref, l_ref,
                    acc_ref, block=block, rank=rank, values=values,
                    scale=scale, dtype=dtype)
    o_ref[...] = (acc_ref[:, :rank] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


def _dense_walk(nb, length, cache_ref, mask_ref, q_ref, rows, bias, sem,
                m_ref, l_ref, acc_ref, *, block: int, rank: int, values: int,
                scale: float, dtype):
    """The other fetch of the step's kernel: the slot's first ``nb`` blocks
    of ``block`` positions, whole (``cache_ref`` (max_len, 1, words): a
    contiguous copy, re-tiled by the DMA, two buffers), under the selection
    as an additive mask (``mask_ref`` (1, max_len)): the same softmax over
    the same selected positions. Only the lanes that hold values are
    multiplied: ``values`` of them in the scores, ``rank`` in the sums."""
    from jax.experimental.pallas import tpu as pltpu

    W = rows.shape[-1]
    parts = 2 if rows.dtype == jnp.uint32 else 1
    nt = (((1,), (1,)), ((), ()))

    def lanes(count):       # of each part's W lanes, those under ``count``
        return [min(W, -(-max(count - i * W, 0) // LANES) * LANES)
                for i in range(parts)]

    scored, summed = lanes(values), lanes(rank)

    def copies(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(cache_ref.at[at, 0], rows.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(mask_ref.at[:, at], bias.at[slot],
                                      sem.at[1, slot]))

    @pl.when(nb > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()

    def body(j, _):
        slot = j % 2

        @pl.when(j + 1 < nb)
        def _():
            for copy in copies(j + 1, 1 - slot):
                copy.start()

        for copy in copies(j, slot):
            copy.wait()
        # what lies behind the live length is another request's: 0 x NaN
        live = j * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0) \
            < length
        lat = _halves(jnp.where(live, rows[slot], 0), dtype)
        q = q_ref[...]                                   # (H, parts * W)
        s = sum(lax.dot_general(q[:, i * W:i * W + k], lat[i][:, :k], nt,
                                preferred_element_type=jnp.float32)
                for i, k in enumerate(scored) if k)
        s = s * scale + bias[slot]
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        for i, k in enumerate(summed):
            at = slice(i * W, i * W + k)
            if k:
                acc_ref[:, at] = acc_ref[:, at] * corr + jnp.dot(
                    p.astype(dtype), lat[i][:, :k],
                    preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return 0

    lax.fori_loop(0, nb, body, 0)


def sparse_mla_decode_attention(q, cache, new, idx, length, *, layer,
                                rank: int, scale: float, group: int = GROUP,
                                n=None, run: int = 1, mask=None,
                                block: int = DENSE_BLOCK,
                                interpret: Optional[bool] = None):
    """``q`` (B, H, rank + rope): the absorbed queries, in the order the
    latents lie; ``cache`` (L, B, max_len, 1, words) (:func:`pack_rows`),
    ``layer`` (traced i32) the layer read and written; ``new`` (B, rank +
    rope): this step's latents, written at ``length - 1`` of every slot
    first, in place (the cache comes back aliased, every other position
    bit-untouched); ``idx`` (B, K) i32: the positions selected, the first
    ``min(length, K)`` of a row valid — or the first ``n`` (B,) i32, where
    a selection of another count says so (pooled keys, ``models/dsa.py``);
    ``length`` (B,) AFTER the append. ``run`` > 1: the selection is whole
    aligned groups of ``run`` consecutive positions (``idx[:, j * run + i] =
    idx[:, j * run] + i``, K a multiple of ``run``): one DMA fetches a group,
    where a descriptor a position is what the read waits for (56 ns each).
    ``mask`` (B, 1, max_len) f32 (:func:`step_mask`): the same selection —
    the valid ``idx`` of a row, each once — as a row added to the scores.
    With it a slot whose live rows are few enough a selected one
    (:func:`reads_dense`) reads its live blocks of ``block`` positions whole
    and attends under the mask; without it every slot fetches by ``idx``.
    Returns (``o_lat`` (B, H, rank) = softmax(q . lat . scale) . c over the
    selected positions, the cache)."""
    from jax.experimental.pallas import tpu as pltpu

    _refuse_mesh("sparse_mla_decode_attention")
    B, H, D = q.shape
    S, W = cache.shape[2], cache.shape[-1]
    K = idx.shape[1]
    dtype = q.dtype
    parts = 2 if cache.dtype == jnp.uint32 else 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lengths = jnp.minimum(_lengths(length, B), S)
    n = jnp.minimum(lengths, K) if n is None else jnp.where(
        lengths > 0, jnp.minimum(n.astype(jnp.int32), K), 0)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, parts * W - D)))
    G = min(group, -(-K // 8) * 8)
    if run > 1:
        if K % run or G % run:
            raise ValueError(f"{K} selected positions in groups of {G} are "
                             f"not whole runs of {run}")
        idx, K = idx[:, ::run], K // run     # (K counts descriptors now)
    # (B, K) indices by scalar prefetch while they fit a quarter of SMEM's
    # 1 MiB (10 slots x 2048: 80 KiB); beyond, a slot's row a program
    idx_block = B * K * 4 > IDX_PREFETCH_BYTES
    idx = jnp.maximum(idx, 0).astype(jnp.int32)
    scalars = (n, lengths, jnp.asarray(layer, jnp.int32).reshape(1))
    blk = 0 if mask is None else _key_block(S, block)
    masks = [] if mask is None else [mask.astype(jnp.float32)]
    o, cache = pl.pallas_call(
        partial(_kernel, group=G, rank=rank, values=D, scale=scale,
                dtype=dtype, idx_block=idx_block, run=run, block=blk),
        name="sparse_mla_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 if idx_block else 4,
            grid=(B,),
            in_specs=([pl.BlockSpec((None, 1, K), lambda b, *_: (b, 0, 0),
                                    memory_space=pltpu.SMEM)]
                      if idx_block else [])
            + [pl.BlockSpec((None, H, parts * W), lambda b, *_: (b, 0, 0)),
               pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(masks)),
            out_specs=[pl.BlockSpec((None, H, rank), lambda b, *_: (b, 0, 0)),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((2, G, 1, W), cache.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, parts * W), jnp.float32)]
            + ([pltpu.VMEM((2, blk, W), cache.dtype),
                pltpu.VMEM((2, 1, blk), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2))] if masks else [])),
        out_shape=[jax.ShapeDtypeStruct((B, H, rank), dtype),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        input_output_aliases={6 + len(masks): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT if masks else None),
        interpret=interpret,
    )(*((*scalars, idx[:, None]) if idx_block else (idx, *scalars)),
      q.astype(dtype), pack_rows(new, dtype), *masks, cache)
    return o, cache


def _key_block(S: int, block: int) -> int:
    """The largest whole number of lane tiles <= ``block`` that divides
    ``S`` positions, or all of them."""
    return next((t for t in range(min(block, S) // LANES * LANES, 0, -LANES)
                 if S % t == 0), S)


def _score_kernel(len_ref, _, q_ref, w_ref, k_ref, o_ref, *, block: int):
    b, j = pl.program_id(0), pl.program_id(1)
    L = len_ref[b]

    @pl.when(j * block < L)
    def _():
        s = jnp.dot(q_ref[...], k_ref[...],
                    preferred_element_type=jnp.float32)       # (heads, blk)
        r = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0, keepdims=True)
        col = j * block + lax.broadcasted_iota(jnp.int32, r.shape, 1)
        o_ref[...] = jnp.where(col < L, r, -jnp.inf)

    @pl.when(j * block >= L)
    def _():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)


def index_scores(q, w, keys, length, *, layer, block: int = 2048,
                 interpret: Optional[bool] = None):
    """An indexer's score of every live key of every slot, for the T == 1
    step: ``I[b, s] = sum_j w[b, j] ReLU(q[b, j] . keys[b, :, s])`` for ``s <
    length[b]``, ``-inf`` behind. ``q`` (B, heads, D), ``w`` (B, heads) f32,
    ``keys`` (F, B, D, max_len) positions on the lanes, ``layer`` (traced
    i32) the indexer read. Grid (slots, key blocks); the index map clamps
    the block to the slot's last live one, so keys behind the live length
    are not fetched. Returns (B, max_len) f32."""
    from jax.experimental.pallas import tpu as pltpu

    _refuse_mesh("dsa_index_score")
    B, H, D = q.shape
    S = keys.shape[3]
    blk = _key_block(S, block)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lengths = jnp.minimum(_lengths(length, B), S)

    def key_block(b, j, n, layer):
        last = jnp.maximum(n[b] - 1, 0) // blk
        return (layer[0], b, 0, jnp.minimum(j, last))

    out = pl.pallas_call(
        partial(_score_kernel, block=blk),
        name="dsa_index_score",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // blk),
            in_specs=[pl.BlockSpec((None, H, D), lambda b, j, *_: (b, 0, 0)),
                      pl.BlockSpec((None, H, 1), lambda b, j, *_: (b, 0, 0)),
                      pl.BlockSpec((None, None, D, blk), key_block)],
            out_specs=pl.BlockSpec((None, 1, blk),
                                   lambda b, j, *_: (b, 0, j))),
        out_shape=jax.ShapeDtypeStruct((B, 1, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(keys.dtype), w.astype(jnp.float32)[..., None], keys)
    return out[:, 0]


def _chunk_kernel(nb_ref, layer_ref, q_ref, wk_ref, wv_ref, keep_ref,
                  cache_ref, o_ref, rows, keep, sem, m_ref, l_ref, acc_ref, *,
                  block: int, rank: int, rope: int, split: int, scale: float,
                  dtype):
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    nb, layer = nb_ref[0], layer_ref[0]
    heads, vd = acc_ref.shape[0], acc_ref.shape[-1]

    # no score is under FLOOR and a masked one stands at BIG_NEG: a row with
    # nothing to see yet keeps m = FLOOR and exp(BIG_NEG - FLOOR) is 0, so
    # what is masked adds nothing without a select a score
    m_ref[...] = jnp.full(m_ref.shape, FLOOR, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def copies(j, slot):
        at = pl.ds(j * block, block)
        # a position's row into a buffer of whole (8, 128) tiles: the DMA
        # re-tiles it, where a reshape in VMEM cost a seventh of the kernel
        return (pltpu.make_async_copy(cache_ref.at[layer, b, at, 0],
                                      rows.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(keep_ref.at[b, :, at], keep.at[slot],
                                      sem.at[1, slot]))

    @pl.when(nb > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()

    def body(j, _):
        slot = j % 2

        @pl.when(j + 1 < nb)
        def _():
            for copy in copies(j + 1, 1 - slot):
                copy.start()

        for copy in copies(j, slot):
            copy.wait()
        lat = jnp.concatenate(_halves(rows[slot], dtype), axis=1)
        c = lat[:, :rank].astype(dtype)
        if rope:
            # k_rope in the first lanes of a tile of its own, 0 behind it
            tail = lat[:, rank:rank + LANES].astype(jnp.float32)
            if tail.shape[1] < LANES:
                tail = jnp.pad(tail, ((0, 0), (0, LANES - tail.shape[1])))
            tail = jnp.where(
                lax.broadcasted_iota(jnp.int32, tail.shape, 1) < rope, tail,
                0.0)
        bias = (keep[slot].astype(jnp.float32) - 1.0) * -BIG_NEG
        nt = (((1,), (1,)), ((), ()))
        for h in range(heads):
            k = jnp.dot(c, wk_ref[h], preferred_element_type=jnp.float32)
            if rope:
                k = jnp.concatenate([k[:, :split], k[:, split:] + tail],
                                    axis=1)
            k = k.astype(dtype)
            v = jnp.dot(c, wv_ref[h],
                        preferred_element_type=jnp.float32).astype(dtype)
            s = lax.dot_general(q_ref[h], k, nt,
                                preferred_element_type=jnp.float32)
            s = s * scale + bias
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p.astype(dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return 0

    lax.fori_loop(0, nb, body, 0)
    for h in range(heads):
        o_ref[:, h * vd:(h + 1) * vd] = (
            acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)


def _chunk_heads(H: int, vd: int, heads: int) -> int:
    """Heads a program of the chunk's kernel takes: the most under ``heads``
    that divide ``H`` and whose outputs fill whole lane tiles, or all."""
    return next((n for n in range(min(heads, H), 0, -1)
                 if H % n == 0 and n * vd % LANES == 0), H)


def chunk_kernel_fits(max_len: int, nope: int, rope: int, rank: int,
                      vd: int) -> bool:
    """Whether :func:`sparse_mla_chunk_attention` takes a cache of
    ``max_len`` at these widths: whole key blocks, ``k_rope`` beside what is
    left of ``k_nope``'s last lane tile and, where Mosaic compiles it,
    latents and values of whole lane tiles. (Any number of queries: they are
    padded to the mask's tile.)"""
    return (max_len % LANES == 0 and nope % LANES + rope <= LANES
            and (jax.default_backend() != "tpu"
                 or rank % LANES == vd % LANES == 0))


def sparse_mla_chunk_attention(q_nope, q_rope, wkv_b, cache, keep, n_keys, *,
                               layer, rank: int, scale: float,
                               heads: int = HEADS, block: int = KEY_BLOCK,
                               interpret: Optional[bool] = None):
    """Causal attention of T queries over the selected of the first
    ``n_keys`` positions, as published (``mla.attend_expanded(selected=)``).
    ``q_nope`` (B, T, H, nope), ``q_rope`` (B, T, H, rope); ``wkv_b`` (rank,
    H, nope + v): a head's ``[k_nope | v]`` out of ``c``; ``cache`` (L, B,
    max_len, 1, words) (:func:`pack_rows`), ``layer`` (traced i32) the layer
    read, the chunk's own latents written already; ``keep`` (B, T, max_len)
    int8: 1 where a query attends a key — the selection's mask, which holds
    no position behind the query's own; ``n_keys`` (traced i32): the live
    length, no block behind it is fetched. A query that may see nothing
    gives 0. Returns (B, T, H, v)."""
    from jax.experimental.pallas import tpu as pltpu

    _refuse_mesh("sparse_mla_chunk_attention")
    B, T, H, nope = q_nope.shape
    rope = q_rope.shape[-1]
    S, W = cache.shape[2], cache.shape[-1]
    vd = wkv_b.shape[-1] - nope
    dtype = q_nope.dtype
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    blk = _key_block(S, block)
    hp = _chunk_heads(H, vd, heads)
    # a key's columns: k_nope's whole tiles, then k_rope beside the rest of
    # k_nope in one tile more; the query's and the weight's to match
    # (a latent with no rope part whose k_nope fills whole tiles: no tile
    # more)
    split = nope // LANES * LANES
    bare = not rope and split == nope
    gap = 0 if bare else LANES - rope - (nope - split)

    def columns(a, mid):
        return jnp.concatenate(
            [a[..., :split], mid, a[..., split:],
             jnp.zeros(a.shape[:-1] + (gap,), a.dtype)], axis=-1)

    wk = columns(wkv_b[..., :nope].astype(dtype),
                 jnp.zeros((rank, H, rope), dtype)).transpose(1, 0, 2)
    wv = wkv_b[..., nope:].astype(dtype).transpose(1, 0, 2)
    Tp = -(-T // MASK_TILE) * MASK_TILE
    q = jnp.pad(columns(q_nope, q_rope).transpose(0, 2, 1, 3),
                ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    keep = jnp.pad(keep, ((0, 0), (0, Tp - T), (0, 0)))
    nb = jnp.minimum((jnp.asarray(n_keys, jnp.int32) + blk - 1) // blk,
                     S // blk)
    P = split if bare else split + LANES
    out = pl.pallas_call(
        partial(_chunk_kernel, block=blk, rank=rank, rope=rope, split=split,
                scale=scale, dtype=dtype),
        name="sparse_mla_chunk_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hp),
            in_specs=[pl.BlockSpec((None, hp, Tp, P),
                                   lambda b, g, *_: (b, g, 0, 0)),
                      pl.BlockSpec((hp, rank, P), lambda b, g, *_: (g, 0, 0)),
                      pl.BlockSpec((hp, rank, vd),
                                   lambda b, g, *_: (g, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, Tp, hp * vd),
                                   lambda b, g, *_: (b, 0, g)),
            scratch_shapes=[pltpu.VMEM((2, blk, W), cache.dtype),
                            pltpu.VMEM((2, Tp, blk), jnp.int8),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((hp, Tp, 1), jnp.float32),
                            pltpu.VMEM((hp, Tp, 1), jnp.float32),
                            pltpu.VMEM((hp, Tp, vd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Tp, H * vd), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(nb.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1), q, wk, wv,
      keep.astype(jnp.int8), cache)
    return out[:, :T].reshape(B, T, H, vd)


def attend_selected(q, lat, idx, length, *, rank: int, scale: float,
                    n=None):
    """The same read in plain ``jax.numpy``: ``q`` (B, H, rank + rope) over
    ``lat`` (B, max_len, rank + rope), the first ``min(length, K)`` of
    ``idx`` (B, K) attended (or the first ``n`` (B,)). What a step traced
    without the kernels pays,
    and what the kernel's test holds it to."""
    B, K = idx.shape
    rows = jnp.take_along_axis(lat, jnp.maximum(idx, 0)[..., None], axis=1)
    s = einsum_f32("bhd,bkd->bhk", q, rows.astype(q.dtype)) * scale
    n = jnp.minimum(_lengths(length, B), K) if n is None else n
    keep = jnp.arange(K, dtype=jnp.int32)[None, None] < n[:, None, None]
    s = jnp.where(keep, s, BIG_NEG)
    p = jnp.where(keep, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhk,bkr->bhr", p.astype(q.dtype),
                      rows[..., :rank].astype(q.dtype))

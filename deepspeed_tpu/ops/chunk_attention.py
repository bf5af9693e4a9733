"""Pallas chunk attention: T > 1 queries that advance together against the
K/V planes, for the prefill chunks of a kind that keeps whole keys and values.

``windowed.attend_blocks`` walks the live key blocks in plain ``jnp``: inside
a chunk's program a block's float32 scores ``(KV, G, T, block)`` (64 MB at
Solar-Open2's shape) stand in HBM between the fusions that write and read
them, beside 2 MB of K and V, so the walk runs at the HBM's speed on bytes
nothing needs, a seventh of the MXU's (alone in a program XLA finds them room
in VMEM and the same walk is four times as fast: PERF.md §6 "PR 58").
:func:`gqa_chunk_attention` is the same softmax over the same keys with
scores, probabilities, the row statistics and the accumulator in VMEM.

- the planes are ``decode_attention``'s: ``(A, B, KV, hd, max_len)``,
  positions on the lanes, read where they lie by a scalar-prefetched layer.
  ``s = q @ k`` takes ``k`` as stored, ``(hd, block)``; ``p . v`` contracts
  the lanes of both (the MXU's NT form), ``p`` in the planes' dtype: no
  transpose, no repeated K/V.
- grid (B, KV, row tiles): a program owns a KV head's ``G x T`` query rows
  (row ``g T + t``: the GQA mapping is a reshape, as the step's kernel has
  it), or :data:`ROWS` of them, and walks the LIVE key blocks only,
  ``ceil(n_keys / block)`` turns, the next block's K and V in flight
  (``make_async_copy`` into two buffers each) while this one is multiplied.
  A dead block is neither fetched nor multiplied.
- a turn takes the program's rows a TILE at a time (:data:`TILE` rows under
  one block's K and V), so K and V come in once a KV head, not once a tile.
  Keys and rows of 1024 by the sweep (PERF.md §6 "PR 58"): the row
  statistics ``m``, ``l`` and the accumulator's rescale are ``(TILE, 1)``
  columns, a vreg a sublane tile whatever the keys a turn — at 512 keys as
  dear as a third of the score tile's own passes (0.105 ms a block of 512
  keys at the cell's shape), at 1024 paid half as often (0.060, 73% of the
  MXU's peak); at 2048 the tile outgrows what the compiler keeps near.
- the causal compare runs only where the diagonal crosses: every block wholly
  at or under the chunk's first position (``(j + 1) block <= start + 1``)
  is multiplied bare (PR 43's rule for the flash kernels). A masked score
  stands at ``BIG_NEG``; every row sees key 0, so its running maximum is a
  real score from its first turn on and ``exp(BIG_NEG - m)`` is 0: what is
  masked adds nothing without a second select.
- float32 scores, scale, maximum, sum and accumulator; ``p`` rounded to the
  values' dtype for ``p . v``: ``attend_blocks``' own precisions.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .decode_attention import BIG_NEG, LANES
from .sparse_mla_attention import _key_block

# the sweep is PERF.md §6 "PR 58" (examples/gqa_chunk_attention_microbench.py)
KEY_BLOCK = 1024     # keys a turn (or the most lane tiles that divide max_len)
TILE = 1024          # query rows a product
ROWS = 4096          # query rows a program (a KV head's at the cell's shape)
VMEM_LIMIT = 64 * 2 ** 20   # of a core's 128 MiB; a program holds ~25


def row_tile(G: int, T: int, tile: int = TILE) -> Optional[int]:
    """The rows a product takes of a KV head's ``G x T`` (row ``g T + t``):
    the most under ``tile`` that are whole heads' worth of the T queries or
    divide them (so a tile's positions are one run or whole runs) and that
    Mosaic slices at a traced offset (whole 16-row tiles of bf16); all the
    rows where they fit one tile. None where nothing tiles them."""
    if G * T <= tile:
        return G * T
    fits = [r for r in range(16, tile + 1, 16)
            if (G * T) % r == 0 and (T % r == 0 or r % T == 0)]
    return max(fits, default=None)


def chunk_kernel_fits(T: int, G: int, max_len: int, hd: int, vd: int) -> bool:
    """Whether :func:`gqa_chunk_attention` takes T queries a slot of G heads
    a KV head over planes of ``max_len``: whole lane blocks of positions,
    rows that tile (:func:`row_tile`) and, where Mosaic compiles it, keys
    and values of whole 128-lane tiles (MiMo's keys of 192 are not: a
    product 192 deep wants padding to 256, another kernel shape)."""
    return (max_len % LANES == 0 and T % 8 == 0
            and row_tile(G, T) is not None
            and (jax.default_backend() != "tpu"
                 or hd % LANES == vd % LANES == 0))


def _kernel(start_ref, nb_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, block: int, tile: int,
            T: int, scale: float):
    from jax.experimental.pallas import tpu as pltpu

    b, kv, r = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start, nb, layer = start_ref[0], nb_ref[0], layer_ref[0]
    rows = q_ref.shape[0]
    tiles = rows // tile

    m_ref[...] = jnp.full(m_ref.shape, BIG_NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def copies(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, kv, :, at],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, b, kv, :, at],
                                      vbuf.at[slot], sem.at[1, slot]))

    @pl.when(nb > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()

    def at(i):
        """Tile ``i``'s rows of the program's."""
        return pl.ds(pl.multiple_of(i * tile, tile), tile) if tiles > 1 \
            else slice(None)

    def each_tile(fn):
        if tiles > 1:
            lax.fori_loop(0, tiles, lambda i, c: fn(i), None)
        else:
            fn(0)

    def offsets(i):
        """(tile, 1): the chunk's position ``t`` of every row of tile ``i``
        (row ``g T + t`` of the KV head's)."""
        row = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        if tile <= T:                      # one run of positions
            return (r * rows + i * tile) % T + row
        t = row                            # whole runs: row % T by compares
        for c in range(1, tile // T):
            t = jnp.where(row >= c * T, row - c * T, t)
        return t

    def turn(j, masked: bool):
        slot = j % 2

        @pl.when(j + 1 < nb)
        def _():
            for copy in copies(j + 1, 1 - slot):
                copy.start()

        for copy in copies(j, slot):
            copy.wait()
        k, v = kbuf[slot], vbuf[slot]
        if masked:
            col = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)

        def one(i):
            s = jnp.dot(q_ref[at(i), :], k,
                        preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(col <= start + offsets(i), s, BIG_NEG)
            m = m_ref[at(i)]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_ref[at(i)] = l_ref[at(i)] * corr \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[at(i)] = acc_ref[at(i)] * corr + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[at(i)] = m_new

        each_tile(one)

    # the blocks wholly at or under the chunk's first position, bare; then
    # the ones the diagonal crosses
    bare = jnp.minimum((start + 1) // block, nb)
    lax.fori_loop(0, bare, lambda j, c: turn(j, False), None)
    lax.fori_loop(bare, nb, lambda j, c: turn(j, True), None)

    def write(i):
        o_ref[at(i), :] = (acc_ref[at(i)]
                           / jnp.maximum(l_ref[at(i)], 1e-30)).astype(
                               o_ref.dtype)

    each_tile(write)


def gqa_chunk_attention(q, ck, cv, start, *, layer, block: int = KEY_BLOCK,
                        tile: int = TILE, rows: int = ROWS,
                        interpret: Optional[bool] = None,
                        name: str = "gqa_chunk_attention"):
    """Causal attention of T queries at positions ``start .. start + T - 1``
    (``start`` a traced i32 scalar: the rows advance together) over the keys
    ``0 .. start + T - 1`` of the planes ``ck`` ``(A, B, KV, hd, max_len)`` /
    ``cv`` ``(A, B, KV, vd, max_len)``, ``layer`` (traced i32) the one read;
    the chunk's own K/V stand in them already. ``q`` (B, T, H, hd). Query
    ``t`` sees keys ``0 .. start + t``: ``windowed.attend_blocks``' softmax
    (float32 scores and statistics, ``p`` in the planes' dtype for ``p . v``)
    with nothing of a block's scores in HBM. No block behind ``start + T`` is
    fetched. ``name``: the ``pallas_call``'s, which a trace tells kernels
    apart by. Returns (B, T, H, vd)."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, hd = q.shape
    KV, S, vd = ck.shape[2], ck.shape[4], cv.shape[3]
    G = H // KV
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    blk = _key_block(S, block)
    tl = row_tile(G, T, tile)
    if tl is None:
        raise ValueError(f"no row tile under {tile} for {G} heads a KV head "
                         f"of {T} queries (chunk_kernel_fits)")
    # a program's rows: whole tiles, at most ``rows`` and at least one tile
    per = next(n * tl for n in range(max(rows // tl, 1), 0, -1)
               if (G * T) % (n * tl) == 0)
    start = jnp.asarray(start, jnp.int32)
    nb = jnp.minimum((start + T + blk - 1) // blk, S // blk)
    # (B, T, H, hd) -> (B, KV, G T, hd): a KV head's rows, head-major
    qs = q.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4).reshape(
        B, KV, G * T, hd).astype(ck.dtype)

    def rows_spec(width):
        return pl.BlockSpec((None, None, per, width),
                            lambda b, h, r, *_: (b, h, r, 0))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        partial(_kernel, block=blk, tile=tl, T=T, scale=1.0 / math.sqrt(hd)),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV, G * T // per),
            in_specs=[rows_spec(hd), in_hbm, in_hbm],
            out_specs=rows_spec(vd),
            scratch_shapes=[pltpu.VMEM((2, hd, blk), ck.dtype),
                            pltpu.VMEM((2, vd, blk), cv.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((per, 1), jnp.float32),
                            pltpu.VMEM((per, 1), jnp.float32),
                            pltpu.VMEM((per, vd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G * T, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(start.reshape(1), nb.reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), qs, ck, cv)
    return out.reshape(B, KV, G, T, vd).transpose(0, 3, 1, 2, 4).reshape(
        B, T, H, vd)

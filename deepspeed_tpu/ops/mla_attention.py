"""Pallas kernels of the latent (MLA) decode step: one absorbed query per
head against a slot's cached latents, and the in-place append.

The cache is ``(L, B, rank + rope, max_len)``: per position the normed
``c`` and the roped ``k_rope`` that ALL heads share (``inference/kinds/latent.py``
``LatentCache``), positions on the lanes.

- ``mla_decode_attention``: in ``ops/decode_attention.py`` every KV head
  has K/V of its own and a product of its own. Here the heads share the
  latents: a program takes a slot's H query rows at once: ``s = q (H,
  rank+rope) @ lat (rank+rope, lanes)``, ``o_lat += p (H, lanes) . c
  (rank, lanes)^T`` — the
  heads are the matmul's rows, and no single row is broadcast over
  sublanes. Grid (slots,): one program a slot, and the walk over the slot's
  latents is the kernel's own loop, bounded by the live length. The cache
  stays in HBM (``pl.ANY``); the program copies ``ceil(length / 128)``
  blocks of 128 positions into two VMEM buffers with ``make_async_copy``,
  a TURN of W adjacent blocks at a time (``turn_blocks``: what fits
  ``_TURN_BYTES``, 8 of 576 bf16 values; one copy a LIVE block, fewer in
  the slot's last turn), the next turn in flight behind this turn's two
  products, and after the slot's last turn the first turn of the NEXT slot
  (which buffer, and whether those copies were started, ride in SMEM
  scratch: the grid runs in order). The online softmax's state is the
  loop's carry. Positions behind the live length are neither fetched nor
  waited for, and a slot at length 0 costs a third of a microsecond: the
  call's time follows the live latents, whatever the cache's length (a
  grid of ``max_len / 512`` steps a slot paid 0.13 us a step behind the
  live length, two fifths of the call at 160 slots of 24 576: PERF.md §6
  "PR 63").
- ``latent_append``: the step's new latents at position ``length - 1`` of
  every slot, a read-modify-write of the one 128-lane tile that holds it
  (``decode_attention.append_in_place``: the latent buffer is a cache of
  one head of ``rank + rope`` values).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .decode_attention import BIG_NEG, LANES, append_in_place

# a loop turn of mla_decode_attention takes as many blocks of 128 positions
# as fit this. A turn costs ~0.4 us whatever its width and 0.15 us a block
# (the waits, two products, the carry), a block of 576 bf16 values 0.18 us
# of HBM: a narrow turn waits for its own chain (34% of the HBM's peak at
# one block, 68% at 4), a wide one multiplies half a turn of lanes behind
# the live length a slot (``turn_blocks``; the sweep is PERF.md §6 "PR 63")
_TURN_BYTES = 1152 * 1024


def _lengths(length, B):
    return jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))


def _refuse_mesh(what: str) -> None:
    from ..platform.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and not mesh.empty and mesh.size > 1:
        raise NotImplementedError(
            f"{what} is a Mosaic kernel with no shard_map wrapper yet: the "
            "latent decode step runs on one device")


def turn_blocks(width: int, max_len: int, dtype) -> int:
    """The live blocks of 128 positions a loop turn of
    ``mla_decode_attention`` takes (W): as many ``(width, 128)`` blocks of
    the cache's dtype as fit ``_TURN_BYTES``; at least 1, at most the
    cache's own. From the shapes, never from the batch: a slot's bits do not
    depend on its neighbours."""
    one = width * LANES * jnp.dtype(dtype).itemsize
    return max(1, min(_TURN_BYTES // one, max_len // LANES))


def _mla_kernel(len_ref, layer_ref, q_ref, c_hbm, o_ref, buf, sem, ahead, *,
                width: int, rank: int, scale: float):
    """One program: a slot's H query rows (``q_ref`` (H, rank + rope)) over
    that slot's live blocks of 128 positions, copied out of the cache in HBM
    (``c_hbm`` (L, B, rank + rope, max_len)) by the kernel itself, ``width``
    (W) adjacent live blocks a loop turn into one of the two buffers ``buf``
    (2, rank + rope, W 128): ``ops/decode_attention.py`` ``_decode_kernel``'s
    scheme without heads of its own, an append or a ring.

    A turn is W copies of one block each into adjacent 128-lane ranges of a
    buffer, fewer where the slot has fewer blocks left (never a block behind
    the live length), and ONE chain over all its lanes: a product, a max, an
    exp, a sum, a second product. Turns count from the slot's first block,
    so which positions share a turn follows from the slot's own length. The
    lanes of a turn's blocks that were not fetched hold what an earlier turn
    left there: their scores are masked, but ``p = 0`` against a NaN among
    stale values is NaN in the MXU, so the first program zeroes the buffers
    (scratch, which nothing outside the kernel can write), and everything
    copied in afterwards is a live block of the cache, as finite as the
    cache's own values."""
    from jax.experimental.pallas import tpu as pltpu

    b, n_slots = pl.program_id(0), pl.num_programs(0)
    S = c_hbm.shape[3]
    W = width

    def live_blocks(n):
        # a caller's length may lie past the cache; a slot that is not
        # running stands at 0: no fetch, no products
        return (jnp.minimum(n, S) + LANES - 1) // LANES

    L = jnp.minimum(len_ref[b], S)
    nb = live_blocks(len_ref[b])

    def copy(half, slot, j, i):
        """Block ``i`` of the turn that starts at block ``j``."""
        at = pl.ds(pl.multiple_of((j + i) * LANES, LANES), LANES)
        return pltpu.make_async_copy(
            c_hbm.at[layer_ref[0], slot, :, at],
            buf.at[half, :, pl.ds(i * LANES, LANES)], sem.at[half])

    def over_turn(half, slot, j, stop, act):
        """``act`` (a copy's start, or its wait) on the copy of every block
        ``j + i`` before ``stop`` of the turn that starts at ``j`` (its
        first is one: there is no empty turn)."""
        def block(i):
            act(copy(half, slot, j, i))

        block(0)
        for i in range(1, W):
            pl.when(j + i < stop)(partial(block, i))

    def fetch(half, slot, j, stop):
        over_turn(half, slot, j, stop, lambda c: c.start())

    # ``ahead``: which buffer this program's first turn goes to, and whether
    # the program before already started its copies (as many as the turn
    # has: both reckon them from the slot's length); the grid runs in order
    @pl.when(b == 0)
    def _():
        ahead[0] = 0
        ahead[1] = 0
        if W > 1:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)

    first = ahead[0]

    @pl.when((nb > 0) & (ahead[1] == 0))
    def _():
        fetch(first, b, 0, nb)

    # the program after this one, and whether it has a block to fetch
    nb_next = live_blocks(len_ref[jnp.minimum(b + 1, n_slots - 1)])
    next_live = (b + 1 < n_slots) & (nb_next > 0)

    q = q_ref[...]                                        # (H, rank + rope)
    turns = (nb + W - 1) // W

    def body(u, carry):
        m, l, acc = carry
        half = (first + u) % 2
        j = u * W                                    # the turn's first block

        # behind this turn's products: the slot's next turn, or after its
        # last the first turn of the next program
        @pl.when(u + 1 < turns)
        def _():
            fetch(1 - half, b, j + W, nb)

        @pl.when((u + 1 == turns) & next_live)
        def _():
            fetch(1 - half, b + 1, 0, nb_next)

        over_turn(half, b, j, nb, lambda c: c.wait())
        lat = buf[half]                               # (rank + rope, W 128)
        s = jnp.dot(q, lat, preferred_element_type=jnp.float32) * scale
        col = j * LANES + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = col < L
        s = jnp.where(keep, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(lat.dtype), lat[:rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # p . c^T
        return m_new, l, acc

    H = q.shape[0]
    _, l, acc = jax.lax.fori_loop(0, turns, body, (
        jnp.full((H, 1), BIG_NEG, jnp.float32),
        jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, rank), jnp.float32)))
    ahead[0] = (first + turns) % 2
    ahead[1] = ((nb > 0) & next_live).astype(jnp.int32)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def mla_decode_attention(q, cache, length, *, layer, rank: int, scale: float,
                         interpret: Optional[bool] = None):
    """``q`` (B, H, rank + rope): the absorbed queries, in the order the
    latents lie; ``cache`` (L, B, rank + rope, max_len), ``layer`` (traced
    i32) the layer read; ``length`` scalar or (B,): positions < length are
    attended. Returns ``o_lat`` (B, H, rank) = softmax(q·lat·scale) · c; a
    slot at length 0 gets zeros. A slot's result depends on that slot's row
    and length alone."""
    from jax.experimental.pallas import tpu as pltpu

    _refuse_mesh("mla_decode_attention")
    B, H, D = q.shape
    S = cache.shape[3]
    if S % LANES:
        raise ValueError(f"cache length {S} not a multiple of {LANES}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    W = turn_blocks(D, S, cache.dtype)
    lengths = _lengths(length, B)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    return pl.pallas_call(
        partial(_mla_kernel, width=W, rank=rank, scale=scale),
        name="mla_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, rank), lambda b, *_: (b, 0, 0)),
            # two buffers of a turn's blocks side by side on the lanes, a
            # semaphore a buffer (a turn's copies count on one), ``ahead``
            scratch_shapes=[pltpu.VMEM((2, D, W * LANES), cache.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        # a program starts the next one's first copies: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths, layer, q.astype(cache.dtype), cache)


def latent_append(cache, new, length, *, layer,
                  interpret: Optional[bool] = None, keep_idle: bool = False):
    """Write this step's latents ``new`` (B, rank + rope) into layer
    ``layer`` of ``cache`` (L, B, rank + rope, max_len) at position
    ``length - 1`` of every slot, in place (output aliased to the input,
    every other position bit-untouched). ``keep_idle``: a slot at length 0
    keeps its position 0 too (``append_in_place``)."""
    _refuse_mesh("latent_append")
    B = new.shape[0]
    if cache.shape[3] % LANES:
        raise ValueError(f"cache length {cache.shape[3]} not a multiple of "
                         f"{LANES}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (out,) = append_in_place(
        (cache[:, :, None],), (new[:, None, None],), _lengths(length, B),
        jnp.asarray(layer, jnp.int32).reshape(1), name="mla_cache_append",
        interpret=interpret, keep_idle=keep_idle)
    return out[:, :, 0]

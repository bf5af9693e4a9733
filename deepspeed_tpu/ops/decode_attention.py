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
- the kernels take the WHOLE cache and a scalar-prefetched layer index:
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
  of V into two VMEM buffers each with ``make_async_copy``, the next turn
  in flight behind the current one's products, and after a slot's last
  turn the first turn of the NEXT program (which buffer, and whether
  those copies were started, ride in SMEM scratch: the grid runs in order).
  Positions behind the live length are neither fetched nor multiplied.
- a loop TURN is ``W`` consecutive live blocks (``blocks_per_turn``): as
  many blocks of the program's heads as fit ``_ATTEND_TURN_BYTES``, from
  ``(KV, hd, vd, max_len, dtype)`` like ``hb``. A turn is a chain — two
  waits, an 8-row product, max, exp, sum, a second product, the carry —
  whose latency the next turn's bytes have to hide. ``hb`` fills the turn
  by taking more heads: 20 heads of 64 are 320 KB of K a block, 16 of 128
  are 512 KB, and W is 1. 2 KV heads of 128 (compressed attention, a GQA
  layer of a hybrid trunk) are 64 KB a block, which the chip moves in a
  third of the chain's time: there W is 8, the buffers are ``W block``
  lanes wide, a turn starts one copy a LIVE block into adjacent lane
  ranges (fewer where the slot has fewer left: nothing behind the last
  live block is fetched) and runs its products once over all of them. At
  W = 1 the kernel is, op for op, the one it was before turns had a width.
- the step APPENDS INSIDE the same kernel: called with the step's new K/V
  (``k=``, ``v=``) the caches are aliased outputs and ``length`` is the
  length after the append. The new position ``length - 1`` lies in the
  slot's last live block, which the kernel fetches anyway: once that block
  has landed, the program that owns it (never the one that fetched it
  ahead) puts the slot's new column on lane ``(length - 1) % 128`` of both
  buffers (of that block's lane range in a wider turn), takes the products
  from the patched buffers and copies that block back to the same block of
  the cache, once. A column alone cannot be
  copied: every (sublanes x 128) HBM tile of the block holds part of it
  and Mosaic refuses a slice narrower than the tile ("Slice shape along
  dimension 3 must be aligned to tiling (128), but is 1"); so a step
  writes a 128-lane block a slot, and reads nothing twice. Called without
  new values (the paged view's slab, a caller that has appended) the same
  body only reads. An XLA-level update of one position lets the compiler
  pick a layout FOR THE UPDATE and convert the whole cache to it; inside
  an aliased kernel nothing can.
- the DEFERRED TAIL (``tail=``; what ``inference/kinds/dense.py`` keeps
  beside K and V): a block of 128 written back to append one position is
  128 times the new bytes, a quarter to a third of all the kernel moved at
  GPT-2's and Ouro's shapes. A column cannot be written, a ROW can: the
  tail is ``(L, B, KV, T, hd + vd)``, T positions ON THE SUBLANES — T one
  sublane tile of the dtype (``tail_rows``: 16 of bf16), K beside V on the
  lanes (128 or 256 wide: no lane is padding) — and position ``p`` of a
  slot stands on row ``p % T`` of the slot's tile until its group of T is
  complete. A step fetches the tile behind the slot's first turn (82 KB at
  GPT-2's shape; the program before starts it with the blocks it fetches
  ahead), puts the new row on it and writes the tile back (a whole tile at
  a tile-aligned offset), and turns the tile's rows into the columns of
  their group in the last live block, in VMEM: the tile lands on rows
  ``[(L - 1) % 128 // T * T, + T)`` of a zeroed ``(hb, 128, hd + vd)``
  buffer, and an identity times that buffer in the kernel's own NT form
  (one non-zero term a sum: exact) is ``(hb, hd + vd, 128)`` with the
  group's columns in their lanes — no roll, no transpose op. That product
  reads no block, so it runs in front of the last turn's wait, behind the
  copies in flight. The products over the patched block are the ones
  without a tail: same turns, masks and carry, bit-equal output. The block
  goes back only when ``L % T == 0``: 2 T + 128 / T positions moved a
  position appended (40 at T = 16) where it was 128. Between steps the
  blocks lack the current group: whatever else reads the planes settles
  the tail into them first (``Dense.settled``). With ``tail=None`` every
  expression is the one it was (the other kinds' write-back is under 1%
  of their step).
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
- ``append_in_place``: an append alone, as a read-modify-write of the one
  128-lane tile that holds position ``length - 1`` with the cache aliased
  to the output: what the K/V step did in a kernel of its own
  (``cache_append``) until the attention kernel took it over; kept for the
  latent cache (``ops/mla_attention.py``), whose attention is another
  kernel and whose append is a twentieth of the bytes.
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
# append_in_place's tile is (kv-block, hd, 128): the most KV heads a program
# takes, so in, out and their double buffers stay far inside scoped VMEM
_APPEND_TILE_BYTES = 512 * 1024
# decode_attention's K (and V) block is (kv-block, hd, block): two buffers
# of each, the scores and the accumulator stay under a third of the 16 MiB
# of scoped VMEM
_ATTEND_BLOCK_BYTES = 1024 * 1024
# a loop turn of decode_attention takes as many blocks as fit this (of its
# wider operand): where the heads are few a block is small, and a turn's
# chain of waits and products costs more than the block's bytes
# (``blocks_per_turn``; the sweep is PERF.md §6 "PR 46")
_ATTEND_TURN_BYTES = 512 * 1024


def _with_column(old_ref, new_ref, b, r):
    """The tile in ``old_ref`` (.., 128 lanes) with lane ``r`` taken from
    ``new_ref``, whose values lie slots-on-lanes: slot ``b``'s column turns
    onto lane ``r`` (Mosaic rotates 32-bit values by a traced amount, so
    through float32)."""
    from jax.experimental.pallas import tpu as pltpu

    col = jax.lax.broadcasted_iota(jnp.int32, old_ref.shape, 2)
    turn = (r - b % LANES) % LANES
    new = pltpu.roll(new_ref[...].astype(jnp.float32), turn, 2)
    return jnp.where(col == r, new.astype(old_ref.dtype), old_ref[...])


def _decode_kernel(*refs, block: int, width: int, scale: float, alibi: bool,
                   group: int, append: bool, window: int = 0,
                   sink: bool = False, tailed: bool = False):
    """One program: a slot's ``hb`` KV heads (``q_ref`` (hb, rows, hd), the
    group's query rows padded to the 8 sublanes) over that slot's live
    blocks, copied out of the cache in HBM by the kernel itself, ``width``
    (W) consecutive live blocks a loop turn. With ``append`` the step's new
    K/V (``new_k`` / ``new_v`` (hb, hd, 128), slots on the lanes) go onto
    lane ``(L - 1) % block`` of the slot's last live block once it has
    landed in VMEM; the products read the patched buffers and one copy
    takes that block back to the aliased cache.

    A turn is W copies of one block each into adjacent ``block``-lane
    ranges of a buffer ``(hb, hd, W block)``, fewer where the slot has fewer
    blocks left (never a block behind the live length), and ONE chain over
    all its lanes: a product, a max, an exp, a sum, a second product. Turns
    count from the slot's first live block, so which positions share a
    turn follows from the slot's own length. The lanes of a turn's blocks
    that were not fetched hold what an earlier turn left there: their
    scores are masked (``keep``), a NaN among the stale K with them, but
    ``p = 0`` against a NaN or Inf among the stale V is NaN in the MXU. The
    V buffers are scratch, which nothing outside the kernel can write: the
    first program zeroes them, and everything copied in afterwards is a
    live block of the cache, as finite as the cache's own values. At one
    block a turn no lane is stale and nothing is zeroed: every expression
    that ``W`` enters is then the one it was before turns had a width
    (``turns``, ``part``, ``j``, ``end``), and the program is that program.

    ``window`` > 0: the cache is a RING, position ``p`` in block
    ``(p // block) % (S // block)``; the slot's live positions are
    ``L - window .. L - 1`` and only the blocks that hold them are fetched
    (positions outside the window are masked), each folded into the ring
    on its own. ``sink``: the running max and sum start at ``(sink_h, 1)``
    in place of ``(-inf, 0)``: one more column of the softmax that carries
    no value. K and V may differ in width (the accumulator and the output
    have V's).

    ``tailed`` (with ``append``): the slot's newest positions stand in its
    TAIL TILE ``t_hbm[layer, slot]`` (hb, T, hd + vd), position ``p`` on row
    ``p % T``, K beside V on the lanes, until their group of T is complete
    (the module's layout notes). The tile comes in behind the slot's first
    turn (``t_buf``, one of two: the program before fetches it ahead with
    the blocks, and with it the step's new rows ``new_hbm[slot, g]``, a
    head a sublane, into ``n_buf``: a copy of the kernel's own, so a slot
    that is not running costs none); in the last turn each head's new row
    goes onto row ``(L - 1) % T`` and the tile goes back, whole.
    Its rows are then turned into columns: the tile lands on rows ``[(L -
    1) % block // T * T, + T)`` of ``z_buf`` (hb, block, hd + vd), zero
    everywhere else, and an identity times ``z_buf`` in the NT form (one
    non-zero term a sum: exact) is ``(hb, hd + vd, block)`` with the
    group's columns in their lanes and zeros beside them; those T lanes
    replace the block's, in both buffers, and the products run as ever. The
    rows behind the live length land on lanes the mask drops. The block
    goes back to the cache only when ``L % T == 0``."""
    from jax.experimental.pallas import tpu as pltpu

    len_ref, layer_ref, *refs = refs
    slopes_ref = refs.pop(0) if alibi else None
    sink_ref = refs.pop(0) if sink else None
    if tailed:
        (q_ref, new_hbm, k_hbm, v_hbm, t_hbm, o_ref, k_out, v_out, t_out,
         k_buf, v_buf, t_buf, n_buf, z_buf, c_buf, sem, ahead) = refs
        T = t_buf.shape[2]
    elif append:
        (q_ref, new_k, new_v, k_hbm, v_hbm, o_ref, k_out, v_out,
         k_buf, v_buf, sem, ahead) = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, ahead = refs
    b, g = pl.program_id(0), pl.program_id(1)
    n_slots, n_groups = pl.num_programs(0), pl.num_programs(1)
    hb, rows, _ = q_ref.shape
    S = k_hbm.shape[4]
    W = width
    ring = S // block            # a window's cache: blocks that come round

    def live(n):
        """Of a slot at length ``n``: (the length the kernel works with,
        its first live block, its live blocks). Block numbers count on
        through a ring; ``at`` folds them."""
        if window:
            return n, jnp.maximum(n - window, 0) // block, \
                (n + block - 1) // block - jnp.maximum(n - window, 0) // block
        # a caller's length may lie past the cache (a scalar that counts
        # on): never past it (the append clamps the same way). A serving
        # slot that is not running stands at 0: no fetch, no products, no
        # write-back
        n = jnp.minimum(n, S)
        return n, 0, (n + block - 1) // block

    def turns(n):
        """The loop turns ``n`` live blocks take."""
        return n if W == 1 else (n + W - 1) // W

    L, j0, nb = live(len_ref[b])                         # only live blocks
    # one past the slot's last live block: what bounds a turn's copies
    # (a turn of one block never asks)
    end = None if W == 1 else j0 + nb

    def at(slot, heads, j):
        if window:
            j = j % ring
        return (layer_ref[0], slot, pl.ds(heads * hb, hb), slice(None),
                pl.ds(pl.multiple_of(j * block, block), block))

    def part(c_buf, buf, i):
        """The lanes of buffer ``buf`` that a turn's block ``i`` lands on."""
        if W == 1:
            return c_buf.at[buf]
        lane = i * block if isinstance(i, int) else \
            pl.multiple_of(i * block, block)
        return c_buf.at[buf, :, :, pl.ds(lane, block)]

    def plus(j, i):
        """``j + i``, and no op at all for a turn's first block."""
        return j if isinstance(i, int) and i == 0 else j + i

    def copies(buf, slot, heads, j, i):
        """Block ``i`` of the turn that starts at block ``j``."""
        j = plus(j, i)
        return (pltpu.make_async_copy(k_hbm.at[at(slot, heads, j)],
                                      part(k_buf, buf, i), sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at(slot, heads, j)],
                                      part(v_buf, buf, i), sem.at[1, buf]))

    def writes(buf, i, j):
        """The copies of this program's patched block ``j``, block ``i`` of
        its turn, back to the cache (to wait on one only the semaphore and
        the size matter)."""
        return (pltpu.make_async_copy(part(k_buf, buf, i),
                                      k_out.at[at(b, g, j)], sem.at[2, 0]),
                pltpu.make_async_copy(part(v_buf, buf, i),
                                      v_out.at[at(b, g, j)], sem.at[2, 1]))

    def patch(buf, i):
        """Lane ``(L - 1) % block`` of the turn's block ``i`` takes the
        slot's new column, in both buffers."""
        for new_ref, c_buf in ((new_k, k_buf), (new_v, v_buf)):
            tile = part(c_buf, buf, i)
            tile[...] = _with_column(tile, new_ref, b, (L - 1) % block)

    def tile_in(t, slot, heads):
        """The copies of a program's tail tile into ``t_buf[t]`` and of its
        new row into ``n_buf[t]`` (they count on one semaphore)."""
        return (pltpu.make_async_copy(
            t_hbm.at[layer_ref[0], slot, pl.ds(heads * hb, hb)],
            t_buf.at[t], sem.at[3, t]),
                pltpu.make_async_copy(
            new_hbm.at[slot, heads], n_buf.at[t], sem.at[3, t]))

    def tile_out(t):
        """This program's tile back to the cache (to wait on one only the
        semaphore and the size matter)."""
        return pltpu.make_async_copy(
            t_buf.at[t], t_out.at[layer_ref[0], b, pl.ds(g * hb, hb)],
            sem.at[4, 0])

    def tail_columns(t):
        """The step's new row into the tile ``t_buf[t]``, the tile on its
        way back, and its rows as columns in ``c_buf`` (hb, hd + vd,
        block): the group's in their lanes, zeros beside them. Nothing here
        reads a block: it runs in front of the last turn's wait, behind
        that turn's copies."""
        for copy in tile_in(t, b, g):
            copy.wait()
        row = jax.lax.broadcasted_iota(jnp.int32, t_buf.shape[1:], 1)
        # (a head's row off the sublanes: through float32, exactly)
        new = n_buf[t, :hb].astype(jnp.float32)[:, None, :].astype(
            t_buf.dtype)
        tile = jnp.where(row == (L - 1) % T, new, t_buf[t])
        t_buf[t] = tile
        tile_out(t).start()
        off = pl.multiple_of((L - 1) % block // T * T, T)
        z_buf[:, pl.ds(off, T), :] = tile
        C = z_buf.shape[2]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
        c_buf[...] = jax.lax.dot_general(                # (hb, C, block)
            jnp.broadcast_to(eye.astype(z_buf.dtype), (hb, C, C)),
            z_buf[...], (((2,), (2,)), ((0,), (0,))),
            precision=(jax.lax.Precision.HIGHEST
                       if z_buf.dtype == jnp.float32 else None),
            preferred_element_type=jnp.float32).astype(c_buf.dtype)
        z_buf[:, pl.ds(off, T), :] = jnp.zeros_like(tile)

    def from_tail(buf, i):
        """The group's columns out of ``c_buf`` onto their T lanes of the
        turn's block ``i``, in both buffers."""
        off = (L - 1) % block // T * T
        hd = k_buf.shape[2]
        for kv_buf, rows in ((k_buf, slice(0, hd)), (v_buf, slice(hd, None))):
            blk = part(kv_buf, buf, i)
            lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 2)
            blk[...] = jnp.where((lane >= off) & (lane < off + T),
                                 c_buf[:, rows, :], blk[...])

    def over_turn(buf, slot, heads, j, stop, act):
        """``act`` (a copy's start, or its wait) on the copies of every
        block ``j + i`` before ``stop`` of the turn that starts at ``j``
        (its first is one: there is no empty turn)."""
        def block(i):
            for copy in copies(buf, slot, heads, j, i):
                act(copy)

        block(0)
        for i in range(1, W):
            pl.when(j + i < stop)(partial(block, i))

    def fetch(buf, slot, heads, j, stop):
        over_turn(buf, slot, heads, j, stop, lambda copy: copy.start())

    # ``ahead``: which buffer this program's first turn goes to, whether
    # the program before already started its copies (as many as the turn
    # has: both reckon them from the slot's length) and, appending, whether
    # it left a write-back in flight
    @pl.when((b == 0) & (g == 0))
    def _():
        ahead[0] = 0
        ahead[1] = 0
        if append:
            ahead[2] = 0
        if tailed:
            ahead[3] = 0
            z_buf[...] = jnp.zeros(z_buf.shape, z_buf.dtype)
        if W > 1:
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    first = ahead[0]
    mine = ahead[3] if tailed else None      # which of t_buf is this slot's

    if tailed:
        # the program before left its tile (1), and its block where its
        # group was complete (2), on their way back to the cache
        @pl.when(ahead[2] >= 1)
        def _():
            tile_out(0).wait()

        @pl.when(ahead[2] == 2)
        def _():
            for copy in writes(0, 0, 0):
                copy.wait()
    elif append:
        # the program before left its patched block on its way back to the
        # cache, out of the buffer this program's second turn goes to
        @pl.when(ahead[2] == 1)
        def _():
            for copy in writes(0, 0, 0):
                copy.wait()

    @pl.when((nb > 0) & (ahead[1] == 0))
    def _():
        fetch(first, b, g, j0, end)
        if tailed:
            for copy in tile_in(mine, b, g):
                copy.start()

    # the program after this one, and whether it has a block to fetch
    wraps = g == n_groups - 1
    b_next = jnp.where(wraps, b + 1, b)
    g_next = jnp.where(wraps, 0, g + 1)
    len_next = len_ref[jnp.minimum(b_next, n_slots - 1)]
    next_live = (b_next < n_slots) & (len_next > 0)
    _, j0_next, nb_next = live(len_next)
    end_next = None if W == 1 else j0_next + nb_next

    q = q_ref[...]
    # (hb, rows, 1) of per-query-head slopes, from SMEM scalars
    slope = _per_head(slopes_ref, g, hb, rows, group, 0.0) if alibi else None

    stop = j0 + turns(nb)        # turns count on from the first live block

    def body(u, carry):
        m, l, acc = carry
        buf = (first + u - j0) % 2
        j = u if W == 1 else j0 + (u - j0) * W       # the turn's first block

        # behind this turn's products: the slot's next turn, or after
        # its last the first turn of the next program
        @pl.when(u + 1 < stop)
        def _():
            fetch(1 - buf, b, g, j + W, end)

        @pl.when((u + 1 == stop) & next_live)
        def _():
            fetch(1 - buf, b_next, g_next, j0_next, end_next)
            if tailed:
                for copy in tile_in(1 - mine, b_next, g_next):
                    copy.start()

        if tailed:
            pl.when(u + 1 == stop)(partial(tail_columns, mine))
        over_turn(buf, b, g, j, end, lambda copy: copy.wait())
        if tailed:
            # the last live block holds the tail's group: its columns come
            # from the tile in VMEM, the new row with them, and the block
            # goes back once the group is complete
            @pl.when(u + 1 == stop)
            def _():
                i = 0 if W == 1 else end - 1 - j
                from_tail(buf, i)

                @pl.when(L % T == 0)
                def _():
                    for copy in writes(buf, i, plus(j, i)):
                        copy.start()
        elif append:
            # the last live block holds position L - 1: patched in VMEM,
            # attended to from there, written back once
            @pl.when(u + 1 == stop)
            def _():
                i = 0 if W == 1 else end - 1 - j
                patch(buf, i)
                for copy in writes(buf, i, plus(j, i)):
                    copy.start()
        k, v = k_buf[buf], v_buf[buf]                    # (hb, hd, W blk)
        s = jax.lax.dot_general(                         # (hb, rows, W blk)
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
        if window:
            keep = keep & (col >= L - window)
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
    if sink:
        # the sink column stands in the sum before any key: (sink_h, 1)
        m0 = _per_head(sink_ref, g, hb, rows, group, BIG_NEG)
        l0 = jnp.where(m0 > BIG_NEG, 1.0, 0.0)
    acc0 = jnp.zeros(o_ref.shape, jnp.float32)
    _, l, acc = jax.lax.fori_loop(j0, stop, body, (m0, l0, acc0))
    if tailed:
        # as below, of the tile and, where it went, the block
        last = (b == n_slots - 1) & wraps
        wrote = jnp.where(nb > 0, 1 + (L % T == 0).astype(jnp.int32), 0)

        @pl.when(last & (wrote >= 1))
        def _():
            tile_out(0).wait()

        @pl.when(last & (wrote == 2))
        def _():
            for copy in writes(0, 0, 0):
                copy.wait()

        ahead[2] = jnp.where(last, 0, wrote)
        ahead[3] = 1 - mine
    elif append:
        # the write runs behind the last turn's products, the next
        # program's first fetch (which is in the other buffer) and the turn
        # of the programs: the next one waits for it, the last one here
        last = (b == n_slots - 1) & wraps

        @pl.when((nb > 0) & last)
        def _():
            for copy in writes(0, 0, 0):
                copy.wait()

        ahead[2] = ((nb > 0) & jnp.logical_not(last)).astype(jnp.int32)
    ahead[0] = (first + turns(nb)) % 2
    ahead[1] = ((nb > 0) & next_live).astype(jnp.int32)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _per_head(ref, g, hb: int, rows: int, group: int, rest: float):
    """(hb, rows, 1) float32 of one SMEM scalar a query head (``ref`` (H,)):
    program ``g``'s KV head ``i``, row ``r`` is head ``(g hb + i) group +
    r``; the rows that pad a group to the sublane tile get ``rest``."""
    head = jax.lax.broadcasted_iota(jnp.int32, (hb, rows, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (hb, rows, 1), 1)
    out = jnp.full((hb, rows, 1), rest, jnp.float32)
    for i in range(hb):
        for r in range(group):
            out = jnp.where((head == i) & (row == r),
                            ref[(g * hb + i) * group + r], out)
    return out


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


def blocks_per_turn(KV: int, hd: int, vd: int, max_len: int, dtype,
                    blk: int = LANES) -> int:
    """The live blocks a loop turn of ``decode_attention`` takes (W): as
    many blocks of a program's ``hb`` heads, at the wider of K and V, as fit
    ``_ATTEND_TURN_BYTES``; at least 1, at most the cache's own. 8 for 2 KV
    heads of 128 (64 KB a block), 2 for 4 heads with keys of 192; 1 where
    the heads fill a turn already: GPT-2 774M's 20 of 64 (320 KB), 16 of
    128 (512 KB), 8 with keys of 192. From the shapes, never from the
    batch: a slot's bits do not depend on its neighbours."""
    hb = _heads_per_program(KV, hd, blk, dtype)
    one = hb * max(hd, vd) * blk * jnp.dtype(dtype).itemsize
    return max(1, min(_ATTEND_TURN_BYTES // one, max_len // blk))


def _slots_on_lanes(x, dtype):
    """(B, 1, KV, hd) → (KV, hd, slots): ``hd`` on the sublanes as in the
    cache, the slots on the lanes, padded to whole tiles (a few KiB; a
    kernel turns its slot's column onto the position's lane)."""
    x = x[:, 0].transpose(1, 2, 0).astype(dtype)
    return jnp.pad(x, ((0, 0), (0, 0), (0, (-x.shape[2]) % LANES)))


def tail_rows(dtype) -> int:
    """The positions a cache of ``dtype`` keeps in a slot's tail (T): the
    rows of one sublane tile, 16 of bf16, 8 of float32: the least a copy
    can write at a traced row offset."""
    return 4 * SUBLANES // jnp.dtype(dtype).itemsize


def decode_attention(q, ck, cv, length, *, k=None, v=None, tail=None,
                     layer=None, alibi_slopes=None, block: int = LANES,
                     interpret: Optional[bool] = None, window: int = 0,
                     sink=None, name: str = "decode_attention"):
    """q: (B, 1, H, hd) current-token queries; ck/cv: the cache
    ``(L, B, KV, hd, max_len)`` with ``layer`` (traced i32) the layer to
    attend over, or one layer's ``(B, KV, hd, max_len)``; ``length`` scalar
    or (B,) live lengths (positions < length attended).
    ``alibi_slopes``: optional (H,) per-head slopes — the ALiBi distance
    bias is reconstructed in-kernel from the live length (Bloom decode
    stays on the streaming kernel instead of the dense fallback).

    ``k`` / ``v`` (B, 1, KV, hd): the step's new K/V, not yet in the cache.
    The kernel then appends as it attends: they go to position
    ``length - 1`` of every slot (``length`` is the length AFTER the
    append; past the cache it clamps to the last position, as
    ``dynamic_update_slice`` clamps; a slot of length 0 is left alone),
    in the slot's last live block while it is in VMEM, and that block is
    written back to the cache, whose outputs are aliased to the inputs:
    every other position keeps every bit. Without them the kernel only
    reads (a caller that has appended already: the paged view's slab).

    ``tail`` ``(L, B, KV, T, hd + vd)`` (with ``k`` / ``v``; one layer's
    beside a slab): the cache's deferred tail, a third aliased output. Where
    the caller keeps one, position ``p`` of a slot stands on row ``p % T``
    of the slot's tile, K beside V on the lanes, until its group of T is
    complete: rows ``0 .. (length - 2) % T`` hold the group's earlier
    positions when the call comes (the blocks hold every group before it;
    what they hold of this one is not read). The step writes its row and
    the tile, attends to the block with the group's columns taken from the
    tile, and writes the block back only when ``length % T == 0``: the
    result is bit for bit the one without a tail over a cache that holds
    the same positions, at a third of the bytes moved to append.

    A slot's result depends on that slot's row and length alone: the block,
    the heads a program takes and the blocks a loop turn takes follow from
    ``(KV, hd, vd, max_len, dtype)``, never from ``B``.

    ``cv`` may hold values of another width than the keys (``(..., vd,
    max_len)``): the result then has V's. ``window`` > 0: the caches are
    RINGS of ``max_len`` positions (whole blocks, at least ``window`` and
    one block more), position ``p`` at ``p % max_len``; ``length`` counts
    on past the ring, the slot attends to positions ``length - window ..
    length - 1`` and fetches only the blocks that hold them. ``sink`` (H,)
    float32: a logit a head that joins the softmax's denominator and carries
    no value. ``name``: the ``pallas_call``'s, which a trace tells kernels
    apart by (a caller with another count of bytes a call gives its own).

    Returns (B, 1, H, vd); with ``k`` / ``v`` also the two caches, and the
    tail behind them where one came."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, hd = q.shape
    assert T == 1, "decode kernel is single-token; use flash_attention for prefill"
    append = k is not None
    tailed = tail is not None
    slab = ck.ndim == 4         # a layer's slab: a cache of that one layer
    if slab:
        ck, cv, layer = ck[None], cv[None], 0
        tail = tail[None] if tailed else None
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    KV, S, vd = ck.shape[2], ck.shape[4], cv.shape[3]
    blk = min(block, S)
    if S % blk != 0:
        raise ValueError(f"cache length {S} not divisible by block {blk}")
    if append and blk != LANES:
        raise ValueError(f"appending takes blocks of {LANES} positions, "
                         f"not {blk}")
    if tailed and (not append or window or LANES % tail.shape[3]
                   or tail.shape[4] != hd + vd
                   or not tail.dtype == ck.dtype == cv.dtype):
        raise ValueError(
            f"a tail {tail.shape} {tail.dtype} goes with the step's new "
            f"K/V, rows that divide {LANES}, K beside V ({hd} + {vd}) in the "
            "planes' one dtype, and no ring")
    if window and S < (-(-window // blk) + 1) * blk:
        raise ValueError(f"a ring of {S} positions does not hold a window "
                         f"of {window} and the block being written")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    group = H // KV
    scale = 1.0 / math.sqrt(hd)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    alibi = alibi_slopes is not None
    slopes = (jnp.asarray(alibi_slopes, jnp.float32),) if alibi else ()
    slopes += (jnp.asarray(sink, jnp.float32),) if sink is not None else ()
    news = (k, v) if append else ()
    tails = (tail,) if tailed else ()

    axes = _shard_axes(ck, H)
    if axes is not None:
        # GSPMD cannot partition a Mosaic kernel: run it per shard, slots
        # over the example-parallel axes and heads over model/seq (inside
        # the body the axes are manual, so the recursion lands below)
        mesh, b_ax, h_ax, cache = axes
        rows = P(b_ax, None, h_ax, None)

        if window or sink is not None:
            raise NotImplementedError(
                "a ring and a sink run on one device: no shard_map rule for "
                "them is under a test")

        def per_shard(q, ck, cv, n, layer, *rest):
            k, v = rest[:2] if append else (None, None)
            tail = rest[2] if tailed else None
            slopes = rest[len(news) + len(tails):]
            return decode_attention(q, ck, cv, n, k=k, v=v, tail=tail,
                                    layer=layer[0],
                                    block=block, interpret=interpret,
                                    alibi_slopes=slopes[0] if slopes else None,
                                    name=name)

        # (the tail's slots and heads shard as the planes')
        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(rows, cache, cache, P(b_ax), P())
            + (rows,) * len(news) + (cache,) * len(tails)
            + ((P(h_ax),) if alibi else ()),
            out_specs=(rows,) + (cache,) * (2 + len(tails)) if append
            else rows,
            check_vma=False)(q, ck, cv, lengths, layer, *news, *tails,
                             *slopes)

    hb = _heads_per_program(KV, hd, blk, ck.dtype)
    W = blocks_per_turn(KV, hd, vd, S, ck.dtype, blk)
    # (B, 1, H, hd) → (B, KV, rows, hd): a KV head's ``group`` query rows,
    # as the cache is stored, padded to the sublane tile — the GQA mapping
    # is this reshape, K/V are never repeated
    rows = -(-group // SUBLANES) * SUBLANES
    qs = jnp.pad(q.reshape(B, KV, group, hd).astype(ck.dtype),
                 ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    def rows_spec(width):
        return pl.BlockSpec((None, hb, rows, width),
                            lambda b, g, *pre: (b, g, 0, 0))

    def new_spec(width):
        return pl.BlockSpec((hb, width, LANES),
                            lambda b, g, *pre: (g, 0, b // LANES))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    n_pre = 2 + len(slopes)
    if tailed:
        # the step's new K beside its V, a row a KV head as the tail has them
        rows_t = tail.shape[3]
        # (it stays in HBM, a program's heads on the sublanes, padded to
        # whole tiles: a program copies its slot's rows with the tile)
        pad = -hb % rows_t
        news = (jnp.pad(
            jnp.concatenate([k, v], -1).astype(ck.dtype).reshape(
                B, KV // hb, hb, hd + vd), ((0, 0),) * 2 + ((0, pad), (0, 0))),)
        new_specs = [in_hbm]
    else:
        news = tuple(_slots_on_lanes(x, ck.dtype) for x in news)
        new_specs = [new_spec(hd), new_spec(vd)][:len(news)]
    carried = (ck, cv) * append + tails       # the aliased outputs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(B, KV // hb),
        in_specs=[rows_spec(hd)] + new_specs
        + [in_hbm] * (2 + len(tails)),
        out_specs=[rows_spec(vd)] + [in_hbm] * len(carried),
        # two buffers of K and of V, a turn's blocks side by side on the
        # lanes; with a tail two tiles, two new rows, the block the rows
        # land in and the columns they are turned into; a
        # semaphore a buffer for the fetches (a turn's copies count on one)
        # and one more pair for the write-back, a tile's two fetches and
        # its write-back; ``ahead`` (see the kernel)
        scratch_shapes=[pltpu.VMEM((2, hb, hd, W * blk), ck.dtype),
                        pltpu.VMEM((2, hb, vd, W * blk), cv.dtype)]
        + ([pltpu.VMEM((2, hb, rows_t, hd + vd), ck.dtype),
            pltpu.VMEM((2, hb + pad, hd + vd), ck.dtype),
            pltpu.VMEM((hb, blk, hd + vd), ck.dtype),
            pltpu.VMEM((hb, hd + vd, blk), ck.dtype)] if tailed else [])
        + [pltpu.SemaphoreType.DMA((2 + append + 2 * tailed, 2)),
           pltpu.SMEM((2 + append + tailed,), jnp.int32)],
    )
    out, *caches = pl.pallas_call(
        partial(_decode_kernel, block=blk, width=W, scale=scale, alibi=alibi,
                group=group, append=append, window=window,
                sink=sink is not None, tailed=tailed),
        name=name,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, rows, vd), q.dtype)]
        + [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in carried],
        # the caches come back where they were
        input_output_aliases={n_pre + 1 + len(news) + i: 1 + i
                              for i in range(len(carried))},
        # a program starts the next one's first copy: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths, layer, *slopes, qs, *news, ck, cv, *tails)
    out = out[:, :, :group].reshape(B, 1, H, vd)
    if not append:
        return out
    return (out,) + tuple(c[0] if slab else c for c in caches)


def _append_kernel(pos_ref, _, *refs, keep_idle: bool = False):
    """``refs``: n new-value blocks, n cache tiles, n output tiles. With
    ``keep_idle`` a position of -1 (a slot that is not running) takes no
    lane: its tile goes back as it came."""
    n = len(refs) // 3
    b = pl.program_id(0)
    r = pos_ref[b] % LANES                  # the position's lane in its tile
    if keep_idle:
        r = jnp.where(pos_ref[b] >= 0, r, -1)
    for new_ref, old_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                         refs[2 * n:]):
        out_ref[...] = _with_column(old_ref, new_ref, b, r)


def append_in_place(caches: tuple, news: tuple, lengths, layer, *, name: str,
                    interpret: bool, keep_idle: bool = False):
    """An append alone, for a cache that :func:`decode_attention` does not
    read (the one buffer of a latent cache, ``ops/mla_attention.py``): any
    number of buffers ``(L, B, KV, hd, max_len)`` written at the same
    positions, ``news`` (B, 1, KV, hd) each at position ``length - 1`` of
    every slot (``lengths`` (B,) AFTER the append, clamped into the cache
    as ``dynamic_update_slice`` clamps), ``layer`` (1,) i32. A
    read-modify-write of the one 128-lane tile that holds the position.
    Returns the caches, outputs aliased to the inputs, every other
    position bit-untouched. A slot at length 0 gets its position 0 written
    (the next insert overwrites it whole) unless ``keep_idle``: then its
    tile goes back bit-equal."""
    from jax.experimental.pallas import tpu as pltpu

    ck = caches[0]
    _, B, KV, hd, S = ck.shape
    n = len(caches)
    pos = jnp.clip(lengths - 1, 0, S - 1)
    if keep_idle:
        pos = jnp.where(lengths > 0, pos, -1)
    news = tuple(_slots_on_lanes(x, c.dtype) for x, c in zip(news, caches))
    kvb = max(d for d in range(1, KV + 1) if KV % d == 0 and (
        d == 1 or d * hd * LANES * ck.dtype.itemsize <= _APPEND_TILE_BYTES))

    def new_block(b, g, pos, layer):
        return (g, 0, b // LANES)

    def tile(b, g, pos, layer):
        at = jnp.maximum(pos[b], 0) if keep_idle else pos[b]
        return (layer[0], b, g, 0, at // LANES)

    new_spec = pl.BlockSpec((kvb, hd, LANES), new_block)
    tile_spec = pl.BlockSpec((None, None, kvb, hd, LANES), tile)
    return pl.pallas_call(
        partial(_append_kernel, keep_idle=True) if keep_idle
        else _append_kernel,
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

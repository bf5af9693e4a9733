"""Slot-based decode state: one persistent KV cache, per-slot everything.

Reference analog: DeepSpeed-MII / FastGen's blocked-KV "ragged batching"
state. TPU-native translation: instead of a paged block table (dynamic
indirection is hostile to XLA's static shapes), the serving state is ONE
``(L, slots, KV, hd, max_len)`` cache, or whatever buffers the model's cache
kind declares (``inference/kinds``: every buffer of every kind has the slot
second) — the same layout ``init_cache`` allocates:
positions on the lanes, so the buffer is compact in HBM at any head size
and the decode step's kernel appends to it and reads it where it lies
(``ops/decode_attention.py``) — plus per-slot ``length`` / ``tok`` /
``rng`` / ``done`` / ``left`` vectors. **A row that is not running is
``done`` and stands at length 0** (docs/SERVING.md, "The slot state"): the
step counts a running row's ``left`` down and marks it ``done`` at 0 or at
eos, a ``done`` row's length is 0, and a row at length 0 does not advance,
so the decode kernels neither fetch nor write for it. A finished slot is
immediately reusable: insertion overwrites the slot's FULL cache extent with the freshly
prefilled request's cache (one donated ``dynamic_update_slice`` of a
slot's whole contiguous extent), so stale KV from the previous occupant
can never leak into a successor's attention, and the decode step stays one
static-shape program no matter which requests come and go.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..inference.decode import GenCarry, init_cache

__all__ = ["init_slots", "insert_request", "retire_slots"]

# a request seated with no budget (a caller that steps a fixed number of
# times itself) never runs out
NO_BUDGET = 2 ** 31 - 1


def init_slots(cfg, slots: int, max_len: int, dtype=None) -> GenCarry:
    """Empty slot state: all slots idle (``done``), length 0.

    The carry is a plain :class:`~..inference.decode.GenCarry` whose cache
    ``length`` is a (slots,) vector — the decode stack's per-slot paths key
    off that shape, so the same ``decode_step`` serves both worlds. The
    cache is of the model's kind: what follows treats its buffers alike."""
    cache = init_cache(cfg, slots, max_len, dtype, length_shape=(slots,))
    return GenCarry(tok=jnp.zeros((slots,), jnp.int32), cache=cache,
                    rng=jnp.zeros((slots, 2), jnp.uint32),
                    done=jnp.ones((slots,), bool),
                    left=jnp.zeros((slots,), jnp.int32))


def seat_row(state: GenCarry, slot, *, tok, rng, done, length, left):
    """The per-slot vectors of ``state`` with row ``slot`` (traced i32)
    taking a request's (1,)-shaped values: (tok, rng, done, length, left).
    ``left`` None: :data:`NO_BUDGET`. A request whose first token ended it
    (``done``: eos out of the final chunk) is seated as every row that is
    not running stands, at length 0: the serving loop seats a request
    before it has read that token (docs/SERVING.md, "The host loop").
    Shared by every program that seats a request (here, and the paged
    pool's insert and import)."""
    left = jnp.full((1,), NO_BUDGET, jnp.int32) if left is None \
        else jnp.asarray(left, jnp.int32).reshape(1)
    length = jnp.where(done.reshape(1), 0, length.reshape(1))

    def put(vec, row):
        return lax.dynamic_update_slice(
            vec, row.astype(vec.dtype), (slot,) + (0,) * (vec.ndim - 1))

    return (put(state.tok, tok), put(state.rng, rng), put(state.done, done),
            put(state.cache.length, length.reshape(1)), put(state.left, left))


def insert_request(state: GenCarry, slot, pf: GenCarry,
                   left=None) -> GenCarry:
    """Write a freshly prefilled request (batch-1 carry, same ``max_len``)
    into slot ``slot``; ``left`` (i32 scalar) is how many tokens it may
    still emit after the one ``pf`` carries, its ``max_new - 1``.

    ``slot`` is a traced i32 scalar, so ONE compiled program inserts into
    any slot. The caller jits this with the state donated: the slot
    cache updates in place — no second copy of the (L, slots, KV, hd,
    max_len) buffers ever exists. The update spans the slot's full ``max_len``
    extent (the prefill cache is allocated at the slot's capacity), which
    is what guarantees a retired request's stale KV is fully overwritten
    before the new occupant's first decode step."""
    kc = state.cache
    # every buffer of the cache (K and V and the tail of their newest
    # positions; the latents; a recurrent state or the window layers'
    # rings beside K/V) has the slot second: a successor never reads its
    # predecessor's ring (None: a buffer this cache does not keep)
    buffers = {
        name: lax.dynamic_update_slice(
            buf, getattr(pf.cache, name).astype(buf.dtype),
            (0, slot) + (0,) * (buf.ndim - 2))
        for name, buf in kc._asdict().items()
        if name != "length" and buf is not None}
    tok, rng, done, length, left = seat_row(
        state, slot, tok=pf.tok, rng=pf.rng, done=pf.done,
        length=pf.cache.length, left=left)
    return GenCarry(tok=tok, cache=kc._replace(length=length, **buffers),
                    rng=rng, done=done, left=left)


def retire_slots(state: GenCarry, mask) -> GenCarry:
    """Rows of ``mask`` (slots,) bool stop running: ``done``, at length 0.
    For a retirement the device cannot foresee (a deadline, ``cancel``, a
    non-finite row, a hand-off); one that it can (eos, the budget) the step
    makes itself. Any cache kind; jit with the state donated."""
    return state._replace(
        done=state.done | mask,
        cache=state.cache._replace(
            length=jnp.where(mask, 0, state.cache.length)))

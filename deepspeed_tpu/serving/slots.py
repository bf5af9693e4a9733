"""Slot-based decode state: one persistent KV cache, per-slot everything.

Reference analog: DeepSpeed-MII / FastGen's blocked-KV "ragged batching"
state. TPU-native translation: instead of a paged block table (dynamic
indirection is hostile to XLA's static shapes), the serving state is ONE
``(L, slots, KV, hd, max_len)`` cache (``(L, slots, rank + rope, max_len)``
for latent attention) — the same layout ``init_cache`` allocates, via the shared :func:`~..inference.decode.cache_layout`:
positions on the lanes, so the buffer is compact in HBM at any head size
and the decode step's kernel appends to it and reads it where it lies
(``ops/decode_attention.py``) — plus per-slot ``length`` / ``tok`` /
``rng`` / ``done`` vectors. A finished slot is immediately reusable:
insertion overwrites the slot's FULL cache extent with the freshly
prefilled request's cache (one donated ``dynamic_update_slice`` of a
slot's whole contiguous extent), so stale KV from the previous occupant
can never leak into a successor's attention, and the decode step stays one
static-shape program no matter which requests come and go.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..inference.decode import GenCarry, init_cache

__all__ = ["init_slots", "insert_request"]


def init_slots(cfg, slots: int, max_len: int, dtype=None) -> GenCarry:
    """Empty slot state: all slots idle (``done``), length 0.

    The carry is a plain :class:`~..inference.decode.GenCarry` whose cache
    ``length`` is a (slots,) vector — the decode stack's per-slot paths key
    off that shape, so the same ``decode_step`` serves both worlds. The
    cache is of the model's kind (K and V, or latents), from
    ``cache_layout``: what follows treats its buffers alike."""
    cache = init_cache(cfg, slots, max_len, dtype, length_shape=(slots,))
    return GenCarry(tok=jnp.zeros((slots,), jnp.int32), cache=cache,
                    rng=jnp.zeros((slots, 2), jnp.uint32),
                    done=jnp.ones((slots,), bool))


def insert_request(state: GenCarry, slot, pf: GenCarry) -> GenCarry:
    """Write a freshly prefilled request (batch-1 carry, same ``max_len``)
    into slot ``slot``.

    ``slot`` is a traced i32 scalar, so ONE compiled program inserts into
    any slot. The caller jits this with the state donated: the slot
    cache updates in place — no second copy of the (L, slots, KV, hd,
    max_len) buffers ever exists. The update spans the slot's full ``max_len``
    extent (the prefill cache is allocated at the slot's capacity), which
    is what guarantees a retired request's stale KV is fully overwritten
    before the new occupant's first decode step."""
    kc = state.cache
    # every buffer of the cache (K and V; the latents) has the slot second
    buffers = {
        name: lax.dynamic_update_slice(
            buf, getattr(pf.cache, name).astype(buf.dtype),
            (0, slot) + (0,) * (buf.ndim - 2))
        for name, buf in kc._asdict().items() if name != "length"}
    length = lax.dynamic_update_slice(
        kc.length, pf.cache.length.reshape(1).astype(jnp.int32), (slot,))
    tok = lax.dynamic_update_slice(state.tok, pf.tok.astype(jnp.int32),
                                   (slot,))
    rng = lax.dynamic_update_slice(state.rng, pf.rng, (slot, 0))
    done = lax.dynamic_update_slice(state.done, pf.done, (slot,))
    return GenCarry(tok=tok, cache=kc._replace(length=length, **buffers),
                    rng=rng, done=done)

"""Tiered KV: a bounded pinned-host page store behind the device pool.

The ROADMAP's tiered-KV wall, built on the measurements PR 13 shipped:
ZeRO-Infinity's memory-wall playbook (PAPERS.md) applied to the paged KV
cache. Today ``PagePool._evict`` reclaims cold tree-held pages by
dropping them, and the next admission of the same prefix re-pays its
whole prefill — the regret kvscope's ghost ledger counts. With a host
tier (``serving.host_pool_bytes``), eviction instead DEMOTES:

- **demote-on-evict** — the pool's ``on_demote`` seam hands the engine
  every evicted full-block tree entry (its page id + the radix-tree
  token prefix that keys it); the engine DISPATCHES a gather of those
  pages' tiles (K, V, and the int8 scale planes when the pool is
  quantized) with ONE fixed-shape program (:func:`demote_rows`, row
  padded with the scratch page) right there — dispatch order is the
  safety: the gather executes ahead of any later-dispatched insert that
  could rewrite the freed pages — and materializes the bytes into this
  store at the END of the serving iteration
  (``ServingEngine._drain_demotes``), keyed exactly like the ghost
  list: ``(prefix_len, prefix_hash)`` with the full token tuple kept
  for verification. Demotion therefore never bills the resuming
  request's TTFT, and rides only the eviction-pressure path.
- **restore-on-resume** — admission consults the tier right after the
  radix-tree match (:meth:`HostKVTier.match`: consecutive full-block
  continuations of the tree hit, token-verified, checksum-verified);
  matched cold blocks are CONSUMED and their tiles scattered into the
  request's prefill cache (:func:`restore_into_cache`) in up to two
  fixed-shape batches, so the second host→device upload overlaps the
  first batch's device write and the whole restore overlaps the
  unshared-suffix prefill chunks behind it (async dispatch). From there
  the request flows through the SAME ``plan_chunks(skip=)`` → hydrate →
  ``insert_paged`` path as a tree hit — the restore is a data question,
  zero new steady-state programs beyond the one restore scatter.
- **degrade, never crash** — a pruned, collision-shadowed, or
  checksum-corrupt host copy is simply not a match: the block stays in
  the chunk plan and is recomputed (corruption counted in
  ``Serve/host_tier_fallbacks``). A restore abandoned mid-admission
  (deferred allocation) releases its pins; a cancelled restored request
  loses the cold copy and later resumes recompute — correctness never
  depends on the tier holding anything.

Capacity is a byte budget (``host_pool_bytes``): the store is LRU — a
put over budget prunes the coldest entries (``Serve/host_tier_prunes``)
until the new page fits. Everything host-side here is numpy + dicts; the
two device programs are compiled once and live in the engine's shared
program LRU (a fleet's replicas reuse them like every other program).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..inference.decode import dequantize_kv
from .tiering import TierStore, tiles_crc

__all__ = ["HostKVTier", "demote_rows", "restore_into_cache"]


# ------------------------------------------------------------ device side
def demote_rows(state, row):
    """Gather ``row``'s pool page tiles for host demotion: K, V, and the
    int8 scale planes when the pool is quantized. ``row`` is a full
    ``(pages_per_slot,)`` id vector padded with the scratch page, so ONE
    compiled program serves every eviction batch — padding entries are
    dropped host-side. MUST stay read-only (no donation) and MUST be
    dispatched before any program that could rewrite the freed pages
    (the engine dispatches it inside the eviction pass, before the
    admission's insert exists): dispatch order — not blocking — is what
    keeps the gathered bytes pristine while the ``device_get`` is
    deferred to end-of-iteration."""
    c = state.cache
    out = {"k": c.k[:, row], "v": c.v[:, row]}   # (L, n, KV, ps, hd)
    if c.k_scale is not None:
        out["k_scale"] = c.k_scale[:, row]       # (L, n, KV, ps)
        out["v_scale"] = c.v_scale[:, row]
    return out


def restore_into_cache(cache, tiles, start, count):
    """Scatter ``count`` host-restored page tiles into a batch-1 prefill
    cache's pages ``[start, start + count)`` — the restore-side analog
    of :func:`~.pages.hydrate_cache`, reading host bytes instead of pool
    pages. ``tiles`` is a fixed-size batch (padding entries masked by
    ``count``), so one compiled program serves every restore; int8 tiles
    dequantize here with the same point-of-use spelling the hydrate
    gather uses (:func:`~..inference.decode.dequantize_kv`), and the
    suffix prefill then runs in the compute dtype exactly as a tree
    hit's would."""
    from .pages import _page_merge, _page_split

    R, ps = tiles["k"].shape[1], tiles["k"].shape[3]
    max_len = cache.k.shape[4]
    n = max_len // ps
    if "k_scale" in tiles:
        tk = dequantize_kv(tiles["k"], tiles["k_scale"], cache.k.dtype)
        tv = dequantize_kv(tiles["v"], tiles["v_scale"], cache.v.dtype)
    else:
        tk = tiles["k"].astype(cache.k.dtype)
        tv = tiles["v"].astype(cache.v.dtype)
    keep = jnp.arange(R) < count
    # masked (padding) entries point past the last page: mode="drop"
    # discards them — no read-modify-write, no duplicate-index hazard
    tgt = jnp.where(keep, start + jnp.arange(R), n)
    ck = _page_split(cache.k, n, ps).at[:, tgt].set(tk, mode="drop")
    cv = _page_split(cache.v, n, ps).at[:, tgt].set(tv, mode="drop")
    return cache._replace(k=_page_merge(ck, cache.k),
                          v=_page_merge(cv, cache.v))


# -------------------------------------------------------------- host side
# shared integrity checksum (one CRC contract across every rung)
_crc = tiles_crc


class HostKVTier(TierStore):
    """Bounded host-memory page store: the demotion target and restore
    source for one engine's :class:`~.pages.PagePool` — the DRAM rung
    of the :mod:`~.tiering` hierarchy.

    Entries are one full tree block each — ``(prefix_len, prefix_hash)``
    key (the ghost-list spelling, via the shared
    :func:`~..observability.workload.token_hash`), the full token tuple
    for exact verification, the page's raw tiles, and a CRC. ``match``
    walks an admitted prompt's block boundaries past the tree hit and
    PINS consecutive matches (a concurrent demotion's prune cannot drop
    a block mid-admission); ``consume`` pops pinned matches into one
    stacked payload; ``release`` unpins when the allocation deferred.
    All ``Serve/host_tier_*`` metrics land in the serving registry.

    Every store behavior — LRU budget, pins, the match/consume/release
    handshake, degrade-never-crash — is the shared
    :class:`~.tiering.TierStore` implementation; this rung's payload
    transport is trivial: tiles simply stay in RAM on the entry."""

    kind = "host_tier"

    # ---------------------------------------------------- payload transport
    def _attach(self, key, ent: dict, tiles: dict) -> None:
        ent["tiles"] = tiles

    def _verify(self, ent: dict):
        return ent["tiles"] if tiles_crc(ent["tiles"]) == ent["crc"] \
            else None


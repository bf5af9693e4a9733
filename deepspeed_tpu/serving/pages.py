"""Paged KV cache: page-pool allocator, radix prefix tree, device programs.

Reference analog: vLLM's PagedAttention block manager and the
DeepSpeed-MII/FastGen blocked KV cache, rebuilt static-shape-native. The
device state is ONE ``(L, pages, KV, page_size, hd)`` pool (per K and V,
the paged kind's layout, :func:`~..inference.decode.cache_layout`) plus integer
per-slot page tables in the decode carry; the attention read gathers
over page ids, so page indirection is DATA — traffic churn changes table
contents, never a compiled program.

The host half lives here too:

- :class:`PagePool` — free-list allocator with per-page refcounts split
  into slot references (live requests) and tree references (the prefix
  cache's own retention). A page frees when both hit zero; tree-held
  pages with no slot users are the eviction pool under pressure (LRU).
- :class:`RadixPrefixTree` — one node per ``page_size``-token block of
  registered prompts. An admitted prompt walks the tree: every matched
  block is a pool page the request SHARES (refcount++, no prefill, no
  copy); the first divergent, partially-matched tail block is the one
  copy-on-write site — its source page is gathered into the request's
  prefill cache (``hydrate``) and written back to a FRESH private page
  at insert, so the donor's page is never mutated.
- tiered host store (``serving/hostkv.py``, ``host`` / ``on_demote``
  seams) — eviction demotes full-block tree entries to pinned host
  memory instead of dropping them, and admission consults the tier
  right after the radix-tree match: matched cold blocks restore at copy
  bandwidth (their tokens join ``skip``) instead of recompute FLOPs.
  ``prefill_tokens_saved`` counts restored tokens too — it is the
  "tokens not recomputed" truth; the tier's own counters split out
  what was paid in copy bytes.
- admission math — a request's worst-case page need assumes zero
  sharing (shared pages can be evicted from under the queue), so a
  request the pool can NEVER hold sheds with a typed
  :class:`~..resilience.guards.PagePoolExhausted` at submit, and a
  transiently full pool defers the queue head until retirement frees
  pages: the OOM-shaped mid-decode crash is impossible by construction.

Pool page 0 is reserved scratch: idle slots' table rows point there, and
the insert scatter redirects shared-page entries there; a row that is not
running (length 0) writes nowhere at all (``paged_append`` drops it) — a
retired slot or a shared prefix can never be written by construction.

Metrics land in the serving registry (``Serve/page_*``); ``snapshot()``
is the flight-recorder provider, so a stall dump shows pool state.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..inference.decode import GenCarry, PagedKVCache, cache_layout, \
    dequantize_kv, quantize_kv
from ..resilience.guards import PagePoolExhausted
from .slots import seat_row

__all__ = ["PagePool", "RadixPrefixTree", "PageAllocation",
           "init_paged_slots", "insert_paged", "hydrate_cache",
           "export_slot", "import_slot", "PagePoolExhausted"]

_SCRATCH = 0        # reserved pool page: idle-slot / shared-entry sink


# ------------------------------------------------------------ device side
def init_paged_slots(cfg, slots: int, max_len: int, page_size: int,
                     pages: int, dtype=None, kv_quant_bits: int = 0) \
        -> GenCarry:
    """Empty paged slot state: all slots idle (``done``), tables on
    scratch, length 0. The carry is a plain GenCarry whose cache is a
    :class:`~..inference.decode.PagedKVCache`, so the SAME ``decode_step``
    serves the contiguous and paged worlds."""
    shape, dt = cache_layout(cfg, slots, max_len, dtype,
                             page_size=page_size, pages=pages)
    if kv_quant_bits == 8:
        pool_dt, ks = jnp.int8, jnp.ones(shape[:-1], jnp.float32)
        k_scale, v_scale = ks, ks
    else:
        pool_dt, k_scale, v_scale = dt, None, None
    n = max_len // page_size
    cache = PagedKVCache(
        k=jnp.zeros(shape, pool_dt), v=jnp.zeros(shape, pool_dt),
        k_scale=k_scale, v_scale=v_scale,
        page_table=jnp.zeros((slots, n), jnp.int32),
        length=jnp.zeros((slots,), jnp.int32))
    return GenCarry(tok=jnp.zeros((slots,), jnp.int32), cache=cache,
                    rng=jnp.zeros((slots, 2), jnp.uint32),
                    done=jnp.ones((slots,), bool),
                    left=jnp.zeros((slots,), jnp.int32))


def _page_split(buf, n: int, ps: int):
    """A batch-1 contiguous cache buffer (L, 1, KV, hd, n*ps) as per-page
    tiles (L, n, KV, ps, hd) — the bridge between the prefill lane
    (positions on the lanes, ``KVCache``) and the pool (``hd`` last: a
    page is fewer positions than a lane tile)."""
    L, _, KV, hd, _ = buf.shape
    return buf[:, 0].reshape(L, KV, hd, n, ps).transpose(0, 3, 1, 4, 2)


def _page_merge(tiles, like):
    """Inverse of :func:`_page_split`: per-page tiles back into the
    batch-1 contiguous layout of ``like``."""
    L, _, KV, hd, max_len = like.shape
    return tiles.transpose(0, 2, 4, 1, 3).reshape(
        L, 1, KV, hd, max_len)


def insert_paged(state: GenCarry, slot, pf: GenCarry, page_row,
                 first_private, left=None) -> GenCarry:
    """Scatter a freshly prefilled request's contiguous cache into its
    pool pages and seat the per-slot vectors (``left`` as
    ``insert_request``'s).

    ``page_row`` is the slot's full (pages_per_slot,) table row;
    ``first_private`` the count of leading SHARED pages — those scatter
    targets are redirected to the scratch page, so a shared prefix is
    never rewritten (the prefill cache holds bit-identical hydrated
    values there anyway; redirecting keeps the write traffic off the
    live pages). Every PRIVATE page of the row is overwritten across its
    full extent — the paged analog of ``insert_request``'s
    stale-KV-leak-impossible-by-construction contract. Quantized pools
    quantize here, on append, with the same per-token per-head scales
    the decode-step append uses."""
    c = state.cache
    n, ps = page_row.shape[0], c.k.shape[3]
    tgt = jnp.where(jnp.arange(n) >= first_private, page_row, _SCRATCH)
    vk, vv = _page_split(pf.cache.k, n, ps), _page_split(pf.cache.v, n, ps)
    if c.k_scale is not None:
        qk, sk = quantize_kv(vk)
        qv, sv = quantize_kv(vv)
        k = c.k.at[:, tgt].set(qk)
        v = c.v.at[:, tgt].set(qv)
        k_scale = c.k_scale.at[:, tgt].set(sk)
        v_scale = c.v_scale.at[:, tgt].set(sv)
    else:
        k = c.k.at[:, tgt].set(vk.astype(c.k.dtype))
        v = c.v.at[:, tgt].set(vv.astype(c.v.dtype))
        k_scale, v_scale = c.k_scale, c.v_scale
    tok, rng, done, length, left = seat_row(
        state, slot, tok=pf.tok, rng=pf.rng, done=pf.done,
        length=pf.cache.length, left=left)
    cache = PagedKVCache(k=k, v=v, k_scale=k_scale, v_scale=v_scale,
                         page_table=c.page_table, length=length)
    return GenCarry(tok=tok, cache=cache, rng=rng, done=done, left=left)


def hydrate_cache(state: GenCarry, cache, hydrate_row, count):
    """Fill the leading pages of a batch-1 prefill cache from the pool:
    the admission-time half of prefix sharing. ``hydrate_row`` is a full
    (pages_per_slot,) id vector (entries past ``count`` ignored), so ONE
    compiled program serves every shared-prefix length. The last entry
    may be a copy-on-write SOURCE page (a donor's partially-matched tail
    block): its bytes bounce through this cache and land in a fresh
    private page at insert — the donor page itself is never written.
    Int8 pools dequantize here; the suffix prefill then runs in the
    compute dtype exactly as an unshared request's would."""
    c = state.cache
    n = hydrate_row.shape[0]
    gk, gv = c.k[:, hydrate_row], c.v[:, hydrate_row]  # (L, n, KV, ps, hd)
    if c.k_scale is not None:
        gk = dequantize_kv(gk, c.k_scale[:, hydrate_row], cache.k.dtype)
        gv = dequantize_kv(gv, c.v_scale[:, hydrate_row], cache.v.dtype)
    else:
        gk = gk.astype(cache.k.dtype)
        gv = gv.astype(cache.v.dtype)
    ps = c.k.shape[3]
    keep = (jnp.arange(n) < count)[None, :, None, None, None]
    ck = jnp.where(keep, gk, _page_split(cache.k, n, ps))
    cv = jnp.where(keep, gv, _page_split(cache.v, n, ps))
    return cache._replace(k=_page_merge(ck, cache.k),
                          v=_page_merge(cv, cache.v))


def export_slot(state: GenCarry, row, slot) -> dict:
    """Gather ONE request's pool pages + per-slot decode vectors into a
    position-major payload: the SOURCE half of the disaggregated
    prefill→decode handoff (serving/fleet.py). ``row`` is the slot's
    full (pages_per_slot,) table row — page indirection is DATA, so one
    compiled program exports any request on any slot. The payload is the
    request's complete decode state: its prompt KV tiles (int8 pools
    include the scale planes), the first sampled token, the per-request
    RNG chain *after* that sample, the done flag, the cache length and
    the tokens it may still emit —
    everything a decode replica needs to continue the exact bit-stream.
    The caller ``device_get``s the result: the transfer is host-mediated
    by design (replicas share no device state)."""
    c = state.cache
    out = {"k": c.k[:, row], "v": c.v[:, row],           # (L, n, KV, ps, hd)
           "tok": lax.dynamic_slice(state.tok, (slot,), (1,)),
           "rng": lax.dynamic_slice(state.rng, (slot, 0), (1, 2)),
           "done": lax.dynamic_slice(state.done, (slot,), (1,)),
           "length": lax.dynamic_slice(c.length, (slot,), (1,)),
           "left": lax.dynamic_slice(state.left, (slot,), (1,))}
    if c.k_scale is not None:
        out["k_scale"] = c.k_scale[:, row]
        out["v_scale"] = c.v_scale[:, row]
    return out


def import_slot(state: GenCarry, slot, payload: dict, row,
                first_private) -> GenCarry:
    """Scatter an exported payload into THIS pool's pages and seat the
    slot vectors: the DESTINATION half of the handoff. ``row`` is the
    destination allocation's table row; tiles below ``first_private``
    (prefix pages the destination already shares via its own radix tree
    — bit-identical KV by the parity oracle) redirect to the scratch
    page exactly like :func:`insert_paged`'s shared entries, so a live
    shared page is never rewritten. Every private page is overwritten
    across its full extent (the stale-KV-impossible contract); garbage
    tiles beyond ``length`` are invisible to the per-row attention mask
    and progressively overwritten by decode appends."""
    c = state.cache
    n = row.shape[0]
    tgt = jnp.where(jnp.arange(n) >= first_private, row, _SCRATCH)
    k = c.k.at[:, tgt].set(payload["k"].astype(c.k.dtype))
    v = c.v.at[:, tgt].set(payload["v"].astype(c.v.dtype))
    if c.k_scale is not None:
        k_scale = c.k_scale.at[:, tgt].set(payload["k_scale"])
        v_scale = c.v_scale.at[:, tgt].set(payload["v_scale"])
    else:
        k_scale, v_scale = c.k_scale, c.v_scale
    tok, rng, done, length, left = seat_row(
        state, slot, tok=payload["tok"], rng=payload["rng"],
        done=payload["done"], length=payload["length"],
        left=payload["left"])
    cache = PagedKVCache(k=k, v=v, k_scale=k_scale, v_scale=v_scale,
                         page_table=c.page_table, length=length)
    return GenCarry(tok=tok, cache=cache, rng=rng, done=done, left=left)


# -------------------------------------------------------------- host side
@dataclasses.dataclass
class PageAllocation:
    """One admitted request's page plan, produced by
    :meth:`PagePool.try_admit` and carried on the ``Request``.

    ``row`` is the full table row (shared ids, then private ids, then
    scratch padding); ``shared`` the leading shared-page count (=
    ``first_private`` for the insert scatter); ``skip`` the prompt
    tokens the prefill lane does NOT recompute (hydrated instead);
    ``hydrate_row``/``hydrate_pages`` the gather plan (``hydrate_pages``
    may exceed ``shared`` by one: the copy-on-write source page)."""

    rid: int
    row: np.ndarray
    pages: int                  # live pages this request references
    shared: int                 # leading pages shared via the prefix tree
    skip: int                   # prompt tokens served from the pool
    hydrate_row: np.ndarray
    hydrate_pages: int
    cow: bool = False           # a partially-matched tail page was copied
    cow_src: Optional[int] = None   # donor page pinned until insert/abort
    registered: bool = False
    # host-tier restore plan (serving/hostkv.py): ``restored`` cold
    # blocks continue the tree match from pinned host memory — their
    # tiles ride here to the engine's restore scatter, their tokens are
    # counted into ``skip`` (restored, not recomputed), and their pages
    # are ordinary private pages that ``insert_paged`` overwrites and
    # ``on_inserted`` registers into the tree like any other prefill.
    restored: int = 0
    restore_tiles: Optional[dict] = None
    restore_tokens: int = 0
    restore_bytes: int = 0


class _Node:
    """One radix-tree node = one ``page_size``-token block of some
    registered prompt, holding the pool page with that block's KV.
    ``tails`` maps partially-filled trailing blocks (prompt length not
    page-aligned) to their pages — the copy-on-write sources."""

    __slots__ = ("children", "tails", "page", "stamp", "tstamp", "parent",
                 "key")

    def __init__(self, parent=None, key=None, page: int = -1):
        self.children: dict = {}
        self.tails: dict = {}          # tail tokens (tuple) -> page id
        self.page = page
        self.stamp = 0
        self.tstamp: "float | None" = None   # clock time of the last touch
        self.parent = parent
        self.key = key


class RadixPrefixTree:
    """Host-side prefix index over registered prompt blocks.

    ``match`` walks an admitted prompt block-by-block, returning the
    shared page run and (optionally) a copy-on-write tail source;
    ``register`` adds a freshly inserted request's prompt blocks under
    its own private pages. Eviction is leaf-first LRU and only ever
    offered pages with zero slot references — the pool drives it when
    allocation runs dry."""

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _Node()
        self._tick = 0
        # eviction-pressure clock: the POOL stamps this before walking
        # the tree (one clock read per match/register call, only when
        # the pool was given a clock — the kvscope opt-in); None keeps
        # entry ages unreported and the hot path clock-free.
        self.now: "float | None" = None

    def _touch(self, node: _Node) -> None:
        self._tick += 1
        node.stamp = self._tick
        if self.now is not None:
            node.tstamp = self.now

    def match(self, prompt: np.ndarray) -> tuple:
        """(shared page ids, cow (src_page, tail_len) | None)."""
        toks = np.asarray(prompt).reshape(-1)
        ps = self.page_size
        node, ids = self.root, []
        i = 0
        while i + ps <= len(toks):
            child = node.children.get(tuple(toks[i:i + ps].tolist()))
            if child is None:
                break
            ids.append(child.page)
            self._touch(child)
            node, i = child, i + ps
        cow = None
        rest = tuple(toks[i:].tolist())
        for tail, page in node.tails.items():
            if len(tail) <= len(rest) and rest[:len(tail)] == tail \
                    and (cow is None or len(tail) > cow[1]):
                cow = (page, len(tail))
        return ids, cow

    def peek_blocks(self, toks: np.ndarray) -> int:
        """Leading full blocks of ``toks`` the tree holds, WITHOUT
        touching LRU stamps — :meth:`match`'s walk minus its side
        effects, for read-only probes (the fleet router's residency
        ranking must not distort eviction order on replicas it only
        considered)."""
        ps = self.page_size
        node, i, n = self.root, 0, 0
        while i + ps <= len(toks):
            child = node.children.get(tuple(toks[i:i + ps].tolist()))
            if child is None:
                break
            n += 1
            node, i = child, i + ps
        return n

    def register(self, prompt: np.ndarray, row: np.ndarray) -> list:
        """Index a just-inserted request's prompt blocks: full blocks as
        child nodes, a trailing partial block as a tail entry. Blocks
        already present keep their existing page (first writer wins — the
        duplicate private copy stays private). Returns the page ids the
        TREE newly references (the pool adds tree refs for them)."""
        toks = np.asarray(prompt).reshape(-1)
        ps = self.page_size
        node, taken = self.root, []
        for b in range(len(toks) // ps):
            key = tuple(toks[b * ps:(b + 1) * ps].tolist())
            child = node.children.get(key)
            if child is None:
                child = node.children[key] = _Node(
                    parent=node, key=key, page=int(row[b]))
                taken.append(child.page)
            self._touch(child)
            node = child
        tail = tuple(toks[(len(toks) // ps) * ps:].tolist())
        if tail and tail not in node.tails:
            node.tails[tail] = int(row[len(toks) // ps])
            taken.append(node.tails[tail])
        return taken

    def evictable(self) -> list:
        """(stamp, kind, node, key, page) for every leaf-evictable entry:
        tail entries, and childless tail-less nodes — oldest first."""
        out = []

        def walk(node):
            for tail, page in node.tails.items():
                out.append((node.stamp, "tail", node, tail, page))
            for key, child in node.children.items():
                if not child.children and not child.tails:
                    out.append((child.stamp, "node", node, key, child.page))
                else:
                    walk(child)

        walk(self.root)
        out.sort(key=lambda e: e[0])
        return out

    def drop(self, kind: str, parent: _Node, key) -> None:
        if kind == "tail":
            parent.tails.pop(key, None)
        else:
            parent.children.pop(key, None)

    @staticmethod
    def entry_tokens(node: _Node, key) -> tuple:
        """The FULL token prefix an evictable entry caches, from the
        root through ``key`` (a child-block tuple or a tail tuple under
        ``node``) — the identity the ghost-tree regret ledger
        (``observability/kvscope.py``) stamps at eviction so a later
        admission of the same prefix is attributable to the eviction
        that made it expensive."""
        parts = []
        while node is not None and node.key is not None:
            parts.append(node.key)
            node = node.parent
        parts.reverse()
        return tuple(t for k in parts for t in k) + tuple(key)

    def oldest_entry_time(self) -> "float | None":
        """Touch time of the oldest evictable entry (None without a
        clock or an evictable entry) — ``now - this`` is the
        eviction-pressure age ``PagePool.snapshot()`` surfaces. One
        sort-free walk (snapshot runs on every health/readyz probe;
        ``evictable()``'s sorted list would pay O(n log n) per probe)."""
        best = None

        def consider(n):
            nonlocal best
            if n.tstamp is not None and (best is None
                                         or n.tstamp < best):
                best = n.tstamp

        stack = [self.root]
        while stack:
            node = stack.pop()
            if node.tails:
                consider(node)
            for child in node.children.values():
                if not child.children and not child.tails:
                    consider(child)
                else:
                    stack.append(child)
        return best

    def __len__(self) -> int:
        n = 0
        stack = [self.root]
        while stack:
            node = stack.pop()
            n += len(node.tails)
            for child in node.children.values():
                n += 1
                stack.append(child)
        return n


class PagePool:
    """Host allocator over the device pool's page ids (1..pages-1; page 0
    is scratch). Tracks, per page, slot references (live requests whose
    table rows include it) and ONE optional tree reference (the prefix
    cache retains it for future sharing); a page returns to the free
    list when both drop. All decisions are host-side numpy/dicts — zero
    device syncs, zero compiled programs."""

    def __init__(self, pages: int, page_size: int, max_len: int,
                 registry=None, prefix_sharing: bool = True, clock=None):
        if pages < 2:
            raise ValueError(f"page pool needs >= 2 pages (one is "
                             f"reserved scratch), got {pages}")
        self.pages = pages
        self.page_size = page_size
        self.pages_per_slot = max_len // page_size
        self.registry = registry
        # injectable clock for eviction-pressure ages (oldest tree-entry
        # age in snapshot()/health()); None (default) keeps the pool
        # entirely clock-free — the engine passes one only when the
        # kvscope residency observatory opted in
        self.clock = clock
        self.free: list[int] = list(range(pages - 1, 0, -1))  # pop() -> 1..
        self.slot_refs = np.zeros(pages, np.int64)
        self.tree_refs = np.zeros(pages, bool)
        self.tree: Optional[RadixPrefixTree] = \
            RadixPrefixTree(page_size) if prefix_sharing else None
        self._alloc: dict[int, PageAllocation] = {}   # rid -> allocation
        # bumped whenever admission prospects improve (pages freed by a
        # release, or new prefixes registered): the scheduler's retry
        # gate, so a deferred queue head re-runs the tree match/eviction
        # walk only when something actually changed
        self.generation = 0
        # eviction-stamp seam (observability/kvscope.py): called once
        # per eviction EVENT with the evicted entries' token prefixes —
        # the ghost-tree regret ledger's input. None (default) = one
        # `is not None` per eviction pass, nothing else.
        self.on_evict = None
        # tiered-KV seams (serving/hostkv.py): ``host`` is the engine's
        # HostKVTier — admission consults it right after the radix-tree
        # match and restores matched cold blocks instead of recomputing
        # them; ``on_demote`` is called during an eviction pass with the
        # evicted FULL-BLOCK entries (page id + token prefix) BEFORE
        # their pages can be reused, so the engine can gather the tiles
        # to host. Both None (default) = one `is not None` per
        # admission/eviction, nothing else.
        self.host = None
        self.on_demote = None
        # page-residency seam (observability/tenantscope.py): called as
        # ``on_pages(rid, delta)`` with the SAME page counts the pool
        # books — +pages at admission, -pages at truncate rollback,
        # -pages at release — so a per-tenant page-second integral sums
        # to the pool's own occupancy exactly. None (default) = one
        # `is not None` per admission/release, nothing else.
        self.on_pages = None
        # cumulative accounting (the capacity advisor's "achieved" side).
        # `evictions` counts PAGES freed by tree eviction (the historical
        # meaning, kept); `eviction_events` counts eviction PASSES — one
        # admission under pressure is one event however many pages it
        # reclaims. The two answer different questions (how much cache
        # was lost vs how often pressure bites) and are reported apart.
        self.prefill_tokens_saved = 0
        self.prompt_tokens = 0
        self.shared_page_acquires = 0
        self.private_page_acquires = 0
        self.cow_copies = 0
        self.evictions = 0              # pages freed by eviction
        self.eviction_events = 0        # eviction passes that freed > 0
        self.defers = 0
        self._publish()

    # ------------------------------------------------------------- metrics
    def _publish(self) -> None:
        if self.registry is None:
            return
        self.registry.set_gauges({
            "Serve/page_pool_free": float(len(self.free)),
            "Serve/page_pool_used": float(self.usable - len(self.free)),
            "Serve/page_pool_tree_held": float(self.tree_held),
            "Serve/page_prefix_hit_rate": self.prefix_hit_rate,
        })

    @property
    def usable(self) -> int:
        return self.pages - 1

    @property
    def tree_held(self) -> int:
        """Pages retained ONLY by the prefix tree (evictable)."""
        return int(np.sum(self.tree_refs & (self.slot_refs == 0)))

    @property
    def prefix_hit_rate(self) -> float:
        total = self.shared_page_acquires + self.private_page_acquires
        return self.shared_page_acquires / total if total else 0.0

    def worst_case_pages(self, prompt_len: int, max_new: int) -> int:
        """Pages a request can need assuming ZERO sharing — the admission
        bound (shared pages are real at admission time, but the bound
        must hold even when the tree has nothing to offer)."""
        return -(-(prompt_len + max_new - 1) // self.page_size)

    def check_submit(self, prompt_len: int, max_new: int) -> None:
        """Typed shed for a request the pool can NEVER hold — raising at
        submit() keeps the failure synchronous instead of wedging the
        queue head forever."""
        need = self.worst_case_pages(prompt_len, max_new)
        if need > self.usable:
            raise PagePoolExhausted(
                f"request needs up to {need} KV pages (prompt {prompt_len}"
                f" + max_new {max_new} @ page_size {self.page_size}) but "
                f"the pool holds {self.usable} — raise serving.pool_pages "
                "or shrink the request", pages_needed=need,
                pages_usable=self.usable)

    # ----------------------------------------------------------- admission
    def _evict(self, need: int) -> bool:
        """Free ``need`` pages by dropping LRU tree entries with no slot
        users. Returns False (nothing dropped beyond what was possible)
        when the tree cannot cover the shortfall."""
        if self.tree is None or need <= 0:
            return need <= 0
        freed = 0
        ghosts = [] if self.on_evict is not None else None
        demote = [] if self.on_demote is not None else None
        while freed < need:
            # leaf-first passes: dropping a leaf can expose its parent as
            # the next evictable entry, so re-snapshot until the need is
            # met or a pass frees nothing (everything left is pinned)
            progress = False
            for _stamp, kind, parent, key, page in self.tree.evictable():
                if freed >= need:
                    break
                if self.slot_refs[page] == 0 and self.tree_refs[page]:
                    if ghosts is not None:
                        # stamp the evicted block's identity BEFORE the
                        # drop: the ghost ledger attributes the prefill
                        # a later admission re-pays to THIS event
                        ghosts.append({
                            "tokens": self.tree.entry_tokens(parent, key),
                            "block": len(key)})
                    if demote is not None and kind == "node":
                        # demote-on-evict: full blocks carry a complete
                        # page of KV worth keeping; partial tails stay
                        # ghost-only (copy-on-write sources are cheap
                        # to recompute and block-granular keys keep the
                        # tier's restore walk trivial)
                        demote.append({
                            "tokens": self.tree.entry_tokens(parent, key),
                            "page": int(page), "block": len(key)})
                    self.tree.drop(kind, parent, key)
                    self.tree_refs[page] = False
                    self.free.append(page)
                    self.evictions += 1
                    freed += 1
                    progress = True
            if not progress:
                break
        if freed:
            self.eviction_events += 1
            if self.registry is not None:
                # pages freed and eviction EVENTS are different signals:
                # Serve/page_evictions keeps its historical pages-freed
                # meaning, the event counter says how often pressure bit
                self.registry.counter("Serve/page_evictions").inc(freed)
                self.registry.counter("Serve/page_eviction_events").inc()
            if demote:
                # BEFORE the freed pages can be popped for reuse: the
                # engine's handler DISPATCHES the tile gather here (so
                # it is ordered ahead of any program that could rewrite
                # the pages) and materializes it to host at the end of
                # the iteration, off the admission path
                self.on_demote(demote)
            if ghosts:
                self.on_evict(ghosts)
        return freed >= need

    def demote_ahead_candidates(self, cutoff: float, limit: int,
                                skip=None) -> list:
        """Full-block tree entries whose last touch is at or before
        ``cutoff`` and whose page has no slot users — the demote-ahead
        lane's feed, shaped exactly like the ``on_demote`` payload
        (``tokens`` / ``page`` / ``block``). Unlike eviction's
        leaf-first passes, this walks EVERY block node of an idle chain
        (an idle session's whole prefix stages in one batch, not one
        block per pass — inner nodes are full blocks too; shared-prefix
        nodes another session still touches stay above the cutoff).
        Read-only: no drops, no stamp touches, no refcount changes —
        the pages stay tree-held and a resuming session keeps them as a
        normal tree hit (staging is a COPY, so a resume mid-stage
        wastes at most that one copy; tree-held pages with no slot
        users are immutable, so the copy can never go stale). Partial
        tails stay recompute-only, same as eviction's demote filter.
        ``skip(tokens)`` filters entries already staged (the tier's
        ``holds``); oldest first, at most ``limit``. Requires the pool
        clock (entries without a ``tstamp`` never qualify)."""
        if self.tree is None or limit <= 0:
            return []
        cands: list = []

        def walk(node):
            for key, child in node.children.items():
                if (child.tstamp is not None and child.tstamp <= cutoff
                        and self.slot_refs[child.page] == 0
                        and self.tree_refs[child.page]):
                    cands.append((child.tstamp, node, key, child.page))
                walk(child)

        walk(self.tree.root)
        cands.sort(key=lambda c: c[0])
        out = []
        for _ts, parent, key, page in cands:
            if len(out) >= limit:
                break
            toks = self.tree.entry_tokens(parent, key)
            if skip is not None and skip(toks):
                continue
            out.append({"tokens": toks, "page": int(page),
                        "block": len(key)})
        return out

    def try_admit(self, prompt: np.ndarray, max_new: int,
                  rid: int, book_savings: bool = True) \
            -> Optional[PageAllocation]:
        """Admission-time page plan: consult the prefix tree, take refs
        on the shared run, allocate private pages for the rest (evicting
        LRU tree-only pages under pressure). None = transiently full —
        the caller leaves the request at the queue head and retries
        after a retirement.

        ``book_savings=False`` (the disaggregated IMPORT path) still
        allocates and shares pages but books no prefill-savings or
        copy-on-write stats: a decode replica seating already-computed
        KV skips no prefill compute, so counting its ``skip`` tokens as
        saved would double-count the prefill replica's real savings."""
        prompt = np.asarray(prompt).reshape(-1)
        P, ps, n = len(prompt), self.page_size, self.pages_per_slot
        if self.tree is not None and self.clock is not None:
            # one clock read per admission: every node the walk touches
            # gets this stamp, so entry AGES (eviction pressure) are
            # reportable without a read per node
            self.tree.now = self.clock()
        shared_ids, cow = (self.tree.match(prompt)
                           if self.tree is not None else ([], None))
        total_need = self.worst_case_pages(P, max_new)
        if total_need > n:
            # unreachable through the scheduler (P + max_new <= max_len);
            # a direct caller exceeding the slot extent is a bug, not
            # backpressure
            raise ValueError(
                f"request needs {total_need} pages > pages_per_slot={n} "
                "(prompt + max_new exceeds max_len)")
        # a fully-shared prompt still recomputes its final token (the
        # first output's logits need a forward at position P-1), so cap
        # the skip below P; the replayed bucket rewrites bit-identical KV
        shared = min(len(shared_ids), total_need)
        shared_ids = shared_ids[:shared]
        skip = shared * ps
        # host-tier restore plan (serving/hostkv.py): cold full blocks
        # CONTINUING the tree match, pinned in the tier until this
        # allocation commits (consume) or defers (release). Their pages
        # are ordinary private pages; only ``skip`` and the tile payload
        # distinguish a restore from a recompute. The disaggregated
        # import path (book_savings=False) seats already-computed KV and
        # must not burn host copies it would never read.
        restore_keys: list = []
        if self.host is not None and book_savings:
            restore_keys = self.host.match(
                prompt, start_block=shared,
                max_blocks=total_need - shared)
        restored = len(restore_keys)
        skip += restored * ps
        # a restored full block covers any copy-on-write tail at the
        # same position — cow only applies to an unrestored admission
        cow_src, cow_len = (cow if restored == 0 and cow is not None
                            and cow[1] > 0 and skip + cow[1] < P
                            else (None, 0))
        private_need = total_need - shared
        # pin the matched pages BEFORE any eviction pass: a tree-only
        # page we are about to share must not be reclaimed to cover the
        # same request's private shortfall
        for p in shared_ids:
            self.slot_refs[p] += 1
        if cow_src is not None:
            self.slot_refs[cow_src] += 1
        short = private_need - len(self.free)
        if short > 0 and not self._evict(short):
            for p in shared_ids:           # undo the pins; defer in queue
                self._unref(p)
            if cow_src is not None:
                self._unref(cow_src)
            if restore_keys:
                # the cold blocks stay restorable for the retry
                self.host.release(restore_keys)
            self.defers += 1
            if self.registry is not None:
                self.registry.counter("Serve/page_defers").inc()
            return None
        private = [self.free.pop() for _ in range(private_need)]
        row = np.zeros(n, np.int32)
        row[:shared] = shared_ids
        row[shared:total_need] = private
        for p in private:
            self.slot_refs[p] += 1
        hyd = np.zeros(n, np.int32)
        hyd[:shared] = shared_ids
        hydrate_pages = shared
        if cow_src is not None:
            # copy-on-write: the donor's partial tail block bounces
            # through the prefill cache into this request's own page
            # (the pin above holds until insert/abort)
            hyd[shared] = cow_src
            hydrate_pages = shared + 1
            skip += cow_len
            if book_savings:
                self.cow_copies += 1
                if self.registry is not None:
                    self.registry.counter("Serve/page_cow_copies").inc()
        skip = min(skip, P - 1)
        tiles, rbytes, rtoks = None, 0, 0
        if restore_keys:
            # commit point: the pinned host copies move onto the
            # allocation (the engine scatters them into the prefill
            # cache before the suffix chunks run)
            tiles, rbytes, rtoks = self.host.consume(restore_keys)
        alloc = PageAllocation(
            rid=rid, row=row, pages=total_need, shared=shared, skip=skip,
            hydrate_row=hyd, hydrate_pages=hydrate_pages,
            cow=cow_src is not None, cow_src=cow_src,
            restored=restored, restore_tiles=tiles,
            restore_tokens=rtoks, restore_bytes=rbytes)
        self._alloc[rid] = alloc
        if book_savings:
            self.prompt_tokens += P
            self.prefill_tokens_saved += skip
            if self.registry is not None:
                self.registry.counter(
                    "Serve/page_prefill_tokens_saved").inc(skip)
        self.shared_page_acquires += shared
        self.private_page_acquires += private_need
        if self.registry is not None:
            self.registry.histogram(
                "Serve/pages_per_request").observe(total_need)
        self._publish()
        if self.on_pages is not None:
            self.on_pages(rid, total_need)
        return alloc

    # ---------------------------------------------------------- completion
    def on_inserted(self, rid: int, prompt: np.ndarray) -> None:
        """The request's prefill landed in the pool: register its prompt
        blocks in the prefix tree (tree refs on its own private pages)
        and release the copy-on-write source pin."""
        alloc = self._alloc.get(rid)
        if alloc is None or alloc.registered:
            return
        alloc.registered = True
        self._release_cow(alloc)
        if self.tree is not None:
            if self.clock is not None:
                self.tree.now = self.clock()
            for page in self.tree.register(np.asarray(prompt), alloc.row):
                self.tree_refs[page] = True
        self.generation += 1
        self._publish()

    def _release_cow(self, alloc: PageAllocation) -> None:
        if alloc.cow_src is not None:
            src, alloc.cow_src = alloc.cow_src, None
            self._unref(src)

    def _unref(self, page: int) -> None:
        self.slot_refs[page] -= 1
        if self.slot_refs[page] <= 0:
            self.slot_refs[page] = 0
            if not self.tree_refs[page]:
                self.free.append(page)

    def release(self, rid: int) -> None:
        """Terminal path (retire / cancel / timeout / nonfinite / shed
        after allocation): drop the request's slot refs; pages with no
        tree reference return to the free list immediately."""
        alloc = self._alloc.pop(rid, None)
        if alloc is None:
            return
        self._release_cow(alloc)
        for page in alloc.row[:alloc.pages]:
            self._unref(int(page))
        self.generation += 1
        self._publish()
        if self.on_pages is not None:
            # alloc.pages already reflects any truncate rewinds, so the
            # admission/truncate/release deltas net to zero per rid
            self.on_pages(rid, -alloc.pages)

    def truncate(self, rid: int, new_tokens: int) -> int:
        """Page-table-aware rollback: rewind ``rid``'s live extent to
        ``new_tokens`` tokens, freeing the whole pages strictly beyond
        the (kept, partially-filled) tail block. The speculative-decode
        lane calls this when a request retires off a verify step whose
        rejected drafts wrote past the final committed length — the
        garbage tail's pages drop their slot refs immediately instead of
        riding to ``release``, and can never be mistaken for live KV by
        a later demotion sweep.

        Invariants preserved:

        - never rewinds below ``alloc.shared`` (tree-pinned prefix pages
          and host-restored blocks are admission-time state, not
          decode-time growth — rollback cannot unshare them);
        - freed entries go through :meth:`_unref`, so a page the prefix
          tree still references stays resident for future hits (host-tier
          demotion candidates included) and only truly unreferenced
          pages hit the free list;
        - the table row's freed entries redirect to scratch, so a stale
          device mirror of this row can only ever write into page 0;
        - ``generation`` bumps like every other occupancy change, so
          deferred admissions retry against the freed pages.

        Returns the number of pages freed. Partial-tail rewinds within
        one block free nothing — the tail block is KEPT and its
        positions past ``new_tokens`` are dead by length (every future
        append overwrites position == committed length first)."""
        alloc = self._alloc.get(rid)
        if alloc is None:
            return 0
        keep = -(-max(0, int(new_tokens)) // self.page_size)
        keep = min(alloc.pages, max(keep, alloc.shared))
        freed = alloc.pages - keep
        if freed <= 0:
            return 0
        for page in alloc.row[keep:alloc.pages]:
            self._unref(int(page))
        alloc.row[keep:alloc.pages] = _SCRATCH
        alloc.pages = keep
        self.generation += 1
        self._publish()
        if self.on_pages is not None:
            self.on_pages(rid, -freed)
        return freed

    # -------------------------------------------------------------- readout
    def residency(self, prompt: np.ndarray) -> tuple:
        """``(tree_blocks, host_blocks)`` holding ``prompt``'s leading
        full blocks right now — a READ-ONLY probe for the fleet
        router's affinity ranking (tree hit > host-tier hit > miss):
        no LRU touches, no refcounts, no pins, so routing a session
        cannot distort eviction order on replicas it only considered."""
        if self.tree is None:
            return (0, 0)
        toks = np.asarray(prompt).reshape(-1)
        tree_blocks = self.tree.peek_blocks(toks)
        host_blocks = (self.host.peek_blocks(toks, tree_blocks)
                       if self.host is not None else 0)
        return tree_blocks, host_blocks

    def snapshot(self) -> dict:
        """Flight-recorder provider + the capacity advisor's achieved
        side: pool occupancy, sharing effectiveness, tree size, and the
        eviction-pressure picture (evictable pages, oldest tree-entry
        age — surfaced through health()/ /readyz)."""
        used = self.usable - len(self.free)
        oldest_age = None
        if self.tree is not None and self.clock is not None:
            t = self.tree.oldest_entry_time()
            if t is not None:
                oldest_age = max(0.0, self.clock() - t)
        return {
            "pages": self.pages,
            "usable_pages": self.usable,
            "page_size": self.page_size,
            "pages_per_slot": self.pages_per_slot,
            "free_pages": len(self.free),
            "used_pages": used,
            "tree_held_pages": self.tree_held,
            "tree_entries": len(self.tree) if self.tree is not None else 0,
            # tree-held pages are reclaimable cache, not waste; the
            # fragmentation figure is the share of the pool neither a
            # slot nor the tree can account for (0 by construction —
            # page granularity leaves nothing stranded)
            "fragmentation": max(0, used - self.tree_held - int(
                np.sum(self.slot_refs > 0))) / max(1, self.usable),
            "live_requests": len(self._alloc),
            "prompt_tokens": self.prompt_tokens,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "tokens_saved_fraction": (
                self.prefill_tokens_saved / self.prompt_tokens
                if self.prompt_tokens else 0.0),
            "shared_page_acquires": self.shared_page_acquires,
            "private_page_acquires": self.private_page_acquires,
            "prefix_hit_rate": self.prefix_hit_rate,
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
            # eviction pressure, disaggregated: how much cache was lost
            # (pages) vs how often pressure bit (events), what could be
            # reclaimed right now, and how stale the coldest entry is
            "pages_evicted": self.evictions,
            "eviction_events": self.eviction_events,
            "evictable_pages": self.tree_held,
            "oldest_tree_entry_age_s": oldest_age,
            "defers": self.defers,
            "prefix_sharing": self.tree is not None,
            # the tiered host store's occupancy/traffic picture (None
            # when no host tier is attached — serving.host_pool_bytes=0)
            "host_tier": (self.host.snapshot()
                          if self.host is not None else None),
        }

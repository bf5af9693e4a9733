"""The cache of latent attention that reads only what an indexer selects
(``cfg.index_pattern``, ``models/dsa.py``). Two buffers:

- ``c`` ``(L, B, max_len, 1, words)``: every layer's latents, **a position a
  row** — ``ops/sparse_mla_attention.py`` says why (one position has to come
  without its 127 lane neighbours, and a DMA takes whole tiles: the row is
  its own ``(1, 128)``-tiled plane, two bf16 values a 32-bit word, 576 values
  in 384 words: 1536 B a position a layer of which 1152 are used);
- ``ik`` ``(F, B, index_head_dim, max_len)``: the indexer's key a position,
  for the ``F`` layers only, positions on the lanes as the latent kind's
  buffer: the score reads every live key of a slot, a plain product.

The T == 1 step, a layer: append ``c`` (inside the sparse kernel) and, in
an ``F`` layer, ``kI`` (``mla_cache_append``), both in place; in an ``F``
layer score the slot's live keys (``dsa_index_score``) and select
(``dsa.select``: a threshold by bisection over the scores' bits, no sort:
the positions and, on the kernels, their mask); bring in the slot's latents
and attend absorbed over the selected (``sparse_mla_decode_attention``: a
slot's live blocks whole under the mask where its live rows are few enough
a selected one, a descriptor a selected row beyond — ``sparse.reads_dense``,
which ``step_meta`` counts by too). An ``s`` layer takes the selection the
carry holds. T > 1 (a chunk, a solo prefill) writes with XLA's update,
selects likewise and walks the live blocks with the selection as a mask:
exact, the work of dense attention, in blocks of :data:`QUERY_BLOCK` queries
so that no (T, max_len) float32 array stands for T in the thousands. Where
the step's kernels run and the kernel tiles the widths
(:meth:`SparseLatent.chunk_kernel`) that walk is ONE kernel a layer,
``sparse_mla_chunk_attention``, whose scores, probabilities and accumulators
stay in VMEM; everywhere else ``mla.attend_expanded(selected=)``, the same
mathematics in plain ``jnp`` (``Serve/chunk_attention_fallback_builds``
counts the chunk programs traced onto it with the kernels on).
"""

from collections import namedtuple
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...models import dsa, mla
from ...models.transformer import _norm
from ...ops import mla_attention
from ...ops import sparse_mla_attention as sparse
from .base import IN_POOL, MOVES_PAGES, Kind, held_counts, split_banks
from .steps import _decode_kernel_ok, _dense_append, _out_ffn

SparseLatentCache = namedtuple("SparseLatentCache", "ik c length")
QUERY_BLOCK = 512


def _index(tree, i):
    """Layer ``i`` (static or traced) of a stacked tree."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def query_blocks(fn, T: int, *xs):
    """``fn`` over blocks of QUERY_BLOCK of the T queries (axis 1 of every
    ``xs``), the results joined along it."""
    if T <= QUERY_BLOCK:
        return fn(*xs)
    nb = -(-T // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - T

    def cut(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                    mode="edge")
        return a.reshape(a.shape[0], nb, QUERY_BLOCK,
                         *a.shape[2:]).swapaxes(0, 1)

    out = lax.map(lambda a: fn(*a), tuple(cut(a) for a in xs))
    return jax.tree.map(
        lambda a: a.swapaxes(0, 1).reshape(
            a.shape[1], nb * QUERY_BLOCK, *a.shape[3:])[:, :T], out)


def read_meta(kind, lens, chosen, run: int) -> dict:
    """How the step's kernel brings in the latents of running slots at
    ``lens`` live positions of which ``chosen`` are selected (arrays, a
    slot), by the kernel's own rule (``sparse.reads_dense``) on the host's
    mirror: the share of the slots that read their live blocks whole, and
    the rows the reads bring in — whole blocks on that side, the selected
    rows on the other — over the selected rows."""
    from ...observability.metrics import get_registry

    words, wdt, _ = sparse.row_layout(kind.cfg.latent_dim,
                                      kind.dtype or kind.cfg.dtype)
    dense = sparse.reads_dense(lens, chosen, run,
                               words * jnp.dtype(wdt).itemsize)
    rows = np.where(dense, sparse.dense_rows(lens, kind.max_len), chosen)
    reg = get_registry()
    reg.counter("Serve/dsa_dense_reads").inc(int(dense.sum()))
    reg.counter("Serve/dsa_gathered_reads").inc(int((~dense).sum()))
    return {"dsa_dense_share": float(dense.sum()) / max(len(lens), 1),
            "dsa_rows_read_over_selected":
                float(rows.sum()) / max(int(chosen.sum()), 1)}


class SparseLatent(Kind):
    cache = SparseLatentCache
    planes = ("ik", "c")      # the gate reads the first's last dimension
    moe_stats = True          # the read-back carries the selection too
    mirrors_lengths = True    # step_meta counts from the slots' lengths
    refuses = {
        "paged": "the paged pool and prefix sharing (page_size): a page "
                 "holds K and V, and a shared prefix would need the latents "
                 "AND the indexer's keys as pages of one tree",
        "kv_quant": IN_POOL,
        "speculation": "speculation: a verify forward of several tokens a "
                       "slot has no selected read, and the model's own "
                       "drafting layer (MTP) is not held",
        "host_kv": MOVES_PAGES,
        "quantize": "weight-only quantization: the latent and the indexer "
                    "projections take dense weights",
        "mesh": "a mesh of several devices: the sparse kernel has no "
                "shard_map rule and the experts held are told by the "
                "configuration, no axis exchanges rows yet"}
    contiguous_only = ("the paged pool holds K and V pages; latents a "
                       "position a row beside indexer keys are contiguous "
                       "only")

    def __init__(self, cfg, *serving):
        super().__init__(cfg, *serving)
        self.what = (f"attention over an indexer's selection (index_pattern="
                     f"{cfg.index_pattern!r}) does not yet compose with")
        self.full = cfg.index_pattern.count("F")

    @staticmethod
    def matches(cfg) -> bool:
        # (beside the mixers of a mixer_pattern: kinds/linear_sparse.py)
        return bool(getattr(cfg, "index_pattern", "")) \
            and not getattr(cfg, "mixer_pattern", "")

    def chunk_kernel(self, flash_decode, T, max_len, *dtypes) -> bool:
        """Whether T > 1 queries over a cache of ``max_len`` attend in
        ``sparse_mla_chunk_attention``: where the step's kernels would run
        (``_decode_kernel_ok``: the switch, no float16, whole lane blocks)
        and the kernel tiles the widths. Any T: the kernel pads a bucket's
        queries to a tile, and more than :data:`QUERY_BLOCK` come to it in
        blocks of that."""
        cfg = self.cfg
        return T > 1 and _decode_kernel_ok(flash_decode, 1, max_len, *dtypes) \
            and sparse.chunk_kernel_fits(
                max_len, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.kv_lora_rank, cfg.v_dim)

    def chunk_fused(self, flash_decode, T, max_len, *dtypes) -> bool:
        fused = self.chunk_kernel(flash_decode, T, max_len, *dtypes)
        if flash_decode and not fused:
            from ...observability.metrics import get_registry

            # counted where a chunk's program is built (a trace, not a
            # call), as Serve/decode_fallback_builds counts the step's
            get_registry().counter(
                "Serve/chunk_attention_fallback_builds").inc()
        return fused

    def buffers(self, batch, max_len, dtype=None):
        cfg, dt = self.cfg, dtype or self.cfg.dtype
        words, wdt, _ = sparse.row_layout(cfg.latent_dim, dt)
        return {"ik": ((self.full, batch, cfg.index_head_dim, max_len), dt),
                "c": ((cfg.n_layer, batch, max_len, 1, words), wdt)}

    def bytes_per_token(self, dtype=None):
        # positions are not the last dimension of ``c``: by the layout
        layout = self.buffers(1, 128, dtype or self.dtype)
        return sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
                   for shape, dt in layout.values()) // 128

    token_bytes = cached_property(bytes_per_token)

    # ------------------------------------------------------------ the loop
    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        """Each run of layers equal in (FFN kind, indexer kind) scans its
        layers (``dsa.runs``), all carrying (x, c, ik, the selection). The
        selection is (``idx`` (B, K), ``mask`` (B, 1, max_len) float32 as
        the step's kernel adds it, None off the kernels) for a step and
        (``idx`` (B, T, K), ``mask`` (B, T, max_len)) for T > 1. Stats:
        (counters (expert layers, 4), (routing (expert layers, B, T, k), the
        F layers' idx (F, B, T, K))) — the second what a comparison with a
        reference follows and ``ServingEngine.routing_log`` taps."""
        cfg = self.cfg
        B, T, _ = x.shape
        per_slot = getattr(new_len, "ndim", 0) == 1
        if per_slot and T > 1:
            raise NotImplementedError(
                "a selected read takes one token a slot (T == 1) or a chunk "
                "of rows that advance together (scalar length): no "
                "multi-token verify forward")
        S = cache.ik.shape[-1]
        K = min(cfg.index_topk, S)
        dt = x.dtype
        scale = mla.softmax_scale(cfg)
        segs = model.segment_params(params["layers"])

        def append_keys(ik, new, full):
            """The T new indexer keys (B, T, D) into layer ``full``."""
            if T > 1:
                return lax.dynamic_update_slice(
                    ik, new.transpose(0, 2, 1)[None].astype(ik.dtype),
                    (full, 0, 0, new_len - T))
            if fused:
                return mla_attention.latent_append(
                    ik, new[:, 0], new_len, layer=full, keep_idle=True)
            return _dense_append(ik[:, :, None], new[:, :, None], full,
                                 new_len)[1][:, :, 0]

        def choose(y, p, ip, ik, full, pos):
            """An F layer's selection for the queries ``y`` at ``pos``."""
            qi, w = dsa.index_queries(cfg, y, mla.query_latent(cfg, y, p),
                                      ip, pos)
            if fused and T == 1:
                score = sparse.index_scores(qi[:, 0], w[:, 0], ik, new_len,
                                            layer=full)
                # the mask beside the indices: the kernel reads a slot's
                # live blocks whole under it where that is the cheaper fetch
                idx, mask = dsa.select(score[:, None], pos, K)
                return idx, sparse.step_mask(mask)
            keys = lax.dynamic_index_in_dim(ik, full, keepdims=False)
            live = None if per_slot else new_len
            idx, mask = dsa.select(dsa.scores(qi, w, keys, live), pos, K,
                                   want_mask=T > 1, n_keys=live)
            # the kernel's DMAs take bytes; the layers behind share them
            return idx, mask.astype(jnp.int8) if fused and T > 1 else mask

        def read_block(c, layer):
            def read(j, blk):
                rows = lax.dynamic_slice(
                    c, (layer, 0, j * blk, 0, 0),
                    (1, B, blk) + c.shape[3:])[0]
                return sparse.unpack_rows(rows, cfg.latent_dim,
                                          dt).transpose(0, 2, 1)
            return read

        def blocks(fn, *xs):
            return query_blocks(fn, T, *xs)

        def layer_fn(carry, p, ip, layer, full, local, kind, banks):
            x, c, ik, sel = carry
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            q_nope, q_rope, new = mla.project(cfg, y, p, positions)
            if kind == "F":
                ik = append_keys(ik, dsa.index_keys(cfg, y, ip, positions),
                                 full)
                if T == 1:
                    idx, mask = choose(y, p, ip, ik, full, positions)
                    sel = (idx[:, 0], mask)
                else:
                    sel = blocks(lambda y, pos: choose(y, p, ip, ik, full,
                                                       pos), y, positions)
            if T > 1:
                c = lax.dynamic_update_slice(
                    c, sparse.pack_rows(new, dt)[None],
                    (layer, 0, new_len - T, 0, 0))
                if fused:
                    w = mla._wkv_b(cfg, p, dt)
                    o = blocks(
                        lambda qn, qr, keep: sparse.sparse_mla_chunk_attention(
                            qn, qr, w, c, keep, new_len, layer=layer,
                            rank=cfg.kv_lora_rank, scale=scale),
                        q_nope, q_rope, sel[1])
                else:
                    o = blocks(
                        lambda qn, qr, pos, mask: mla.attend_expanded(
                            cfg, p, qn, qr, (read_block(c, layer), S), pos,
                            new_len, selected=mask),
                        q_nope, q_rope, positions, sel[1])
            else:
                q = mla.absorb_q(cfg, p, q_nope, q_rope)
                if fused:
                    o_lat, c = sparse.sparse_mla_decode_attention(
                        q, c, new[:, 0], sel[0], new_len, layer=layer,
                        rank=cfg.kv_lora_rank, scale=scale, mask=sel[1])
                else:
                    # XLA's update on the layer's slab, its rows unpacked
                    slab = jax.vmap(lambda s, r, at: lax.dynamic_update_slice(
                        s, r[None], (at, 0, 0)))(
                            lax.dynamic_index_in_dim(c, layer,
                                                     keepdims=False),
                            sparse.pack_rows(new[:, 0], dt),
                            jnp.broadcast_to(new_len, (B,)) - 1)
                    c = lax.dynamic_update_slice(c, slab[None],
                                                 (layer, 0, 0, 0, 0))
                    o_lat = sparse.attend_selected(
                        q, sparse.unpack_rows(slab, cfg.latent_dim, dt),
                        sel[0], new_len, rank=cfg.kv_lora_rank, scale=scale)
                o = mla.absorb_o(cfg, p, o_lat)
            x, stats = _out_ffn(model, x, o, p, banks, local,
                                cfg.moe_router == "sigmoid")
            pick = sel[0] if T > 1 else sel[0][:, None]
            return (x, c, ik, sel), (stats, pick)

        if T == 1:
            sel = (jnp.zeros((B, K), jnp.int32),
                   jnp.zeros((B, 1, S), jnp.float32) if fused else None)
        else:
            sel = (jnp.zeros((B, T, K), jnp.int32),
                   jnp.zeros((B, T, S), jnp.int8 if fused else bool))
        carry = (x, cache.c, cache.ik, sel)
        counters, routing, picks = [], [], []
        for seg, at, n, kind, first, full in dsa.runs(cfg):
            moe = cfg.segments[seg][0] == "moe"
            banks, rest = split_banks(model, segs[seg], moe)
            ix = params["indexer"] if kind == "F" else None

            def body(carry, i, rest=rest, ix=ix, at=at, first=first,
                     full=full, kind=kind, banks=banks):
                return layer_fn(
                    carry, _index(rest, at + i),
                    None if ix is None else _index(ix, full + i),
                    first + i, full + i, at + i, kind, banks)

            with jax.named_scope("decode_layer"):
                if n == 1:
                    carry, out = body(carry, 0)
                    out = jax.tree.map(lambda a: a[None], out)
                else:
                    carry, out = lax.scan(body, carry,
                                          jnp.arange(n, dtype=jnp.int32))
            (st, chose), pick = out
            if moe:
                counters.append(st)
                routing.append(chose)
            if kind == "F":
                picks.append(pick)
        x, c, ik, _ = carry
        stats = (jnp.concatenate(counters),
                 (jnp.concatenate(routing), jnp.concatenate(picks))) \
            if counters else None
        return (x, SparseLatentCache(c=c, ik=ik, length=new_len), stats, None)

    # ------------------------------------------------------------ the spans
    def _chosen(self, n):
        """The positions a query at the end of ``n`` reads (arrays)."""
        return np.minimum(n, self.cfg.index_topk)

    def _dsa(self, n) -> dict:
        """Of queries at the ends of ``n`` positions each (an array): the
        positions their layers' attention reads, those that are live, and
        the latent bytes the sparse read fetches over what is used of
        them."""
        cfg = self.cfg
        n = np.asarray(n)
        chosen = int(self._chosen(n).sum())
        live = int(n.sum())
        words, wdt, _ = sparse.row_layout(cfg.latent_dim, self.dtype
                                          or cfg.dtype)
        used = cfg.latent_dim * jnp.dtype(self.dtype or cfg.dtype).itemsize
        return {"dsa_selected": chosen, "dsa_live": live,
                "dsa_selected_over_live": chosen / max(live, 1),
                "dsa_fetched_over_selected":
                    words * jnp.dtype(wdt).itemsize / used}

    def chunk_meta(self, chunk):
        real = chunk.last_index + 1 if chunk.final else chunk.size
        meta = self._dsa(chunk.start + 1 + np.arange(real))
        dt = self.dtype or self.cfg.dtype
        return {"dsa_selected_over_live": meta["dsa_selected_over_live"],
                "cache_bytes_per_token": self.token_bytes,
                "attn_live_keys": chunk.start + chunk.size,
                "attn_kernel": self.chunk_kernel(self.flash, chunk.size,
                                                 self.max_len, dt)}

    def step_meta(self, read, pending, lens, running):
        from ...observability.metrics import get_registry

        meta = {"cache_bytes_per_token": self.token_bytes}
        if lens is not None:
            live = lens[lens > 0]
            meta.update(self._dsa(live))
            if self.flash:      # the kernel reads, by its own rule
                meta.update(read_meta(self, live, self._chosen(live), 1))
            reg = get_registry()
            reg.counter("Serve/dsa_selected_positions").inc(
                meta["dsa_selected"])
            reg.counter("Serve/dsa_live_positions").inc(meta["dsa_live"])
        meta.update(held_counts(self, read, pending))
        return meta

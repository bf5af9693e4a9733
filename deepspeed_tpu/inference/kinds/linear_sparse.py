"""The cache of a trunk of delta-rule mixers beside latent attention over an
indexer's selection (``cfg.mixer_pattern``; GLM-5.3-Flash, ``model_type:
glm5_next_text``): two kinds of per-slot memory side by side.

What grows with the position, for the attention ("A") layers only:

- ``c`` ``(A, B, max_len, 1, words)``: the layers' latents, a position a row
  (``ops/sparse_mla_attention.py``); with no rope part a row is exactly
  ``kv_lora_rank`` values, 1024 B at 512 in bf16, no padding;
- ``ik`` ``(F, B, index_head_dim, max_len / index_kpool)``: the indexer's
  POOLED keys, one a group of ``index_kpool`` positions (the mean of the
  group's keys, written when the group closes), groups on the lanes.

What a slot holds whatever its length:

- ``kda`` ``(K, B, H, D, D)`` float32: the KDA layers' delta-rule state;
- ``conv`` ``(K, B, kda_conv - 1, 3 H D)``: the last inputs of their three
  depthwise convs;
- ``ikt`` ``(F, B, index_kpool - 1, index_head_dim)``: the indexer keys of
  the group that is still open.

The layer loop carries ``n = hc_mult`` residual streams a token
(``models/mhc.py``): the embedding repeated on the way in, summed on the way
out, every sub-layer reading and writing through its own maps. The T == 1
step, an attention layer: the new key joins the open group's (or closes it:
the mean goes into ``ik``, in place, ``mla_cache_append``); the slot's closed
groups are scored (``dsa_index_score``), the ``index_topk / index_kpool``
best and the open one selected (``dsa.select_pooled``), and the slot's
latents attended absorbed over them (``sparse_mla_decode_attention``, which
appends the step's own row first, then reads the slot's live blocks whole
under the selection's mask — or, past ``sparse.reads_dense``'s crossover,
fetches the selected groups, whose ``index_kpool`` rows lie side by side: one
DMA a group). A KDA layer: ``kda_state_step`` moves the
state in place. T > 1 (a chunk that starts at a group's edge — the
scheduler's chunks start at multiples of ``prefill_chunk`` — or a solo
prefill): XLA's updates, the chunkwise scan (``kda_chunk_scan``, ONE kernel
a layer, where the kernels run and T is whole blocks of 64:
``kda.chunk_kernel_ok``; else ``kda.scan_chunked`` in plain ``jnp``, a chunk
of whole blocks counted by ``Serve/chunk_scan_fallback_builds``), the
selection as a mask over ``sparse_mla_chunk_attention`` where the kernels
run, else over ``mla.attend_expanded``.
"""

from collections import namedtuple
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...models import dsa, kda, mhc, mla
from ...models.transformer import _norm
from ...ops import mla_attention
from ...ops import sparse_mla_attention as sparse
from ..quantization import matmul_any
from .base import (IN_POOL, MOVES_PAGES, Kind, _nbytes,
                   count_chunk_fallbacks, held_counts,
                   served_bytes, split_banks)
from .sparse_latent import SparseLatent, _index, query_blocks, read_meta

LinearSparseCache = namedtuple("LinearSparseCache",
                               "ik c kda conv ikt length")


class LinearSparse(Kind):
    cache = LinearSparseCache
    planes = ("ik", "c")      # the gate reads the first's last dimension
    recurrent = True
    moe_stats = True          # the read-back carries the selection too
    mirrors_lengths = True    # step_meta counts from the slots' lengths
    refuses = {
        "paged": "the paged pool and prefix sharing (page_size): a "
                 "delta-rule state has no pages, and a shared prefix would "
                 "need the state, the conv tails and the open group's keys "
                 "as they stood at the prefix's end",
        "kv_quant": IN_POOL,
        "speculation": "speculation: a rejected draft would have to roll "
                       "the delta-rule state back, and the model's own "
                       "drafting layer (MTP) is not held",
        "host_kv": MOVES_PAGES,
        "quantize": "weight-only quantization: the mixers', the latent's "
                    "and the indexer's projections take dense weights",
        "mesh": "a mesh of several devices: the state step's and the sparse "
                "kernels have no shard_map rule and the experts held are "
                "told by the configuration, no axis exchanges rows yet"}
    contiguous_only = ("the paged pool holds K and V pages; latents a "
                       "position a row, pooled indexer keys and a recurrent "
                       "state beside them are contiguous only")

    def __init__(self, cfg, slots: int = 1, dtype=None, params=None):
        super().__init__(cfg, slots, dtype, params)
        self.what = (f"delta-rule mixers beside attention over an indexer's "
                     f"selection (mixer_pattern={cfg.mixer_pattern!r}) do "
                     "not yet compose with")
        self.full = cfg.index_pattern.count("F")
        self.attends = cfg.mixer_pattern.count("A")
        self.mixers = cfg.mixer_pattern.count("K")
        self.layers = self.attends
        # what a step reads of the weights whoever runs, the routed experts'
        # banks apart (the step's counters say how many it touched)
        self.layer_bytes, self.expert_bytes, self.head_bytes = served_bytes(
            cfg, params)

    @staticmethod
    def matches(cfg) -> bool:
        # (beside the config's own GQA: kinds/delta_gqa.py; beside dense
        # MLA, no indexer: kinds/delta_latent.py)
        return bool(getattr(cfg, "mixer_pattern", "")) \
            and getattr(cfg, "attention", "") == "mla" \
            and bool(getattr(cfg, "index_pattern", ""))

    # ---------------------------------------------------------- the layout
    def buffers(self, batch, max_len, dtype=None):
        cfg, dt = self.cfg, dtype or self.cfg.dtype
        if max_len % cfg.index_kpool:
            raise ValueError(f"a cache of {max_len} positions is not whole "
                             f"groups of index_kpool={cfg.index_kpool}")
        words, wdt, _ = sparse.row_layout(cfg.latent_dim, dt)
        return {"ik": ((self.full, batch, cfg.index_head_dim,
                        max_len // cfg.index_kpool), dt),
                "c": ((self.attends, batch, max_len, 1, words), wdt)}

    def state(self, batch, dtype=None):
        cfg, dt = self.cfg, dtype or self.cfg.dtype
        shapes = kda.state_shapes(cfg, batch)
        return {"kda": ((self.mixers,) + shapes["kda"], jnp.float32),
                "conv": ((self.mixers,) + shapes["conv"], dt),
                "ikt": ((self.full, batch, cfg.index_kpool - 1,
                         cfg.index_head_dim), dt)}

    def bytes_per_token(self, dtype=None):
        # positions are not the last dimension of ``c``: by the layout
        n = 128 * self.cfg.index_kpool
        return _nbytes(self.buffers(1, n, dtype or self.dtype)) // n

    token_bytes = cached_property(bytes_per_token)

    # whether T > 1 queries attend in ``sparse_mla_chunk_attention``: the
    # rule of the kind that kernel was written for (it reads the config)
    chunk_kernel = SparseLatent.chunk_kernel

    def chunk_fused(self, flash_decode, T, groups, *dtypes) -> bool:
        # (the gate hands the first plane's last dimension: the groups)
        fused = self.chunk_kernel(flash_decode, T,
                                  groups * self.cfg.index_kpool, *dtypes)
        count_chunk_fallbacks(flash_decode, not fused,
                              kda.chunk_scan_falls_back(self.cfg, fused, T))
        return fused

    # ------------------------------------------------------------ the loop
    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        """Each run of layers equal in (mixer, FFN kind) scans its layers,
        all carrying (the streams, the five buffers). Stats: (counters
        (expert layers, 4), (routing (expert layers, B, T, k), the attention
        layers' selected positions (F, B, T, K'), -1 behind a row's last))."""
        cfg = self.cfg
        B, T, _ = x.shape
        per_slot = getattr(new_len, "ndim", 0) == 1
        pool, topk = cfg.index_kpool, cfg.index_topk
        S = cache.c.shape[2]
        G = S // pool
        dt = x.dtype
        scale = mla.softmax_scale(cfg)
        lens = new_len if per_slot else jnp.broadcast_to(new_len, (B,))
        live = lens > 0
        segs = model.segment_params(params["layers"])
        streams = cfg.hc_mult > 1

        def residual(X, p, side, f):
            """Sub-layer ``f`` (xin -> (out, what it leaves)) onto X."""
            left = []

            def g(xin):
                out, extra = f(xin)
                left.append(extra)
                return out

            X = mhc.sublayer(X, g, mhc.maps(cfg, X, p, side) if streams
                             else None, wide=True)
            return X, left[0]

        def ffn(X, p, banks, local):
            def f(xin):
                y = _norm(xin, p["ln2_scale"], None, cfg.norm,
                          cfg.norm_eps).astype(dt)
                if "router" in p:
                    out, stats, chose = model.experts(y, p, banks=banks,
                                                      layer=local)
                    return out, (stats, chose)
                return model._mlp_block(y, p)[0], (
                    jnp.zeros((4,), jnp.float32),
                    jnp.zeros((B, T, 0), jnp.int32))
            return residual(X, p, 1, f)

        def mixer(carry, p, ki):
            X, c, ik, ikt, St, W = carry

            def f(xin):
                y = _norm(xin, p["ln1_scale"], None, cfg.norm,
                          cfg.norm_eps).astype(dt)
                out, S2, W2 = kda.mix(cfg, p, y, St, W, ki, lens, valid,
                                      fused)
                return out, (S2, W2)

            X, (St, W) = residual(X, p, 0, f)
            return X, c, ik, ikt, St, W

        def keys_in(ik, ikt, kI, fi):
            """The T new indexer keys (B, T, D) into indexer ``fi``: the
            groups they close into ``ik``, the open group's into ``ikt``."""
            tail = lax.dynamic_index_in_dim(ikt, fi, keepdims=False)
            if T > 1:
                start = new_len - T
                pad = -T % pool
                kp = jnp.pad(kI, ((0, 0), (0, pad + pool), (0, 0)))
                ik = lax.dynamic_update_slice(
                    ik, dsa.pool_keys(kp[:, :T + pad], pool).transpose(
                        0, 2, 1)[None].astype(ik.dtype),
                    (fi, 0, 0, start // pool))
                real = T if valid is None else valid
                tail = lax.dynamic_slice_in_dim(
                    kp, real - real % pool, pool - 1, axis=1).astype(
                        tail.dtype)
                return ik, lax.dynamic_update_slice(ikt, tail[None],
                                                    (fi, 0, 0, 0))
            t = jnp.maximum(lens - 1, 0)
            r = t % pool
            closes = live & (r == pool - 1)
            pooled = ((jnp.sum(tail.astype(jnp.float32), axis=1)
                       + kI[:, 0].astype(jnp.float32)) / pool).astype(ik.dtype)
            if fused:
                ik = mla_attention.latent_append(
                    ik, pooled, jnp.where(closes, lens // pool, 0), layer=fi,
                    keep_idle=True)
            else:
                slab = lax.dynamic_index_in_dim(ik, fi, keepdims=False)
                at = closes[:, None, None] & (
                    jnp.arange(G)[None, None] == (t // pool)[:, None, None])
                ik = lax.dynamic_update_slice(
                    ik, jnp.where(at, pooled[:, :, None], slab)[None],
                    (fi, 0, 0, 0))
            here = (live & ~closes)[:, None, None] & (
                jnp.arange(pool - 1)[None, :, None] == r[:, None, None])
            tail = jnp.where(here, kI.astype(tail.dtype), tail)
            return ik, lax.dynamic_update_slice(ikt, tail[None],
                                                (fi, 0, 0, 0))

        def choose(y, cq, ip, ik, fi, pos):
            """An attention layer's selection for the queries ``y`` at
            ``pos``: (positions, how many of a row are valid, mask)."""
            qi, w = dsa.index_queries(cfg, y, cq, ip, pos)
            if fused and T == 1:
                score = sparse.index_scores(qi[:, 0], w[:, 0], ik,
                                            pos[:, 0] // pool, layer=fi)[:, None]
                groups = None
            else:
                keys = lax.dynamic_index_in_dim(ik, fi, keepdims=False)
                groups = None if per_slot else (new_len + pool - 1) // pool
                score = dsa.scores(qi, w, keys, groups)
            idx, n, mask = dsa.select_pooled(
                score, pos, topk, pool, want_mask=T > 1 or fused,
                n_groups=groups)
            if fused:
                # the kernels' DMAs take bytes (a chunk's) or a row added
                # to the scores (the step's, which may read dense under it)
                mask = mask.astype(jnp.int8) if T > 1 \
                    else sparse.step_mask(mask)
            return idx, n, mask

        def read_block(c, ai):
            def read(j, blk):
                rows = lax.dynamic_slice(
                    c, (ai, 0, j * blk, 0, 0), (1, B, blk) + c.shape[3:])[0]
                return sparse.unpack_rows(rows, cfg.latent_dim,
                                          dt).transpose(0, 2, 1)
            return read

        def attention(carry, p, ip, ai, fi):
            X, c, ik, ikt, St, W = carry

            def f(xin):
                y = _norm(xin, p["ln1_scale"], None, cfg.norm,
                          cfg.norm_eps).astype(dt)
                q_nope, q_rope, new = mla.project(cfg, y, p, positions)
                cq = mla.query_latent(cfg, y, p)
                ik2, ikt2 = keys_in(ik, ikt, dsa.index_keys(cfg, y, ip,
                                                            positions), fi)
                if T == 1:
                    idx, n, keep = choose(y, cq, ip, ik2, fi, positions)
                    idx, n = idx[:, 0], n[:, 0]
                    q = mla.absorb_q(cfg, p, q_nope, q_rope)
                    if fused:
                        o_lat, c2 = sparse.sparse_mla_decode_attention(
                            q, c, new[:, 0], idx, new_len, layer=ai,
                            rank=cfg.kv_lora_rank, scale=scale, n=n,
                            run=pool, mask=keep)
                    else:
                        slab = jax.vmap(
                            lambda s, r, at: lax.dynamic_update_slice(
                                s, r[None], (at, 0, 0)))(
                                    lax.dynamic_index_in_dim(c, ai,
                                                             keepdims=False),
                                    sparse.pack_rows(new[:, 0], dt),
                                    jnp.maximum(lens - 1, 0))
                        c2 = lax.dynamic_update_slice(c, slab[None],
                                                      (ai, 0, 0, 0, 0))
                        o_lat = sparse.attend_selected(
                            q, sparse.unpack_rows(slab, cfg.latent_dim, dt),
                            idx, new_len, rank=cfg.kv_lora_rank, scale=scale,
                            n=n)
                    o = mla.absorb_o(cfg, p, o_lat)
                    pick = jnp.where(jnp.arange(idx.shape[-1])[None]
                                     < n[:, None], idx, -1)[:, None]
                else:
                    c2 = lax.dynamic_update_slice(
                        c, sparse.pack_rows(new, dt)[None],
                        (ai, 0, new_len - T, 0, 0))
                    idx, n, keep = query_blocks(
                        lambda y, cq, pos: choose(y, cq, ip, ik2, fi, pos),
                        T, y, cq, positions)
                    if fused:
                        w = mla._wkv_b(cfg, p, dt)
                        o = query_blocks(
                            lambda qn, qr, keep:
                            sparse.sparse_mla_chunk_attention(
                                qn, qr, w, c2, keep, new_len, layer=ai,
                                rank=cfg.kv_lora_rank, scale=scale),
                            T, q_nope, q_rope, keep)
                    else:
                        o = query_blocks(
                            lambda qn, qr, pos, mask: mla.attend_expanded(
                                cfg, p, qn, qr, (read_block(c2, ai), S), pos,
                                new_len, selected=mask),
                            T, q_nope, q_rope, positions, keep)
                    pick = jnp.where(jnp.arange(idx.shape[-1])[None, None]
                                     < n[..., None], idx, -1)
                out = matmul_any(o.reshape(B, T, -1), p["wo"],
                                 use_kernel=False)
                return out, (c2, ik2, ikt2, pick)

            X, (c, ik, ikt, pick) = residual(X, p, 0, f)
            return (X, c, ik, ikt, St, W), pick

        Kp = (min(topk // pool + 1, G)) * pool
        carry = (mhc.enter(cfg, x), cache.c, cache.ik, cache.ikt, cache.kda,
                 cache.conv)
        counters, routing, picks = [], [], []
        seen = {"K": 0, "A": 0, "F": 0}
        for seg, at, n, kind, _, full in dsa.runs(cfg):
            moe = cfg.segments[seg][0] == "moe"
            attends = cfg.segment_attn[seg] == "A"
            banks, rest = split_banks(model, segs[seg], moe)
            ix = params["indexer"] if attends else None
            first = seen["A" if attends else "K"]

            def body(carry, i, rest=rest, ix=ix, at=at, first=first,
                     full=full, attends=attends, banks=banks):
                p = _index(rest, at + i)
                if attends:
                    carry, pick = attention(carry, p, _index(ix, full + i),
                                            first + i, full + i)
                else:
                    carry = mixer(carry, p, first + i)
                    pick = jnp.zeros((B, T, 0), jnp.int32)
                X, stats = ffn(carry[0], p, banks, at + i)
                return (X,) + tuple(carry[1:]), (stats, pick)

            with jax.named_scope("decode_layer"):
                if n == 1:
                    carry, out = body(carry, 0)
                    out = jax.tree.map(lambda a: a[None], out)
                else:
                    carry, out = lax.scan(body, carry,
                                          jnp.arange(n, dtype=jnp.int32))
            seen["A" if attends else "K"] += n
            (st, chose), pick = out
            if moe:
                counters.append(st)
                routing.append(chose)
            if attends:
                picks.append(pick)
        X, c, ik, ikt, St, W = carry
        stats = None
        if counters:
            chosen = jnp.concatenate(picks) if picks \
                else jnp.zeros((0, B, T, Kp), jnp.int32)
            stats = (jnp.concatenate(counters),
                     (jnp.concatenate(routing), chosen))
        return (mhc.leave(cfg, X, dt),
                LinearSparseCache(c=c, ik=ik, ikt=ikt, kda=St, conv=W,
                                  length=new_len), stats, None)

    # ------------------------------------------------------------ the spans
    def _chosen(self, n):
        """The positions a query at the end of ``n`` reads (arrays): the
        best closed groups whole and its own group up to itself."""
        pool = self.cfg.index_kpool
        return np.minimum((n - 1) // pool, self.cfg.index_topk // pool) \
            * pool + (n - 1) % pool + 1

    def _dsa(self, n) -> dict:
        """Of queries at the ends of ``n`` positions each (an array): the
        positions their attention layers read, the keys their indexers
        score (closed groups; the open group's keys are read unscored and
        counted with them), and those that are live."""
        cfg = self.cfg
        n = np.asarray(n)
        pool = cfg.index_kpool
        closed = (n - 1) // pool
        chosen = int(self._chosen(n).sum())
        scored = int((closed + (n - 1) % pool + 1).sum())
        live = int(n.sum())
        return {"dsa_selected": chosen, "dsa_live": live,
                "dsa_keys_scored": scored,
                "dsa_selected_over_live": chosen / max(live, 1),
                "dsa_keys_scored_over_live": scored / max(live, 1),
                # a row is exactly the latent: nothing beside it is fetched
                "dsa_fetched_over_selected": 1.0 * sparse.row_layout(
                    cfg.latent_dim, self.dtype or cfg.dtype)[0] * 4
                / (cfg.latent_dim * jnp.dtype(self.dtype
                                              or cfg.dtype).itemsize)}

    def sizes(self, state_key: str = "state_bytes_per_slot") -> dict:
        return {**super().sizes(state_key),
                "residual_streams": max(self.cfg.hc_mult, 1)}

    def chunk_meta(self, chunk):
        real = chunk.last_index + 1 if chunk.final else chunk.size
        meta = self._dsa(chunk.start + 1 + np.arange(real))
        attn = self.chunk_kernel(self.flash, chunk.size, self.max_len,
                                 self.dtype or self.cfg.dtype)
        return {**self.sizes(),
                "dsa_selected_over_live": meta["dsa_selected_over_live"],
                "dsa_keys_scored_over_live":
                    meta["dsa_keys_scored_over_live"],
                "tokens_real": real, "tokens_padded": chunk.size - real,
                "attn_live_keys": chunk.start + chunk.size,
                "attn_kernel": attn,
                "scan_kernel": kda.chunk_kernel_ok(self.cfg, attn,
                                                   chunk.size)}

    def step_meta(self, read, pending, lens, running):
        """:meth:`sizes`; from the mirror of the slots' lengths what the
        attention layers select and score; what the step has to move —
        ``state_bytes_step`` (the running slots' delta-rule state, in and
        out, and their tails), ``kv_bytes_step`` (the selected latents and
        the pooled keys scored), ``weight_bytes_step`` (everything but the
        experts' banks), ``expert_bytes_step`` (the held experts touched),
        ``head_bytes_step`` — and the state's share of their sum; the held
        experts' counters."""
        from ...observability.metrics import get_registry

        cfg = self.cfg
        meta = self.sizes()
        held = held_counts(self, read, pending)
        if lens is not None:
            live = lens[lens > 0]
            meta.update(self._dsa(live))
            if self.flash:      # the kernel reads, by its own rule
                meta.update(read_meta(self, live, self._chosen(live),
                                      cfg.index_kpool))
            itemsize = jnp.dtype(self.dtype or cfg.dtype).itemsize
            state = 2 * len(running) * self.slot_bytes
            moved = {
                "state_bytes_step": state,
                "kv_bytes_step": self.attends * meta["dsa_selected"]
                * cfg.latent_dim * itemsize + self.full
                * meta["dsa_keys_scored"] * cfg.index_head_dim * itemsize,
                "weight_bytes_step": self.layer_bytes,
                "expert_bytes_step": int(
                    held.get("experts_touched", 0.0) * self.expert_bytes
                    * sum(n for k, n in cfg.segments if k == "moe")),
                "head_bytes_step": self.head_bytes}
            meta.update(moved, state_share_of_step_bytes=state / max(
                sum(moved.values()), 1))
            reg = get_registry()
            reg.counter("Serve/dsa_selected_positions").inc(
                meta["dsa_selected"])
            reg.counter("Serve/dsa_live_positions").inc(meta["dsa_live"])
            reg.counter("Serve/dsa_pooled_keys_scored").inc(
                meta["dsa_keys_scored"])
            reg.counter("Serve/kda_state_bytes_moved").inc(state)
        meta.update(held)
        return meta


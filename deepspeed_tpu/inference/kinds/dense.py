"""K and V a layer: the cache of every MHA / GQA / MQA trunk, and of a looped
trunk (``cfg.loop_steps > 1``), which is this kind with a plane a pass;
contiguous, or in a pool of pages shared by the serving slots
(``serving/pages.py``), chosen by a ``page_size`` and not by the model."""

from collections import namedtuple
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.decode_attention import LANES, tail_rows
from .base import IN_POOL, Kind
from .steps import _layer_step


# k, v (L, B, KV, hd | vd, max_len): POSITIONS ON THE LANES. HBM tiles the
# last two dims 8 x 128 words, and max_len is a multiple of 128 wherever the
# kernels run, so the buffer has no padding at any head_dim and the decode
# kernels' (KV, hd, 128) blocks are the memory as it lies. With hd last, a
# head_dim of 64 fills half of every tile: the compiler then stores the
# cache the other way round anyway and re-lays every layer's slab out around
# each kernel call (PERF.md F10). Heads-major, so a slot's heads over 128
# positions are one block. ``length``: i32 tokens cached, scalar (all rows
# advance together) or (B,) per-slot (serving/slots.py).
# ``tail`` (L, B, KV, T, hd + vd), or None where the kind keeps none: the
# DEFERRED TAIL, T positions ON THE SUBLANES (T one sublane tile of the
# dtype: 16 rows of bf16), K beside V on the lanes. With positions on the
# lanes one position is one lane of every tile of its block, which no copy
# can write alone: the T == 1 step's kernel had to write the whole block of
# 128 back to append one. With a tail it writes position ``p`` onto row
# ``p % T`` of the slot's tile, and the block once the group of T is
# complete (``ops/decode_attention.py``). So between two steps the blocks
# hold every position before the current group, ``[0, (length - 1) // T *
# T)``, and the tail's rows ``0 .. (length - 1) % T`` the group itself;
# what the blocks hold of that group, and the tile's rows behind the
# length, is not read. Everything but that kernel reads and writes the
# blocks: ``Dense.settled`` puts the group into them, ``Dense.rewound``
# fills the tail from them (what ``Dense.forward`` does behind every T > 1
# forward), and the seat copies the tail with the rest
# (``serving/slots.py``).
KVCache = namedtuple("KVCache", "k v length tail", defaults=(None,))
# Page-pool KV state for the serving slot batch (``serving/pages.py`` has the
# pool's allocator, prefix tree and scratch page 0): fixed-size pages shared
# by all slots, each slot mapping its positions onto pool pages through its
# ``page_table`` row (slots, pages a slot). The pools are in the compute
# dtype, or int8 (``kv_quant_bits=8``) with f32 scales (L, pages, KV,
# page_size) a token a head beside the pages (None in fp mode): quantized on
# append and dequantized at the attention read, never a hoisted copy.
PagedKVCache = namedtuple("PagedKVCache",
                          "k v k_scale v_scale page_table length")


class Dense(Kind):
    """``(L, batch, KV, hd, max_len)`` K beside V of the value width. A
    looped trunk keeps a pass's keys and values apart from every other
    pass's: ``L`` = ``n_layer x loop_steps`` planes, pass ``r``'s layer
    ``l`` at ``r * n_layer + l``; its refusals, its ``exit_pdf`` read-back
    and its span meta are what this kind says when the config loops.
    With a ``page_size``: ``(L, pages, KV, page_size, hd)`` pools — a page
    is far fewer positions than a lane tile, so it keeps ``hd`` last;
    decode-side only (T = 1, or the speculative verify's max_draft + 1):
    prefill runs contiguous and is scattered into pages at insert."""

    cache = KVCache

    def __init__(self, cfg, slots=1, dtype=None, params=None):
        super().__init__(cfg, slots, dtype)
        self.loops = int(getattr(cfg, "loop_steps", 1))
        self.layers = cfg.n_layer * self.loops
        # K beside V, as a row of the deferred tail has them
        self.row_width = cfg.head_dim + getattr(cfg, "v_dim", cfg.head_dim)
        self.exit_pdf = self.loops > 1 and cfg.exit_gate
        if self.loops > 1:
            self.what = (f"a looped trunk (loop_steps={self.loops}) does not "
                         "yet compose with")
            self.refuses = {
                "paged": "the paged pool (page_size): it holds one plane a "
                         "layer",
                "kv_quant": IN_POOL,
                "speculation": "speculation: its verify forward has no pass "
                               "loop under a test",
                "mesh": "a mesh of several devices: no sharding of n_layer x "
                        "loop_steps planes is under a test"}
            self.contiguous_only = (
                "the paged pool holds one plane a layer; a looped trunk's "
                "n_layer x loop_steps planes are contiguous only")
            # what a looped program reads of the weights, from the served
            # tree's shapes: the layers once a pass, and the head (with the
            # closing norm and the gate) once
            params = params or {"layers": (), "tok_embed": ()}
            layers, whole, embed = (
                sum(a.nbytes for a in jax.tree.leaves(tree))
                for tree in (params["layers"], params, params["tok_embed"]))
            self.weight_bytes = (
                self.loops * layers,
                whole - layers - embed * (not cfg.tie_embeddings))

    @staticmethod
    def matches(cfg) -> bool:
        # (duck-typed configs of other trunks have no attention kinds: K/V)
        return getattr(cfg, "attention", "mha") == "mha" \
            and not getattr(cfg, "block_pattern", "") \
            and not getattr(cfg, "attn_pattern", "") \
            and not getattr(cfg, "mixer_pattern", "")

    def deferred_rows(self, dtype=None):
        """T of the deferred tail (``KVCache``): a sublane tile's rows where
        K beside V fill whole lane tiles (``hd + vd`` a multiple of 128:
        GPT-2's 128, Ouro's 256) — a narrower row would be padding in HBM,
        and no copy takes part of a lane tile: such a cache has no tail and
        keeps the block's write-back."""
        return 0 if self.row_width % LANES \
            else tail_rows(dtype or self.cfg.dtype)

    def state(self, batch, dtype=None):
        rows = self.deferred_rows(dtype)
        if not rows:
            return {}
        return {"tail": ((self.layers, batch, self.cfg.kv_heads, rows,
                          self.row_width), dtype or self.cfg.dtype)}

    @staticmethod
    def _group(cache):
        """Of a contiguous cache with a tail, a slot: where the block of up
        to 128 lanes that holds its current group — that of position
        ``length - 1`` — starts (B,), the block's width, and ``(B, T,
        width)`` bool: tail row r is the block's lane s and a live position.
        (One-hot: a product with it moves rows onto lanes, or back, exactly,
        and reads whole lane tiles where a slice of T lanes would be padded
        to them; what it leaves out is zeroed first, since 0 x NaN is NaN
        and nothing behind the live length is read.)"""
        T, S = cache.tail.shape[3], cache.k.shape[4]
        n = jnp.broadcast_to(jnp.minimum(cache.length, S),
                             (cache.k.shape[1],))
        first = jnp.maximum(n - 1, 0) // T * T
        wide = min(LANES, S)
        at = jnp.minimum(first // wide * wide, S - wide)
        row = (first[:, None] + jnp.arange(T))[:, :, None]       # (B, T, 1)
        lane = (at[:, None] + jnp.arange(wide))[:, None, :]      # (B, 1, wide)
        return at, wide, (row == lane) & (row < n[:, None, None])

    @staticmethod
    def _blocks(plane, at, wide: int):
        """``wide`` lanes of every slot's planes from the slot's ``at``."""
        return jax.vmap(partial(lax.dynamic_slice_in_dim, slice_size=wide,
                                axis=3), (1, 0), 1)(plane, at)

    @staticmethod
    def _moved(x, onto, spec, keep):
        """``x``, zero where ``keep`` is not, times the one-hot ``onto``:
        one non-zero term a sum."""
        return jnp.einsum(spec, jnp.where(keep, x, 0), onto.astype(x.dtype),
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    def settled(self, cache):
        """``cache`` with every live position in its planes: the current
        group's live rows go from the tail onto their lanes. What reads a
        slot's K/V anywhere but in the T == 1 step's kernel takes the cache
        through here first (the tail stays true). A cache without a tail is
        settled as it stands."""
        if getattr(cache, "tail", None) is None:
            return cache
        at, wide, live = self._group(cache)
        hd = cache.k.shape[3]

        def put(plane, rows):
            new = jnp.where(live.any(1)[None, :, None, None],
                            self._moved(rows, live, "lbhrd,brs->lbhds",
                                        live.any(2)[None, :, None, :, None]),
                            self._blocks(plane, at, wide))
            return jax.vmap(partial(lax.dynamic_update_slice_in_dim, axis=3),
                            (1, 1, 0), 1)(plane, new, at)

        return cache._replace(k=put(cache.k, cache.tail[..., :hd]),
                              v=put(cache.v, cache.tail[..., hd:]))

    def rewound(self, cache, length=None):
        """A SETTLED ``cache`` standing at ``length`` (its own if None; a
        right-padded final chunk's real tokens), as the T == 1 step takes it
        over: the tail filled from the planes with the group of position
        ``length - 1`` (its rows behind the length: zeros)."""
        if length is not None:
            cache = cache._replace(length=length)
        if getattr(cache, "tail", None) is None:
            return cache
        at, wide, live = self._group(cache)
        return cache._replace(tail=jnp.concatenate(
            [self._moved(self._blocks(plane, at, wide), live,
                         "lbhds,brs->lbhrd", live.any(1)[None, :, None, None])
             for plane in (cache.k, cache.v)], -1))

    def buffers(self, batch, max_len, dtype=None, page_size=0, pages=0):
        if not page_size:
            return super().buffers(batch, max_len, dtype)
        cfg = self.cfg
        pool = ((cfg.n_layer, pages, cfg.kv_heads, page_size, cfg.head_dim),
                dtype or cfg.dtype)
        return {"k": pool, "v": pool}

    def in_place(self, cache):
        # (a page pool's read is a gathered view, gated where it is made)
        return [] if isinstance(cache, PagedKVCache) \
            else super().in_place(cache)

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        cfg = self.cfg
        if isinstance(cache, PagedKVCache):
            def paged_scan(x, layer_in):
                lp, ck, cv, ks, vs = layer_in
                x, *pools = _layer_step(
                    model, x, lp, ck, cv, new_len, positions,
                    flash_decode=fused, paged=(cache.page_table, ks, vs))
                return x, pools

            x, (ck, cv, ks, vs) = lax.scan(
                paged_scan, x, (params["layers"], cache.k, cache.v,
                                cache.k_scale, cache.v_scale))
            return x, cache._replace(k=ck, v=cv, k_scale=ks, v_scale=vs,
                                     length=new_len), None, None

        # the cache is ONE buffer carried through the layer loop and
        # indexed by layer: as the loop's xs/ys every layer's slab is sliced
        # out and written back, and the whole cache copied around the loop
        # (the deferred tail rides with the planes where the cache has one:
        # the T == 1 step's kernel alone reads and writes it)
        def scan_fn(carry, layer_in):
            x, ck, cv, *tails = carry
            lp, layer = layer_in
            return _layer_step(model, x, lp, ck, cv, new_len, positions,
                               flash_decode=fused, layer=layer,
                               tail=tails[0] if tails else None), None

        def stack(carry, plane0=None):
            """Every layer once; ``plane0`` (traced): the cache plane of
            layer 0 in a looped trunk's later passes."""
            first = 0
            for (_, n), seg in zip(cfg.segments,
                                   model.segment_params(params["layers"])):
                planes = jnp.arange(first, first + n, dtype=jnp.int32)
                carry, _ = lax.scan(
                    scan_fn, carry,
                    (seg, planes if plane0 is None else plane0 + planes))
                first += n
            return carry

        passes = None
        kv = (cache.k, cache.v) + (() if cache.tail is None
                                   else (cache.tail,))
        if self.loops > 1:
            # the passes are a loop of the program too (one layer body):
            # each appends to and reads from its own n_layer planes
            def one_pass(x, kv, r):
                x, *kv = stack((x, *kv), r * cfg.n_layer)
                return x, tuple(kv)

            x, kv, passes = model.loop_passes(params, x, kv, one_pass)
        else:
            x, *kv = stack((x, *kv))
        new = cache._replace(**dict(zip(("k", "v", "tail"), kv)),
                             length=new_len)
        # a T > 1 forward wrote the planes: the tail follows them, for the
        # step to take over (a T == 1 step off the kernels leaves the tail
        # alone: it is the kernels', which this cache's steps do not run)
        return (x, new if fused or x.shape[1] == 1 else self.rewound(new),
                None, passes)

    def _loop_meta(self, tokens: int, head: bool = True) -> dict:
        """What a looped trunk's spans say beside their times: the passes,
        the cache planes and bytes a token costs, and the bytes of weights
        the program reads for each of the ``tokens`` it works on."""
        layer_bytes, head_bytes = self.weight_bytes
        return {"loop_steps": self.loops,
                "cache_planes": self.layers,
                "cache_bytes_per_token": self.token_bytes,
                "weight_bytes_per_token":
                    (layer_bytes + head * head_bytes) / max(tokens, 1)}

    def step_meta(self, read, pending, lens, running):
        """A looped trunk's: :meth:`_loop_meta` over the rows the step ran
        and, where the trunk has its gate, ``exit_pdf``: the mean over their
        slots of the distribution over exit passes (``read[0]``, (slots,
        passes)); the chunks' means go onto their own spans."""
        if self.loops == 1:
            return {}
        for (chunk_span, _, _), pdf in zip(pending, read[1:]):
            chunk_span.amend(exit_pdf=pdf.tolist())
        running = list(running)
        meta = self._loop_meta(len(running))
        if read and running:
            meta["exit_pdf"] = read[0][running].mean(0).tolist()
        return meta

    def chunk_meta(self, chunk):
        if self.loops == 1:
            return {}
        return self._loop_meta(chunk.last_index + 1 if chunk.final
                               else chunk.size, head=chunk.final)

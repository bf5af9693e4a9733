"""K and V a layer: the cache of every MHA / GQA / MQA trunk, and of a looped
trunk (``cfg.loop_steps > 1``), which is this kind with a plane a pass;
contiguous, or in a pool of pages shared by the serving slots
(``serving/pages.py``), chosen by a ``page_size`` and not by the model."""

from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax

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
KVCache = namedtuple("KVCache", "k v length")
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
            and not getattr(cfg, "attn_pattern", "")

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
        def scan_fn(carry, layer_in):
            x, ck, cv = carry
            lp, layer = layer_in
            return _layer_step(model, x, lp, ck, cv, new_len, positions,
                               flash_decode=fused, layer=layer), None

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
        if self.loops > 1:
            # the passes are a loop of the program too (one layer body):
            # each appends to and reads from its own n_layer planes
            def one_pass(x, kv, r):
                x, ck, cv = stack((x, *kv), r * cfg.n_layer)
                return x, (ck, cv)

            x, (ck, cv), passes = model.loop_passes(
                params, x, (cache.k, cache.v), one_pass)
        else:
            x, ck, cv = stack((x, cache.k, cache.v))
        return x, KVCache(k=ck, v=cv, length=new_len), None, passes

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

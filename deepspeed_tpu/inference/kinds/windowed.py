"""The cache of a trunk of window layers beside full ones
(``cfg.attn_pattern``, ``models/windowed.py``): planes for the FULL layers
only, laid out as ``KVCache``'s with values ``v_dim`` wide beside keys of
``head_dim``; and for each WINDOW layer a ring ``wk`` / ``wv`` of
``ring_len(cfg)`` positions a slot (two 128-lane blocks for a window of 128),
position ``p`` at ``p % ring``, with that kind's KV heads: it stops growing
where a plane goes on."""

import math
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...models import windowed
from ...models.transformer import _norm
from ...ops import decode_attention as da     # (tests patch its function)
from .base import (IN_POOL, MOVES_PAGES, Kind, held_counts, split_banks,
                   stacked)
from .steps import _append_attend, _dense_append, _out_ffn


WindowedCache = namedtuple("WindowedCache", "k v wk wv length")


def _ring_update(ring, new, layer, start, end):
    """Layer ``layer`` of the ring buffer ``(L, B, KV, w, R)`` after a chunk
    wrote positions ``start .. end - 1`` (``new`` (B, T, KV, w) holds
    ``start .. start + T - 1``; what lies at or behind ``end`` is padding):
    ring place ``r`` holds the last position < ``end`` that is ``r`` mod
    ``R`` — the chunk's where it reaches that far back, else what it held."""
    R, T = ring.shape[4], new.shape[1]
    r = jnp.arange(R, dtype=jnp.int32)
    pos = end - 1 - (end - 1 - r) % R
    old = lax.dynamic_index_in_dim(ring, layer, keepdims=False)
    took = jnp.take(new.transpose(0, 2, 3, 1).astype(ring.dtype),
                    jnp.clip(pos - start, 0, T - 1), axis=3)
    slab = jnp.where((pos >= start) & (pos >= 0), took, old)
    return lax.dynamic_update_slice(ring, slab[None], (layer, 0, 0, 0, 0))


def _ring_before(ring, layer, start, n: int):
    """The ``n`` positions before ``start`` out of layer ``layer`` of the
    ring, in order: ``(B, KV, w, n)`` (the caller masks what lies before
    position 0)."""
    R = ring.shape[4]
    at = (start - n + jnp.arange(n, dtype=jnp.int32)) % R
    return jnp.take(lax.dynamic_index_in_dim(ring, layer, keepdims=False),
                    at, axis=3)


def _ring_attend(q, rk, rv, length, window: int, sink):
    """The T = 1 read of a ring in plain XLA: ``q`` (B, 1, H, hd) over one
    layer's ring (B, KV, ., R) of a slot at ``length`` (B,) after the
    append. Ring place ``r`` holds position ``length - 1 - (length - 1 - r)
    % R``; the window keeps ``length - window .. length - 1``."""
    B, _, H, hd = q.shape
    KV, R = rk.shape[1], rk.shape[3]
    n = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    r = jnp.arange(R, dtype=jnp.int32)[None]
    pos = n[:, None] - 1 - (n[:, None] - 1 - r) % R
    keep = ((pos >= 0) & (pos >= n[:, None] - window))[:, None, None]
    qg = q[:, 0].reshape(B, KV, H // KV, hd)
    s = jnp.einsum("bkgd,bkdr->bkgr", qg, rk.astype(q.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    s = jnp.where(keep, s, da.BIG_NEG)
    top = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, KV, H // KV, 1)
        top = jnp.maximum(top, sk)
    pr = jnp.where(keep, jnp.exp(s - top), 0.0)
    den = jnp.sum(pr, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - top)
    o = jnp.einsum("bkgr,bkvr->bkgv", pr.astype(rv.dtype), rv,
                   preferred_element_type=jnp.float32)
    return (o / jnp.maximum(den, 1e-30)).astype(q.dtype).reshape(
        B, 1, H, rv.shape[2])


class Windowed(Kind):
    cache = WindowedCache
    recurrent = True         # a ring is never rewound
    refuses = {
        "paged": "the paged pool and prefix sharing (page_size): a page "
                 "holds one K/V width for every layer, and a ring has no "
                 "pages; a shared prefix would need the rings as they stood "
                 "at the prefix's end",
        "kv_quant": IN_POOL,
        "speculation": "speculation: a rejected draft would have to take its "
                       "columns back out of the rings, and the model's own "
                       "drafting layers (MTP) are not held",
        "host_kv": MOVES_PAGES,
        "quantize": "weight-only quantization: the two kinds' projections "
                    "take dense weights",
        "mesh": "a mesh of several devices: the ring kernel has no "
                "shard_map rule and the experts held are told by the "
                "configuration, no axis exchanges rows yet"}
    contiguous_only = ("the paged pool holds pages of one K/V width for "
                       "every layer; full-layer planes beside window rings "
                       "are contiguous only")

    def __init__(self, cfg, *serving):
        super().__init__(cfg, *serving)
        self.what = (f"window layers beside full ones (attn_pattern="
                     f"{cfg.attn_pattern!r}) do not yet compose with")
        self.moe_stats = any(ffn == "moe" for ffn, _ in cfg.segments)
        self.layers = cfg.attn_pattern.count("G")

    @staticmethod
    def matches(cfg) -> bool:
        return bool(getattr(cfg, "attn_pattern", "")) \
            and getattr(cfg, "attention", "mha") == "mha"

    def state(self, batch, dtype=None):
        cfg = self.cfg
        rings = self.kv_planes(cfg.attn_pattern.count("S"), batch,
                               windowed.ring_len(cfg), dtype,
                               cfg.attn_kv_heads("S"))
        return {"wk": rings["k"], "wv": rings["v"]}

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        """Each run of layers equal in (attention kind, FFN kind) over its
        own stacked weights, all of them carrying the cache's four buffers,
        a layer touching only its kind's two. The T == 1 step runs
        ``decode_attention`` under two names, both appending in place:
        ``full_decode_attention`` over a full layer's live blocks,
        ``window_decode_attention`` over the one or two ring blocks a window
        layer's last ``window`` positions lie in, the sink in the sum. T > 1
        appends with XLA's update — into the ring the last ``ring`` of the
        chunk's REAL positions (``valid``) — and attends in blocks: a full
        layer over its plane's live key blocks, a window layer over the
        chunk and the ``window - 1`` positions the ring held before it."""
        cfg = self.cfg
        T = x.shape[1]
        per_slot = getattr(new_len, "ndim", 0) == 1
        ring = cache.wk.shape[4]
        start = None if per_slot else new_len - T
        end = None if per_slot else start + (T if valid is None else valid)

        def layer_fn(carry, p, idx, local, kind, banks):
            x, ck, cv, wk, wv = carry
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            q, k, v = windowed.project(cfg, y, p, positions, kind)
            sink = p.get("sink")
            if kind == "G":
                if T == 1:
                    o, ck, cv = _append_attend(
                        q, ck, cv, k, v, idx, new_len, fused,
                        name="full_decode_attention")
                else:
                    # the chunk into the carried planes, and the read block
                    # by block out of them, by layer — no slab is sliced out
                    ck, cv = (lax.dynamic_update_slice(
                        c, n.transpose(0, 2, 3, 1)[None].astype(c.dtype),
                        (idx, 0, 0, 0, start)) for c, n in ((ck, k), (cv, v)))
                    o = windowed.attend_blocks(q, ck, cv, positions, new_len,
                                               layer=idx)
            elif fused:
                o, wk, wv = da.decode_attention(
                    q, wk, wv, new_len, k=k, v=v, layer=idx,
                    window=cfg.window, sink=sink,
                    name="window_decode_attention")
            elif T == 1:
                # the new column at its ring place, then the ring densely
                at = jnp.where(new_len > 0, (new_len - 1) % ring + 1, 0)
                slab_k, wk = _dense_append(wk, k, idx, at)
                slab_v, wv = _dense_append(wv, v, idx, at)
                o = _ring_attend(q, slab_k, slab_v, new_len, cfg.window, sink)
            else:
                before = windowed.prev_len(cfg)
                o = windowed.attend_window(
                    q, k, v, _ring_before(wk, idx, start, before),
                    _ring_before(wv, idx, start, before), start, cfg.window,
                    sink)
                wk = _ring_update(wk, k, idx, start, end)
                wv = _ring_update(wv, v, idx, start, end)
            x, stats = _out_ffn(model, x, o, p, banks, local)
            return (x, ck, cv, wk, wv), stats

        carry = (x, cache.k, cache.v, cache.wk, cache.wv)
        seen = {"G": 0, "S": 0}
        stats = []
        for (ffn, n), kind, seg in zip(cfg.segments, cfg.segment_attn,
                                       model.segment_params(params["layers"])):
            banks, rest = split_banks(model, seg, ffn == "moe")
            with jax.named_scope("decode_layer"):
                carry, out = lax.scan(
                    lambda c, xs, kind=kind, banks=banks: layer_fn(
                        c, *xs, kind, banks), carry,
                    (rest,
                     jnp.arange(seen[kind], seen[kind] + n, dtype=jnp.int32),
                     jnp.arange(n, dtype=jnp.int32)))
            seen[kind] += n
            if ffn == "moe":
                stats.append(out)
        x, k, v, wk, wv = carry
        return (x, WindowedCache(k=k, v=v, wk=wk, wv=wv, length=new_len),
                stacked(stats), None)

    def sizes(self):
        return super().sizes("window_bytes_per_slot")

    def chunk_meta(self, chunk):
        """:meth:`sizes` (a cached token: the full layers' planes alone; a
        slot: its rings) and ``key_blocks_walked_over_live``: the key blocks
        (``windowed.KEY_BLOCK``) a full layer's queries walk over those that
        hold a key some row of the chunk may see — 1: the walk stops at the
        live length."""
        walked = -(-(chunk.start + chunk.size) // windowed.KEY_BLOCK)
        real = chunk.last_index + 1 if chunk.final else chunk.size
        return {**self.sizes(), "key_blocks_walked_over_live":
                walked / -(-(chunk.start + real) // windowed.KEY_BLOCK)}

    def step_meta(self, read, pending, lens, running):
        """:meth:`sizes`; ``window_fetched_over_live`` (the positions the
        window layers' kernel fetches — the one or two ring blocks of 128
        that hold a running slot's last ``window`` positions — over the
        positions inside the running slots' windows: 1 ideal, 2 with both
        ring blocks); and :func:`held_counts` of the step's expert layers."""
        meta = self.sizes()
        if lens is not None:
            n = lens[lens > 0]
            w = self.cfg.window
            blocks = -(-n // da.LANES) - np.maximum(n - w, 0) // da.LANES
            inside = int(np.minimum(n, w).sum())
            # the lengths the kernels' rooflines are reckoned from
            meta.update(live_positions=int(n.sum()), window_live=inside,
                        window_fetched_over_live=float(
                            da.LANES * blocks.sum() / max(inside, 1)))
        meta.update(held_counts(self, read, pending))
        return meta

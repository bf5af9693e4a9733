"""Prefill + single-token decode with a static-shape KV cache.

Reference analog: the fused inference kernels and KV-cache workspace of
``csrc/transformer/inference/`` (``softmax_context`` = attention over the
cache, ``inference_context.h`` = the cache allocator). TPU-native: the cache
is a pair of ``(L, B, KV, hd, max_len)`` arrays; attention over the cache
masks positions beyond the current length, so every decode step has an
identical static shape (one compiled program for the whole generation).
The T == 1 step carries the cache through its layer loop as ONE donated
buffer that only one kernel touches (``ops/decode_attention.py``:
``decode_attention`` takes the step's new K/V, puts them into the last
live block it has fetched anyway, attends, and writes that block back in
place, by layer); T > 1 (prefill, speculative verify) appends with
``dynamic_update_slice`` and attends densely over the same layout.

The attention kind decides the cache (:func:`cache_layout`). Latent
attention (``cfg.attention == "mla"``, ``models/mla.py``) keeps ONE buffer
``(L, B, rank + rope, max_len)`` of what its layers share between heads
(:class:`LatentCache`), written by the latent projection and read two ways:
T > 1 expands K and V from the live prefix's latents block by block and
attends as published; the T == 1 step appends in place and attends absorbed
(``ops/mla_attention.py``). No expanded K or V is ever stored.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models.transformer import TransformerConfig, _activation, _norm, _rope
from ..platform.mesh import BATCH_AXES, constrain
from .quantization import (QuantizedTensor, dequant_rows, matmul_any,
                           tp_quant_dot, woq_dot, woq_dot_t)

# Host constant, NOT jnp.float32(...): a device constant here would run a
# computation at import time and initialize the XLA backend — which breaks
# multi-host jobs that must call jax.distributed.initialize() first.
BIG_NEG = -2.0 ** 30


class KVCache(NamedTuple):
    # (L, B, KV, hd, max_len): POSITIONS ON THE LANES. HBM tiles the last
    # two dims 8 x 128 words, and max_len is a multiple of 128 wherever the
    # kernels run, so the buffer has no padding at any head_dim and the
    # decode kernels' (KV, hd, 128) blocks are the memory as it lies. With
    # hd last, a head_dim of 64 fills half of every tile: the compiler then
    # stores the cache the other way round anyway and re-lays every layer's
    # slab out around each kernel call (PERF.md F10).
    # Heads-major, so a slot's heads over 128 positions are one block.
    k: jnp.ndarray           # (L, B, KV, hd, max_len)
    v: jnp.ndarray           # (L, B, KV, hd, max_len)
    length: jnp.ndarray      # i32 tokens cached: scalar (all rows advance
                             # together) or (B,) per-slot (serving/slots.py)


class LatentCache(NamedTuple):
    """The cache of latent attention: per position the layer's normed
    ``c`` (``kv_lora_rank`` values) and roped ``k_rope``
    (``qk_rope_head_dim``), one for all heads — positions on the lanes as
    in :class:`KVCache`, for the same reason."""

    c: jnp.ndarray           # (L, B, rank + rope, max_len)
    length: jnp.ndarray      # as KVCache.length


class PagedKVCache(NamedTuple):
    """Page-pool KV state for the serving slot batch (serving/pages.py).

    The contiguous per-slot cache above owns ``max_len`` positions per
    slot whether or not they are ever written; the paged layout instead
    pools fixed-size pages shared by all slots, and each slot maps its
    logical positions onto pool pages through an integer ``page_table``
    row. Identical prompt prefixes can then point at the SAME physical
    pages (host-side radix tree, refcounted) — prefilled once, shared
    copy-free. Pool page 0 is a reserved scratch page: idle slots' table
    rows (and the shared-page entries of an insert) are redirected there,
    so a retired or not-yet-placed row's appends can never touch live
    data.

    ``k``/``v`` are the pools in the compute dtype, or int8 when the KV
    cache itself is quantized (``kv_quant_bits=8``); then ``k_scale`` /
    ``v_scale`` hold symmetric per-token per-head scales alongside the
    pages (``None`` in fp mode), quantized on append and dequantized at
    the attention read — the same point-of-use dispatch discipline as the
    WOQ weight path (never a hoisted dequantized copy of the pool)."""

    k: jnp.ndarray            # (L, pages, KV, page_size, hd) fp or int8
    v: jnp.ndarray            # (L, pages, KV, page_size, hd) fp or int8
    k_scale: "jnp.ndarray | None"   # (L, pages, KV, page_size) f32 | None
    v_scale: "jnp.ndarray | None"   # (L, pages, KV, page_size) f32 | None
    page_table: jnp.ndarray   # (slots, pages_per_slot) i32 pool page ids
    length: jnp.ndarray       # (slots,) i32 tokens cached per slot

    @property
    def page_size(self) -> int:
        return self.k.shape[3]


class HybridCache(NamedTuple):
    """The cache of a trunk of one mixer a layer (``cfg.block_pattern``,
    ``models/hybrid.py``): K/V planes for the attention layers ONLY, laid
    out as :class:`KVCache`'s, beside what does not grow with the position —
    per Mamba-2 layer and slot a float32 SSM state and the conv's last
    ``K - 1`` inputs (``models/ssm.py``). Every buffer has the slot second,
    so ``serving/slots.py`` seats a request by overwriting the slot's whole
    extent of each: a successor never reads its predecessor's state."""

    k: jnp.ndarray           # (attention layers, B, KV, hd, max_len)
    v: jnp.ndarray           # (attention layers, B, KV, hd, max_len)
    ssm: jnp.ndarray         # (Mamba layers, B, H, P, N) float32
    conv: jnp.ndarray        # (Mamba layers, B, K - 1, conv channels)
    length: jnp.ndarray      # as KVCache.length


class WindowedCache(NamedTuple):
    """The cache of a trunk of window layers beside full ones
    (``cfg.attn_pattern``, ``models/windowed.py``): planes for the FULL
    layers only, laid out as :class:`KVCache`'s with values ``v_dim`` wide
    beside keys of ``head_dim``; and for each WINDOW layer a ring of
    ``ring_len(cfg)`` positions a slot (two 128-lane blocks for a window of
    128), position ``p`` at ``p % ring``, with that kind's KV heads: it
    stops growing where a plane goes on. Every buffer has the slot second,
    so ``serving/slots.py`` seats a request by overwriting the slot's whole
    extent of each: a successor never reads its predecessor's ring."""

    k: jnp.ndarray           # (full layers, B, KV, hd, max_len)
    v: jnp.ndarray           # (full layers, B, KV, vd, max_len)
    wk: jnp.ndarray          # (window layers, B, window KV, hd, ring)
    wv: jnp.ndarray          # (window layers, B, window KV, vd, ring)
    length: jnp.ndarray      # as KVCache.length


class CCACache(NamedTuple):
    """The cache of compressed convolutional attention (``cfg.attention ==
    "cca"``, ``models/cca.py``): K/V planes laid out as :class:`KVCache`'s —
    the attention runs in the latent, so they are ``n_kv_head x head_dim``
    wide, an eighth of the model — beside, per layer and slot, the **tail**
    the two convolutions and the value shift reach back into: the last rows
    of ``z`` and ``z1`` and of the shifted value's projection
    (``cca.tail_width`` values whatever the length). The slot is second in
    every buffer, so ``serving/slots.py`` seats a request by overwriting the
    slot's whole extent of each: a successor never reads its predecessor's
    last positions."""

    k: jnp.ndarray           # (L, B, KV, hd, max_len)
    v: jnp.ndarray           # (L, B, KV, hd, max_len)
    tail: jnp.ndarray        # (L, B, cca.tail_width)
    length: jnp.ndarray      # as KVCache.length


def cache_layout(cfg: TransformerConfig, batch: int, max_len: int,
                 dtype=None, *, page_size: int = 0, pages: int = 0) -> tuple:
    """(shape, dtype) of one cache buffer (K or V; for latent attention
    the one buffer of latents) — the single source of truth shared by :func:`init_cache`, the serving slot allocator
    (``serving/slots.py``), and the paged pool allocator
    (``serving/pages.py``), so a prefilled request's cache can be written
    into its slot (or scattered into its pages) with no relayout.

    ``page_size=0`` (default) is the contiguous per-slot layout
    ``(L, batch, KV, hd, max_len)``, positions on the lanes (see
    :class:`KVCache`); ``page_size > 0`` is the pooled page layout
    ``(L, pages, KV, page_size, hd)`` — a page is ``page_size`` whole
    positions, far fewer than a lane tile, so it keeps ``hd`` last; the
    gather over a slot's page-table row (:func:`_paged_view`) and the
    bridges in ``serving/pages.py`` turn pages into the contiguous view.

    Latent attention (``cfg.latent_dim > 0``): ``(L, batch, rank + rope,
    max_len)``, contiguous only.

    A looped trunk (``cfg.loop_steps > 1``) keeps a pass's keys and values
    apart from every other pass's: ``L`` = ``n_layer x loop_steps`` planes,
    pass ``r``'s layer ``l`` at ``r * n_layer + l``, contiguous only."""
    # (duck-typed configs of other trunks have no attention kinds: K/V)
    if getattr(cfg, "attn_pattern", ""):
        # window layers beside full ones: planes for the full layers only
        # (V's are value_shape()'s); the window layers' rings are
        # state_layout()'s
        if page_size > 0:
            raise NotImplementedError(
                "the paged pool holds pages of one K/V width for every "
                "layer; full-layer planes beside window rings are "
                "contiguous only")
        return ((cfg.attn_pattern.count("G"), batch, cfg.kv_heads,
                 cfg.head_dim, max_len), dtype or cfg.dtype)
    pattern = getattr(cfg, "block_pattern", "")
    if pattern:
        # one mixer a layer: planes for the attention layers only; what the
        # other layers keep is state_layout()'s
        if page_size > 0:
            raise NotImplementedError(
                "the paged pool holds pages of K and V; a recurrent state "
                "beside them has no pages: contiguous only")
        return ((pattern.count("*"), batch, cfg.kv_heads, cfg.head_dim,
                 max_len), dtype or cfg.dtype)
    if getattr(cfg, "attention", "") == "cca" and page_size > 0:
        raise NotImplementedError(
            "the paged pool holds pages of K and V; the conv tail a slot "
            "beside them has no pages: contiguous only")
    loops = getattr(cfg, "loop_steps", 1)
    if loops > 1 and page_size > 0:
        raise NotImplementedError(
            "the paged pool holds one plane a layer; a looped trunk's "
            "n_layer x loop_steps planes are contiguous only")
    if getattr(cfg, "latent_dim", 0):
        if page_size > 0:
            raise NotImplementedError(
                "the paged pool holds K and V pages; a latent cache is "
                "contiguous only")
        return ((cfg.n_layer, batch, cfg.latent_dim, max_len),
                dtype or cfg.dtype)
    if page_size > 0:
        return ((cfg.n_layer, pages, cfg.kv_heads, page_size, cfg.head_dim),
                dtype or cfg.dtype)
    return ((cfg.n_layer * loops, batch, cfg.kv_heads, cfg.head_dim,
             max_len), dtype or cfg.dtype)


def value_shape(cfg: TransformerConfig, shape: tuple) -> tuple:
    """The V buffer's shape beside a contiguous K buffer of ``shape``
    (:func:`cache_layout`): the same, but for a model whose values are
    another width than its keys (``v_dim``)."""
    if len(shape) != 5 or getattr(cfg, "latent_dim", 0):
        return shape
    return shape[:3] + (getattr(cfg, "v_dim", shape[3]),) + shape[4:]


def state_layout(cfg: TransformerConfig, batch: int, dtype=None) -> dict:
    """{name: (shape, dtype)} of what a cache holds per slot whatever the
    position — a ``block_pattern`` trunk's Mamba-2 layers' SSM state and
    conv window (:class:`HybridCache`), an ``attn_pattern`` trunk's window
    layers' rings (:class:`WindowedCache`), a ``cca`` trunk's conv tails
    (:class:`CCACache`); {} for every other trunk."""
    if getattr(cfg, "attention", "") == "cca":
        from ..models.cca import tail_width

        return {"tail": ((cfg.n_layer, batch, tail_width(cfg)),
                         dtype or cfg.dtype)}
    if getattr(cfg, "attn_pattern", ""):
        from ..models.windowed import ring_len

        n = cfg.attn_pattern.count("S")
        kv, ring = cfg.attn_kv_heads("S"), ring_len(cfg)
        return {"wk": ((n, batch, kv, cfg.head_dim, ring), dtype or cfg.dtype),
                "wv": ((n, batch, kv, cfg.v_dim, ring), dtype or cfg.dtype)}
    pattern = getattr(cfg, "block_pattern", "")
    if "M" not in pattern:
        return {}
    from ..models.ssm import state_shapes

    n = pattern.count("M")
    return {name: ((n,) + shape,
                   jnp.float32 if name == "ssm" else dtype or cfg.dtype)
            for name, shape in state_shapes(cfg, batch).items()}


def state_bytes_per_slot(cfg: TransformerConfig, dtype=None) -> int:
    """Bytes a slot's fixed-size state costs, from :func:`state_layout`."""
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in state_layout(cfg, 1, dtype).values())


def cache_buffers(shape: tuple) -> int:
    """How many buffers of ``shape`` (a contiguous :func:`cache_layout`) a
    cache holds: K and V ``(L, B, KV, hd, max_len)``, or the one buffer of
    latents ``(L, B, rank + rope, max_len)``."""
    return 1 if len(shape) == 4 else 2


def cache_bytes_per_token(cfg: TransformerConfig, dtype=None) -> int:
    """Bytes one cached position costs over all layers, from
    :func:`cache_layout`."""
    shape, dt = cache_layout(cfg, 1, 1, dtype)
    if cache_buffers(shape) == 1:
        return math.prod(shape) * jnp.dtype(dt).itemsize
    return (math.prod(shape) + math.prod(value_shape(cfg, shape))) \
        * jnp.dtype(dt).itemsize


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None, length_shape: tuple = ()):
    """An empty cache of the model's kind; ``length_shape`` () for rows that
    advance together, (batch,) for serving slots."""
    state = state_layout(cfg, batch, dtype)
    shape, dtype = cache_layout(cfg, batch, max_len, dtype)
    length = jnp.zeros(length_shape, jnp.int32)
    if state:
        kind = WindowedCache if "wk" in state else \
            CCACache if "tail" in state else HybridCache
        return kind(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(value_shape(cfg, shape), dtype),
            length=length, **{name: jnp.zeros(sh, dt)
                              for name, (sh, dt) in state.items()})
    if cache_buffers(shape) == 1:
        return LatentCache(c=jnp.zeros(shape, dtype), length=length)
    return KVCache(k=jnp.zeros(shape, dtype),
                   v=jnp.zeros(value_shape(cfg, shape), dtype),
                   length=length)


def _decode_kernel_ok(flash_decode: bool, T: int, max_len: int,
                      *dtypes) -> bool:
    """Whether a T-token forward over a cache of ``max_len`` positions runs
    the Pallas decode kernels (``ops/decode_attention.py``) — the ONE gate
    :func:`forward_with_cache` (which loop to build) and
    :func:`_cache_attend` share."""
    # Mosaic has no f16: an fp16 engine (or an externally-built fp16 KV
    # cache under a bf16 trunk) must take the dense path on TPU instead of
    # failing Mosaic compilation inside the decode scan — same gate and
    # one-shot warning as flash_attention's.
    f16_in = any(jnp.dtype(d) == jnp.float16 for d in dtypes) \
        and jax.default_backend() == "tpu"
    if f16_in and flash_decode:
        from ..utils.logging import warning_once

        warning_once(
            "decode: float16 q/KV-cache falls back to the dense XLA "
            "cache attention on TPU (Mosaic has no f16). The dense "
            "path materializes (B, H, 1, max_len) scores per step — "
            "prefer bf16 compute for long generations.")
    # TPU lane tiling wants full 128-wide blocks: generate_tokens pads the
    # cache to a 128 multiple when flash_decode is on, so this gate only
    # declines externally-built odd caches (which take the dense path
    # rather than risking an unaligned Pallas tile on hardware).
    return (flash_decode and not f16_in and T == 1 and max_len % 128 == 0)


def _cache_attend(q, ck, cv, length, flash_decode: bool = False, bias=None,
                  alibi=None):
    """q: (B, T, H, hd) vs cache (B, KV, hd, max_len); positions >= length
    masked. For prefill T = prompt len (with causal offset); decode T = 1.

    ``length`` is a scalar (all rows at the same position — the
    single-request generate() path) or a (B,) vector of per-row lengths
    (the serving slot batch, where every slot is at its own position).
    The per-row math is the same expressions with a batch dim on the
    position grid; masked scores underflow to exactly 0 after softmax, so
    a row's output depends only on its own live positions.

    ``bias`` is an additive (H, T, max_len) score bias; ``alibi`` is the
    (H,) slope vector — preferred over a materialized bias because the
    streaming kernel reconstructs the distance ramp in-kernel, so Bloom
    decode stays on the fused path. ``flash_decode`` routes the T == 1
    hot path to the Pallas streaming kernel (ops/decode_attention.py)
    instead of materializing the full (B, H, 1, max_len) score tensor."""
    B, T, H, hd = q.shape
    max_len = ck.shape[3]
    if bias is None and _decode_kernel_ok(flash_decode, T, max_len,
                                          q.dtype, ck.dtype, cv.dtype):
        from ..ops.decode_attention import decode_attention

        return decode_attention(q, ck, cv, length, alibi_slopes=alibi)
    KV = ck.shape[1]
    if KV != H:
        ck = jnp.repeat(ck, H // KV, axis=1)
        cv = jnp.repeat(cv, H // KV, axis=1)
    scores = jnp.einsum("bthd,bhds->bhts", q, ck).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if getattr(length, "ndim", 0) == 1:
        # per-slot lengths: the position grid gains a batch dim; an
        # externally materialized bias has no per-row layout, so only the
        # in-house alibi slopes are supported here
        if bias is not None:
            raise ValueError("per-slot lengths don't compose with a "
                             "materialized (H, T, max_len) bias — pass "
                             "alibi slopes instead")
        t_pos = length[:, None, None] - T \
            + jnp.arange(T)[None, :, None]               # (B, T, 1)
        s_pos = jnp.arange(max_len)[None, None, :]       # (1, 1, max_len)
        if alibi is not None:
            rel = (s_pos - t_pos).astype(jnp.float32)    # (B, T, max_len)
            scores = scores + alibi[None, :, None, None] * rel[:, None]
        keep = s_pos <= t_pos                            # (B, T, max_len)
        scores = jnp.where(keep[:, None], scores, BIG_NEG)
    else:
        # query t sits at global position length - T + t; key at slot s —
        # ONE set of position math drives both the alibi bias and the mask
        t_pos = length - T + jnp.arange(T)[:, None]      # (T, 1)
        s_pos = jnp.arange(max_len)[None, :]             # (1, max_len)
        if alibi is not None:
            rel = (s_pos - t_pos).astype(jnp.float32)    # (T, max_len)
            ab = alibi[:, None, None] * rel[None]        # (H, T, max_len)
            bias = ab if bias is None else bias + ab
        if bias is not None:
            scores = scores + bias[None]
        keep = s_pos <= t_pos                            # (T, max_len)
        scores = jnp.where(keep[None, None], scores, BIG_NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhds->bthd", probs, cv)


def quantize_kv(x, axis: int = -1):
    """Symmetric int8 quantization of appended KV values: per-head scales
    (one fp32 scale per token per head over the ``hd`` axis), the KV-cache
    analog of the WOQ weight path's per-channel groups. ``quantize →
    dequantize → quantize`` is idempotent at these scales (the max
    element round-trips to exactly ±127), which is what lets a hydrated
    shared prefix re-insert without drift."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.expand_dims(scale, axis)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype, axis: int = -1):
    """Inverse of :func:`quantize_kv` at the point of use — the ONE
    spelling shared by the shared-prefix hydrate gather
    (``serving/pages.py``) and the host-tier restore scatter
    (``serving/hostkv.py``), so a page's bytes dequantize identically
    whether they come from the live pool or from pinned host memory."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale, axis)).astype(dtype)


def _paged_append(ck, cv, ks, vs, k, v, page_table, new_len):
    """Append T decode tokens' K/V per slot into the page pool.

    ``ck``/``cv`` are one layer's pools ``(pages, KV, page_size, hd)``;
    ``k``/``v`` the new projections ``(B, T, KV, hd)``; ``new_len`` the
    (B,) post-append lengths. Row ``b``'s token ``j`` writes position
    ``new_len[b] - T + j``, which maps through its ``page_table`` row to
    (pool page, in-page offset) — one scatter per pool covering all B·T
    writes. T == 1 is the plain decode step; T > 1 is the speculative
    verify forward (``serving/engine.py``), whose headroom gate
    guarantees every live row has ``new_len <= max_len`` so the clip
    below never folds a live write back onto the row's last page. A row
    that is not running has ``new_len`` 0 (:func:`forward_with_cache`)
    and writes nowhere: its page id is put behind the pool and the
    scatter drops it, whatever its table row still holds (the host
    clears a retired row's table only at the end of its iteration)."""
    B, T = k.shape[0], k.shape[1]
    ps, n = ck.shape[2], page_table.shape[1]
    pos = (new_len - T)[:, None] + jnp.arange(T, dtype=new_len.dtype)[None, :]
    pidx = jnp.clip(pos // ps, 0, n - 1)
    pid = jnp.take_along_axis(page_table, pidx, axis=1)     # (B, T)
    pid = jnp.where((new_len > 0)[:, None], pid, ck.shape[0])
    off = pos % ps
    if ks is not None:
        qk, sk = quantize_kv(k)
        qv, sv = quantize_kv(v)
        ck = ck.at[pid, :, off, :].set(qk, mode="drop")
        cv = cv.at[pid, :, off, :].set(qv, mode="drop")
        ks = ks.at[pid, :, off].set(sk, mode="drop")
        vs = vs.at[pid, :, off].set(sv, mode="drop")
    else:
        ck = ck.at[pid, :, off, :].set(k.astype(ck.dtype), mode="drop")
        cv = cv.at[pid, :, off, :].set(v.astype(cv.dtype), mode="drop")
    return ck, cv, ks, vs


def _paged_view(cp, sp, page_table, dtype):
    """Gather one layer's pool pages into the slot batch's contiguous
    attention view ``(B, KV, hd, max_len)`` — the page-table indirection
    the tentpole puts INSIDE the attention read. Page ids are data, not
    shapes: traffic churn changes table contents, never the program. An
    int8 pool dequantizes here, at the point of use (scales broadcast
    over ``hd``), so the fp path's gathered bytes are bit-identical to
    the contiguous cache and the int8 path never materializes a
    dequantized pool."""
    g = cp[page_table]                             # (B, n, KV, ps, hd)
    B, n, KV, ps, hd = g.shape
    g = g.transpose(0, 2, 4, 1, 3).reshape(B, KV, hd, n * ps)
    if sp is not None:
        s = sp[page_table].transpose(0, 2, 1, 3).reshape(B, KV, 1, n * ps)
        g = (g.astype(jnp.float32) * s).astype(dtype)
    return g


def _dense_append(cache, new, layer, length):
    """Write T new positions ``new`` (B, T, KV, hd) into layer ``layer`` of
    the carried cache ``(L, B, KV, hd, max_len)`` with XLA's own update,
    ending at ``length`` (scalar, or (B,) per slot). Returns (the layer's
    slab ``(B, KV, hd, max_len)`` to attend over, the cache)."""
    T = new.shape[1]
    start = length - T     # positions [start, start + T) get the new values
    new = new.transpose(0, 2, 3, 1).astype(cache.dtype)     # (B, KV, hd, T)
    if getattr(length, "ndim", 0) == 0:
        cache = lax.dynamic_update_slice(cache, new[None],
                                         (layer, 0, 0, 0, start))
        return lax.dynamic_index_in_dim(cache, layer, keepdims=False), cache
    # per-slot write positions: one dynamic_update_slice per row via vmap
    # (lowers to a scatter) — each serving slot appends at its own length
    # while the batch stays one static program. On the layer's slab: a
    # scatter into the carried cache itself makes the compiler re-lay the
    # WHOLE cache out for the update
    slab = jax.vmap(lambda c, u, s: lax.dynamic_update_slice(c, u, (0, 0, s)))(
        lax.dynamic_index_in_dim(cache, layer, keepdims=False), new, start)
    return slab, lax.dynamic_update_slice(cache, slab[None],
                                          (layer, 0, 0, 0, 0))


def _tp_quant_eligible(model, p, T: int) -> int:
    """int8 bits when the quantized TP decode collective applies to this
    step, else 0. Gates: the engine opted in (``tp_comm_quant``, stamped
    on the model like ``woq_kernel``), T == 1 (decode only — prefill is
    compute-bound and pays the psum once per request, not per token),
    and the row-sharded projections are DENSE (a WOQ ``QuantizedTensor``
    reduces inside its own shard_map — see ``woq_dot``'s psum — and
    keeps the fp wire there). ``tp_quant_dot`` itself declines meshes
    without a ``model`` axis, so a TP=1 engine with the knob on compiles
    the identical program."""
    bits = int(getattr(model, "tp_quant", 0) or 0)
    if not bits or T != 1:
        return 0
    if isinstance(p.get("wo"), QuantizedTensor):
        return 0
    return bits


def _mlp_tp_quant(model, y, p, bits: int):
    """The dense-MLP half of a decode step with the ``w_out`` model-axis
    partial-sum reduction quantized (two-sided int8) — the same math as
    ``TransformerLM._mlp_block`` (decode never remats, so the
    checkpoint-name tags there are identities this spelling drops).
    Falls back to the model's own block when the explicit spelling
    doesn't apply (no TP mesh, uneven shards, quantized w_out)."""
    cfg = model.cfg
    if isinstance(p.get("w_out"), QuantizedTensor):
        return model._mlp_block(y, p)
    u = model._maybe_bias(model._proj(y, p, "w_in"), p, "b_in")
    if cfg.is_glu:
        u = jax.nn.silu(model._proj(y, p, "w_gate")) * u
    else:
        u = _activation(u, cfg.activation)
    u = constrain(u, P(BATCH_AXES, "seq", "model"))
    out = tp_quant_dot(u, p["w_out"], bits=bits)
    if out is None:
        out = model._proj(u, p, "w_out")
    return model._maybe_bias(out, p, "b_out"), jnp.float32(0.0)


def _qkv_proj(model, y, p):
    """The attention projections as ONE GEMM when the engine pre-fused
    them (``wqkv`` = [wq | wk | wv] along the output dim, ``bqkv``
    likewise): a T=1 decode step's three skinny (B, d) x (d, n) dots
    become a single (B, d) x (d, 2d-ish) call — one weight stream, one
    MXU dispatch, one bias add — instead of three kernel launches over
    the same activations. Falls back to the per-projection weights for
    unfused trees (training params via HybridEngine, external callers)."""
    cfg = model.cfg
    B, T, _ = y.shape
    h, kv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    use_kernel = getattr(model, "woq_kernel", False)
    if "wqkv" in p:
        qkv = matmul_any(y, p["wqkv"], use_kernel=use_kernel)
        if cfg.use_bias and "bqkv" in p:
            qkv = qkv + p["bqkv"].astype(qkv.dtype)
        q, k, v = jnp.split(qkv, [h * hd, (h + kv) * hd], axis=-1)
    else:
        q = model._maybe_bias(matmul_any(y, p["wq"], use_kernel), p, "bq")
        k = model._maybe_bias(matmul_any(y, p["wk"], use_kernel), p, "bk")
        v = model._maybe_bias(matmul_any(y, p["wv"], use_kernel), p, "bv")
    return (q.reshape(B, T, h, hd), k.reshape(B, T, kv, hd),
            v.reshape(B, T, kv, hd))


@jax.named_scope("decode_layer")
def _layer_step(model, x, p, cache_k, cache_v, length, positions,
                flash_decode: bool = False, paged=None, layer=None):
    """One transformer layer over x: (B, T, d), reading/writing the cache.

    Returns (x_out, new_cache_k, new_cache_v) — plus the new scale pools
    when ``paged`` is set. Mirrors ``TransformerLM._attention_block`` /
    ``_mlp_block`` with cache attention substituted for the full causal
    attention. Weights may arrive dense OR quantized (int8/int4
    ``QuantizedTensor`` leaves): every projection goes through the
    point-of-use dispatch, so quantized decode re-reads int8 bytes from
    HBM each step — never a hoisted bf16 copy.

    ``paged`` is ``(page_table, k_scale, v_scale)`` for the pooled page
    layout (serving decode: T == 1 plain steps, T == max_draft + 1
    speculative verify): the append scatters through the page table and
    the attention read gathers the slot's pages back into the contiguous
    view — same values, same mask math, so the fp paged step is
    bit-identical to the contiguous one by construction.

    Without ``paged``, ``cache_k``/``cache_v`` are the WHOLE carried
    ``(L, B, KV, hd, max_len)`` cache and ``layer`` (traced i32) this
    layer's index in it; ``flash_decode`` is then the gate's answer
    (:func:`_decode_kernel_ok`, asked once by :func:`forward_with_cache`):
    the decode kernel appends and attends in place, by layer index.
    """
    cfg = model.cfg
    B, T, d = x.shape
    h, kv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim

    y = _norm(x, p["ln1_scale"], p.get("ln1_bias"), cfg.norm, cfg.norm_eps)
    q, k, v = _qkv_proj(model, y, p)
    if cfg.pos_embedding == "rope":
        q, k = _rope(q, k, positions, cfg.rope_theta, cfg.rotary_dim)

    alibi = None
    if cfg.pos_embedding == "alibi":
        # ALiBi positional signal (mirrors _attention_block's training
        # bias): passed as SLOPES — the streaming decode kernel rebuilds
        # the distance ramp in-kernel, the dense fallback materializes it.
        from ..models.transformer import alibi_slopes

        alibi = alibi_slopes(h)
    scale_k = scale_v = None
    if paged is None and flash_decode:
        from ..ops.decode_attention import decode_attention

        o, cache_k, cache_v = decode_attention(
            q, cache_k, cache_v, length, k=k, v=v, layer=layer,
            alibi_slopes=alibi)
    else:
        if paged is not None:
            page_table, scale_k, scale_v = paged
            cache_k, cache_v, scale_k, scale_v = _paged_append(
                cache_k, cache_v, scale_k, scale_v, k, v, page_table, length)
            attend_k = _paged_view(cache_k, scale_k, page_table, cfg.dtype)
            attend_v = _paged_view(cache_v, scale_v, page_table, cfg.dtype)
        else:
            attend_k, cache_k = _dense_append(cache_k, k, layer, length)
            attend_v, cache_v = _dense_append(cache_v, v, layer, length)
        o = _cache_attend(q, attend_k, attend_v, length,
                          flash_decode=flash_decode, alibi=alibi)
    # Quantized TP decode collective (inference.tp_comm_quant): the wo
    # and dense-MLP w_out partial-sum reductions — the per-token
    # model-axis wire cost every TP decode step pays — spell as explicit
    # two-sided int8 all-reduces. 0 (default) keeps this path bit-frozen
    # on the GSPMD fp psum.
    tpq = _tp_quant_eligible(model, p, T)
    o_flat = o.reshape(B, T, h * hd)
    o = tp_quant_dot(o_flat, p["wo"], bits=tpq) if tpq else None
    if o is None:
        o = matmul_any(o_flat, p["wo"],
                       use_kernel=getattr(model, "woq_kernel", False))
    o = model._maybe_bias(o, p, "bo")
    # MoE trunks expose a single-group no-drop dispatch (_mlp_block_infer,
    # models/moe.py) for the T=1 decode step; prefill (T>1) and dense
    # trunks use the training MLP unchanged (per-row grouping keeps
    # prefill's dispatch one-hots at the training memory profile).
    moe_infer = getattr(model, "_mlp_block_infer", None) if T == 1 else None
    mlp = moe_infer or model._mlp_block
    if tpq and moe_infer is None:
        mlp = partial(_mlp_tp_quant, model, bits=tpq)
    if cfg.parallel_residual:
        y2 = y if cfg.parallel_shared_ln else _norm(
            x, p["ln2_scale"], p.get("ln2_bias"), cfg.norm, cfg.norm_eps)
        out, _aux = mlp(y2, p)
        x = x + o + out
    else:
        # (sandwich norms and residual scales, if any)
        x = model._residual(x, model._post_norm(o, p, "ln1"), p, 0)
        y2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), cfg.norm,
                   cfg.norm_eps)
        out, _aux = mlp(y2, p)
        x = model._residual(x, model._post_norm(out, p, "ln2"), p, 1)
    if paged is not None:
        return x, cache_k, cache_v, scale_k, scale_v
    return x, cache_k, cache_v


@jax.named_scope("decode_layer")
def _latent_layer_step(model, x, p, cache_c, length, positions, fused: bool,
                       layer, banks=None, bank_layer=None):
    """One latent-attention layer over x: (B, T, d) against the carried
    latent cache ``(L, B, rank + rope, max_len)``, layer ``layer`` of it.
    ``banks`` / ``bank_layer``: the segment's stacked expert weights and
    this layer's index in them (``MoETransformerLM.experts``).
    Returns (x_out, cache, (stats, routing)): the expert layer's counters
    and chosen experts (B, T, k), or zeros for a dense FFN."""
    from ..models import mla

    cfg = model.cfg
    B, T, _ = x.shape
    y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
    q_nope, q_rope, new = mla.project(cfg, y, p, positions)
    if fused:
        from ..ops.mla_attention import latent_append, mla_decode_attention

        cache_c = latent_append(cache_c, new[:, 0], length, layer=layer)
        o_lat = mla_decode_attention(
            mla.absorb_q(cfg, p, q_nope, q_rope), cache_c, length,
            layer=layer, rank=cfg.kv_lora_rank, scale=mla.softmax_scale(cfg))
        o = mla.absorb_o(cfg, p, o_lat)
    elif T > 1 and getattr(length, "ndim", 0) == 0:
        # prefill: the new latents into the carried cache, and the expanded
        # read block by block out of it, by layer — no slab is sliced out
        cache_c = lax.dynamic_update_slice(
            cache_c, new.transpose(0, 2, 1)[None].astype(cache_c.dtype),
            (layer, 0, 0, length - T))
        o = mla.attend_expanded(cfg, p, q_nope, q_rope, cache_c, positions,
                                length, layer=layer)
    else:
        # the K/V helper on the latent buffer seen as one head of
        # rank + rope values: same update, same layout
        slab, cache5 = _dense_append(cache_c[:, :, None], new[:, :, None],
                                     layer, length)
        cache_c, slab = cache5[:, :, 0], slab[:, 0]
        if T == 1:
            o = mla.absorb_o(cfg, p, mla.attend_absorbed(
                cfg, mla.absorb_q(cfg, p, q_nope, q_rope), slab, length))
        else:
            o = mla.attend_expanded(cfg, p, q_nope, q_rope, slab, positions,
                                    jnp.max(length))
    x = x + matmul_any(o.reshape(B, T, cfg.n_head * cfg.v_dim), p["wo"],
                       use_kernel=False)
    y2 = _norm(x, p["ln2_scale"], None, cfg.norm, cfg.norm_eps)
    if "router" in p and cfg.moe_router == "sigmoid":
        out, stats, idx = model.experts(y2, p, banks=banks, layer=bank_layer)
    else:
        out, stats = model._mlp_block(y2, p)[0], jnp.zeros((4,), jnp.float32)
        idx = jnp.zeros((B, T, 0), jnp.int32)
    return x + out, cache_c, (stats, idx)


def _forward_latent(model, params, x, cache: LatentCache, new_len, positions,
                    flash_decode: bool):
    """The layer loop over a latent cache: every segment of the trunk scans
    its own stacked weights, all of them carrying the one cache buffer.
    Returns (x, cache, (stats (expert layers, 3), routing (expert layers,
    B, T, k)) or None)."""
    T = x.shape[1]
    fused = _decode_kernel_ok(flash_decode, T, cache.c.shape[3], x.dtype,
                              cache.c.dtype)
    if T == 1 and not fused:
        from ..observability.metrics import get_registry

        get_registry().counter("Serve/decode_fallback_builds").inc()
    c, first, stats = cache.c, 0, []
    for (kind, n), seg in zip(model.cfg.segments,
                              model.segment_params(params["layers"])):
        # the expert banks stay out of the loop's xs: sliced per layer they
        # would be copied (0.4 GB a matrix); the kernel indexes them by layer
        names = getattr(model, "BANKS", ()) if kind == "moe" \
            and model.cfg.moe_router == "sigmoid" else ()
        banks = {k: seg[k] for k in names} or None
        rest = {k: v for k, v in seg.items() if k not in names}

        def scan_fn(carry, layer_in, banks=banks):
            x, c = carry
            lp, layer, local = layer_in
            x, c, st = _latent_layer_step(model, x, lp, c, new_len,
                                          positions, fused, layer,
                                          banks=banks, bank_layer=local)
            return (x, c), st

        (x, c), st = lax.scan(
            scan_fn, (x, c),
            (rest, jnp.arange(first, first + n, dtype=jnp.int32),
             jnp.arange(n, dtype=jnp.int32)))
        first += n
        if kind == "moe":
            stats.append(st)
    return (x, LatentCache(c=c, length=new_len),
            tuple(jnp.concatenate(part) for part in zip(*stats))
            if stats else None)


def _run(body, carry, seg, n: int, first: int):
    """``body(carry, layer weights, index)`` over a run of ``n`` layers
    stacked in ``seg``, ``first`` the run's first index in its kind's
    buffers: a scan, or the body itself with a static index for a run of
    one (its slices of the carried buffers are then static too)."""
    if n == 1:
        carry, out = body(carry, jax.tree.map(lambda a: a[0], seg), first)
        return carry, jax.tree.map(lambda a: a[None], out)
    return lax.scan(lambda c, xs: body(c, *xs), carry,
                    (seg, jnp.arange(first, first + n, dtype=jnp.int32)))


def _forward_hybrid(model, params, x, cache: HybridCache, new_len, valid,
                    flash_decode: bool):
    """The layer loop of a ``block_pattern`` trunk (``models/hybrid.py``):
    each run of equal layers over its own stacked weights, all of them
    carrying the cache's four buffers, a layer touching only its kind's.
    ``valid`` (traced i32 or None): how many of the T tokens are real — a
    right-padded final chunk must leave the recurrent state as its last
    real token did. Returns (x, cache, (stats (expert layers, 4), routing
    (expert layers, B, T, k)) or None)."""
    from ..models import ssm

    cfg = model.cfg
    B, T, _ = x.shape
    per_slot = getattr(new_len, "ndim", 0) == 1
    if per_slot and T > 1:
        raise NotImplementedError(
            "a recurrent state advances one token a slot (T == 1) or a chunk "
            "of ONE request (scalar length): no multi-token verify forward")
    fused = _decode_kernel_ok(flash_decode, T, cache.k.shape[4], x.dtype,
                              cache.k.dtype, cache.v.dtype)
    if T == 1 and not fused:
        from ..observability.metrics import get_registry

        get_registry().counter("Serve/decode_fallback_builds").inc()
    # a slot at length 0 is not running: its state stays as it is
    lens = new_len if per_slot else jnp.broadcast_to(new_len, (B,))
    in_place = ssm.step_kernel_ok(cfg, fused)

    def mamba(carry, p, layer):
        x, k, v, S, W = carry
        y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
        if T == 1:
            out, S, W = ssm.mix_step(cfg, p, y, S, W, layer, lens, in_place)
        else:
            out, s_l, w_l = ssm.mix_chunk(
                cfg, p, y, lax.dynamic_index_in_dim(S, layer, keepdims=False),
                lax.dynamic_index_in_dim(W, layer, keepdims=False), valid)
            S = lax.dynamic_update_slice(S, s_l[None], (layer, 0, 0, 0, 0))
            W = lax.dynamic_update_slice(W, w_l[None], (layer, 0, 0, 0))
        return (x + out, k, v, S, W), ()

    def attention(carry, p, layer):
        x, ck, cv, S, W = carry
        y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
        q, k, v = _qkv_proj(model, y, p)          # no position code
        if fused:
            from ..ops.decode_attention import decode_attention

            o, ck, cv = decode_attention(q, ck, cv, new_len, k=k, v=v,
                                         layer=layer)
        else:
            slab_k, ck = _dense_append(ck, k, layer, new_len)
            slab_v, cv = _dense_append(cv, v, layer, new_len)
            o = _cache_attend(q, slab_k, slab_v, new_len)
        o = matmul_any(o.reshape(B, T, cfg.n_head * cfg.head_dim), p["wo"],
                       use_kernel=False)
        return (x + o, ck, cv, S, W), ()

    def experts(carry, p, layer):
        x = carry[0]
        y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
        out, stats, idx = model.latent_experts(y, p)
        return (x + out,) + carry[1:], (stats, idx)

    bodies = {"M": mamba, "*": attention, "E": experts}
    carry = (x, cache.k, cache.v, cache.ssm, cache.conv)
    seen = dict.fromkeys(bodies, 0)
    stats = []
    for (kind, n), seg in zip(cfg.segments, params["layers"]):
        with jax.named_scope("decode_layer"):
            carry, out = _run(bodies[kind], carry, seg, n, seen[kind])
        seen[kind] += n
        if kind == "E":
            stats.append(out)
    x, k, v, S, W = carry
    return (x, HybridCache(k=k, v=v, ssm=S, conv=W, length=new_len),
            tuple(jnp.concatenate(part) for part in zip(*stats))
            if stats else None)


def _ring_update(ring, new, layer, start, end):
    """Layer ``layer`` of the ring buffer ``(L, B, KV, w, R)`` after a chunk
    wrote positions ``start .. end - 1`` (``new`` (B, T, KV, w) holds
    ``start .. start + T - 1``; what lies at or behind ``end`` is padding):
    ring place ``r`` holds the last position < ``end`` that is ``r`` mod
    ``R`` — the chunk's, where the chunk reaches that far back, else what
    it held."""
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
    ring, in order: ``(B, KV, w, n)`` (what lies before position 0 is
    whatever the ring holds there: the caller masks it)."""
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
    s = jnp.where(keep, s, BIG_NEG)
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


def _forward_windowed(model, params, x, cache: WindowedCache, new_len,
                      positions, valid, flash_decode: bool):
    """The layer loop of an ``attn_pattern`` trunk (``models/windowed.py``):
    each run of layers equal in (attention kind, FFN kind) over its own
    stacked weights, all of them carrying the cache's four buffers, a layer
    touching only its kind's two. The T == 1 step runs ``decode_attention``
    under two names: over a full layer's live blocks
    (``full_decode_attention``), over the one or two ring blocks a window
    layer's last ``window`` positions lie in, the sink in the sum
    (``window_decode_attention``); both append in place. T > 1 (a chunk of
    ONE request, or rows that advance together) appends with XLA's update —
    into the ring the last ``ring`` of the chunk's REAL positions (``valid``
    of T, traced or None: a right-padded final chunk) — and attends in
    blocks: a full layer over the live key blocks of its plane, a window
    layer over the chunk itself and the ``window - 1`` positions the ring
    held before it. Returns (x, cache, (stats (expert layers, 4), routing
    (expert layers, B, T, k)) or None)."""
    from ..models import windowed
    from ..ops.decode_attention import decode_attention

    cfg = model.cfg
    B, T, _ = x.shape
    per_slot = getattr(new_len, "ndim", 0) == 1
    if per_slot and T > 1:
        raise NotImplementedError(
            "a ring takes one token a slot (T == 1) or a chunk of rows that "
            "advance together (scalar length): no multi-token verify forward")
    fused = _decode_kernel_ok(flash_decode, T, cache.k.shape[4], x.dtype,
                              cache.k.dtype, cache.v.dtype)
    if T == 1 and not fused:
        from ..observability.metrics import get_registry

        get_registry().counter("Serve/decode_fallback_builds").inc()
    ring = cache.wk.shape[4]
    start = None if per_slot else new_len - T
    end = None if per_slot else start + (T if valid is None else valid)

    def layer_fn(carry, p, idx, local, kind, banks):
        x, ck, cv, wk, wv = carry
        y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
        q, k, v = windowed.project(cfg, y, p, positions, kind)
        sink = p.get("sink")
        if kind == "G":
            if fused:
                o, ck, cv = decode_attention(
                    q, ck, cv, new_len, k=k, v=v, layer=idx,
                    name="full_decode_attention")
            elif T == 1:
                slab_k, ck = _dense_append(ck, k, idx, new_len)
                slab_v, cv = _dense_append(cv, v, idx, new_len)
                o = _cache_attend(q, slab_k, slab_v, new_len)
            else:
                # the chunk into the carried planes, and the read block by
                # block out of them, by layer — no slab is sliced out
                ck, cv = (lax.dynamic_update_slice(
                    c, n.transpose(0, 2, 3, 1)[None].astype(c.dtype),
                    (idx, 0, 0, 0, start)) for c, n in ((ck, k), (cv, v)))
                o = windowed.attend_blocks(q, ck, cv, positions, new_len,
                                           layer=idx)
        elif fused:
            o, wk, wv = decode_attention(
                q, wk, wv, new_len, k=k, v=v, layer=idx, window=cfg.window,
                sink=sink, name="window_decode_attention")
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
                _ring_before(wv, idx, start, before), start, cfg.window, sink)
            wk = _ring_update(wk, k, idx, start, end)
            wv = _ring_update(wv, v, idx, start, end)
        x = x + matmul_any(o.reshape(B, T, cfg.n_head * cfg.v_dim), p["wo"],
                           use_kernel=False)
        y2 = _norm(x, p["ln2_scale"], None, cfg.norm, cfg.norm_eps)
        if "router" in p:
            out, stats, chose = model.experts(y2, p, banks=banks, layer=local)
        else:
            out, stats = model._mlp_block(y2, p)[0], jnp.zeros((4,),
                                                                jnp.float32)
            chose = jnp.zeros((B, T, 0), jnp.int32)
        return (x + out, ck, cv, wk, wv), (stats, chose)

    carry = (x, cache.k, cache.v, cache.wk, cache.wv)
    seen = {"G": 0, "S": 0}
    stats = []
    for (ffn, n), kind, seg in zip(cfg.segments, cfg.segment_attn,
                                   model.segment_params(params["layers"])):
        # the expert banks stay out of the loop's xs (see _forward_latent)
        names = getattr(model, "BANKS", ()) if ffn == "moe" else ()
        banks = {k: seg[k] for k in names} or None
        rest = {k: v for k, v in seg.items() if k not in names}
        with jax.named_scope("decode_layer"):
            carry, out = lax.scan(
                lambda c, xs, kind=kind, banks=banks: layer_fn(
                    c, *xs, kind, banks), carry,
                (rest, jnp.arange(seen[kind], seen[kind] + n, dtype=jnp.int32),
                 jnp.arange(n, dtype=jnp.int32)))
        seen[kind] += n
        if ffn == "moe":
            stats.append(out)
    x, k, v, wk, wv = carry
    return (x, WindowedCache(k=k, v=v, wk=wk, wv=wv, length=new_len),
            tuple(jnp.concatenate(part) for part in zip(*stats))
            if stats else None)


def _forward_cca(model, params, x, cache: CCACache, new_len, positions,
                 valid, flash_decode: bool):
    """The layer loop of an ``attention='cca'`` trunk (``models/cca.py``,
    the zaya router of ``models/moe.py``): one scan over the stacked
    weights carrying ``(x, s)`` — the stream and the router's state, which
    layer l's router reads of layer l - 1 at the same token — and the K/V
    planes; each layer's tail goes in and comes out beside its weights. The
    T == 1 step runs ``decode_attention`` under the name
    ``cca_decode_attention`` (append in place, the live blocks of 2 KV
    heads) and writes the tail back for live slots only. T > 1 (a chunk of
    ONE request, or rows that advance together) appends with XLA's update
    and attends densely over the layer's slab; its tail is what the last
    REAL token leaves (``valid`` of T, traced or None: a right-padded final
    chunk).
    Returns (x, cache, (stats (layers, 5), routing (layers, B, T, 1))):
    ``MoETransformerLM.experts``' four counters and the mean weight p of
    the layer's choices."""
    from ..models import cca
    from ..ops.decode_attention import decode_attention

    cfg = model.cfg
    B, T, _ = x.shape
    per_slot = getattr(new_len, "ndim", 0) == 1
    if per_slot and T > 1:
        raise NotImplementedError(
            "a conv tail takes one token a slot (T == 1) or a chunk of rows "
            "that advance together (scalar length): no multi-token verify "
            "forward")
    fused = _decode_kernel_ok(flash_decode, T, cache.k.shape[4], x.dtype,
                              cache.k.dtype, cache.v.dtype)
    if T == 1 and not fused:
        from ..observability.metrics import get_registry

        get_registry().counter("Serve/decode_fallback_builds").inc()
    # a slot at length 0 is not running: its tail stays as it is
    live = jnp.broadcast_to(new_len > 0, (B,))[:, None]
    seg = params["layers"]
    # the expert banks stay out of the loop's xs (see _forward_latent)
    banks = {k: seg[k] for k in model.BANKS}
    rest = {k: v for k, v in seg.items() if k not in banks}

    def layer_fn(carry, xs):
        (x, s, ck, cv), (p, tail, idx) = carry, xs
        y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
        q, k, v, new_tail = cca.front(cfg, p, y, tail, positions, valid)
        if fused:
            o, ck, cv = decode_attention(q, ck, cv, new_len, k=k, v=v,
                                         layer=idx,
                                         name="cca_decode_attention")
        else:
            # a chunk, or a step the gate declines: XLA's update, then the
            # layer's slab densely (2 KV heads: (8, T, max_len) scores)
            slab_k, ck = _dense_append(ck, k, idx, new_len)
            slab_v, cv = _dense_append(cv, v, idx, new_len)
            o = _cache_attend(q, slab_k, slab_v, new_len)
        o = matmul_any(o.reshape(B, T, cfg.n_head * cfg.head_dim), p["wo"],
                       use_kernel=False)
        x = model._residual(x, o, p, 0)
        y2 = _norm(x, p["ln2_scale"], None, cfg.norm, cfg.norm_eps)
        chose, w, s = model.route(y2.reshape(B * T, -1), p,
                                  s.reshape(B * T, -1))
        out, stats, chose = model.experts(y2, p, banks=banks, layer=idx,
                                          routed=(chose, w))
        x = model._residual(x, out, p, 1)
        return (x, s.reshape(B, T, -1), ck, cv), (
            jnp.where(live, new_tail, tail),
            jnp.concatenate([stats, jnp.mean(w)[None]]), chose)

    s0 = jnp.zeros((B, T, cfg.router_hidden), jnp.float32)
    with jax.named_scope("decode_layer"):
        (x, _, k, v), (tails, stats, routing) = lax.scan(
            layer_fn, (x, s0, cache.k, cache.v),
            (rest, cache.tail, jnp.arange(cfg.n_layer, dtype=jnp.int32)))
    return (x, CCACache(k=k, v=v, tail=tails, length=new_len),
            (stats, routing))


def _embed_rows(table, ids, dtype):
    """Row gather from a dense or int8/int4-stored embedding table — a
    quantized table reads int8 bytes for exactly the batch's tokens."""
    if isinstance(table, QuantizedTensor):
        return dequant_rows(table, ids, dtype)
    return table.astype(dtype)[ids]


def _decode_head(model, params, x):
    """Final norm + unembedding for the decode path, in fp32.

    Differences from the training head that matter per token:
    - logits come out of the MXU in fp32 (``preferred_element_type``)
      and STAY fp32 into the sampler — the old path rounded the dot to
      bf16 and the sampler cast straight back, a pure bf16↔fp32
      round-trip over (B, V) every step;
    - a quantized tied table is consumed in (V, d) layout by the fused
      transposed WOQ GEMM (``woq_dot_t``) — the unembedding, the single
      largest weight read of a decode step, streams int8;
    - no (V, d) transpose is ever materialized for the dense tied case
      either (``dot_general`` contracts the table's last dim directly).
    """
    cfg = model.cfg
    x = model._pre_head(params, x)
    use_kernel = getattr(model, "woq_kernel", False)
    w = params["tok_embed"] if cfg.tie_embeddings else params["lm_head"]
    if isinstance(w, QuantizedTensor):
        dot = woq_dot_t if cfg.tie_embeddings else woq_dot
        logits = dot(x, w, use_kernel=use_kernel, out_dtype=jnp.float32)
    elif cfg.tiled_head > 1 and w.shape[0 if cfg.tie_embeddings else 1] \
            % cfg.tiled_head == 0 and x.shape[1] > 1:
        # big-vocab prefill through the public API: keep the tiled head
        # (bounds the (B, T, V) logits working set; the generation loop
        # never lands here — its prefill slices to the last position)
        from ..ops.tiled import tiled_matmul

        w2 = (w.T if cfg.tie_embeddings else w).astype(x.dtype)
        logits = tiled_matmul(x, w2, cfg.tiled_head)
    elif cfg.tie_embeddings:
        logits = lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    if cfg.lm_head_bias:
        logits = logits + params["lm_head_bias"].astype(logits.dtype)
    return constrain(logits, P(BATCH_AXES, None, "model"))


def forward_with_cache(model, params, input_ids, cache: KVCache,
                       positions=None, flash_decode: bool = False,
                       last_token_head: bool = False, last_index=None,
                       with_stats: bool = False, with_routing: bool = False,
                       with_passes: bool = False):
    """Run T tokens through all layers, appending to the cache.

    input_ids: (B, T). Works for both prefill (T = prompt length, cache
    empty) and decode (T = 1). Returns (fp32 logits (B, T, V), new cache).
    ``cache.length`` may be a scalar (every row at the same position) or a
    (B,) per-slot vector (serving: each slot appends at its own length).
    ``cache`` may also be a :class:`PagedKVCache` (decode-side T: the
    plain step's 1 or the speculative verify's max_draft + 1): appends
    scatter through the slot page tables and the attention read gathers
    each slot's pages — page-table CONTENTS are data, so traffic churn
    never changes the program.
    ``last_token_head=True`` computes the unembedding only for the final
    position (the generation loop's prefill: the other T-1 logit rows are
    discarded anyway, and at GPT-2 vocab sizes they're the biggest tensor
    of the whole prefill); ``last_index`` (traced i32 scalar) overrides
    which position that is — the serving engine's right-padded final
    prefill chunk puts the last real token at ``true_len - 1``, not T-1.
    ``with_stats`` adds a third result: the expert layers' counters
    ``(expert layers, 3)`` (``MoETransformerLM.experts``), or None where
    the trunk has none to give (only the latent path collects them);
    ``with_routing`` a further one, the experts chosen ``(expert layers, B,
    T, k)``: what a comparison with a reference needs to follow the system's
    choice at a near-tie. ``with_passes`` a last one: what a looped trunk's
    passes left (``TransformerLM.loop_passes``: every pass's closed hidden
    state and the exit distribution), None for any other trunk.
    """
    cfg = model.cfg
    B, T = input_ids.shape
    paged = isinstance(cache, PagedKVCache)
    # Paged T > 1 is the serving engine's speculative verify forward
    # (carry token + drafts in one fixed-shape call); its headroom gate
    # keeps every live slot's post-append length within max_len. Prefill
    # still runs through a contiguous per-request cache and is scattered
    # into pages at insert (serving/pages.py).
    per_slot = getattr(cache.length, "ndim", 0) == 1
    # ONE rule for the three cache kinds (new_len feeds each loop below): a
    # slot at length 0 is not running (serving/slots.py: every seated
    # request has its prompt cached) and stays at 0, where the decode
    # kernels neither fetch nor write for it and the XLA appends land in
    # the row's own extent (the paged pool: on the scratch page, through
    # the row's cleared table), which the next insert overwrites whole
    new_len = jnp.where(cache.length > 0, cache.length + T, 0) if per_slot \
        else cache.length + T
    if positions is None:
        base = cache.length[:, None] if per_slot else cache.length
        positions = base + jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    x = _embed_rows(params["tok_embed"], input_ids, cfg.dtype)
    if cfg.pos_embedding == "learned":
        if per_slot:   # rows sit at different positions: per-row gather
            x = x + _embed_rows(params["pos_embed"], positions, cfg.dtype)
        else:
            x = x + _embed_rows(params["pos_embed"], positions[0],
                                cfg.dtype)[None]
    if cfg.embed_norm:
        x = _norm(x, params["embed_ln_scale"], params.get("embed_ln_bias"),
                  cfg.norm, cfg.norm_eps)

    stats = passes = None
    if isinstance(cache, CCACache):
        x, new_cache, stats = _forward_cca(
            model, params, x, cache, new_len, positions,
            None if last_index is None else last_index + 1, flash_decode)
    elif isinstance(cache, WindowedCache):
        x, new_cache, stats = _forward_windowed(
            model, params, x, cache, new_len, positions,
            None if last_index is None else last_index + 1, flash_decode)
    elif isinstance(cache, HybridCache):
        x, new_cache, stats = _forward_hybrid(
            model, params, x, cache, new_len,
            None if last_index is None else last_index + 1, flash_decode)
    elif isinstance(cache, LatentCache):
        x, new_cache, stats = _forward_latent(model, params, x, cache,
                                              new_len, positions, flash_decode)
    elif paged:
        def paged_scan(carry, layer_in):
            x = carry
            lp, ck, cv, ks, vs = layer_in
            x, ck, cv, ks, vs = _layer_step(
                model, x, lp, ck, cv, new_len, positions,
                flash_decode=flash_decode,
                paged=(cache.page_table, ks, vs))
            return x, (ck, cv, ks, vs)

        x, (ck, cv, ks, vs) = lax.scan(
            paged_scan, x, (params["layers"], cache.k, cache.v,
                            cache.k_scale, cache.v_scale))
        new_cache = PagedKVCache(k=ck, v=cv, k_scale=ks, v_scale=vs,
                                 page_table=cache.page_table, length=new_len)
    else:
        # the cache is ONE buffer carried through the layer loop and
        # indexed by layer. As the loop's xs/ys every layer's slab is
        # sliced out and written back, and the whole cache copied around
        # the loop. The T == 1 step appends and reads with the two decode
        # kernels, in place; T > 1 (and a step the gate declines) appends
        # with dynamic_update_slice and attends densely over the layer
        fused = _decode_kernel_ok(flash_decode, T, cache.k.shape[4], x.dtype,
                                  cache.k.dtype, cache.v.dtype)
        if T == 1 and not fused:
            from ..observability.metrics import get_registry

            # counted where a step program is built (a trace, not a call):
            # 0 says every step program of this process runs the kernels
            get_registry().counter("Serve/decode_fallback_builds").inc()

        def scan_fn(carry, layer_in):
            x, ck, cv = carry
            lp, layer = layer_in
            return _layer_step(model, x, lp, ck, cv, new_len, positions,
                               flash_decode=fused, layer=layer), None

        def stack(carry, plane0=None):
            """Every layer once; ``plane0`` (traced) is the cache plane of
            layer 0 where that is not plane 0: a looped trunk's later
            passes."""
            first = 0
            for (_, n), seg in zip(cfg.segments,
                                   model.segment_params(params["layers"])):
                planes = jnp.arange(first, first + n, dtype=jnp.int32)
                carry, _ = lax.scan(
                    scan_fn, carry,
                    (seg, planes if plane0 is None else plane0 + planes))
                first += n
            return carry

        if cfg.loop_steps > 1:
            # the passes are a loop of the program too (one layer body):
            # each appends to and reads from its own n_layer planes
            def one_pass(x, kv, r):
                x, ck, cv = stack((x, *kv), r * cfg.n_layer)
                return x, (ck, cv)

            x, (ck, cv), passes = model.loop_passes(
                params, x, (cache.k, cache.v), one_pass)
        else:
            x, ck, cv = stack((x, cache.k, cache.v))
        new_cache = KVCache(k=ck, v=cv, length=new_len)
    if last_token_head:
        x = x[:, -1:] if last_index is None else \
            lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    logits = _decode_head(model, params, x)
    counters, routing = stats if stats is not None else (None, None)
    return (logits, new_cache) + ((counters,) if with_stats else ()) \
        + ((routing,) if with_routing else ()) \
        + ((passes,) if with_passes else ())


class GenCarry(NamedTuple):
    """Generation state between the prefill and the decode scan.

    ``rng`` is one (2,) key (whole-batch sampling stream) or a (B, 2)
    per-row key stack — each row then advances its own independent chain,
    so a request folded from its own seed samples identically whether it
    runs alone, in a static batch, or through the serving scheduler."""

    tok: jnp.ndarray         # (B,) i32 — latest sampled token
    cache: KVCache
    rng: jnp.ndarray         # (2,) or (B, 2) uint32
    done: jnp.ndarray        # (B,) bool — eos reached (a slot: not running)
    # serving slots only (serving/slots.py): (B,) i32, the tokens a row may
    # still emit. None (no leaf: nothing is carried) wherever rows run
    # together for a fixed number of steps
    left: Optional[jnp.ndarray] = None


def prefill_tokens(model, params, input_ids, rng, *, max_new: int,
                   sampler, eos_token_id=None, cache_dtype=None,
                   flash_decode: bool = False, materialize=None,
                   cache_len=None) -> GenCarry:
    """Prompt → first sampled token + primed KV cache (the TTFT phase).

    ``cache_len`` overrides the tight ``S + max_new`` cache allocation —
    the serving layer buckets cache shapes so one compiled program serves
    many (prompt, max_new) combinations; positions past the live length
    are masked either way.

    ``materialize``: optional ``quantized params -> dense params`` fn,
    applied ONLY here (prefill is compute-bound; dense is right there).
    The decode scan consumes ``params`` as given: a quantized tree stays
    int8/int4 end-to-end — every projection dispatches through
    ``matmul_any``/``woq_dot_t`` at its point of use, so the weight bytes
    re-read from HBM each token are the quantized ones. The old
    alternative (re-materializing the whole tree in the scan body and
    hoping XLA fuses the convert) measurably did not fuse — XLA hoisted
    the loop-invariant dequant and decode re-read a bf16 copy
    (docs/WOQ_DECODE.md) — which is why the consumption sites dispatch
    explicitly now.
    """
    from .sampling import split_keys

    objective = getattr(model.cfg, "objective", "clm")
    if objective != "clm":
        raise ValueError(
            f"generation needs a causal LM head; this model's objective is "
            f"{objective!r} — use forward() (MLM logits / feature hidden "
            "states) instead")
    B, S = input_ids.shape
    if cache_len is None:
        cache_len = S + max_new
    elif cache_len < S + max_new:
        raise ValueError(f"cache_len={cache_len} < prompt + max_new "
                         f"= {S + max_new}")
    if flash_decode:
        # round up to the Pallas decode kernel's 128-lane block: the spare
        # slots are masked by the live length, and every decode step stays
        # on the streaming kernel regardless of prompt/output lengths
        cache_len = -(-cache_len // 128) * 128
    cache = init_cache(model.cfg, B, cache_len, cache_dtype or model.cfg.dtype)
    mat = materialize if materialize is not None else (lambda p: p)

    with jax.named_scope("prefill"):
        logits, cache = forward_with_cache(model, mat(params), input_ids,
                                           cache, last_token_head=True)
    rng, sub = split_keys(rng)
    tok = sampler(logits[:, -1], sub)
    done = (tok == eos_token_id) if eos_token_id is not None \
        else jnp.zeros((B,), bool)
    return GenCarry(tok=tok, cache=cache, rng=rng, done=done)


def decode_step(model, params, carry: GenCarry, *, sampler,
                eos_token_id=None, flash_decode: bool = False,
                logit_guard: bool = False, poison_row=None,
                moe_stats: bool = False, exit_pdf: bool = False):
    """ONE decode iteration: forward the carry token, sample the next.

    The single definition shared by :func:`decode_tokens`' scan body and
    the serving engine's slot step (``serving/slots.py``), so the eos
    forcing and rng-split order cannot drift between the static-batch and
    continuous-batching paths — that shared order is what makes serving
    outputs bit-identical to single-request ``generate()``.

    ``logit_guard=True`` (the serving step) additionally returns a (B,)
    bool of per-row logit finiteness — ``(carry, ok)`` — computed on
    device and read back fused with the step's existing tok/done sync, so
    the guard adds ZERO host syncs. Sampling is unchanged either way.

    ``poison_row`` (chaos only; a traced i32 scalar, -1 = none) overwrites
    that one row's logits with NaN before sampling — AFTER the forward, so
    the poison can never reach the KV cache or any other row. ``where``
    with a false mask returns the original logits bit-exactly, so a chaos
    program running with poison_row=-1 matches the clean program.

    ``moe_stats=True`` (with ``logit_guard``) returns ``(carry, ok, stats,
    routing)``: the step's expert-layer counters, for the same read-back,
    and the experts it chose (expert layers, B, 1, k). ``exit_pdf=True``
    (with ``logit_guard``; a looped trunk with its gate) returns ``(carry,
    ok, pdf)``: each row's distribution over exit passes (B, passes), for
    that read-back too.

    A carry with ``left`` is the serving slots' (``serving/slots.py``): a
    running row (not ``done``) has one token fewer left after the step; at
    none, as at eos, it is ``done``, and a row that is ``done`` stands at
    length 0: the forward leaves such a row where it is, so from the step
    after its last token until the next insert it costs the decode kernels
    nothing. The host retires a request on the same two conditions off the
    same read-back, so it never has to tell the device."""
    from .sampling import split_keys

    tok, cache, rng, done, left = carry
    with jax.named_scope("decode_step"):
        lg, cache, stats, routing, passes = forward_with_cache(
            model, params, tok[:, None], cache, flash_decode=flash_decode,
            with_stats=True, with_routing=True, with_passes=True)
    if poison_row is not None:
        bad = jnp.arange(lg.shape[0], dtype=jnp.int32)[:, None, None] \
            == poison_row
        lg = jnp.where(bad, jnp.float32(float("nan")), lg)
    rng, sub = split_keys(rng)
    nxt = sampler(lg[:, 0], sub)
    if eos_token_id is not None:
        nxt = jnp.where(done, eos_token_id, nxt)
        done = done | (nxt == eos_token_id)
    if left is not None:
        left = left - (~carry.done).astype(left.dtype)
        done = done | (left <= 0)
        cache = cache._replace(length=jnp.where(done, 0, cache.length))
    out = GenCarry(nxt, cache, rng, done, left)
    if logit_guard:
        ok = jnp.all(jnp.isfinite(lg), axis=(1, 2))
        if exit_pdf:
            return out, ok, passes["exit_pdf"][:, 0]
        return (out, ok, stats, routing) if moe_stats else (out, ok)
    return out


def decode_tokens(model, params, carry: GenCarry, *, steps: int, sampler,
                  eos_token_id=None, flash_decode: bool = False,
                  return_carry: bool = False):
    """Decode scan: ``steps`` more tokens after the carry's.

    Returns (B, steps + 1) — the carry token plus everything it generated
    — or ``(tokens, carry)`` with ``return_carry=True`` (the engine's
    chunked-decode path resumes the scan from the returned carry after a
    host-side ``done.all()`` check). The KV cache threads through the scan
    carry, so XLA reuses (donates) the cache buffers in place — cache
    update and attend live in the same scan body with no copy between
    steps.
    """

    def step(carry, _):
        nxt = decode_step(model, params, carry, sampler=sampler,
                          eos_token_id=eos_token_id,
                          flash_decode=flash_decode)
        return nxt, carry.tok

    out, toks = lax.scan(step, carry, None, length=steps)
    # emitted tokens 0..steps-1 plus the final carry token. Constrain both
    # concat operands to an explicit replicated layout first: under TP the
    # partitioner resolves the scan-stacked ys and the carry token to
    # DIFFERENT shardings, and GSPMD has reconciled them with a
    # spurious cross-shard reduce — every emitted token id summed tp_size
    # times. Token ids are (steps, B) int32 — replication is free next to
    # a decode step, and the constraint is a no-op off-mesh.
    tokens = jnp.concatenate([constrain(toks, P(None, None)),
                              constrain(out.tok[None], P(None, None))],
                             axis=0).T                     # (B, steps + 1)
    return (tokens, out) if return_carry else tokens


def generate_tokens(model, params, input_ids, rng, *, max_new: int,
                    sampler, eos_token_id=None, cache_dtype=None,
                    flash_decode: bool = False, materialize=None,
                    cache_len=None):
    """Shared prefill + decode-scan generation loop, as ONE traceable fn.

    Used by both :class:`~deepspeed_tpu.inference.InferenceEngine` and the
    RLHF :class:`~deepspeed_tpu.runtime.hybrid_engine.HybridEngine` so the
    schedule/eos logic cannot drift between them. ``sampler(logits, rng)``
    -> (B,) int32.

    Composes :func:`prefill_tokens` + :func:`decode_tokens` inside one
    trace — jitted as a unit this is the zero-host-sync fast path (nothing
    leaves the device between prompt in and tokens out). The engine's
    request-tracing mode jits the two halves separately instead, buying an
    honest TTFT / per-token-latency split for exactly one extra host sync
    per request (see ``InferenceEngine.generate``).
    """
    carry = prefill_tokens(model, params, input_ids, rng, max_new=max_new,
                           sampler=sampler, eos_token_id=eos_token_id,
                           cache_dtype=cache_dtype, flash_decode=flash_decode,
                           materialize=materialize, cache_len=cache_len)
    return decode_tokens(model, params, carry, steps=max_new - 1,
                         sampler=sampler, eos_token_id=eos_token_id,
                         flash_decode=flash_decode)

"""The cache of a trunk of delta-rule mixers beside softmax GQA layers
(``cfg.mixer_pattern`` with ``attention='mha'``; Solar-Open2, ``model_type:
solar_open2``): the two kinds of per-slot memory at their plainest, side by
side.

What grows with the position, for the attention ("A") layers only:

- ``k`` / ``v`` ``(A, B, KV, head_dim, max_len)``: whole keys and values of
  the layers' KV heads, laid out as ``KVCache``'s (``Kind.kv_planes``, as
  ``kinds/hybrid.py``); no position code is applied to either.

What a slot holds whatever its length (``kda.state_shapes``, as
``kinds/linear_sparse.py``):

- ``kda`` ``(K, B, H, D, D)`` float32: the KDA layers' delta-rule state;
- ``conv`` ``(K, B, kda_conv - 1, 3 H D)``: the last inputs of their three
  depthwise convs.

One residual stream. The T == 1 step, an attention layer:
``decode_attention`` appends the new K/V in place and reads the slot's live
blocks, under the name ``nope_gqa_decode_attention``; the heads' output times
``sigmoid(y w_ogate)`` (``attn_out_gate``), then ``wo``. A KDA layer:
``kda_state_step`` moves the state in place. T > 1 (a chunk, or a solo
prefill): XLA's update of the planes, then the read of the live key blocks
out of them — where the step's kernels run and the kernel tiles the shapes
(:meth:`DeltaGQA.chunk_kernel`) ONE kernel a layer, ``gqa_chunk_attention``
under the name ``nope_gqa_chunk_attention``, whose scores, probabilities and
accumulators stay in VMEM; everywhere else the walk in plain ``jnp``
(``windowed.attend_blocks``: the scores of one block of keys at a time, never
``T x max_len``; ``Serve/chunk_attention_fallback_builds`` counts the chunk
programs traced onto it with the kernels on) — and the chunkwise scan behind
``kda.mix_chunk``: with the kernels on and T whole blocks of 64
(``kda.chunk_kernel_ok``) ``kda_chunk_scan``, ONE kernel a layer whose
decays, ``(I + A)^-1`` and carried state stay in VMEM; else
``kda.scan_chunked`` in plain ``jnp`` (a bucket of 8 to 32 by the rule;
``Serve/chunk_scan_fallback_builds`` counts the chunk programs of whole
blocks traced onto it with the kernels on).
"""

from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax

from ...models import kda, windowed
from ...models.transformer import _norm
from ...ops import chunk_attention
from ..quantization import matmul_any
from .base import (IN_POOL, MOVES_PAGES, Kind, count_chunk_fallbacks,
                   held_counts, served_bytes, split_banks, stacked)
from .steps import (_append_attend, _decode_kernel_ok, _ffn, _out_gate,
                    _qkv_proj, _run)

DeltaGQACache = namedtuple("DeltaGQACache", "k v kda conv length")


class DeltaGQA(Kind):
    cache = DeltaGQACache
    recurrent = True
    mirrors_lengths = True    # step_meta counts from the slots' lengths
    refuses = {
        "paged": "the paged pool and prefix sharing (page_size): a "
                 "delta-rule state has no pages, and a shared prefix would "
                 "need the state and the conv tails as they stood at the "
                 "prefix's end beside the prefix's K/V pages",
        "kv_quant": IN_POOL,
        "speculation": "speculation: a rejected draft would have to roll "
                       "the delta-rule state back while the K/V planes only "
                       "rewind",
        "host_kv": MOVES_PAGES,
        "quantize": "weight-only quantization: the mixers' and the gated "
                    "attention's projections take dense weights",
        "mesh": "a mesh of several devices: the state step's kernel has no "
                "shard_map rule and the experts held are told by the "
                "configuration, no axis exchanges rows yet"}
    contiguous_only = ("the paged pool holds pages of K and V; a delta-rule "
                       "state and conv tails beside them have no pages: "
                       "contiguous only")

    def __init__(self, cfg, slots: int = 1, dtype=None, params=None):
        super().__init__(cfg, slots, dtype, params)
        self.what = (f"delta-rule mixers beside softmax GQA layers "
                     f"(mixer_pattern={cfg.mixer_pattern!r}, attention="
                     "'mha') do not yet compose with")
        self.moe_stats = any(ffn == "moe" for ffn, _ in cfg.segments)
        self.layers = cfg.mixer_pattern.count("A")
        self.mixers = cfg.mixer_pattern.count("K")
        self.layer_bytes, self.expert_bytes, self.head_bytes = served_bytes(
            cfg, params)

    @staticmethod
    def matches(cfg) -> bool:
        return bool(getattr(cfg, "mixer_pattern", "")) \
            and getattr(cfg, "attention", "") == "mha"

    def chunk_kernel(self, flash_decode, T, max_len, *dtypes) -> bool:
        """Whether T > 1 queries over planes of ``max_len`` attend in
        ``gqa_chunk_attention``: where the step's kernels would run
        (``_decode_kernel_ok``: the switch, no float16, whole lane blocks)
        and the kernel tiles the shapes (keys and values of whole lane
        tiles, T a bucket its rows tile)."""
        cfg = self.cfg
        return T > 1 and _decode_kernel_ok(flash_decode, 1, max_len, *dtypes) \
            and chunk_attention.chunk_kernel_fits(
                T, cfg.n_head // cfg.kv_heads, max_len, cfg.head_dim,
                cfg.head_dim)

    def chunk_fused(self, flash_decode, T, max_len, *dtypes) -> bool:
        fused = self.chunk_kernel(flash_decode, T, max_len, *dtypes)
        count_chunk_fallbacks(flash_decode, not fused,
                              kda.chunk_scan_falls_back(self.cfg, fused, T))
        return fused

    def state(self, batch, dtype=None):
        shapes = kda.state_shapes(self.cfg, batch)
        return {"kda": ((self.mixers,) + shapes["kda"], jnp.float32),
                "conv": ((self.mixers,) + shapes["conv"],
                         dtype or self.cfg.dtype)}

    # ------------------------------------------------------------ the loop
    def loop(self, model, params, x, planes, cache, lens, valid, fused,
             attention, live=None):
        """Each run of layers equal in (mixer, FFN kind, clamps) over its
        own stacked weights, all of them carrying (the stream, the
        attention layers' ``planes``, the state, the tails), a layer touching
        only its kind's. ``attention(x, planes, p, layer) -> (x, planes)``: an
        "A" layer's mixer, the kind's own; ``live`` (B,) bool or None: the
        rows the expert layers' fifth counter counts. Returns (x, planes,
        state, tails, stats): stats (counters (expert layers, 4 or 5),
        routing (expert layers, B, T, k)) or None."""
        cfg = self.cfg

        def mixer(carry, p, ki):
            x, planes, St, W = carry
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            out, St, W = kda.mix(cfg, p, y, St, W, ki, lens, valid, fused)
            return x + out, planes, St, W

        def attends(carry, p, ai):
            x, planes, St, W = carry
            x, planes = attention(x, planes, p, ai)
            return x, planes, St, W

        mixers = {"K": mixer, "A": attends}
        carry = (x, planes, cache.kda, cache.conv)
        seen = dict.fromkeys(mixers, 0)
        stats = []
        for (ffn, n), attn, limits, seg in zip(
                cfg.segments, cfg.segment_attn, cfg.segment_limits,
                model.segment_params(params["layers"])):
            banks, rest = split_banks(model, seg, ffn == "moe")

            def body(carry, p, idx, attn=attn, banks=banks, limits=limits,
                     first=seen[attn]):
                carry = mixers[attn](carry, p, idx)
                x, out = _ffn(model, carry[0], p, banks, idx - first,
                              limits=limits, live=live)
                return (x,) + carry[1:], out

            with jax.named_scope("decode_layer"):
                carry, out = _run(body, carry, rest, n, seen[attn])
            seen[attn] += n
            if ffn == "moe":
                stats.append(out)
        return (*carry, stacked(stats))

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        cfg = self.cfg
        B, T, _ = x.shape
        per_slot = getattr(new_len, "ndim", 0) == 1
        lens = new_len if per_slot else jnp.broadcast_to(new_len, (B,))
        start = None if per_slot else new_len - T

        def attention(x, planes, p, ai):
            ck, cv = planes
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            q, k, v = _qkv_proj(model, y, p)          # no position code
            if T == 1:
                o, ck, cv = _append_attend(
                    q, ck, cv, k, v, ai, new_len, fused,
                    name="nope_gqa_decode_attention")
            else:
                # the chunk into the carried planes, and the read block by
                # block out of them, by layer: no slab, no T x max_len
                ck, cv = (lax.dynamic_update_slice(
                    c, n.transpose(0, 2, 3, 1)[None].astype(c.dtype),
                    (ai, 0, 0, 0, start)) for c, n in ((ck, k), (cv, v)))
                if fused:
                    o = chunk_attention.gqa_chunk_attention(
                        q, ck, cv, start, layer=ai,
                        name="nope_gqa_chunk_attention")
                else:
                    o = windowed.attend_blocks(q, ck, cv, positions, new_len,
                                               layer=ai)
            o = _out_gate(cfg, p, y, o) if cfg.attn_out_gate \
                else o.reshape(B, T, -1)
            return x + matmul_any(o, p["wo"], use_kernel=False), (ck, cv)

        x, (k, v), St, W, stats = self.loop(
            model, params, x, (cache.k, cache.v), cache, lens, valid, fused,
            attention)
        return (x, DeltaGQACache(k=k, v=v, kda=St, conv=W, length=new_len),
                stats, None)

    # ------------------------------------------------------------ the spans
    def chunk_meta(self, chunk) -> dict:
        """:meth:`sizes`, the chunk's tokens, real and padded, the keys its
        attention layers read (``attn_live_keys``: the positions before the
        chunk and its own), whether the kernel reads them (``attn_kernel``)
        and whether the KDA layers' scan is the kernel's (``scan_kernel``)."""
        real = chunk.last_index + 1 if chunk.final else chunk.size
        attn = self.chunk_kernel(self.flash, chunk.size, self.max_len,
                                 self.dtype or self.cfg.dtype)
        return {**self.sizes(), "tokens_real": real,
                "tokens_padded": chunk.size - real,
                "attn_live_keys": chunk.start + chunk.size,
                "attn_kernel": attn,
                "scan_kernel": kda.chunk_kernel_ok(self.cfg, attn,
                                                   chunk.size)}

    def step_meta(self, read, pending, lens, running):
        """:meth:`sizes`; from the mirror of the slots' lengths what the
        step has to move — ``state_bytes_step`` (the running slots'
        delta-rule state and tails, in and out), ``kv_bytes_step``
        (``live_positions`` x the bytes a cached position takes: the
        attention layers read every live key and value),
        ``weight_bytes_step`` (everything but the experts' banks),
        ``expert_bytes_step`` (the held experts touched),
        ``head_bytes_step`` — with the state's and the K/V's shares of their
        sum; the held experts' counters."""
        from ...observability.metrics import get_registry

        cfg = self.cfg
        meta = self.sizes()
        held = held_counts(self, read, pending)
        if lens is not None:
            live = int(lens[lens > 0].sum())
            moved = {
                "state_bytes_step": 2 * len(running) * self.slot_bytes,
                "kv_bytes_step": live * self.token_bytes,
                "weight_bytes_step": self.layer_bytes,
                "expert_bytes_step": int(
                    held.get("experts_touched", 0.0) * self.expert_bytes
                    * sum(n for k, n in cfg.segments if k == "moe")),
                "head_bytes_step": self.head_bytes}
            total = max(sum(moved.values()), 1)
            meta.update(
                moved, live_positions=live,
                state_share_of_step_bytes=moved["state_bytes_step"] / total,
                kv_share_of_step_bytes=moved["kv_bytes_step"] / total)
            get_registry().counter("Serve/kda_state_bytes_moved").inc(
                moved["state_bytes_step"])
        meta.update(held)
        return meta

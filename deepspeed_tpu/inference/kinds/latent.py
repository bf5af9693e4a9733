"""Latent attention's cache (``cfg.attention == "mla"``, ``models/mla.py``):
ONE buffer ``c`` ``(L, B, rank + rope, max_len)`` of what its layers share
between heads — per position the layer's normed ``c`` (``kv_lora_rank``
values) and roped ``k_rope`` (``qk_rope_head_dim``), positions on the lanes as
in ``KVCache`` — written by the latent projection and read two ways: T > 1
expands K and V from the live prefix's latents block by block and attends as
published; the T == 1 step appends in place and attends absorbed
(``ops/mla_attention.py``). No expanded K or V is ever stored."""

from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax

from ...models import mla, moe
from ...models.transformer import _norm
from ...ops import mla_attention
from .base import Kind, routed_counts, split_banks, stacked
from .steps import _dense_append, _out_ffn


LatentCache = namedtuple("LatentCache", "c length")


def attend(cfg, p, y, cache_c, length, positions, fused: bool, layer,
           keep_idle: bool = False):
    """Latent attention of the layer's normed input ``y`` (B, T, d) against
    the carried latent cache ``(L, B, rank + rope, max_len)``, layer ``layer``
    of it: the T new latents appended, then the read — absorbed over the
    slot's live latents for T == 1 (``fused``: ``ops/mla_attention.py``'s
    kernels, in place), expanded block by block over the live prefix for a
    chunk. ``keep_idle``: the kernels' append leaves a slot at length 0
    bit-equal (else its position 0 is written, which the next insert
    overwrites). Returns (o (B, T, H, v), cache): what every kind whose
    attention layers are dense MLA runs for one."""
    T = y.shape[1]
    q_nope, q_rope, new = mla.project(cfg, y, p, positions)
    if fused:
        cache_c = mla_attention.latent_append(
            cache_c, new[:, 0], length, layer=layer,
            **({"keep_idle": True} if keep_idle else {}))
        o_lat = mla_attention.mla_decode_attention(
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
    return o, cache_c


@jax.named_scope("decode_layer")
def _layer_step(model, x, p, cache_c, length, positions, fused: bool,
                layer, banks=None, bank_layer=None):
    """One latent-attention layer over x: (B, T, d) against the carried
    latent cache ``(L, B, rank + rope, max_len)``, layer ``layer`` of it.
    ``banks`` / ``bank_layer``: as :func:`_out_ffn`'s. Returns (x_out,
    cache, (the expert layer's counters, the experts chosen))."""
    cfg = model.cfg
    y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
    o, cache_c = attend(cfg, p, y, cache_c, length, positions, fused, layer)
    x, stats = _out_ffn(model, x, o, p, banks, bank_layer,
                        cfg.moe_router == "sigmoid")
    return x, cache_c, stats


class Latent(Kind):
    """``(L, batch, rank + rope, max_len)``, contiguous only."""

    cache = LatentCache
    planes = ("c",)        # under ops/mla_attention.py's kernels
    # one list for the latent cache and the sorted expert rows it came
    # with (models/moe.py): the sentence names both
    what, sep, refuses = moe.SERVED
    contiguous_only = ("the paged pool holds K and V pages; a latent cache "
                       "is contiguous only")

    def __init__(self, cfg, *serving):
        super().__init__(cfg, *serving)
        self.moe_stats = cfg.moe_router == "sigmoid" \
            and any(ffn == "moe" for ffn, _ in cfg.segments)

    @staticmethod
    def matches(cfg) -> bool:
        # (with an index_pattern the latents lie a position a row:
        # kinds/sparse_latent.py)
        # (beside the mixers of a mixer_pattern: kinds/delta_latent.py)
        return getattr(cfg, "attention", "") == "mla" \
            and not getattr(cfg, "index_pattern", "") \
            and not getattr(cfg, "mixer_pattern", "")

    def buffers(self, batch, max_len, dtype=None):
        cfg = self.cfg
        return {"c": ((cfg.n_layer, batch, cfg.latent_dim, max_len),
                      dtype or cfg.dtype)}

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        """Every segment of the trunk scans its own stacked weights, all of
        them carrying the one cache buffer. Stats: (counters (expert
        layers, 4), routing (expert layers, B, T, k)) or None."""
        c, first, stats = cache.c, 0, []
        for (kind, n), seg in zip(self.cfg.segments,
                                  model.segment_params(params["layers"])):
            banks, rest = split_banks(
                model, seg, kind == "moe" and self.moe_stats)

            def scan_fn(carry, layer_in, banks=banks):
                x, c = carry
                lp, layer, local = layer_in
                x, c, st = _layer_step(model, x, lp, c, new_len, positions,
                                       fused, layer, banks=banks,
                                       bank_layer=local)
                return (x, c), st

            (x, c), st = lax.scan(
                scan_fn, (x, c),
                (rest, jnp.arange(first, first + n, dtype=jnp.int32),
                 jnp.arange(n, dtype=jnp.int32)))
            first += n
            if kind == "moe":
                stats.append(st)
        return x, LatentCache(c=c, length=new_len), stacked(stats), None

    def step_meta(self, read, pending, lens, running):
        return routed_counts(self, read, pending)

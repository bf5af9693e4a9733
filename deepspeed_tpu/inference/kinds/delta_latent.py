"""The cache of a trunk of delta-rule mixers beside roped latent attention
over every live position (``cfg.mixer_pattern`` with ``attention='mla'`` and
no ``index_pattern``; Ling-3.0-flash, ``model_type: bailing_hybrid``).

What grows with the position, for the attention ("A") layers only:

- ``c`` ``(A, B, rank + rope, max_len)``: the layers' latents, the normed
  ``c`` beside the roped ``k_rope``, positions on the lanes — ``kinds/
  latent.py``'s buffer over the "A" layers alone, so ``mla_cache_append`` and
  ``mla_decode_attention`` are called as that kind calls them.

What a slot holds whatever its length (``kda.state_shapes``, as
``kinds/delta_gqa.py`` and ``kinds/linear_sparse.py``):

- ``kda`` ``(K, B, H, D, D)`` float32: the KDA layers' delta-rule state;
- ``conv`` ``(K, B, kda_conv - 1, 3 H D)``: the last inputs of their three
  depthwise convs.

One residual stream. What this file shares, and with what:

- ``kinds/latent.py`` ``attend``: an "A" layer's whole attention — the
  projection with rope on the latent's rope part, the append, the absorbed
  read of the live latents for T == 1 (the kernels where the gate says so)
  and ``mla.attend_expanded``'s walk over the live prefix for a chunk;
- ``models/kda.py`` ``mix``: a "K" layer's whole mixer (``kda_state_step``
  in place for T == 1; for a chunk ``kda_chunk_scan`` where
  ``kda.chunk_kernel_ok``, else ``kda.scan_chunked``), with the full maps and
  the q/k gains the config asks of it;
- ``kinds/steps.py``: ``_out_gate`` (``delta_gqa``'s gate, here a value a
  head);
- ``kinds/delta_gqa.py`` (its base class): the loop itself (``DeltaGQA.loop``:
  runs of layers equal in mixer, FFN kind and clamps, all carrying the stream,
  the attention layers' planes, the state and the tails; ``steps._ffn`` told
  the segment's two clamps and the rows that are live), the state's layout,
  what it refuses and what its spans say.

Its own: the three buffers side by side, the gate a head behind both reads,
``held_group_token_share`` on the step's span. A chunk's attention has no
kernel: ``fused`` for T > 1 says only that the KDA layers' scan may take its
(``Serve/chunk_scan_fallback_builds`` counts the chunk programs of whole
blocks traced onto XLA's scan with the kernels on).
"""

from collections import namedtuple

import jax.numpy as jnp

from ...models import kda
from ...models.transformer import _norm
from ..quantization import matmul_any
from . import delta_gqa, latent
from .base import count_chunk_fallbacks
from .steps import _decode_kernel_ok, _out_gate

DeltaLatentCache = namedtuple("DeltaLatentCache", "c kda conv length")


class DeltaLatent(delta_gqa.DeltaGQA):
    cache = DeltaLatentCache
    planes = ("c",)        # under ops/mla_attention.py's kernels
    refuses = {
        **delta_gqa.DeltaGQA.refuses,
        "paged": "the paged pool and prefix sharing (page_size): a "
                 "delta-rule state has no pages, and a shared prefix would "
                 "need the state and the conv tails as they stood at the "
                 "prefix's end beside the prefix's latents",
        "speculation": "speculation: a rejected draft would have to roll "
                       "the delta-rule state back while the latents only "
                       "rewind, and the model's own drafting layer (MTP) is "
                       "not held",
        "quantize": "weight-only quantization: the mixers' and the latent "
                    "attention's projections take dense weights"}
    contiguous_only = ("the paged pool holds pages of K and V; latents, a "
                       "delta-rule state and conv tails beside them have no "
                       "pages: contiguous only")

    def __init__(self, cfg, slots: int = 1, dtype=None, params=None):
        super().__init__(cfg, slots, dtype, params)
        self.what = (f"delta-rule mixers beside roped latent attention "
                     f"(mixer_pattern={cfg.mixer_pattern!r}, attention="
                     "'mla', no index_pattern) do not yet compose with")

    @staticmethod
    def matches(cfg) -> bool:
        return bool(getattr(cfg, "mixer_pattern", "")) \
            and getattr(cfg, "attention", "") == "mla" \
            and not getattr(cfg, "index_pattern", "")

    def buffers(self, batch, max_len, dtype=None):
        cfg = self.cfg
        return {"c": ((self.layers, batch, cfg.latent_dim, max_len),
                      dtype or cfg.dtype)}

    def chunk_kernel(self, flash_decode, T, max_len, *dtypes) -> bool:
        """A chunk's attention is XLA's walk (``mla.attend_expanded``)."""
        return False

    def chunk_fused(self, flash_decode, T, max_len, *dtypes) -> bool:
        fused = T > 1 and _decode_kernel_ok(flash_decode, 1, max_len, *dtypes)
        count_chunk_fallbacks(flash_decode, False,
                              kda.chunk_scan_falls_back(self.cfg, fused, T))
        return fused

    # ------------------------------------------------------------ the loop
    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        cfg = self.cfg
        B, T, _ = x.shape
        per_slot = getattr(new_len, "ndim", 0) == 1
        lens = new_len if per_slot else jnp.broadcast_to(new_len, (B,))

        def attention(x, c, p, ai):
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            o, c = latent.attend(cfg, p, y, c, new_len, positions,
                                 fused and T == 1, ai, keep_idle=True)
            o = _out_gate(cfg, p, y, o) if cfg.attn_out_gate \
                else o.reshape(B, T, -1)
            return x + matmul_any(o, p["wo"], use_kernel=False), c

        x, c, St, W, stats = self.loop(
            model, params, x, cache.c, cache, lens, valid, fused, attention,
            live=lens > 0 if per_slot else None)
        return (x, DeltaLatentCache(c=c, kda=St, conv=W, length=new_len),
                stats, None)

    # ------------------------------------------------------------ the spans
    def chunk_meta(self, chunk) -> dict:
        """``delta_gqa``'s, the scan's kernel told from the step's gate (no
        kernel attends a chunk here: ``attn_kernel`` is False)."""
        on = _decode_kernel_ok(self.flash, 1, self.max_len,
                               self.dtype or self.cfg.dtype)
        return {**super().chunk_meta(chunk),
                "scan_kernel": kda.chunk_kernel_ok(self.cfg, on, chunk.size)}

    def step_meta(self, read, pending, lens, running):
        """``delta_gqa``'s (the latents are what its ``kv_bytes_step``
        counts: every live position is read) and, with routing groups,
        ``held_group_token_share``: the running tokens whose kept groups
        hold an expert held here over the running tokens, a layer's mean."""
        meta = super().step_meta(read, pending, lens, running)
        if read and read[0].shape[-1] > 4:
            meta["held_group_token_share"] = float(
                read[0][:, 4].mean() / max(len(running), 1))
        return meta

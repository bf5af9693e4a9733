"""The cache of compressed convolutional attention behind the zaya router
(``cfg.attention == "cca"``, ``models/cca.py``, ``models/moe.py``): K/V
planes laid out as ``KVCache``'s — the attention runs in the latent, so they
are ``n_kv_head x head_dim`` wide, an eighth of the model — beside, per layer
and slot, the **tail** the two convolutions and the value shift reach back
into: the last rows of ``z`` and ``z1`` and of the shifted value's projection
(``cca.tail_width`` values whatever the length)."""

from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax

from ...models import cca
from ...models.transformer import _norm
from ..quantization import matmul_any
from .base import IN_POOL, MOVES_PAGES, Kind, routed_counts, split_banks
from .steps import _append_attend


CCACache = namedtuple("CCACache", "k v tail length")


class CCA(Kind):
    cache = CCACache
    recurrent = True         # a tail is never rewound
    moe_stats = True
    what = ("compressed convolutional attention (attention='cca') does not "
            "yet compose with")
    refuses = {
        "paged": "the paged pool and prefix sharing (page_size): a conv "
                 "tail has no pages, and a shared prefix would need the "
                 "tail as it stood at the prefix's end",
        "kv_quant": IN_POOL,
        "speculation": "speculation: a rejected draft would have to roll the "
                       "tails back, and the verify forward is many tokens a "
                       "slot",
        "host_kv": MOVES_PAGES,
        "quantize": "weight-only quantization: the latent's projections and "
                    "the router take dense weights",
        "mesh": "a mesh of several devices: the convs' channels and the "
                "sorted expert rows have no sharding rule under a test"}
    contiguous_only = ("the paged pool holds pages of K and V; the conv tail "
                       "a slot beside them has no pages: contiguous only")

    @staticmethod
    def matches(cfg) -> bool:
        return getattr(cfg, "attention", "") == "cca"

    def state(self, batch, dtype=None):
        cfg = self.cfg
        return {"tail": ((cfg.n_layer, batch, cca.tail_width(cfg)),
                         dtype or cfg.dtype)}

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        """One scan over the stacked weights carrying ``(x, s)`` — the
        stream and the router's state, which layer l's router reads of
        layer l - 1 at the same token — and the K/V planes; each layer's
        tail goes in and comes out beside its weights. The T == 1 step runs
        ``decode_attention`` under the name ``cca_decode_attention`` and
        writes the tail back for live slots only; T > 1 appends with XLA's
        update and attends densely over the layer's slab, its tail what the
        last REAL token leaves (``valid``). Stats: (counters (layers, 5),
        routing (layers, B, T, 1)): ``MoETransformerLM.experts``' four
        counters and the mean weight p of the layer's choices."""
        cfg = self.cfg
        B, T, _ = x.shape
        # a slot at length 0 is not running: its tail stays as it is
        live = jnp.broadcast_to(new_len > 0, (B,))[:, None]
        banks, rest = split_banks(model, params["layers"], True)

        def layer_fn(carry, xs):
            (x, s, ck, cv), (p, tail, idx) = carry, xs
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            q, k, v, new_tail = cca.front(cfg, p, y, tail, positions, valid)
            o, ck, cv = _append_attend(q, ck, cv, k, v, idx, new_len, fused,
                                       name="cca_decode_attention")
            o = matmul_any(o.reshape(B, T, cfg.n_head * cfg.head_dim),
                           p["wo"], use_kernel=False)
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
                (stats, routing), None)

    def step_meta(self, read, pending, lens, running):
        """:meth:`sizes`; ``live_positions``, the kernel's count; of the
        step's expert layers (one row a layer: the experts' four counters,
        then the mean weight p of the layer's choices) :func:`routed_counts`,
        ``moe_rows_routed`` (slots x 1) and ``router_top_p``: 1 / num_experts
        says the router is flat, near 1 that it is saturated. A chunk's
        ``router_top_p`` goes onto its own span."""
        meta = {**self.sizes(), **routed_counts(self, read, pending)}
        if lens is not None:
            meta["live_positions"] = int(lens.sum())
        if read:
            for (chunk_span, _, _), st in zip(pending, read[1:]):
                chunk_span.amend(router_top_p=float(st[:, 4].mean()))
            meta.update(moe_rows_routed=self.slots,
                        router_top_p=float(read[0][:, 4].mean()))
        return meta

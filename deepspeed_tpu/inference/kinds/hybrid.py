"""The cache of a trunk of one mixer a layer (``cfg.block_pattern``,
``models/hybrid.py``): K/V planes for the attention layers ONLY, laid out as
``KVCache``'s, beside what does not grow with the position: per Mamba-2 layer
and slot a float32 SSM state ``ssm`` (H, P, N) and the conv's last ``K - 1``
inputs ``conv`` (``models/ssm.py``)."""

from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax

from ...models.transformer import _norm
from ..quantization import matmul_any
from .base import IN_POOL, MOVES_PAGES, Kind, held_counts, stacked
from .steps import _append_attend, _qkv_proj, _run


HybridCache = namedtuple("HybridCache", "k v ssm conv length")


class Hybrid(Kind):
    cache = HybridCache
    recurrent = True
    refuses = {
        "paged": "the paged pool and prefix sharing (page_size): a recurrent "
                 "state has no pages, and a shared prefix would need the "
                 "state as it stood at the prefix's end",
        "kv_quant": IN_POOL,
        "speculation": "speculation: a rejected draft would have to roll the "
                       "recurrent state back, and the model's own drafting "
                       "head is not held",
        "host_kv": MOVES_PAGES,
        "quantize": "weight-only quantization: the mixers' projections take "
                    "dense weights",
        "mesh": "a mesh of several devices: the experts held are told by "
                "the configuration, no axis exchanges rows yet"}
    contiguous_only = ("the paged pool holds pages of K and V; a recurrent "
                       "state beside them has no pages: contiguous only")

    def __init__(self, cfg, *serving):
        super().__init__(cfg, *serving)
        self.what = (f"a trunk of one mixer a layer (block_pattern="
                     f"{cfg.block_pattern!r}) does not yet compose with")
        self.moe_stats = "E" in cfg.block_pattern
        self.layers = cfg.block_pattern.count("*")

    @staticmethod
    def matches(cfg) -> bool:
        return bool(getattr(cfg, "block_pattern", "")) \
            and "P" not in cfg.block_pattern \
            and not getattr(cfg, "attn_pattern", "") \
            and getattr(cfg, "attention", "mha") == "mha"

    def state(self, batch, dtype=None):
        # (imported where a trunk has mixers: no other family loads them)
        from ...models import ssm

        cfg = self.cfg
        n = cfg.block_pattern.count("M")
        return {name: ((n,) + shape,
                       jnp.float32 if name == "ssm" else dtype or cfg.dtype)
                for name, shape in ssm.state_shapes(cfg, batch).items()}

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        """Each run of equal layers over its own stacked weights, all of
        them carrying the cache's four buffers, a layer touching only its
        kind's. Stats: (counters (expert layers, 4), routing (expert layers,
        B, T, k)) or None."""
        cfg = self.cfg
        from ...models import ssm

        B, T, _ = x.shape
        # a slot at length 0 is not running: its state stays as it is
        lens = new_len if getattr(new_len, "ndim", 0) == 1 \
            else jnp.broadcast_to(new_len, (B,))
        in_place = ssm.step_kernel_ok(cfg, fused)

        def mamba(carry, p, layer):
            x, k, v, S, W = carry
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            if T == 1:
                out, S, W = ssm.mix_step(cfg, p, y, S, W, layer, lens,
                                         in_place)
            else:
                out, s_l, w_l = ssm.mix_chunk(
                    cfg, p, y,
                    lax.dynamic_index_in_dim(S, layer, keepdims=False),
                    lax.dynamic_index_in_dim(W, layer, keepdims=False), valid)
                S = lax.dynamic_update_slice(S, s_l[None], (layer, 0, 0, 0, 0))
                W = lax.dynamic_update_slice(W, w_l[None], (layer, 0, 0, 0))
            return (x + out, k, v, S, W), ()

        def attention(carry, p, layer):
            x, ck, cv, S, W = carry
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            q, k, v = _qkv_proj(model, y, p)          # no position code
            o, ck, cv = _append_attend(q, ck, cv, k, v, layer, new_len, fused)
            o = matmul_any(o.reshape(B, T, cfg.n_head * cfg.head_dim),
                           p["wo"], use_kernel=False)
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
                stacked(stats), None)

    def step_meta(self, read, pending, lens, running):
        """:meth:`sizes`, ``ssm_block_bytes`` (the state one program of the
        step's kernel takes), and :func:`held_counts` of the step's expert
        layers (``HybridLM.latent_experts``' counters)."""
        from ...models import ssm

        return {**self.sizes(),
                "ssm_block_bytes": ssm.step_block_bytes(self.cfg),
                **held_counts(self, read, pending)}

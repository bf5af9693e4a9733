"""The cache of a trunk whose every layer runs a Mamba-2 mixer AND rotary
GQA attention on the same normed input (``cfg.block_pattern`` of ``P``,
``models/hybrid.py``; Falcon-H1): **one layer owns a K/V plane and a
recurrent state**. K/V planes for every layer, laid out as ``KVCache``'s,
beside what does not grow with the position: per layer and slot a float32
SSM state ``ssm`` (H, P, N) and the conv's last ``K - 1`` inputs ``conv``
(``models/ssm.py``)."""

from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax

from ...models.transformer import _norm
from .base import IN_POOL, MOVES_PAGES, Kind
from .steps import _append_attend, _run


ParallelCache = namedtuple("ParallelCache", "k v ssm conv length")


class ParallelHybrid(Kind):
    cache = ParallelCache
    recurrent = True
    refuses = {
        "paged": "the paged pool and prefix sharing (page_size): the layer's "
                 "recurrent state has no pages, and a shared prefix would "
                 "need the state as it stood at the prefix's end beside the "
                 "prefix's K/V pages",
        "kv_quant": IN_POOL,
        "speculation": "speculation: a rejected draft would have to roll the "
                       "recurrent state back while the K/V planes only rewind",
        "host_kv": MOVES_PAGES,
        "quantize": "weight-only quantization: the mixers' projections take "
                    "dense weights",
        "mesh": "a mesh of several devices: the query heads over fewer KV "
                "heads and the mixer's groups have no sharding rule, and the "
                "state step's kernel no shard_map yet"}
    contiguous_only = ("the paged pool holds pages of K and V; the recurrent "
                       "state the same layer holds has no pages: contiguous "
                       "only")

    def __init__(self, cfg, slots: int = 1, dtype=None, params=None):
        super().__init__(cfg, slots, dtype, params)
        self.what = ("a trunk of a Mamba-2 mixer and attention side by side "
                     "in every layer (block_pattern 'P') does not yet "
                     "compose with")
        # what a step reads of the weights whoever runs: the layers', and
        # the head apart (a table row a slot is left out)
        params = params or {"layers": (), "lm_head": ()}
        self.layer_bytes, self.head_bytes = (
            sum(a.nbytes for a in jax.tree.leaves(params[name]))
            for name in ("layers", "lm_head"))

    @staticmethod
    def matches(cfg) -> bool:
        return "P" in getattr(cfg, "block_pattern", "")

    def state(self, batch, dtype=None):
        # (imported where a trunk has mixers: no other family loads them)
        from ...models import ssm

        cfg = self.cfg
        return {name: ((cfg.n_layer,) + shape,
                       jnp.float32 if name == "ssm" else dtype or cfg.dtype)
                for name, shape in ssm.state_shapes(cfg, batch).items()}

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused):
        """One scan over the layers' stacked weights carrying the cache's
        four buffers, every layer touching its own row of each. T == 1 runs
        ``ssm_state_step`` and the decode kernel (``gqa_decode_attention``)
        on the same normed input; T > 1 the chunked scan and the chunk's
        attention over the layer's slab, handing on the state and the window
        as the last REAL token leaves them (``valid``)."""
        from ...models import ssm
        from ...models.hybrid import parallel_close, parallel_qkv

        cfg = self.cfg
        B, T, _ = x.shape
        # a slot at length 0 is not running: its state stays as it is
        lens = new_len if getattr(new_len, "ndim", 0) == 1 \
            else jnp.broadcast_to(new_len, (B,))
        in_place = ssm.step_kernel_ok(cfg, fused)

        def layer_fn(carry, p, layer):
            x, ck, cv, S, W = carry
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            with jax.named_scope("parallel_mixers"):
                q, k, v = parallel_qkv(cfg, y, p, positions)
                o, ck, cv = _append_attend(q, ck, cv, k, v, layer, new_len,
                                           fused, name="gqa_decode_attention")
                if T == 1:
                    mixed, S, W = ssm.mix_step(cfg, p, y, S, W, layer, lens,
                                               in_place)
                else:
                    mixed, s_l, w_l = ssm.mix_chunk(
                        cfg, p, y,
                        lax.dynamic_index_in_dim(S, layer, keepdims=False),
                        lax.dynamic_index_in_dim(W, layer, keepdims=False),
                        valid)
                    S = lax.dynamic_update_slice(S, s_l[None],
                                                 (layer, 0, 0, 0, 0))
                    W = lax.dynamic_update_slice(W, w_l[None],
                                                 (layer, 0, 0, 0))
            return (parallel_close(cfg, x, o, mixed, p), ck, cv, S, W), ()

        carry = (x, cache.k, cache.v, cache.ssm, cache.conv)
        (seg,) = params["layers"]
        with jax.named_scope("decode_layer"):
            carry, _ = _run(layer_fn, carry, seg, cfg.n_layer, 0)
        x, k, v, S, W = carry
        return (x, ParallelCache(k=k, v=v, ssm=S, conv=W, length=new_len),
                None, None)

    def step_meta(self, read, pending, lens, running):
        """:meth:`sizes`, ``ssm_block_bytes`` (the state one program of the
        step's kernel takes), and what the step has to move: ``state_bytes_step``
        (the running slots' state of every layer, in and out),
        ``kv_bytes_step`` (``live_positions``, the kernel's count, x the
        bytes a token), ``weight_bytes_step`` (the layers') with the head
        apart (``head_bytes_step``), and the state's share of their sum."""
        from ...models import ssm

        meta = {**self.sizes(),
                "ssm_block_bytes": ssm.step_block_bytes(self.cfg)}
        if lens is None:
            return meta
        live = int(lens.sum())
        moved = {"state_bytes_step": 2 * len(running) * self.slot_bytes,
                 "kv_bytes_step": live * self.token_bytes,
                 "weight_bytes_step": self.layer_bytes,
                 "head_bytes_step": self.head_bytes}
        return {**meta, "live_positions": live, **moved,
                "state_share_of_step_bytes":
                    moved["state_bytes_step"] / max(sum(moved.values()), 1)}

    def chunk_meta(self, chunk) -> dict:
        """:meth:`sizes`, and the chunk's tokens, real and padded."""
        real = chunk.last_index + 1 if chunk.final else chunk.size
        return {**self.sizes(), "tokens_real": real,
                "tokens_padded": chunk.size - real}

"""Pallas kernels of the latent (MLA) decode step: one absorbed query per
head against a slot's cached latents, and the in-place append.

The cache is ``(L, B, rank + rope, max_len)``: per position the normed
``c`` and the roped ``k_rope`` that ALL heads share (``inference/kinds/latent.py``
``LatentCache``), positions on the lanes.

- ``mla_decode_attention``: in ``ops/decode_attention.py`` every KV head
  has K/V of its own and a product of its own. Here the heads share the
  latents: a program takes a slot's H query rows at once: ``s = q (H,
  rank+rope) @ lat (rank+rope, block)``, ``o_lat += p (H, block) . c
  (rank, block)^T`` — the
  heads are the matmul's rows, and no single row is broadcast over
  sublanes. Grid (slots, position blocks): the online softmax's state lives
  in VMEM scratch across a slot's blocks; the index map clamps the block to
  the slot's last live one, so positions behind the live length are neither
  fetched nor multiplied.
- ``latent_append``: the step's new latents at position ``length - 1`` of
  every slot, a read-modify-write of the one 128-lane tile that holds it
  (``decode_attention.append_in_place``: the latent buffer is a cache of
  one head of ``rank + rope`` values).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .decode_attention import BIG_NEG, LANES, append_in_place


def _lengths(length, B):
    return jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))


def _refuse_mesh(what: str) -> None:
    from ..platform.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and not mesh.empty and mesh.size > 1:
        raise NotImplementedError(
            f"{what} is a Mosaic kernel with no shard_map wrapper yet: the "
            "latent decode step runs on one device")


def _mla_kernel(len_ref, _, q_ref, c_ref, o_ref, m_ref, l_ref, acc_ref, *,
                block: int, rank: int, scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    # a caller's length may lie past the cache; a slot that is not running
    # stands at 0 and multiplies nothing
    L = jnp.minimum(len_ref[b], block * pl.num_programs(1))

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, BIG_NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * block < L)
    def _():
        q = q_ref[...]                                    # (H, rank + rope)
        lat = c_ref[...]                                  # (rank + rope, blk)
        s = jnp.dot(q, lat, preferred_element_type=jnp.float32) * scale
        col = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = col < L
        s = jnp.where(keep, s, BIG_NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(lat.dtype), lat[:rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # p . c^T
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def mla_decode_attention(q, cache, length, *, layer, rank: int, scale: float,
                         block: int = 512, interpret: Optional[bool] = None):
    """``q`` (B, H, rank + rope): the absorbed queries, in the order the
    latents lie; ``cache`` (L, B, rank + rope, max_len), ``layer`` (traced
    i32) the layer read; ``length`` scalar or (B,): positions < length are
    attended. Returns ``o_lat`` (B, H, rank) = softmax(q·lat·scale) · c."""
    from jax.experimental.pallas import tpu as pltpu

    _refuse_mesh("mla_decode_attention")
    B, H, D = q.shape
    S = cache.shape[3]
    if S % LANES:
        raise ValueError(f"cache length {S} not a multiple of {LANES}")
    # the largest whole number of lane tiles <= block that divides S
    blk = next(t for t in range(min(block, S) // LANES * LANES, 0, -LANES)
               if S % t == 0)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lengths = _lengths(length, B)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def lat_block(b, j, n, layer):
        # behind the slot's last live block: the same block again (no fetch)
        last = jnp.maximum(jnp.minimum(n[b], S) - 1, 0) // blk
        return (layer[0], b, 0, jnp.minimum(j, last))

    return pl.pallas_call(
        partial(_mla_kernel, block=blk, rank=rank, scale=scale),
        name="mla_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // blk),
            in_specs=[pl.BlockSpec((None, H, D), lambda b, j, *_: (b, 0, 0)),
                      pl.BlockSpec((None, None, D, blk), lat_block)],
            out_specs=pl.BlockSpec((None, H, rank),
                                   lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, layer, q.astype(cache.dtype), cache)


def latent_append(cache, new, length, *, layer,
                  interpret: Optional[bool] = None, keep_idle: bool = False):
    """Write this step's latents ``new`` (B, rank + rope) into layer
    ``layer`` of ``cache`` (L, B, rank + rope, max_len) at position
    ``length - 1`` of every slot, in place (output aliased to the input,
    every other position bit-untouched). ``keep_idle``: a slot at length 0
    keeps its position 0 too (``append_in_place``)."""
    _refuse_mesh("latent_append")
    B = new.shape[0]
    if cache.shape[3] % LANES:
        raise ValueError(f"cache length {cache.shape[3]} not a multiple of "
                         f"{LANES}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (out,) = append_in_place(
        (cache[:, :, None],), (new[:, None, None],), _lengths(length, B),
        jnp.asarray(layer, jnp.int32).reshape(1), name="mla_cache_append",
        interpret=interpret, keep_idle=keep_idle)
    return out[:, :, 0]

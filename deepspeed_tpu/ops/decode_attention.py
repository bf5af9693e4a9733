"""Pallas decode attention: one query token against the KV cache.

TPU-native answer to the reference's ``softmax_context`` inference kernel
(``csrc/transformer/inference/csrc/softmax_context_cuda.cu`` via
``pt_binding.cpp``): fused attention of the current token over the cached
keys/values, masking cache slots past the live length.  The XLA fallback in
``inference/decode.py`` materializes the full (B, H, 1, max_len) score tensor
in HBM each step; this kernel streams the cache through VMEM with an online
softmax instead — the decode hot loop is bandwidth-bound, so not spilling
scores is the win.

Layout notes:
- grid (B, H); each program handles one (batch, head) pair.
- the cache keeps its storage layout (B, KV, max_len, hd) — heads-major so
  the per-head block is (None, None, max_len, hd), whose last two dims are
  (sublane, lane)-shaped as the TPU lowering requires (a seq-major cache
  would squeeze the second-to-last dim: rejected on hardware). The GQA head
  group mapping happens in the BlockSpec index_map (h // group), so there is
  no repeated-KV materialization at all (the training kernel pays a
  ``jnp.repeat``; decode can't afford it).
- the single query row is broadcast to the 8-sublane tile (q_sub trick) so
  the s = q @ k.T matmul is MXU/VPU shaped.
- the live length is a scalar-prefetch operand (SMEM), letting the kernel
  bound its streaming loop at ceil(length / block) instead of max_len.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

BIG_NEG = -2.0 ** 30
SUBLANES = 8


def _decode_kernel(*refs, block: int, scale: float, alibi: bool):
    if alibi:
        len_ref, slopes_ref, q_ref, k_ref, v_ref, o_ref = refs
    else:
        len_ref, q_ref, k_ref, v_ref, o_ref = refs
        slopes_ref = None
    b = pl.program_id(0)
    h = pl.program_id(1)
    L = len_ref[b]
    q = q_ref[...].astype(jnp.float32) * scale          # (SUBLANES, hd)
    S = k_ref.shape[0]

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block, block), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block, block), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (SUB, blk)
        col = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (SUBLANES, block), 1)
        if slopes_ref is not None:
            # ALiBi is a pure function of (slot, live length): slope·(s -
            # t) with the query at global position t = L-1 — no (H, S)
            # bias tensor ever exists (the dense fallback builds one per
            # step; Bloom's positional signal costs one SMEM scalar here)
            s = s + slopes_ref[h] * (col - (L - 1)).astype(jnp.float32)
        keep = col < L
        s = jnp.where(keep, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    nb = (L + block - 1) // block                        # only live blocks
    m0 = jnp.full((SUBLANES, 1), BIG_NEG, jnp.float32)
    l0 = jnp.zeros((SUBLANES, 1), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def decode_attention(q, ck, cv, length, *, alibi_slopes=None,
                     block: int = 128, interpret: Optional[bool] = None):
    """q: (B, 1, H, hd) current-token queries; ck/cv: (B, KV, max_len, hd)
    cache; ``length`` scalar or (B,) live lengths (slots < length attended).
    ``alibi_slopes``: optional (H,) per-head slopes — the ALiBi distance
    bias is reconstructed in-kernel from the live length (Bloom decode
    stays on the streaming kernel instead of the dense fallback).

    Returns (B, 1, H, hd)."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, hd = q.shape
    assert T == 1, "decode kernel is single-token; use flash_attention for prefill"
    KV, S = ck.shape[1], ck.shape[2]
    blk = min(block, S)
    if S % blk != 0:
        raise ValueError(f"cache length {S} not divisible by block {blk}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    group = H // KV
    scale = 1.0 / math.sqrt(hd)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    alibi = alibi_slopes is not None
    from ..platform.mesh import attention_shard_axes

    axes = attention_shard_axes(B, H, KV)
    if axes is not None:
        # GSPMD cannot partition a Mosaic kernel: run it per shard, slots
        # over the example-parallel axes and heads over model/seq (inside
        # the body the axes are manual, so the recursion lands below)
        mesh, b_ax, h_ax = axes
        cache = P(b_ax, h_ax, None, None)

        def per_shard(q, ck, cv, n, *slopes):
            return decode_attention(q, ck, cv, n, block=block,
                                    interpret=interpret,
                                    alibi_slopes=slopes[0] if slopes else None)

        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(b_ax, None, h_ax, None), cache, cache, P(b_ax))
            + ((P(h_ax),) if alibi else ()),
            out_specs=P(b_ax, None, h_ax, None), check_vma=False)(
                q, ck, cv, lengths,
                *((jnp.asarray(alibi_slopes, jnp.float32),) if alibi else ()))

    # (B, 1, H, hd) → (B, H, SUBLANES, hd): sublane-replicated single query
    qs = jnp.broadcast_to(q.swapaxes(1, 2), (B, H, SUBLANES, hd))

    n_prefetch = 2 if alibi else 1
    pre_args = ((lengths, jnp.asarray(alibi_slopes, jnp.float32))
                if alibi else (lengths,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((None, None, SUBLANES, hd),
                         lambda b, h, *pre: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, hd),
                         lambda b, h, *pre: (b, h // group, 0, 0)),
            pl.BlockSpec((None, None, S, hd),
                         lambda b, h, *pre: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, SUBLANES, hd),
                               lambda b, h, *pre: (b, h, 0, 0)),
    )
    out = pl.pallas_call(
        partial(_decode_kernel, block=blk, scale=scale, alibi=alibi),
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, SUBLANES, hd), q.dtype),
        interpret=interpret,
    )(*pre_args, qs, ck, cv)
    return out[:, :, :1, :].swapaxes(1, 2)               # (B, 1, H, hd)

"""Latent attention (MLA, DeepSeek-V2/V3): the mathematics both trunks share.

Per token a layer keeps ``c`` (``kv_lora_rank`` values, after its RMSNorm)
and ``k_rope`` (``qk_rope_head_dim`` values, after rope, one for all heads)
instead of every head's K and V. The *latents* travel as one array
``(B, rank + rope, S)``, positions on the lanes, which is how the cache
holds them (``inference/kinds/latent.py``); two paths read them:

- :func:`attend_expanded` (T > 1: the full forward and prefill): expands
  ``k_nope`` and ``v`` from a block of latents with ``wkv_b`` and attends as
  published, block by block over the live prefix with a running softmax, so
  neither (T, S) scores nor expanded K/V of the whole context ever exist.
- :func:`absorb_q` / :func:`absorb_o` (T = 1): ``wkv_b`` folded into the
  query and the output, so the step reads the latents alone
  (``ops/mla_attention.py`` streams them; :func:`attend_absorbed` is the
  same in plain XLA).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .transformer import _norm, _rope

BIG_NEG = -2.0 ** 30


def project(cfg, y, p, positions):
    """``y`` (B, T, d) normed activations → ``q_nope`` (B, T, H, nope),
    ``q_rope`` (B, T, H, rope), and the T new latents ``(B, T, rank+rope)``
    = [RMSNorm(c) | rope(k_rope)]: what the cache stores (c alone where
    ``qk_rope_head_dim`` is 0)."""
    B, T, _ = y.shape
    H, nope, rd, r = (cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.kv_lora_rank)
    if cfg.q_lora_rank:
        # heads of nope + rope are split off whole lane tiles (192 | 64):
        # behind the barrier the split re-lays out the rows, where XLA would
        # otherwise re-lay out the weights to suit it (a copy of wq_b, 64 MB
        # a layer a step; models/windowed.py)
        q = lax.optimization_barrier(
            query_latent(cfg, y, p) @ p["wq_b"].astype(y.dtype))
    else:
        q = y @ p["wq"].astype(y.dtype)
    q = q.reshape(B, T, H, nope + rd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kva = y @ p["wkv_a"].astype(y.dtype)                     # (B, T, r + rd)
    c = _norm(kva[..., :r], p["kv_norm_scale"], None, "rmsnorm", cfg.norm_eps)
    if not rd:
        # a latent with no rope part: the row is c alone
        return q_nope, q_rope, c
    q_rope, k_rope = _rope(q_rope, kva[..., None, r:], positions,
                           cfg.rope_theta)
    return q_nope, q_rope, jnp.concatenate([c, k_rope[:, :, 0]], axis=-1)


def query_latent(cfg, y, p):
    """``cq = RMSNorm(y wq_a)`` (B, T, q_lora_rank): what a low-rank query is
    projected from, and what an indexer's queries read (models/dsa.py)."""
    return _norm(y @ p["wq_a"].astype(y.dtype), p["q_norm_scale"], None,
                 "rmsnorm", cfg.norm_eps)


def _wkv_b(cfg, p, dtype):
    """``wkv_b`` as (rank, H, nope + v): per head [k_nope | v]."""
    return p["wkv_b"].astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.n_head, cfg.qk_nope_head_dim + cfg.v_dim)


def softmax_scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


@jax.named_scope("mla_attend")
def attend_expanded(cfg, p, q_nope, q_rope, latents, q_pos, n_keys,
                    block: int = 512, layer=None, selected=None):
    """Causal attention of T queries at absolute positions ``q_pos`` (B, T)
    over the latents ``(B, rank+rope, S)`` of positions 0..S-1, expanding K
    and V block by block. ``n_keys`` bounds the blocks visited: a Python int
    (the full forward: a scan, differentiable) or a traced scalar (the
    cache's live length: positions behind it are never read). A key is
    attended iff its position <= the query's, which also hides whatever a
    cache holds behind the live prefix. With ``layer`` (traced i32)
    ``latents`` is the whole cache ``(L, B, rank+rope, S)`` and every block
    is read out of that layer of it. ``latents`` may also be ``(read, S)``:
    ``read(j, blk)`` gives block ``j`` as ``(B, rank+rope, blk)`` out of a
    cache laid out otherwise. ``selected`` (B, T, S) bool: the keys a query
    may see beside the causal rule (a learned selection, models/dsa.py): a
    mask over the same walk. Returns (B, T, H, v)."""
    B, T, H, nope = q_nope.shape
    read = None
    if isinstance(latents, tuple):
        read, S = latents
    else:
        S = latents.shape[-1]
    r = cfg.kv_lora_rank
    vd = cfg.v_dim
    blk = block if S % block == 0 else S
    w = _wkv_b(cfg, p, q_nope.dtype)
    scale = softmax_scale(cfg)

    def body(j, carry):
        m, l, acc = carry
        if read is not None:
            lat = read(j, blk)
        elif layer is None:
            lat = lax.dynamic_slice_in_dim(latents, j * blk, blk, axis=2)
        else:
            lat = lax.dynamic_slice(
                latents, (layer, 0, 0, j * blk),
                (1,) + latents.shape[1:3] + (blk,))[0]
        kv = jnp.einsum("brs,rhm->bshm", lat[:, :r].astype(w.dtype), w)
        s = jnp.einsum("bthn,bshn->bhts", q_nope, kv[..., :nope])
        if cfg.qk_rope_head_dim:
            s = s + jnp.einsum("bthr,brs->bhts", q_rope,
                               lat[:, r:].astype(q_rope.dtype))
        s = s.astype(jnp.float32) * scale
        k_pos = j * blk + jnp.arange(blk, dtype=jnp.int32)
        keep = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]
        if selected is not None:
            keep &= lax.dynamic_slice_in_dim(selected, j * blk, blk,
                                             axis=2)[:, None]
        s = jnp.where(keep, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(pr, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhts,bshv->bhtv", pr.astype(w.dtype),
            kv[..., nope:]).astype(jnp.float32)
        return m_new, l, acc

    init = (jnp.full((B, H, T, 1), BIG_NEG, jnp.float32),
            jnp.zeros((B, H, T, 1), jnp.float32),
            jnp.zeros((B, H, T, vd), jnp.float32))
    if isinstance(n_keys, int):
        nb = -(-min(n_keys, S) // blk)
    else:
        nb = jnp.minimum((n_keys + blk - 1) // blk, S // blk)
    _, l, acc = lax.fori_loop(0, nb, body, init)
    o = acc / jnp.maximum(l, 1e-30)
    return o.astype(q_nope.dtype).transpose(0, 2, 1, 3)


def absorb_q(cfg, p, q_nope, q_rope):
    """T = 1 queries (B, 1, H, ·) against the latents directly:
    ``q_lat = q_nope · W_kb^T`` per head, beside ``q_rope`` → (B, H,
    rank + rope), in the order the latents lie."""
    w = _wkv_b(cfg, p, q_nope.dtype)[..., :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w)
    return jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)


def absorb_o(cfg, p, o_lat):
    """``o = o_lat · W_vb`` per head: (B, H, rank) → (B, 1, H, v)."""
    w = _wkv_b(cfg, p, o_lat.dtype)[..., cfg.qk_nope_head_dim:]
    return jnp.einsum("bhr,rhv->bhv", o_lat, w)[:, None]


@jax.named_scope("mla_attend")
def attend_absorbed(cfg, q, latents, length):
    """The absorbed T = 1 read in plain XLA: ``q`` (B, H, rank+rope) over
    one layer's latents (B, rank+rope, S), positions < ``length`` (scalar
    or (B,)) attended. Materializes (B, H, S) scores: what a step traced
    here pays, and ``Serve/decode_fallback_builds`` counts."""
    B, _, S = latents.shape
    s = jnp.einsum("bhr,brs->bhs", q, latents.astype(q.dtype))
    s = s.astype(jnp.float32) * softmax_scale(cfg)
    n = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    keep = jnp.arange(S, dtype=jnp.int32)[None, None, :] < n[:, None, None]
    pr = jax.nn.softmax(jnp.where(keep, s, BIG_NEG), axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,brs->bhr", pr,
                      latents[:, :cfg.kv_lora_rank].astype(q.dtype))

"""Window layers beside full ones in one trunk (MiMo-V2-Flash,
``model_type: mimo_v2_flash``).

Layer ``i`` is ``cfg.attn_pattern[i]``: ``G`` full causal attention, ``S`` a
sliding window. Both kinds are the trunk's pre-norm MHA block with heads
``qk_head_dim`` wide for q and k over ``v_head_dim`` for v, rope on the first
``rotary_dim`` dims (pairs ``i``, ``i + rotary_dim / 2`` with ``rope_halves``),
the scale ``1 / sqrt(qk_head_dim)`` and V times ``attn_value_scale`` (applied
where V is projected, so the cache holds it scaled). They differ in

- KV heads: ``n_kv_head`` (G) | ``window_kv_heads`` (S);
- theta: ``rope_theta`` (G) | ``window_rope_theta`` (S);
- the keys a query at position ``i`` sees: ``0 .. i`` (G) |
  ``i - window + 1 .. i`` (S);
- a learned sink logit a head (S, with ``attn_sink``): one more column in the
  softmax's denominator that carries no value,
  ``p_ij = exp(s_ij - m) / (exp(sink_h - m) + sum_j exp(s_ij - m))``.

The FFN beside them is the trunk's own (``moe_first_dense`` dense layers,
then sigmoid-routed experts, ``models/moe.py``). Segments are runs of layers
equal in (attention kind, FFN kind), each scanned over its own stacked
weights (``TransformerConfig.segments`` / ``segment_attn``).

Nothing here materialises a ``(heads, T, S)`` score tensor: a full layer's
queries walk the LIVE key blocks with a running max and sum
(:func:`attend_blocks`), a window layer's see their own block and the
``window`` positions before it (:func:`attend_window`). The cache path is
``inference/kinds/windowed.py``: planes for the ``G`` layers
beside a ring of :func:`ring_len` positions a slot for each ``S`` layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

LANES = 128
KEY_BLOCK = 512      # keys a step of a full layer's chunk walk (attend_blocks)
BIG_NEG = -2.0 ** 30
# a window layer's sink logits at init: normal(mean, sd)
SINK_INIT = (3.0, 1.0)
KINDS = "GS"


def check_config(c, attention_fn) -> None:
    """Refuse what an ``attn_pattern`` trunk does not run."""
    pat = c.attn_pattern
    if len(pat) != c.n_layer or set(pat) - set(KINDS):
        raise ValueError(f"attn_pattern {pat!r} has to name each of the "
                         f"{c.n_layer} layers' attention, one of {KINDS!r}")
    if (c.attention != "mha" or c.pos_embedding != "rope" or c.use_bias
            or not c.causal or c.objective != "clm" or c.post_ln
            or c.parallel_residual or c.loop_steps > 1 or c.sandwich_norm
            or c.block_pattern or attention_fn is not None):
        raise ValueError(
            "an attn_pattern trunk is the MiMo-V2 block: a causal LM of "
            "pre-norm MHA layers with rope, two-hop residual, no biases, "
            "its own blocked attention (no attention_fn)")
    if "S" in pat and c.window < 1:
        raise ValueError("'S' layers need window >= 1")
    for kind in set(pat):
        if c.n_head % c.attn_kv_heads(kind):
            raise ValueError(f"{c.n_head} heads do not divide over "
                             f"{c.attn_kv_heads(kind)} KV heads ({kind!r})")
    if c.num_experts > 1 and c.moe_router != "sigmoid":
        raise ValueError("expert layers beside an attn_pattern take the "
                         "sigmoid router (MoETransformerLM.experts)")


def ring_len(cfg) -> int:
    """Positions a window layer keeps a slot: whole 128-lane blocks, enough
    for ``window`` positions and the block being written."""
    return LANES * (-(-cfg.window // LANES) + 1)


def prev_len(cfg) -> int:
    """W': the positions before a chunk that its window layer reads."""
    return LANES * -(-max(cfg.window - 1, 1) // LANES)


def project(cfg, y, p, positions, kind: str):
    """q (B, T, H, hd), k (B, T, KV, hd), v (B, T, KV, vd) of one layer of
    ``kind``: roped at the kind's theta, v scaled."""
    from .transformer import _rope

    B, T, _ = y.shape
    h, kv = cfg.n_head, cfg.attn_kv_heads(kind)
    # heads of 192 are not whole lane tiles: behind the barrier the split
    # into heads re-lays out the rows, where XLA would otherwise re-lay out
    # the weights to suit it (a copy of wq, 100 MB, a layer a step)
    q, k = lax.optimization_barrier((y @ p["wq"].astype(y.dtype),
                                     y @ p["wk"].astype(y.dtype)))
    q, k = q.reshape(B, T, h, cfg.head_dim), k.reshape(B, T, kv, cfg.head_dim)
    v = (y @ p["wv"].astype(y.dtype)).reshape(B, T, kv, cfg.v_dim)
    q, k = _rope(q, k, positions, cfg.attn_theta(kind), cfg.rotary_dim,
                 halves=cfg.rope_halves)
    if cfg.attn_value_scale != 1.0:
        v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
    return q, k, v


def _grouped(q, kv: int):
    """(B, T, H, hd) -> (B, T, KV, G, hd): a KV head's query heads."""
    B, T, H, hd = q.shape
    return q.reshape(B, T, kv, H // kv, hd)


def attend_blocks(q, keys, vals, q_pos, n_keys, *, layer=None,
                  block: int = KEY_BLOCK):
    """Causal attention of T queries at absolute positions ``q_pos`` (B, T)
    over keys ``(B, KV, hd, S)`` / values ``(B, KV, vd, S)`` of positions
    0..S-1 — or the whole cache ``(L, B, KV, ., S)`` with ``layer`` (traced
    i32), every block read out of that layer of it. Walks blocks of
    ``block`` keys with a running max and sum; ``n_keys`` (traced) bounds the
    blocks visited: positions behind it are never read. A key is attended
    iff its position <= the query's. Returns (B, T, H, vd)."""
    B, T, H, hd = q.shape
    KV, S = keys.shape[-3], keys.shape[-1]
    vd = vals.shape[-2]
    blk = block if S % block == 0 else S
    qg = _grouped(q, KV)
    scale = 1.0 / math.sqrt(hd)

    def read(buf, j):
        if layer is None:
            return lax.dynamic_slice_in_dim(buf, j * blk, blk, axis=3)
        return lax.dynamic_slice(
            buf, (layer, 0, 0, 0, j * blk), (1,) + buf.shape[1:4] + (blk,))[0]

    def body(j, carry):
        m, l, acc = carry
        k, v = read(keys, j), read(vals, j)
        s = jnp.einsum("btkgd,bkds->bkgts", qg, k.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        k_pos = j * blk + jnp.arange(blk, dtype=jnp.int32)
        keep = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, None]
        s = jnp.where(keep, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(pr, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bkgts,bkvs->bkgtv", pr.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    G = H // KV
    init = (jnp.full((B, KV, G, T, 1), BIG_NEG, jnp.float32),
            jnp.zeros((B, KV, G, T, 1), jnp.float32),
            jnp.zeros((B, KV, G, T, vd), jnp.float32))
    nb = jnp.minimum((n_keys + blk - 1) // blk, S // blk)
    _, l, acc = lax.fori_loop(0, nb, body, init)
    o = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, H, vd)


def attend_window(q, k, v, prev_k, prev_v, start, window: int, sink=None):
    """A window layer's T queries at positions ``start .. start + T - 1``
    (``start`` a traced scalar) over their own keys ``k`` (B, T, KV, hd) /
    values ``v`` (B, T, KV, vd) and the positions before them: ``prev_k``
    (B, KV, hd, W') / ``prev_v`` hold positions ``start - W' .. start - 1``
    in order, W' a multiple of 128 >= ``window - 1`` (what lies before
    position 0 is masked, whatever it holds). Query ``i`` sees keys
    ``i - window + 1 .. i``; ``sink`` (H,) float32 is one more column of the
    denominator. Blocks of 128 queries against the ``W' + 128`` keys they
    can see: nothing is as wide as ``T x T``. Returns (B, T, H, vd)."""
    B, T, H, hd = q.shape
    KV, vd, Wp = k.shape[2], v.shape[3], prev_k.shape[-1]
    G = H // KV
    nb = -(-T // LANES)
    pad = nb * LANES - T
    m = Wp // LANES

    def cat(prev, new):
        """(B, KV, ., nb, W' + 128): block i's keys."""
        new = jnp.pad(new.transpose(0, 2, 3, 1).astype(prev.dtype),
                      ((0, 0),) * 3 + ((0, pad),))
        blocks = jnp.concatenate([prev, new], -1).reshape(
            B, KV, -1, m + nb, LANES)
        return jnp.concatenate([blocks[..., i:i + nb, :]
                                for i in range(m + 1)], -1)

    kc, vc = cat(prev_k, k), cat(prev_v, v)
    qg = jnp.pad(_grouped(q, KV), ((0, 0), (0, pad)) + ((0, 0),) * 3)
    qg = qg.reshape(B, nb, LANES, KV, G, hd)
    s = jnp.einsum("bnqkgd,bkdnc->bkgnqc", qg, kc.astype(q.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    first = start + LANES * jnp.arange(nb, dtype=jnp.int32)[:, None, None]
    q_pos = first + jnp.arange(LANES, dtype=jnp.int32)[None, :, None]
    k_pos = first - Wp + jnp.arange(Wp + LANES, dtype=jnp.int32)[None, None]
    keep = (k_pos <= q_pos) & (k_pos > q_pos - window) & (k_pos >= 0)
    s = jnp.where(keep, s, BIG_NEG)
    top = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, KV, G, 1, 1, 1)
        top = jnp.maximum(top, sk)
    pr = jnp.where(keep, jnp.exp(s - top), 0.0)
    den = jnp.sum(pr, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - top)
    o = jnp.einsum("bkgnqc,bkvnc->bnqkgv", pr.astype(vc.dtype), vc,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(den, 1e-30).transpose(0, 3, 4, 1, 2, 5)
    return o.astype(q.dtype).reshape(B, nb * LANES, H, vd)[:, :T]


@jax.named_scope("attn")
def attention_block(cfg, y, p, positions, kind: str, chunk: int = 512):
    """One layer's attention of ``kind`` on a whole sequence (no cache): y
    (B, S, d) post-norm -> (B, S, d). A full layer's queries go in chunks of
    ``chunk``, each over the key blocks up to its own end."""
    B, S, _ = y.shape
    q, k, v = project(cfg, y, p, positions, kind)
    if kind == "S":
        # nothing lies before position 0: the mask hides these
        def none(width):
            return jnp.zeros((B, cfg.attn_kv_heads(kind), width,
                              prev_len(cfg)), q.dtype)

        o = attend_window(q, k, v, none(cfg.head_dim), none(cfg.v_dim),
                          jnp.int32(0), cfg.window, p.get("sink"))
    else:
        n = -(-S // chunk)
        pad = n * chunk - S
        keys = jnp.pad(k.transpose(0, 2, 3, 1), ((0, 0),) * 3 + ((0, pad),))
        vals = jnp.pad(v.transpose(0, 2, 3, 1), ((0, 0),) * 3 + ((0, pad),))
        if n == 1:
            o = attend_blocks(q, keys, vals, positions, S, block=chunk)
        else:
            qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
                B, n, chunk, *q.shape[2:])
            pos = jnp.pad(positions, ((0, 0), (0, pad)), mode="edge").reshape(
                B, n, chunk)
            o = lax.map(
                lambda a: attend_blocks(a[0], keys, vals, a[1],
                                        (a[2] + 1) * chunk, block=chunk),
                (qs.swapaxes(0, 1), pos.swapaxes(0, 1),
                 jnp.arange(n, dtype=jnp.int32)))
            o = o.swapaxes(0, 1).reshape(B, n * chunk, *o.shape[3:])[:, :S]
    return o.reshape(B, S, cfg.n_head * cfg.v_dim) @ p["wo"].astype(y.dtype)

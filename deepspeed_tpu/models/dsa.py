"""Learned sparse attention over latents (DeepSeek-V3.2's sparse attention
with GLM-5.2's ``indexer_types``; ``model_type: glm_moe_dsa``): the
mathematics the full forward and the cache path share.

Layer ``i`` is ``cfg.index_pattern[i]``:

- ``F`` (published ``"full"``) has an **indexer**, a second, small attention
  whose only product is a choice. From the query's normed latent ``cq``
  (``mla.query_latent``) it projects ``index_heads`` queries of
  ``index_head_dim``; from the layer's normed input ``y`` ONE key of that
  width a position (behind a LayerNorm) and a weight a head; rope turns the
  first ``qk_rope_head_dim`` dims of queries and key (interleaved pairs).
  ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` and ``S_t`` = the
  ``index_topk`` positions ``s <= t`` of largest ``I[t, s]`` (all of them
  while ``t < index_topk``). The layer's latent attention (``models/mla.py``)
  reads the positions of ``S_t`` and no other.
- ``s`` (``"shared"``) has no indexer and caches no indexer key: its ``S_t``
  is the ``S_t`` of the last ``F`` layer before it.

So the layer loop carries ``(x, selection)``. The indexers' weights are a
stacked tree of their own (``params["indexer"]``, one entry an ``F`` layer):
the layers' own stacks stay uniform, runs of FFN kinds as in every trunk.

A selection travels in two forms. ``idx`` (B, T, K) i32, K = min(index_topk,
keys), -1 where a query has fewer positions than K: what the T == 1 step's
kernel fetches by (``ops/sparse_mla_attention.py``) and what a comparison with
a reference follows. ``mask`` (B, T, S) bool: what T > 1 applies to the walk
over the live blocks (``mla.attend_expanded(selected=)``) — exact, the work of
dense attention (ROADMAP R10: a chunk that attends over the selection alone).

The mask defines the set: ``score > the K-th largest`` and, of the scores
equal to it, the lowest positions (``lax.top_k``'s own order among equals),
-0.0 and +0.0 one value. NO sort finds it (:func:`select`): the K-th largest
is counting passes over the scores' bits (a float's bits, a negative's
flipped, order as the floats do; two bits a pass), a chunk's walking only the
blocks of keys that are live, and ``idx`` is counted out of the mask
(:func:`_positions`: block counts, a one-hot product, a rank inside the
block), ascending — so the two forms cannot disagree. A ``lax.top_k`` of a
chunk's 512 x 32 768 scores took 16 ms an ``F`` layer and of the step's
10 x 32 768 1.2 ms, where this takes a few ms and 0.2 (PERF.md §6 "PR 52").
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.sparse_mla_attention import einsum_f32
from .transformer import _norm, _rope

KINDS = "Fs-"        # "-": a layer with no attention (a mixer of another kind)
KEY_BLOCK = 1024     # keys a step of a chunk's score walk
SELECT_BLOCK = 4096  # keys a step of the selection's counting walk
SELECT_BITS = 2      # bits of the threshold a counting pass settles
LANE_BLOCK = 256     # positions a block of the indices' counting-out
POSITION_ROWS = 64   # queries a tile of it


def check_config(c) -> None:
    """Refuse what an ``index_pattern`` trunk does not run, each with why."""
    pat = c.index_pattern
    attends = pat.replace("-", "")
    if len(pat) != c.n_layer or set(pat) - set(KINDS) \
            or not attends.startswith("F"):
        raise ValueError(
            f"index_pattern {pat!r} has to name each of the {c.n_layer} "
            f"layers, one of {KINDS!r}, the first that attends an 'F': a "
            "layer that takes a selection over needs one before it")
    if "-" in pat and not c.mixer_pattern:
        raise ValueError(
            "index_pattern says '-' (no attention) of a layer: only beside "
            "a mixer_pattern, which says what the layer has instead")
    if min(c.q_lora_rank, c.index_topk, c.index_heads,
           c.index_head_dim) <= 0 or c.index_head_dim < c.qk_rope_head_dim:
        raise ValueError(
            "an indexer reads the query's latent (q_lora_rank) and needs "
            "index_topk, index_heads and index_head_dim >= qk_rope_head_dim")
    if c.moe_router not in ("gshard", "sigmoid"):
        raise ValueError("index_pattern: the zaya router's carried state is "
                         "not threaded through the selection's layer loop")
    if c.loop_steps > 1:
        raise ValueError("index_pattern: a looped trunk's passes would each "
                         "need indexer keys of their own")
    if c.block_pattern or c.attn_pattern:
        raise ValueError(
            "index_pattern selects positions of a latent cache: it stands "
            "beside neither a block_pattern (one mixer a layer over K/V "
            "planes) nor an attn_pattern (window rings)")


def runs(cfg) -> list:
    """The trunk as runs of layers equal in (FFN kind, indexer kind): (the
    segment's index, the run's first layer inside it, layers, "F" | "s", its
    first layer in the trunk, its first indexer)."""
    out, layer, full = [], 0, 0
    for seg, (_, n) in enumerate(cfg.segments):
        at = 0
        while at < n:
            kind = cfg.index_pattern[layer]
            m = 1
            while at + m < n and cfg.index_pattern[layer + m] == kind:
                m += 1
            out.append((seg, at, m, kind, layer, full))
            full += m if kind == "F" else 0
            at, layer = at + m, layer + m
    return out


def init_indexers(cfg, key, dense) -> dict:
    """The ``F`` layers' indexers, stacked. The key's LayerNorm has a bias;
    scales at 1 and biases at 0 as a fresh norm's."""
    n = cfg.index_pattern.count("F")
    d, ql, H, D = (cfg.d_model, cfg.q_lora_rank, cfg.index_heads,
                   cfg.index_head_dim)
    k = iter(jax.random.split(key, 3))
    return {"wq_b": dense(next(k), (n, ql, H * D)),
            "wk": dense(next(k), (n, d, D)),
            "k_norm_scale": jnp.ones((n, D), jnp.float32),
            "k_norm_bias": jnp.zeros((n, D), jnp.float32),
            "weights_proj": dense(next(k), (n, d, H))}


def indexer_specs() -> dict:
    # small beside the attention it steers: replicated, as the latent
    # projection is
    return {"wq_b": P(None, None, None), "wk": P(None, None, None),
            "k_norm_scale": P(None, None), "k_norm_bias": P(None, None),
            "weights_proj": P(None, None, None)}


def index_keys(cfg, y, ip, positions):
    """``kI = rope(LayerNorm(y wk))`` (B, T, index_head_dim): what an ``F``
    layer caches beside its latents, one for all of the indexer's heads."""
    k = _norm(y @ ip["wk"].astype(y.dtype), ip["k_norm_scale"],
              ip["k_norm_bias"], "layernorm", cfg.norm_eps)
    if not cfg.qk_rope_head_dim:
        return k
    return _rope(k[:, :, None], k[:, :, None], positions, cfg.rope_theta,
                 cfg.qk_rope_head_dim)[1][:, :, 0]


def index_queries(cfg, y, cq, ip, positions):
    """(``qI`` (B, T, heads, index_head_dim) roped, ``w`` (B, T, heads) f32 =
    ``y weights_proj`` times heads^-1/2 index_head_dim^-1/2)."""
    B, T, _ = y.shape
    H, D = cfg.index_heads, cfg.index_head_dim
    q = (cq @ ip["wq_b"].astype(cq.dtype)).reshape(B, T, H, D)
    if cfg.qk_rope_head_dim:
        q = _rope(q, q, positions, cfg.rope_theta, cfg.qk_rope_head_dim)[0]
    w = einsum_f32("btd,dh->bth", y, ip["weights_proj"].astype(y.dtype))
    return q, w * (1.0 / math.sqrt(H * D))


def scores(q, w, keys, n_keys=None, block: int = KEY_BLOCK):
    """``I[t, s] = sum_j w[t, j] ReLU(q[t, j] . keys[s])`` (B, T, S) f32 of
    ``keys`` (B, index_head_dim, S), positions on the lanes. One product for
    a step (T == 1); T > 1 walks blocks of ``block`` keys up to ``n_keys``
    (traced, or None: all), so that no (heads, T, S) array stands — what
    lies behind is left at 0 and masked by the caller's causal rule."""
    B, T = q.shape[:2]
    S = keys.shape[-1]

    def part(k):
        s = einsum_f32("bthd,bds->bhts", q, k.astype(q.dtype))
        return jnp.einsum("bth,bhts->bts", w, jnp.maximum(s, 0.0))

    if T == 1 or S % block or S == block:
        return part(keys)
    nb = S // block if n_keys is None \
        else jnp.minimum((n_keys + block - 1) // block, S // block)

    def body(j, out):
        k = lax.dynamic_slice_in_dim(keys, j * block, block, axis=2)
        return lax.dynamic_update_slice_in_dim(out, part(k), j * block,
                                               axis=2)

    return lax.fori_loop(0, nb, body, jnp.zeros((B, T, S), jnp.float32))


def _order_keys(score, causal):
    """int32 keys whose order is the float32 scores' own (a negative
    float's magnitude bits flipped), -0.0 as +0.0 and what is no candidate
    as -inf, the lowest."""
    x = jnp.where(causal, jnp.where(score == 0, 0.0, score), -jnp.inf)
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


_NO_CANDIDATE = -0x7F800001     # _order_keys of -inf


def _kth_largest(key, k: int, n_keys=None, block: int = SELECT_BLOCK,
                 bits: int = SELECT_BITS):
    """The ``k``-th largest of each row of ``key`` (B, T, S) i32, (B, T, 1):
    bisection over the 32 bits from the top, ``bits`` a pass — a pass counts
    the row's keys >= each of its ``2**bits - 1`` candidates in one reading
    and keeps the largest that still has ``k``. With ``n_keys`` (traced) a
    pass walks the blocks of ``block`` keys that hold a position under it;
    what lies behind is no candidate, so the result is floored at that key,
    which is what counting it would give."""
    S = key.shape[-1]
    walk = n_keys is not None and S % block == 0 and S > block
    rows = key.shape[:-1] + (1,)

    def counts(part, cands):
        return tuple(jnp.sum(part >= c, axis=-1, keepdims=True,
                             dtype=jnp.int32) for c in cands)

    def narrow(i, thr):
        shift = 32 - bits * (i + 1)
        cands = [thr + lax.shift_left(jnp.int32(d), shift)
                 for d in range(1, 1 << bits)]    # ascending: the counts fall
        if walk:
            found = lax.fori_loop(
                0, jnp.minimum((n_keys + block - 1) // block, S // block),
                lambda j, n: tuple(a + b for a, b in zip(n, counts(
                    lax.dynamic_slice_in_dim(key, j * block, block, axis=2),
                    cands))),
                (jnp.zeros(rows, jnp.int32),) * len(cands))
        else:
            found = counts(key, cands)
        for cand, n in zip(cands, found):
            thr = jnp.where(n >= k, cand, thr)
        return thr

    lowest = jnp.full(rows, jnp.iinfo(jnp.int32).min, jnp.int32)
    return jnp.maximum(lax.fori_loop(0, 32 // bits, narrow, lowest),
                       _NO_CANDIDATE)


def _positions(mask, k: int):
    """The positions a ``mask`` (B, T, S) holds, at most ``k`` a row,
    ascending, -1 behind the last: (B, T, k) i32. Compares and small
    products, no sort and no scatter: a row is blocks of :data:`LANE_BLOCK`
    positions; the blocks' running counts give output ``j`` its block and
    its rank inside it, a one-hot product fetches that block's lanes, each
    marked with its rank among the block's selected (a triangular product;
    0 / 1 and ranks up to 256 are exact in bf16), and the lane that carries
    the rank is the position. In tiles of :data:`POSITION_ROWS` rows, so
    that no (rows, k, blocks) one-hot stands for more."""
    B, T, S = mask.shape
    lanes, tile_rows = LANE_BLOCK, POSITION_ROWS
    nb = -(-S // lanes)
    rows = jnp.pad(mask.reshape(B * T, S), ((0, 0), (0, nb * lanes - S)))
    upper = jnp.triu(jnp.ones((lanes, lanes), jnp.bfloat16))
    j = jnp.arange(k, dtype=jnp.int32)[None, :, None]

    def tile(m):
        m = m.reshape(m.shape[0], nb, lanes)
        rank = jnp.einsum("rnl,lm->rnm", m.astype(jnp.bfloat16), upper,
                          preferred_element_type=jnp.float32)
        ends = jnp.cumsum(rank[..., -1].astype(jnp.int32), axis=-1)[:, None]
        before = ends <= j                                   # (rows, k, nb)
        blk = jnp.sum(before, axis=-1, dtype=jnp.int32)
        start = jnp.max(jnp.where(before, ends, 0), axis=-1)
        hot = jnp.arange(nb, dtype=jnp.int32) == blk[..., None]
        got = jnp.einsum("rkn,rnl->rkl", hot.astype(jnp.bfloat16),
                         jnp.where(m, rank, 0.0).astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        want = (j[..., 0] - start + 1).astype(jnp.float32)[..., None]
        lane = jnp.sum(jnp.where(got == want,
                                 jnp.arange(lanes, dtype=jnp.int32), 0), -1)
        return jnp.where(blk < nb, blk * lanes + lane, -1)

    n = B * T
    if n <= tile_rows:
        return tile(rows).reshape(B, T, k)
    nt = -(-n // tile_rows)
    rows = jnp.pad(rows, ((0, nt * tile_rows - n), (0, 0)))
    out = lax.map(tile, rows.reshape(nt, tile_rows, nb * lanes))
    return out.reshape(nt * tile_rows, k)[:n].reshape(B, T, k)


def select(score, q_pos, topk: int, want_mask: bool = True, n_keys=None):
    """The selection of T queries at positions ``q_pos`` (B, T) from their
    scores (B, T, S) over positions 0..S-1: (``idx`` (B, T, K) i32, K =
    min(topk, S), ascending, -1 where the query has fewer candidates;
    ``mask`` (B, T, S) bool or None). A candidate is a position <= the
    query's; ``n_keys`` (traced, or None) promises that none stands at or
    behind it. No sort: the K-th largest score by bisection, the mask from
    it, the indices counted out of the mask."""
    S = score.shape[-1]
    k = min(topk, S)
    causal = jnp.arange(S, dtype=jnp.int32)[None, None] <= q_pos[..., None]
    with jax.named_scope("dsa_select"):
        key = _order_keys(score, causal)
        thr = _kth_largest(key, k, n_keys)
        # above the K-th largest, and of those equal to it the lowest
        # positions, as many as there is room for (``lax.top_k``'s order
        # among equals; a ReLU makes exact ties, all heads at 0)
        above, tied = key > thr, (key == thr) & causal
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        # the running sum over S is three passes of XLA's scan: taken only
        # where some row has more ties than room
        mask = lax.cond(
            jnp.any(jnp.sum(tied, axis=-1, keepdims=True) > room),
            lambda: above | (tied & (jnp.cumsum(tied, axis=-1) <= room)),
            lambda: above | tied)
        return _positions(mask, k), mask if want_mask else None


# ------------------------------------------------------------ pooled keys
def pool_keys(keys, pool: int):
    """The mean of every whole group of ``pool`` keys: ``keys`` (B, T, D),
    the first at a group's edge -> (B, T // pool, D), in float32 and back."""
    B, T, D = keys.shape
    n = T // pool
    return jnp.mean(keys[:, :n * pool].astype(jnp.float32).reshape(
        B, n, pool, D), axis=2).astype(keys.dtype)


def select_pooled(score, q_pos, topk: int, pool: int, want_mask: bool = True,
                  n_groups=None):
    """The selection over pooled keys: ``score`` (B, T, G) of the queries at
    positions ``q_pos`` (B, T) against the groups' keys. A query scores the
    closed groups before its own, takes the ``topk // pool`` best whole and
    always reads its own group up to itself. Returns (``idx`` (B, T, (topk
    // pool + 1) pool) i32 positions, the first ``n`` of a row valid; ``n``
    (B, T) i32; ``mask`` (B, T, G pool) bool over positions, causal
    included, or None). The open group rides through :func:`select` as the
    one candidate nothing outscores."""
    G = score.shape[-1]
    own = q_pos // pool
    score = jnp.where(jnp.arange(G, dtype=jnp.int32)[None, None]
                      == own[..., None], jnp.inf, score)
    groups, gmask = select(score, own, topk // pool + 1, want_mask, n_groups)
    lane = jnp.arange(pool, dtype=jnp.int32)
    idx = (groups[..., None] * pool + lane).reshape(
        groups.shape[:-1] + (groups.shape[-1] * pool,))
    chosen = jnp.minimum(own + 1, groups.shape[-1])
    n = chosen * pool - (pool - 1 - q_pos % pool)
    mask = None
    if want_mask:
        mask = jnp.repeat(gmask, pool, axis=-1) & (
            jnp.arange(G * pool, dtype=jnp.int32)[None, None]
            <= q_pos[..., None])
    return idx, n, mask


# ------------------------------------------------------------ full forward
def trunk(model, params, x, positions, with_selection: bool = False):
    """The layer stack on a whole sequence, no cache: a Python loop over the
    layers (this trunk is served; the forward is what tests and
    ``InferenceEngine.forward`` compare with). Returns (x, routing (expert
    layers, B, S, k) i32 — the sigmoid router's aux, as every such trunk's —
    or a zero aux), and with ``with_selection`` the ``F`` layers' ``idx``
    (F layers, B, S, K) behind them."""
    from . import mla

    cfg = model.cfg
    B, S, _ = x.shape
    segs = model.segment_params(params["layers"])
    ix = params["indexer"]
    routing, picks, mask = [], [], None
    for seg, at, n, kind, _, full in runs(cfg):
        for i in range(n):
            p = jax.tree.map(lambda a: a[at + i], segs[seg])
            y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
            q_nope, q_rope, new = mla.project(cfg, y, p, positions)
            lat = new.transpose(0, 2, 1)
            if kind == "F":
                ip = jax.tree.map(lambda a: a[full + i], ix)
                qi, w = index_queries(cfg, y, mla.query_latent(cfg, y, p),
                                      ip, positions)
                keys = index_keys(cfg, y, ip, positions).transpose(0, 2, 1)
                idx, mask = select(scores(qi, w, keys), positions,
                                   cfg.index_topk)
                picks.append(idx)
            o = mla.attend_expanded(cfg, p, q_nope, q_rope, lat, positions,
                                    S, selected=mask)
            x = x + o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)
            y2 = _norm(x, p["ln2_scale"], None, cfg.norm, cfg.norm_eps)
            out, aux = model._mlp_block(y2, p)
            x = x + out
            if jnp.issubdtype(aux.dtype, jnp.integer):
                routing.append(aux)
    aux = jnp.stack(routing) if routing else jnp.float32(0.0)
    return (x, aux, jnp.stack(picks)) if with_selection else (x, aux)

"""Pallas flash attention (fused causal attention, fwd + bwd kernels).

TPU-native answer to the reference's fused transformer kernels
(``csrc/transformer/*.cu`` and the inference softmax/attention kernels,
~13 kLoC of CUDA — SURVEY §2.3 #8/#9): on TPU the elementwise zoo evaporates
into XLA fusion and the one kernel worth hand-writing is blockwise attention.

Design (standard flash attention 2, MXU-shaped):
- forward: grid (B, H, S/blk); per q-block online-softmax stream over k/v
  blocks, accumulators in fp32 carries, saves per-row logsumexp for the
  backward.
- backward: two kernels — dq (grid over q blocks, streams k/v) and dk/dv
  (grid over k blocks, streams q/dO), both recomputing probabilities from
  the saved logsumexp; ``delta = rowsum(dO * O)`` precomputed outside.
- the tile schedule (PR 43; times are device ms a call at (16, 20, 1024, 64)
  bf16 on a v5e, PERF.md "PR 43"): under a causal mask a program's loop
  runs over the tiles UNDER the diagonal, which take no mask arithmetic at
  all (no iota, compare or select, and no second select behind the exp:
  every row keeps its own position), and the tile the diagonal crosses is a
  statically shaped step of its own. The forward (1.55 -> 0.91) and the
  dk/dv kernel (2.03 -> 1.52) hold a score tile KEYS FIRST, (keys, queries):
  the forward's running max / sum / correction are then (1, blk) rows on
  the lanes (4 registers an op where a (blk, 1) column takes 64) and its
  reductions run down the sublanes; in dk/dv lse and delta broadcast down
  the sublanes as stored and all four products are NN / NT forms (no
  (blk, blk) transpose). Both backward kernels (dq 1.30 -> 1.14) cut the
  diagonal tile into strips of 128 queries, each against the keys up to its
  own, so only the strip's last (128, 128) square is masked and what lies
  over the diagonal is not computed (``scores_computed_over_needed``: 1.50
  -> 1.12 at S 1024 / block 512). The forward keeps its diagonal tile
  whole: every strip form lost there (0.91 whole; 1.04-1.38 in strips by
  keys or by queries, 128 or 256 wide). 1/sqrt(hd) is folded into the
  operand a program holds for all its tiles (q; k in dk/dv) where that is
  exact, a power of two. A key mask keeps its select on every tile, a bias
  or ALiBi ramp is added on every tile.
- residuals: the one ``custom_vjp`` takes and returns the model's
  (B, S, H, hd) layout and names what it saves beside q/k/v — ``flash_o``
  as (B, S, H*hd) and ``flash_lse`` as one (B, H, S) row — so a remat
  policy that keeps those two names never runs the forward kernel again.
- GQA: kv heads are repeated to H with ``jnp.repeat`` *outside* the
  custom_vjp, so the head-group sum in dk/dv falls out of autodiff.
- dtype: matmul OPERANDS stay in their storage dtype (bf16 runs the MXU
  at full rate; pre-casting to f32 forces multi-pass emulation — round-5
  profile finding) with fp32 accumulation (``preferred_element_type``);
  softmax math in fp32.

On non-TPU backends the kernels run in Pallas interpret mode (tests), and
inputs that the kernel doesn't cover (padding masks, non-divisible shapes)
fall back to the plain XLA attention.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

BIG_NEG = -2.0 ** 30
SUBLANES = 8  # fp32 sublane tile: lse/delta rows replicated to (8, S)
# fallback notices warn once per process via utils.logging.warning_once


# ------------------------------------------------------------ tile helpers
def _dot_nt(a, b):
    """a (m, d) against b (n, d) over d: (m, n) in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fold_scale(x, scale):
    """The 1/sqrt(hd) factor of the scores, folded into the operand a program
    holds for all its tiles where that is exact in any float storage type (a
    power of two: hd 16, 64, 256), else left for the float32 product.
    Returns (operand, the factor still to apply or None)."""
    if math.frexp(scale)[0] == 0.5:
        return x * jnp.asarray(scale, x.dtype), None
    return x, scale


def _diag_chunk(block: int) -> int:
    """Width of the strips the backward kernels cut a diagonal tile into: one
    lane tile where the block is a larger multiple of it, else the block
    whole (nothing narrower than 128 slices on the lanes)."""
    return 128 if block > 128 and block % 128 == 0 else block


def scores_computed_over_needed(seq: int, block: int, sub=None,
                                causal: bool = True) -> float:
    """Score elements a resident kernel computes over the pairs attention
    needs, S(S+1)/2 under a causal mask: whole tiles under the diagonal, and
    of each diagonal tile the strips of ``sub`` at or under it (``None``: the
    tile whole, what the forward kernel computes). 1.50 at (1024, 512), 1.12
    with strips of 128 (both backward kernels)."""
    if not causal:
        return 1.0
    sub = sub or block
    nq, n = seq // block, block // sub
    computed = block * block * nq * (nq - 1) // 2 \
        + nq * sub * sub * n * (n + 1) // 2
    return computed / (seq * (seq + 1) // 2)


def _tile_scores(s, post, bias_tile, h_slope, q0, k0, keys_first=False):
    """What is added to a raw float32 product before any mask: the scale
    where it was not folded, a bias tile, the ALiBi ramp of a tile whose
    first query is position q0 and first key k0."""
    if post is not None:
        s = s * post
    if bias_tile is not None:
        s = s + bias_tile.astype(jnp.float32)
    if h_slope is not None:
        s = s + h_slope * _alibi_rel(q0, k0, s.shape, keys_first)
    return s


def _mask_tile(s, keys_first: bool, crosses: bool, mk):
    """Force what a score tile may not see to BIG_NEG before any exp.
    ``crosses``: the causal diagonal runs through the tile's trailing square
    (the last keys are the last queries' own; whatever lies before the
    square is wholly visible). ``mk``: the key mask of the tile's keys,
    broadcastable to it, or None. Returns (s, keep); keep is None where
    every row keeps a finite score (its own position), so that exp needs no
    second select: with no key mask only the square the diagonal crosses is
    touched, and a tile under the diagonal not at all."""
    if mk is None and not crosses:
        return s, None
    lead, trail = (0, 1) if keys_first else (1, 0)   # keys' axis, queries'
    before = s.shape[lead] - s.shape[trail]          # keys before the square

    def visible(shape, offset):
        return jax.lax.broadcasted_iota(jnp.int32, shape, lead) <= \
            jax.lax.broadcasted_iota(jnp.int32, shape, trail) + offset

    if mk is not None:
        keep = jnp.broadcast_to(mk, s.shape)
        if crosses:
            keep = keep & visible(s.shape, before)
        return jnp.where(keep, s, BIG_NEG), keep
    keep = visible((s.shape[trail],) * 2, 0)
    if keys_first:
        sq = jnp.where(keep, s[before:], BIG_NEG)
        s = jnp.concatenate([s[:before], sq], 0) if before else sq
    else:
        sq = jnp.where(keep, s[:, before:], BIG_NEG)
        s = jnp.concatenate([s[:, :before], sq], 1) if before else sq
    return s, None


# ---------------------------------------------------------------- forward
def _fwd_kernel(*refs, block: int, scale: float, causal: bool, masked: bool,
                biased: bool, alibi: bool = False):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    mask_ref = bias_ref = slopes_ref = None
    if masked:
        mask_ref = refs[i]; i += 1
    if biased:
        bias_ref = refs[i]; i += 1
    if alibi:
        slopes_ref = refs[i]; i += 1
    o_ref, lse_ref = refs[i:]
    iq = pl.program_id(2)
    h_slope = slopes_ref[0, 0] if slopes_ref is not None else None
    q, post = _fold_scale(q_ref[...], scale)            # (blk, hd) bf16
    hd = q.shape[1]
    nkb = k_ref.shape[0] // block

    def step(jk, carry, crosses=False):
        # the tile is held keys first, (blk keys, blk queries): the running
        # max, sum and correction are then (1, blk) rows on the lanes, 4
        # registers an op where a (blk, 1) column takes 64, and the two
        # reductions run down the sublanes on the VPU
        m, l, acc = carry
        keys = pl.ds(pl.multiple_of(jk * block, block), block)
        k, v = k_ref[keys, :], v_ref[keys, :]
        # additive score bias tile (blk, blk), streamed from the (blk, S)
        # row slice this q-block owns — never a full (S, S)
        # materialization; key-padding mask of this k block
        s = _tile_scores(
            _dot_nt(k, q), post,
            bias_ref[:, keys].astype(jnp.float32).T
            if bias_ref is not None else None, h_slope,
            iq * block, jk * block, True)
        mk = (mask_ref[0, keys][:, None] > 0.5
              if mask_ref is not None else None)
        s, keep = _mask_tile(s, True, crosses, mk)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (hd, blk)
        return m_new, l, acc

    carry = (jnp.full((1, block), BIG_NEG, jnp.float32),
             jnp.zeros((1, block), jnp.float32),
             jnp.zeros((hd, block), jnp.float32))
    # the tiles under the diagonal carry no mask arithmetic at all; the one
    # the diagonal crosses is a step of its own behind the loop
    carry = jax.lax.fori_loop(0, iq if causal else nkb, step, carry)
    m, l, acc = step(iq, carry, True) if causal else carry
    # l == 0 only for rows whose keys are ALL masked (e.g. left-padded
    # queries); clamp so o is 0, not NaN (their loss contribution is masked)
    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[...] = (acc / l_safe).T.astype(o_ref.dtype)
    # (8, blk): replicated across sublanes to satisfy TPU (8, 128) tiling
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l_safe), (SUBLANES, block))


def _mask_operand(mask, S):
    """(B, S) {0,1} key mask → (B, SUBLANES, S) fp32 kernel operand."""
    m = mask.astype(jnp.float32).reshape(mask.shape[0], 1, S)
    return jnp.broadcast_to(m, (mask.shape[0], SUBLANES, S))


def _alibi_rel(q0, k0, shape, keys_first: bool = False):
    """Signed key−query distance of a score tile whose first query is
    position q0 and first key k0 — the ALiBi ramp built IN-kernel, so long
    sequences never materialize an (H, S, S) bias operand (at 64k seq that
    operand alone would be 100+ GB; the decode kernel does the same from
    the live length)."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, int(keys_first))
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                          int(not keys_first))
    return (k_pos - q_pos).astype(jnp.float32)


def _masked_scores(q, k, iq, jk, block, causal, mk, h_slope, *, scale):
    """The (blk, blk) score tile of the three streamed kernels, whose grid
    step cannot tell a diagonal tile from one under it: s = scale·q·kᵀ
    (+ALiBi ramp), with causal / key-padding positions forced to BIG_NEG
    BEFORE any exp (for all-masked rows lse ~ BIG_NEG and a raw
    exp(s − lse) would overflow to inf — the round-4 fix).

    q/k arrive in their STORAGE dtype (bf16 in practice): the MXU runs
    bf16×bf16→f32 at full rate but emulates f32×f32 matmuls in multiple
    passes — pre-casting operands to f32 (the round-5 profile's finding)
    halves attention-matmul throughput. Returns (s, keep) where keep is
    None when nothing is masked."""
    s = _dot_nt(q, k) * scale
    if h_slope is not None:
        s = s + h_slope * _alibi_rel(iq * block, jk * block,
                                     (block, block))
    keep = None
    if causal:
        q_pos = iq * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = jk * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        keep = q_pos >= k_pos
    if mk is not None:
        keep = mk[None, :] if keep is None else (keep & mk[None, :])
    if keep is not None:
        s = jnp.where(keep, s, BIG_NEG)
    return s, keep


def _probs_from_lse(s, keep, lse):
    """Backward-pass probabilities recomputed from the saved logsumexp,
    masked positions zeroed — shared by the streamed backward kernels."""
    p = jnp.exp(s - lse[:, None])
    return jnp.where(keep, p, 0.0) if keep is not None else p


def _slopes_operand(slopes):
    """(H,) → (1, H) fp32 operand; each grid program receives ITS head's
    slope as a (1, 1) block via a static index map — no dynamic lane
    extract for Mosaic to lower."""
    return jnp.asarray(slopes, jnp.float32).reshape(1, -1)


def _slopes_spec(H):
    return pl.BlockSpec((1, 1), lambda b, h, i: (0, h))


def _bias_row_spec(bias_shape, B, H, block):
    """(blk, S) row-slice BlockSpec for a (BB, HH, S, S) bias with BB in
    {1, B} and HH in {1, H} (broadcast handled by the index map, NOT by
    materializing the broadcast in HBM)."""
    bb, hh = bias_shape[0], bias_shape[1]
    return pl.BlockSpec(
        (None, None, block, bias_shape[3]),
        lambda b, h, i: (b if bb > 1 else 0, h if hh > 1 else 0, i, 0))


def _bias_col_spec(bias_shape, B, H, block):
    """(S, blk) column-slice BlockSpec (dk/dv kernel: grid over k blocks)."""
    bb, hh = bias_shape[0], bias_shape[1]
    return pl.BlockSpec(
        (None, None, bias_shape[2], block),
        lambda b, h, j: (b if bb > 1 else 0, h if hh > 1 else 0, 0, j))


def _fwd_call(q, k, v, mask, bias, *, block: int, causal: bool,
              interpret: bool, alibi=None):
    B, H, S, hd = q.shape
    if bias is None and _use_streamed(S, hd, q.dtype.itemsize):
        return _fwd_call_streamed(q, k, v, mask, block=block, causal=causal,
                                  interpret=interpret, alibi=alibi)
    scale = 1.0 / math.sqrt(hd)
    grid = (B, H, S // block)
    masked, biased = mask is not None, bias is not None
    kernel = partial(_fwd_kernel, block=block, scale=scale, causal=causal,
                     masked=masked, biased=biased, alibi=alibi is not None)
    in_specs = [
        pl.BlockSpec((None, None, block, hd), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((None, None, S, hd), lambda b, h, i: (b, h, 0, 0)),
        pl.BlockSpec((None, None, S, hd), lambda b, h, i: (b, h, 0, 0)),
    ]
    args = [q, k, v]
    if masked:
        in_specs.append(pl.BlockSpec((None, SUBLANES, S),
                                     lambda b, h, i: (b, 0, 0)))
        args.append(mask)
    if biased:
        in_specs.append(_bias_row_spec(bias.shape, B, H, block))
        args.append(bias)
    if alibi is not None:
        in_specs.append(_slopes_spec(H))
        args.append(alibi)
    return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, block, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, SUBLANES, block),
                         lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, SUBLANES, S), jnp.float32),
        ],
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------- backward
def _make_bwd_dq_kernel(block: int, scale: float, causal: bool, masked: bool,
                        biased: bool = False, grad_bias: bool = False,
                        alibi: bool = False):
    sub = _diag_chunk(block)

    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        i = 6
        mask_ref = bias_ref = dbias_ref = slopes_ref = None
        if masked:
            mask_ref = refs[i]; i += 1
        if biased:
            bias_ref = refs[i]; i += 1
        if alibi:
            slopes_ref = refs[i]; i += 1
        h_slope = slopes_ref[0, 0] if slopes_ref is not None else None
        dq_ref = refs[i]; i += 1
        if grad_bias:
            dbias_ref = refs[i]
            # causal bias rows never visit what lies over the diagonal:
            # zero-fill so the untouched part doesn't carry garbage
            dbias_ref[...] = jnp.zeros(dbias_ref.shape, dbias_ref.dtype)
        iq = pl.program_id(2)
        q, post = _fold_scale(q_ref[...], scale)         # storage dtype
        do = do_ref[...]
        nkb = k_ref.shape[0] // block

        def tile(rows, k0, n, lse, delta, crosses=False):
            """dq of the block's query ``rows`` (a static slice) from the
            ``n`` keys at ``k0``; lse / delta their (len(rows), 1) columns."""
            keys = pl.ds(k0, n)
            k, v = k_ref[keys, :], v_ref[keys, :]
            s = _tile_scores(
                _dot_nt(q[rows], k), post,
                bias_ref[rows, keys] if bias_ref is not None else None,
                h_slope, iq * block + rows.start, k0)
            mk = (mask_ref[0:1, keys] > 0.5
                  if mask_ref is not None else None)
            s, keep = _mask_tile(s, False, crosses, mk)
            p = jnp.exp(s - lse)
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
            ds = p * (_dot_nt(do[rows], v) - delta)
            if dbias_ref is not None:
                # d(bias) == d(scores): each element is owned by exactly
                # one grid step, so this is a plain write
                dbias_ref[rows, keys] = ds.astype(dbias_ref.dtype)
            return jnp.dot(ds.astype(k.dtype), k,
                           preferred_element_type=jnp.float32)

        whole = slice(0, block)

        def body(jk, dq):
            # the (blk,) lane rows become columns tile by tile: held as
            # (blk, 1) columns across the loop they cost a quarter more
            return dq + tile(whole, pl.multiple_of(jk * block, block), block,
                             lse_ref[0][:, None], delta_ref[0][:, None])

        dq = jax.lax.fori_loop(0, iq if causal else nkb, body,
                               jnp.zeros(q.shape, jnp.float32))
        if causal:
            # the diagonal tile in strips of `sub` query rows, each against
            # the keys up to its own: what lies over the diagonal is not
            # computed, and only a strip's last square is masked
            k0 = pl.multiple_of(iq * block, block)
            lse, delta = lse_ref[0][:, None], delta_ref[0][:, None]
            strips = []
            for r in range(0, block, sub):
                rows = slice(r, r + sub)
                strips.append(dq[rows] + tile(rows, k0, r + sub, lse[rows],
                                              delta[rows], True))
            dq = jnp.concatenate(strips, 0)
        dq_ref[...] = (dq * scale).astype(dq_ref.dtype)

    return kernel


def _make_bwd_dkv_kernel(block: int, scale: float, causal: bool, masked: bool,
                         biased: bool = False, alibi: bool = False):
    sub = _diag_chunk(block)

    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        i = 6
        mask_ref = bias_ref = slopes_ref = None
        if masked:
            mask_ref = refs[i]; i += 1
        if biased:
            bias_ref = refs[i]; i += 1
        if alibi:
            slopes_ref = refs[i]; i += 1
        dk_ref, dv_ref = refs[i:]
        h_slope = slopes_ref[0, 0] if slopes_ref is not None else None
        jk = pl.program_id(2)
        k, post = _fold_scale(k_ref[...], scale)         # (blk, hd) storage
        v = v_ref[...]
        nqb = q_ref.shape[0] // block
        mk = None
        if mask_ref is not None:                         # this k block's
            mk = mask_ref[0, pl.ds(jk * block, block)][:, None] > 0.5

        def tile(q0, w, n, crosses=False):
            """(dk, dv) of the block's first ``n`` keys from the ``w``
            queries at ``q0``. The tile is held keys first, (n, w): lse and
            delta broadcast down the sublanes as they are stored, and all
            four products are plain NN / NT forms — no (blk, blk) transpose."""
            qs = pl.ds(q0, w)
            q, do = q_ref[qs, :], do_ref[qs, :]
            # (S, blk) column slice of the bias: rows are the queries
            s = _tile_scores(
                _dot_nt(k[:n], q), post,
                bias_ref[qs, :n].astype(jnp.float32).T
                if bias_ref is not None else None, h_slope,
                q0, jk * block, True)
            s, keep = _mask_tile(s, True, crosses,
                                 mk[:n] if mk is not None else None)
            p = jnp.exp(s - lse_ref[0:1, qs])
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
            dv = jnp.dot(p.astype(do.dtype), do,
                         preferred_element_type=jnp.float32)
            ds = p * (_dot_nt(v[:n], do) - delta_ref[0:1, qs])
            dk = jnp.dot(ds.astype(q.dtype), q,
                         preferred_element_type=jnp.float32)
            return dk, dv

        def body(iq, carry):
            dk, dv = tile(pl.multiple_of(iq * block, block), block, block)
            return carry[0] + dk, carry[1] + dv

        z = jnp.zeros(k.shape, jnp.float32)
        dk, dv = z, z
        if causal:
            # the diagonal tile in strips of `sub` queries, each against
            # the keys up to its own (see the dq kernel)
            for r in range(0, block, sub):
                n = r + sub
                a, b = tile(pl.multiple_of(jk * block + r, sub), sub, n, True)
                dk, dv = (x + y if n == block else
                          jnp.concatenate([x[:n] + y, x[n:]], 0)
                          for x, y in ((dk, a), (dv, b)))
        dk, dv = jax.lax.fori_loop(jk + 1 if causal else 0, nqb, body,
                                   (dk, dv))
        # dk accumulated against UNSCALED q: apply the 1/√hd chain-rule
        # factor once at the end
        dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)

    return kernel


def _row_operand(x):
    """(B, H, S) fp32 rows (lse, delta) → the (B, H, SUBLANES, S) sublane
    tile the backward kernels read."""
    B, H, S = x.shape
    return jnp.broadcast_to(x[:, :, None, :], (B, H, SUBLANES, S))


def _bwd_call(q, k, v, lse, delta, do, mask, bias, *, block: int,
              causal: bool, interpret: bool, grad_bias: bool = False,
              alibi=None):
    """q/k/v/do (B, H, S, hd); lse and delta = rowsum(dO * O) one (B, H, S)
    row each, replicated to the sublane tile here."""
    B, H, S, hd = q.shape
    if bias is None and _use_streamed(S, hd, q.dtype.itemsize):
        return _bwd_call_streamed(q, k, v, lse, delta, do, mask, block=block,
                                  causal=causal, interpret=interpret,
                                  alibi=alibi)
    scale = 1.0 / math.sqrt(hd)
    lse, delta = _row_operand(lse), _row_operand(delta)
    grid = (B, H, S // block)
    masked, biased = mask is not None, bias is not None
    # dbias tiles are plain writes (one owner per grid step): only valid
    # when the bias carries its own full (B, H) leading dims — broadcast
    # biases would need cross-iteration accumulation
    assert not grad_bias or (biased and bias.shape[:2] == (B, H))
    blk_spec = pl.BlockSpec((None, None, block, hd), lambda b, h, i: (b, h, i, 0))
    full_spec = pl.BlockSpec((None, None, S, hd), lambda b, h, i: (b, h, 0, 0))
    row_blk = pl.BlockSpec((None, None, SUBLANES, block),
                           lambda b, h, i: (b, h, 0, i))
    row_full = pl.BlockSpec((None, None, SUBLANES, S),
                            lambda b, h, i: (b, h, 0, 0))
    mask_spec = pl.BlockSpec((None, SUBLANES, S), lambda b, h, i: (b, 0, 0))
    extra_args = ([mask] if masked else []) + ([bias] if biased else []) \
        + ([alibi] if alibi is not None else [])
    extra_row = ([mask_spec] if masked else []) \
        + ([_bias_row_spec(bias.shape, B, H, block)] if biased else []) \
        + ([_slopes_spec(H)] if alibi is not None else [])
    extra_col = ([mask_spec] if masked else []) \
        + ([_bias_col_spec(bias.shape, B, H, block)] if biased else []) \
        + ([_slopes_spec(H)] if alibi is not None else [])
    has_alibi = alibi is not None

    dq_outs = pl.pallas_call(
        _make_bwd_dq_kernel(block, scale, causal, masked, biased, grad_bias,
                            has_alibi),
        name="flash_attention_bwd_dq",
        grid=grid,
        in_specs=[blk_spec, full_spec, full_spec, blk_spec, row_blk, row_blk]
                 + extra_row,
        out_specs=[blk_spec] + ([_bias_row_spec(bias.shape, B, H, block)]
                                if grad_bias else []),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)]
                  + ([jax.ShapeDtypeStruct(bias.shape, bias.dtype)]
                     if grad_bias else []),
        interpret=interpret,
    )(q, k, v, do, lse, delta, *extra_args)
    dq = dq_outs[0]
    dbias = dq_outs[1] if grad_bias else None

    dk, dv = pl.pallas_call(
        _make_bwd_dkv_kernel(block, scale, causal, masked, biased, has_alibi),
        name="flash_attention_bwd_dkv",
        grid=grid,
        in_specs=[full_spec, blk_spec, blk_spec, full_spec, row_full, row_full]
                 + extra_col,
        out_specs=[blk_spec, blk_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
    )(q, k, v, do, lse, delta, *extra_args)
    return dq, dk, dv, dbias


# ----------------------------------------------- streamed (long-seq) kernels
# The baseline kernels above stage the ENTIRE (S, hd) K/V (fwd, dq) or
# Q/dO (dkv) operand in VMEM and fori_loop over it — simple and fast up
# to ~8k tokens, but the staged operand grows linearly with S and blows
# the ~16 MiB scoped-VMEM budget near 16-32k (round-5 measurement: the
# 32k fwd wants a 32.5 MiB stack allocation). Past _STREAM_VMEM_BYTES
# the calls switch to a 4D grid (B, H, nq, nk) that streams the inner
# operand block-by-block through the grid's innermost dimension, carrying
# the online-softmax state (fwd: m/l/acc; bwd: grad accumulators) in VMEM
# scratch across inner steps — constant VMEM in S, the canonical TPU
# flash-attention shape. Causal skipping is a pl.when guard (idle DMA for
# the never-visible triangle, no compute). Bias operands stay on the
# baseline path: learned-bias callers (evoformer pair stacks) are
# short-sequence by construction.
_STREAM_VMEM_BYTES = 6 * 1024 * 1024


def _use_streamed(S, hd, itemsize) -> bool:
    # 2 operands (k+v or q+do) x double buffering; callers pre-exclude
    # biased inputs (bias stays on the baseline path). 6 MiB: S=16384 at
    # hd=64 bf16 computes to exactly 8 MiB and the baseline form measured
    # a 16.8 MiB scoped-vmem OOM there (round-5 16k row) — the boundary
    # must stream; S<=8192 (4.2 MiB) measured fine on the baseline form.
    return 2 * S * hd * itemsize * 2 > _STREAM_VMEM_BYTES


def _vmem_scratch(block, hd):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((block, 128), jnp.float32),     # m (lane-replicated)
            pltpu.VMEM((block, 128), jnp.float32),     # l
            pltpu.VMEM((block, hd), jnp.float32)]      # acc


def _fwd_kernel_streamed(*refs, block: int, scale: float, causal: bool,
                         masked: bool, alibi: bool, nk: int):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    mask_ref = slopes_ref = None
    if masked:
        mask_ref = refs[i]; i += 1
    if alibi:
        slopes_ref = refs[i]; i += 1
    o_ref, lse_ref = refs[i:i + 2]
    m_scr, l_scr, acc_scr = refs[i + 2:]
    iq, jk = pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, BIG_NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        mk = mask_ref[0, :] > 0.5 if mask_ref is not None else None
        h_slope = slopes_ref[0, 0] if slopes_ref is not None else None
        s, keep = _masked_scores(q, k, iq, jk, block, causal, mk, h_slope,
                                 scale=scale)
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    if causal:
        pl.when(jk <= iq)(_step)
    else:
        _step()

    @pl.when(jk == (iq if causal else nk - 1))
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        m_col = m_scr[:, 0]
        lse_ref[...] = jnp.broadcast_to(
            (m_col + jnp.log(l_safe[:, 0]))[None, :], (SUBLANES, block))


def _fwd_call_streamed(q, k, v, mask, *, block: int, causal: bool,
                       interpret: bool, alibi=None):
    B, H, S, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    nq = nk = S // block
    masked = mask is not None
    kernel = partial(_fwd_kernel_streamed, block=block, scale=scale,
                     causal=causal, masked=masked, alibi=alibi is not None,
                     nk=nk)
    in_specs = [
        pl.BlockSpec((None, None, block, hd), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((None, None, block, hd), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((None, None, block, hd), lambda b, h, i, j: (b, h, j, 0)),
    ]
    args = [q, k, v]
    if masked:
        in_specs.append(pl.BlockSpec((None, SUBLANES, block),
                                     lambda b, h, i, j: (b, 0, j)))
        args.append(mask)
    if alibi is not None:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, h, i, j: (0, h)))
        args.append(alibi)
    return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, block, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, SUBLANES, block),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, SUBLANES, S), jnp.float32),
        ],
        scratch_shapes=_vmem_scratch(block, hd),
        interpret=interpret,
    )(*args)


def _make_bwd_dq_kernel_streamed(block: int, scale: float, causal: bool,
                                 masked: bool, alibi: bool, nk: int):
    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        i = 6
        mask_ref = slopes_ref = None
        if masked:
            mask_ref = refs[i]; i += 1
        if alibi:
            slopes_ref = refs[i]; i += 1
        dq_ref = refs[i]
        dq_scr = refs[i + 1]
        iq, jk = pl.program_id(2), pl.program_id(3)

        @pl.when(jk == 0)
        def _init():
            dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

        def _step():
            q = q_ref[...]
            do = do_ref[...]
            lse = lse_ref[0]
            delta = delta_ref[0]
            k = k_ref[...]
            v = v_ref[...]
            mk = mask_ref[0, :] > 0.5 if mask_ref is not None else None
            h_slope = slopes_ref[0, 0] if slopes_ref is not None else None
            s, keep = _masked_scores(q, k, iq, jk, block, causal, mk,
                                     h_slope, scale=scale)
            p = _probs_from_lse(s, keep, lse)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None])
            dq_scr[...] = dq_scr[...] + jnp.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

        if causal:
            pl.when(jk <= iq)(_step)
        else:
            _step()

        @pl.when(jk == (iq if causal else nk - 1))
        def _finalize():
            dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)

    return kernel


def _make_bwd_dkv_kernel_streamed(block: int, scale: float, causal: bool,
                                  masked: bool, alibi: bool, nq: int):
    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        i = 6
        mask_ref = slopes_ref = None
        if masked:
            mask_ref = refs[i]; i += 1
        if alibi:
            slopes_ref = refs[i]; i += 1
        dk_ref, dv_ref = refs[i:i + 2]
        dk_scr, dv_scr = refs[i + 2:]
        jk, iq = pl.program_id(2), pl.program_id(3)   # iq innermost

        @pl.when(iq == 0)
        def _init():
            dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
            dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

        def _step():
            k = k_ref[...]
            v = v_ref[...]
            q = q_ref[...]
            do = do_ref[...]
            lse = lse_ref[0]
            delta = delta_ref[0]
            mk = mask_ref[0, :] > 0.5 if mask_ref is not None else None
            h_slope = slopes_ref[0, 0] if slopes_ref is not None else None
            s, keep = _masked_scores(q, k, iq, jk, block, causal, mk,
                                     h_slope, scale=scale)
            p = _probs_from_lse(s, keep, lse)
            dv_scr[...] = dv_scr[...] + jnp.dot(
                p.astype(do.dtype).T, do, preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None])
            dk_scr[...] = dk_scr[...] + jnp.dot(
                ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32)

        if causal:
            pl.when(iq >= jk)(_step)
        else:
            _step()

        @pl.when(iq == nq - 1)
        def _finalize():
            # dk accumulated against UNSCALED q (see baseline dkv kernel)
            dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    return kernel


def _bwd_call_streamed(q, k, v, lse, delta, do, mask, *, block: int,
                       causal: bool, interpret: bool, alibi=None):
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    lse, delta = _row_operand(lse), _row_operand(delta)
    nq = nk = S // block
    masked = mask is not None
    q_blk = pl.BlockSpec((None, None, block, hd),
                         lambda b, h, i, j: (b, h, i, 0))
    kv_blk = pl.BlockSpec((None, None, block, hd),
                          lambda b, h, i, j: (b, h, j, 0))
    row_q = pl.BlockSpec((None, None, SUBLANES, block),
                         lambda b, h, i, j: (b, h, 0, i))
    mask_kv = pl.BlockSpec((None, SUBLANES, block),
                           lambda b, h, i, j: (b, 0, j))
    slope_spec = pl.BlockSpec((1, 1), lambda b, h, i, j: (0, h))
    extra_args = ([mask] if masked else []) \
        + ([alibi] if alibi is not None else [])
    extra_dq = ([mask_kv] if masked else []) \
        + ([slope_spec] if alibi is not None else [])

    dq = pl.pallas_call(
        _make_bwd_dq_kernel_streamed(block, scale, causal, masked,
                                     alibi is not None, nk),
        name="flash_attention_bwd_dq",
        grid=(B, H, nq, nk),
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, row_q, row_q] + extra_dq,
        out_specs=[q_blk],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta, *extra_args)[0]

    # dkv grid: iq runs innermost so dk/dv accumulate across q blocks
    q_blk2 = pl.BlockSpec((None, None, block, hd),
                          lambda b, h, j, i: (b, h, i, 0))
    kv_blk2 = pl.BlockSpec((None, None, block, hd),
                           lambda b, h, j, i: (b, h, j, 0))
    row_q2 = pl.BlockSpec((None, None, SUBLANES, block),
                          lambda b, h, j, i: (b, h, 0, i))
    mask_kv2 = pl.BlockSpec((None, SUBLANES, block),
                            lambda b, h, j, i: (b, 0, j))
    slope2 = pl.BlockSpec((1, 1), lambda b, h, j, i: (0, h))
    extra_dkv = ([mask_kv2] if masked else []) \
        + ([slope2] if alibi is not None else [])
    dk, dv = pl.pallas_call(
        _make_bwd_dkv_kernel_streamed(block, scale, causal, masked,
                                      alibi is not None, nq),
        name="flash_attention_bwd_dkv",
        grid=(B, H, nk, nq),
        in_specs=[q_blk2, kv_blk2, kv_blk2, q_blk2, row_q2, row_q2]
                 + extra_dkv,
        out_specs=[kv_blk2, kv_blk2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32)] * 2,
        interpret=interpret,
    )(q, k, v, do, lse, delta, *extra_args)
    return dq, dk, dv, None


# ------------------------------------------------------------- custom VJP
# What the forward rule hands the backward beside q/k/v, by the name a remat
# policy may keep (runtime/engine.py OFFLOAD_ACTIVATION_NAMES): a trunk that
# saves these two spares its backward the kernel's forward, the S^2 work.
RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _fwd_rows(block, causal, interpret, q, k, v, bias, slopes, mask):
    """The forward kernel on (B, S, H, hd) operands. Returns o as the
    output projection reads it, (B, S, H*hd) with the lanes full (the
    kernel's own (B, H, S, hd) may be stored with hd = 64 padded to 128
    lanes), and lse as ONE (B, H, S) row of the SUBLANES equal ones the
    kernel writes."""
    B, S, H, hd = q.shape
    o, lse = _fwd_call(*(x.swapaxes(1, 2) for x in (q, k, v)), mask, bias,
                       block=block, causal=causal, interpret=interpret,
                       alibi=slopes)
    return o.swapaxes(1, 2).reshape(B, S, H * hd), lse[:, :, 0]


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash(block, causal, interpret, grad_bias, q, k, v, bias, slopes, mask):
    """q/k/v (B, S, H, hd) in and o out, the layout the model holds them
    in: the transposes to the kernels' (B, H, S, hd) are inside the rule,
    so what it saves needs none. ``bias`` (4D), ``slopes`` ((1, H)
    operand) and ``mask`` ((B, SUBLANES, S) operand) may each be None."""
    o, _ = _fwd_rows(block, causal, interpret, q, k, v, bias, slopes, mask)
    return o.reshape(q.shape)


def _flash_fwd(block, causal, interpret, grad_bias, q, k, v, bias, slopes,
               mask):
    o, lse = _fwd_rows(block, causal, interpret, q, k, v, bias, slopes, mask)
    if o.shape[-1] % 128 == 0:
        # Held row-major where that fills the lanes, as the projection and
        # delta read it. Left to itself the compiler stacked a 36-layer
        # scan's saved o with the positions on the lanes, (L, B, D, S), and
        # the backward copied each layer's slice back for the wo product and
        # once more in float32 for delta: 18 ms of an 888 ms step and 80 MiB
        # (GPT-2 774M on a v5e, PERF.md "PR 41"). A width off the lanes
        # (1.5B's 1600) is the compiler's to lay out: it puts the positions
        # on the lanes everywhere, and row-major there is one more copy.
        o = with_layout_constraint(o, Layout(major_to_minor=(0, 1, 2)))
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return o.reshape(q.shape), (q, k, v, o, lse, bias, slopes, mask)


def _flash_bwd(block, causal, interpret, grad_bias, res, g):
    q, k, v, o, lse, bias, slopes, mask = res
    # o serves delta = rowsum(dO * O) alone: reduced in the layout it was
    # saved in, and only the (B, S, H) result transposed
    delta = jnp.sum(g.astype(jnp.float32)
                    * o.reshape(q.shape).astype(jnp.float32), axis=-1)
    dq, dk, dv, dbias = _bwd_call(
        *(x.swapaxes(1, 2) for x in (q, k, v)), lse, delta.swapaxes(1, 2),
        g.swapaxes(1, 2), mask, bias, block=block, causal=causal,
        interpret=interpret, grad_bias=grad_bias, alibi=slopes)
    if bias is not None and dbias is None:
        # Broadcast-shaped biases (ALiBi slopes x positions, padding
        # biases) are positional constants: a zero cotangent is correct
        # and DCE'd under jit. Learned biases must come in full-shape
        # (B, H, S, S) to get a real dbias (enforced in flash_attention).
        dbias = jnp.zeros_like(bias)
    # slopes are deterministic positional constants and the mask is {0,1}
    # data: zero cotangents
    dslopes = None if slopes is None else jnp.zeros_like(slopes)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return (*(x.swapaxes(1, 2) for x in (dq, dk, dv)), dbias, dslopes, dmask)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------------- public API
def flash_attention(q, k, v, *, mask: Optional[jnp.ndarray] = None,
                    bias: Optional[jnp.ndarray] = None,
                    bias_is_constant: bool = False,
                    alibi_slopes: Optional[jnp.ndarray] = None,
                    causal: bool = True, block: int = 512,
                    interpret: Optional[bool] = None):
    """Fused causal attention. q: (B, S, H, hd); k/v: (B, S, KV, hd).

    ``mask`` is a (B, S) key-padding mask ({0,1}); it is applied INSIDE the
    kernel (fwd and both bwd kernels), so padded/packed batches stay on the
    fused path — the reference-parity requirement the round-1 fallback
    violated.

    ``bias`` is an additive score bias, shape (S, S), (H, S, S),
    (B|1, H|1, S, S) — streamed into the fwd and both bwd kernels in
    (block, S) slices, never materializing (B, H, S, S) *scores* in HBM.
    Gradient handling by shape:

    - full (B, H, S, S): differentiable in-kernel (dbias = ds tiles — the
      evoformer pair-bias case, reference
      csrc/deepspeed4science/evoformer_attn/);
    - broadcast shapes with ``bias_is_constant=True``: index-map broadcast,
      explicit ``stop_gradient`` — zero HBM cost, for positional constants
      (ALiBi, additive masks);
    - broadcast shapes otherwise: broadcast OUTSIDE the kernel so the
      ``broadcast_to`` transpose sums a CORRECT cotangent for learned
      shared biases (costs a (B, H, S, S) bias materialization — still
      cheaper than the dense path, which adds scores+probs on top; pass
      ``bias_is_constant=True`` to opt out when the bias isn't trained).

    ``alibi_slopes``: (H,) per-head slopes — the ALiBi distance ramp is
    built IN-kernel from block indices (an (H, S, S) bias operand at 64k
    seq would be 100+ GB; slopes cost H floats). Mutually exclusive with
    ``bias``.

    ``block`` default 512. The three kernels' device ms a call at
    (16, 20, 1024, 64) bf16 on a v5e with this schedule (PERF.md "PR 43"):
    block 128 → 4.33 / 3.86 / 4.09 (fwd / dq / dkv), 256 → 1.82 / 1.74 /
    2.49, 512 → 0.91 / 1.14 / 1.52 — wider tiles feed the MXU 512-wide
    dots and cut the loop trips 4×; a (512, 512) f32 score tile is 1 MiB of
    VMEM. What a wide diagonal tile wastes over the diagonal the backward
    kernels cut away in strips of 128 inside (``_diag_chunk``; no argument).
    Shapes not divisible by the block clamp it to S (single tile), then
    shrink toward the largest power-of-two divisor of S ≥ 128 (512 → 256
    → 128, one-shot warning) so S = 768/1152/1920 stay fused.

    The only remaining fallback is S with no fused-eligible divisor
    (warned once — the dense path is an HBM cliff at long sequence).
    """
    B, S, H, hd = q.shape
    assert bias is None or alibi_slopes is None, \
        "pass either bias or alibi_slopes, not both"
    blk = min(block, S)
    if S % blk != 0:
        # Shrink to the largest halving of the block ≥ 128 that divides S
        # before giving up: S = 768/1152/1920 are divisible by 256 or 128
        # and must stay fused — the dense fallback materializes
        # (B, H, S, S) scores. Candidates derive from blk (a 1024 caller
        # block still tries 512 first), wider-first because wider tiles
        # feed the MXU better (the block sweep in the docstring).
        cand = blk // 2
        while cand >= 128:
            if S % cand == 0:
                from ..utils.logging import warning_once

                warning_once(
                    f"flash_attention: seq {S} not divisible by block "
                    f"{blk}; shrinking to {cand} to stay on the fused "
                    "path (wider tiles feed the MXU better — pad S to "
                    f"a multiple of {blk} to avoid the shrink)")
                blk = cand
                break
            cand //= 2
    # Mosaic has no f16: fp16-compute inputs (any of q/k/v — an fp16 KV
    # cache under a bf16 trunk counts) take the same XLA fallback as
    # non-divisible shapes; bf16/f32 stay fused. Warn ONCE for the f16
    # case: the dense path materializes (B, H, S, S) scores, an HBM cliff
    # at long sequence that would otherwise surface as an opaque OOM.
    f16_in = any(jnp.dtype(x.dtype) == jnp.float16 for x in (q, k, v)) \
        and jax.default_backend() == "tpu"
    if f16_in:
        from ..utils.logging import warning_once

        warning_once(
            "flash_attention: float16 inputs fall back to the dense "
            "XLA path on TPU (Mosaic has no f16). The dense path "
            "materializes (B, H, S, S) scores — prefer bf16 compute "
            "for long sequences.")
    if f16_in or S % blk != 0:
        if S % blk != 0:
            from ..utils.logging import warning_once

            warning_once(
                f"flash_attention: seq {S} has no fused-eligible block "
                f"divisor (tried {blk}, 256, 128); demoting to the "
                "dense XLA path, which materializes (B, H, S, S) "
                "scores in HBM")
        from ..models.transformer import alibi_bias, causal_attention

        if alibi_slopes is not None:
            bias = alibi_bias(alibi_slopes, S)
        o = causal_attention(q, k, v, mask=mask, causal=causal, bias=bias)
        # a trunk built on this function tags no attn_out of its own: the
        # demoted output stands under the kernel's name for it
        return checkpoint_name(o.reshape(B, S, H * hd),
                               RESIDUAL_NAMES[0]).reshape(o.shape)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    KV = k.shape[2]
    from ..platform.mesh import attention_shard_axes

    axes = attention_shard_axes(B, H, KV) if bias is None else None
    if axes is not None:
        # GSPMD cannot partition a Mosaic kernel: run it per shard, batch
        # over the example-parallel axes and heads over model/seq (inside
        # the body the axes are manual, so the recursion lands below)
        mesh, b_ax, h_ax = axes
        qkv = P(b_ax, None, h_ax, None)
        extra = {"mask": (mask, P(b_ax, None)),
                 "alibi_slopes": (alibi_slopes, P(h_ax))}
        names = [n for n, (a, _) in extra.items() if a is not None]

        def per_shard(q, k, v, *rest):
            return flash_attention(q, k, v, causal=causal, block=blk,
                                   interpret=interpret,
                                   **dict(zip(names, rest)))

        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(qkv, qkv, qkv) + tuple(extra[n][1] for n in names),
            out_specs=qkv, check_vma=False)(
                q, k, v, *(extra[n][0] for n in names))
    if KV != H:  # GQA: differentiable repeat — dk/dv group-sum via autodiff
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    grad_bias = False
    if bias is not None:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        if bias.shape[:2] != (B, H):
            if bias_is_constant:
                bias = jax.lax.stop_gradient(bias)
            else:
                # learned shared bias: materialize the broadcast so its
                # transpose sums the true dbias (silent zero grads were
                # the round-4 review's finding #1)
                bias = jnp.broadcast_to(bias, (B, H) + bias.shape[2:])
        grad_bias = bias.shape[:2] == (B, H)
    return _flash(blk, causal, interpret, grad_bias, q, k, v, bias,
                  _slopes_operand(alibi_slopes)
                  if alibi_slopes is not None else None,
                  _mask_operand(mask, S) if mask is not None else None)


def make_flash_attention(block: int = 512, interpret: Optional[bool] = None,
                         bias_is_constant: bool = True):
    """attention_fn factory for :class:`TransformerLM`.

    ``bias_is_constant=True`` (the model-path default) stop-gradients a
    broadcast-shaped bias — correct for ALiBi ramps, WRONG for a learned
    bias. Callers training through the bias (e.g. evoformer pair bias)
    must pass ``bias_is_constant=False`` to get true dbias tiles."""

    def attn(q, k, v, *, mask=None, bias=None, alibi_slopes=None):
        # model-path biases are ALiBi distance ramps: positional
        # constants, streamed via index-map broadcast at zero HBM cost
        # (slopes preferred: the ramp is built in-kernel)
        return flash_attention(q, k, v, mask=mask, bias=bias,
                               alibi_slopes=alibi_slopes,
                               bias_is_constant=bias_is_constant, block=block,
                               interpret=interpret)

    # capability flags: constant-bias only under the default factory args —
    # learned-bias callers must rebuild with bias_is_constant=False
    attn.accepts_bias = True
    attn.bias_is_constant = bias_is_constant
    attn.accepts_alibi_slopes = True  # in-kernel ramp: no (H,S,S) operand
    # the forward rule names what it hands the backward (RESIDUAL_NAMES):
    # the trunk leaves its projected attn_out untagged, and a names policy
    # keeps the kernel's o and lse in its place
    attn.names_residuals = True
    return attn

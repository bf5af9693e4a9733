"""Fused softmax-cross-entropy over the unembedding: Pallas TPU kernel.

The naive head computes ``logits = h @ W`` ((T, V), ~800 MiB bf16 for GPT-2
shapes), then reduces them — three-plus HBM round-trips over the largest
tensor in the step, and the backward materializes a (T, V) d_logits as
well. This kernel streams W in (block_v, d) tiles and keeps each logits
tile in VMEM only: forward emits just the per-token NLL and logsumexp
(flash-attention's online-softmax trick applied to the vocab dim, the same
role the reference's fused CUDA softmax/logits kernels play,
``csrc/transformer/inference/csrc/softmax.cu``); backward recomputes
logits per tile and feeds ``p - onehot`` straight into the dx / dW
matmuls. HBM traffic drops from O(T*V) tensors to O(T + V*d) operands.

Layout: W is taken in (V, d) — the natural layout of a tied embedding
table, so no transpose is ever materialized. An optional output bias
(BERT's decoder bias) rides along: (V,) added per tile, gradient
accumulated in the dW kernel. The backward runs two kernels with
transposed grids (dx accumulates over vocab tiles per token block; dW and
dbias over token blocks per vocab tile) because a Pallas TPU output block
may only be revisited on consecutive grid steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

SUBLANES = 8
BIG_NEG = -1e30


def _tile_logits(x, w, b, vj, V):
    """One (bt, bv) logits tile in f32, vocab padding masked."""
    bt, bv = x.shape[0], w.shape[0]
    logits = lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    logits = logits + b[None, :]
    col = vj * bv + lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    return jnp.where(col < V, logits, BIG_NEG), col


# ------------------------------------------------------------------ forward
def _fwd_kernel(x_ref, w_ref, b_ref, t_ref, nll_ref, lse_ref,
                m_sc, s_sc, tgt_sc, *, V: int, n_vj: int,
                partials: bool = False):
    """``partials=False``: emit per-token (nll, lse). ``partials=True``
    (TP vocab shards): emit per-token (target-logit partial, shard-local
    logsumexp m + log s); the cross-shard combine (pmax/psum) happens
    upstream in ``_fwd_tp``. Both modes share every tile op; only _emit
    differs."""
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, BIG_NEG, jnp.float32)
        s_sc[...] = jnp.zeros(s_sc.shape, jnp.float32)
        tgt_sc[...] = jnp.zeros(tgt_sc.shape, jnp.float32)

    logits, col = _tile_logits(x_ref[...], w_ref[...],
                               b_ref[0, :].astype(jnp.float32), vj, V)
    t = t_ref[0, :]                                    # (bt,) int32
    # col < V guard: under TP a FOREIGN shard's shifted target id can land
    # in this shard's padded vocab region [V, Vp), where logits are
    # BIG_NEG — matching it would poison the psum'd target partial with
    # -1e30 (real hit: NeoX vocab 50304 / tp 4 pads 12576→12800)
    tgt_sc[...] += jnp.sum(
        jnp.where((col == t[:, None]) & (col < V), logits, 0.0),
        axis=1, keepdims=True)
    m = m_sc[...]
    m_new = jnp.maximum(m, jnp.max(logits, axis=1, keepdims=True))
    s_sc[...] = (s_sc[...] * jnp.exp(m - m_new)
                 + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
    m_sc[...] = m_new

    @pl.when(vj == n_vj - 1)
    def _emit():
        if partials:
            # shard-local (m, tgt) ride out for the cross-shard combine;
            # s is carried as log for a numerically uniform psum upstream
            a = m_sc[:, 0] + jnp.log(jnp.maximum(s_sc[:, 0], 1e-30))
            nll_ref[...] = jnp.broadcast_to(tgt_sc[:, 0][None, :],
                                            nll_ref.shape)
            lse_ref[...] = jnp.broadcast_to(a[None, :], lse_ref.shape)
        else:
            lse = m_sc[:, 0] + jnp.log(s_sc[:, 0])
            # (SUBLANES, bt): replicated across sublanes for (8,128) tiling
            nll_ref[...] = jnp.broadcast_to((lse - tgt_sc[:, 0])[None, :],
                                            nll_ref.shape)
            lse_ref[...] = jnp.broadcast_to(lse[None, :], lse_ref.shape)


# ----------------------------------------------------------------- backward
def _dlogits(x, w, b, t, lse, g, vj, V):
    """Recompute one logits tile; return (softmax - onehot) * dnll (f32)."""
    logits, col = _tile_logits(x, w, b, vj, V)
    p = jnp.exp(logits - lse[:, None])                 # exact: saved lse
    # col < V: a foreign target in the padded region must not set a onehot
    # (its dw/db rows are sliced off and padded w rows are zeros, so the
    # damage would be bounded — but keep fwd/bwd masking identical)
    onehot = ((col == t[:, None]) & (col < V)).astype(jnp.float32)
    return (p - onehot) * g[:, None]                   # (bt, bv)


def _dx_kernel(x_ref, w_ref, b_ref, t_ref, lse_ref, g_ref, dx_ref, acc_sc,
               *, V: int, n_vj: int):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    dl = _dlogits(x_ref[...], w_ref[...], b_ref[0, :].astype(jnp.float32),
                  t_ref[0, :], lse_ref[0, :], g_ref[0, :], vj, V)
    acc_sc[...] += jnp.dot(dl.astype(w_ref.dtype), w_ref[...],
                           preferred_element_type=jnp.float32)

    @pl.when(vj == n_vj - 1)
    def _emit():
        dx_ref[...] = acc_sc[...].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, b_ref, t_ref, lse_ref, g_ref, dw_ref, db_ref,
               acc_sc, bacc_sc, *, V: int, n_ti: int):
    vj = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        bacc_sc[...] = jnp.zeros(bacc_sc.shape, jnp.float32)

    x = x_ref[...]
    dl = _dlogits(x, w_ref[...], b_ref[0, :].astype(jnp.float32),
                  t_ref[0, :], lse_ref[0, :], g_ref[0, :], vj, V)
    acc_sc[...] += lax.dot_general(dl.astype(x.dtype), x,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    bacc_sc[...] += jnp.sum(dl, axis=0, keepdims=True)

    @pl.when(ti == n_ti - 1)
    def _emit():
        dw_ref[...] = acc_sc[...].astype(dw_ref.dtype)
        db_ref[...] = jnp.broadcast_to(bacc_sc[...], db_ref.shape)


# ----------------------------------------------------------------- wrapper
def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _rep(v):
    """(T,) → (SUBLANES, T) replicated operand for TPU tiling."""
    return jnp.broadcast_to(v[None, :], (SUBLANES, v.shape[0]))


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _pow2_ceil(n):
    return 1 << max(0, math.ceil(math.log2(max(1, n))))


def _resolve_interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


# Element budget for the kernels' VMEM stack ((bt + bv) x d tiles,
# double-buffered): the default (256, 512) tiles measure ~13 MiB of scoped
# VMEM at d=2048 (and 16.8 MiB at d=2560 — the round-5 remote-compile OOM),
# so (256+512)*2048 elements is the proven-safe ceiling.
_TILE_ELEM_BUDGET = (256 + 512) * 2048
_MIN_TILE = 128


def fused_xent_eligible_d(d: int) -> bool:
    """Can the kernels' tiles be shrunk to fit scoped VMEM at this feature
    width? Past d=6144 even the minimum (128, 128) tiles blow the budget —
    gates must route the XLA loss path instead."""
    return (2 * _MIN_TILE) * d <= _TILE_ELEM_BUDGET


def fused_xent_eligible(cfg_dtype, compute_dtype, d_model: int) -> bool:
    """Shared hardware-eligibility gate for the decoder and T5 loss paths
    (model-structure checks stay with each model). False when:

    - float16 could reach the kernel on TPU, via EITHER the trunk's
      activation dtype (cfg) or the engine's compute params (fp16 engines
      cast params to f16 even when cfg.dtype stays bf16) — Mosaic has no
      f16 ("Unsupported type in mosaic dialect", round-5 smoke); interpret
      mode on other backends handles f16 fine;
    - the feature width is past what tile-shrinking can fit in scoped VMEM
      (fused_xent_eligible_d)."""
    if jax.default_backend() == "tpu" and (
            jnp.dtype(cfg_dtype) == jnp.float16
            or (compute_dtype is not None
                and jnp.dtype(compute_dtype) == jnp.float16)):
        return False
    return fused_xent_eligible_d(d_model)


def _pow2_floor_tile(b):
    """Normalize a user block to a lane-aligned power of two: a 192 block
    would otherwise reach Mosaic as a misaligned 192-lane tile whenever
    the VMEM budget doesn't force shrinking (the shrink-loop clamp alone
    only covers the shrinking case)."""
    p = 1 << (int(b).bit_length() - 1)       # power-of-two floor
    return max(_MIN_TILE, p)


def _blocks(T, V, block_t, block_v, d=0):
    bt = min(_pow2_floor_tile(block_t), _pow2_ceil(T))
    bv = min(_pow2_floor_tile(block_v), _pow2_ceil(V))
    # shrink tiles (largest first) until the ELEMENT budget holds at this
    # d — a ratio-with-floor underestimates past d~4096 (round-5 review).
    # Each halving clamps at _MIN_TILE: a non-power-of-two user block
    # (e.g. 192) must land on the 128 lane floor, not sail past it to 96.
    while d and (bt + bv) * d > _TILE_ELEM_BUDGET \
            and (bt > _MIN_TILE or bv > _MIN_TILE):
        if bv >= bt and bv > _MIN_TILE:
            bv = max(_MIN_TILE, bv // 2)
        else:
            bt = max(_MIN_TILE, bt // 2)
    return bt, bv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_token_nll(x, w, bias, targets, block_t=256, block_v=512,
                    interpret=None):
    """Per-token NLL of ``softmax(x @ w.T + bias)`` with no (T, V) tensor.

    x: (T, d) compute dtype; w: (V, d) — unembedding in embedding-table
    layout; bias: (V,) or None; targets: (T,) int32 in [0, V).
    Returns (T,) fp32 NLL. Differentiable in x, w, bias.
    """
    nll, _ = _fwd(x, w, bias, targets, block_t, block_v, interpret)
    return nll


def _operands(x, w, bias, targets, bt, bv, extra=()):
    xp = _pad_to(x, bt, 0)
    wp = _pad_to(w, bv, 0)
    bp = _pad_to(jnp.zeros((w.shape[0],), x.dtype) if bias is None
                 else bias.astype(x.dtype), bv, 0)
    tp = _pad_to(targets, bt, 0)
    return xp, wp, _rep(bp), _rep(tp), *(
        _rep(_pad_to(e, bt, 0)) for e in extra)


def _fwd(x, w, bias, targets, block_t, block_v, interpret, partials=False):
    T, d = x.shape
    V = w.shape[0]
    interpret = _resolve_interpret(interpret)
    bt, bv = _blocks(T, V, block_t, block_v, d)
    xp, wp, bp, tp = _operands(x, w, bias, targets, bt, bv)
    Tp, Vp = xp.shape[0], wp.shape[0]
    n_ti, n_vj = Tp // bt, Vp // bv
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, V=V, n_vj=n_vj, partials=partials),
        name="fused_xent_fwd",
        grid=(n_ti, n_vj),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((SUBLANES, bv), lambda i, j: (0, j)),
            pl.BlockSpec((SUBLANES, bt), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, bt), lambda i, j: (0, i)),
            pl.BlockSpec((SUBLANES, bt), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((SUBLANES, Tp), jnp.float32),
            jax.ShapeDtypeStruct((SUBLANES, Tp), jnp.float32),
        ],
        scratch_shapes=[_vmem((bt, 1)), _vmem((bt, 1)), _vmem((bt, 1))],
        interpret=interpret,
    )(xp, wp, bp, tp)
    return nll[0, :T], lse[0, :T]


def _fwd_rule(x, w, bias, targets, block_t, block_v, interpret):
    nll, lse_p = _fwd(x, w, bias, targets, block_t, block_v, interpret)
    return nll, (x, w, bias, targets, lse_p)


def _bwd_kernels(x, w, bias, targets, lse, g, block_t, block_v, interpret):
    """Shared dx/dW/dbias pass: recompute-logits kernels against a given
    per-token lse (the GLOBAL one under TP). Returns (dx, dw, db[:V])."""
    T, d = x.shape
    V = w.shape[0]
    interpret = _resolve_interpret(interpret)
    bt, bv = _blocks(T, V, block_t, block_v, d)
    # padded tokens enter with g = 0: no contribution to dx / dW / dbias
    # (their padded lse of 0 is therefore harmless)
    xp, wp, bp, tp, gp, lp = _operands(
        x, w, bias, targets, bt, bv,
        extra=(g.astype(jnp.float32), lse.astype(jnp.float32)))
    Tp, Vp = xp.shape[0], wp.shape[0]
    n_ti, n_vj = Tp // bt, Vp // bv

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, V=V, n_vj=n_vj),
        name="fused_xent_bwd_dx",
        grid=(n_ti, n_vj),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((SUBLANES, bv), lambda i, j: (0, j)),
            pl.BlockSpec((SUBLANES, bt), lambda i, j: (0, i)),
            pl.BlockSpec((SUBLANES, bt), lambda i, j: (0, i)),
            pl.BlockSpec((SUBLANES, bt), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, d), x.dtype),
        scratch_shapes=[_vmem((bt, d))],
        interpret=interpret,
    )(xp, wp, bp, tp, lp, gp)

    dw, db = pl.pallas_call(
        functools.partial(_dw_kernel, V=V, n_ti=n_ti),
        name="fused_xent_bwd_dw",
        grid=(n_vj, n_ti),
        in_specs=[
            pl.BlockSpec((bt, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bv, d), lambda j, i: (j, 0)),
            pl.BlockSpec((SUBLANES, bv), lambda j, i: (0, j)),
            pl.BlockSpec((SUBLANES, bt), lambda j, i: (0, i)),
            pl.BlockSpec((SUBLANES, bt), lambda j, i: (0, i)),
            pl.BlockSpec((SUBLANES, bt), lambda j, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((bv, d), lambda j, i: (j, 0)),
            pl.BlockSpec((SUBLANES, bv), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Vp, d), w.dtype),
            jax.ShapeDtypeStruct((SUBLANES, Vp), jnp.float32),
        ],
        scratch_shapes=[_vmem((bv, d)), _vmem((1, bv))],
        interpret=interpret,
    )(xp, wp, bp, tp, lp, gp)

    return dx[:T], dw[:V], db[0, :V]


def _bwd_rule(block_t, block_v, interpret, res, g):
    x, w, bias, targets, lse = res
    dx, dw, db = _bwd_kernels(x, w, bias, targets, lse, g,
                              block_t, block_v, interpret)
    # bias=None is an empty pytree argument: its cotangent is None too
    dbias = None if bias is None else db.astype(bias.dtype)
    zeros_t = np.zeros(targets.shape, jax.dtypes.float0)
    return dx, dw, dbias, zeros_t


fused_token_nll.defvjp(_fwd_rule, _bwd_rule)


# ------------------------------------------------ tensor-parallel (vocab)
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_token_nll_tp(x, w_shard, bias_shard, targets, axis="model",
                       block_t=256, block_v=512, interpret=None):
    """Vocab-sharded fused NLL — call INSIDE shard_map with ``axis`` bound.

    Each shard streams its own (V/P, d) slice of the unembedding through
    the kernel in partials mode (shard-local logsumexp + target-logit
    partial), then two collectives assemble the global loss: the same
    max/sum-exp exchange the pipeline's vocab-sharded head does in XLA,
    but with no shard ever materializing its (T, V/P) logits. Targets are
    GLOBAL ids; shards own contiguous equal slices.
    """
    nll, _ = _fwd_tp(x, w_shard, bias_shard, targets, axis,
                     block_t, block_v, interpret)
    return nll


def _fwd_tp(x, w_shard, bias_shard, targets, axis, block_t, block_v,
            interpret):
    v_local = w_shard.shape[0]
    off = lax.axis_index(axis) * v_local
    t_loc = (targets - off).astype(jnp.int32)   # foreign ids never match
    tgt_p, lse_l = _fwd(x, w_shard, bias_shard, t_loc,
                        block_t, block_v, interpret, partials=True)
    m_g = lax.pmax(lse_l, axis)
    lse_g = m_g + jnp.log(lax.psum(jnp.exp(lse_l - m_g), axis))
    tgt_g = lax.psum(tgt_p, axis)
    return lse_g - tgt_g, lse_g


def _fwd_tp_rule(x, w_shard, bias_shard, targets, axis, block_t, block_v,
                 interpret):
    nll, lse_g = _fwd_tp(x, w_shard, bias_shard, targets, axis,
                         block_t, block_v, interpret)
    return nll, (x, w_shard, bias_shard, targets, lse_g)


def _bwd_tp_rule(axis, block_t, block_v, interpret, res, g):
    x, w_shard, bias_shard, targets, lse_g = res
    v_local = w_shard.shape[0]
    off = lax.axis_index(axis) * v_local
    t_loc = (targets - off).astype(jnp.int32)
    # Under check_vma=False shard_map distributes a replicated output's
    # cotangent as g/axis_size per shard; undo that so each shard's
    # slice-local dw/dbias (and its dx partial, which shard_map's
    # replicated-x backward then psums) carry the full signal.
    # CAUTION (JAX-upgrade checklist, pinned jax==0.9.0): this
    # unmentioned-out-axis transpose convention is a JAX internal, not
    # documented API — a release that changes it would silently double- or
    # under-scale TP gradients. test_xent.py's TP-equivalence test pins it;
    # re-run that test first on any JAX bump (docs/OPERATIONS.md).
    g = g * lax.psum(jnp.float32(1.0), axis)
    dx_l, dw, db = _bwd_kernels(x, w_shard, bias_shard, t_loc, lse_g, g,
                                block_t, block_v, interpret)
    # each shard returns only its vocab slice's dx contribution;
    # shard_map's backward for the replicated x operand performs the
    # cross-shard psum (an explicit psum here double-counts)
    dx = dx_l
    dbias = None if bias_shard is None else db.astype(bias_shard.dtype)
    zeros_t = np.zeros(targets.shape, jax.dtypes.float0)
    return dx, dw, dbias, zeros_t


fused_token_nll_tp.defvjp(_fwd_tp_rule, _bwd_tp_rule)

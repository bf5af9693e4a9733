"""Pallas chunkwise scan of the KDA delta rule: T tokens that advance together
against one slot's state, for the prefill chunks of a kind that holds KDA
layers (``models/kda.py`` ``scan_chunked`` is the specification and the path
off the kernel).

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(D)

``scan_chunked`` in plain ``jnp`` solves a block's ``(I + A) U = beta (V - K+
S_0)`` by 64 serial rows over a ``(B, nc, H, 64, 64)`` inverse in HBM and
moves a dozen ``(B, T, H, D)`` float32 temporaries through HBM beside it
(PERF.md §6 "PR 61"). :func:`kda_chunk_scan` is the same mathematics with
nothing but q, k, v, g, beta in and o out touching HBM:

- grid ``(B, H / hb, T / 64)``: a program owns ``hb`` heads of one batch row
  and takes the blocks of :data:`CHUNK` tokens in order (the last grid axis
  is sequential); **the carried state stands in VMEM scratch from the first
  block to the last**, transposed (values x keys: the whole block's decay is
  then a row that broadcasts down the sublanes), and leaves once a call.
- q, k, v, g come as they lie, ``(B, T, H D)``: a block is ``(64, hb D)`` and
  a head's ``(64, D)`` tile a slice of whole lane tiles: no head-major copy.
  beta comes as ``(B, H / hb, T, hb)`` (a column a head; 128 KB a call).
- **every decay is the exponential of a sum of g's, never of a difference of
  running sums, and no exponent is positive** (:func:`decay_sums`, on the
  vector unit: sublane rolls and adds). A pair ``j < i`` whose highest
  differing bit is ``s`` (a LEVEL, ``s = 1 .. 32``) stands in one aligned
  segment of ``2 s`` tokens, ``i`` above its middle ``r`` and ``j`` at or
  under it, and ``Gam_i / Gam_j = exp(G_i - G_r) exp(G_r - G_j)``, both
  factors <= 1 whatever the gate: with ``F_s`` the level's factor a token,
  ``A`` and ``QK`` are six products ``(k F_s)(k F_s)^T`` under the levels'
  masks (``scan_chunked`` takes the same pairs about a sub-block's first
  position and, inside a sub-block, pair by pair; on this chip a pair's sum
  over the channels would be a lane reduction a pair). From a level of 8 up
  the rows above the middles are whole sublane tiles and the product takes
  those 32 alone.
- ``(I + A)^-1`` on the resident ``(64, 64)`` tile by the same levels: with
  ``X_s`` the inverse of the diagonal blocks of ``s`` and ``R_s`` the part of
  ``A`` under level ``s``'s mask, ``X_2s = X_s - X_s R_s X_s`` (block forward
  substitution, two products a level, no serial row).
- float32 operands and ``Precision.HIGHEST`` on every product, as the scan;
  ``u = (I + A)^-1 beta (V - K+ S_0)`` as it is written: one product with the
  state, one with the inverse.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANES = 128
CHUNK = 64                      # tokens a block: models/kda.py's
LEVELS = tuple(1 << n for n in range(CHUNK.bit_length() - 1))   # 1 .. 32
HEADS = 8                       # heads a program at most (the sweep: PERF.md)
HI = lax.Precision.HIGHEST


def kernel_fits(T: int, D: int) -> bool:
    """The shapes the kernel lays out, from the shapes alone: whole blocks
    of :data:`CHUNK` tokens, whole sublane tiles of channels and, where
    Mosaic compiles it, whole lane tiles."""
    return T > 0 and T % CHUNK == 0 and D % 8 == 0 and (
        jax.default_backend() != "tpu" or D % LANES == 0)


def heads_per_program(H: int, most: int = HEADS) -> int:
    return max(d for d in range(1, min(H, most) + 1) if H % d == 0)


def decay_sums(g, roll=jnp.roll):
    """A block's log decays ``g`` (CHUNK, D), each as a SUM of g's over a run
    of tokens (so <= 0, and exact to the rounding of that sum however far
    the running sum has grown). Returns (``G`` the running sum, ``G_last -
    G``, a list by :data:`LEVELS`): level ``s`` holds, for token ``t`` in
    the aligned segment of ``2 s`` whose lower half ends at ``r``, ``G_t -
    G_r`` above the middle and ``G_r - G_t`` at or under it. ``P`` / ``Q``:
    the sum of a token's aligned segment of ``s`` up to it / behind it,
    ``W`` the segment's whole; a level reads them, then the segments pair
    up (``roll(x, n, 0)[t] = x[t - n]``: sublane rolls in the kernel)."""
    C = g.shape[0]
    tok = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    P, Q, W, levels = g, jnp.zeros_like(g), g, []
    for s in LEVELS:
        above = (tok & s) != 0
        levels.append(jnp.where(above, P, Q))
        lower, upper = roll(W, s, 0), roll(W, C - s, 0)  # W[t - s], W[t + s]
        P = P + jnp.where(above, lower, 0.0)
        Q = Q + jnp.where(above, 0.0, upper)
        W = W + jnp.where(above, lower, upper)
    return P, Q, levels


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), precision=HI,
                           preferred_element_type=jnp.float32)


def _nn(a, b):      # (m, k) (k, n)
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):      # (m, k) (n, k)
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):      # (k, m) (k, n)
    return _dot(a, b, ((0,), (0,)))


def _rows(x, s: int):
    """The rows of ``x`` (CHUNK, n) above the middles of level ``s >= 8``'s
    segments: whole sublane tiles."""
    return jnp.concatenate([x[t:t + 8] for t in range(0, CHUNK, 8) if t & s],
                           axis=0)


def _put(x, s: int, new):
    """``x`` with ``new`` for :func:`_rows`' rows."""
    tiles = (new[n:n + 8] for n in range(0, new.shape[0], 8))
    return jnp.concatenate([next(tiles) if t & s else x[t:t + 8]
                            for t in range(0, CHUNK, 8)], axis=0)


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, st_ref,
            z_ref, *, hb: int, D: int, scale: float):
    from jax.experimental.pallas import tpu as pltpu

    c, last = pl.program_id(2), pl.num_programs(2) - 1
    C = CHUNK

    @pl.when(c == 0)
    def _():
        for h in range(hb):
            z_ref[h] = s0_ref[h].T                  # values x keys

    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    under = [(i > j) & ((i ^ j) >= s) & ((i ^ j) < 2 * s) for s in LEVELS]
    eye = (i == j).astype(jnp.float32)

    for h in range(hb):
        at = slice(h * D, (h + 1) * D)
        q, k, v = q_ref[:, at], k_ref[:, at], v_ref[:, at]
        beta = beta_ref[:, h:h + 1]                 # (C, 1)
        Z = z_ref[h]
        G, Gend, sums = decay_sums(g_ref[:, at], pltpu.roll)
        eG, eEnd = jnp.exp(G), jnp.exp(Gend)
        A = jnp.zeros((C, C), jnp.float32)
        QK = eye * jnp.sum(q * k, axis=1, keepdims=True)
        for s, keep, E in zip(LEVELS, under, sums):
            F = jnp.exp(E)                          # <= 1
            kf, qf = k * F, q * F
            if s >= 8:      # the rows above the middles: whole tiles
                keep = _rows(keep, s)
                A = _put(A, s, _rows(A, s) + jnp.where(
                    keep, _nt(_rows(kf, s), kf), 0.0))
                QK = _put(QK, s, _rows(QK, s) + jnp.where(
                    keep, _nt(_rows(qf, s), kf), 0.0))
            else:
                A = A + jnp.where(keep, _nt(kf, kf), 0.0)
                QK = QK + jnp.where(keep, _nt(qf, kf), 0.0)
        A = A * beta
        # (I + A)^-1, the diagonal blocks of 1 (the identity), 2, 4 .. 64
        X = eye - jnp.where(under[0], A, 0.0)
        for s, keep in zip(LEVELS[1:], under[1:]):
            R = jnp.where(keep, A, 0.0)
            if s >= 8:
                Xu = _rows(X, s)
                X = _put(X, s, Xu - _nn(_nn(Xu, R), X))
            else:
                X = X - _nn(_nn(X, R), X)
        u = _nn(X, (v - _nt(k * eG, Z)) * beta)
        o_ref[:, at] = (_nt(q * eG, Z) + _nn(QK, u)) * scale
        z_ref[h] = Z * eG[C - 1:C] + _tn(u, k * eEnd)

    @pl.when(c == last)
    def _():
        for h in range(hb):
            st_ref[h] = z_ref[h].T


def kda_chunk_scan(q, k, v, g, beta, S0, *, heads: int = HEADS,
                   interpret: Optional[bool] = None):
    """The delta rule over T tokens (a whole number of blocks of
    :data:`CHUNK`): q, k, v, g (B, T, H, D) float32, ``g <= 0`` and otherwise
    unbounded, beta (B, T, H) (a padded token: beta 0, g 0), S0 (B, H, D, D)
    keys x values. Returns (o (B, T, H, D) float32 = ``S_t^T q_t / sqrt(D)``,
    S_T): ``kda.scan_chunked``'s results."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, D = q.shape
    if T % CHUNK:
        raise ValueError(f"{T} tokens are no whole number of blocks of "
                         f"{CHUNK} (kernel_fits)")
    f32 = jnp.float32
    hb = heads_per_program(H, heads)
    G, nc = H // hb, T // CHUNK
    rows = pl.BlockSpec((None, CHUNK, hb * D), lambda b, h, c: (b, c, h))
    state = pl.BlockSpec((None, hb, D, D), lambda b, h, c: (b, h, 0, 0))
    o, S = pl.pallas_call(
        partial(_kernel, hb=hb, D=D, scale=1.0 / math.sqrt(D)),
        name="kda_chunk_scan",
        grid=(B, G, nc),
        in_specs=[rows, rows, rows, rows,
                  pl.BlockSpec((None, None, CHUNK, hb),
                               lambda b, h, c: (b, h, c, 0)),
                  state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * D), f32),
                   jax.ShapeDtypeStruct((B, H, D, D), f32)],
        scratch_shapes=[pltpu.VMEM((hb, D, D), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*(a.astype(f32).reshape(B, T, H * D) for a in (q, k, v, g)),
      beta.astype(f32).reshape(B, T, G, hb).transpose(0, 2, 1, 3),
      S0.astype(f32))
    return o.reshape(B, T, H, D), S

"""Fused weight-only-quant GEMM: int8/int4 weights dequantized in VMEM.

The decode hot loop is HBM-bandwidth-bound on the weight re-read, and the
reference's entire int8 inference stack (``csrc/transformer/inference/``,
``csrc/quantization/``) exists to cut that traffic. The repo's previous WOQ
path stored int8 but dequantized whole matrices in XLA, which hoists the
loop-invariant convert out of the decode scan: decode then re-read a bf16
copy and int8 was no faster than bf16 (docs/WOQ_DECODE.md). These kernels
make the hoist impossible: the int8 (or nibble-packed int4) tiles stream
HBM→VMEM, are dequantized *inside the matmul loop* on the VPU, and feed
the MXU in the activation dtype with an fp32 accumulator.
HBM weight traffic per token drops ~2x (int8) / ~4x (int4) vs bf16 — the
EQuARX/qwZ principle of dequantizing at the point of consumption.

Quantization layout (see ``inference/quantization.py``): groups of
``group_size`` rows along the weight's second-to-last dim share a scale
row, so ``scale`` is ``(G, N)`` fp32 for a ``(K, N)`` weight with
``G = K / group_size``. Two consumption patterns:

- :func:`woq_matmul` — ``x @ W`` for projection/MLP weights stored
  ``(K, N)``: the k-loop steps one *group* at a time, so the scale is a
  single ``(1, bn)`` row per step and folds into the accumulator AFTER the
  int8 dot (``(x @ q) * s`` == ``x @ (q * s)`` within a group) — the MXU
  never sees a dequantized weight tile at all;
- :func:`woq_matmul_t` — ``x @ W.T`` for the tied-embedding head, W stored
  ``(V, K)`` with groups along V: the output tile is clamped to one group
  (``bv <= group_size``), the ``(1, bc)`` scale row broadcasts over the
  tile's rows in VMEM, then the MXU contracts the lane dim.

The TPU lowering wants the last two dims of every block divisible by
(8, 128) or equal to the array's, so a ``(1, bn)`` scale block is refused.
The scale operand is therefore blocked ``(gb, bn)`` — eight group rows, or
all ``G`` of them when ``G`` is no multiple of eight — and the kernel picks
its row with a dynamic sublane slice (:func:`_scale_rows`).

int4 packs two signed nibbles per byte along *adjacent rows* of the grouped
dim (row ``2r`` low nibble, ``2r+1`` high): in-kernel unpack is two int32
shifts + a sublane concat (even rows, then odd rows); the matching column
permutation is applied to the small activation (or output) outside the
kernel, so the weight's lane layout is untouched. Everything runs under
``interpret=True`` off-TPU, so parity is tier-1-testable on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# shared tile math (same helpers the fused-xent kernels use — one place
# for pow2 rounding / axis padding so the two kernel modules can't drift)
from .xent import _pad_to as _pad_axis
from .xent import _pow2_ceil, _resolve_interpret


def _unpack_rows(p):
    """(R/2, C) packed bytes → (R, C) signed int4 values, DE-interleaved:
    the R/2 low nibbles (even rows of the weight) stacked on the R/2 high
    nibbles (odd rows). Shifts run in int32 (Mosaic has no int8 shift) and
    the stack is a plain sublane concat — no interleave, lanes untouched.
    The callers permute the activation's columns (:func:`_even_odd`) or
    the output's (:func:`woq_matmul_t`) to match."""
    p = p.astype(jnp.int32)
    lo = (p << 28) >> 28                         # sign-extend low nibble
    hi = p >> 4                                  # arithmetic: high nibble
    return jnp.concatenate([lo, hi], axis=0)


def _even_odd(x, tile: int):
    """Reorder the last dim so each ``tile`` holds its even columns, then
    its odd ones — the order :func:`_unpack_rows` yields weight rows in."""
    lead = x.shape[:-1]
    return x.reshape(*lead, -1, tile // 2, 2).swapaxes(-1, -2).reshape(x.shape)


def _odd_even_inverse(x, tile: int):
    """Undo :func:`_even_odd` on the last dim."""
    lead = x.shape[:-1]
    return x.reshape(*lead, -1, 2, tile // 2).swapaxes(-1, -2).reshape(x.shape)


def _scale_rows(G: int) -> int:
    """Sublane extent of the scale block: 8 rows when that tiles ``G``,
    else the whole group dim (a block dim equal to the array's is legal)."""
    return 8 if G % 8 == 0 else G


# --------------------------------------------------------- x @ W  (K, N)
def _matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_sc, *, n_k: int,
                   bits: int, gb: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    q = q_ref[...]
    if bits == 4:
        q = _unpack_rows(q)
    x = x_ref[...]
    # int8→activation-dtype convert happens HERE, on the VPU, on the tile
    # already resident in VMEM — HBM only ever saw the int8 bytes. The
    # group scale is constant over this k-step's rows, so it distributes
    # out of the dot and multiplies the fp32 partial instead (the MXU runs
    # a pure integer-valued matmul).
    part = jnp.dot(x, q.astype(x.dtype), preferred_element_type=jnp.float32)
    acc_sc[...] += part * s_ref[pl.ds(k % gb, 1), :]   # (1, bn) broadcast

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = acc_sc[...].astype(o_ref.dtype)


def woq_matmul(x, q, scale, *, group_size: int, bits: int = 8,
               block_m: int = 256, block_n: int = 512,
               interpret: Optional[bool] = None, out_dtype=None):
    """``x @ W`` with ``W`` stored quantized ``(K, N)``.

    x: (M, K) bf16/f32; q: (K, N) int8 — int4 packs row pairs to
    (K/2, N); scale: (G, N) fp32, G = K // group_size. Returns (M, N) in
    ``x.dtype`` (or ``out_dtype``) with fp32 accumulation.
    """
    M, K = x.shape
    G, N = scale.shape
    gs = group_size
    assert G * gs == K, (K, group_size, scale.shape)
    assert bits in (4, 8), bits
    assert q.shape == ((K // 2, N) if bits == 4 else (K, N)), q.shape
    interpret = _resolve_interpret(interpret)
    out_dtype = out_dtype or x.dtype

    bm = min(block_m, max(16, _pow2_ceil(M)))
    bn = min(block_n, _pow2_ceil(N))
    if bits == 4:
        x = _even_odd(x, gs)
    xp = _pad_axis(x, bm, 0)
    qp = _pad_axis(q, bn, 1)
    sp = _pad_axis(scale, bn, 1)
    Mp, Np = xp.shape[0], qp.shape[1]
    rows = gs // 2 if bits == 4 else gs          # q rows per k-step
    gb = _scale_rows(G)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=G, bits=bits, gb=gb),
        name="woq_matmul",
        grid=(Mp // bm, Np // bn, G),
        in_specs=[
            pl.BlockSpec((bm, gs), lambda i, j, k: (i, k)),
            pl.BlockSpec((rows, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((gb, bn), lambda i, j, k: (k // gb, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[_vmem((bm, bn))],
        interpret=interpret,
    )(xp, qp, sp)
    return out[:M, :N]


# ------------------------------------------------------ x @ W.T  (V, K)
def _matmul_t_kernel(x_ref, q_ref, s_ref, o_ref, acc_sc, *, n_k: int,
                     bits: int, gb: int, tiles_per_group: int):
    k = pl.program_id(2)
    row = (pl.program_id(1) // tiles_per_group) % gb

    @pl.when(k == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    q = q_ref[...]
    if bits == 4:
        q = _unpack_rows(q)
    x = x_ref[...]
    # the whole (bv, bc) tile sits in ONE row group (bv <= group_size), so
    # its scale is a single (1, bc) row broadcast down the tile — dequant
    # in VMEM, then contract the lane dim on the MXU
    wd = (q.astype(jnp.float32) * s_ref[pl.ds(row, 1), :]).astype(x.dtype)
    acc_sc[...] += lax.dot_general(x, wd, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = acc_sc[...].astype(o_ref.dtype)


def woq_matmul_t(x, q, scale, *, group_size: int, bits: int = 8,
                 block_m: int = 256, block_v: int = 128, block_c: int = 512,
                 interpret: Optional[bool] = None, out_dtype=None):
    """``x @ W.T`` with ``W`` stored quantized ``(V, K)`` — the tied
    embedding table consumed as the unembedding, never transposed in HBM.

    x: (M, K); q: (V, K) int8 — int4 packs row pairs to (V/2, K);
    scale: (G, K) fp32, G = V // group_size. Returns (M, V).
    """
    M, K = x.shape
    G, Ks = scale.shape
    gs = group_size
    V = q.shape[0] * (2 if bits == 4 else 1)
    assert Ks == K and G * gs == V, (q.shape, scale.shape, group_size)
    assert bits in (4, 8), bits
    interpret = _resolve_interpret(interpret)
    out_dtype = out_dtype or x.dtype

    bm = min(block_m, max(16, _pow2_ceil(M)))
    bc = min(block_c, _pow2_ceil(K))
    if G == 1:
        # degraded single group (odd vocab): every row shares the scale
        # row, so the output tile is unconstrained by group alignment
        bv = min(block_v, max(2 if bits == 4 else 1, _pow2_ceil(V)))
    else:
        # output tile bounded by (and aligned to) one group so its scale
        # is a single row: bv | gs, largest candidate first
        bv = block_v if gs % block_v == 0 else gs
    gb = _scale_rows(G)

    xp = _pad_axis(_pad_axis(x, bm, 0), bc, 1)
    qrows = bv // 2 if bits == 4 else bv
    qp = _pad_axis(_pad_axis(q, qrows, 0), bc, 1)
    Vp = qp.shape[0] * (2 if bits == 4 else 1)
    # output tiles per scale row (one group: every tile reads row 0)
    tiles_per_group = Vp // bv if G == 1 else gs // bv
    sp = _pad_axis(scale, bc, 1)
    Mp, Kp = xp.shape
    n_c = Kp // bc

    out = pl.pallas_call(
        functools.partial(_matmul_t_kernel, n_k=n_c, bits=bits, gb=gb,
                          tiles_per_group=tiles_per_group),
        name="woq_matmul_t",
        grid=(Mp // bm, Vp // bv, n_c),
        in_specs=[
            pl.BlockSpec((bm, bc), lambda i, j, k: (i, k)),
            pl.BlockSpec((qrows, bc), lambda i, j, k: (j, k)),
            pl.BlockSpec((gb, bc),
                         lambda i, j, k: (j // tiles_per_group // gb, k)),
        ],
        out_specs=pl.BlockSpec((bm, bv), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Vp), out_dtype),
        scratch_shapes=[_vmem((bm, bv))],
        interpret=interpret,
    )(xp, qp, sp)
    if bits == 4:
        out = _odd_even_inverse(out, bv)
    return out[:M, :V]


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


# --------------------------------------------------------------- helpers
# VMEM element budget for one kernel step (double-buffered operands +
# accumulator), mirroring ops/xent.py's proven ceiling. Leaves whose
# degraded group covers a huge K (e.g. an odd 50k vocab) would blow this —
# the dispatcher in inference/quantization.py routes them to XLA instead.
_TILE_ELEM_BUDGET = (256 + 512) * 4096


def woq_matmul_eligible(K: int, group_size: int, bits: int) -> bool:
    """Can :func:`woq_matmul` stream this weight? The k-step tile is one
    whole group, so a degraded (group == K) wide leaf must stay on XLA.

    On real TPU the x-tile's LANE dim is the group size, so it must be a
    128 multiple (or the full K, which Pallas pads internally) — Mosaic
    rejects other widths at compile time, inside the decode scan, where
    interpret-mode CI can't see it. Off-TPU (interpret) any group works."""
    if bits == 4 and group_size % 2 != 0:
        return False
    if jax.default_backend() == "tpu" \
            and group_size % 128 != 0 and group_size < K:
        return False
    return K % group_size == 0 and group_size * 512 <= _TILE_ELEM_BUDGET


def woq_matmul_t_eligible(V: int, K: int, group_size: int,
                          bits: int) -> bool:
    """Same gate for the transposed (tied-head) consumption: the output
    tile must fit inside (or be) one group; nothing constrains K (it
    streams). A degraded single group (group >= V) is fine — every tile
    shares the one scale row — but a non-dividing multi-group layout or a
    group too wide to be an output tile stays on XLA."""
    if bits == 4 and (group_size % 2 != 0 or V % 2 != 0):
        return False
    if group_size >= V:
        return True           # single group: bv is a free power of two
    if jax.default_backend() == "tpu" and group_size % 128 != 0:
        # multi-group forces bv | gs; a non-128-multiple bv is a
        # lane-misaligned output tile Mosaic rejects (interpret is fine)
        return False
    return V % group_size == 0 and group_size <= 1024

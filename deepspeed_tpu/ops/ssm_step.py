"""Pallas one-token step of the Mamba-2 recurrence on a batch of slots, in
place in the carried state (``models/ssm.py``, ``inference/kinds/hybrid.py``).

    S[b, h] <- exp(dt[b, h] A[h]) S[b, h] + dt[b, h] x[b, h] (x) B[b, g]
    y[b, h]  = S[b, h] C[b, g]                       (P x N a head, float32)

The state ``(L, B, H, P, N)`` is the step's largest operand by far (a slot
and layer: ``H P N`` float32, 4 MiB at 128 x 64 x 128) and is touched once:
a program takes one slot's groups of heads (a group: the ``H / G`` heads
that share B and C), as many as 2 MiB of state hold
(:func:`groups_per_program`), reads their block, writes it back through the
aliased output, and leaves every other bit of the buffer alone. **A slot at
length 0 is not running and costs nothing**: its block index is its nearest
running neighbour's (the next one, else the one before), which the pipeline
has fetched anyway and writes back once, and its body is skipped — its own
state is neither read nor written. With no slot running at all the one block
everything points at is copied through.

**Why 2 MiB.** The copies in and out run faster in larger blocks: at 128 x
64 x 128 in 8 groups the step moves 570 GB/s of the chip's 819 a group a
program (512 KiB), 614 at two, 634 at four (2 MiB), and 638 at a slot's
whole 4 MiB, which needs a raised limit (PERF.md §6 "PR 50"); in and out
double-buffered, 2 MiB is 8 MiB of a core's 16 MiB of scoped VMEM.

What varies along P has to stand on the sublanes to meet a ``(P, N)`` block:
``dt x`` comes in as a lane-dense operand ``(B, G, P, 128)`` — lane ``j`` the
group's head ``j``, transposed — a column of which broadcasts along N; y
goes out the same way, a head's column stored where it falls, and is
transposed back outside. That costs 2 x 32 KiB beside a group's 2 x 512 KiB
of state. The decay is one number a head and comes as a scalar from SMEM,
the batch's ``B x H`` of them by scalar prefetch (1 MiB of SMEM holds 2048
slots of 128 heads; a slot's ``(1, H)`` block a program is as fast, but asks
XLA for a layout that turned the in-projection's output batch-minor in a
serving step): a column of it repeated down P cost a lane broadcast a state
tile, and with the lane reduction for y the two were what the step waited
for at N = 128, not the copies.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def kernel_fits(H: int, G: int, P: int, N: int) -> bool:
    """The shapes the kernel lays out: a group's heads in one lane tile
    (``dt x`` in, y out), whole sublane tiles down P."""
    return H % G == 0 and H // G <= LANES and P % 8 == 0


# the float32 state a program takes at most (``groups_per_program``): in and
# out double-buffered that is 8 MiB of a core's 16 MiB of scoped VMEM
_BLOCK_BYTES = 2 << 20


def groups_per_program(H: int, G: int, P: int, N: int) -> int:
    """The groups of ``H / G`` heads one program takes (gb): the largest
    divisor of ``G`` whose float32 state fits ``_BLOCK_BYTES``, 1 where one
    group fills it already. 4 for 128 heads of 64 x 128 in 8 groups (4 x
    512 KiB), 1 for 32 heads of 128 x 256 in 2 (2 MiB a group). From the
    shapes alone, never from the batch: a slot's bits do not depend on its
    neighbours."""
    one = (H // G) * P * N * 4
    return max(d for d in range(1, G + 1)
               if G % d == 0 and (d == 1 or d * one <= _BLOCK_BYTES))


def _kernel(layer_ref, src_ref, live_ref, any_ref, dec_ref, s_ref, dtx_ref,
            bc_ref, o_ref, y_ref, *, hg: int, gb: int, H: int):
    g, b = pl.program_id(0), pl.program_id(1)
    P = s_ref.shape[1]

    @pl.when(live_ref[b] > 0)
    def _():
        for i in range(gb):                  # a group: its own B and C rows
            dtx = dtx_ref[i * P:(i + 1) * P, :]                # (P, 128)
            brow = bc_ref[2 * i:2 * i + 1, :]                  # (1, N)
            crow = bc_ref[2 * i + 1:2 * i + 2, :]
            for j in range(hg):
                h = i * hg + j
                new = s_ref[h] * dec_ref[b * H + g * gb * hg + h] \
                    + dtx[:, j:j + 1] * brow
                o_ref[h] = new
                y_ref[i * P:(i + 1) * P, j:j + 1] = jnp.sum(
                    new * crow, axis=1, keepdims=True)

    @pl.when(live_ref[b] == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(any_ref[0] == 0)
    def _():
        o_ref[...] = s_ref[...]


def ssm_state_step(S, layer, x, dt, A, Bv, Cv, length, *,
                   interpret: Optional[bool] = None):
    """S (L, B, H, P, N) float32, ``layer`` (traced i32) the layer to step;
    x (B, H, P), dt (B, H), A (H,), Bv / Cv (B, G, N), all float32;
    ``length`` (B,) i32: a slot at 0 is left alone. Returns (y (B, H, P)
    float32 — zeros for a slot left alone — and S, aliased to the input)."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L, B, H, P, N = S.shape
    G = Bv.shape[1]
    hg = H // G
    gb = groups_per_program(H, G, P, N)
    f32 = jnp.float32
    dtx = (dt[..., None] * x).reshape(B, G, hg, P).transpose(0, 1, 3, 2)
    dtx = jnp.pad(dtx.astype(f32), ((0, 0),) * 3 + ((0, LANES - hg),))
    # a program's gb groups one under the other: (gb P, 128), (2 gb, N)
    dtx = dtx.reshape(B, G // gb, gb * P, LANES)
    bc = jnp.stack([Bv, Cv], axis=2).astype(f32).reshape(
        B, G // gb, 2 * gb, N)
    dec = jnp.exp(dt * A).astype(f32).reshape(B * H)
    live = (length > 0).astype(jnp.int32)
    # a slot that is not running borrows the block of the next running one,
    # else of the last one before it (no fetch of its own, no write)
    idx = jnp.arange(B, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(live > 0, idx, B), reverse=True)
    prv = jax.lax.cummax(jnp.where(live > 0, idx, -1))
    src = jnp.where(nxt < B, nxt, jnp.maximum(prv, 0)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(G // gb, B),
        in_specs=[
            pl.BlockSpec((None, None, gb * hg, P, N),
                         lambda g, b, lay, src, *_: (lay[0], src[b], g, 0, 0)),
            pl.BlockSpec((None, None, gb * P, LANES),
                         lambda g, b, lay, src, *_: (src[b], g, 0, 0)),
            pl.BlockSpec((None, None, 2 * gb, N),
                         lambda g, b, lay, src, *_: (src[b], g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, gb * hg, P, N),
                         lambda g, b, lay, src, *_: (lay[0], src[b], g, 0, 0)),
            pl.BlockSpec((None, None, gb * P, LANES),
                         lambda g, b, *_: (b, g, 0, 0)),
        ])
    S, y = pl.pallas_call(
        partial(_kernel, hg=hg, gb=gb, H=H), name="ssm_state_step",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, G // gb, gb * P, LANES), f32)],
        input_output_aliases={5: 0},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), src, live,
      jnp.sum(live).reshape(1), dec, S, dtx, bc)
    y = y.reshape(B, G, P, LANES)[..., :hg].transpose(0, 1, 3, 2) \
        .reshape(B, H, P)
    return y, S

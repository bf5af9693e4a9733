"""Pallas one-token step of the Mamba-2 recurrence on a batch of slots, in
place in the carried state (``models/ssm.py``, ``inference/kinds/hybrid.py``).

    S[b, h] <- exp(dt[b, h] A[h]) S[b, h] + dt[b, h] x[b, h] (x) B[b, g]
    y[b, h]  = S[b, h] C[b, g]                       (P x N a head, float32)

The state ``(L, B, H, P, N)`` is the step's largest operand by far (a slot
and layer: ``H P N`` float32, 4 MiB at 128 x 64 x 128) and is touched once:
a program takes one slot's one group of heads (the ``H / G`` heads that
share B and C), reads its block, writes it back through the aliased output,
and leaves every other bit of the buffer alone. **A slot at length 0 is not
running and costs nothing**: its block index is its nearest running
neighbour's (the next one, else the one before), which the pipeline has
fetched anyway and writes back once, and its body is skipped — its own state
is neither read nor written. With no slot running at all the one block
everything points at is copied through.

What varies along P has to stand on the sublanes to meet a ``(P, N)`` block:
``dt x`` and the decay come in as one lane-dense operand ``(B, G, P, 128)``
— lanes ``[0, H/G)`` the group's ``dt x`` transposed, lanes ``[H/G, 2 H/G)``
its decay repeated down P — a column of which broadcasts along N; y goes out
the same way and is transposed back outside. That costs 2 x 32 KiB beside a
group's 2 x 512 KiB of state.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def kernel_fits(H: int, G: int, P: int, N: int) -> bool:
    """The shapes the kernel lays out: both packed columns of a group in one
    lane tile, whole sublane tiles down P."""
    return H % G == 0 and 2 * (H // G) <= LANES and P % 8 == 0


def _kernel(layer_ref, src_ref, live_ref, any_ref, s_ref, cols_ref, bc_ref,
            o_ref, y_ref, *, hg: int):
    b = pl.program_id(1)

    @pl.when(live_ref[b] > 0)
    def _():
        cols = cols_ref[...]                               # (P, 128)
        brow, crow = bc_ref[0:1, :], bc_ref[1:2, :]        # (1, N)
        lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
        y = jnp.zeros_like(cols)
        for j in range(hg):
            new = s_ref[j] * cols[:, hg + j:hg + j + 1] \
                + cols[:, j:j + 1] * brow
            o_ref[j] = new
            y = jnp.where(lane == j,
                          jnp.sum(new * crow, axis=1, keepdims=True), y)
        y_ref[...] = y

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
    f32 = jnp.float32
    dtx = (dt[..., None] * x).reshape(B, G, hg, P)
    dec = jnp.broadcast_to(jnp.exp(dt * A).reshape(B, G, hg, 1), dtx.shape)
    cols = jnp.concatenate([dtx, dec], axis=2).transpose(0, 1, 3, 2)
    cols = jnp.pad(cols.astype(f32), ((0, 0),) * 3 + ((0, LANES - 2 * hg),))
    bc = jnp.stack([Bv, Cv], axis=2).astype(f32)           # (B, G, 2, N)
    live = (length > 0).astype(jnp.int32)
    # a slot that is not running borrows the block of the next running one,
    # else of the last one before it (no fetch of its own, no write)
    idx = jnp.arange(B, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(live > 0, idx, B), reverse=True)
    prv = jax.lax.cummax(jnp.where(live > 0, idx, -1))
    src = jnp.where(nxt < B, nxt, jnp.maximum(prv, 0)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G, B),
        in_specs=[
            pl.BlockSpec((None, None, hg, P, N),
                         lambda g, b, lay, src, *_: (lay[0], src[b], g, 0, 0)),
            pl.BlockSpec((None, None, P, LANES),
                         lambda g, b, lay, src, *_: (src[b], g, 0, 0)),
            pl.BlockSpec((None, None, 2, N),
                         lambda g, b, lay, src, *_: (src[b], g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, hg, P, N),
                         lambda g, b, lay, src, *_: (lay[0], src[b], g, 0, 0)),
            pl.BlockSpec((None, None, P, LANES),
                         lambda g, b, *_: (b, g, 0, 0)),
        ])
    S, y = pl.pallas_call(
        partial(_kernel, hg=hg), name="ssm_state_step",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, G, P, LANES), f32)],
        input_output_aliases={4: 0},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), src, live,
      jnp.sum(live).reshape(1), S, cols, bc)
    y = y[..., :hg].transpose(0, 1, 3, 2).reshape(B, H, P)
    return y, S

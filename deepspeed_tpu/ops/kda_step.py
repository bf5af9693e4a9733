"""Pallas one-token step of the KDA delta rule on a batch of slots, in place
in the carried state (``models/kda.py``, ``inference/kinds/linear_sparse.py``).

    S'      = Diag(exp(g[b, h])) S[b, h]                 (keys x values, f32)
    r       = beta[b, h] (v[b, h] - k[b, h]^T S')
    S[b, h] <- S' + k[b, h] (x) r
    o[b, h]  = S[b, h]^T q[b, h] / sqrt(D)

The state ``(L, B, H, D, D)`` is the step's largest operand by far (a slot
and layer: ``H D D`` float32, 4 MiB at 64 x 128 x 128) and is touched once: a
program takes ``hb`` of one slot's heads (:func:`heads_per_program`: 2 MiB of
state), reads their block, writes it back through the aliased output, and
leaves every other bit of the buffer alone, as ``ops/ssm_step.py`` does.
**A slot at length 0 is not running and costs nothing**: its block index is
its nearest running neighbour's, which the pipeline has fetched anyway and
writes back once, and its body is skipped.

What varies along the keys has to stand on the sublanes to meet a ``(D, D)``
block: the decay, k and q of a program's heads come as ONE lane-dense operand
``(B, H / hb, D, 128)`` — lanes ``[0, hb)`` the heads' decays, ``[hb, 2 hb)``
their k, ``[2 hb, 3 hb)`` their q, transposed — a column of which broadcasts
along the values; 64 KiB beside a program's 2 x 2 MiB of state. v comes and o
goes as rows ``(hb, D)``; beta is one number a head, from SMEM. ``k^T S'`` and
``S^T q`` are sums down the sublanes: adds of whole registers, one reduction
inside a register a head.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# the float32 state a program takes at most: in and out double-buffered that
# is 8 MiB of a core's 16 MiB of scoped VMEM (ops/ssm_step.py measured it)
_BLOCK_BYTES = 2 << 20


def heads_per_program(H: int, D: int) -> int:
    """The heads one program takes: the largest divisor of ``H`` whose
    float32 state fits ``_BLOCK_BYTES`` and whose three columns a head fit
    one lane tile. From the shapes alone, never from the batch."""
    most = min(_BLOCK_BYTES // (D * D * 4), LANES // 3)
    return max(d for d in range(1, H + 1)
               if H % d == 0 and (d == 1 or d <= most))


def kernel_fits(H: int, D: int) -> bool:
    """The shapes the kernel lays out: whole sublane tiles of keys, a head's
    state inside a program's block and, where Mosaic compiles it, whole lane
    tiles of values."""
    return D % 8 == 0 and D * D * 4 <= _BLOCK_BYTES and (
        jax.default_backend() != "tpu" or D % LANES == 0)


def _kernel(layer_ref, src_ref, live_ref, any_ref, beta_ref, s_ref, col_ref,
            v_ref, o_ref, y_ref, *, hb: int, H: int, scale: float):
    g, b = pl.program_id(0), pl.program_id(1)

    @pl.when(live_ref[b] > 0)
    def _():
        for i in range(hb):
            dec = col_ref[:, i:i + 1]                          # (D, 1)
            kc = col_ref[:, hb + i:hb + i + 1]
            qc = col_ref[:, 2 * hb + i:2 * hb + i + 1]
            sd = s_ref[i] * dec
            r = beta_ref[b * H + g * hb + i] * (
                v_ref[i:i + 1, :] - jnp.sum(sd * kc, axis=0, keepdims=True))
            new = sd + kc * r
            o_ref[i] = new
            y_ref[i:i + 1, :] = jnp.sum(new * qc, axis=0,
                                        keepdims=True) * scale

    @pl.when(live_ref[b] == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(any_ref[0] == 0)
    def _():
        o_ref[...] = s_ref[...]


def kda_state_step(S, layer, q, k, v, g, beta, length, *,
                   interpret: Optional[bool] = None):
    """S (L, B, H, D, D) float32, ``layer`` (traced i32) the layer to step;
    q, k, v, g (B, H, D) and beta (B, H), float32; ``length`` (B,) i32: a
    slot at 0 is left alone. Returns (o (B, H, D) float32 — zeros for a slot
    left alone — and S, aliased to the input)."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L, B, H, D, _ = S.shape
    hb = heads_per_program(H, D)
    G = H // hb
    f32 = jnp.float32
    # (B, G, D, 3 hb -> 128): a program's decays, k and q, keys on the sublanes
    cols = jnp.stack([jnp.exp(g), k, q], axis=1).astype(f32)   # (B, 3, H, D)
    cols = cols.reshape(B, 3, G, hb, D).transpose(0, 2, 4, 1, 3)
    cols = jnp.pad(cols.reshape(B, G, D, 3 * hb),
                   ((0, 0),) * 3 + ((0, LANES - 3 * hb),))
    live = (length > 0).astype(jnp.int32)
    # a slot that is not running borrows the block of the next running one,
    # else of the last one before it (no fetch of its own, no write)
    idx = jnp.arange(B, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(live > 0, idx, B), reverse=True)
    prv = jax.lax.cummax(jnp.where(live > 0, idx, -1))
    src = jnp.where(nxt < B, nxt, jnp.maximum(prv, 0)).astype(jnp.int32)
    state = pl.BlockSpec((None, None, hb, D, D),
                         lambda g, b, lay, src, *_: (lay[0], src[b], g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(G, B),
        in_specs=[
            state,
            pl.BlockSpec((None, None, D, LANES),
                         lambda g, b, lay, src, *_: (src[b], g, 0, 0)),
            pl.BlockSpec((None, None, hb, D),
                         lambda g, b, lay, src, *_: (src[b], g, 0, 0)),
        ],
        out_specs=[
            state,
            pl.BlockSpec((None, None, hb, D), lambda g, b, *_: (b, g, 0, 0)),
        ])
    S, y = pl.pallas_call(
        partial(_kernel, hb=hb, H=H, scale=1.0 / math.sqrt(D)),
        name="kda_state_step", grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, G, hb, D), f32)],
        input_output_aliases={5: 0},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), src, live,
      jnp.sum(live).reshape(1), beta.astype(f32).reshape(B * H), S, cols,
      v.astype(f32).reshape(B, G, hb, D))
    return y.reshape(B, H, D), S

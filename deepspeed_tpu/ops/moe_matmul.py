"""Pallas grouped expert product: rows sorted by expert, multiplied per expert.

The expert layer (``models/moe.py`` ``experts``) lays the routed rows out
sorted by expert, every expert's rows padded to whole blocks of ``bm`` rows,
so a block belongs to ONE expert; ``block_expert`` (scalar-prefetched) names
it and the index map fetches that expert's weights. Blocks of the same
expert follow each other, and a weight tile whose index did not change is
not fetched again: every touched expert's weights are read once, an
untouched expert's never. Blocks behind the last used one repeat its expert
(no fetch) and are skipped.

- ``experts_up``: ``silu(x @ w_gate[e]) * (x @ w_in[e])`` → (R, f); grid
  (f tiles, blocks), the blocks innermost. With ``limit`` both factors are
  clamped first (``swiglu_limit``).
- ``experts_down``: ``h @ w_out[e]`` → (R, d); grid (d tiles, blocks).

Rows of skipped blocks are left unwritten; nothing reads them (the combine
gathers routed rows only).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def block_rows(dtype) -> int:
    """Rows of one block: the sublane tile of ``dtype`` (8 x 32-bit words),
    the least a matmul operand can hold — what padding costs per expert."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _tile(n: int, most: int) -> int:
    """Largest multiple of 128 dividing ``n`` that is <= ``most``; else n."""
    for t in range(most - most % LANES, 0, -LANES):
        if n % t == 0:
            return t
    return n


def _fits(k_in: int, weights: int, dtype, most: int) -> int:
    """``most`` columns of a weight tile, or as many fewer as keep the
    tiles of ``weights`` (k_in, columns) operands, two buffers each, inside
    half the 16 MiB of scoped VMEM (MiMo-V2's 4096-wide rows: 256)."""
    room = (8 << 20) // (2 * weights * k_in * jnp.dtype(dtype).itemsize)
    return max(LANES, min(most, room - room % LANES))


def _up_kernel(be_ref, used_ref, _, x_ref, wg_ref, wi_ref, o_ref, *,
               limit: float = 0.0):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wi_ref[...], preferred_element_type=jnp.float32)
        if limit:
            g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
        o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)


def _down_kernel(be_ref, used_ref, _, h_ref, wo_ref, o_ref):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(h_ref[...], wo_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _call(kernel, name, rows, weights, block_expert, used, layer, bm, n_out,
          tile, interpret):
    from jax.experimental.pallas import tpu as pltpu

    R, k_in = rows.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_out // tile, R // bm),
        in_specs=[pl.BlockSpec((bm, k_in), lambda t, b, *_: (b, 0))]
        + [pl.BlockSpec((None, None, k_in, tile),
                        lambda t, b, be, used, layer: (layer[0], be[b], 0, t))
           ] * len(weights),
        out_specs=pl.BlockSpec((bm, tile), lambda t, b, *_: (b, t)))
    return pl.pallas_call(
        kernel, name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, n_out), rows.dtype),
        interpret=interpret,
    )(block_expert, used, layer, rows, *weights)


def experts_swiglu(xs, w_gate, w_in, w_out, block_expert, blocks_used, *,
                   bm: int, layer=None, limit: float = 0.0,
                   interpret: Optional[bool] = None):
    """``xs`` (R, d): rows sorted by expert and padded to blocks of ``bm``;
    ``w_gate``/``w_in`` (E, d, f), ``w_out`` (E, f, d) — or the banks of
    ALL layers, (L, E, ·, ·), with ``layer`` (traced i32) the one to use:
    a layer loop then carries the stacked banks untouched and the index map
    picks the layer, where slicing a layer's bank out for the call would
    copy 0.4 GB a matrix (PERF.md, PR 29). ``block_expert`` (R // bm,) i32
    the expert of every block; ``blocks_used`` i32 how many blocks hold
    rows. ``limit`` (``swiglu_limit``, 0: none): the gate clamped from
    above and the up product on both sides before they meet. Returns (R,
    d): row i through its block's expert."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R, d = xs.shape
    if R % bm:
        raise ValueError(f"{R} rows are not whole blocks of {bm}")
    if w_in.ndim == 3:
        w_gate, w_in, w_out, layer = w_gate[None], w_in[None], w_out[None], 0
    f = w_in.shape[3]
    be = block_expert.astype(jnp.int32)
    used = jnp.asarray(blocks_used, jnp.int32).reshape(1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    up = partial(_up_kernel, limit=float(limit)) if limit else _up_kernel
    h = _call(up, "moe_experts_up", xs,
              (w_gate.astype(xs.dtype), w_in.astype(xs.dtype)), be, used,
              layer, bm, f, _tile(f, _fits(d, 2, xs.dtype, 512)), interpret)
    return _call(_down_kernel, "moe_experts_down", h,
                 (w_out.astype(xs.dtype),), be, used, layer, bm, d,
                 _tile(d, _fits(f, 1, xs.dtype, 1024)), interpret)


def _up_relu2_kernel(be_ref, used_ref, _, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        u = jnp.dot(x_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32)
        o_ref[...] = jnp.square(jnp.maximum(u, 0.0)).astype(o_ref.dtype)


def experts_relu2(xs, w1, w2, block_expert, blocks_used, *, bm: int,
                  interpret: Optional[bool] = None):
    """Non-gated experts over a latent (``models/hybrid.py``): row i of
    ``xs`` (R, lat), laid out as for :func:`experts_swiglu`, through its
    block's expert ``relu(x @ w1[e]) ** 2 @ w2[e]``; ``w1`` (E, lat, f),
    ``w2`` (E, f, lat), the experts this device holds. The calls have names
    of their own (``latent_experts_up`` / ``_down``): one up matrix at the
    latent width is another count of operations and bytes than gate + up at
    the model width (``benchmark/kernels``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R, lat = xs.shape
    if R % bm:
        raise ValueError(f"{R} rows are not whole blocks of {bm}")
    f = w1.shape[2]
    be = block_expert.astype(jnp.int32)
    used = jnp.asarray(blocks_used, jnp.int32).reshape(1)
    layer = jnp.zeros((1,), jnp.int32)
    h = _call(_up_relu2_kernel, "latent_experts_up", xs,
              (w1[None].astype(xs.dtype),), be, used, layer, bm, f,
              _tile(f, 1024), interpret)
    return _call(_down_kernel, "latent_experts_down", h,
                 (w2[None].astype(xs.dtype),), be, used, layer, bm, lat,
                 _tile(lat, 512), interpret)

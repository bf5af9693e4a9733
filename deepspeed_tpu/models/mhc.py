"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880 §4, on the
streams of Hyper-Connections, arXiv:2409.19606): the residual path of a trunk
that carries ``n = cfg.hc_mult`` streams a token.

A token's state is ``X`` (n, C). A sub-layer ``f`` (a mixer, an FFN; it
norms its own input) has three maps, computed from the token's own streams
by ONE product with ``phi`` (n C, n^2 + 2n) in float32:

    u      = RMSNorm_noweight(vec(X)) phi
    H_pre  = sigmoid(a_pre u[:n] + b[:n])                  (n,)   what f reads
    H_post = 2 sigmoid(a_post u[n:2n] + b[n:2n])           (n,)   where f writes
    M_0    = exp(a_res mat(u[2n:]) + b[2n:])               (n, n)
    M_t    = rows(cols(M_{t-1})),  t = 1 .. hc_sinkhorn_iters
    H_res  = M_last   (doubly stochastic: the streams are mixed, not scaled)
    X'     = H_res X + H_post^T (x) f(H_pre X)

``cols`` / ``rows`` divide by the column / row sum + ``hc_eps``. The stream
enters as the embedding repeated n times and leaves as the streams' sum
(:func:`enter`, :func:`leave`). :func:`sublayer` is the ONE residual
function: with no maps (a trunk of one stream) it is ``x + f(x)``, and at
``n = 1`` with its three maps at one it computes the same bits
(``tests/unit/test_linear_sparse.py``). The maps are float32 whatever the
compute type, they steer every layer's mix, and so are the streams
(:func:`enter`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# leaves the engine's compute cast leaves in float32
FP32_NAMES = ("mhc_phi", "mhc_b", "mhc_a")


def init_params(cfg, key, n_layers: int) -> dict:
    """Stacked maps of ``n_layers`` layers, two sub-layers each: ``a = 1``,
    ``b = 0`` and ``phi ~ N(0, 1 / nC)``, so that u is of order one and
    ``H_res`` is far from the identity: at an init where it is the identity
    to 1e-3 a path without its Sinkhorn passes would pass a comparison."""
    n, d = cfg.hc_mult, cfg.d_model
    m = n * n + 2 * n
    return {"mhc_phi": jax.random.normal(key, (n_layers, 2, n * d, m),
                                         jnp.float32) / math.sqrt(n * d),
            "mhc_b": jnp.zeros((n_layers, 2, m), jnp.float32),
            "mhc_a": jnp.ones((n_layers, 2, 3), jnp.float32)}


def param_specs() -> dict:
    return {"mhc_phi": P(None, None, None, None), "mhc_b": P(None, None, None),
            "mhc_a": P(None, None, None)}


def sinkhorn(m, iters: int, eps: float):
    """``iters`` passes of columns then rows over ``m`` (..., n, n) > 0."""
    # (unrolled: 2 x iters small divisions fuse into one program, where a
    # loop's turns would each be a program of their own in a decode step)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def maps(cfg, X, p, side: int):
    """(``H_pre`` (..., n), ``H_post`` (..., n), ``H_res`` (..., n, n)),
    float32, of the streams ``X`` (..., n, C) for sub-layer ``side`` (0 the
    mixer's, 1 the FFN's) of the layer whose weights ``p`` holds."""
    n, d = X.shape[-2:]
    f32 = jnp.float32
    v = X.astype(f32).reshape(X.shape[:-2] + (n * d,))
    v = v * lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                      + cfg.norm_eps)
    u = jnp.dot(v, p["mhc_phi"][side].astype(f32),
                precision=lax.Precision.HIGHEST)
    a, b = p["mhc_a"][side].astype(f32), p["mhc_b"][side].astype(f32)
    pre = jax.nn.sigmoid(a[0] * u[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * u[..., n:2 * n] + b[n:2 * n])
    m0 = jnp.exp(a[2] * u[..., 2 * n:] + b[2 * n:]).reshape(
        u.shape[:-1] + (n, n))
    return pre, post, sinkhorn(m0, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def sublayer(X, f, hc=None, wide: bool = False):
    """A sub-layer ``f`` joining the stream. ``hc`` None: ``X`` (..., C) is
    the one stream and the result ``X + f(X)``. Else ``hc`` = (H_pre,
    H_post, H_res) of ``X`` (..., n, C) and the result ``H_res X + H_post^T
    (x) f(H_pre X)``, mixed in float32 and handed on in ``X``'s type.
    ``wide``: ``f`` is handed the float32 mix itself (it norms first and
    rounds once, behind its norm) rather than the mix in ``X``'s type."""
    if hc is None:
        return X + f(X)
    pre, post, res = hc
    f32 = jnp.float32
    Xf = X.astype(f32)
    xin = jnp.sum(pre[..., None] * Xf, axis=-2)
    out = f(xin if wide else xin.astype(X.dtype))
    mixed = jnp.sum(res[..., None] * Xf[..., None, :, :], axis=-2)
    return (mixed + post[..., None] * out.astype(f32)[..., None, :]
            ).astype(X.dtype)


def enter(cfg, x):
    """The embedding (..., C) as the streams' start (..., n, C), float32:
    every sub-layer mixes all n streams in float32 and would round all of
    them again on the way out (ten roundings of the whole state in five
    layers, 0.6% of a logit at unit-test size), where one stream's ``x +
    out`` rounds a sum."""
    if cfg.hc_mult <= 1:
        return x
    return jnp.broadcast_to(x.astype(jnp.float32)[..., None, :],
                            x.shape[:-1] + (cfg.hc_mult, x.shape[-1]))


def leave(cfg, X, dtype):
    """What the final norm reads: the streams' sum, in ``dtype``."""
    if cfg.hc_mult <= 1:
        return X
    return jnp.sum(X, axis=-2).astype(dtype)

"""Compressed convolutional attention (CCA, arXiv:2510.04476, its CCGQA form;
ZAYA1, ``model_type: zaya``): the mathematics the full forward and the cache
path share.

The whole attention runs in a latent narrower than the model: queries
``n_head x head_dim``, keys and values ``n_kv_head x head_dim``. With ``h`` the
normed input at position t, ``C = (n_head + n_kv_head) head_dim`` channels =
``n_head + n_kv_head`` heads, and ``K0, K1 = cfg.cca_conv``:

1. ``q~ = h Wq``, ``k~ = h Wk``; **value shift**: ``u = h Wv``, the first half
   of the KV heads take ``u_t``, the second half ``u_{t-1}`` (``u_{-1}`` = 0).
2. **Two causal convolutions over positions** on ``z = [q~ ; k~]``, zeros to
   the left: ``z1_t = b0 + sum_{j<K0} w0_j * z_{t-j}`` (depthwise, a tap a
   channel), then ``z2_t = b1 + sum_{j<K1} z1_{t-j} W1_j^(head)`` (grouped, one
   ``head_dim x head_dim`` matrix a head a tap).
3. **q-k mean**: what went in is added back, shared between the two sides.
   With g(h) the KV head of query head h:
   ``q_h = z2_q[h] + (q~_h + k~_g(h)) / 2``,
   ``k_g = z2_k[g] + (mean_{h in g} q~_h + k~_g) / 2``.
4. **L2 norm a head**, keys with a learned temperature a KV head:
   ``q <- sqrt(hd) q / |q|``, ``k <- tau_g sqrt(hd) k / |k|``.
5. Rope on the first ``rotary_dim`` dims of q and k, then causal softmax of
   ``q k^T / sqrt(hd)`` and ``o = concat(heads) Wo`` (``n_head x head_dim`` ->
   ``d_model``): the trunk's own attention over K and V, which the cache
   holds as any GQA model's (``inference/kinds/cca.py`` ``CCACache``).

**What a position needs of those before it** beside K and V: the last
``K0 - 1`` rows of ``z``, the last ``K1 - 1`` rows of ``z1`` and the last row
of ``u``'s second half — the **tail**, :func:`tail_width` values a layer
whatever the length. :func:`front` takes it in and hands it out as the last
REAL token leaves it (``valid``: a right-padded chunk), so a chunk boundary, a
decode step and the whole sequence are one computation; zeros are the left
edge.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .transformer import _rope

# the learned temperature at init: exp(normal * sd); the convs' biases: sd
TEMP_SD = 0.3
BIAS_SD = 0.1


def check_config(c, attention_fn) -> None:
    """Refuse what an ``attention='cca'`` trunk does not run."""
    if (c.pos_embedding != "rope" or c.use_bias or not c.causal
            or c.objective != "clm" or c.post_ln or c.parallel_residual
            or c.loop_steps > 1 or c.sandwich_norm or c.block_pattern
            or c.attn_pattern or attention_fn is not None
            or c.moe_router != "zaya" or c.moe_first_dense):
        raise ValueError(
            "attention='cca' is the ZAYA1 block: a causal LM of pre-norm "
            "layers with rope, two-hop residual, no biases, the zaya router "
            "in every layer, its own attention (no attention_fn)")
    if c.kv_heads % 2 or c.n_head % c.kv_heads or not c.qk_head_dim \
            or c.v_dim != c.head_dim or min(c.cca_conv) < 1:
        raise ValueError(
            "cca needs an even number of KV heads (the value shift halves "
            "them) dividing n_head, qk_head_dim set (the latent's head), "
            "values as wide as keys, conv kernels >= 1")


def channels(cfg) -> int:
    """C: what the convolutions run over, [q~ ; k~]."""
    return (cfg.n_head + cfg.kv_heads) * cfg.head_dim


def _parts(cfg) -> tuple:
    """Rows of z, rows of z1 and values of u a tail holds."""
    K0, K1 = cfg.cca_conv
    return K0 - 1, K1 - 1, cfg.kv_heads // 2 * cfg.head_dim


def tail_width(cfg) -> int:
    """Values a layer keeps a slot beside K and V, whatever the length."""
    n0, n1, hv = _parts(cfg)
    return (n0 + n1) * channels(cfg) + hv


def init_params(cfg, k, dense, L: int, depth: int) -> dict:
    """Stacked attention weights of ``L`` layers (``k`` an iterator of keys,
    ``dense(key, shape, scale=None)`` the trunk's normal draw). The convs
    keep the variance of what they are fed; their biases, the temperature
    and the taps behind the current one are drawn so that a path which drops
    any of them reads differently."""
    d, h, kv, hd = cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim
    K0, K1 = cfg.cca_conv
    C = channels(cfg)

    def normal(shape, sd):
        return sd * jax.random.normal(next(k), shape, jnp.float32)

    return {
        "wq": dense(next(k), (L, d, h * hd)),
        "wk": dense(next(k), (L, d, kv * hd)),
        "wv": dense(next(k), (L, d, kv * hd)),
        "wo": dense(next(k), (L, h * hd, d),
                    scale=1.0 / math.sqrt(2 * depth * h * hd)),
        "cca_w0": normal((L, K0, C), 1.0 / math.sqrt(K0)),
        "cca_b0": normal((L, C), BIAS_SD),
        "cca_w1": normal((L, K1, h + kv, hd, hd), 1.0 / math.sqrt(K1 * hd)),
        "cca_b1": normal((L, C), BIAS_SD),
        "cca_temp": jnp.exp(normal((L, kv), TEMP_SD)),
    }


def param_specs() -> dict:
    """Every leaf replicated: a mesh is refused for this kind
    (``serving/engine.py``), so no rule here is under a test."""
    return {"wq": P(None, None, None), "wk": P(None, None, None),
            "wv": P(None, None, None), "wo": P(None, None, None),
            "cca_w0": P(None, None, None), "cca_b0": P(None, None),
            "cca_w1": P(None, None, None, None, None),
            "cca_b1": P(None, None), "cca_temp": P(None, None)}


FP32_NAMES = ("cca_w0", "cca_b0", "cca_b1", "cca_temp")


def _causal_taps(seq, n: int, T: int, tap):
    """sum_{j < n + 1} tap(j, rows t - j) for the T rows behind the ``n``
    that ``seq`` (B, n + T, ...) leads with."""
    acc = tap(0, seq[:, n:n + T])
    for j in range(1, n + 1):
        acc = acc + tap(j, seq[:, n - j:n - j + T])
    return acc


def _unit(x):
    """sqrt(head_dim) x / |x| over the last dim (= x over its RMS)."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-12)


def front(cfg, p, y, tail, positions, valid=None):
    """Everything before the product of q and k, for T positions: ``y`` (B,
    T, d) the layer's normed input, ``tail`` (B, :func:`tail_width`) what
    the positions before them left (zeros: nothing came before),
    ``positions`` (B, T) for the rope, ``valid`` (traced i32, None: T) how
    many of the T are real. Returns (q (B, T, H, hd), k (B, T, KV, hd), v (B,
    T, KV, hd), the tail as position ``valid - 1`` leaves it)."""
    B, T, _ = y.shape
    h, kv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    n0, n1, hv = _parts(cfg)
    C = channels(cfg)
    f32 = jnp.float32
    z_prev, z1_prev, u_prev = jnp.split(tail.astype(y.dtype),
                                        [n0 * C, (n0 + n1) * C], axis=-1)
    if "wqkv" in p:
        # the serving tree's one GEMM (inference/engine.py): [q~ ; k~ ; u]
        z, u = jnp.split(y @ p["wqkv"].astype(y.dtype), [C], axis=-1)
    else:
        z = jnp.concatenate([y @ p["wq"].astype(y.dtype),
                             y @ p["wk"].astype(y.dtype)], axis=-1)
        u = y @ p["wv"].astype(y.dtype)
    # the value shift: the second half of the KV heads sees the row before
    u_seq = jnp.concatenate([u_prev[:, None], u[..., hv:]], axis=1)
    v = jnp.concatenate([u[..., :hv], u_seq[:, :T]], axis=-1)
    # the depthwise conv, float32, rounded as z is
    seq0 = jnp.concatenate([z_prev.reshape(B, n0, C), z], axis=1)
    w0 = p["cca_w0"].astype(f32)
    z1 = (_causal_taps(seq0, n0, T, lambda j, rows: rows.astype(f32) * w0[j])
          + p["cca_b0"].astype(f32)).astype(y.dtype)
    # the grouped conv: a head's channels through its own matrix, a tap each
    seq1 = jnp.concatenate([z1_prev.reshape(B, n1, C), z1], axis=1)
    # (operands widened: bf16-valued, so the product is the bf16 one with a
    # float32 sum, and XLA:CPU has no bf16 x bf16 = f32 dot of this shape)
    w1 = p["cca_w1"].astype(f32)
    z2 = _causal_taps(
        seq1.reshape(B, n1 + T, h + kv, hd), n1, T,
        lambda j, rows: jnp.einsum("btgd,gde->btge", rows.astype(f32),
                                   w1[j])) \
        + p["cca_b1"].astype(f32).reshape(h + kv, hd)
    # the q-k mean: what went in, shared between the two sides
    zq = z[..., :h * hd].astype(f32).reshape(B, T, kv, h // kv, hd)
    zk = z[..., h * hd:].astype(f32).reshape(B, T, kv, 1, hd)
    q = z2[:, :, :h].reshape(B, T, kv, h // kv, hd) + (zq + zk) / 2
    k = z2[:, :, h:] + (zq.mean(3) + zk[:, :, :, 0]) / 2
    q = _unit(q).reshape(B, T, h, hd)
    k = _unit(k) * p["cca_temp"].astype(f32)[:, None]
    q, k = _rope(q, k, positions, cfg.rope_theta, cfg.rotary_dim,
                 halves=cfg.rope_halves)
    at = T if valid is None else valid
    new_tail = jnp.concatenate(
        [lax.dynamic_slice_in_dim(seq0, at, n0, axis=1).reshape(B, n0 * C),
         lax.dynamic_slice_in_dim(seq1, at, n1, axis=1).reshape(B, n1 * C),
         lax.dynamic_slice_in_dim(u_seq, at, 1, axis=1)[:, 0]], axis=-1)
    return (q.astype(y.dtype), k.astype(y.dtype),
            v.reshape(B, T, kv, hd), new_tail.astype(tail.dtype))


@jax.named_scope("attn")
def attention_block(cfg, y, p, positions, attend):
    """One layer's attention on a whole sequence (no cache): y (B, S, d)
    post-norm -> (B, S, d); ``attend`` the trunk's causal attention."""
    B, S, _ = y.shape
    q, k, v, _ = front(cfg, p, y, jnp.zeros((B, tail_width(cfg)), y.dtype),
                       positions)
    o = attend(q, k, v, mask=None)
    return o.reshape(B, S, cfg.n_head * cfg.head_dim) @ p["wo"].astype(y.dtype)

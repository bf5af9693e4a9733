"""GPT-2 as published (Radford et al. 2019; the layout of HF ``GPT2LMHeadModel``),
plainly: ``jax.numpy``, float32, no kernel, no cache, no remat, no sharding.

It reads the repo model's parameter tree (layers stacked on a leading axis)
so that it can be fed the engine's own seeded weights, and shares no code
with ``deepspeed_tpu``. On a TPU a float32 matmul multiplies in bf16 unless
told otherwise, so callers run it under
``jax.default_matmul_precision("highest")`` (``run_highest`` does).

Departures from the published model: none in the mathematics; dropout is
absent (inference and this benchmark's training both run without it).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layernorm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def logits(params, input_ids, n_head: int, eps: float = 1e-5,
           last_only: bool = False):
    """(B, S) token ids -> (B, S, V) float32 logits, or (B, V) of the last
    position with ``last_only``. Weights of any float type are widened to
    float32 where they are used."""
    p = dict(params)
    B, S = input_ids.shape
    emb = _f32(p["tok_embed"])
    x = emb[input_ids] + _f32(p["pos_embed"])[:S][None]
    d = x.shape[-1]
    hd = d // n_head
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        w = _f32(w)
        y = _layernorm(x, w["ln1_scale"], w["ln1_bias"], eps)
        q = (y @ w["wq"] + w["bq"]).reshape(B, S, n_head, hd)
        k = (y @ w["wk"] + w["bk"]).reshape(B, S, n_head, hd)
        v = (y @ w["wv"] + w["bv"]).reshape(B, S, n_head, hd)
        s = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(B, S, d) @ w["wo"] + w["bo"]
        y = _layernorm(x, w["ln2_scale"], w["ln2_bias"], eps)
        x = x + _gelu_new(y @ w["w_in"] + w["b_in"]) @ w["w_out"] + w["b_out"]
        return x, None

    # one layer body scanned over the stacked weights: the same mathematics
    # as a Python loop, compiled once instead of once per layer
    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _layernorm(x, _f32(p["lnf_scale"]), _f32(p["lnf_bias"]), eps)
    if last_only:
        x = x[:, -1]
    return x @ emb.T


def loss(params, input_ids, n_head: int, eps: float = 1e-5):
    """Mean next-token cross-entropy over every position but the last."""
    lg = logits(params, input_ids, n_head, eps)[:, :-1]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, input_ids[:, 1:, None], -1)[..., 0]
    return nll.mean()


def run_highest(fn, *args, **static):
    """``fn`` jitted and run in true float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **static))(*args)

"""The Ouro looped language model as published (Zhu et al. 2025, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741; the layout of
the model's public ``modeling_ouro.py`` beside its ``config.json``), plainly:
``jax.numpy``, float32, full causal attention, a Python loop over the passes
and, inside it, one layer body over the stacked layers; no cache, no kernel,
and nothing of ``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    layer l:  y = RMS(x; g1);  q, k, v = y Wq, y Wk, y Wv  (rope on q, k)
              x = x + RMS(softmax(causal(q k^T / sqrt(hd))) v Wo; g2)
              y = RMS(x; g3);  x = x + RMS((silu(y Wgate) * (y Wup)) Wdown; g4)
    model:    h_0 = E[ids];  for r = 1..R: h_r = RMS(layers(h_{r-1}); g_f),
              lambda_r = sigmoid(w_g . h_r + b_g)
              p_r = lambda_r prod_{j<r} (1 - lambda_j) for r < R,
              p_R = prod_{j<R} (1 - lambda_j)
              exit pass = the first r whose cumulative p reaches the
              threshold, R if none does;  logits = h_exit W_head

Every pass attends over ITS OWN keys and values (no cache here, so a pass
simply recomputes them from its own input); the final norm closes every pass,
the last included, and what it leaves is what the next pass starts from.

It reads the repo model's parameter tree (layers stacked on a leading axis:
``ln1_scale`` g1, ``ln1_post_scale`` g2, ``ln2_scale`` g3, ``ln2_post_scale``
g4, ``w_gate`` / ``w_in`` (up) / ``w_out`` (down), ``lnf_scale`` g_f,
``exit_gate_w`` / ``exit_gate_b``, ``lm_head``) so that it can be fed the
engine's own seeded weights. What cannot be read off the weights' shapes
(heads, epsilon, theta, the passes, the threshold) comes from the
configuration's published keys, handed over with :func:`configure`.

Departures from the published model: none in the mathematics. Written from
memory of ``modeling_ouro.py`` (no network here): the sandwich norms, the
norm closing every pass, the gate and the exit rule are the configuration
file's ``assumed``. **Rope basis:** HF rotates a head's halves (column i with
i + hd/2); this file, like the repo's trunk, rotates the pairs (2i, 2i + 1)
by the same angles, which is the same function of weights whose q / k columns
are permuted by :func:`pairs_from_halves` (what ``models/importer.py`` does
to a Llama-family checkpoint); ``rope="halves"`` is HF's own basis, and
``benchmark/tests/test_ouro.py`` holds the two equal under that permutation.
Each layer's weights are widened to float32 inside the layer loop, one layer
at a time, so that on the chip the reference fits beside the engine.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PUBLISHED: dict = {}


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    for key, only in (("rope_scaling", None), ("use_sliding_window", False),
                      ("hidden_act", "silu"), ("tie_word_embeddings", False)):
        if published.get(key, only) != only:
            raise ValueError(f"this reference has {key} = {only!r} only")
    if set(published.get("layer_types", ["full_attention"])) \
            != {"full_attention"}:
        raise ValueError("this reference has full attention in every layer")
    PUBLISHED.clear()
    PUBLISHED.update(published)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta: float, basis: str):
    """x (B, S, H, hd) turned by pos * theta^(-2i/hd): ``pairs`` turns
    (x[2i], x[2i+1]), ``halves`` turns (x[i], x[i + hd/2])."""
    S, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]     # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if basis == "halves":
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def pairs_from_halves(w, n_head: int):
    """A q or k projection (..., d, H * hd) from HF's half-rotating column
    order to the pair-rotating one: pair i of a head is HF's columns
    (i, i + hd/2)."""
    hd = w.shape[-1] // n_head
    heads = w.reshape(w.shape[:-1] + (n_head, 2, hd // 2))
    return jnp.swapaxes(heads, -1, -2).reshape(w.shape)


def exit_pdf(lam):
    """Gate values (R, ...) -> the distribution over exit passes (R, ...)."""
    out, stay = [], jnp.ones_like(lam[0])
    for r in range(lam.shape[0] - 1):
        out.append(lam[r] * stay)
        stay = stay * (1.0 - lam[r])
    return jnp.stack(out + [stay])


def exit_pass(pdf, threshold: float):
    """The first pass (0-based) whose cumulative probability reaches
    ``threshold``; the last pass where none does."""
    reached = jnp.cumsum(pdf, 0)[:-1] >= threshold
    last = pdf.shape[0] - 1
    return jnp.where(reached.any(0), jnp.argmax(reached, 0), last)


def passes(params, input_ids, rope: str = "pairs"):
    """(B, S) token ids -> (hidden (R, B, S, d): h_1 .. h_R, each closed by
    the final norm; pdf (R, B, S): the exit distribution)."""
    pub = PUBLISHED
    H, eps = pub["num_attention_heads"], pub["rms_norm_eps"]
    theta = float(pub["rope_theta"])
    p = dict(params)
    B, S = input_ids.shape
    x = jnp.asarray(p["tok_embed"][input_ids], jnp.float32)
    d = x.shape[-1]
    hd = d // H
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        w = _f32(w)                     # one layer's weights at a time
        y = _rmsnorm(x, w["ln1_scale"], eps)
        q = _rope((y @ w["wq"]).reshape(B, S, H, hd), theta, rope)
        k = _rope((y @ w["wk"]).reshape(B, S, H, hd), theta, rope)
        v = (y @ w["wv"]).reshape(B, S, H, hd)
        s = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
        x = x + _rmsnorm(a.reshape(B, S, d) @ w["wo"], w["ln1_post_scale"],
                         eps)
        y = _rmsnorm(x, w["ln2_scale"], eps)
        m = (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_in"])) @ w["w_out"]
        return x + _rmsnorm(m, w["ln2_post_scale"], eps), None

    g_f = _f32(p["lnf_scale"])
    w_g, b_g = _f32(p["exit_gate_w"]), _f32(p["exit_gate_b"])
    hidden, lam = [], []
    for _ in range(pub["total_ut_steps"]):
        x, _ = jax.lax.scan(layer, x, p["layers"])
        x = _rmsnorm(x, g_f, eps)
        hidden.append(x)
        lam.append(jax.nn.sigmoid((x * w_g).sum(-1) + b_g))
    return jnp.stack(hidden), exit_pdf(jnp.stack(lam))


def logits(params, input_ids, n_head=None, eps=None, last_only: bool = False,
           rows=None, rope: str = "pairs"):
    """(B, S) token ids -> (B, S, V) float32 logits of the exit pass's hidden
    state; (B, V) of the last position with ``last_only``, (B, len(rows), V)
    of the positions ``rows``. ``n_head`` and ``eps`` are what the shared
    serving kind hands every reference; they have to be the configured
    ones."""
    pub = PUBLISHED
    if n_head not in (None, pub["num_attention_heads"]) \
            or eps not in (None, pub["rms_norm_eps"]):
        raise ValueError("n_head / eps differ from the configured keys")
    hidden, pdf = passes(params, input_ids, rope)
    at = exit_pass(pdf, float(pub["early_exit_threshold"]))          # (B, S)
    x = jnp.take_along_axis(hidden, at[None, ..., None], 0)[0]
    if last_only:
        x = x[:, -1]
    elif rows is not None:
        x = x[:, jnp.asarray(rows)]
    return x @ _f32(params["lm_head"])


def run_highest(fn, *args, **static):
    """``fn`` jitted and run in true float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **static))(*args)

"""Falcon-H1 as published (``model_type: falcon_h1``; tiiuae/Falcon-H1-34B-
Instruct's ``config.json`` beside the layout of the family's public
``modeling_falcon_h1.py``), plainly: ``jax.numpy``, float32, the Mamba-2
recurrence token by token, full causal attention, every multiplier applied
where the model publishes it; no cache, no chunked scan, no kernel, and
nothing of ``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    h0 = E[ids] * embedding_multiplier
    layer:  y  = RMS(x; g_in)
            a  = Attn(y * attention_in_multiplier) * attention_out_multiplier
            m  = Mamba2(y) * ssm_out_multiplier
            x  = x + a + m
            y2 = RMS(x; g_ff)
            x  = x + W_down(silu(W_gate y2 * mlp_multipliers[0]) * (W_up y2))
                     * mlp_multipliers[1]
    logits = W_head RMS(x_L; g_f) * lm_head_multiplier
    Attn:   q = W_q u;  k = (W_k u) * key_multiplier;  v = W_v u;  RoPE on q
            and k over the whole head, dim i paired with i + head_dim / 2
            (rotate_half), angles in float32;  softmax(causal(q k^T /
            sqrt(head_dim))) v;  W_o       (GQA: a KV head serves H / KV heads)
    Mamba2: p = (W_in (y * ssm_in_multiplier)) * mup, mup = ssm_multipliers
            spread over [z | x | B | C | dt];  xBC = silu(conv1d_4(xBC) + b)
            (causal, depthwise);  dt = softplus(dt + dt_bias);
            A = -exp(A_log);  head h of group g = h // (H / G):
            S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
            y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
            out = RMS_per_group(y * silu(z); gain) W_out

It reads the repo model's parameter tree (``models/hybrid.py``: one run of
stacked ``P`` layers) so that it can be fed the engine's own seeded weights.
What cannot be read off the weights' shapes (heads, groups, state, epsilon,
theta, the multipliers) comes from the published keys.

Departures from the published model: none in the mathematics. Written from
memory of ``modeling_falcon_h1.py`` (no network here): the order [z | x | B |
C | dt] of ``in_proj``'s columns and the five ``ssm_multipliers`` over them,
that ``ssm_in_multiplier`` scales the mixer's input and ``key_multiplier``
the keys before the rotation, the gate before the grouped norm
(``mamba_norm_before_gate`` false), the softmax scale 1 / sqrt(head_dim) and
the rotation over the whole head are the configuration file's ``assumed``.
The published module multiplies by ``mup`` in the model's dtype; here
everything is float32. Each layer's weights are widened to float32 one layer
at a time and the head by blocks of the vocabulary, so that on the chip the
reference fits beside the engine.

The module's pieces a control replaces (``benchmark/kinds/backlog_parallel
.py``): :data:`PUBLISHED`'s multipliers, :data:`ROUND` (every product's
operands), :data:`WINDOW_CUT` (the conv's window dropped every so many
tokens).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PUBLISHED: dict = {}
ROUND = None          # a control's rounding of every product's operands
WINDOW_CUT = 0        # a control: the conv reads zeros across every multiple
HEAD_BLOCKS = 8       # the head is widened an eighth of the vocabulary a time


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    for key, only in (("hidden_act", "silu"), ("attention_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("mamba_rms_norm", True), ("mamba_use_mlp", True),
                      ("mamba_norm_before_gate", False),
                      ("attn_layer_indices", None), ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        if published.get(key, only) != only:
            raise ValueError(f"this reference has {key} = {only!r} only")
    PUBLISHED.clear()
    PUBLISHED.update(published)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _mm(a, b):
    if ROUND is not None:
        a, b = ROUND(a), ROUND(b)
    return a @ b


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x (B, S, heads, hd) rotated by its position, HF's ``rotate_half``."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs       # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def attention(y, w, c):
    B, S, _ = y.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    u = y * c["attention_in_multiplier"]
    q = _mm(u, w["wq"]).reshape(B, S, H, hd)
    k = (_mm(u, w["wk"]) * c["key_multiplier"]).reshape(B, S, KV, hd)
    v = _mm(u, w["wv"]).reshape(B, S, KV, hd)
    theta = float(c["rope_theta"])       # (1e11 stands as an int in the file)
    q, k = rope(q, theta), rope(k, theta)
    k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
    return _mm(a.reshape(B, S, H * hd), w["wo"])


def mup_vector(c):
    """``ssm_multipliers`` over in_proj's columns [z | x | B | C | dt]."""
    inner, bc = c["mamba_d_ssm"], c["mamba_n_groups"] * c["mamba_d_state"]
    return jnp.concatenate([
        jnp.full((n,), m, jnp.float32) for n, m in zip(
            (inner, inner, bc, bc, c["mamba_n_heads"]),
            c["ssm_multipliers"])])


def mamba(y, w, c):
    """The Mamba-2 mixer on y (B, S, d) by the plain recurrence from an empty
    state. Returns out (B, S, d)."""
    B, S, _ = y.shape
    H, P = c["mamba_n_heads"], c["mamba_d_head"]
    G, N, K = c["mamba_n_groups"], c["mamba_d_state"], c["mamba_d_conv"]
    inner, bc = H * P, G * N
    u = _mm(y * c["ssm_in_multiplier"], w["w_in"]) * mup_vector(c)
    z, xbc, dt = u[..., :inner], u[..., inner:2 * inner + 2 * bc], \
        u[..., 2 * inner + 2 * bc:]
    seq = jnp.concatenate([jnp.zeros((B, K - 1, inner + 2 * bc)), xbc], 1)
    conv = w["conv_b"]
    for j in range(K):                # tap j reaches back K - 1 - j tokens
        tap = seq[:, j:j + S]
        if WINDOW_CUT:                # a window dropped at a chunk boundary
            across = jnp.arange(S) % WINDOW_CUT < K - 1 - j
            tap = jnp.where(across[None, :, None], 0.0, tap)
        conv = conv + tap * w["conv_w"][:, j]
    conv = jax.nn.silu(conv)
    x = conv[..., :inner].reshape(B, S, H, P)
    Bm = conv[..., inner:inner + bc].reshape(B, S, G, N)
    Cm = conv[..., inner + bc:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])                        # (B, S, H)
    A = -jnp.exp(w["A_log"])
    rep = H // G

    def token(St, t):
        xt, bt, ct, dtt = t
        bt, ct = jnp.repeat(bt, rep, 1), jnp.repeat(ct, rep, 1)   # (B, H, N)
        St = jnp.exp(dtt * A)[..., None, None] * St \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return St, (St * ct[:, :, None, :]).sum(-1) + w["D"][:, None] * xt

    _, ys = jax.lax.scan(token, jnp.zeros((B, H, P, N), jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt)))
    g = jnp.moveaxis(ys, 0, 1).reshape(B, S, inner) * jax.nn.silu(z)
    g = g.reshape(B, S, G, inner // G)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + c["rms_norm_eps"])
    return _mm(g.reshape(B, S, inner) * w["ssm_norm_scale"], w["w_out"])


def mlp(y2, w, c):
    gate, down = c["mlp_multipliers"]
    h = jax.nn.silu(_mm(y2, w["w_gate"]) * gate) * _mm(y2, w["w_up"])
    return _mm(h, w["w_down"]) * down


def _layer(x, w, c):
    """One layer on x (B, S, d); ``w`` its weights as stored."""
    w = _f32(w)
    eps = c["rms_norm_eps"]
    y = _rmsnorm(x, w["ln1_scale"], eps)
    x = x + attention(y, w, c) * c["attention_out_multiplier"] \
        + mamba(y, w, c) * c["ssm_out_multiplier"]
    return x + mlp(_rmsnorm(x, w["ln2_scale"], eps), w, c)


def head(params, x, c):
    """x (..., d) after the final norm -> (..., V), the head widened a block
    of the vocabulary at a time."""
    w = params["lm_head"]
    V = w.shape[1]
    step = -(-V // HEAD_BLOCKS)
    return jnp.concatenate([
        _mm(x, jnp.asarray(w[:, i:i + step], jnp.float32))
        for i in range(0, V, step)], -1) * c["lm_head_multiplier"]


def logits(params, input_ids, n_head=None, eps=None, last_only: bool = False,
           rows=None):
    """(B, S) token ids -> (B, S, V) float32 logits; (B, V) of the last
    position with ``last_only``, (B, len(rows), V) of the positions ``rows``.
    ``n_head`` and ``eps`` are what the shared serving kind hands every
    reference; they have to be the configured ones."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    if n_head not in (None, c["num_attention_heads"]) \
            or eps not in (None, c["rms_norm_eps"]):
        raise ValueError("n_head / eps differ from the configured keys")
    x = jnp.asarray(params["tok_embed"][input_ids], jnp.float32) \
        * c["embedding_multiplier"]
    (seg,) = params["layers"]
    x, _ = jax.lax.scan(lambda x, w: (_layer(x, w, c), None), x, seg)
    x = _rmsnorm(x, _f32(params["lnf_scale"]), c["rms_norm_eps"])
    if last_only:
        x = x[:, -1]
    elif rows is not None:
        x = x[:, jnp.asarray(rows)]
    return head(params, x, c)


def loss(params, batch):
    """Mean next-token negative log-likelihood over ``batch["input_ids"]``
    (B, S), float32."""
    ids = batch["input_ids"]
    lp = jax.nn.log_softmax(logits(params, ids)[:, :-1], -1)
    return -jnp.take_along_axis(lp, ids[:, 1:, None], -1).mean()


def run_highest(fn, *args, **static):
    """``fn`` jitted and run in true float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **static))(*args)

"""NemotronH as published (``model_type: nemotron_h``; NVIDIA-Nemotron-3-
Super-120B-A12B's ``config.json`` beside the layout of the family's public
``modeling_nemotron_h.py``), plainly: ``jax.numpy``, float32, the Mamba-2
recurrence token by token, full causal attention, every held expert on every
token; no cache, no chunked scan, no kernel, and nothing of
``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    layer i of kind pattern[i]:  x = x + mixer_i(RMS(x; g_i))
    M:  [z | xBC | dt] = y W_in;  xBC = silu(conv1d_4(xBC) + b)  (causal,
        depthwise);  x, B, C = xBC;  dt = softplus(dt + dt_bias);
        A = -exp(A_log);  head h of group g = h // (H / G):
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
        y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
        out = RMS_per_group(y * silu(z); gain) W_out     (groups of d_inner / G)
    *:  q, k, v = y Wq, y Wk, y Wv;  softmax(causal(q k^T / sqrt(hd))) v Wo
        (GQA: a KV head serves H / KV query heads; NO position code)
    E:  s = sigmoid(y W_r);  chosen = top-k of s + bias;  w = s[chosen] /
        sum * scale;  u = y W_dn;  expert e = relu(u W1_e)^2 W2_e
        out = (sum_chosen w_e expert_e(u)) W_up + relu(y Ws1)^2 Ws2
    model:  logits = RMS(x_L; g_f) W_head

It reads the repo model's parameter tree (``models/hybrid.py``: a tuple of
runs of equal layers, stacked) so that it can be fed the engine's own seeded
weights. **The chip's share**: ``w1`` / ``w2`` hold the experts this device
holds, ``first_held`` (handed to :func:`configure` with the published keys)
says which of the router's outputs the first of them is; a chosen expert held
elsewhere adds nothing here, as in the program. What cannot be read off the
weights' shapes (heads, groups, state, epsilon, top-k, the scale) comes from
the published keys.

Departures from the published model: none in the mathematics of the layers
held. Written from memory of ``modeling_nemotron_h.py`` (no network here): the
order [z | xBC | dt] and [x | B | C], the gate before the grouped norm, that
attention applies no rotary code (``rope_theta`` and ``partial_rotary_factor``
stand in the config unused), relu^2 experts without a gate, the latent
projections shared by all routed experts and the shared expert on the full
width are the configuration file's ``assumed``. The multi-token-prediction
module (``num_nextn_predict_layers``) drafts for speculation and is no part of
the forward. Each layer's weights are widened to float32 one layer (one expert)
at a time, so that on the chip the reference fits beside the engine.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PUBLISHED: dict = {}


def configure(published: dict, first_held: int = 0) -> None:
    """The configuration's published keys (``config`` of its file) and the
    first expert of the router's outputs that this share holds."""
    for key, only in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1), ("use_bias", False),
                      ("use_conv_bias", True), ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if published.get(key, only) != only:
            raise ValueError(f"this reference has {key} = {only!r} only")
    PUBLISHED.clear()
    PUBLISHED.update(published, first_held=int(first_held))


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def mamba(y, w, c, state=None):
    """The Mamba-2 mixer on y (B, S, d) by the plain recurrence. ``state``:
    (S_0 (B, H, P, N), the conv's last K - 1 inputs (B, K - 1, C)) to start
    from, default empty. Returns (out (B, S, d), (S_T, window))."""
    B, S, _ = y.shape
    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    G, N, K = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    inner, bc = H * P, G * N
    u = y @ w["w_in"]
    z, xbc, dt = u[..., :inner], u[..., inner:2 * inner + 2 * bc], \
        u[..., 2 * inner + 2 * bc:]
    S0, win = state if state is not None else (
        jnp.zeros((B, H, P, N), jnp.float32),
        jnp.zeros((B, K - 1, inner + 2 * bc), jnp.float32))
    seq = jnp.concatenate([win, xbc], 1)
    conv = w["conv_b"] + sum(seq[:, j:j + S] * w["conv_w"][:, j]
                             for j in range(K))
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

    ST, ys = jax.lax.scan(token, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt)))
    g = jnp.moveaxis(ys, 0, 1).reshape(B, S, inner) * jax.nn.silu(z)
    g = g.reshape(B, S, G, inner // G)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True)
                          + c["layer_norm_epsilon"])
    out = (g.reshape(B, S, inner) * w["ssm_norm_scale"]) @ w["w_out"]
    return out, (ST, seq[:, S:])


def attention(y, w, c):
    B, S, d = y.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    q = (y @ w["wq"]).reshape(B, S, H, hd)
    k = jnp.repeat((y @ w["wk"]).reshape(B, S, KV, hd), H // KV, 2)
    v = jnp.repeat((y @ w["wv"]).reshape(B, S, KV, hd), H // KV, 2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, S, H * hd) @ w["wo"]


def router(y, w, c, follow=None, gap: float = 0.0):
    """(N, d) tokens -> ((N, E) combine weights over ALL experts, zero but
    for the chosen; how many tokens followed ``follow``).

    ``follow`` (N, k): another implementation's choice for these tokens.
    With random weights the k-th and (k+1)-th biased scores of a token can
    lie closer than that implementation's rounding, and it then takes the
    other expert: a different model from there on, not an error. A token
    whose own k-th and (k+1)-th scores lie within ``gap`` takes ``follow``'s
    experts (weighted by this router's own scores); every other token keeps
    its own choice, whatever ``follow`` says."""
    score = jax.nn.sigmoid(y @ w["router"])
    biased = score + w["router_bias"]
    k = c["num_experts_per_tok"]
    ranked = jnp.sort(biased, -1)
    chosen = biased >= ranked[:, -k][:, None]
    followed = jnp.zeros((), jnp.int32)
    if follow is not None:
        theirs = jax.nn.one_hot(follow, biased.shape[-1], dtype=bool).any(1)
        near = (ranked[:, -k] - ranked[:, -k - 1]) < gap
        followed = (near & (theirs != chosen).any(-1)).sum().astype(jnp.int32)
        chosen = jnp.where(near[:, None], theirs, chosen)
    g = jnp.where(chosen, score, 0.0)
    g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return g * c["routed_scaling_factor"], followed


def experts(y, w, c, follow=None, gap: float = 0.0, shared: bool = True):
    """The ``E`` mixer on (N, d): every HELD expert on every token, weighted
    by the router's weight for it (0 where it was not chosen); the chosen
    experts held elsewhere add nothing. ``w`` is the layer's tree as stored
    (the bank is widened an expert at a time)."""
    f = _f32
    g, followed = router(y, f({k: w[k] for k in ("router", "router_bias")}),
                         c, follow, gap)
    held = w["w1"].shape[0]
    g = jax.lax.dynamic_slice_in_dim(g, c["first_held"], held, 1)
    u = y @ f(w["w_dn"])

    def one(acc, ew):
        w1, w2, ge = ew
        h = jnp.square(jax.nn.relu(u @ f(w1))) @ f(w2)
        return acc + ge[:, None] * h, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (w["w1"], w["w2"], g.T))
    out = routed @ f(w["w_up"])
    if shared:
        out = out + jnp.square(jax.nn.relu(y @ f(w["ws_in"]))) @ f(w["ws_out"])
    return out, followed


def _layer(x, w, c, follow=None, gap: float = 0.0):
    """One layer, its kind read off what its weights hold; (x, tokens that
    followed ``follow``)."""
    eps = c["layer_norm_epsilon"]
    y = _rmsnorm(x, _f32(w["ln1_scale"]), eps)
    none = jnp.zeros((), jnp.int32)
    if "router" in w:
        B, S, d = y.shape
        out, followed = experts(
            y.reshape(B * S, d), w, c,
            None if follow is None else follow.reshape(B * S, -1), gap)
        return x + out.reshape(B, S, d), followed
    if "A_log" in w:
        return x + mamba(y, _f32(w), c)[0], none
    return x + attention(y, _f32(w), c), none


def logits(params, input_ids, n_head=None, eps=None, last_only: bool = False,
           rows=None, follow=None, gap: float = 0.0):
    """(B, S) token ids -> (B, S, V) float32 logits; (B, V) of the last
    position with ``last_only``, (B, len(rows), V) of the positions ``rows``.
    ``n_head`` and ``eps`` are what the shared serving kind hands every
    reference; they have to be the configured ones. With ``follow`` (expert
    layers, B, S, k), another implementation's routing, the result is
    (logits, tokens x layers that followed it): see :func:`router`."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    if n_head not in (None, c["num_attention_heads"]) \
            or eps not in (None, c["layer_norm_epsilon"]):
        raise ValueError("n_head / eps differ from the configured keys")
    x = _f32(params["tok_embed"])[input_ids]
    first, followed = 0, jnp.zeros((), jnp.int32)
    for seg in params["layers"]:
        n = jax.tree.leaves(seg)[0].shape[0]
        routed = follow is not None and "router" in seg
        theirs = follow[first:first + n] if routed else None
        x, took = jax.lax.scan(
            lambda x, wf: _layer(x, wf[0], c, wf[1], gap), x, (seg, theirs))
        followed = followed + took.sum()
        first += n if routed else 0
    x = _rmsnorm(x, _f32(params["lnf_scale"]), c["layer_norm_epsilon"])
    if last_only:
        x = x[:, -1]
    elif rows is not None:
        x = x[:, jnp.asarray(rows)]
    out = x @ _f32(params["lm_head"])
    return out if follow is None else (out, followed)


def run_highest(fn, *args, **static):
    """``fn`` jitted and run in true float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **static))(*args)

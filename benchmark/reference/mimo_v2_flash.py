"""MiMo-V2-Flash as published (``model_type: mimo_v2_flash``;
XiaomiMiMo/MiMo-V2-Flash's ``config.json``), plainly: ``jax.numpy``, float32,
every layer's attention the full score matrix under its mask, every held
expert on every token; no cache, no ring, no kernel, no running max, and
nothing of ``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    layer i:  x = x + attn_i(RMS(x; g1_i));  x = x + ffn_i(RMS(x; g2_i))
    attn:  q = y Wq (H heads of 192), k = y Wk (KV heads of 192),
           v = 0.707 * y Wv (KV heads of 128);  the first 64 dims of every
           head of q and k turned by rope (pairs i, i + 32; theta by kind);
           s = q k^T / sqrt(192);  a KV head serves H / KV query heads
       full   (pattern 0): 4 KV heads, theta 5e6,  p = softmax(causal(s))
       window (pattern 1): 8 KV heads, theta 1e4,  key j for query i iff
           i - 127 <= j <= i;  p_ij = exp(s_ij - m) / (exp(sink_h - m)
           + sum_j exp(s_ij - m)): the sink a column that carries no value
       out = (p v) Wo                                  (H x 128 -> d)
    ffn (moe_layer_freq 0):  (silu(y Wg) * (y Wi)) Wo
    ffn (moe_layer_freq 1):  s = sigmoid(y Wr);  chosen = top-8 of s + bias;
           w = s[chosen] / sum(s[chosen]);  sum_chosen w_e swiglu_e(y)
    model:  logits = RMS(x_L; g_f) W_head

It reads the repo model's parameter tree (a tuple of runs of layers equal in
attention kind and FFN kind, stacked) so that it can be fed the engine's own
seeded weights; a layer's kind comes from the published
``hybrid_layer_pattern`` / ``moe_layer_freq`` by its index, its KV heads from
the published keys (the weights' shapes have to agree). **The chip's share**:
``w_gate`` / ``w_in`` / ``w_out`` hold the experts this device holds,
``first_held`` (handed to :func:`configure`) says which of the router's
outputs the first of them is; a chosen expert held elsewhere adds nothing
here, as in the program, and its weight still counts in the normalisation.
The head holds the vocabulary's slice.

The queries go in blocks of ``QUERY_BLOCK`` rows (each against every key,
under the mask), so that a prompt of several thousand tokens fits; that is
the same mathematics as one matrix. Each layer's weights are widened to
float32 one layer (one expert) at a time.

Departures from the published model: the 3 multi-token-prediction layers of
the model card are no part of ``config.json`` or of the forward, and are not
held. Assumed (the configuration file's ``assumed``; there is no network
here): the rotation pairs dim i with i + 32 of the first 64 (HF
``rotate_half``) with frequencies over those 64; ``attention_value_scale``
multiplies V (equally: the attention's output before ``o_proj``); the sink
logit joins the softmax's denominator only.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PUBLISHED: dict = {}
QUERY_BLOCK = 256
# None, or a control's rounding of every matrix as it is widened (the
# router's apart, so that the choice of experts stays the comparison's own):
# benchmark/kinds/backlog_windowed.py CONTROLS. Never set in a timed run.
ROUND = None


def configure(published: dict, first_held: int = 0) -> None:
    """The configuration's published keys (``config`` of its file) and the
    first expert of the router's outputs that this share holds."""
    for key, only in (("model_type", "mimo_v2_flash"), ("hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("n_shared_experts", None),
                      ("routed_scaling_factor", None),
                      ("add_swa_attention_sink_bias", True),
                      ("add_full_attention_sink_bias", False)):
        if published.get(key, only) != only:
            raise ValueError(f"this reference has {key} = {only!r} only")
    PUBLISHED.clear()
    PUBLISHED.update(published, first_held=int(first_held))


def _f32(tree, matrices: bool = True):
    def widen(a):
        a = jnp.asarray(a, jnp.float32)
        return ROUND(a) if ROUND and matrices and a.ndim >= 2 else a

    return jax.tree.map(widen, tree)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope(x, theta: float, rd: int):
    """x (B, S, heads, hd): the first ``rd`` dims of every head turned, dim
    i with i + rd / 2, by position * theta^(-2i / rd); the rest as it is."""
    S = x.shape[1]
    inv = theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]     # (S, rd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rd:]], -1)


def attention(y, w, c, window: bool):
    """One layer's attention on y (B, S, d) post-norm -> (B, S, d); ``w``
    float32. The whole score matrix under the kind's mask, a block of query
    rows at a time."""
    B, S, _ = y.shape
    H, hd, vd = c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    KV = c["swa_num_key_value_heads" if window else "num_key_value_heads"]
    theta = c["swa_rope_theta" if window else "rope_theta"]
    rd = int(hd * c["partial_rotary_factor"])
    if w["wk"].shape[-1] != KV * hd or w["wv"].shape[-1] != KV * vd:
        raise ValueError("the layer's K/V projections are not its kind's")
    q = rope((y @ w["wq"]).reshape(B, S, H, hd), theta, rd)
    k = rope((y @ w["wk"]).reshape(B, S, KV, hd), theta, rd)
    v = (y @ w["wv"]).reshape(B, S, KV, vd) * c["attention_value_scale"]
    k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
    width = c["sliding_window"]
    n = -(-S // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - S
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, n, QUERY_BLOCK, H, hd)
    j = jnp.arange(S)[None, :]

    def block(args):
        qi, first = args
        i = first + jnp.arange(QUERY_BLOCK)[:, None]
        keep = j <= i
        if window:
            keep &= j > i - width
        s = jnp.einsum("bqhd,bthd->bhqt", qi, k) / math.sqrt(hd)
        s = jnp.where(keep[None, None], s, -jnp.inf)
        # (a padded query row past S sees keys too: it is cut off below)
        m = s.max(-1, keepdims=True)
        if window:
            sink = w["sink"][None, :, None, None]
            m = jnp.maximum(m, sink)
        e = jnp.exp(s - m)
        den = e.sum(-1, keepdims=True)
        if window:
            den = den + jnp.exp(sink - m)
        return jnp.einsum("bhqt,bthv->bqhv", e / den, v)

    out = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0),
                              QUERY_BLOCK * jnp.arange(n)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n * QUERY_BLOCK, H * vd)[:, :S]
    return out @ w["wo"]


def _swiglu(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ w_gate) * (y @ w_in)) @ w_out


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
    return g / (g.sum(-1, keepdims=True) + 1e-20), followed


def experts(y, w, c, follow=None, gap: float = 0.0):
    """The expert layer on (N, d): every HELD expert on every token,
    weighted by the router's weight for it (0 where it was not chosen); the
    chosen experts held elsewhere add nothing. ``w`` is the layer's tree as
    stored (the bank is widened an expert at a time)."""
    g, followed = router(y, _f32({k: w[k] for k in ("router", "router_bias")},
                                 matrices=False), c, follow, gap)
    held = w["w_gate"].shape[0]
    g = jax.lax.dynamic_slice_in_dim(g, c["first_held"], held, 1)

    def one(acc, ew):
        w_gate, w_in, w_out, ge = ew
        return acc + ge[:, None] * _swiglu(y, *_f32((w_gate, w_in, w_out))), \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          (w["w_gate"], w["w_in"], w["w_out"], g.T))
    return out, followed


def _layer(x, w, c, window: bool, follow=None, gap: float = 0.0):
    """One layer; (x, tokens that followed ``follow``)."""
    eps = c["layernorm_epsilon"]
    attn = _f32({k: w[k] for k in ("wq", "wk", "wv", "wo", "sink") if k in w})
    x = x + attention(_rmsnorm(x, _f32(w["ln1_scale"]), eps), attn, c, window)
    y = _rmsnorm(x, _f32(w["ln2_scale"]), eps)
    if "router" in w:
        B, S, d = y.shape
        out, followed = experts(
            y.reshape(B * S, d), w, c,
            None if follow is None else follow.reshape(B * S, -1), gap)
        return x + out.reshape(B, S, d), followed
    return x + _swiglu(y, *_f32((w["w_gate"], w["w_in"], w["w_out"]))), \
        jnp.zeros((), jnp.int32)


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
            or eps not in (None, c["layernorm_epsilon"]):
        raise ValueError("n_head / eps differ from the configured keys")
    x = _f32(params["tok_embed"])[input_ids]
    at, first, followed = 0, 0, jnp.zeros((), jnp.int32)
    layers = params["layers"]
    for seg in layers if isinstance(layers, (tuple, list)) else (layers,):
        n = jax.tree.leaves(seg)[0].shape[0]
        kinds = {(c["hybrid_layer_pattern"][i], c["moe_layer_freq"][i])
                 for i in range(at, at + n)}
        if len(kinds) != 1:
            raise ValueError(f"layers {at}..{at + n - 1} are stacked as one "
                             "run and are not of one kind")
        (window, moe), = kinds
        if bool(moe) != ("router" in seg) or bool(window) != ("sink" in seg):
            raise ValueError(f"layers {at}..{at + n - 1} do not hold what "
                             "their kind holds")
        routed = follow is not None and bool(moe)
        theirs = follow[first:first + n] if routed else None
        x, took = jax.lax.scan(
            lambda x, wf: _layer(x, wf[0], c, bool(window), wf[1], gap), x,
            (seg, theirs))
        followed = followed + took.sum()
        first += n if routed else 0
        at += n
    if at != c["num_hidden_layers"]:
        raise ValueError(f"{at} layers, not num_hidden_layers")
    x = _rmsnorm(x, _f32(params["lnf_scale"]), c["layernorm_epsilon"])
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

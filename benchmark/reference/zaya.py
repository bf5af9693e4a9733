"""ZAYA1 as published (``model_type: zaya``; Zyphra/ZAYA1-8B's
``config.json``), plainly: ``jax.numpy``, float32, every layer's attention the
full score matrix under its mask, every expert on every token, the two
convolutions explicit sums over shifted copies of the whole sequence, the
value shift a shifted copy of the layer's input, the router's state a
variable of the Python loop over the layers; no cache, no tail, no kernel,
and nothing of ``deepspeed_tpu``.

With ``h = RMS(x; g)`` the normed input of a sub-layer at position t,
``sh(a)_t = a_{t-1}`` (``sh(a)_0`` = 0), H = ``num_attention_heads``, KV =
``num_key_value_heads``, hd = ``head_dim``:

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    layer l:  x = res(x, attn_l(RMS(x; g1)); R_l[0])
              f, s_l = ffn_l(RMS(x; g2), s_{l-1});  x = res(x, f; R_l[1])
    res(x, f; a_res, b_res, a_out, b_out) = (a_res x + b_res) + (a_out f + b_out)
        a scale and a bias a channel on each side of each sub-layer (ZAYA1
        report, arXiv:2511.17127: residual scaling; its existence and its
        negligible cost are published, THE PER-CHANNEL FORM IS ASSUMED)
    attn (CCA, arXiv:2510.04476, the CCGQA variant; config: cca_time0 2,
          cca_time1 2, partial_rotary_factor 0.5, rope theta 5e6):
      1. q~ = h Wq (H hd), k~ = h Wk (KV hd);
         value shift: v = [h Wv1 ; sh(h) Wv2]: the first half of the KV
         heads sees this token, the second half the token before
      2. z = [q~ ; k~] (H + KV heads of hd):
         z1 = b0 + sum_{j < time0} a_j * sh^j(z)         (depthwise)
         z2 = b1 + sum_{j < time1} sh^j(z1) A_j^(head)   (grouped: one
              hd x hd matrix a head a tap)
      3. q-k mean, g(h) = h // (H / KV):
         q_h = z2_q[h] + (q~_h + k~_g(h)) / 2
         k_g = z2_k[g] + (mean_{h in g} q~_h + k~_g) / 2
      4. q <- sqrt(hd) q / |q|;  k <- tau_g sqrt(hd) k / |k|
      5. rope on the first hd / 2 dims of q and k (pairs i, i + hd / 4);
         p = softmax(causal(q k^T / sqrt(hd))), H / KV query heads a KV head;
         out = concat(heads)(p v) Wo                      (H hd -> d)
    ffn (ZAYA1 report: the router; config: num_experts 16,
         num_experts_per_tok 1, router_hidden_size 256):
         r = h Wd + bd;  s_l = r + gamma_l * s_{l-1}  (depth averaging: the
         state of the layer before at the SAME token, s_{-1} = 0)
         p = softmax(W3 gelu(W2 gelu(W1 RMS(s_l; g_r))))   (gelu: erf)
         e = argmax(p + b_bal);  f = p_e * W_out^e(silu(h W_gate^e) * h W_in^e)
    model:  logits = RMS(x_L; g_f) E^T                    (tied head)

It reads the repo model's parameter tree (one run of layers, stacked; the
attention's projections apart, ``wq`` / ``wk`` / ``wv`` = [Wv1 | Wv2], or as
the serving tree has them, one ``wqkv``), so that it can be fed the engine's
own seeded weights. The queries go in blocks of ``QUERY_BLOCK`` rows (each
against every key, under the mask), so that a prompt of a thousand tokens
fits; each layer is widened to float32 by itself, its experts one at a time,
the head's table in blocks of rows.

Departures from the published model: the "MoD" of the catalog's
``described_as`` is listed there beside the 74 B sibling; ``config`` has
``num_experts`` 16 and no key for a skip expert, so the router has 16 outputs
and every token runs one expert. The ``hybrid_sliding`` rope entry is unused
(``sliding_window`` null, no layer is of that type). Assumed (the
configuration file's ``assumed``; there is no network here): the rotation
pairs dim i with i + 32 of the first 64 (HF ``rotate_half``) with frequencies
over those 64; the convs carry a bias each (``nn.Conv1d``'s default); gelu is
the erf form; ``gamma`` is one scalar a layer; the per-channel residual
scaling above.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PUBLISHED: dict = {}
QUERY_BLOCK = 256
HEAD_ROWS = 1 << 17      # rows of the head's table widened at a time
# None, or a control's rounding of every matrix as it is widened (the
# router's apart, so that the choice of experts stays the comparison's own):
# benchmark/kinds/backlog_cca.py CONTROLS. Never set in a timed run.
ROUND = None


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    for key, only in (("model_type", "zaya"), ("hidden_act", "silu"),
                      ("attention_bias", False), ("lm_head_bias", False),
                      ("tie_word_embeddings", True), ("sliding_window", None),
                      ("num_experts_per_tok", 1)):
        if published.get(key, only) != only:
            raise ValueError(f"this reference has {key} = {only!r} only")
    if set(published["layer_types"]) != {"hybrid"} \
            or len(published["layer_types"]) != published["num_hidden_layers"]:
        raise ValueError("layer_types names num_hidden_layers hybrid layers")
    PUBLISHED.clear()
    PUBLISHED.update(published)


def _f32(tree, matrices: bool = True):
    def widen(a):
        a = jnp.asarray(a, jnp.float32)
        return ROUND(a) if ROUND and matrices and a.ndim >= 2 else a

    return jax.tree.map(widen, tree)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def shifted(a):
    """a (B, S, ...) one position later: row t holds a_{t-1}, row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(a[:, :1]), a[:, :-1]], axis=1)


# the value's one row back is a name of its own: a control can drop it alone
value_shifted = shifted


def unit(x):
    """sqrt(hd) x / |x| a head."""
    return math.sqrt(x.shape[-1]) * x / jnp.sqrt((x * x).sum(-1, keepdims=True))


def qk_mean(z2q, z2k, zq, zk):
    """What went into the convs added back, shared between the two sides:
    z2q, zq (B, S, KV, G, hd) a KV head's G query heads; z2k, zk (B, S, KV,
    hd)."""
    return (z2q + (zq + zk[:, :, :, None]) / 2,
            z2k + (zq.mean(3) + zk) / 2)


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


def attention(y, w, c):
    """One layer's CCA on y (B, S, d) post-norm -> (B, S, d); ``w``
    float32. The whole score matrix under the causal mask, a block of query
    rows at a time."""
    B, S, _ = y.shape
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    G = H // KV
    if "wqkv" in w:
        wq, wk, wv = jnp.split(w["wqkv"], [H * hd, (H + KV) * hd], axis=1)
    else:
        wq, wk, wv = w["wq"], w["wk"], w["wv"]
    half = KV // 2 * hd
    zq, zk = y @ wq, y @ wk
    v = jnp.concatenate([y @ wv[:, :half], value_shifted(y) @ wv[:, half:]],
                        -1)
    # the two convolutions over positions, on [q~ ; k~]
    z = jnp.concatenate([zq, zk], -1)
    taps0, taps1 = w["cca_w0"], w["cca_w1"]
    if (len(taps0), len(taps1)) != (c["cca_time0"], c["cca_time1"]):
        raise ValueError("the convs' taps are not cca_time0 / cca_time1")
    z1, back = w["cca_b0"], z
    for a in taps0:                               # a_j * z_{t-j}
        z1, back = z1 + a * back, shifted(back)
    z2, back = w["cca_b1"].reshape(H + KV, hd), z1.reshape(B, S, H + KV, hd)
    for A in taps1:                               # z1_{t-j} A_j, a head
        z2, back = z2 + jnp.einsum("bsgd,gde->bsge", back, A), shifted(back)
    q, k = qk_mean(z2[:, :, :H].reshape(B, S, KV, G, hd), z2[:, :, H:],
                   zq.reshape(B, S, KV, G, hd), zk.reshape(B, S, KV, hd))
    q = unit(q).reshape(B, S, H, hd)
    k = unit(k) * w["cca_temp"][:, None]
    rd = int(hd * c["rope_parameters"]["hybrid"]["partial_rotary_factor"])
    theta = float(c["rope_parameters"]["hybrid"]["rope_theta"])
    q, k = rope(q, theta, rd), rope(k, theta, rd)
    k = jnp.repeat(k, G, 2)
    v = jnp.repeat(v.reshape(B, S, KV, hd), G, 2)
    n = -(-S // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - S
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, n, QUERY_BLOCK, H, hd)
    j = jnp.arange(S)[None, :]

    def block(args):
        qi, first = args
        i = first + jnp.arange(QUERY_BLOCK)[:, None]
        s = jnp.einsum("bqhd,bthd->bhqt", qi, k) / math.sqrt(hd)
        # (a padded query row past S sees keys too: it is cut off below)
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        return jnp.einsum("bhqt,bthv->bqhv", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0),
                              QUERY_BLOCK * jnp.arange(n)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n * QUERY_BLOCK, H * hd)[:, :S]
    return out @ w["wo"]


def router(y, w, c, state, follow=None, gap: float = 0.0):
    """(N, d) tokens and the state (N, R) the layer before left -> ((N, E)
    combine weights, zero but for the ONE chosen expert's p; this layer's
    state; how many tokens followed ``follow``).

    ``follow`` (N, 1): another implementation's choice for these tokens.
    With random weights the first and second of ``p + b_bal`` can lie closer
    than that implementation's rounding, and it then takes the other expert:
    a different model from there on — with top-1 the token's whole FFN —
    not an error. A token whose own first and second lie within ``gap``
    takes ``follow``'s expert (weighted by this router's own p for it);
    every other token keeps its own choice, whatever ``follow`` says."""
    s = y @ w["router"] + w["router_bd"] + w["router_gamma"] * state
    t = _rmsnorm(s, w["router_norm"], c["rms_norm_eps"])
    gelu = lambda a: jax.nn.gelu(a, approximate=False)      # noqa: E731
    p = jax.nn.softmax(gelu(gelu(t @ w["router_w1"]) @ w["router_w2"])
                       @ w["router_w3"], -1)
    biased = p + w["router_bias"]
    E = biased.shape[-1]
    chosen = jax.nn.one_hot(biased.argmax(-1), E, dtype=bool)
    followed = jnp.zeros((), jnp.int32)
    if follow is not None:
        ranked = jnp.sort(biased, -1)
        near = (ranked[:, -1] - ranked[:, -2]) < gap
        theirs = jax.nn.one_hot(follow[:, 0], E, dtype=bool)
        followed = (near & (theirs != chosen).any(-1)).sum().astype(jnp.int32)
        chosen = jnp.where(near[:, None], theirs, chosen)
    return jnp.where(chosen, p, 0.0), s, followed


def _swiglu(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ w_gate) * (y @ w_in)) @ w_out


def experts(y, w, c, state, follow=None, gap: float = 0.0):
    """The expert sub-layer on (N, d): every expert on every token, weighted
    by the router's weight for it (0 where it was not chosen). ``w`` is the
    layer's tree as stored (the bank is widened an expert at a time).
    Returns (out, this layer's router state, tokens that followed)."""
    names = ("router", "router_bd", "router_gamma", "router_norm",
             "router_w1", "router_w2", "router_w3", "router_bias")
    g, state, followed = router(y, _f32({k: w[k] for k in names},
                                        matrices=False), c, state, follow, gap)

    def one(acc, ew):
        w_gate, w_in, w_out, ge = ew
        return acc + ge[:, None] * _swiglu(y, *_f32((w_gate, w_in, w_out))), \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          (w["w_gate"], w["w_in"], w["w_out"], g.T))
    return out, state, followed


def residual(x, f, scales):
    a_res, b_res, a_out, b_out = scales
    return (a_res * x + b_res) + (a_out * f + b_out)


def _layer(x, state, w, follow, c, gap: float):
    """One layer on (x, s); (x, s, tokens that followed ``follow``)."""
    eps = c["rms_norm_eps"]
    names = ("wq", "wk", "wv", "wqkv", "wo", "cca_w0", "cca_b0", "cca_w1",
             "cca_b1", "cca_temp")
    attn = _f32({k: w[k] for k in names if k in w})
    scales = _f32(w["res_scale"], matrices=False)
    x = residual(x, attention(_rmsnorm(x, _f32(w["ln1_scale"]), eps), attn,
                              c), scales[0])
    y = _rmsnorm(x, _f32(w["ln2_scale"]), eps)
    B, S, d = y.shape
    out, state, followed = experts(
        y.reshape(B * S, d), w, c, state.reshape(B * S, -1),
        None if follow is None else follow.reshape(B * S, -1), gap)
    return (residual(x, out.reshape(B, S, d), scales[1]),
            state.reshape(B, S, -1), followed)


def _head(x, table):
    """x (..., d) against the tied table (V, d), its rows widened a block at
    a time."""
    V = table.shape[0]
    n = next(n for n in range(1, V + 1)
             if V % n == 0 and V // n <= HEAD_ROWS)
    out = jax.lax.map(lambda rows: x @ _f32(rows).T,
                      table.reshape(n, V // n, -1))
    return jnp.moveaxis(out, 0, -2).reshape(x.shape[:-1] + (V,))


def logits(params, input_ids, n_head=None, eps=None, last_only: bool = False,
           rows=None, follow=None, gap: float = 0.0):
    """(B, S) token ids -> (B, S, V) float32 logits; (B, V) of the last
    position with ``last_only``, (B, len(rows), V) of the positions ``rows``.
    ``n_head`` and ``eps`` are what the shared serving kind hands every
    reference; they have to be the configured ones. With ``follow`` (layers,
    B, S, 1), another implementation's routing, the result is (logits,
    tokens x layers that followed it): see :func:`router`."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    if n_head not in (None, c["num_attention_heads"]) \
            or eps not in (None, c["rms_norm_eps"]):
        raise ValueError("n_head / eps differ from the configured keys")
    layers = params["layers"]
    L = jax.tree.leaves(layers)[0].shape[0]
    if L != c["num_hidden_layers"]:
        raise ValueError(f"{L} layers, not num_hidden_layers")
    # one layer is one program, whatever its index: 20 unrolled into one
    # would be compiled anew for every prompt length
    layer = jax.jit(lambda x, s, w, theirs: _layer(x, s, w, theirs, c, gap))
    x = jnp.asarray(params["tok_embed"][input_ids], jnp.float32)
    # s_{-1} = 0; the router's state is this loop's variable
    state = jnp.zeros(x.shape[:2] + (c["router_hidden_size"],), jnp.float32)
    followed = 0
    for l in range(L):
        x, state, took = layer(x, state, jax.tree.map(lambda a: a[l], layers),
                               None if follow is None else follow[l])
        followed = followed + took
    if last_only:
        x = x[:, -1]
    elif rows is not None:
        x = x[:, jnp.asarray(rows)]
    out = jax.jit(lambda x, g, table: _head(
        _rmsnorm(x, _f32(g), c["rms_norm_eps"]), table))(
            x, params["lnf_scale"], params["tok_embed"])
    return out if follow is None else (out, followed)


def run_highest(fn, *args, **static):
    """``fn`` run in true float32 (its programs are :func:`logits`' own: a
    layer, the head)."""
    with jax.default_matmul_precision("highest"):
        return fn(*args, **static)

"""Solar-Open2 (``model_type: solar_open2``; upstage/Solar-Open2-250B's
``config.json``, Kimi Linear arXiv:2510.26692 and its open kernels in
flash-linear-attention for the linear layers, Gated Attention
arXiv:2505.06708 for the softmax ones), plainly: ``jax.numpy``, float32, the
delta rule token by token, full causal softmax, every held expert on every
token; no cache, no kernel, no chunkwise form, and nothing of
``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g;  C = 4096, eps 1e-5
    layer l:  x += Mixer_l(RMS(x; g1));  x += MoE(RMS(x; g2));  one stream;
              Mixer_l is GQA where l in gqa_layers (l % 4 == 0), else KDA
    logits = RMS(x_L; g_f) W_head         (untied; NO position code anywhere)
    KDA:     [q | k | v] = silu(conv4(y W_qkv)) (causal, depthwise, no bias),
             64 heads of 128, q and k L2-normed a head;
             beta = 2 sigmoid(y W_beta)                (kda_allow_neg_eigval)
             g = -exp(A_log[h]) softplus(y W_f1 W_f2 + dt_bias)  a head a key
             channel, in (-inf, 0): no gate_lower_bound in the source
             S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                   + beta_t k_t v_t^T;   o_t = S_t^T q_t / sqrt(128);
             out = (RMS(o_t; g_o) * sigmoid(y W_g1 W_g2)) W_o
    GQA:     q = y W_q (64 x 128);  k = y W_k, v = y W_v (8 x 128), no bias,
             no rope, no q/k norm;  o_t = softmax_{s<=t}(q_t . k_s /
             sqrt(128)) v_s;  out = (o * sigmoid(y W_gate)) W_o, W_gate
             4096 x 8192: a value a head a channel off the layer's normed
             input
    MoE:     s = sigmoid(y W_r) (320 wide);  the top 8 of s + b;  weights
             s_i / sum s_i (x routed_scaling_factor 1);  SwiGLU experts of
             1280 beside ONE shared expert of 1280 added unweighted

It reads the repo model's parameter tree (``layers``: runs of layers equal in
(mixer, FFN kind), stacked) so that it can be fed the engine's own seeded
weights. **The chip's share**: the banks hold the experts this device holds
(``first_expert_held`` says which of the router's outputs the first is); a
chosen expert held elsewhere adds nothing here and its weight still counts
in the normalisation. The head holds the vocabulary's slice.

**So that some ten thousand tokens at the published widths fit beside the
weights**: one sequence at a time; attention's queries in blocks of
``QUERY_BLOCK`` a KV head's group of query heads at a time; the bank one
expert at a time, each matrix widened to float32 where it is used; the KDA
heads in groups of ``HEAD_GROUP`` through one scan over the tokens.

**Following** (``follow`` = routing (expert layers, B, S, k)): a token takes
the system's experts only where every expert in which the two sets differ
scores within ``gap`` of this reference's own 8th biased score
(:func:`router`).

Departures from the source, each the configuration file's ``assumed`` with
the reading it excludes, and each excluded reading that this file can express
a control (:data:`CONTROL`, ``benchmark/kinds/backlog_delta.py``): KDA's two
low-rank widths (128); the gate with no floor ("gate-floored": GLM-5.3's
``-5 sigmoid(exp(A_log) .)``); beta in (0, 2) ("beta-sigmoid"); the
attention's output gate elementwise off the normed input
("out-gate-dropped", "out-gate-per-head": one value a head, the first column
of the head's block); no rope ("rope-on-attention": ``rope_theta`` 10000 over
the whole head, halves); sigmoid scores with a selection bias
("softmax-router"). ``intermediate_size`` 10240 has no layer to be the width
of (``first_k_dense_replace`` 0) and is read by nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

PUBLISHED: dict = {}
QUERY_BLOCK = 256
HEAD_GROUP = 8
# None, or a control's rounding of every matrix as it is widened
ROUND = None
# the names of the deviations a control switches on: what a wrong system
# computes
CONTROL: set = set()
BANKS = ("w_gate", "w_in", "w_out")


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    p = published
    for key, only in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("first_k_dense_replace", 0), ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if p.get(key, only) != only:
            raise ValueError(f"this reference runs {key}={only!r}")
    PUBLISHED.clear()
    PUBLISHED.update(p, first_held=int(p.get("first_expert_held", 0)))


def _f32(tree, matrices: bool = True):
    def widen(a):
        a = jnp.asarray(a, jnp.float32)
        return ROUND(a) if ROUND and matrices and a.ndim >= 2 else a
    return jax.tree.map(widen, tree)


def _at(tree, i):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _blocks(fn, rows: int, *xs):
    """``fn`` over blocks of QUERY_BLOCK of the leading ``rows`` of every
    ``xs``, the results joined."""
    nb = -(-rows // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - rows

    def cut(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((nb, QUERY_BLOCK) + a.shape[1:])

    out = lax.map(lambda a: fn(*a), tuple(cut(a) for a in xs))
    return jax.tree.map(
        lambda a: a.reshape((nb * QUERY_BLOCK,) + a.shape[2:])[:rows], out)


# ------------------------------------------------------------------- KDA
def kda_gates(y, w, c):
    """(beta (S, H), g (S, H, D)) of one sequence's normed input."""
    lin = c["linear_attn_config"]
    H, D = lin["num_heads"], lin["head_dim"]
    S = y.shape[0]
    beta = jax.nn.sigmoid(y @ _f32(w["kda_wbeta"]))
    if "beta-sigmoid" not in CONTROL:
        beta = 2.0 * beta
    f = ((y @ _f32(w["kda_wf1"])) @ _f32(w["kda_wf2"])
         + jnp.asarray(w["kda_dt_bias"], jnp.float32)).reshape(S, H, D)
    A = jnp.exp(jnp.asarray(w["kda_A_log"], jnp.float32))[:, None]
    if "gate-floored" in CONTROL:
        return beta, -5.0 * jax.nn.sigmoid(A * f)
    return beta, -A * jax.nn.softplus(f)


def kda(y, w, c, S0=None):
    """One sequence's KDA branch by the plain recurrence: ``y`` (S, d) the
    layer's normed input. ``S0`` (H, D, D): the state to start from (tests;
    the conv then still starts from zeros). Returns (out (S, d), S_T)."""
    S, d = y.shape
    lin = c["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    u = y @ _f32(w["kda_wqkv"])
    taps = jnp.asarray(w["kda_conv_w"], jnp.float32)            # (3 H D, K)
    seq = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(seq[j:j + S] * taps[:, j] for j in range(K)))
    q, k, v = (a.reshape(S, H, D) for a in jnp.split(u, 3, axis=-1))

    def l2(a):
        return a * lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q, k = l2(q), l2(k)
    beta, g = kda_gates(y, w, c)
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    S0 = jnp.zeros((H, D, D), jnp.float32) if S0 is None else S0

    def group(args):
        q, k, v, g, beta, St = args                 # (S, G, D) ..., (S, G)

        def token(St, t):
            q, k, v, g, beta = t
            Sd = jnp.exp(g)[..., None] * St
            r = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, Sd))
            St = Sd + k[..., None] * r[:, None, :]
            return St, jnp.einsum("hk,hkv->hv", q, St) / math.sqrt(D)

        return lax.scan(token, St, (q, k, v, g, beta))

    def cut(a):
        return jnp.moveaxis(a.reshape((S, H // G, G) + a.shape[2:]), 1, 0)

    ST, o = lax.map(group, tuple(cut(a) for a in (q, k, v, g, beta))
                    + (S0.reshape(H // G, G, D, D),))
    o = jnp.moveaxis(o, 0, 1).reshape(S, H, D)
    o = _rmsnorm(o, jnp.asarray(w["kda_norm_scale"], jnp.float32),
                 c["rms_norm_eps"])
    z = (y @ _f32(w["kda_wg1"])) @ _f32(w["kda_wg2"])
    out = (o * jax.nn.sigmoid(z.reshape(S, H, D))).reshape(S, H * D) \
        @ _f32(w["wo"])
    return out, ST.reshape(H, D, D)


# ------------------------------------------------------------- gated GQA
def _rope(a, theta: float):
    """(S, heads, hd) at positions 0..S-1: rotate_half over the whole head."""
    S, _, hd = a.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1)[:, None]
                for f in (jnp.cos, jnp.sin))
    rot = jnp.concatenate([-a[..., hd // 2:], a[..., :hd // 2]], -1)
    return a * cos + rot * sin


def attention(y, w, c):
    """One sequence's attention branch: ``y`` (S, d) the normed input."""
    S, d = y.shape
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    G = H // KV
    q = (y @ _f32(w["wq"])).reshape(S, KV, G, hd)
    k = (y @ _f32(w["wk"])).reshape(S, KV, hd)
    v = (y @ _f32(w["wv"])).reshape(S, KV, hd)
    if "rope-on-attention" in CONTROL:
        theta = float(c["rope_theta"])
        q = _rope(q.reshape(S, H, hd), theta).reshape(S, KV, G, hd)
        k = _rope(k, theta)
    pos = jnp.arange(S, dtype=jnp.int32)

    def head(args):
        q, k, v = args                       # (S, G, hd), (S, hd), (S, hd)

        def rows(q, t):
            s = jnp.einsum("qgd,sd->gqs", q, k) / math.sqrt(hd)
            s = jnp.where((pos[None] <= t[:, None])[None], s, -jnp.inf)
            return jnp.einsum("gqs,sd->qgd", jax.nn.softmax(s, -1), v)

        return _blocks(rows, S, q, pos)

    o = lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                       jnp.moveaxis(v, 1, 0)))       # (KV, S, G, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(S, H, hd)
    if "out-gate-dropped" not in CONTROL:
        gate = jax.nn.sigmoid(y @ _f32(w["w_ogate"])).reshape(S, H, hd)
        if "out-gate-per-head" in CONTROL:
            gate = gate[..., :1]
        o = o * gate
    return o.reshape(S, H * hd) @ _f32(w["wo"])


# --------------------------------------------------------------- experts
def _swiglu(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ w_gate) * (y @ w_in)) @ w_out


def router(y, w, c, follow=None, gap: float = 0.0):
    """(N, d) tokens -> ((N, E) combine weights over ALL experts, zero but
    for the chosen; (how many tokens followed ``follow`` (N, k), the largest
    distance from this router's own threshold — its 8th biased score — of an
    expert in which the two sets differ: what ``gap`` has to excuse of a
    sound system's rounding, whatever ``gap`` is)).

    A token takes ``follow``'s experts only where EVERY expert in which the
    two sets differ scores, by this router, within ``gap`` of its own
    threshold (``reference/glm5_next.py``'s rule for a selection): two bf16
    programs that round a router's input differently swap experts that
    stand at the threshold, and only those; an expert chosen from further
    down is a wrong choice and is not followed."""
    logit = y @ w["router"]
    score = jax.nn.softmax(logit, -1) if "softmax-router" in CONTROL \
        else jax.nn.sigmoid(logit)
    biased = score + w["router_bias"]
    k = c["num_experts_per_tok"]
    ranked = jnp.sort(biased, -1)
    chosen = biased >= ranked[:, -k][:, None]
    followed, far = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
    if follow is not None:
        theirs = jax.nn.one_hot(follow, biased.shape[-1], dtype=bool).any(1)
        # how far from this router's own threshold (its 8th biased score)
        # the experts lie in which the two sets differ
        dist = jnp.where(theirs != chosen,
                         jnp.abs(biased - ranked[:, -k][:, None]), 0.0).max(-1)
        near = dist <= gap
        followed = (near & (dist > 0)).sum().astype(jnp.int32)
        far = dist.max()
        chosen = jnp.where(near[:, None], theirs, chosen)
    g = jnp.where(chosen, score, 0.0)
    if c["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return g * c["routed_scaling_factor"], (followed, far)


def experts(y, w, c, follow=None, gap: float = 0.0, banks=None,
            shared: bool = True):
    """The expert layer on (N, d): every HELD expert on every token,
    weighted by the router's weight for it; the chosen experts held
    elsewhere add nothing; the shared expert once (``shared``). ``banks`` =
    (the run's stacked banks ``(layers, held, ., .)``, this layer's index),
    or None: the layer's own ``(held, ., .)``."""
    g, followed = router(y, _f32({k: w[k] for k in ("router", "router_bias")},
                                 matrices=False), c, follow, gap)
    stacked, layer = banks if banks is not None else (
        {k: w[k][None] for k in BANKS}, 0)
    held = stacked["w_gate"].shape[1]
    g = lax.dynamic_slice_in_dim(g, c.get("first_held", 0), held, 1)

    def one(acc, e):
        ws = tuple(lax.dynamic_slice(
            stacked[k], (layer, e, 0, 0), (1, 1) + stacked[k].shape[2:])[0, 0]
            for k in BANKS)
        ge = lax.dynamic_index_in_dim(g, e, 1, keepdims=False)
        return acc + ge[:, None] * _swiglu(y, *_f32(ws)), None

    out, _ = lax.scan(one, jnp.zeros_like(y),
                      jnp.arange(held, dtype=jnp.int32))
    if shared:
        out = out + _swiglu(y, *_f32((w["ws_gate"], w["ws_in"],
                                      w["ws_out"])))
    return out, followed


# ----------------------------------------------------------------- model
def _sequence(params, ids, c, routing, gap):
    """One sequence (S,) -> (the stream (S, d), (tokens x layers that
    followed ``routing``, the largest distance from the threshold of an expert the two chose otherwise))."""
    x = _f32(params["tok_embed"][ids])
    layers = params["layers"]
    segs = layers if isinstance(layers, (tuple, list)) else (layers,)
    eps = c["rms_norm_eps"]
    layer = routed = 0
    followed, far = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
    for seg in segs:
        count = jax.tree.leaves(seg)[0].shape[0]
        linear = "kda_wqkv" in seg
        banks = {k: seg[k] for k in BANKS}
        rest = {k: v for k, v in seg.items() if k not in BANKS}
        for i in range(count):
            if linear == (layer in c["gqa_layers"]):
                raise ValueError(f"layer {layer} does not hold what "
                                 "gqa_layers says it holds")
            w = _at(rest, i)
            y = _rmsnorm(x, _f32(w["ln1_scale"]), eps)
            x = x + (kda(y, w, c)[0] if linear else attention(y, w, c))
            y = _rmsnorm(x, _f32(w["ln2_scale"]), eps)
            out, took = experts(
                y, w, c, routing[routed] if routing is not None else None,
                gap, (banks, i))
            x = x + out
            followed, far = followed + took[0], jnp.maximum(far, took[1])
            routed += 1
            layer += 1
    if layer != c["num_hidden_layers"]:
        raise ValueError(f"{layer} layers, not num_hidden_layers")
    return x, (followed, far)


def head(x, w):
    """``x @ w`` with the head's slice widened a block of columns at a time."""
    d, V = w.shape
    nb = next(n for n in (16, 12, 8, 6, 4, 3, 2, 1) if V % n == 0)
    cols = w.reshape(d, nb, V // nb).transpose(1, 0, 2)
    out = lax.map(lambda c: x @ _f32(c), cols)
    return jnp.moveaxis(out, 0, -2).reshape(x.shape[:-1] + (V,))


def logits(params, input_ids, n_head=None, eps=None, last_only: bool = False,
           rows=None, follow=None, gap: float = 0.0):
    """(B, S) token ids -> (B, S, V) float32 logits; (B, V) of the last
    position with ``last_only``, (B, len(rows), V) of the positions ``rows``.
    With ``follow`` (expert layers, B, S, k), another implementation's
    routing, the result is (logits, (tokens x layers that followed it, the
    largest distance from this reference's threshold of an expert in which
    the two sets differ: :func:`router`))."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    if n_head not in (None, 0, c["num_attention_heads"]) \
            or eps not in (None, 0.0, c["rms_norm_eps"]):
        raise ValueError("n_head / eps differ from the configured keys")
    outs, took, far = [], jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
    for b in range(input_ids.shape[0]):
        x, (n, f) = _sequence(params, input_ids[b], c,
                              None if follow is None else follow[:, b], gap)
        x = _rmsnorm(x, _f32(params["lnf_scale"]), c["rms_norm_eps"])
        if last_only:
            x = x[-1]
        elif rows is not None:
            x = x[jnp.asarray(rows)]
        outs.append(head(x, params["lm_head"]))
        took, far = took + n, jnp.maximum(far, f)
    out = jnp.stack(outs)
    return out if follow is None else (out, (took, far))


def run_highest(fn, *args, **static):
    """``fn`` jitted and run in true float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **static))(*args)

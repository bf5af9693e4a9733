"""GLM-5.3-Flash (``model_type: glm5_next_text``; zai-org/GLM-5.3-Flash's
``config.json``, Kimi Linear arXiv:2510.26692 for the linear layers, mHC
arXiv:2512.24880 for the residual, DeepSeek-V3.2 arXiv:2512.02556 for the
sparse ones), plainly: ``jax.numpy``, float32, the delta rule token by token,
full causal scores with the selection as a mask, the pooled keys recomputed
from every position's key, every held expert on every token; no cache, no
kernel, no chunkwise form, no absorbed projection, and nothing of
``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g;  LN = LayerNorm with a bias
    stream:  X_0 = the embedding repeated n = 4 times;  logits = RMS(sum_j
             X_L[j]; g_f) W_head
    sub-layer f (a mixer, an FFN; two a layer), its own phi, b, a (float32):
             u = RMS(vec(X); 1) phi;  H_pre = sigmoid(a0 u[:n] + b[:n]);
             H_post = 2 sigmoid(a1 u[n:2n] + b[n:2n]);
             M_0 = exp(a2 mat(u[2n:]) + b[2n:]);  M_t = rows(cols(M_{t-1})),
             t = 1..20, sums + 1e-6;  H_res = M_20;
             X' = H_res X + H_post^T (x) f(RMS(H_pre X; g))
    KDA:     [q | k | v] = silu(conv4(y W_qkv)) (causal, depthwise, no bias),
             q and k L2-normed a head;  beta = sigmoid(y W_beta);
             g = -5 sigmoid(exp(A_log[h]) (y W_f1 W_f2 + dt_bias));
             S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                   + beta_t k_t v_t^T;   o_t = S_t^T q_t / sqrt(128);
             out = (RMS(o_t; g_o) * sigmoid(y W_g1 W_g2)) W_o
    attn:    cq = RMS(y W_qa);  q = cq W_qb (64 x 256);  c = RMS(y W_kva)
             (512);  [k_h | v_h] = c W_kvb;  softmax_{s in S_t}(q_h . k_h[s]
             / 16) v_h[s] into W_o.  No rope anywhere.
    indexer: qI = cq W_Iq (32 x 128);  kI = LN(y W_Ik) (128);  w = y W_Iw x
             32^-1/2 x 128^-1/2;  group g = positions 4g .. 4g + 3, its key
             the MEAN of its four kI;  query t scores the closed groups g <
             floor(t / 4): I[t, g] = sum_j w[t, j] ReLU(qI[t, j] . key_g);
             S_t = the positions of the 512 best groups (all if fewer; of
             equal scores the lowest) and of the open group floor(t / 4) up
             to t
    ffn:     silu(min(gate, 10)) * clip(up, -10, 10), dense 12 288 | sigmoid
             router over 288, top 8 of score + bias, normalised x 2.5, beside
             one shared expert of 2048

It reads the repo model's parameter tree (``layers``: runs of layers equal in
(mixer, FFN kind), stacked; ``indexer``: the attention layers' indexers,
stacked) so that it can be fed the engine's own seeded weights. **The chip's
share**: the banks hold the experts this device holds (``first_expert_held``
says which of the router's outputs the first is); a chosen expert held
elsewhere adds nothing here and its weight still counts in the
normalisation. The head holds the vocabulary's slice.

**So that 2 k tokens at the published widths fit beside the weights**: one
sequence at a time; attention's queries in blocks of ``QUERY_BLOCK`` and
heads in groups of ``HEAD_GROUP``; the dense FFN in column blocks and the bank
one expert at a time, each widened to float32 where it is used; the KDA heads
in groups of ``HEAD_GROUP`` through one scan over the tokens.

**Following** (``follow=(routing, selection)``) as
``reference/glm_moe_dsa.py``: a token takes the system's experts only where
its own 8th and 9th biased scores lie within ``gap``; a query takes the
system's selected positions only where every GROUP in which the two sets
differ scores within ``select_gap`` of this reference's own 512th group.

Departures from the sources (the configuration file's ``assumed``): KDA's two
low-rank widths (128), the bounded gate read from ``gate_lower_bound``, the
pooled keys read from the three ``index_kpool*`` keys, the clamp read from
``swiglu_limit``, the streams' entry and exit (Hyper-Connections §3); the
multi-token-prediction layer and the vision tower are not held.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

PUBLISHED: dict = {}
QUERY_BLOCK = 256
HEAD_GROUP = 8
FFN_BLOCK = 2048
# None, or a control's rounding of every matrix as it is widened
ROUND = None
# the names of the deviations a control switches on
# (benchmark/kinds/backlog_linear.py CONTROLS): what a wrong system computes
CONTROL: set = set()
BANKS = ("w_gate", "w_in", "w_out")


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    p = published
    for key, only in (("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("mhc", True), ("mla_use_nope", True),
                      ("qk_rope_head_dim", 0), ("index_kpool_compress", True),
                      ("index_kpool_always_select_tail", True)):
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


def _layernorm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


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


# ------------------------------------------------------------------- mHC
def hc_maps(X, w, side: int, c):
    """One sequence's maps of sub-layer ``side``: X (S, n, C) -> (H_pre (S,
    n), H_post (S, n), H_res (S, n, n))."""
    S, n, C = X.shape
    v = X.reshape(S, n * C)
    v = v * lax.rsqrt((v * v).mean(-1, keepdims=True) + c["rms_norm_eps"])
    u = v @ jnp.asarray(w["mhc_phi"][side], jnp.float32)
    a = jnp.asarray(w["mhc_a"][side], jnp.float32)
    b = jnp.asarray(w["mhc_b"][side], jnp.float32)
    pre = jax.nn.sigmoid(a[0] * u[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * u[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(a[2] * u[:, 2 * n:] + b[2 * n:]).reshape(S, n, n)
    for _ in range(1 if "sinkhorn-once" in CONTROL
                   else c["hc_sinkhorn_iters"]):
        m = m / (m.sum(-2, keepdims=True) + c["hc_eps"])
        m = m / (m.sum(-1, keepdims=True) + c["hc_eps"])
    return pre, post, m


def hc_sublayer(X, w, side: int, c, f):
    """``X' = H_res X + H_post^T (x) f(H_pre X)``; ``f`` norms its input
    itself and may hand back a second result."""
    pre, post, res = hc_maps(X, w, side, c)
    out = f(jnp.einsum("sn,snc->sc", pre, X))
    out, extra = out if isinstance(out, tuple) else (out, None)
    return jnp.einsum("snm,smc->snc", res, X) \
        + post[:, :, None] * out[:, None, :], extra


# ------------------------------------------------------------------- KDA
def kda(x, w, c):
    """One sequence's KDA branch: ``x`` (S, d) what the sub-layer reads."""
    S, d = x.shape
    lin = c["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    floor = float(lin["gate_lower_bound"])
    y = _rmsnorm(x, _f32(w["ln1_scale"]), c["rms_norm_eps"])
    u = y @ _f32(w["kda_wqkv"])
    taps = jnp.asarray(w["kda_conv_w"], jnp.float32)            # (3 H D, K)
    seq = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(seq[j:j + S] * taps[:, j] for j in range(K)))
    q, k, v = (a.reshape(S, H, D) for a in jnp.split(u, 3, axis=-1))

    def l2(a):
        return a * lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q, k = l2(q), l2(k)
    beta = jax.nn.sigmoid(y @ _f32(w["kda_wbeta"]))              # (S, H)
    f = (y @ _f32(w["kda_wf1"])) @ _f32(w["kda_wf2"]) \
        + jnp.asarray(w["kda_dt_bias"], jnp.float32)
    g = floor * jax.nn.sigmoid(
        jnp.exp(jnp.asarray(w["kda_A_log"], jnp.float32))[:, None]
        * f.reshape(S, H, D))
    if "gate-unbounded" in CONTROL:
        g = -jnp.exp(jnp.asarray(w["kda_A_log"], jnp.float32))[:, None] \
            * jax.nn.softplus(f.reshape(S, H, D))
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H

    def group(args):
        q, k, v, g, beta = args                     # (S, G, D) ..., (S, G)

        def token(St, t):
            q, k, v, g, beta = t
            Sd = jnp.exp(g)[..., None] * St
            r = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, Sd))
            St = Sd + k[..., None] * r[:, None, :]
            if "state-bf16" in CONTROL:
                St = St.astype(jnp.bfloat16).astype(jnp.float32)
            return St, jnp.einsum("hk,hkv->hv", q, St) / math.sqrt(D)

        _, o = lax.scan(token, jnp.zeros((G, D, D), jnp.float32),
                        (q, k, v, g, beta))
        return o

    def cut(a):
        return jnp.moveaxis(a.reshape((S, H // G, G) + a.shape[2:]), 1, 0)

    o = lax.map(group, tuple(cut(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(S, H, D)
    o = _rmsnorm(o, jnp.asarray(w["kda_norm_scale"], jnp.float32),
                 c["rms_norm_eps"])
    z = (y @ _f32(w["kda_wg1"])) @ _f32(w["kda_wg2"])
    return (o * jax.nn.sigmoid(z.reshape(S, H, D))).reshape(S, H * D) \
        @ _f32(w["wo"])


# ------------------------------------------------------- sparse attention
def top_mask(score, k: int):
    """(rows, G) scores (-inf where a group is no candidate) -> (the mask of
    each row's ``k`` largest, of equal scores the lowest; the k-th largest
    (rows, 1), -inf where a row has fewer candidates)."""
    S = score.shape[-1]
    if k >= S:
        return score > -jnp.inf, jnp.full(score.shape[:-1] + (1,), -jnp.inf)
    thr = jnp.sort(score, -1)[..., S - k][..., None]
    above = score > thr
    tied = (score == thr) & (score > -jnp.inf)
    room = k - above.sum(-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, -1) <= room)), thr


def select(y, cq, ip, c, theirs=None, select_gap: float = 0.0):
    """An attention layer's selection for one sequence: ``y`` (S, d) the
    normed input, ``cq`` (S, q_lora_rank), ``ip`` its indexer. Returns (mask
    (S, S) bool over positions, causal included; query rows that followed
    ``theirs`` (S, K') i32 positions (-1: none); the largest distance from
    the threshold of a group the two sets differ in)."""
    S = y.shape[0]
    H, D, pool = c["index_n_heads"], c["index_head_dim"], c["index_kpool"]
    K = c["index_topk"] // pool
    ip = _f32(ip)
    q = (cq @ ip["wq_b"]).reshape(S, H, D)
    k = _layernorm(y @ ip["wk"], ip["k_norm_scale"], ip["k_norm_bias"],
                   c["rms_norm_eps"])
    Gn = -(-S // pool)
    kp = jnp.pad(k, ((0, Gn * pool - S), (0, 0))).reshape(Gn, pool, D)
    keys = kp.max(1) if "max-for-mean" in CONTROL else kp.mean(1)
    w = (y @ ip["weights_proj"]) / math.sqrt(H * D)
    pos = jnp.arange(S, dtype=jnp.int32)
    grp = jnp.arange(Gn, dtype=jnp.int32)
    theirs = jnp.full((S, 1), -1, jnp.int32) if theirs is None else theirs

    def rows(q, w, t, theirs):
        s = jnp.maximum(jnp.einsum("qhd,gd->qhg", q, keys), 0.0)
        score = jnp.einsum("qh,qhg->qg", w, s)
        own_group = (t // pool)[:, None]
        score = jnp.where(grp[None] < own_group, score, -jnp.inf)
        own, thr = top_mask(score, K)
        # their positions as groups (the open group apart: always read)
        given = (theirs >= 0).any(-1)
        tg = jnp.where(theirs >= 0, theirs // pool, Gn)
        sys = jnp.zeros((own.shape[0], Gn + 1), bool).at[
            jnp.arange(own.shape[0])[:, None], tg].set(True)[:, :Gn]
        sys = sys & (grp[None] < own_group)
        differ = (sys != own) & given[:, None]
        far = jnp.where(differ, jnp.nan_to_num(
            jnp.abs(score - thr), nan=jnp.inf, posinf=jnp.inf), 0.0).max(-1)
        follow = given & (far <= select_gap)
        groups = jnp.where(follow[:, None], sys, own)
        unread = "open-group-unread" in CONTROL
        if not unread:
            groups = groups | (grp[None] == own_group)
        mask = jnp.repeat(groups, pool, axis=-1)[:, :S] \
            & (pos[None] <= t[:, None])
        if unread:          # (a query still has to see itself)
            mask = mask | (pos[None] == t[:, None])
        return mask, follow & differ.any(-1), far

    mask, took, far = _blocks(rows, S, q, w, pos, theirs)
    return mask, took.sum().astype(jnp.int32), far.max()


def attention(x, w, ip, c, theirs, select_gap):
    """One sequence's attention branch: ``x`` (S, d) what the sub-layer
    reads. Returns (out (S, d), (rows that followed, the distance))."""
    S, d = x.shape
    H, nope, vd, r = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["v_head_dim"], c["kv_lora_rank"])
    eps = c["rms_norm_eps"]
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    y = _rmsnorm(x, _f32(w["ln1_scale"]), eps)
    cq = _rmsnorm(y @ _f32(w["wq_a"]), _f32(w["q_norm_scale"]), eps)
    mask, took, far = select(y, cq, ip, c, theirs, select_gap)
    lat = _rmsnorm(y @ _f32(w["wkv_a"]), _f32(w["kv_norm_scale"]), eps)

    def per_group(a, cols):
        return a.reshape(a.shape[0], H // G, G * cols).transpose(1, 0, 2)

    groups = (per_group(w["wq_b"], nope), per_group(w["wkv_b"], nope + vd),
              w["wo"].reshape(H // G, G * vd, d))

    def one(out, ws):
        wq, wkv, wo = _f32(ws)
        q = (cq @ wq).reshape(S, G, nope)
        kv = (lat @ wkv).reshape(S, G, nope + vd)

        def rows(q, m):
            s = jnp.einsum("qgn,sgn->gqs", q, kv[..., :nope]) \
                / math.sqrt(nope)
            s = jnp.where(m[None], s, -jnp.inf)
            p = jax.nn.softmax(jnp.where(m.any(-1)[None, :, None], s, 0.0),
                               -1)
            return jnp.einsum("gqs,sgv->qgv", p, kv[..., nope:])

        o = _blocks(rows, S, q, mask)
        return out + o.reshape(S, G * vd) @ wo, None

    out, _ = lax.scan(one, jnp.zeros_like(x), groups)
    return out, (took, far)


# ------------------------------------------------------------------ FFNs
def _swiglu(y, w_gate, w_in, w_out, limit):
    gate, up = y @ w_gate, y @ w_in
    if limit and "clamp-dropped" not in CONTROL:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return (jax.nn.silu(gate) * up) @ w_out


def dense_ffn(y, w, limit):
    """SwiGLU in column blocks of FFN_BLOCK: the hidden units add up."""
    d, f = w["w_in"].shape
    nb = max(1, f // FFN_BLOCK)
    cols = (w["w_gate"].reshape(d, nb, f // nb).transpose(1, 0, 2),
            w["w_in"].reshape(d, nb, f // nb).transpose(1, 0, 2),
            w["w_out"].reshape(nb, f // nb, d))
    out, _ = lax.scan(
        lambda acc, ws: (acc + _swiglu(y, *_f32(ws), limit), None),
        jnp.zeros_like(y), cols)
    return out


def router(y, w, c, follow=None, gap: float = 0.0):
    """(N, d) tokens -> ((N, E) combine weights over ALL experts, zero but
    for the chosen; how many tokens followed ``follow`` (N, k))."""
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
    if c["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return g * c["routed_scaling_factor"], followed


def experts(y, w, c, follow=None, gap: float = 0.0, banks=None,
            shared: bool = True):
    """The expert layer on (N, d): every HELD expert on every token,
    weighted by the router's weight for it; the chosen experts held
    elsewhere add nothing; the shared expert once (``shared``). ``banks`` =
    (the run's stacked banks ``(layers, held, ., .)``, this layer's index),
    or None: the layer's own ``(held, ., .)``."""
    limit = float(c.get("swiglu_limit", 0))
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
        return acc + ge[:, None] * _swiglu(y, *_f32(ws), limit), None

    out, _ = lax.scan(one, jnp.zeros_like(y),
                      jnp.arange(held, dtype=jnp.int32))
    if shared:
        out = out + _swiglu(y, *_f32((w["ws_gate"], w["ws_in"],
                                      w["ws_out"])), limit)
    return out, followed


# ----------------------------------------------------------------- model
def _sequence(params, ids, c, follow, gaps):
    """One sequence (S,) -> (the streams' sum (S, d), what followed)."""
    n = c["hc_mult"]
    x = _f32(params["tok_embed"][ids])
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    layers = params["layers"]
    segs = layers if isinstance(layers, (tuple, list)) else (layers,)
    routing, picks = follow if follow is not None else (None, None)
    gap, select_gap = gaps
    limit = float(c.get("swiglu_limit", 0))
    layer = full = routed = 0
    totals = [jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
              jnp.zeros((), jnp.float32)]
    for seg in segs:
        count = jax.tree.leaves(seg)[0].shape[0]
        moe, linear = "router" in seg, "kda_wqkv" in seg
        banks = {k: seg[k] for k in BANKS} if moe else None
        rest = {k: v for k, v in seg.items() if not (moe and k in BANKS)}
        for i in range(count):
            if moe != (c["mlp_layer_types"][layer] == "sparse") or linear != (
                    c["layer_types"][layer] == "linear_attention"):
                raise ValueError(f"layer {layer} does not hold what its "
                                 "kinds hold")
            w = _at(rest, i)
            if linear:
                X, _ = hc_sublayer(X, w, 0, c, lambda x, w=w: kda(x, w, c))
            else:
                ip = _at(params["indexer"], full)
                theirs = picks[full] if picks is not None else None
                X, (took, far) = hc_sublayer(
                    X, w, 0, c, lambda x, w=w, ip=ip, theirs=theirs:
                    attention(x, w, ip, c, theirs, select_gap))
                totals[1] = totals[1] + took
                totals[2] = jnp.maximum(totals[2], far)
                full += 1

            def ffn(x, w=w, i=i):
                y = _rmsnorm(x, _f32(w["ln2_scale"]), c["rms_norm_eps"])
                if moe:
                    return experts(
                        y, w, c, routing[routed] if routing is not None
                        else None, gap, (banks, i))
                return dense_ffn(y, w, limit), jnp.zeros((), jnp.int32)

            X, followed = hc_sublayer(X, w, 1, c, ffn)
            totals[0] = totals[0] + followed
            routed += moe
            layer += 1
    if layer != c["num_hidden_layers"]:
        raise ValueError(f"{layer} layers, not num_hidden_layers")
    return X.sum(1), tuple(totals)


def head(x, w):
    """``x @ w`` with the head's slice widened a block of columns at a time."""
    d, V = w.shape
    nb = next(n for n in (16, 11, 10, 8, 5, 4, 2, 1) if V % n == 0)
    cols = w.reshape(d, nb, V // nb).transpose(1, 0, 2)
    out = lax.map(lambda c: x @ _f32(c), cols)
    return jnp.moveaxis(out, 0, -2).reshape(x.shape[:-1] + (V,))


def logits(params, input_ids, n_head=None, eps=None, last_only: bool = False,
           rows=None, follow=None, gap: float = 0.0,
           select_gap: float = 0.0):
    """(B, S) token ids -> (B, S, V) float32 logits; (B, V) of the last
    position with ``last_only``, (B, len(rows), V) of the positions ``rows``.
    With ``follow`` = (routing (expert layers, B, S, k), selection
    (attention layers, B, S, K') positions with -1 where a query has fewer),
    another implementation's choices, the result is (logits, (tokens x
    layers that followed its routing, query rows x layers that followed its
    selection, the largest distance from the threshold of a group in which
    the two selections differ)): see the top of this file."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    if n_head not in (None, 0, c["num_attention_heads"]) \
            or eps not in (None, 0.0, c["rms_norm_eps"]):
        raise ValueError("n_head / eps differ from the configured keys")
    outs, notes = [], []
    for b in range(input_ids.shape[0]):
        theirs = None if follow is None else tuple(a[:, b] for a in follow)
        x, took = _sequence(params, input_ids[b], c, theirs,
                            (gap, select_gap))
        x = _rmsnorm(x, _f32(params["lnf_scale"]), c["rms_norm_eps"])
        if last_only:
            x = x[-1]
        elif rows is not None:
            x = x[jnp.asarray(rows)]
        outs.append(head(x, params["lm_head"]))
        notes.append(took)
    out = jnp.stack(outs)
    if follow is None:
        return out
    return out, (sum(t[0] for t in notes), sum(t[1] for t in notes),
                 jnp.stack([t[2] for t in notes]).max())


def run_highest(fn, *args, **static):
    """``fn`` jitted and run in true float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **static))(*args)

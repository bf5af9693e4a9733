"""Ling-3.0-flash (``model_type: bailing_hybrid``; inclusionAI/Ling-3.0-flash's
``config.json``, Kimi Linear arXiv:2510.26692 and its open kernels in
flash-linear-attention for the linear layers, DeepSeek-V3 for the latent
attention and the grouped router, Gated Attention arXiv:2505.06708 for the
gate), plainly: ``jax.numpy``, float32, the delta rule token by token,
expanded (not absorbed) latent attention with a full causal softmax, the
group rule written out, every held expert on every token; no cache, no
kernel, no chunkwise form, and nothing of ``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g;  C = 2560, eps 1e-6
    layer l:  x += Mixer_l(RMS(x; g1));  x += FFN_l(RMS(x; g2));  one stream;
              Mixer_l is MLA where (l + 1) % layer_group_size == 0, else KDA
    logits = RMS(x_L; g_f) W_head                              (untied)
    KDA:     [q | k | v] = silu(conv4(y W_qkv)) (causal, depthwise, no bias),
             32 heads of 128;  q = L2(a_q * q), k = L2(a_k * k) with a learned
             gain a channel (use_qk_norm);  beta = sigmoid(y W_beta)
             g = -5 sigmoid(exp(A_log[h]) (y W_f + dt_bias))   a head a key
             channel (kda_safe_gate, kda_lower_bound -5; W_f a FULL 2560 x
             4096 map: no_kda_lora)
             S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                   + beta_t k_t v_t^T;   o_t = S_t^T q_t / sqrt(128);
             out = (RMS(o_t; g_o) * sigmoid(y W_g)) W_o,  W_g full too
    MLA:     q = y W_q (32 x (128 nope | 64 rope));  [c | k_r] = y W_kva (512
             | 64);  c = RMS(c; g_kv);  rope (theta 6e6, interleaved pairs) on
             q's rope part and on k_r, one k_r for all heads;  [k_nope | v] =
             c W_kvb a head;  o_t = softmax_{s<=t}((q_nope . k_nope + q_rope
             . k_r) / sqrt(192)) v_s;  out = (o_h * sigmoid(y W_gate)_h) W_o,
             W_gate 2560 x 32: a value a head off the layer's normed input
    MoE:     s = sigmoid(y W_r) (512 wide);  b = s + bias;  the experts in 8
             groups of 64 neighbours, a group's score the sum of its two best
             b, the 4 best groups kept, the top 8 of b among their experts;
             weights s_i / sum s_i x 2.5;  SwiGLU experts of 768 beside ONE
             shared expert of 768 added unweighted;  in layer l every routed
             expert computes silu(min(gate, L_l)) * clip(up, -L_l, L_l) with
             L_l = expert_swiglu_limit_list[l] (0: no clamp) and the shared
             expert the same with share_expert_swiglu_limit_list[l]

It reads the repo model's parameter tree (``layers``: runs of layers equal in
(mixer, FFN kind, clamps), stacked) so that it can be fed the engine's own
seeded weights. **The chip's share** is given as (first expert, count):
``first_expert_held`` and the banks' own width; a chosen expert held
elsewhere adds nothing here and its weight still counts in the
normalisation; a token whose kept groups leave the held experts out adds the
shared expert alone. The head holds the vocabulary's slice.

**So that some ten thousand tokens at the published widths fit beside the
weights**: one sequence at a time; attention a head at a time, its queries
in blocks of ``QUERY_BLOCK``; the bank one expert at a time, each matrix
widened to float32 where it is used; the KDA heads in groups of
``HEAD_GROUP`` through one scan over the tokens.

**Following** (``follow`` = routing (expert layers, B, S, k)): a token takes
the system's experts only where they are this router's own choice but for
swaps AT a threshold: every expert in which the two sets differ scores within
``gap`` of this reference's 8th biased score among the groups kept, the
groups kept being its own four or its own with groups swapped whose scores
(sums of two) stand within ``2 gap`` of the groups' threshold (:func:`router`,
:func:`their_groups`).

Departures from the source, each the configuration file's ``assumed`` with
the reading it excludes, and each excluded reading that this file can express
a control (:data:`CONTROLS`, ``benchmark/kinds/backlog_delta_latent.py``).
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
# every deviation this file can express; the on-chip comparison has to call
# each not correct (low-rank W_f / W_g cannot be one: other shapes)
CONTROLS = (
    "gate-unbounded",         # g = -exp(A_log) softplus(.), Kimi Linear's own
    "kda-out-gate-dropped",   # sigmoid(y W_g) left out behind the head's norm
    "qk-gain-dropped",        # no learned gain before the L2 norm
    "rope-dropped",           # no rope on the A layer
    "rope-halves",            # rotate_half pairs (i, i + 32) for (2i, 2i + 1)
    "out-gate-per-channel",   # the 32 gate values tiled over the channels
    "out-gate-dropped",       # the MLA layer's output gate left out
    "plain-top8",             # the top 8 of all 512, no groups
    "routed-clamp-dropped",   # the routed experts unclamped
    "shared-clamp-routed",    # the shared expert clamped at the routed value
    "routed-scale-1",         # routed_scaling_factor 1
)
BANKS = ("w_gate", "w_in", "w_out")


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    p = published
    for key, only in (("use_qk_norm", True), ("no_kda_lora", True),
                      ("use_kda_lora", False), ("kda_safe_gate", True),
                      ("use_mla_nope", False), ("rope_interleave", True),
                      ("gated_attention_proj_granularity_type", "head_wise"),
                      ("q_lora_rank", None), ("norm_topk_prob", True),
                      ("score_function", "sigmoid"), ("group_norm_size", 1),
                      ("linear_silu", True), ("num_shared_experts", 1),
                      ("tie_word_embeddings", False)):
        if p.get(key, only) != only:
            raise ValueError(f"this reference runs {key}={only!r}")
    L = p["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if len(p[key]) != L:
            raise ValueError(f"{key} gives every layer's clamp: {L} values")
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


def is_mla(layer: int, c) -> bool:
    return (layer + 1) % c["layer_group_size"] == 0


# ------------------------------------------------------------------- KDA
def kda_gates(y, w, c):
    """(beta (S, H), g (S, H, D)) of one sequence's normed input."""
    H, D = c["num_attention_heads"], c["head_dim"]
    S = y.shape[0]
    beta = jax.nn.sigmoid(y @ _f32(w["kda_wbeta"]))
    f = (y @ _f32(w["kda_wf"])
         + jnp.asarray(w["kda_dt_bias"], jnp.float32)).reshape(S, H, D)
    A = jnp.exp(jnp.asarray(w["kda_A_log"], jnp.float32))[:, None]
    if "gate-unbounded" in CONTROL:
        return beta, -A * jax.nn.softplus(f)
    return beta, float(c["kda_lower_bound"]) * jax.nn.sigmoid(A * f)


def kda(y, w, c, S0=None):
    """One sequence's KDA branch by the plain recurrence: ``y`` (S, d) the
    layer's normed input. ``S0`` (H, D, D): the state to start from (tests;
    the conv then still starts from zeros). Returns (out (S, d), S_T)."""
    S, d = y.shape
    H, D, K = (c["num_attention_heads"], c["head_dim"],
               c["short_conv_kernel_size"])
    u = y @ _f32(w["kda_wqkv"])
    taps = jnp.asarray(w["kda_conv_w"], jnp.float32)            # (3 H D, K)
    seq = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(seq[j:j + S] * taps[:, j] for j in range(K)))
    q, k, v = (a.reshape(S, H, D) for a in jnp.split(u, 3, axis=-1))
    if "qk-gain-dropped" not in CONTROL:
        gain = jnp.asarray(w["kda_qk_scale"], jnp.float32)      # (2, D)
        q, k = q * gain[0], k * gain[1]

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
    if "kda-out-gate-dropped" not in CONTROL:
        o = o * jax.nn.sigmoid((y @ _f32(w["kda_wg"])).reshape(S, H, D))
    return o.reshape(S, H * D) @ _f32(w["wo"]), ST.reshape(H, D, D)


# ------------------------------------------------------- latent attention
def _rope(a, theta: float):
    """(S, heads, rd) at positions 0..S-1: the pairs (2i, 2i + 1) rotated by
    ``pos * theta^(-2i / rd)`` (``rope_interleave``)."""
    S, _, rd = a.shape
    inv = theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    if "rope-halves" in CONTROL:
        a1, a2 = a[..., :rd // 2], a[..., rd // 2:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)
    a1, a2 = a[..., ::2], a[..., 1::2]
    return jnp.stack([a1 * cos - a2 * sin, a2 * cos + a1 * sin],
                     -1).reshape(a.shape)


def attention(y, w, c):
    """One sequence's latent attention, expanded: ``y`` (S, d) the normed
    input."""
    S, d = y.shape
    H, r, nope, rd, vd = (c["num_attention_heads"], c["kv_lora_rank"],
                          c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                          c["v_head_dim"])
    q = (y @ _f32(w["wq"])).reshape(S, H, nope + rd)
    kva = y @ _f32(w["wkv_a"])
    lat = _rmsnorm(kva[:, :r], jnp.asarray(w["kv_norm_scale"], jnp.float32),
                   c["rms_norm_eps"])
    q_rope, k_rope = q[..., nope:], kva[:, None, r:]
    if "rope-dropped" not in CONTROL:
        theta = float(c["rope_theta"])
        q_rope, k_rope = _rope(q_rope, theta), _rope(k_rope, theta)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    wkv_b = _f32(w["wkv_b"]).reshape(r, H, nope + vd)
    pos = jnp.arange(S, dtype=jnp.int32)
    scale = 1.0 / math.sqrt(nope + rd)

    def head(args):
        q, wb = args                              # (S, nope + rd), (r, .)
        kv = lat @ wb                                           # (S, nope + vd)
        k = jnp.concatenate([kv[:, :nope], k_rope[:, 0]], -1)
        v = kv[:, nope:]

        def rows(q, t):
            s = (q @ k.T) * scale
            s = jnp.where(pos[None] <= t[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, -1) @ v

        return _blocks(rows, S, q, pos)

    o = lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(wkv_b, 1, 0)))
    o = jnp.moveaxis(o, 0, 1)                                   # (S, H, vd)
    if "out-gate-dropped" not in CONTROL:
        gate = jax.nn.sigmoid(y @ _f32(w["w_ogate"]))           # (S, H)
        if "out-gate-per-channel" in CONTROL:
            o = (o.reshape(S, vd, H) * gate[:, None]).reshape(S, H, vd)
        else:
            o = o * gate[..., None]
    return o.reshape(S, H * vd) @ _f32(w["wo"])


# --------------------------------------------------------------- experts
def _swiglu(y, w_gate, w_in, w_out, limit: float = 0.0):
    gate, up = y @ w_gate, y @ w_in
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return (jax.nn.silu(gate) * up) @ w_out


def _top(a, k: int):
    """The ``k`` largest of every row as a bool mask; of equal values the
    first (the lower index) wins."""
    order = jnp.argsort(-a, axis=-1, stable=True)[:, :k]
    return jax.nn.one_hot(order, a.shape[-1], dtype=bool).any(1)


def group_scores(biased, c):
    """(N, E) biased scores -> (N, n_group): a group's two best, summed."""
    N, E = biased.shape
    G = c["n_group"]
    return jnp.sort(biased.reshape(N, G, E // G), -1)[..., -2:].sum(-1)


def choice(biased, groups, c):
    """The top ``num_experts_per_tok`` of ``biased`` (N, E) among the
    experts of the groups ``groups`` (N, n_group) bool (None: all 512). ->
    (chosen (N, E) bool, masked scores, the row's threshold: its k-th)."""
    k = c["num_experts_per_tok"]
    if groups is not None:
        biased = jnp.where(jnp.repeat(
            groups, biased.shape[-1] // c["n_group"], axis=-1),
            biased, -jnp.inf)
    return _top(biased, k), biased, jnp.sort(biased, -1)[:, -k][:, None]


def their_groups(biased, kept, theirs, c):
    """The groups another implementation may have kept, told from its choice
    ``theirs`` (N, E) bool, as this router's own ``kept`` (N, n_group) with
    groups AT the groups' threshold swapped: (a) every group left out that
    holds one of theirs taken in, for as many of the weakest kept groups
    that hold none of theirs; (b) that set with its weakest group that holds
    none of theirs swapped once more for the best group outside it (a swap
    that shows in no expert taken from the newcomer). Each as (groups (N,
    n_group) bool, the distance its swaps need excused: half the most that a
    swapped group's score — a sum of two experts' — stands from the score it
    would have to pass, the best left out for a kept group, the weakest kept
    for one left out; inf where the groups do not add up to ``topk_group``).
    """
    gs = group_scores(biased, c)
    G = c["n_group"]
    holds = theirs.reshape(theirs.shape[0], G, -1).any(-1)
    flip = jnp.where(kept, gs - jnp.where(kept, -jnp.inf, gs).max(-1)[:, None],
                     jnp.where(kept, gs, jnp.inf).min(-1)[:, None] - gs)

    def weakest(groups, n):
        """The ``n`` (N,) lowest-scored of ``groups`` that hold none of
        theirs, as a mask."""
        free = groups & ~holds
        rank = jnp.argsort(jnp.argsort(jnp.where(free, gs, jnp.inf), -1), -1)
        return free & (rank < n[:, None])

    def moved(groups):
        cost = jnp.where(groups != kept, flip, 0.0).max(-1) / 2.0
        return jnp.where(groups.sum(-1) == c["topk_group"], cost, jnp.inf)

    came = holds & ~kept
    first = (kept & ~weakest(kept, came.sum(-1))) | came
    one = jnp.ones(first.shape[:1], jnp.int32)
    best_out = _top(jnp.where(first, -jnp.inf, gs), 1) & ~first
    second = (first & ~weakest(first, one)) | best_out
    return (first, moved(first)), (second, moved(second))


def router(y, w, c, follow=None, gap: float = 0.0, rows=None):
    """(N, d) tokens -> ((N, E) combine weights over ALL experts, zero but
    for the chosen; (how many tokens followed ``follow`` (N, k), the largest
    distance from this router's own thresholds that a token would need to be
    followed — what ``gap`` has to excuse of a sound system's rounding,
    whatever ``gap`` is, among the tokens ``rows`` (None: all; a comparison
    reads a few rows of a long sequence, and a token that was not followed
    in one layer meets the next layer's router with another stream and a
    larger distance: a cascade the rows that are compared must not be
    charged with); a choice that no swap at a threshold explains needs no
    finite distance and is left out of it)).

    The choice, written out: sigmoid scores, the selection bias added; the
    groups' scores (:func:`group_scores`); the ``topk_group`` best groups;
    the top ``num_experts_per_tok`` biased scores among their experts.

    A token takes ``follow``'s experts only where they are this router's
    choice but for swaps at a threshold: under its own groups, or under the
    groups the other side may have kept (:func:`their_groups`: groups whose
    scores stand within ``2 gap`` of the groups' threshold swapped — a
    group's score sums two experts'), EVERY expert in which the two sets
    differ scores within ``gap`` of the 8th biased score among the groups
    kept. Two bf16 programs that round a router's input differently swap
    experts, or groups, that stand at a threshold, and only those; one
    chosen from further down is a wrong choice and is not followed."""
    logit = y @ w["router"]
    score = jax.nn.sigmoid(logit)
    biased = score + w["router_bias"]
    grouped = "plain-top8" not in CONTROL and c["n_group"] > c["topk_group"]
    kept = _top(group_scores(biased, c), c["topk_group"]) if grouped else None
    chosen, _, _ = choice(biased, kept, c)
    followed, far = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
    if follow is not None:
        theirs = jax.nn.one_hot(follow, biased.shape[-1], dtype=bool).any(1)

        def needed(groups, moved=0.0):
            """What ``theirs`` needs excused if the groups kept are
            ``groups``: the groups' own distance ``moved``, and every
            expert in which the two sets then differ from the threshold."""
            mine, masked, thr = choice(biased, groups, c)
            return jnp.maximum(moved, jnp.where(
                theirs != mine, jnp.abs(masked - thr), 0.0).max(-1))

        need = needed(kept)
        if grouped:
            need = jnp.minimum(need, jnp.minimum(
                *(needed(*g) for g in their_groups(biased, kept, theirs, c))))
        near = need <= gap
        followed = (near & (need > 0)).sum().astype(jnp.int32)
        far = jnp.where(jnp.isfinite(need), need, 0.0)
        far = (far if rows is None else far[jnp.asarray(rows)]).max()
        chosen = jnp.where(near[:, None], theirs, chosen)
    g = jnp.where(chosen, score, 0.0)
    if c["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    scale = 1.0 if "routed-scale-1" in CONTROL \
        else float(c["routed_scaling_factor"])
    return g * scale, (followed, far)


def experts(y, w, c, limits=(0.0, 0.0), follow=None, gap: float = 0.0,
            banks=None, shared: bool = True, rows=None):
    """The expert layer on (N, d): every HELD expert on every token,
    weighted by the router's weight for it; the chosen experts held
    elsewhere add nothing; the shared expert once (``shared``). ``limits``:
    the layer's (routed, shared) clamps. ``banks`` = (the run's stacked
    banks ``(layers, held, ., .)``, this layer's index), or None: the
    layer's own ``(held, ., .)``."""
    g, followed = router(y, _f32({k: w[k] for k in ("router", "router_bias")},
                                 matrices=False), c, follow, gap, rows)
    stacked, layer = banks if banks is not None else (
        {k: w[k][None] for k in BANKS}, 0)
    held = stacked["w_gate"].shape[1]
    g = lax.dynamic_slice_in_dim(g, c.get("first_held", 0), held, 1)
    routed = 0.0 if "routed-clamp-dropped" in CONTROL else float(limits[0])
    own = float(limits[0] if "shared-clamp-routed" in CONTROL else limits[1])

    def one(acc, e):
        ws = tuple(lax.dynamic_slice(
            stacked[k], (layer, e, 0, 0), (1, 1) + stacked[k].shape[2:])[0, 0]
            for k in BANKS)
        ge = lax.dynamic_index_in_dim(g, e, 1, keepdims=False)
        return acc + ge[:, None] * _swiglu(y, *_f32(ws), limit=routed), None

    out, _ = lax.scan(one, jnp.zeros_like(y),
                      jnp.arange(held, dtype=jnp.int32))
    if shared:
        out = out + _swiglu(y, *_f32((w["ws_gate"], w["ws_in"],
                                      w["ws_out"])), limit=own)
    return out, followed


# ----------------------------------------------------------------- model
def _sequence(params, ids, c, routing, gap, rows=None):
    """One sequence (S,) -> (the stream (S, d), (tokens x layers that
    followed ``routing``, the largest distance from a threshold that one of
    them needed))."""
    x = _f32(params["tok_embed"][ids])
    layers = params["layers"]
    segs = layers if isinstance(layers, (tuple, list)) else (layers,)
    eps = c["rms_norm_eps"]
    layer = routed = 0
    followed, far = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
    for seg in segs:
        count = jax.tree.leaves(seg)[0].shape[0]
        linear, sparse = "kda_wqkv" in seg, "router" in seg
        banks = {k: seg[k] for k in BANKS} if sparse else None
        rest = {k: v for k, v in seg.items()
                if not (sparse and k in BANKS)}
        for i in range(count):
            if linear == is_mla(layer, c) \
                    or sparse == (layer < c["first_k_dense_replace"]):
                raise ValueError(f"layer {layer} does not hold what "
                                 "layer_group_size and first_k_dense_replace "
                                 "say it holds")
            w = _at(rest, i)
            y = _rmsnorm(x, _f32(w["ln1_scale"]), eps)
            x = x + (kda(y, w, c)[0] if linear else attention(y, w, c))
            y = _rmsnorm(x, _f32(w["ln2_scale"]), eps)
            if sparse:
                out, took = experts(
                    y, w, c, (c["expert_swiglu_limit_list"][layer],
                              c["share_expert_swiglu_limit_list"][layer]),
                    routing[routed] if routing is not None else None, gap,
                    (banks, i), rows=rows)
                followed, far = followed + took[0], jnp.maximum(far, took[1])
                routed += 1
            else:
                out = _swiglu(y, *_f32((w["w_gate"], w["w_in"], w["w_out"])))
            x = x + out
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
    largest distance from this reference's thresholds that one of the
    tokens ``rows`` needed: :func:`router`))."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    if n_head not in (None, 0, c["num_attention_heads"]) \
            or eps not in (None, 0.0, c["rms_norm_eps"]):
        raise ValueError("n_head / eps differ from the configured keys")
    outs, took, far = [], jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
    for b in range(input_ids.shape[0]):
        x, (n, f) = _sequence(params, input_ids[b], c,
                              None if follow is None else follow[:, b], gap,
                              rows)
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

"""GLM-5.2 (``model_type: glm_moe_dsa``; zai-org/GLM-5.2's ``config.json`` and
the DeepSeek-V3.2 description of the same attention, arXiv:2512.02556),
plainly: ``jax.numpy``, float32, full causal scores with the selection as a
mask, every held expert on every token; no cache, no kernel, no absorbed
projection, no gather, and nothing of ``deepspeed_tpu``.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g;  LN = LayerNorm with a bias
    layer i:  x = x + attn_i(RMS(x; g1_i));  x = x + ffn_i(RMS(x; g2_i))
    query:   cq = RMS(h W_qa) (2048);  q = cq W_qb -> 64 heads of
             [q_nope 192 | q_rope 64], rope on q_rope
    latent:  [ckv 512 | kr 64] = h W_kva;  c = RMS(ckv);  k_rope = rope(kr),
             one for all heads;  [k_nope_h 192 | v_h 256] = c W_kvb per head
    indexer (a "full" layer):  qI = cq W_Iq -> 32 heads of 128;
             kI = LN(h W_Ik) (128, one for all heads);  rope on the first 64
             dims of both;  w = h W_Iw (32) x 32^-1/2 x 128^-1/2;
             I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]);
             S_t = the 2048 positions s <= t of largest I[t, s] (all while
             t < 2048; of equal scores the lowest positions)
    a "shared" layer:  S_t is the S_t of the last "full" layer before it
    attn:    softmax_{s in S_t}((q_nope_h . k_nope_h[s] + q_rope_h .
             k_rope[s]) / 16) v_h[s], heads concatenated (64 x 256) into W_o
    ffn:     dense SwiGLU 12 288 | sigmoid(h' W_r), the top 8 of score + bias
             (noaux_tc, one group), chosen scores normalised x 2.5, beside
             one shared SwiGLU expert of 2048
    model:   logits = RMS(x_L; g_f) W_head

Rope turns the pairs (2i, 2i + 1) (``rope_interleave``,
``indexer_rope_interleave``), theta 8e6, frequencies over the 64 dims turned.

It reads the repo model's parameter tree (``layers``: the dense run then the
expert run, stacked; ``indexer``: the "full" layers' indexers, stacked) so
that it can be fed the engine's own seeded weights. **The chip's share**: the
banks hold the experts this device holds, ``first_expert_held`` says which of
the router's outputs the first of them is; a chosen expert held elsewhere
adds nothing here, as in the program, and its weight still counts in the
normalisation. The head holds the vocabulary's slice.

**So that 12 k tokens at the published widths fit beside the weights**: one
sequence at a time; queries in blocks of ``QUERY_BLOCK`` rows (each against
every key, under the mask); heads in groups of ``HEAD_GROUP``; the dense FFN
in column blocks and the bank one expert at a time, each widened to float32
where it is used. The same mathematics as one matrix.

**Following** (``follow=(routing, selection)``): with random weights a
token's 8th and 9th expert scores, and a query's 2048th and 2049th indexer
scores, often lie closer than bf16 rounds; the system then takes the other
and runs a different, equally valid model from there. A token takes the
system's experts only where its OWN 8th and 9th biased scores lie within
``gap``; a query-layer takes the system's selection only where EVERY position
in which the two sets differ has a score here within ``select_gap`` of this
reference's own 2048th. Everywhere else the reference keeps its own, so a
system that routes or selects wrongly still fails. The result then carries
what followed.

Departures (the configuration file's ``assumed``): the published inference
code turns qI and kI by a Hadamard matrix and holds them in 8 bits (the turn
leaves every product as it is; the 8 bits are not this configuration's); the
multi-token-prediction layer is not held.
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
# None, or a control's rounding of every matrix as it is widened (the
# router's apart): benchmark/kinds/backlog_sparse.py CONTROLS. Never set in
# a timed run.
ROUND = None
# the names of the deviations a control switches on (same file): what a
# wrong system would compute
CONTROL: set = set()


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    p = published
    for key, only in (("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("rope_interleave", True),
                      ("indexer_rope_interleave", True)):
        if p.get(key, only) != only:
            raise ValueError(f"this reference runs {key}={only!r}")
    if p["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("this reference has no rope scaling")
    if p["indexer_types"][0] != "full":
        raise ValueError("the first layer has to select for itself")
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


def rope(x, theta: float, rd: int):
    """x (S, H, D): the pairs (2i, 2i + 1) of the first ``rd`` dims turned by
    pos * theta^(-2i/rd); the rest pass."""
    S = x.shape[0]
    inv = theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., 0:rd:2], x[..., 1:rd:2]
    turned = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape[:-1] + (rd,))
    return jnp.concatenate([turned, x[..., rd:]], -1)


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
        lambda a: a.reshape((nb * QUERY_BLOCK,) + a.shape[2:])[:rows]
        if a.ndim >= 2 else a, out)


def top_mask(score, k: int):
    """(rows, S) scores (-inf where a position is no candidate) -> (the mask
    of each row's ``k`` largest, of equal scores the lowest positions; the
    k-th largest (rows, 1), -inf where a row has fewer candidates)."""
    S = score.shape[-1]
    if k >= S:
        return score > -jnp.inf, jnp.full(score.shape[:-1] + (1,), -jnp.inf)
    thr = jnp.sort(score, -1)[..., S - k][..., None]
    above = score > thr
    tied = (score == thr) & (score > -jnp.inf)
    room = k - above.sum(-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, -1) <= room)), thr


def select(h, cq, ip, c, theirs=None, select_gap: float = 0.0):
    """A "full" layer's selection for one sequence: ``h`` (S, d) the normed
    input, ``cq`` (S, q_lora_rank), ``ip`` its indexer. Returns (mask (S, S)
    bool, query rows that followed ``theirs`` (S, K) i32 (-1: none), the
    largest distance from the threshold of a position the two sets differ
    in — over all rows, whether they followed or not)."""
    S = h.shape[0]
    H, D, rd = c["index_n_heads"], c["index_head_dim"], c["qk_rope_head_dim"]
    K, theta = c["index_topk"], float(c["rope_parameters"]["rope_theta"])
    ip = _f32(ip)
    q = (cq @ ip["wq_b"]).reshape(S, H, D)
    k = h @ ip["wk"]
    if "k-norm-dropped" not in CONTROL:
        k = _layernorm(k, ip["k_norm_scale"], ip["k_norm_bias"],
                       c["rms_norm_eps"])
    q = rope(q, theta, rd)
    if "k-rope-dropped" not in CONTROL:
        k = rope(k[:, None], theta, rd)[:, 0]
    w = h @ ip["weights_proj"]
    if "head-weights-dropped" in CONTROL:
        w = jnp.ones_like(w)
    w = w / math.sqrt(H * D)
    pos = jnp.arange(S, dtype=jnp.int32)
    theirs = jnp.full((S, 1), -1, jnp.int32) if theirs is None else theirs

    def rows(q, w, t, theirs):
        s = jnp.einsum("qhd,sd->qhs", q, k)
        if "relu-dropped" not in CONTROL:
            s = jnp.maximum(s, 0.0)
        score = jnp.einsum("qh,qhs->qs", w, s)
        causal = pos[None] <= t[:, None]
        score = jnp.where(causal, score, -jnp.inf)
        own, thr = top_mask(score, K)
        if "newest-selected" in CONTROL:
            own = causal & (pos[None] > t[:, None] - K)
        given = (theirs >= 0).any(-1)
        sys = jnp.zeros(own.shape, jnp.int32).at[
            jnp.arange(own.shape[0])[:, None], jnp.maximum(theirs, 0)].max(
                (theirs >= 0).astype(jnp.int32)) > 0
        differ = (sys != own) & given[:, None]
        far = jnp.where(differ, jnp.nan_to_num(
            jnp.abs(score - thr), nan=jnp.inf, posinf=jnp.inf), 0.0).max(-1)
        follow = given & (far <= select_gap)
        return (jnp.where(follow[:, None], sys, own),
                follow & differ.any(-1), far)

    mask, took, far = _blocks(rows, S, q, w, pos, theirs)
    return mask, took.sum().astype(jnp.int32), far.max()


def query_latent(x, w, c):
    """(``h`` (S, d) the layer's normed input, ``cq`` (S, q_lora_rank) the
    query's normed latent, which the indexer reads too)."""
    h = _rmsnorm(x, _f32(w["ln1_scale"]), c["rms_norm_eps"])
    cq = h @ _f32(w["wq_a"])
    if "q-norm-dropped" not in CONTROL:
        cq = _rmsnorm(cq, _f32(w["q_norm_scale"]), c["rms_norm_eps"])
    return h, cq


def attention(x, w, c, mask):
    """One sequence's attention: ``x`` (S, d) the stream, ``w`` the layer's
    weights as stored, ``mask`` (S, S) the keys each query may see (causal
    included). Returns the branch's output (S, d)."""
    S, d = x.shape
    H, nope, rd, vd, r = (
        c["num_attention_heads"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])
    eps, theta = c["rms_norm_eps"], float(c["rope_parameters"]["rope_theta"])
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    h, cq = query_latent(x, w, c)
    kva = h @ _f32(w["wkv_a"])
    lat = _rmsnorm(kva[:, :r], _f32(w["kv_norm_scale"]), eps)
    k_rope = rope(kva[:, None, r:], theta, rd)[:, 0]              # (S, rd)

    def per_group(a, cols):
        """(rows, H * cols) -> (H / G, rows, G * cols), stored type."""
        return a.reshape(a.shape[0], H // G, G * cols).transpose(1, 0, 2)

    groups = (per_group(w["wq_b"], nope + rd), per_group(w["wkv_b"], nope + vd),
              w["wo"].reshape(H // G, G * vd, d))

    def one(out, ws):
        wq, wkv, wo = _f32(ws)
        q = (cq @ wq).reshape(S, G, nope + rd)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], theta, rd)
        kv = (lat @ wkv).reshape(S, G, nope + vd)

        def rows(q_nope, q_rope, m):
            s = (jnp.einsum("qgn,sgn->gqs", q_nope, kv[..., :nope])
                 + jnp.einsum("qgr,sr->gqs", q_rope, k_rope)) \
                / math.sqrt(nope + rd)
            s = jnp.where(m[None], s, -jnp.inf)
            # (a padded row sees nothing: its softmax is discarded)
            p = jax.nn.softmax(jnp.where(m.any(-1)[None, :, None], s, 0.0),
                               -1)
            return jnp.einsum("gqs,sgv->qgv", p, kv[..., nope:])

        o = _blocks(rows, S, q_nope, q_rope, mask)
        return out + o.reshape(S, G * vd) @ wo, None

    out, _ = lax.scan(one, jnp.zeros_like(x), groups)
    return out


def _swiglu(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ w_gate) * (y @ w_in)) @ w_out


def dense_ffn(y, w):
    """SwiGLU in column blocks of FFN_BLOCK: the hidden units add up."""
    d, f = w["w_in"].shape
    nb = max(1, f // FFN_BLOCK)
    cols = (w["w_gate"].reshape(d, nb, f // nb).transpose(1, 0, 2),
            w["w_in"].reshape(d, nb, f // nb).transpose(1, 0, 2),
            w["w_out"].reshape(nb, f // nb, d))
    out, _ = lax.scan(lambda acc, ws: (acc + _swiglu(y, *_f32(ws)), None),
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


BANKS = ("w_gate", "w_in", "w_out")


def experts(y, w, c, follow=None, gap: float = 0.0, banks=None):
    """The expert layer on (N, d): every HELD expert on every token, weighted
    by the router's weight for it (0 where it was not chosen); the chosen
    experts held elsewhere add nothing; the shared expert once. ``w``: the
    layer's tree as stored, its bank ``(held, ., .)`` a matrix — or, with
    ``banks`` = (the run's stacked banks ``(layers, held, ., .)``, this
    layer's index in them), without one: an expert's matrices are then read
    out of the run's, one expert at a time, and widened there (a layer's
    bank sliced out whole is 1.2 GB at the published widths, and six of
    them stood at once)."""
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
    return out + _swiglu(y, *_f32((w["ws_gate"], w["ws_in"], w["ws_out"]))), \
        followed


def _layer(x, w, ip, c, mask, theirs, gaps, banks=None):
    """One layer of one sequence. ``ip``: the indexer this layer selects
    with, or None (it reads ``mask``). ``theirs``: (routing (S, k) | None,
    selection (S, K) | None). Returns (x, mask, (tokens that followed the
    routing, query rows that followed the selection, the largest distance
    from the threshold of a position the sets differ in))."""
    gap, select_gap = gaps
    zero = jnp.zeros((), jnp.int32)
    took, far = zero, jnp.zeros((), jnp.float32)
    if ip is not None:
        mask, took, far = select(*query_latent(x, w, c), ip, c, theirs[1],
                                 select_gap)
    x = x + attention(x, w, c, mask)
    y = _rmsnorm(x, _f32(w["ln2_scale"]), c["rms_norm_eps"])
    if "router" in w:
        out, followed = experts(y, w, c, theirs[0], gap, banks)
        return x + out, mask, (followed, took, far)
    return x + dense_ffn(y, w), mask, (zero, took, far)


def _sequence(params, ids, c, follow, gaps):
    """One sequence (S,) -> (the final stream (S, d), what followed)."""
    S = ids.shape[0]
    x = _f32(params["tok_embed"][ids])       # (the rows, then widened)
    layers = params["layers"]
    segs = layers if isinstance(layers, (tuple, list)) else (layers,)
    kinds = c["indexer_types"]
    routing, picks = follow if follow is not None else (None, None)
    mask = first_mask = jnp.tril(jnp.ones((S, S), bool))
    layer = full = routed = 0
    totals = [jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
              jnp.zeros((), jnp.float32)]
    last_ip = None
    for seg in segs:
        n = jax.tree.leaves(seg)[0].shape[0]
        moe = "router" in seg
        if moe != (c["mlp_layer_types"][layer] == "sparse"):
            raise ValueError(f"layer {layer} does not hold what its kind "
                             "holds")
        banks = {k: seg[k] for k in BANKS} if moe else None
        rest = {k: v for k, v in seg.items() if not (moe and k in BANKS)}
        for i in range(n):
            w = _at(rest, i)
            selects = kinds[layer] == "full"
            ip = _at(params["indexer"], full) if selects else None
            if selects:
                last_ip = ip
            elif "shared-selects-itself" in CONTROL:
                ip = last_ip
            theirs = (routing[routed] if moe and routing is not None
                      else None,
                      picks[full] if selects and picks is not None else None)
            seen = first_mask if ("shared-takes-first" in CONTROL
                                  and not selects and full > 0) else mask
            x, new_mask, took = _layer(x, w, ip, c, seen, theirs, gaps,
                                       (banks, i) if moe else None)
            if selects:
                mask = new_mask
                if full == 0:
                    first_mask = new_mask
            totals = [totals[0] + took[0], totals[1] + took[1],
                      jnp.maximum(totals[2], took[2])]
            full += selects
            routed += moe
            layer += 1
    if layer != c["num_hidden_layers"]:
        raise ValueError(f"{layer} layers, not num_hidden_layers")
    return x, tuple(totals)


def head(x, w):
    """``x @ w`` with the head's slice widened a block of columns at a time
    (whole, it is half a gigabyte of float32 beside a full chip)."""
    d, V = w.shape
    nb = next(n for n in (16, 11, 10, 8, 5, 4, 2, 1) if V % n == 0)
    cols = w.reshape(d, nb, V // nb).transpose(1, 0, 2)
    out = lax.map(lambda c: x @ _f32(c), cols)          # (nb, ..., V / nb)
    return jnp.moveaxis(out, 0, -2).reshape(x.shape[:-1] + (V,))


def logits(params, input_ids, n_head=None, eps=None, last_only: bool = False,
           rows=None, follow=None, gap: float = 0.0,
           select_gap: float = 0.0):
    """(B, S) token ids -> (B, S, V) float32 logits; (B, V) of the last
    position with ``last_only``, (B, len(rows), V) of the positions ``rows``.
    ``n_head`` and ``eps`` are what the shared serving kind hands every
    reference; they have to be the configured ones. With ``follow`` =
    (routing (expert layers, B, S, k), selection (full layers, B, S, K) with
    -1 where a query has fewer), another implementation's choices, the
    result is (logits, (tokens x layers that followed its routing, query
    rows x layers that followed its selection, the largest distance from the
    threshold of a position in which the two selections differ)): see the
    top of this file."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    if n_head not in (None, 0, c["num_attention_heads"]) \
            or eps not in (None, 0.0, c["rms_norm_eps"]):
        raise ValueError("n_head / eps differ from the configured keys")
    outs, notes = [], []
    for b in range(input_ids.shape[0]):
        theirs = None if follow is None else tuple(
            a[:, b] for a in follow)
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

"""The DeepSeek-V3 block as published (DeepSeek-AI 2024, arXiv:2412.19437,
sections 2.1.1 and 2.1.2; the layout of HF ``DeepseekV3ForCausalLM`` with
``q_lora_rank`` null), plainly: ``jax.numpy``, float32, full causal attention
over expanded K and V, a loop over the layers and a scan over the
experts in which every expert multiplies every token; no cache, no kernel, no
sorting or grouping of rows, no absorbed projection.

It reads the repo model's parameter tree (``layers``: one stacked tree per
run of like layers, the dense ones first) so that it can be fed the engine's
own seeded weights, and shares no code with ``deepspeed_tpu``. What cannot
be read off the weights' shapes (experts per token, the scaling factor,
theta, the head split) comes from the configuration's published keys, which
the family's builder hands over with :func:`configure` before the first call.

Departures from the published model: none in the mathematics. ``n_group`` =
``topk_group`` = 1 in the configurations run here, so the group-limited
choice is the plain top-k over all experts (other values are refused). Rope
turns the pairs (2i, 2i+1) of the rope part (``rope_interleave``); HF
stores the turned pairs de-interleaved, the same permutation on q and k,
which leaves every score as it is.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PUBLISHED: dict = {}


def configure(published: dict) -> None:
    """The configuration's published keys (``config`` of its file)."""
    if published.get("n_group", 1) != 1 or published.get("topk_group", 1) != 1:
        raise ValueError("this reference chooses over one group of experts")
    if published.get("q_lora_rank") is not None:
        raise ValueError("this reference has no low-rank query projection")
    if published.get("rope_scaling") is not None:
        raise ValueError("this reference has no rope scaling")
    if published["scoring_func"] != "sigmoid" \
            or published["topk_method"] != "noaux_tc":
        raise ValueError("this reference routes by sigmoid scores, noaux_tc")
    PUBLISHED.clear()
    PUBLISHED.update(published)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, r): pair i = (x[2i], x[2i+1]) turned by pos * theta^(-2i/r)."""
    S, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]      # (S, r/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def _swiglu(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ w_gate) * (y @ w_in)) @ w_out


def _attention(x, w, c, eps):
    B, S, d = x.shape
    H = c["num_attention_heads"]
    nope, rd, vd, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                       c["v_head_dim"], c["kv_lora_rank"])
    y = _rmsnorm(x, w["ln1_scale"], eps)
    q = (y @ w["wq"]).reshape(B, S, H, nope + rd)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], c["rope_theta"])
    kva = y @ w["wkv_a"]
    lat = _rmsnorm(kva[..., :r], w["kv_norm_scale"], eps)
    k_rope = _rope(kva[..., None, r:], c["rope_theta"])           # (B, S, 1, rd)
    kv = (lat @ w["wkv_b"]).reshape(B, S, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, S, H, rd))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(nope + rd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), kv[..., nope:])
    return x + a.reshape(B, S, H * vd) @ w["wo"]


def router(y, w, c, follow=None, gap: float = 0.0):
    """(N, d) tokens -> ((N, E) combine weights, zero but for the chosen;
    the biased scores the choice was made on; how many tokens followed).

    ``follow`` (N, k): another implementation's choice for these tokens.
    With random weights the k-th and (k+1)-th biased scores of a token can
    lie closer than that implementation's rounding, and it then takes the
    other expert: a different model from there on, not an error. A token
    whose own k-th and (k+1)-th scores lie within ``gap`` takes ``follow``'s
    experts (weighted by this router's own scores); every other token keeps
    its own choice, whatever ``follow`` says."""
    score = jax.nn.sigmoid(y @ w["router"])                          # (N, E)
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
    return g * c["routed_scaling_factor"], biased, followed


def _experts(y, w, c, follow=None, gap: float = 0.0):
    """Every expert on every token, weighted; the shared experts once."""
    g, _, followed = router(y, w, c, follow, gap)

    def one(acc, ew):
        w_gate, w_in, w_out, ge = ew
        return acc + ge[:, None] * _swiglu(y, w_gate, w_in, w_out), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          (w["w_gate"], w["w_in"], w["w_out"], g.T))
    return out + _swiglu(y, w["ws_gate"], w["ws_in"], w["ws_out"]), followed


def _layer(x, w, c, eps, follow=None, gap: float = 0.0):
    """One layer; (x, tokens that followed ``follow``)."""
    w = _f32(w)
    x = _attention(x, w, c, eps)
    y = _rmsnorm(x, w["ln2_scale"], eps)
    if "router" in w:
        B, S, d = y.shape
        out, followed = _experts(
            y.reshape(B * S, d), w, c,
            None if follow is None else follow.reshape(B * S, -1), gap)
        return x + out.reshape(B, S, d), followed
    return x + _swiglu(y, w["w_gate"], w["w_in"], w["w_out"]), \
        jnp.zeros((), jnp.int32)


def _segments(layers):
    return tuple(layers) if isinstance(layers, (tuple, list)) else (layers,)


def logits(params, input_ids, n_head: int = 0, eps: float = 0.0,
           last_only: bool = False, follow=None, gap: float = 0.0):
    """(B, S) token ids -> (B, S, V) float32 logits, or (B, V) of the last
    position with ``last_only``. ``n_head`` and ``eps`` are what the serving
    kind passes every family; they must agree with the published keys.
    With ``follow`` (expert layers, B, S, k), another implementation's
    routing, the result is (logits, tokens x layers that followed it):
    see :func:`router`."""
    c = PUBLISHED
    if not c:
        raise RuntimeError("configure(published) first")
    eps = eps or c["rms_norm_eps"]
    if (n_head and n_head != c["num_attention_heads"]) \
            or eps != c["rms_norm_eps"]:
        raise ValueError("n_head / eps disagree with the published keys")
    x = _f32(params["tok_embed"])[input_ids]
    first, followed = 0, jnp.zeros((), jnp.int32)
    for seg in _segments(params["layers"]):
        # one layer body scanned over the run's stacked weights: the same
        # mathematics as a Python loop, with one layer's float32 copy alive
        # at a time
        n = jax.tree.leaves(seg)[0].shape[0]
        routed = follow is not None and "router" in seg
        theirs = follow[first:first + n] if routed else None
        x, took = jax.lax.scan(
            lambda x, wf: _layer(x, wf[0], c, eps, wf[1], gap), x,
            (seg, theirs))
        followed = followed + took.sum()
        first += n if routed else 0
    x = _rmsnorm(x, _f32(params["lnf_scale"]), eps)
    if last_only:
        x = x[:, -1]
    out = x @ _f32(params["lm_head"])
    return out if follow is None else (out, followed)


def loss(params, input_ids, n_head: int = 0, eps: float = 0.0):
    """Mean next-token cross-entropy over every position but the last."""
    lg = logits(params, input_ids, n_head, eps)[:, :-1]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, input_ids[:, 1:, None], -1)[..., 0]
    return nll.mean()


def run_highest(fn, *args, **static):
    """``fn`` jitted and run in true float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **static))(*args)

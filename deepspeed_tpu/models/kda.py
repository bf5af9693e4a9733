"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692 §3): the linear
attention mixer of GLM-5.3-Flash (``model_type: glm5_next_text``), of
Solar-Open2 (``model_type: solar_open2``) and of Ling-3.0-flash (``model_type:
bailing_hybrid``), ``cfg.mixer_pattern`` "K", in the forms a served trunk
needs. They have to agree, and ``tests/unit/test_linear_sparse.py``,
``tests/unit/test_delta_gqa.py`` and ``tests/unit/test_delta_latent.py`` hold
them to the plain recurrence of ``benchmark/reference/glm5_next.py``,
``solar_open2.py`` and ``bailing_hybrid.py``.

On the layer's normed input ``y``, per head h of ``kda_heads`` with ``D =
kda_head_dim`` key and value channels:

    q = L2(silu(conv(y W_q)));  k = L2(silu(conv(y W_k)));  v = silu(conv(y W_v))
    beta = c sigmoid(y W_beta)                                   (a head)
    g = floor * sigmoid(exp(A_log[h]) (y W_f1 W_f2 + dt_bias))   (a key channel)
     or -exp(A_log[h]) softplus(y W_f1 W_f2 + dt_bias)           (no floor)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(D);  out = (RMS_head(o_t) * sigmoid(y W_g1 W_g2)) W_o

``conv`` is causal and depthwise, ``kda_conv`` taps, no bias. ``floor`` is
``kda_gate_floor``: -5 is the bounded gate of flash-linear-attention's KDA
(GLM-5.3-Flash's ``gate_lower_bound``), 0 reads "no floor" — Kimi Linear's
own gate, unbounded below (Solar-Open2's). ``c`` is 2 with
``kda_neg_eigval`` (``I - beta k k^T`` then has the eigenvalue ``1 - beta``
in (-1, 1)), else 1. With ``kda_rank`` 0 the two low-rank pairs ``W_f1 W_f2``
and ``W_g1 W_g2`` are ONE full map each (``kda_wf``, ``kda_wg``:
Ling-3.0-flash's ``no_kda_lora``); with ``kda_qk_norm`` q and k take a learned
gain a channel before their L2 norm. Every form here holds for any ``g <= 0``: no factor is
the exponential of a positive number. ``S`` (D x D a head) is float32, and
everything that multiplies it.

- :func:`mix_chunk`: T tokens that take the conv tails and the state in and
  hand both out, the paper's chunkwise form (:func:`scan_chunked`): blocks
  of :data:`CHUNK` tokens; inside a block the delta rule is a unit lower
  triangular system, solved by forward substitution for every block at once;
  between blocks a scan over the block-start states — or, where the kind's
  kernels run and T is whole blocks (:func:`chunk_kernel_ok`), the same
  mathematics in ONE kernel with the blocks' temporaries and the carried
  state in VMEM (``ops/kda_chunk.py``). ``valid`` (traced) says
  how many of the T tokens are real: what a bucket pads behind a prompt gets
  ``beta = 0`` and ``g = 0``, which changes nothing, and the tails end at
  the last real token.
- :func:`mix_step`: one token a slot (:func:`state_step`, or in place
  ``ops/kda_step.py``). A row that is not live keeps state and tails
  bit-equal.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.sparse_mla_attention import einsum_f32

HI = lax.Precision.HIGHEST
CHUNK = 64      # tokens a block of the chunkwise form (the paper's)
SUB = 16        # tokens a sub-block: its decays are formed pair by pair
FP32_NAMES = ("kda_A_log", "kda_dt_bias")
KINDS = "KA"


def check_config(c) -> None:
    """Refuse what a ``mixer_pattern`` trunk does not run, each with why."""
    pat = c.mixer_pattern
    if (c.hc_mult > 1 or c.index_kpool > 1) and not pat:
        raise ValueError(
            "hc_mult and index_kpool are the glm5_next_text trunk's "
            "(mixer_pattern): the other trunks' layer loops carry one "
            "stream and one indexer key a position")
    if c.attn_out_gate and not (pat and (
            (c.attention == "mha" and c.attn_out_gate is True)
            or (c.attention == "mla" and c.attn_out_gate == "head"
                and not c.index_pattern))):
        raise ValueError(
            "attn_out_gate is the solar_open2 block's (mixer_pattern "
            "beside attention='mha': True, a value a head a channel) or the "
            "bailing_hybrid block's (mixer_pattern beside attention='mla' "
            "with no index_pattern: 'head', a value a head): no other "
            "trunk's attention layer multiplies its output by a gate")
    if len(pat) != c.n_layer or set(pat) - set(KINDS):
        raise ValueError(f"mixer_pattern {pat!r} has to name each of the "
                         f"{c.n_layer} layers, one of {KINDS!r}")
    if "K" in pat and (min(c.kda_heads, c.kda_head_dim) <= 0
                       or c.kda_rank < 0
                       or c.kda_conv < 2 or c.kda_gate_floor > 0):
        raise ValueError(
            "a 'K' layer needs kda_heads, kda_head_dim, kda_rank (0: full "
            "maps), kda_conv >= 2 and a kda_gate_floor that is negative (the "
            "bounded gate) or 0 (no floor: the gate unbounded below)")
    # the bailing_hybrid block: "A" is roped dense MLA, every latent read
    roped = c.attention == "mla" and not c.index_pattern
    if roped and (c.hc_mult > 1 or c.index_kpool > 1 or c.q_lora_rank
                  or c.qk_rope_head_dim <= 0 or c.pos_embedding != "rope"):
        raise ValueError(
            "mixer_pattern beside attention='mla' with no index_pattern is "
            "the bailing_hybrid block: KDA layers beside roped latent "
            "attention over every live position under one residual stream "
            "— rope (pos_embedding='rope') on the 'A' layers' "
            "qk_rope_head_dim, one query matrix, no hc_mult or index_kpool "
            "(with an index_pattern it is the glm5_next_text block)")
    if c.num_experts < 2 or c.moe_router != "sigmoid" \
            or c.norm != "rmsnorm" or c.use_bias or c.tie_embeddings \
            or c.pos_embedding != ("rope" if roped else "none") \
            or c.loop_steps > 1 or c.block_pattern or c.attn_pattern:
        raise ValueError(
            "a mixer_pattern trunk has no position code (the KDA layers "
            "carry the order; beside attention='mla' with no index_pattern "
            "rope, on the 'A' layers' qk_rope_head_dim alone), RMSNorm, no "
            "biases, an untied head and a dense FFN or sigmoid-routed "
            "experts beside every mixer")
    if c.attention == "mha":
        # the solar_open2 block: "A" is the config's own GQA, gated
        if c.index_pattern or c.hc_mult > 1 or c.index_kpool > 1 \
                or c.v_head_dim not in (0, c.head_dim) \
                or c.n_head % c.kv_heads:
            raise ValueError(
                "mixer_pattern beside attention='mha' is the solar_open2 "
                "block: KDA layers beside softmax GQA over whole K/V planes "
                "under one residual stream — no index_pattern, hc_mult or "
                "index_kpool (the glm5_next_text block's, attention='mla'), "
                "values as wide as keys, whole groups of query heads")
        return
    if c.attention != "mla":
        raise ValueError(
            "mixer_pattern beside attention='mla' is the glm5_next_text "
            "block (KDA layers beside latent attention over an indexer's "
            "selection: index_pattern) or, with no index_pattern, the "
            "bailing_hybrid block; beside attention='mha' it is the "
            "solar_open2 block; no other attention stands beside KDA layers")
    if roped:
        return
    if "s" in c.index_pattern:
        raise ValueError(
            "index_pattern 's' (a layer that takes the selection of the one "
            "before it) beside a mixer_pattern: no published model has it, "
            "and this layer loop carries no selection from layer to layer")
    if any((m == "K") != (i == "-") for m, i in zip(pat, c.index_pattern)):
        raise ValueError(
            f"index_pattern {c.index_pattern!r} has to say '-' (no "
            f"attention) exactly where mixer_pattern {pat!r} says 'K'")
    if c.index_kpool < 1 or c.index_topk % c.index_kpool:
        raise ValueError("index_topk counts positions: a multiple of "
                         "index_kpool")


def dims(cfg) -> dict:
    inner = cfg.kda_heads * cfg.kda_head_dim
    return {"inner": inner, "conv": 3 * inner}


def state_shapes(cfg, batch: int) -> dict:
    """One layer's recurrent state: name -> shape (the slot first)."""
    H, D = cfg.kda_heads, cfg.kda_head_dim
    return {"kda": (batch, H, D, D),
            "conv": (batch, cfg.kda_conv - 1, dims(cfg)["conv"])}


def init_params(cfg, key, dense, n: int, depth: int) -> dict:
    """Stacked weights of ``n`` KDA layers. The decay at init is a trained
    mixer's, not a coin toss a channel. Behind a floor: ``A_log = log U(1,
    4)`` a head and ``dt_bias`` such that the gate's pre-activation stands
    in (-6, -1) before the input moves it by about one (``W_f2`` drawn a
    quarter wide), so a channel forgets over two to a few hundred tokens, as
    Mamba-2's ``dt`` is drawn (``models/ssm.py``), where a draw about zero
    saturates half the channels at the floor and half at none. With no floor
    (``kda_gate_floor`` 0) Kimi Linear's own: ``A_log = log U(1, 16)`` a
    head, ``dt_bias`` the inverse softplus of ``dt ~ logU(1e-3, 1e-1)``, and
    one head in eight (the last of a trunk with fewer) with a bias 6 higher:
    a trained gate saturates, and
    such a head's channels forget within a token or two (``g`` of -6 to
    -100), which is what no floor means and what the chunkwise form has to
    hold. ``W_beta``'s draw (sd 1 / sqrt(d): a pre-activation of sd 1 on a
    normed input) puts 14% of the sigmoids above 0.75: with
    ``kda_neg_eigval`` so many betas lie above 1.5. A path that drops the
    gate, the floor, the factor of beta or a conv still reads differently."""
    d, H, D, R, K = (cfg.d_model, cfg.kda_heads, cfg.kda_head_dim,
                     cfg.kda_rank, cfg.kda_conv)
    inner = H * D
    k = iter(jax.random.split(key, 12))
    bound = 1.0 / math.sqrt(K)

    def maps(name, scale=1.0):
        """The decay's or the gate's map d_model -> inner: a low-rank pair
        or (``kda_rank`` 0) one full matrix."""
        if not R:
            return {name: dense(next(k), (n, d, inner)) * scale}
        return {name + "1": dense(next(k), (n, d, R)),
                name + "2": dense(next(k), (n, R, inner)) * scale}

    if cfg.kda_gate_floor:
        A = jax.random.uniform(next(k), (n, H), jnp.float32, 1.0, 4.0)
        dt_bias = -jax.random.uniform(next(k), (n, H, D), jnp.float32, 1.0,
                                      6.0) / A[..., None]
    else:
        A = jax.random.uniform(next(k), (n, H), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(next(k), (n, H, D), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        dt_bias = dt + jnp.log(-jnp.expm1(-dt)) \
            + 6.0 * (jnp.arange(H) % 8 == min(7, H - 1))[None, :, None]
    out = {
        "kda_wqkv": dense(next(k), (n, d, 3 * inner)),
        "kda_conv_w": jax.random.uniform(next(k), (n, 3 * inner, K),
                                         jnp.float32, -bound, bound),
        "kda_wbeta": dense(next(k), (n, d, H)),
        **maps("kda_wf", 0.25),
        "kda_A_log": jnp.log(A),
        "kda_dt_bias": dt_bias.reshape(n, inner),
        **maps("kda_wg"),
        "kda_norm_scale": jnp.ones((n, D), jnp.float32),
        "wo": dense(next(k), (n, inner, d),
                    scale=1.0 / math.sqrt(2 * depth * inner)),
    }
    if cfg.kda_qk_norm:
        # a trained gain is not 1 a channel: drawn in (0.5, 1.5), so that a
        # path which drops it (the L2 norm behind it hides any constant)
        # reads differently
        out["kda_qk_scale"] = jax.random.uniform(
            next(k), (n, 2, D), jnp.float32, 0.5, 1.5)
    return out


def param_specs(cfg) -> dict:
    # whole on every device: the served trunk refuses a mesh
    three = P(None, None, None)
    maps = ("kda_wf1", "kda_wf2", "kda_wg1", "kda_wg2") if cfg.kda_rank \
        else ("kda_wf", "kda_wg")
    return {"kda_wqkv": three, "kda_conv_w": three, "kda_wbeta": three,
            "kda_A_log": P(None, None), "kda_dt_bias": P(None, None),
            "kda_norm_scale": P(None, None), "wo": three,
            **dict.fromkeys(maps, three),
            **({"kda_qk_scale": three} if cfg.kda_qk_norm else {})}


def step_kernel_ok(cfg, fused: bool) -> bool:
    """Whether the one-token step moves the state with the Pallas kernel
    (``ops/kda_step.py``: in place, running rows only)."""
    from ..ops.kda_step import kernel_fits

    return fused and kernel_fits(cfg.kda_heads, cfg.kda_head_dim)


def chunk_kernel_ok(cfg, fused: bool, T: int) -> bool:
    """Whether T > 1 tokens scan in the Pallas kernel (``ops/kda_chunk.py``:
    a block's decays, its ``(I + A)^-1`` and the carried state in VMEM):
    where the kind's kernels run (``fused``) and T is a whole number of
    blocks of :data:`CHUNK` of whole lane tiles of channels. A bucket of 8,
    16 or 32 keeps :func:`scan_chunked`: a block filled up costs the kernel
    what a whole one does, 0.18 ms, where the scan of so few tokens reads
    0.05 to 0.14 (PERF.md §6 "PR 61")."""
    from ..ops.kda_chunk import kernel_fits

    return bool(fused) and T > 1 and kernel_fits(T, cfg.kda_head_dim)


def chunk_scan_falls_back(cfg, fused: bool, T: int) -> bool:
    """A chunk whose shapes the kernel takes, scanned by XLA all the same:
    the kind's kernels do not run (``Serve/chunk_scan_fallback_builds``
    counts such a program where ``flash_decode`` is on)."""
    return not fused and chunk_kernel_ok(cfg, True, T)


# ---------------------------------------------------------------- the parts
def _gates(cfg, p, y):
    """y (B, T, d) -> (beta (B, T, H) f32 in (0, 1), or (0, 2) with
    ``kda_neg_eigval``; g (B, T, H, D) f32 in (floor, 0), or in (-inf, 0)
    where ``kda_gate_floor`` is 0; the output gate's pre-activation (B, T,
    H, D) f32). Every product
    leaves the MXU in float32, and the low-rank pairs' second product is a
    float32 one: a decay rounded to bf16 a token is an error of a few
    thousandths in its log that the running product of decays adds up over
    a channel's whole memory (a percent of a logit at five layers)."""
    H, D = cfg.kda_heads, cfg.kda_head_dim
    f32 = jnp.float32
    lead = y.shape[:-1]

    def through(name):
        """y through the decay's or the gate's map: a low-rank pair, or
        (``kda_rank`` 0) the one full matrix."""
        first = p[name + "1" if cfg.kda_rank else name]
        a = einsum_f32("btd,dr->btr", y, first.astype(y.dtype))
        if not cfg.kda_rank:
            return a
        return jnp.dot(a, p[name + "2"].astype(f32), precision=HI)

    beta = jax.nn.sigmoid(einsum_f32("btd,dh->bth", y,
                                     p["kda_wbeta"].astype(y.dtype)))
    if cfg.kda_neg_eigval:
        beta = 2.0 * beta
    f = (through("kda_wf")
         + p["kda_dt_bias"].astype(f32)).reshape(lead + (H, D))
    A = jnp.exp(p["kda_A_log"].astype(f32))[:, None]
    g = cfg.kda_gate_floor * jax.nn.sigmoid(A * f) if cfg.kda_gate_floor \
        else -A * jax.nn.softplus(f)
    return beta, g, through("kda_wg").reshape(lead + (H, D))


def _heads(cfg, p, u):
    """The convs' float32 output (..., 3 inner) after silu as q, k
    (L2-normed; with ``kda_qk_norm`` behind the layer's gain a channel) and
    v, each (..., H, D) float32."""
    H, D = cfg.kda_heads, cfg.kda_head_dim
    u = jax.nn.silu(u.astype(jnp.float32))
    q, k, v = (a.reshape(a.shape[:-1] + (H, D))
               for a in jnp.split(u, 3, axis=-1))
    if cfg.kda_qk_norm:
        gain = p["kda_qk_scale"].astype(jnp.float32)
        q, k = q * gain[0], k * gain[1]

    def l2(a):
        return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    return l2(q), l2(k), v


def _gate_out(cfg, p, o, z, dtype):
    """o, z (..., H, D) float32: the norm a head, the gate, the output
    projection in ``dtype``."""
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                      + cfg.norm_eps) * p["kda_norm_scale"].astype(jnp.float32)
    o = o * jax.nn.sigmoid(z.astype(jnp.float32))
    o = o.reshape(o.shape[:-2] + (-1,)).astype(dtype)
    return o @ p["wo"].astype(dtype)


# ------------------------------------------------------------ the recurrence
@jax.named_scope("kda_state_step")
def state_step(S, q, k, v, g, beta, live):
    """One token of the delta rule on a batch of slots, float32: S (B, H, D,
    D) keys x values, q / k / v / g (B, H, D), beta (B, H), ``live`` (B,)
    bool. Returns (o (B, H, D) = S_t^T q / sqrt(D), S_t); a row that is not
    live keeps its S."""
    D = S.shape[-1]
    Sd = jnp.exp(g)[..., None] * S
    r = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, Sd,
                                          precision=HI))
    new = Sd + k[..., None] * r[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, new, precision=HI) / math.sqrt(D)
    return o, jnp.where(live[:, None, None, None], new, S)


def _decayed(a, G, ref, hi: int):
    """``a * exp(G - ref)`` for the positions ``j < hi`` of a block (axis
    -3), 0 behind: the exponent is taken only where it is wanted, so none
    overflows."""
    keep = jnp.arange(a.shape[-3])[:, None, None] < hi
    return jnp.where(keep, a * jnp.exp(jnp.where(keep, G - ref, 0.0)), 0.0)


def _diagonal(rows, k, G):
    """A sub-block against itself: ``sum_d rows_i[d] k_j[d] exp(G_i[d] -
    G_j[d])`` for ``j <= i``, 0 above the diagonal — the decay formed pair
    by pair, channel by channel, before the sum over the channels
    (flash-linear-attention's way): ``G_i - G_j <= 0`` there whatever the
    gate, so nothing overflows and what underflows is 0. rows, k, G (..., SUB,
    H, D) -> (..., H, SUB, SUB)."""
    n = G.shape[-3]
    low = (jnp.arange(n)[:, None] >= jnp.arange(n)[None, :])[:, :, None, None]
    dec = jnp.exp(jnp.where(low, G[..., :, None, :, :] - G[..., None, :, :, :],
                            -jnp.inf))
    out = jnp.sum(rows[..., :, None, :, :] * k[..., None, :, :, :] * dec, -1)
    return jnp.moveaxis(out, -1, -3)


@jax.named_scope("kda_chunk_scan")
def scan_chunked(q, k, v, g, beta, S0):
    """The delta rule over T tokens in blocks of :data:`CHUNK`. q, k, v,
    g (B, T, H, D) float32, ``g <= 0`` and otherwise unbounded, beta (B, T,
    H) (a padded token: beta 0, g 0), S0 (B, H, D, D). Returns (o (B, T, H,
    D) float32, S_T).

    In a block with G the running sum of g and ``Gam = exp(G)``: ``u_i =
    beta_i (v_i - S_0^T (Gam_i k_i) - sum_{j<i} (Gam_i k_i . k_j / Gam_j)
    u_j)``, a unit lower triangular system ``(I + A) U = beta (V - K+ S_0)``
    (``(I + A)^-1`` by forward substitution on the identity, then a product);
    ``o_i = S_0^T (Gam_i q_i) + sum_{j<=i} (Gam_i q_i . k_j / Gam_j) u_j``;
    ``S_C = Gam_C S_0 + sum_j (Gam_C / Gam_j) k_j u_j^T``. ``Gam_i / Gam_j``
    is formed sub-block by sub-block of :data:`SUB`: against the columns
    BEFORE a sub-block both factors stand about the log decay before its
    first position (rows ``exp(G_i - ref)``, columns ``exp(ref - G_j)``,
    both exponents <= 0); inside the sub-block pair by pair
    (:func:`_diagonal`). No exponent is positive, so the form holds for any
    ``g <= 0`` — a gate with no floor, a channel that forgets within a
    token — where a factor ``1 / Gam_j`` about the sub-block's start would
    be ``exp(SUB |g|)``."""
    B, T, H, D = q.shape
    C = min(CHUNK, -(-T // SUB) * SUB)
    pad = -T % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (T + pad) // C
    q, k, v, g = (a.reshape(B, nc, C, H, D) for a in (q, k, v, g))
    beta = beta.reshape(B, nc, C, H)
    G = jnp.cumsum(g, axis=2)                       # (B, nc, C, H, D), <= 0
    A, QK = [], []
    for a in range(C // SUB):
        lo, hi = a * SUB, (a + 1) * SUB
        rows = slice(lo, hi)
        # the columns before the sub-block (none before the first)
        ref = G[:, :, lo - 1:lo] if a else jnp.zeros_like(G[:, :, :1])
        kn = _decayed(k, -G, -ref, lo)              # k_j Gam_ref / Gam_j
        kp = k[:, :, rows] * jnp.exp(G[:, :, rows] - ref)
        qp = q[:, :, rows] * jnp.exp(G[:, :, rows] - ref)
        for out, rp, r in ((A, kp, k), (QK, qp, q)):
            before = jnp.einsum("bcihd,bcjhd->bchij", rp, kn, precision=HI)
            out.append(before.at[..., rows].set(
                _diagonal(r[:, :, rows], k[:, :, rows], G[:, :, rows])))
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    bt = beta.transpose(0, 1, 3, 2)                 # (B, nc, H, C)
    A = jnp.where(j < i, jnp.concatenate(A, axis=3), 0.0) * bt[..., None]
    QK = jnp.where(j <= i, jnp.concatenate(QK, axis=3), 0.0)
    # (I + A)^-1 by forward substitution on the identity, for every block
    # at once, then ONE product with [beta V | beta K+]: a row of the
    # substitution reads the whole of what it builds, C columns a row here
    # where the right-hand sides have 2 D
    kplus = (k * jnp.exp(G)).transpose(0, 1, 3, 2, 4)        # (B, nc, H, C, D)
    rhs = jnp.concatenate([v.transpose(0, 1, 3, 2, 4), kplus], axis=-1) \
        * bt[..., None]
    eye = jnp.eye(C, dtype=A.dtype)

    def row(r, inv):
        new = lax.dynamic_index_in_dim(eye, r, 0, keepdims=False) \
            - jnp.einsum("bchj,bchjk->bchk",
                         lax.dynamic_index_in_dim(A, r, 3, keepdims=False),
                         inv, precision=HI)
        return lax.dynamic_update_index_in_dim(inv, new, r, 3)

    inv = lax.fori_loop(0, C, row, jnp.zeros_like(A))
    U = jnp.einsum("bchij,bchjd->bchid", inv, rhs, precision=HI)
    Uv, W = U[..., :D], U[..., D:]                  # T beta V, T beta K+
    qplus = (q * jnp.exp(G)).transpose(0, 1, 3, 2, 4)
    k_end = (k * jnp.exp(G[:, :, -1:] - G)).transpose(0, 1, 3, 2, 4)
    whole = jnp.exp(G[:, :, -1])                             # (B, nc, H, D)

    def block(S, xs):
        uv, w, qp, qk, ke, dec = xs
        u = uv - jnp.einsum("bhck,bhkv->bhcv", w, S, precision=HI)
        o = jnp.einsum("bhck,bhkv->bhcv", qp, S, precision=HI) \
            + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=HI)
        S = dec[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", ke, u,
                                            precision=HI)
        return S, o

    S_T, o = lax.scan(block, S0.astype(jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (Uv, W, qplus, QK, k_end, whole)))
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)       # (B, nc, C, H, D)
    return o.reshape(B, nc * C, H, D)[:, :T] / math.sqrt(D), S_T


def mix_chunk(cfg, p, y, S, conv, valid=None, fused: bool = False):
    """T tokens y (B, T, d) after the layer's norm; ``S`` (B, H, D, D)
    float32 and ``conv`` (B, K - 1, 3 inner) the state before them;
    ``valid`` (traced i32, None: T) how many are real. ``fused``: the Pallas
    kernel scans (:func:`chunk_kernel_ok`). Returns (out (B, T, d), S, conv)
    with the states as the last real token leaves them."""
    B, T, _ = y.shape
    K = cfg.kda_conv
    u = einsum_f32("btd,dc->btc", y, p["kda_wqkv"].astype(y.dtype))
    seq = jnp.concatenate([conv.astype(u.dtype), u], axis=1)
    wc = p["kda_conv_w"].astype(jnp.float32)                 # (3 inner, K)
    acc = 0.0
    for j in range(K):                  # out_t = sum_j w_j in_{t-(K-1)+j}
        acc = acc + seq[:, j:j + T] * wc[:, j]
    q, k, v = _heads(cfg, p, acc)
    beta, g, z = _gates(cfg, p, y)
    if valid is None:
        new_conv = seq[:, T:]
    else:
        real = jnp.arange(T)[None, :, None] < valid
        beta = jnp.where(real, beta, 0.0)
        g = jnp.where(real[..., None], g, 0.0)
        new_conv = lax.dynamic_slice_in_dim(seq, valid, K - 1, axis=1)
    if fused:
        from ..ops.kda_chunk import kda_chunk_scan

        o, S = kda_chunk_scan(q, k, v, g, beta, S)
    else:
        o, S = scan_chunked(q, k, v, g, beta, S)
    return _gate_out(cfg, p, o, z, y.dtype), S, new_conv.astype(conv.dtype)


def mix_step(cfg, p, y, S, W, layer, length, fused: bool):
    """One token y (B, 1, d) a slot against the carried state: ``S`` (L, B,
    H, D, D) float32 and ``W`` (L, B, K - 1, 3 inner), ``layer`` (traced
    i32) this layer's index in them; ``length`` (B,) i32 the slots' lengths,
    0 for a slot that is not running: its state and tails stay bit-equal.
    ``fused``: the Pallas kernel moves the state (:func:`step_kernel_ok`).
    Returns (out (B, 1, d), S, W)."""
    f32 = jnp.float32
    live = length > 0
    u = einsum_f32("btd,dc->btc", y, p["kda_wqkv"].astype(y.dtype))
    conv = lax.dynamic_index_in_dim(W, layer, keepdims=False)
    win = jnp.concatenate([conv.astype(f32), u], axis=1)     # (B, K, 3 inner)
    acc = jnp.sum(win * p["kda_conv_w"].astype(f32).T, axis=1)
    q, k, v = _heads(cfg, p, acc)
    beta, g, z = _gates(cfg, p, y)
    W = lax.dynamic_update_slice(W, jnp.where(
        live[:, None, None], win[:, 1:].astype(W.dtype), conv)[None],
        (layer, 0, 0, 0))
    if fused:
        from ..ops.kda_step import kda_state_step

        o, S = kda_state_step(S, layer, q, k, v, g[:, 0], beta[:, 0], length)
    else:
        o, new = state_step(lax.dynamic_index_in_dim(S, layer, keepdims=False),
                            q, k, v, g[:, 0], beta[:, 0], live)
        S = lax.dynamic_update_slice(S, new[None], (layer, 0, 0, 0, 0))
    return _gate_out(cfg, p, o[:, None], z, y.dtype), S, W


def mix(cfg, p, y, S, W, layer, lens, valid, fused: bool):
    """A KDA layer of a served trunk on y (B, T, d) against the carried
    buffers ``S`` (L, B, H, D, D) and ``W`` (L, B, K - 1, 3 inner) at
    ``layer``: :func:`mix_step` for T == 1 (``lens`` (B,)), :func:`mix_chunk`
    with XLA's updates else (``valid``). ``fused``: the kind's kernels run;
    whether this layer's does is told from the shapes
    (:func:`step_kernel_ok`, :func:`chunk_kernel_ok`). Returns (out, S, W):
    what every kind that holds KDA layers runs for one."""
    T = y.shape[1]
    if T == 1:
        return mix_step(cfg, p, y, S, W, layer, lens,
                        step_kernel_ok(cfg, fused))
    out, s_l, w_l = mix_chunk(
        cfg, p, y, lax.dynamic_index_in_dim(S, layer, keepdims=False),
        lax.dynamic_index_in_dim(W, layer, keepdims=False), valid,
        chunk_kernel_ok(cfg, fused, T))
    return (out, lax.dynamic_update_slice(S, s_l[None], (layer, 0, 0, 0, 0)),
            lax.dynamic_update_slice(W, w_l[None], (layer, 0, 0, 0)))


# ------------------------------------------------------------ full forward
def trunk(model, params, x, positions):
    """The layer stack on whole sequences, no cache handed in: the prefill
    of an empty one, through the very loop the served path runs (the
    config's kind, ``inference/kinds``), its kernels off. Returns (the
    stream (B, S, d), the expert layers' routing (layers, B, S, k))."""
    from ..inference.kinds import kind_of

    B, S, _ = x.shape
    kind = kind_of(model.cfg)
    pool = model.cfg.index_kpool
    max_len = -(-S // pool) * pool
    cache = kind.empty(B, max_len, x.dtype)
    x, _, stats, _ = kind.forward(model, params, x, cache,
                                  jnp.asarray(S, jnp.int32), positions, None,
                                  False)
    routing = stats[1]
    return x, routing[0] if isinstance(routing, tuple) else routing

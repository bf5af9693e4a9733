"""The Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs"; the layout of
NemotronH's public ``modeling_nemotron_h.py``), in the forms a served trunk
needs. They have to agree, and ``tests/unit/test_hybrid_trunk.py`` holds them
to the plain recurrence of ``benchmark/reference/nemotron_h.py``:

- :func:`mix_chunk`: T tokens that take a conv window and an SSM state in and
  hand both out — a prompt's chunk; the whole sequence is the same call from
  an empty state (``apply()``). The scan is the chunked (SSD) form in blocks
  of ``cfg.ssm_chunk``: inside a block the quadratic dual form, two matrix
  products; between blocks a recurrence over the block-end states.
  ``valid`` (traced) says how many of the T tokens are real: the positions a
  bucket pads behind a prompt get ``dt = 0``, which decays nothing and adds
  nothing, and the window handed out ends at the last real token.
- :func:`mix_step`: one token on a batch of slots. A row that is not
  ``live`` leaves its state and its window bit-equal.

    [z | xBC | dt] = y W_in;  xBC <- silu(conv1d_K(xBC) + b);  x, B, C = xBC
    dt <- softplus(dt + dt_bias);  A = -exp(A_log);  head h in group g:
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]   (P x N)
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
    out = RMS_groups(y * silu(z); gain) W_out

The state is float32 (the recurrence rounds once a token); everything that
multiplies it is float32 too. The window holds the LAST ``K - 1`` inputs of
the conv (before it), positions on the sublanes: ``(B, K - 1, C)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def dims(cfg) -> dict:
    """The mixer's widths from the configuration."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = cfg.ssm_groups * cfg.ssm_state
    return {"inner": inner, "bc": bc, "conv": inner + 2 * bc,
            "in": 2 * inner + 2 * bc + cfg.ssm_heads}


def state_shapes(cfg, batch: int) -> dict:
    """One layer's recurrent state: name -> shape (the slot first)."""
    return {"ssm": (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            "conv": (batch, cfg.ssm_conv - 1, dims(cfg)["conv"])}


def in_multipliers(cfg):
    """``cfg.mup.ssm`` spread over w_in's output columns [z | x | B | C |
    dt], float32 (in,); None where the model publishes none."""
    if not cfg.mup.ssm:
        return None
    w = dims(cfg)
    return jnp.repeat(
        jnp.asarray(cfg.mup.ssm, jnp.float32),
        jnp.asarray([w["inner"], w["inner"], w["bc"], w["bc"],
                     cfg.ssm_heads]), total_repeat_length=w["in"])


def init_params(cfg, key, n: int, depth: int) -> dict:
    """Stacked weights of ``n`` Mamba-2 layers. ``dt_bias`` is the inverse
    softplus of a dt drawn log-uniform in ``ssm_dt_init`` (min, max), floored;
    ``A_log = log U(1, 16)``; ``D = 1``; the conv as ``nn.Conv1d`` draws it;
    the output projection scaled down by the depth (one branch a layer).
    Under ``cfg.mup`` a projection is drawn over its multipliers, so that
    what reaches the conv, the gate, dt and the stream is what it is
    without them."""
    d, H, K = cfg.d_model, cfg.ssm_heads, cfg.ssm_conv
    w = dims(cfg)
    mup, cols = cfg.mup, in_multipliers(cfg)
    k = iter(jax.random.split(key, 6))
    lo, hi, floor = cfg.ssm_dt_init
    dt = jnp.exp(jax.random.uniform(next(k), (n, H), jnp.float32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    dt = jnp.maximum(dt, floor)
    bound = 1.0 / math.sqrt(K)
    return {
        "ln1_scale": jnp.ones((n, d), jnp.float32),
        "w_in": jax.random.normal(next(k), (n, d, w["in"]), jnp.float32)
        / (math.sqrt(d) * mup.ssm_in * (1.0 if cols is None else cols)),
        "conv_w": jax.random.uniform(next(k), (n, w["conv"], K), jnp.float32,
                                     -bound, bound),
        "conv_b": jax.random.uniform(next(k), (n, w["conv"]), jnp.float32,
                                     -bound, bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(next(k), (n, H), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((n, H), jnp.float32),
        "ssm_norm_scale": jnp.ones((n, w["inner"]), jnp.float32),
        "w_out": jax.random.normal(next(k), (n, w["inner"], d), jnp.float32)
        / (math.sqrt(depth * w["inner"]) * mup.ssm_out),
    }


# leaves the engine's compute cast leaves in float32: they steer the decay
FP32_NAMES = ("dt_bias", "A_log", "D")


def step_kernel_ok(cfg, fused: bool) -> bool:
    """Whether the one-token step moves the state with the Pallas kernel
    (``ops/ssm_step.py``: in place, running rows only) rather than with the
    XLA form below: where the decode kernels run (``fused``, the answer of
    ``inference/kinds/steps.py`` ``_decode_kernel_ok``) and the shapes fit."""
    from ..ops.ssm_step import kernel_fits

    return fused and kernel_fits(cfg.ssm_heads, cfg.ssm_groups,
                                 cfg.ssm_head_dim, cfg.ssm_state)


def step_block_bytes(cfg) -> int:
    """The float32 bytes of state one program of the step's kernel takes
    (``ops/ssm_step.py`` ``groups_per_program``): ``ssm_block_bytes`` of the
    ``decode_step`` spans. From the configuration, no device read."""
    from ..ops.ssm_step import groups_per_program

    H, G, P, N = (cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim,
                  cfg.ssm_state)
    return groups_per_program(H, G, P, N) * (H // G) * P * N * 4


def _project(cfg, p, y):
    """y (B, T, d) -> z (B, T, inner), xBC (B, T, conv) before the conv,
    dt (B, T, H) float32 after the softplus."""
    w = dims(cfg)
    if cfg.mup.ssm_in != 1.0:
        y = y * jnp.asarray(cfg.mup.ssm_in, y.dtype)
    u = y @ p["w_in"].astype(y.dtype)
    cols = in_multipliers(cfg)
    if cols is not None:
        u = (u.astype(jnp.float32) * cols).astype(u.dtype)
    z, xbc, dt = jnp.split(u, [w["inner"], w["inner"] + w["conv"]], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return z, xbc, dt


def _split(cfg, xbc):
    """The conv's output (..., conv) as x (..., H, P), B, C (..., G, N)."""
    w = dims(cfg)
    x, b, c = jnp.split(xbc, [w["inner"], w["inner"] + w["bc"]], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
            b.reshape(lead + (cfg.ssm_groups, cfg.ssm_state)),
            c.reshape(lead + (cfg.ssm_groups, cfg.ssm_state)))


def _gate_out(cfg, p, y, z):
    """y (..., H, P) float32, z (..., inner): gate, the norm over each of
    the G groups of channels, the output projection."""
    inner = dims(cfg)["inner"]
    lead = z.shape[:-1]
    g = y.reshape(lead + (inner,)) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(lead + (cfg.ssm_groups, inner // cfg.ssm_groups))
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                      + cfg.norm_eps)
    g = g.reshape(lead + (inner,)) * p["ssm_norm_scale"].astype(jnp.float32)
    return g.astype(z.dtype) @ p["w_out"].astype(z.dtype)


@jax.named_scope("ssm_chunk_scan")
def scan_chunked(cfg, x, dt, A, Bm, Cm, S0):
    """The recurrence over T tokens in blocks of ``cfg.ssm_chunk``.
    x (B, T, H, P), dt (B, T, H) float32 (0 where a token is padding),
    A (H,) float32 negative, Bm / Cm (B, T, G, N), S0 (B, H, P, N) float32.
    Returns (y (B, T, H, P) float32 without the D term, S_T)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    Q = min(cfg.ssm_chunk, T)
    pad = -T % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    nc, hg = (T + pad) // Q, H // G
    f32 = jnp.float32
    x = x.reshape(B, nc, Q, G, hg, P)
    dt = dt.reshape(B, nc, Q, G, hg)
    Bm, Cm = (m.reshape(B, nc, Q, G, N) for m in (Bm, Cm))
    cum = jnp.cumsum(dt * A.reshape(G, hg), axis=2)         # log decay so far
    # inside a block: y_q = sum_{s <= q} exp(cum_q - cum_s) (C_q . B_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cm, Bm, preferred_element_type=f32)
    diff = cum[:, :, :, None] - cum[:, :, None]             # (B,nc,q,s,G,hg)
    keep = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    w = jnp.exp(jnp.where(keep, diff, -jnp.inf)) * dt[:, :, None]
    w = w * cb.transpose(0, 1, 3, 4, 2)[..., None]
    y = jnp.einsum("bcqsgh,bcsghp->bcqghp", w.astype(x.dtype), x,
                   preferred_element_type=f32)
    # what a block adds to the state by its end
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt             # (B,nc,Q,G,hg)
    add = jnp.einsum("bcsgn,bcsghp->bcghpn", Bm.astype(f32),
                     x.astype(f32) * to_end[..., None],
                     precision=lax.Precision.HIGHEST)
    whole = jnp.exp(cum[:, :, -1])                          # (B,nc,G,hg)

    def boundary(S, blk):
        dec, a = blk
        return dec[..., None, None] * S + a, S

    S_T, starts = lax.scan(
        boundary, S0.reshape(B, G, hg, P, N).astype(f32),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(add, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                     # (B,nc,G,hg,P,N)
    y = y + jnp.einsum("bcqgn,bcghpn->bcqghp", Cm.astype(f32), starts,
                       precision=lax.Precision.HIGHEST) \
        * jnp.exp(cum)[..., None]
    return (y.reshape(B, nc * Q, H, P)[:, :T], S_T.reshape(B, H, P, N))


def mix_chunk(cfg, p, y, ssm, conv, valid=None):
    """T tokens y (B, T, d) after the layer's norm; ``ssm`` (B, H, P, N)
    float32 and ``conv`` (B, K - 1, C) the state before them; ``valid``
    (traced i32, None: T) how many are real. Returns (out (B, T, d), ssm,
    conv) with the states as the last real token leaves them."""
    B, T, _ = y.shape
    K = cfg.ssm_conv
    z, xbc, dt = _project(cfg, p, y)
    seq = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)
    wc = p["conv_w"].astype(jnp.float32)                    # (C, K)
    acc = p["conv_b"].astype(jnp.float32)
    for j in range(K):                  # out_t = b + sum_j w_j in_{t-(K-1)+j}
        acc = acc + seq[:, j:j + T].astype(jnp.float32) * wc[:, j]
    x, Bm, Cm = _split(cfg, jax.nn.silu(acc).astype(y.dtype))
    if valid is None:
        new_conv = seq[:, T:]
    else:
        dt = jnp.where(jnp.arange(T)[None, :, None] < valid, dt, 0.0)
        new_conv = lax.dynamic_slice_in_dim(seq, valid, K - 1, axis=1)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    out, new_ssm = scan_chunked(cfg, x, dt, A, Bm, Cm, ssm)
    out = out + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return (_gate_out(cfg, p, out, z), new_ssm.astype(ssm.dtype),
            new_conv.astype(conv.dtype))


@jax.named_scope("ssm_state_step")
def state_step(S, x, dt, A, Bv, Cv, live):
    """One token of the recurrence on a batch of slots, float32: S (B, H, P,
    N), x (B, H, P), dt (B, H), A (H,), Bv / Cv (B, G, N), ``live`` (B,)
    bool. Returns (y (B, H, P) without the D term, S); a row that is not
    live keeps its S."""
    B, H, P, N = S.shape
    G = Bv.shape[1]
    hg = H // G
    S5 = S.reshape(B, G, hg, P, N)
    dec = jnp.exp(dt * A).reshape(B, G, hg, 1, 1)
    add = (dt[..., None] * x).reshape(B, G, hg, P, 1) \
        * Bv[:, :, None, None, :]
    new = dec * S5 + add
    y = jnp.sum(new * Cv[:, :, None, None, :], axis=-1)
    new = jnp.where(live[:, None, None, None, None], new, S5)
    return y.reshape(B, H, P), new.reshape(B, H, P, N)


def mix_step(cfg, p, y, S, W, layer, length, fused: bool):
    """One token y (B, 1, d) a slot against the carried state: ``S`` (L, B,
    H, P, N) float32 and ``W`` (L, B, K - 1, C), ``layer`` (traced i32) this
    layer's index in them; ``length`` (B,) i32 the slots' lengths, 0 for a
    slot that is not running: its state and window stay bit-equal.
    ``fused``: the Pallas kernel moves the state (:func:`step_kernel_ok`).
    Returns (out (B, 1, d), S, W)."""
    f32 = jnp.float32
    live = length > 0
    z, xbc, dt = _project(cfg, p, y)
    conv = lax.dynamic_index_in_dim(W, layer, keepdims=False)
    win = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)   # (B, K, C)
    acc = jnp.einsum("bkc,ck->bc", win.astype(f32),
                     p["conv_w"].astype(f32)) + p["conv_b"].astype(f32)
    x, Bm, Cm = _split(cfg, jax.nn.silu(acc).astype(y.dtype))
    W = lax.dynamic_update_slice(W, jnp.where(
        live[:, None, None], win[:, 1:].astype(W.dtype), conv)[None],
        (layer, 0, 0, 0))
    A = -jnp.exp(p["A_log"].astype(f32))
    args = (x.astype(f32), dt[:, 0], A, Bm.astype(f32), Cm.astype(f32))
    if fused:
        from ..ops.ssm_step import ssm_state_step

        out, S = ssm_state_step(S, layer, *args, length)
    else:
        out, new = state_step(
            lax.dynamic_index_in_dim(S, layer, keepdims=False), *args, live)
        S = lax.dynamic_update_slice(S, new[None], (layer, 0, 0, 0, 0))
    out = out + p["D"].astype(f32)[:, None] * x.astype(f32)
    return _gate_out(cfg, p, out[:, None], z), S, W

"""The steps every cache kind's layer loop shares: the attention over a
cache and its one gate, the dense and the paged append, the plain layer.
Below the kinds and ``decode.py``, which both import it."""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...models.transformer import _activation, _norm, _rope, alibi_slopes
from ...ops import decode_attention as da     # (tests patch its function)
from ...ops.sparse_mla_attention import einsum_f32
from ...platform.mesh import BATCH_AXES, constrain
from ..quantization import QuantizedTensor, matmul_any, tp_quant_dot


def quantize_kv(x, axis: int = -1):
    """Symmetric int8 quantization of appended KV values: one fp32 scale
    per token per head over the ``hd`` axis. ``quantize → dequantize →
    quantize`` is idempotent at these scales (the max element round-trips
    to exactly ±127), which is what lets a hydrated shared prefix re-insert
    without drift."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.expand_dims(scale, axis)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype, axis: int = -1):
    """Inverse of :func:`quantize_kv` at the point of use — the ONE
    spelling shared by the shared-prefix hydrate gather and the host-tier
    restore scatter, so a page's bytes dequantize identically whether they
    come from the live pool or from pinned host memory."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale, axis)).astype(dtype)


def paged_append(ck, cv, ks, vs, k, v, page_table, new_len):
    """Append T decode tokens' K/V per slot into one layer's pools ``ck`` /
    ``cv`` ``(pages, KV, page_size, hd)``: ``k``/``v`` the new projections
    ``(B, T, KV, hd)``, ``new_len`` the (B,) post-append lengths. Row
    ``b``'s token ``j`` writes position ``new_len[b] - T + j``, which maps
    through its ``page_table`` row to (pool page, in-page offset) — one
    scatter per pool. T > 1 is the speculative verify forward, whose
    headroom gate keeps every live row at ``new_len <= max_len``: the clip
    below never folds a live write back onto the row's last page. A row
    that is not running has ``new_len`` 0 and writes nowhere: its page id
    is put behind the pool and the scatter drops it, whatever its table
    row still holds."""
    B, T = k.shape[0], k.shape[1]
    ps, n = ck.shape[2], page_table.shape[1]
    pos = (new_len - T)[:, None] + jnp.arange(T, dtype=new_len.dtype)[None, :]
    pidx = jnp.clip(pos // ps, 0, n - 1)
    pid = jnp.take_along_axis(page_table, pidx, axis=1)     # (B, T)
    pid = jnp.where((new_len > 0)[:, None], pid, ck.shape[0])
    off = pos % ps
    if ks is not None:
        qk, sk = quantize_kv(k)
        qv, sv = quantize_kv(v)
        ck = ck.at[pid, :, off, :].set(qk, mode="drop")
        cv = cv.at[pid, :, off, :].set(qv, mode="drop")
        ks = ks.at[pid, :, off].set(sk, mode="drop")
        vs = vs.at[pid, :, off].set(sv, mode="drop")
    else:
        ck = ck.at[pid, :, off, :].set(k.astype(ck.dtype), mode="drop")
        cv = cv.at[pid, :, off, :].set(v.astype(cv.dtype), mode="drop")
    return ck, cv, ks, vs


def paged_view(cp, sp, page_table, dtype):
    """Gather one layer's pool pages into the slot batch's contiguous
    attention view ``(B, KV, hd, max_len)``. Page ids are data, not shapes:
    traffic churn never changes the program. An int8 pool dequantizes here,
    at the point of use: the fp path's gathered bytes are bit-identical to
    the contiguous cache and no dequantized pool is ever materialized."""
    g = cp[page_table]                             # (B, n, KV, ps, hd)
    B, n, KV, ps, hd = g.shape
    g = g.transpose(0, 2, 4, 1, 3).reshape(B, KV, hd, n * ps)
    if sp is not None:
        s = sp[page_table].transpose(0, 2, 1, 3).reshape(B, KV, 1, n * ps)
        g = (g.astype(jnp.float32) * s).astype(dtype)
    return g


def _decode_kernel_ok(flash_decode: bool, T: int, max_len: int,
                      *dtypes) -> bool:
    """Whether a T-token forward over a cache of ``max_len`` positions runs
    the Pallas decode kernels (``ops/decode_attention.py``) — the ONE gate
    ``forward_with_cache`` and :func:`_cache_attend` share."""
    # Mosaic has no f16: an fp16 engine (or an externally-built fp16 KV
    # cache under a bf16 trunk) takes the dense path on TPU instead of
    # failing Mosaic compilation inside the decode scan
    f16_in = any(jnp.dtype(d) == jnp.float16 for d in dtypes) \
        and jax.default_backend() == "tpu"
    if f16_in and flash_decode:
        from ...utils.logging import warning_once

        warning_once(
            "decode: float16 q/KV-cache falls back to the dense XLA "
            "cache attention on TPU (Mosaic has no f16). The dense "
            "path materializes (B, H, 1, max_len) scores per step — "
            "prefer bf16 compute for long generations.")
    # TPU lane tiling wants full 128-wide blocks: generate_tokens pads the
    # cache to a 128 multiple when flash_decode is on, so this only
    # declines externally-built odd caches
    return (flash_decode and not f16_in and T == 1 and max_len % 128 == 0)


def _cache_attend(q, ck, cv, length, flash_decode: bool = False, alibi=None):
    """q: (B, T, H, hd) vs cache (B, KV, hd, max_len); positions >= length
    masked. For prefill T = prompt len (with causal offset); decode T = 1.

    ``length`` is a scalar (all rows at the same position — the
    single-request generate() path) or a (B,) vector (the serving slot
    batch, every slot at its own position): the same expressions with a
    batch dim on the position grid; masked scores underflow to exactly 0
    after softmax, so a row's output depends only on its own live positions.

    ``alibi`` is the (H,) slope vector: the streaming kernel rebuilds the
    distance ramp in-kernel, so Bloom decode stays on the fused path.
    ``flash_decode`` routes the T == 1 hot path to the Pallas kernel instead
    of materializing the full (B, H, 1, max_len) score tensor."""
    B, T, H, hd = q.shape
    max_len = ck.shape[3]
    if _decode_kernel_ok(flash_decode, T, max_len, q.dtype, ck.dtype,
                         cv.dtype):
        return da.decode_attention(q, ck, cv, length, alibi_slopes=alibi)
    KV = ck.shape[1]
    if KV != H:
        ck = jnp.repeat(ck, H // KV, axis=1)
        cv = jnp.repeat(cv, H // KV, axis=1)
    scores = jnp.einsum("bthd,bhds->bhts", q, ck).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if getattr(length, "ndim", 0) == 1:
        # per-slot lengths: the position grid gains a batch dim
        t_pos = length[:, None, None] - T \
            + jnp.arange(T)[None, :, None]               # (B, T, 1)
        s_pos = jnp.arange(max_len)[None, None, :]       # (1, 1, max_len)
        if alibi is not None:
            rel = (s_pos - t_pos).astype(jnp.float32)    # (B, T, max_len)
            scores = scores + alibi[None, :, None, None] * rel[:, None]
        keep = s_pos <= t_pos                            # (B, T, max_len)
        scores = jnp.where(keep[:, None], scores, da.BIG_NEG)
    else:
        # query t sits at global position length - T + t; key at slot s —
        # ONE set of position math drives both the alibi bias and the mask
        t_pos = length - T + jnp.arange(T)[:, None]      # (T, 1)
        s_pos = jnp.arange(max_len)[None, :]             # (1, max_len)
        if alibi is not None:
            rel = (s_pos - t_pos).astype(jnp.float32)    # (T, max_len)
            scores = scores + (alibi[:, None, None] * rel[None])[None]
        keep = s_pos <= t_pos                            # (T, max_len)
        scores = jnp.where(keep[None, None], scores, da.BIG_NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhds->bthd", probs, cv)


def _dense_append(cache, new, layer, length):
    """Write T new positions ``new`` (B, T, KV, hd) into layer ``layer`` of
    the carried cache ``(L, B, KV, hd, max_len)`` with XLA's own update,
    ending at ``length`` (scalar, or (B,) per slot). Returns (the layer's
    slab ``(B, KV, hd, max_len)`` to attend over, the cache)."""
    T = new.shape[1]
    start = length - T     # positions [start, start + T) get the new values
    new = new.transpose(0, 2, 3, 1).astype(cache.dtype)     # (B, KV, hd, T)
    if getattr(length, "ndim", 0) == 0:
        cache = lax.dynamic_update_slice(cache, new[None],
                                         (layer, 0, 0, 0, start))
        return lax.dynamic_index_in_dim(cache, layer, keepdims=False), cache
    # per-slot write positions: one dynamic_update_slice per row via vmap
    # (lowers to a scatter). On the layer's slab: a scatter into the carried
    # cache itself makes the compiler re-lay the WHOLE cache out
    slab = jax.vmap(lambda c, u, s: lax.dynamic_update_slice(c, u, (0, 0, s)))(
        lax.dynamic_index_in_dim(cache, layer, keepdims=False), new, start)
    return slab, lax.dynamic_update_slice(cache, slab[None],
                                          (layer, 0, 0, 0, 0))


def _append_attend(q, ck, cv, k, v, layer, length, fused: bool, alibi=None,
                   name: str = "decode_attention", tail=None):
    """The T new positions ``k`` / ``v`` into layer ``layer`` of the carried
    planes, and ``q`` over that layer: where the gate said so (``fused``)
    the decode kernel, which appends and attends in place, under ``name``;
    else XLA's update and the layer's slab densely. ``tail``: the deferred
    tail of a kind that keeps one (``kinds/dense.py``), which the kernel
    alone reads and writes: the dense path needs the planes settled and
    leaves them so (the kind fills the tail again behind its layer loop).
    Returns (o, ck, cv), and the tail behind them where one came."""
    tails = () if tail is None else (tail,)
    if fused:
        return da.decode_attention(q, ck, cv, length, k=k, v=v, layer=layer,
                                   alibi_slopes=alibi, name=name, tail=tail)
    slab_k, ck = _dense_append(ck, k, layer, length)
    slab_v, cv = _dense_append(cv, v, layer, length)
    return (_cache_attend(q, slab_k, slab_v, length, alibi=alibi), ck, cv,
            *tails)


def _tp_quant_eligible(model, p, T: int) -> int:
    """int8 bits when the quantized TP decode collective applies to this
    step, else 0. Gates: the engine opted in (``tp_comm_quant``, stamped
    on the model like ``woq_kernel``), T == 1 (prefill is compute-bound
    and pays the psum once per request), and the row-sharded projections
    are DENSE (a WOQ ``QuantizedTensor`` reduces inside its own shard_map
    and keeps the fp wire there). ``tp_quant_dot`` itself declines meshes
    without a ``model`` axis: a TP=1 engine with the knob on compiles the
    identical program."""
    bits = int(getattr(model, "tp_quant", 0) or 0)
    if not bits or T != 1:
        return 0
    if isinstance(p.get("wo"), QuantizedTensor):
        return 0
    return bits


def _mlp_tp_quant(model, y, p, bits: int):
    """The dense-MLP half of a decode step with the ``w_out`` model-axis
    partial-sum reduction quantized (two-sided int8) — the same math as
    ``TransformerLM._mlp_block`` (decode never remats, so the
    checkpoint-name tags there are identities this spelling drops).
    Falls back to the model's own block when the explicit spelling
    doesn't apply (no TP mesh, uneven shards, quantized w_out)."""
    cfg = model.cfg
    if isinstance(p.get("w_out"), QuantizedTensor):
        return model._mlp_block(y, p)
    u = model._maybe_bias(model._proj(y, p, "w_in"), p, "b_in")
    if cfg.is_glu:
        u = jax.nn.silu(model._proj(y, p, "w_gate")) * u
    else:
        u = _activation(u, cfg.activation)
    u = constrain(u, P(BATCH_AXES, "seq", "model"))
    out = tp_quant_dot(u, p["w_out"], bits=bits)
    if out is None:
        out = model._proj(u, p, "w_out")
    return model._maybe_bias(out, p, "b_out"), jnp.float32(0.0)


def _qkv_proj(model, y, p):
    """The attention projections as ONE GEMM when the engine pre-fused
    them (``wqkv`` = [wq | wk | wv] along the output dim, ``bqkv``
    likewise): one weight stream, one MXU dispatch, one bias add instead of
    three skinny dots over the same activations. Falls back to the
    per-projection weights for unfused trees (training params via
    HybridEngine, external callers)."""
    cfg = model.cfg
    B, T, _ = y.shape
    h, kv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    use_kernel = getattr(model, "woq_kernel", False)
    if "wqkv" in p:
        qkv = matmul_any(y, p["wqkv"], use_kernel=use_kernel)
        if cfg.use_bias and "bqkv" in p:
            qkv = qkv + p["bqkv"].astype(qkv.dtype)
        q, k, v = jnp.split(qkv, [h * hd, (h + kv) * hd], axis=-1)
    else:
        q = model._maybe_bias(matmul_any(y, p["wq"], use_kernel), p, "bq")
        k = model._maybe_bias(matmul_any(y, p["wk"], use_kernel), p, "bk")
        v = model._maybe_bias(matmul_any(y, p["wv"], use_kernel), p, "bv")
    return (q.reshape(B, T, h, hd), k.reshape(B, T, kv, hd),
            v.reshape(B, T, kv, hd))


@jax.named_scope("decode_layer")
def _layer_step(model, x, p, cache_k, cache_v, length, positions,
                flash_decode: bool = False, paged=None, layer=None,
                tail=None):
    """One transformer layer over x: (B, T, d), reading/writing the cache.

    Returns (x_out, new_cache_k, new_cache_v) — plus the new scale pools
    when ``paged`` is set. Mirrors ``TransformerLM._attention_block`` /
    ``_mlp_block`` with cache attention substituted for the full causal
    attention. Weights may arrive dense OR quantized (``QuantizedTensor``
    leaves): every projection goes through the point-of-use dispatch, so
    quantized decode re-reads int8 bytes from HBM each step — never a
    hoisted bf16 copy.

    ``paged`` is ``(page_table, k_scale, v_scale)`` for the pooled page
    layout: the append scatters through the page table and the read
    gathers the slot's pages back into the contiguous view — same values,
    same mask math, so the fp paged step is bit-identical to the
    contiguous one. Without it, ``cache_k``/``cache_v`` are the WHOLE
    carried ``(L, B, KV, hd, max_len)`` cache, ``layer`` (traced i32) this
    layer's index in it and ``flash_decode`` the gate's answer
    (:func:`_decode_kernel_ok`): the decode kernel appends and attends in
    place, by layer index. ``tail``: the cache's deferred tail where its
    kind keeps one (:func:`_append_attend`), returned last.
    """
    cfg = model.cfg
    B, T, d = x.shape
    h, kv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim

    y = _norm(x, p["ln1_scale"], p.get("ln1_bias"), cfg.norm, cfg.norm_eps)
    q, k, v = _qkv_proj(model, y, p)
    if cfg.pos_embedding == "rope":
        q, k = _rope(q, k, positions, cfg.rope_theta, cfg.rotary_dim)

    alibi = None
    if cfg.pos_embedding == "alibi":
        # ALiBi positional signal (mirrors _attention_block's training
        # bias): passed as SLOPES — the streaming decode kernel rebuilds
        # the distance ramp in-kernel, the dense fallback materializes it.
        alibi = alibi_slopes(h)
    scale_k = scale_v = None
    if paged is None:
        o, cache_k, cache_v, *tails = _append_attend(
            q, cache_k, cache_v, k, v, layer, length, flash_decode, alibi,
            tail=tail)
    else:
        page_table, scale_k, scale_v = paged
        cache_k, cache_v, scale_k, scale_v = paged_append(
            cache_k, cache_v, scale_k, scale_v, k, v, page_table, length)
        o = _cache_attend(
            q, paged_view(cache_k, scale_k, page_table, cfg.dtype),
            paged_view(cache_v, scale_v, page_table, cfg.dtype), length,
            flash_decode=flash_decode, alibi=alibi)
    # Quantized TP decode collective (inference.tp_comm_quant): the wo
    # and dense-MLP w_out partial-sum reductions spell as explicit
    # two-sided int8 all-reduces. 0 (default) keeps this path bit-frozen
    # on the GSPMD fp psum.
    tpq = _tp_quant_eligible(model, p, T)
    o_flat = o.reshape(B, T, h * hd)
    o = tp_quant_dot(o_flat, p["wo"], bits=tpq) if tpq else None
    if o is None:
        o = matmul_any(o_flat, p["wo"],
                       use_kernel=getattr(model, "woq_kernel", False))
    o = model._maybe_bias(o, p, "bo")
    # MoE trunks expose a single-group no-drop dispatch (_mlp_block_infer,
    # models/moe.py) for the T=1 decode step; prefill (T>1) and dense
    # trunks use the training MLP unchanged (per-row grouping keeps
    # prefill's dispatch one-hots at the training memory profile).
    moe_infer = getattr(model, "_mlp_block_infer", None) if T == 1 else None
    mlp = moe_infer or model._mlp_block
    if tpq and moe_infer is None:
        mlp = partial(_mlp_tp_quant, model, bits=tpq)
    if cfg.parallel_residual:
        y2 = y if cfg.parallel_shared_ln else _norm(
            x, p["ln2_scale"], p.get("ln2_bias"), cfg.norm, cfg.norm_eps)
        out, _aux = mlp(y2, p)
        x = x + o + out
    else:
        # (sandwich norms and residual scales, if any)
        x = model._residual(x, model._post_norm(o, p, "ln1"), p, 0)
        y2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), cfg.norm,
                   cfg.norm_eps)
        out, _aux = mlp(y2, p)
        x = model._residual(x, model._post_norm(out, p, "ln2"), p, 1)
    if paged is not None:
        return x, cache_k, cache_v, scale_k, scale_v
    return (x, cache_k, cache_v, *tails)


def _out_gate(cfg, p, y, o):
    """The heads' output ``o`` (B, T, H, vd) times ``sigmoid(y w_ogate)`` off
    the layer's normed input ``y`` (``attn_out_gate``, arXiv:2505.06708): a
    value a head a channel, or with ``'head'`` one a head; the product in
    float32. Returns (B, T, H vd)."""
    B, T, _, vd = o.shape
    dt = y.dtype
    o = o.reshape(B, T, -1)
    with jax.named_scope("attn_out_gate"):
        gate = jax.nn.sigmoid(einsum_f32("btd,dc->btc", y.astype(dt),
                                         p["w_ogate"].astype(dt)))
        if cfg.attn_out_gate == "head":
            gate = jnp.repeat(gate, vd, axis=-1)
        return (o.astype(jnp.float32) * gate).astype(dt)


def _out_ffn(model, x, o, p, banks, layer, sorted_rows: bool = True):
    """The attention's output ``o`` (B, T, H, vd) through ``wo`` onto the
    stream, then the layer's FFN (:func:`_ffn`)."""
    B, T, _ = x.shape
    x = x + matmul_any(o.reshape(B, T, -1), p["wo"], use_kernel=False)
    return _ffn(model, x, p, banks, layer, sorted_rows)


def _ffn(model, x, p, banks, layer, sorted_rows: bool = True, **segment):
    """The layer's FFN onto the stream: the sorted expert rows where it has
    a router (``banks`` / ``layer``: the segment's stacked expert weights and
    this layer's index in them; ``segment``: its clamps and the rows that
    are live, ``MoETransformerLM.experts``' ``limits`` / ``live``), else the
    dense block. Returns (x, (the expert layer's counters, the experts
    chosen (B, T, k))), zeros if dense."""
    cfg = model.cfg
    B, T, _ = x.shape
    y2 = _norm(x, p["ln2_scale"], None, cfg.norm, cfg.norm_eps)
    if "router" in p and sorted_rows:
        out, stats, chose = model.experts(y2, p, banks=banks, layer=layer,
                                          **segment)
    else:
        out, stats = model._mlp_block(y2, p)[0], jnp.zeros((4,), jnp.float32)
        chose = jnp.zeros((B, T, 0), jnp.int32)
    return x + out, (stats, chose)


def _run(body, carry, seg, n: int, first: int):
    """``body(carry, layer weights, index)`` over a run of ``n`` layers
    stacked in ``seg``, ``first`` the run's first index in its kind's
    buffers: a scan, or the body itself with a static index for a run of
    one (its slices of the carried buffers are then static too)."""
    if n == 1:
        carry, out = body(carry, jax.tree.map(lambda a: a[0], seg), first)
        return carry, jax.tree.map(lambda a: a[None], out)
    return lax.scan(lambda c, xs: body(c, *xs), carry,
                    (seg, jnp.arange(first, first + n, dtype=jnp.int32)))

"""Prefill + single-token decode with a static-shape KV cache.

Reference analog: the fused inference kernels and KV-cache workspace of
``csrc/transformer/inference/`` (``softmax_context`` = attention over the
cache, ``inference_context.h`` = the cache allocator). TPU-native: attention
over the cache masks positions beyond the current length, so every decode
step has an identical static shape (one compiled program for the whole
generation). The T == 1 step carries the cache through its layer loop as
ONE donated buffer that only one kernel touches, in place
(``ops/decode_attention.py``); T > 1 (prefill, speculative verify) appends
with ``dynamic_update_slice`` and attends densely over the same layout.

The model's config decides the cache, and everything that differs with it
lives in one file a kind (``kinds/``; docs/SERVING.md, "Cache kinds"), over
the steps they share (``kinds/steps.py``). Here: the embedding, the head,
:func:`forward_with_cache` (embed -> ``kind.forward`` -> head) and the
generation loop.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models.transformer import TransformerConfig, _norm
from ..platform.mesh import BATCH_AXES, constrain
from .kinds import (CCACache, HybridCache, KVCache, LatentCache,  # noqa: F401
                    PagedKVCache, ParallelCache, WindowedCache, kind_of)
from .kinds.steps import (_cache_attend, _decode_kernel_ok,  # noqa: F401
                          dequantize_kv, quantize_kv)
from .quantization import (QuantizedTensor, dequant_rows, woq_dot,
                           woq_dot_t)


# The layout functions answer from the kind: kept here for their callers.
def cache_layout(cfg: TransformerConfig, batch: int, max_len: int,
                 dtype=None, *, page_size: int = 0, pages: int = 0) -> tuple:
    """(shape, dtype) of the kind's first buffer; with ``page_size > 0``
    of a pool ``(L, pages, KV, page_size, hd)``, which a plain K/V trunk
    alone has (any other kind raises with why it is contiguous only)."""
    kind, pool = kind_of(cfg), ()
    if page_size > 0:
        if kind.contiguous_only:
            raise NotImplementedError(kind.contiguous_only)
        pool = (page_size, pages)
    return next(iter(kind.buffers(batch, max_len, dtype, *pool).values()))


def state_layout(cfg: TransformerConfig, batch: int, dtype=None) -> dict:
    """{name: (shape, dtype)} of what a cache holds per slot whatever the
    position; {} where the kind has none."""
    return kind_of(cfg).state(batch, dtype)


def cache_bytes_per_token(cfg: TransformerConfig, dtype=None) -> int:
    """Bytes one cached position costs over all layers."""
    return kind_of(cfg).bytes_per_token(dtype)


def state_bytes_per_slot(cfg: TransformerConfig, dtype=None) -> int:
    """Bytes a slot's fixed-size state costs."""
    return kind_of(cfg).state_bytes_per_slot(dtype)


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None, length_shape: tuple = ()):
    """An empty cache of the model's kind; ``length_shape`` () for rows that
    advance together, (batch,) for serving slots."""
    return kind_of(cfg).empty(batch, max_len, dtype, length_shape)


def _embed_rows(table, ids, dtype):
    """Row gather from a dense or int8/int4-stored embedding table — a
    quantized table reads int8 bytes for exactly the batch's tokens."""
    if isinstance(table, QuantizedTensor):
        return dequant_rows(table, ids, dtype)
    return table.astype(dtype)[ids]


def _decode_head(model, params, x):
    """Final norm + unembedding for the decode path, in fp32.

    Differences from the training head that matter per token:
    - logits come out of the MXU in fp32 (``preferred_element_type``)
      and STAY fp32 into the sampler: no bf16 round-trip over (B, V);
    - a quantized tied table is consumed in (V, d) layout by the fused
      transposed WOQ GEMM (``woq_dot_t``) — the unembedding, the single
      largest weight read of a decode step, streams int8;
    - no (V, d) transpose is ever materialized for the dense tied case
      either (``dot_general`` contracts the table's last dim directly).
    """
    cfg = model.cfg
    x = model._pre_head(params, x)
    use_kernel = getattr(model, "woq_kernel", False)
    w = params["tok_embed"] if cfg.tie_embeddings else params["lm_head"]
    if isinstance(w, QuantizedTensor):
        dot = woq_dot_t if cfg.tie_embeddings else woq_dot
        logits = dot(x, w, use_kernel=use_kernel, out_dtype=jnp.float32)
    elif cfg.tiled_head > 1 and w.shape[0 if cfg.tie_embeddings else 1] \
            % cfg.tiled_head == 0 and x.shape[1] > 1:
        # big-vocab prefill through the public API: keep the tiled head
        # (bounds the (B, T, V) logits working set; the generation loop
        # never lands here — its prefill slices to the last position)
        from ..ops.tiled import tiled_matmul

        w2 = (w.T if cfg.tie_embeddings else w).astype(x.dtype)
        logits = tiled_matmul(x, w2, cfg.tiled_head)
    elif cfg.tie_embeddings:
        logits = lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    if cfg.mup.head != 1.0:
        logits = logits * jnp.asarray(cfg.mup.head, logits.dtype)
    if cfg.lm_head_bias:
        logits = logits + params["lm_head_bias"].astype(logits.dtype)
    return constrain(logits, P(BATCH_AXES, None, "model"))


def forward_with_cache(model, params, input_ids, cache: KVCache,
                       positions=None, flash_decode: bool = False,
                       last_token_head: bool = False, last_index=None,
                       with_stats: bool = False, with_routing: bool = False,
                       with_passes: bool = False):
    """Run T tokens through all layers, appending to the cache.

    input_ids: (B, T). Works for both prefill (T = prompt length, cache
    empty) and decode (T = 1). Returns (fp32 logits (B, T, V), new cache).
    ``cache.length`` may be a scalar (every row at the same position) or a
    (B,) per-slot vector (serving: each slot appends at its own length).
    ``cache`` may also be a :class:`PagedKVCache` (decode-side T only).
    ``last_token_head=True`` computes the unembedding only for the final
    position (the generation loop's prefill: the other T-1 logit rows are
    discarded anyway, and at GPT-2 vocab sizes they're the biggest tensor
    of the whole prefill); ``last_index`` (traced i32 scalar) overrides
    which position that is — the serving engine's right-padded final
    prefill chunk puts the last real token at ``true_len - 1``, not T-1.
    ``with_stats`` adds a third result: the expert layers' counters
    (``MoETransformerLM.experts``), or None where the kind gives none;
    ``with_routing`` a further one, the experts chosen ``(expert layers, B,
    T, k)``: what a comparison with a reference needs to follow the system's
    choice at a near-tie. ``with_passes`` a last one: what a looped trunk's
    passes left (``TransformerLM.loop_passes``), None for any other trunk.
    """
    cfg = model.cfg
    B, T = input_ids.shape
    kind = kind_of(cfg)
    per_slot = getattr(cache.length, "ndim", 0) == 1
    # ONE rule for every cache kind: a slot at length 0 is not running
    # (serving/slots.py: every seated request has its prompt cached) and
    # stays at 0, where the decode kernels neither fetch nor write for it
    # and the XLA appends land in the row's own extent (the paged pool: on
    # the scratch page), which the next insert overwrites whole
    new_len = jnp.where(cache.length > 0, cache.length + T, 0) if per_slot \
        else cache.length + T
    if positions is None:
        base = cache.length[:, None] if per_slot else cache.length
        positions = base + jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    x = _embed_rows(params["tok_embed"], input_ids, cfg.dtype)
    if cfg.mup.embed != 1.0:
        x = x * jnp.asarray(cfg.mup.embed, x.dtype)
    if cfg.pos_embedding == "learned":
        if per_slot:   # rows sit at different positions: per-row gather
            x = x + _embed_rows(params["pos_embed"], positions, cfg.dtype)
        else:
            x = x + _embed_rows(params["pos_embed"], positions[0],
                                cfg.dtype)[None]
    if cfg.embed_norm:
        x = _norm(x, params["embed_ln_scale"], params.get("embed_ln_bias"),
                  cfg.norm, cfg.norm_eps)

    # ONE gate for the kernels that append to a kind's planes and read
    # them where they lie (a page pool has none: _cache_attend gates its)
    fused, planes = flash_decode, kind.in_place(cache)
    if planes:
        fused = _decode_kernel_ok(flash_decode, T, planes[0].shape[-1],
                                  x.dtype, *(p.dtype for p in planes))
        if T == 1 and not fused:
            from ..observability.metrics import get_registry

            # counted where a step program is built (a trace, not a call):
            # 0 says every step program of this process runs the kernels
            get_registry().counter("Serve/decode_fallback_builds").inc()
    if T > 1:
        # the step's gate keeps T == 1; a kind with a kernel for a chunk's
        # attention answers for it
        fused = bool(planes) and kind.chunk_fused(
            flash_decode, T, planes[0].shape[-1], x.dtype,
            *(p.dtype for p in planes))
    # a right-padded final chunk tells a state that is never rewound how
    # many of its tokens are real
    valid = last_index + 1 \
        if kind.recurrent and last_index is not None else None
    if kind.recurrent and per_slot and T > 1:
        raise NotImplementedError(
            "a state that is never rewound (a recurrent state, a ring, a "
            "conv tail) takes one token a slot (T == 1) or a chunk of rows "
            "that advance together (scalar length): no multi-token verify "
            "forward")
    x, new_cache, stats, passes = kind.forward(
        model, params, x, cache, new_len, positions, valid, fused)
    if last_token_head:
        x = x[:, -1:] if last_index is None else \
            lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    logits = _decode_head(model, params, x)
    counters, routing = stats if stats is not None else (None, None)
    return (logits, new_cache) + ((counters,) if with_stats else ()) \
        + ((routing,) if with_routing else ()) \
        + ((passes,) if with_passes else ())


class GenCarry(NamedTuple):
    """Generation state between the prefill and the decode scan.

    ``rng`` is one (2,) key (whole-batch sampling stream) or a (B, 2)
    per-row key stack — each row then advances its own independent chain,
    so a request folded from its own seed samples identically whether it
    runs alone, in a static batch, or through the serving scheduler."""

    tok: jnp.ndarray         # (B,) i32 — latest sampled token
    cache: KVCache
    rng: jnp.ndarray         # (2,) or (B, 2) uint32
    done: jnp.ndarray        # (B,) bool — eos reached (a slot: not running)
    # serving slots only (serving/slots.py): (B,) i32, the tokens a row may
    # still emit. None (no leaf: nothing is carried) wherever rows run
    # together for a fixed number of steps
    left: Optional[jnp.ndarray] = None


def prefill_tokens(model, params, input_ids, rng, *, max_new: int,
                   sampler, eos_token_id=None, cache_dtype=None,
                   flash_decode: bool = False, materialize=None,
                   cache_len=None) -> GenCarry:
    """Prompt → first sampled token + primed KV cache (the TTFT phase).

    ``cache_len`` overrides the tight ``S + max_new`` cache allocation —
    the serving layer buckets cache shapes so one compiled program serves
    many (prompt, max_new) combinations; positions past the live length
    are masked either way.

    ``materialize``: optional ``quantized params -> dense params`` fn,
    applied ONLY here (prefill is compute-bound; dense is right there).
    The decode scan consumes ``params`` as given: a quantized tree stays
    int8/int4 end-to-end — every projection dispatches through
    ``matmul_any``/``woq_dot_t`` at its point of use, so the weight bytes
    re-read from HBM each token are the quantized ones (re-materializing
    the tree in the scan body did not fuse: XLA hoisted the dequant and
    decode re-read a bf16 copy, docs/WOQ_DECODE.md).
    """
    from .sampling import split_keys

    objective = getattr(model.cfg, "objective", "clm")
    if objective != "clm":
        raise ValueError(
            f"generation needs a causal LM head; this model's objective is "
            f"{objective!r} — use forward() (MLM logits / feature hidden "
            "states) instead")
    B, S = input_ids.shape
    if cache_len is None:
        cache_len = S + max_new
    elif cache_len < S + max_new:
        raise ValueError(f"cache_len={cache_len} < prompt + max_new "
                         f"= {S + max_new}")
    if flash_decode:
        # round up to the Pallas decode kernel's 128-lane block: the spare
        # slots are masked by the live length, and every decode step stays
        # on the streaming kernel regardless of prompt/output lengths
        cache_len = -(-cache_len // 128) * 128
    cache = init_cache(model.cfg, B, cache_len, cache_dtype or model.cfg.dtype)
    mat = materialize if materialize is not None else (lambda p: p)

    with jax.named_scope("prefill"):
        logits, cache = forward_with_cache(model, mat(params), input_ids,
                                           cache, last_token_head=True)
    rng, sub = split_keys(rng)
    tok = sampler(logits[:, -1], sub)
    done = (tok == eos_token_id) if eos_token_id is not None \
        else jnp.zeros((B,), bool)
    return GenCarry(tok=tok, cache=cache, rng=rng, done=done)


def decode_step(model, params, carry: GenCarry, *, sampler,
                eos_token_id=None, flash_decode: bool = False,
                logit_guard: bool = False, poison_row=None,
                moe_stats: bool = False, exit_pdf: bool = False):
    """ONE decode iteration: forward the carry token, sample the next.

    The single definition shared by :func:`decode_tokens`' scan body and
    the serving engine's slot step (``serving/slots.py``), so the eos
    forcing and rng-split order cannot drift between the static-batch and
    continuous-batching paths — that shared order is what makes serving
    outputs bit-identical to single-request ``generate()``.

    ``logit_guard=True`` (the serving step) additionally returns a (B,)
    bool of per-row logit finiteness — ``(carry, ok)`` — computed on
    device and read back fused with the step's existing tok/done sync: no
    host sync is added. Sampling is unchanged either way.

    ``poison_row`` (chaos only; a traced i32 scalar, -1 = none) overwrites
    that one row's logits with NaN before sampling — AFTER the forward, so
    the poison can never reach the KV cache or any other row; with -1 the
    ``where`` returns the original logits bit-exactly.

    ``moe_stats=True`` (with ``logit_guard``) returns ``(carry, ok, stats,
    routing)``: the step's expert-layer counters, for the same read-back,
    and the experts it chose (expert layers, B, 1, k). ``exit_pdf=True``
    (with ``logit_guard``; a looped trunk with its gate) returns ``(carry,
    ok, pdf)``: each row's distribution over exit passes (B, passes), for
    that read-back too.

    A carry with ``left`` is the serving slots' (``serving/slots.py``): a
    running row (not ``done``) has one token fewer left after the step; at
    none, as at eos, it is ``done``, and a row that is ``done`` stands at
    length 0, where the forward leaves it and the decode kernels do nothing
    for it. The host retires a request on the same two conditions off the
    same read-back, so it never has to tell the device."""
    from .sampling import split_keys

    tok, cache, rng, done, left = carry
    with jax.named_scope("decode_step"):
        lg, cache, stats, routing, passes = forward_with_cache(
            model, params, tok[:, None], cache, flash_decode=flash_decode,
            with_stats=True, with_routing=True, with_passes=True)
    if poison_row is not None:
        bad = jnp.arange(lg.shape[0], dtype=jnp.int32)[:, None, None] \
            == poison_row
        lg = jnp.where(bad, jnp.float32(float("nan")), lg)
    rng, sub = split_keys(rng)
    nxt = sampler(lg[:, 0], sub)
    if eos_token_id is not None:
        nxt = jnp.where(done, eos_token_id, nxt)
        done = done | (nxt == eos_token_id)
    if left is not None:
        left = left - (~carry.done).astype(left.dtype)
        done = done | (left <= 0)
        cache = cache._replace(length=jnp.where(done, 0, cache.length))
    out = GenCarry(nxt, cache, rng, done, left)
    if logit_guard:
        ok = jnp.all(jnp.isfinite(lg), axis=(1, 2))
        if exit_pdf:
            return out, ok, passes["exit_pdf"][:, 0]
        return (out, ok, stats, routing) if moe_stats else (out, ok)
    return out


def decode_tokens(model, params, carry: GenCarry, *, steps: int, sampler,
                  eos_token_id=None, flash_decode: bool = False,
                  return_carry: bool = False):
    """Decode scan: ``steps`` more tokens after the carry's.

    Returns (B, steps + 1) — the carry token plus everything it generated
    — or ``(tokens, carry)`` with ``return_carry=True`` (the engine's
    chunked-decode path resumes the scan from the returned carry after a
    host-side ``done.all()`` check). The KV cache threads through the scan
    carry, so XLA reuses (donates) the cache buffers in place.
    """

    def step(carry, _):
        nxt = decode_step(model, params, carry, sampler=sampler,
                          eos_token_id=eos_token_id,
                          flash_decode=flash_decode)
        return nxt, carry.tok

    out, toks = lax.scan(step, carry, None, length=steps)
    # emitted tokens 0..steps-1 plus the final carry token. Constrain both
    # concat operands to an explicit replicated layout first: under TP the
    # partitioner resolves the scan-stacked ys and the carry token to
    # DIFFERENT shardings, and GSPMD has reconciled them with a spurious
    # cross-shard reduce — every emitted token id summed tp_size times.
    # (Replicating (steps, B) int32 is free; a no-op off-mesh.)
    tokens = jnp.concatenate([constrain(toks, P(None, None)),
                              constrain(out.tok[None], P(None, None))],
                             axis=0).T                     # (B, steps + 1)
    return (tokens, out) if return_carry else tokens


def generate_tokens(model, params, input_ids, rng, *, max_new: int,
                    sampler, eos_token_id=None, cache_dtype=None,
                    flash_decode: bool = False, materialize=None,
                    cache_len=None):
    """Shared prefill + decode-scan generation loop, as ONE traceable fn.

    Used by both :class:`~deepspeed_tpu.inference.InferenceEngine` and the
    RLHF :class:`~deepspeed_tpu.runtime.hybrid_engine.HybridEngine` so the
    schedule/eos logic cannot drift between them. ``sampler(logits, rng)``
    -> (B,) int32.

    Composes :func:`prefill_tokens` + :func:`decode_tokens` inside one
    trace: jitted as a unit, nothing leaves the device between prompt in
    and tokens out. The engine's request-tracing mode jits the two halves
    separately, an honest TTFT / per-token split for one more host sync.
    """
    carry = prefill_tokens(model, params, input_ids, rng, max_new=max_new,
                           sampler=sampler, eos_token_id=eos_token_id,
                           cache_dtype=cache_dtype, flash_decode=flash_decode,
                           materialize=materialize, cache_len=cache_len)
    return decode_tokens(model, params, carry, steps=max_new - 1,
                         sampler=sampler, eos_token_id=eos_token_id,
                         flash_decode=flash_decode)

"""Weight-only int8/int4 quantization for inference — int8 end-to-end.

Reference analog: ``deepspeed/inference/quantization/`` (int4/int8 WOQ) and
the ``GroupQuantizer`` used by kernel injection
(``module_inject/replace_module.py:43``). TPU-native: weights are stored as
int8 (or nibble-packed int4) + per-channel fp32 group scales in HBM and are
consumed *quantized* by the decode step — either by the fused Pallas GEMM
(``ops/woq_matmul.py``: int8 tiles dequantized in VMEM inside the matmul
loop, the in-kernel design of ``csrc/transformer/inference/``) or, off-TPU
and for kernel-ineligible leaves, by a per-use XLA dequant at the point of
consumption. The previous whole-matrix ``dequantize_params`` hoist — which
let XLA materialize a bf16 copy outside the decode scan and re-read *that*
(int8 decode then ran no faster than bf16: docs/WOQ_DECODE.md) — is gone from
the decode path; it survives only for the cold full-forward.

Layout: groups of ``group_size`` rows along the weight's second-to-last
dim (the contraction dim of an ``x @ W`` projection) share one scale row:
``scale`` is ``(..., G, N)`` fp32 — per-channel along N, grouped along K.
This is the layout that lets the fused GEMM fold the scale *outside* the
int8 dot (one ``(1, bn)`` multiply per k-step) instead of dequantizing
whole tiles. int4 packs two signed nibbles per byte along *adjacent rows*
of the grouped dim (the kernel unpacks them with shifts and a sublane concat).
"""

from __future__ import annotations

from functools import reduce
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """int8 (or row-pair-packed int4) weight + per-channel group scales.

    ``q``: original shape, or ``(..., K/2, N)`` packed for int4;
    ``scale``: ``(..., G, N)`` fp32 with ``G = K / group_size`` groups
    along the second-to-last dim. ``group_size``/``bits``/``pspec`` are
    pytree aux data (static under jit). ``pspec`` carries the leaf's
    ``param_specs()`` PartitionSpec so the consumption-side dispatcher can
    wrap the Pallas GEMM in the right shard_map under tensor parallelism —
    the sharding rule travels WITH the weight, the way the reference's
    GroupQuantizer splits scales alongside their mp-sharded weights."""

    def __init__(self, q, scale, group_size: int, bits: int = 8,
                 pspec: Optional[P] = None):
        self.q = q
        self.scale = scale
        self.group_size = group_size
        self.bits = bits
        self.pspec = pspec

    def tree_flatten(self):
        return (self.q, self.scale), (self.group_size, self.bits, self.pspec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        gs, bits, pspec = aux
        return cls(children[0], children[1], gs, bits, pspec)

    @property
    def shape(self):
        if self.bits == 4:
            return (self.q.shape[:-2]
                    + (self.q.shape[-2] * 2, self.q.shape[-1]))
        return self.q.shape


def _pack_int4(q):
    """(..., K, N) signed int4 values in int8 → (..., K/2, N): adjacent
    rows pack as (low nibble = even row, high nibble = odd row)."""
    lo = q[..., 0::2, :] & 0x0F
    hi = (q[..., 1::2, :] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def _unpack_int4(packed):
    """(..., K/2, N) packed bytes → (..., K, N) signed int4 values
    (int8), interleaving the row pairs back."""
    lo = (packed << 4).astype(jnp.int8) >> 4          # sign-extend low
    hi = packed >> 4                                  # arithmetic: high
    out = jnp.stack([lo, hi], axis=-2)                # (..., K/2, 2, N)
    return out.reshape(packed.shape[:-2]
                       + (packed.shape[-2] * 2, packed.shape[-1]))


def quantize(w, group_size: int = 128, bits: int = 8,
             pspec: Optional[P] = None) -> QuantizedTensor:
    """Symmetric int8/int4 quantization, groups along the second-to-last
    dim, scales per-channel along the last dim.

    A leaf whose second-to-last dim isn't group-divisible degrades to one
    whole group (e.g. GPT-2's odd 50257-row vocab table); a group that
    can't row-pack (odd size) degrades int4 → int8 per leaf instead of
    failing the whole model."""
    assert bits in (4, 8), bits
    shape = w.shape
    K, N = shape[-2], shape[-1]
    gs = group_size if K % group_size == 0 else K
    if bits == 4 and gs % 2 != 0:
        bits = 8
    G = K // gs
    wf = w.astype(jnp.float32).reshape(shape[:-2] + (G, gs, N))
    qmax = 7.0 if bits == 4 else 127.0
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)   # (..., G, 1, N)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(wf / scale), -qmax, qmax).astype(jnp.int8)
    q = q.reshape(shape)
    if bits == 4:
        q = _pack_int4(q)
    return QuantizedTensor(q=q, scale=scale[..., 0, :], group_size=gs,
                           bits=bits, pspec=pspec)


def dequantize(qt: QuantizedTensor, dtype=jnp.bfloat16) -> jnp.ndarray:
    qv = _unpack_int4(qt.q) if qt.bits == 4 else qt.q
    shape = qv.shape
    K, N = shape[-2], shape[-1]
    G = K // qt.group_size
    qf = qv.astype(jnp.float32).reshape(shape[:-2] + (G, qt.group_size, N))
    out = qf * qt.scale[..., :, None, :]
    return out.reshape(shape).astype(dtype)


def dequant_rows(qt: QuantizedTensor, ids, dtype=jnp.bfloat16):
    """Gather + dequantize only the rows named by ``ids`` — the embedding
    lookup of an int8-stored table reads int8 bytes for exactly the batch's
    tokens instead of materializing the dense table. qt: 2-D (V, N)."""
    if qt.bits == 4:
        pr = qt.q[ids // 2]                           # (..., N) packed
        lo = (pr << 4).astype(jnp.int8) >> 4
        hi = pr >> 4
        rows = jnp.where((ids % 2 == 0)[..., None], lo, hi)
    else:
        rows = qt.q[ids]
    G = qt.scale.shape[-2]
    g = ids // qt.group_size if G > 1 else jnp.zeros_like(ids)
    return (rows.astype(jnp.float32) * qt.scale[g]).astype(dtype)


# ----------------------------------------------------------- consumption
def _mesh_tp():
    from ..platform.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return None, 1
    return mesh, int(mesh.shape["model"])


def _whole_on_each_device(fn, *args):
    """Run a Pallas GEMM whole on every device of a multi-device mesh —
    GSPMD cannot partition a Mosaic kernel, and a leaf that reaches here
    has no ``model``-sharded layout to run shard-local. A plain call on
    one device or inside a manual region."""
    from ..platform.mesh import kernel_mesh

    mesh = kernel_mesh()
    if mesh is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(P() for _ in args),
                         out_specs=P(), check_vma=False)(*args)


def _has_model(entry) -> bool:
    names = entry if isinstance(entry, (tuple, list)) else (entry,)
    return "model" in names


def woq_dot(x, qt: QuantizedTensor, use_kernel: bool = False,
            out_dtype=None):
    """``x @ W`` for a quantized ``(K, N)`` weight (leading x dims free).

    ``use_kernel=True`` routes eligible leaves through the fused Pallas
    GEMM (int8 stays int8 all the way into VMEM); otherwise — and for
    kernel-ineligible layouts — the weight is dequantized per-use at the
    point of consumption (XLA may fuse the convert into the operand load;
    on TPU prefer the kernel, which makes the fusion non-optional).

    Under a tensor-parallel mesh the kernel call is shard_mapped according
    to the weight's travelling ``pspec``: column-sharded weights run
    shard-local with no collective; row-sharded (contraction-split)
    weights psum their fp32 partials — the same math GSPMD emits for the
    dense path."""
    from ..ops.woq_matmul import woq_matmul, woq_matmul_eligible

    K = x.shape[-1]
    N = qt.shape[-1]
    gs, bits = qt.group_size, qt.bits
    out_dtype = out_dtype or x.dtype
    if (not use_kernel) or qt.q.ndim != 2 \
            or not woq_matmul_eligible(K, gs, bits):
        return jax.lax.dot_general(
            x, dequantize(qt, x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=out_dtype)
    x2 = x.reshape(-1, K)
    G = qt.scale.shape[-2]

    mesh, tp = _mesh_tp()
    spec = qt.pspec
    ent = tuple(spec)[-2:] if spec is not None and len(tuple(spec)) >= 2 \
        else (None, None)
    if tp > 1 and _has_model(ent[1]):
        if N % tp != 0:
            # shard_map needs even shards (GSPMD tolerated uneven); the
            # per-use dequant keeps such configs serving
            return jax.lax.dot_general(
                x, dequantize(qt, x.dtype),
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=out_dtype)
        # column-sharded (wqkv/w_in/w_gate): shard-local columns, no
        # collective; scale columns shard identically
        fn = jax.shard_map(
            lambda xs, qs, ss: woq_matmul(xs, qs, ss, group_size=gs,
                                          bits=bits, out_dtype=out_dtype),
            mesh=mesh, in_specs=(P(None, None), P(None, "model"),
                                 P(None, "model")),
            out_specs=P(None, "model"), check_vma=False)
        out2 = fn(x2, qt.q, qt.scale)
    elif tp > 1 and _has_model(ent[0]):
        qrows = qt.q.shape[0]
        if (G % tp != 0 and G != 1) or qrows % tp != 0 \
                or x2.shape[1] % tp != 0:
            return jax.lax.dot_general(
                x, dequantize(qt, x.dtype),
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=out_dtype)
        # row-sharded (wo/w_out): contraction splits, fp32 partials psum.
        # A degraded single group (G == 1) replicates its scale row and
        # each shard treats its local row count as the group — the scale
        # is constant over all rows, so the math is identical.
        if G == 1:
            s_spec, gs_local = P(None, None), K // tp
        else:
            s_spec, gs_local = P("model", None), gs

        def body(xs, qs, ss):
            part = woq_matmul(xs, qs, ss, group_size=gs_local, bits=bits,
                              out_dtype=jnp.float32)
            return jax.lax.psum(part, "model").astype(out_dtype)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(None, "model"), P("model", None),
                                     s_spec),
                           out_specs=P(None, None), check_vma=False)
        out2 = fn(x2, qt.q, qt.scale)
    else:
        out2 = _whole_on_each_device(
            lambda xs, qs, ss: woq_matmul(xs, qs, ss, group_size=gs,
                                          bits=bits, out_dtype=out_dtype),
            x2, qt.q, qt.scale)
    return out2.reshape(x.shape[:-1] + (N,))


def woq_dot_t(x, qt: QuantizedTensor, use_kernel: bool = False,
              out_dtype=None):
    """``x @ W.T`` for a quantized ``(V, K)`` weight — the tied-embedding
    unembedding, consumed in table layout. Returns (..., V) in
    ``out_dtype`` (default ``x.dtype``; the decode head asks for fp32 so
    the sampler never round-trips through bf16)."""
    from ..ops.woq_matmul import woq_matmul_t, woq_matmul_t_eligible

    K = x.shape[-1]
    V = qt.shape[-2]
    gs, bits = qt.group_size, qt.bits
    out_dtype = out_dtype or x.dtype
    if (not use_kernel) or qt.q.ndim != 2 \
            or not woq_matmul_t_eligible(V, K, gs, bits):
        w = dequantize(qt, x.dtype)
        return jax.lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=out_dtype)
    x2 = x.reshape(-1, K)
    G = qt.scale.shape[-2]

    mesh, tp = _mesh_tp()
    spec = qt.pspec
    ent = tuple(spec)[-2:] if spec is not None and len(tuple(spec)) >= 2 \
        else (None, None)
    if tp > 1 and _has_model(ent[0]) and V % tp == 0 \
            and (G % tp == 0 or G == 1) and qt.q.shape[0] % tp == 0:
        # vocab-sharded table: shard-local output columns. A degraded
        # single-group table (vocab not group-divisible) replicates its
        # one scale row; each shard's local vocab IS its group then —
        # the whole-table dequant this path replaces is the single
        # largest per-step weight read of a tied-head model.
        if G == 1:
            s_spec, gs_local = P(None, None), V // tp
        else:
            s_spec, gs_local = P("model", None), gs
        fn = jax.shard_map(
            lambda xs, qs, ss: woq_matmul_t(xs, qs, ss, group_size=gs_local,
                                            bits=bits, out_dtype=out_dtype),
            mesh=mesh, in_specs=(P(None, None), P("model", None), s_spec),
            out_specs=P(None, "model"), check_vma=False)
        out2 = fn(x2, qt.q, qt.scale)
    elif tp > 1 and spec is not None and any(map(_has_model, ent)):
        w = dequantize(qt, x.dtype)
        return jax.lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=out_dtype)
    else:
        out2 = _whole_on_each_device(
            lambda xs, qs, ss: woq_matmul_t(xs, qs, ss, group_size=gs,
                                            bits=bits, out_dtype=out_dtype),
            x2, qt.q, qt.scale)
    return out2.reshape(x.shape[:-1] + (V,))


def matmul_any(x, w, use_kernel: bool = False):
    """``x @ w`` whether ``w`` is dense or a :class:`QuantizedTensor` —
    the one dispatch point every decode-path projection goes through."""
    if isinstance(w, QuantizedTensor):
        return woq_dot(x, w, use_kernel=use_kernel)
    return x @ w.astype(x.dtype)


def tp_quant_dot(x, w, bits: int = 8):
    """``x @ w`` for a DENSE row-sharded (contraction-split) weight with
    the ``model``-axis partial-sum reduction spelled as an explicit
    EQuARX-style two-sided int8 all-reduce
    (``comm.compressed.int8_psum``) instead of the fp psum GSPMD
    inserts — the quantized TP decode collective
    (``inference.tp_comm_quant``).

    Local partials accumulate in fp32 (``preferred_element_type``), the
    wire carries int8 payloads + fp32 block scales on both hops, and the
    result is cast back to ``x.dtype``. Returns ``None`` when the
    explicit spelling doesn't apply — no TP mesh in context, or the
    contraction dim doesn't shard evenly — and the caller falls back to
    the plain GSPMD matmul (same program as the knob-off path)."""
    if bits != 8:
        raise ValueError(f"tp_quant_dot supports int8 only, got {bits}")
    mesh, tp = _mesh_tp()
    if tp <= 1:
        return None
    K = x.shape[-1]
    N = w.shape[-1]
    if K % tp != 0:
        return None
    from ..comm.compressed import int8_psum

    x2 = x.reshape(-1, K)

    def body(xs, ws):
        part = jax.lax.dot_general(
            xs, ws.astype(xs.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return int8_psum(part, "model").astype(x.dtype)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "model"), P("model", None)),
                       out_specs=P(None, None), check_vma=False)
    return fn(x2, w).reshape(x.shape[:-1] + (N,))


# ------------------------------------------------------------- pytree ops
def _should_quantize(path, leaf, min_size: int) -> bool:
    if leaf.ndim < 2 or leaf.size < min_size:
        return False
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    # norms/bias stay full precision (match the reference WOQ exclusions);
    # MoE routers too — near-tie routing decisions flap across quantization
    # rounding, same reason the engine's compute cast keeps them fp32.
    return not (name.startswith(("ln", "b")) or "bias" in name
                or "scale" in name or name == "router")


def _spec_at(specs: Any, path):
    """Walk a matching specs pytree by a tree_map_with_path key path."""
    if specs is None:
        return None
    try:
        return reduce(lambda t, k: t[getattr(k, "key", getattr(
            k, "idx", None))], path, specs)
    except (KeyError, TypeError, IndexError):
        return None


def quantize_params(params: Any, group_size: int = 128,
                    min_size: int = 4096, bits: int = 8,
                    specs: Any = None) -> Any:
    """Quantize every large matmul weight in a param pytree to int8/int4.
    ``specs`` (a matching ``param_specs()`` tree) stamps each quantized
    leaf's PartitionSpec into its aux data for the TP-aware dispatcher."""
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: quantize(leaf, group_size, bits=bits,
                                 pspec=_spec_at(specs, p))
        if _should_quantize(p, leaf, min_size) else leaf, params)


def dequantize_params(params: Any, dtype=jnp.bfloat16) -> Any:
    """Materialize every quantized leaf — the COLD path only (one-shot
    full forward, prefill). The decode scan consumes leaves quantized via
    :func:`matmul_any` / :func:`woq_dot_t` / :func:`dequant_rows`."""
    return jax.tree.map(
        lambda leaf: dequantize(leaf, dtype)
        if isinstance(leaf, QuantizedTensor) else leaf,
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


def quantized_shardings(specs: Any, qtree: Any, mesh) -> Any:
    """Map a model's ``param_specs()`` tree onto the quantized pytree.

    ``q`` takes the original leaf's PartitionSpec verbatim (int4's packed
    row dim halves the row count; row-sharding stays valid when the
    per-shard row count is even — the usual d % (2*tp) == 0 case).
    ``scale`` — shaped ``orig[:-2] + (G, N)`` — takes the same entries
    with the second-to-last (grouped-dim) entry kept on G when G > 1 and
    dropped (replicated) when the leaf degraded to one whole group, where
    a sharded size-1 dim would be invalid. An entry that does not divide
    its dim (10 scale groups over ``model`` = 4) is dropped too
    (``platform.mesh.fit_spec``); ``woq_dot`` then takes the per-use
    dequant for that leaf."""
    from ..platform.mesh import fit_spec

    def leaf_shardings(spec, q_or_leaf):
        spec = spec if spec is not None else P()
        if not isinstance(q_or_leaf, QuantizedTensor):
            return NamedSharding(mesh, spec)
        rank = len(q_or_leaf.q.shape)
        entries = tuple(spec) + (None,) * (rank - len(tuple(spec)))
        n_groups = q_or_leaf.scale.shape[-2]
        group_entry = entries[-2] if n_groups > 1 else None
        return QuantizedTensor(
            q=NamedSharding(mesh, fit_spec(P(*entries), q_or_leaf.q.shape,
                                           mesh)),
            scale=NamedSharding(mesh, fit_spec(
                P(*entries[:-2], group_entry, entries[-1]),
                q_or_leaf.scale.shape, mesh)),
            group_size=q_or_leaf.group_size, bits=q_or_leaf.bits,
            pspec=q_or_leaf.pspec)

    return jax.tree.map(leaf_shardings, specs, qtree,
                        is_leaf=lambda x: x is None or isinstance(x, P))


def quantized_bytes(params: Any) -> int:
    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor):
            total += leaf.q.size + leaf.scale.size * 4   # packed for int4
        else:
            total += leaf.size * leaf.dtype.itemsize
    return int(total)


def decode_weight_bytes(params: Any, skip: tuple = ("pos_embed",)) -> int:
    """Model of the weight HBM bytes one decode step re-reads: every
    matmul weight streams fully per token (int8/int4 leaves count their
    quantized bytes + scales — the fused GEMM's whole point); embedding
    *lookups* are row gathers, not full reads, so positional tables are
    skipped. A TIED token table is read fully — by the unembedding
    matmul — and counts once; an untied model's unembedding read is its
    ``lm_head``, so there ``tok_embed`` is gather-only and skipped too."""
    if isinstance(params, dict) and "lm_head" in params:
        skip = skip + ("tok_embed",)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor))[0]:
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in skip:
            continue
        if isinstance(leaf, QuantizedTensor):
            total += leaf.q.size + leaf.scale.size * 4
        elif leaf.ndim >= 2:
            total += leaf.size * leaf.dtype.itemsize
    return int(total)

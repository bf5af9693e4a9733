"""What every cache kind is asked (docs/SERVING.md, "Cache kinds"): its
cache type and buffers, its layer loop, what it does not compose with when
served, what its spans say. Nothing else in the package tells a model from
another."""

import math
from functools import cached_property
from typing import Optional

import jax
import jax.numpy as jnp

from ...models import moe

# what a ServingEngine can be built with that a kind may refuse, in the order
# the reasons are given; two reasons every kind that refuses them gives
FEATURES = ("paged", "kv_quant", "speculation", "host_kv", "quantize", "mesh")
IN_POOL = "an int8 KV cache (kv_quant_bits): it lives in the paged pool"
MOVES_PAGES = "tiered / host KV (host_pool_bytes): it moves pages"


class Kind:
    """One kind of cache over a config ``cfg``. A subclass sets ``cache``
    (its ``NamedTuple``: the buffers by name, then ``length``) and gives
    :meth:`matches`, :meth:`forward` and what of the rest differs from a
    plain K/V cache's. ``slots``, ``dtype``, ``params``: the serving
    engine's, of which the span meta speaks; built of the config alone a
    kind answers for one slot at the config's dtype."""

    cache: type = None
    # the buffers that grow with the position (positions last), by name
    planes = ("k", "v")
    # a state that is never rewound: chunk plans do not overlap, a padded
    # final chunk says how many of its tokens are real (``valid``), slots
    # take one token each (no multi-token verify forward)
    recurrent = False
    # the read-backs the step and the chunks carry beside the tokens: the
    # expert layers' counters and choices, a looped trunk's exit pdf
    moe_stats = exit_pdf = False
    # the serving engine keeps a host mirror of the slots' lengths for
    # :meth:`step_meta` (``lens``) whatever the planes are called
    mirrors_lengths = False
    # what the kind is called where it refuses (with its verb), how its
    # reasons are joined, feature -> reason; why it has no pages
    what, sep, refuses = "", "; ", {}
    contiguous_only = ""
    # the serving engine's, set once it is built: whether its programs take
    # the kernels (``flash_decode``) and the positions a slot's cache holds
    flash, max_len = False, 0

    def __init__(self, cfg, slots: int = 1, dtype=None, params=None):
        self.cfg, self.slots, self.dtype = cfg, slots, dtype
        self.layers = cfg.n_layer      # that have planes

    def buffers(self, batch: int, max_len: int, dtype=None) -> dict:
        """{name: (shape, dtype)} of the buffers that grow with the
        position, in the cache type's order."""
        return self.kv_planes(self.layers, batch, max_len, dtype)

    def state(self, batch: int, dtype=None) -> dict:
        """{name: (shape, dtype)} of what a slot holds whatever its length."""
        return {}

    def kv_planes(self, layers: int, batch: int, max_len: int, dtype,
                  kv_heads: Optional[int] = None) -> dict:
        """K beside V of the value width, (layers, batch, KV, ., max_len)."""
        cfg, dt = self.cfg, dtype or self.cfg.dtype
        lead = (layers, batch, kv_heads or cfg.kv_heads)
        return {"k": (lead + (cfg.head_dim, max_len), dt),
                "v": (lead + (getattr(cfg, "v_dim", cfg.head_dim), max_len),
                      dt)}

    def empty(self, batch: int, max_len: int, dtype=None,
              length_shape: tuple = ()):
        """An empty cache; ``length_shape`` (batch,) for serving slots."""
        layout = {**self.buffers(batch, max_len, dtype),
                  **self.state(batch, dtype)}
        return self.cache(length=jnp.zeros(length_shape, jnp.int32), **{
            name: jnp.zeros(shape, dt)
            for name, (shape, dt) in layout.items()})

    def bytes_per_token(self, dtype=None) -> int:
        """Bytes one cached position costs over all layers."""
        return _nbytes(self.buffers(1, 1, dtype or self.dtype))

    def state_bytes_per_slot(self, dtype=None) -> int:
        """Bytes a slot's fixed-size state costs."""
        return _nbytes(self.state(1, dtype or self.dtype))

    def in_place(self, cache) -> list:
        """The buffers of ``cache`` that the T == 1 step's kernels append
        to and read where they lie (``forward_with_cache``'s one gate)."""
        return [getattr(cache, name) for name in self.planes]

    def forward(self, model, params, x, cache, new_len, positions, valid,
                fused: bool):
        """The layer loop over ``x`` (B, T, d) against ``cache``, which
        ends at ``new_len``. ``valid`` (traced i32 or None, a recurrent
        kind's alone): how many of the T tokens are real. ``fused``: the
        gate's answer. Returns (x, cache, (counters, routing) of the expert
        layers or None, a looped trunk's passes or None)."""
        raise NotImplementedError

    def chunk_fused(self, flash_decode: bool, T: int, max_len: int,
                    *dtypes) -> bool:
        """What :meth:`forward` is told as ``fused`` for T > 1 tokens over a
        cache of ``max_len`` positions and ``dtypes`` (the activations',
        the planes'): whether a kernel of the kind's own attends a chunk.
        Asked where a program is traced; none has one but says so."""
        return False

    def deferred_rows(self, dtype=None) -> int:
        """The positions a slot's newest K/V wait in a tail of rows before
        their block is written (``kinds/dense.py``): 0 where the T == 1
        step writes the block back every time."""
        return 0

    def rewound(self, cache, length):
        """``cache`` standing at ``length``, at most where a forward left it
        (a right-padded final chunk's real tokens), as the T == 1 step takes
        it over."""
        return cache._replace(length=length)

    def settled(self, cache):
        """``cache`` with every live position where whatever is not the
        T == 1 step's kernel reads it: as it stands, for a kind whose step
        defers nothing."""
        return cache

    def refusal(self, on) -> Optional[str]:
        """The sentence ``ServingEngine`` refuses to be built with, of the
        features ``on``, or None: the kind's own list, behind the sorted
        expert rows' (``moe.SERVED``) where the trunk has them and the kind
        does not refuse each of their features itself."""
        asked = [(self.what, self.sep, self.refuses)]
        if getattr(self.cfg, "moe_router", "") == "sigmoid" \
                and not set(moe.SERVED[2]) <= set(self.refuses):
            asked.insert(0, moe.SERVED)
        for what, sep, refuses in asked:
            reasons = [refuses[f] for f in FEATURES
                       if f in on and f in refuses]
            if reasons:
                return what + " " + sep.join(reasons)
        return None

    token_bytes = cached_property(bytes_per_token)
    slot_bytes = cached_property(state_bytes_per_slot)

    def sizes(self, state_key: str = "state_bytes_per_slot") -> dict:
        """What every span of a kind with a state says beside its times."""
        return {"cache_bytes_per_token": self.token_bytes,
                state_key: self.slot_bytes}

    def step_meta(self, read: list, pending: list, lens,
                  running: list) -> dict:
        """Meta of a ``decode_step`` span. ``read``: what the read-back
        brought behind tok / done / ok, the step's first, then one entry
        for each of ``pending`` — (span, device counters, size) of the
        chunks that ran since the step before, whose figures go onto their
        own spans here (``Span.amend``). ``lens``: the mirror of the
        device's lengths as the step had them, or None; ``running``: the
        slots it ran. The counters' columns are the forward's that stacked
        them."""
        return {}

    def chunk_meta(self, chunk) -> dict:
        """Meta of a ``prefill_chunk`` span at its dispatch (``chunk``: the
        scheduler's ``ChunkPlan``): :meth:`sizes` of a kind with a state."""
        return self.sizes() if self.slot_bytes else {}


def _nbytes(layout: dict) -> int:
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in layout.values())


def split_banks(model, seg, routed: bool):
    """(banks or None, the rest) of a segment's stacked weights: the banks
    stay out of the layer loop's xs — sliced per layer they would be copied
    (0.4 GB a matrix); the kernel indexes them by layer."""
    names = getattr(model, "BANKS", ()) if routed else ()
    return ({k: seg[k] for k in names} or None,
            {k: v for k, v in seg.items() if k not in names})


def served_bytes(cfg, params, banks=("w_gate", "w_in", "w_out")) -> tuple:
    """Of a served tree whose expert layers hold a share: (what a step
    reads of the layers' weights whoever runs, the routed experts' banks
    apart and an indexer's weights with them; ONE held expert's matrices;
    the head's) in bytes — the step's counters say how many experts it
    touched. Zeros of a kind built of the config alone."""
    params = params or {}
    segs = params.get("layers", ())
    segs = segs if isinstance(segs, (tuple, list)) else (segs,)

    def nbytes(tree):
        return sum(a.nbytes for a in jax.tree.leaves(tree))

    routed = [{k: seg[k] for k in banks} for seg in segs if "router" in seg]
    layers = sum(n for k, n in cfg.segments if k == "moe")
    return (nbytes(segs) - nbytes(routed) + nbytes(params.get("indexer", ())),
            nbytes(routed) // max(1, cfg.held_experts * layers),
            nbytes(params.get("lm_head", ())))


def count_chunk_fallbacks(flash_decode: bool, attention: bool, scan: bool):
    """Where a chunk's program of a kind with KDA layers is built (a trace,
    not a call, as ``Serve/decode_fallback_builds`` counts the step's): with
    the kernels on, count the program onto ``Serve/
    chunk_attention_fallback_builds`` if its ``attention`` fell back to
    XLA's walk and onto ``Serve/chunk_scan_fallback_builds`` if its KDA
    layers' ``scan`` fell back to XLA's (``kda.chunk_scan_falls_back``)."""
    if not flash_decode:
        return
    from ...observability.metrics import get_registry

    for name, fell_back in (("attention", attention), ("scan", scan)):
        if fell_back:
            get_registry().counter(f"Serve/chunk_{name}_fallback_builds").inc()


def stacked(stats: list):
    """The expert segments' (counters, routing) as one of each, or None."""
    return tuple(jnp.concatenate(part) for part in zip(*stats)) \
        if stats else None


# ``MoETransformerLM.experts``' counters, one row a layer: most rows an
# expert got, experts touched, rows multiplied (padding included), rows that
# chose a held expert. Two readings of them.
def routed_counts(kind: Kind, read: list, pending: list) -> dict:
    """Of a step whose every expert is held: the most rows any expert got
    over the mean, the rows multiplied over the rows routed (the whole slot
    batch: an idle slot's token is routed like any other), the experts
    touched; what a cached token costs. The chunks' rows over routed go
    onto their own spans. {} where the step brought no counters (no expert
    layers, or the chaos build's)."""
    if not read:
        return {}
    k = kind.cfg.moe_top_k
    for (chunk_span, _, size), st in zip(pending, read[1:]):
        chunk_span.amend(moe_rows_over_routed=float(
            st[:, 2].sum() / (len(st) * size * k)))
    st = read[0]
    routed = kind.slots * k
    return {"moe_load_max_over_mean": float(
                st[:, 0].max() * kind.cfg.num_experts / routed),
            "moe_rows_over_routed": float(
                st[:, 2].sum() / (len(st) * routed)),
            "experts_touched": float(st[:, 1].mean()),
            "cache_bytes_per_token": kind.token_bytes}


def held_counts(kind: Kind, read: list, pending: list) -> dict:
    """Of a step whose expert layers hold a share: ``held_rows`` and
    ``experts_touched`` (means over the layers), ``held_rows_share`` of the
    slots x k rows routed, the load over the held experts; the chunks' first
    two go onto their own spans. {} where the step brought no counters."""
    if not read:
        return {}
    k, held = kind.cfg.moe_top_k, kind.cfg.held_experts
    for (chunk_span, _, size), st in zip(pending, read[1:]):
        chunk_span.amend(held_rows=float(st[:, 3].mean()),
                         experts_touched=float(st[:, 1].mean()))
    st = read[0]
    held_rows = float(st[:, 3].mean())
    return dict(
        held_rows=held_rows,
        held_rows_share=held_rows / (kind.slots * k),
        experts_touched=float(st[:, 1].mean()),
        moe_load_max_over_mean=float(
            st[:, 0].max() * held / max(held_rows, 1.0)))

"""The cache kinds: one module a kind, and the tuple of them (``base.Kind``;
docs/SERVING.md, "Cache kinds"). ``decode.py``, the serving engine and the
capacity ledger ask :func:`kind_of`; the steps the kinds' loops share are
below them (``steps.py``); nothing outside this package adds a kind."""

from .base import FEATURES, Kind
from .cca import CCA, CCACache
from .delta_gqa import DeltaGQA, DeltaGQACache
from .delta_latent import DeltaLatent, DeltaLatentCache
from .dense import Dense, KVCache, PagedKVCache
from .hybrid import Hybrid, HybridCache
from .latent import Latent, LatentCache
from .linear_sparse import LinearSparse, LinearSparseCache
from .parallel import ParallelCache, ParallelHybrid
from .sparse_latent import SparseLatent, SparseLatentCache
from .windowed import Windowed, WindowedCache

KINDS = (Dense, Latent, LinearSparse, SparseLatent, Hybrid, Windowed, CCA,
         ParallelHybrid, DeltaGQA, DeltaLatent)

__all__ = ["KINDS", "LinearSparse", "DeltaGQA", "DeltaLatent", "FEATURES", "Kind", "kind_of",
           "KVCache", "PagedKVCache", "LatentCache", "HybridCache",
           "WindowedCache", "CCACache", "ParallelCache", "SparseLatentCache",
           "LinearSparseCache", "DeltaGQACache", "DeltaLatentCache"]


def kind_of(cfg, *serving) -> Kind:
    """The kind of cache a model of ``cfg`` runs over: exactly one of
    :data:`KINDS` matches a config. ``serving``: the slots, cache dtype and
    served tree of the engine that asks (``Kind``)."""
    for kind in KINDS:
        if kind.matches(cfg):
            return kind(cfg, *serving)
    raise ValueError(f"no cache kind matches {cfg!r}")

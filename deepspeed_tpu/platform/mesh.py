"""Device-mesh construction and axis bookkeeping.

One global ``jax.sharding.Mesh`` with named axes replaces the reference's
process-group bookkeeping (``deepspeed/utils/groups.py``, 530 LoC) and the
pipeline cartesian grid (``runtime/pipe/topology.py:244``). Every parallelism
strategy is an axis:

    ====================  =============================================
    axis                  reference analog
    ====================  =============================================
    ``pipe``              pipeline-parallel stage groups (pipe/topology.py)
    ``data``              data-parallel / ZeRO partition groups
    ``expert``            expert-parallel groups (utils/groups.py:113)
    ``seq``               Ulysses sequence-parallel groups (groups.py:420)
    ``model``             tensor(model)-parallel groups (Megatron mpu)
    ====================  =============================================

Axis order is chosen for fabric locality: ``model`` (highest-traffic
collectives) innermost so it lands on the tightest ICI ring, ``pipe``/``data``
outermost so they can span DCN on multi-slice deployments — the 2-level
ICI/DCN hierarchy that the reference builds by hand for MiCS hierarchical
allgather (``runtime/zero/mics.py:227``) and ZeRO++ hpZ falls out of this
layout for free.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.logging import logger

# Canonical axis order, outermost (DCN-friendly) to innermost (ICI-friendly).
# ``zero`` is the hpZ/MiCS sub-axis: a fast-ICI subgroup carved out of the
# data-parallel dimension (total DP world = data x zero). It sits inside
# ``data`` so its collectives ride the tighter interconnect — the 2-level
# hierarchy the reference builds by hand for ZeRO++ hpZ secondary shards
# (runtime/zero/config.py:256) and MiCS sub-groups (runtime/zero/mics.py:55).
AXIS_ORDER = ("pipe", "data", "zero", "expert", "seq", "model")

# Axes that partition *examples* (the batch dim): DP, and expert-parallel
# groups, which are carved out of the DP group in the reference
# (utils/groups.py:113). The ``seq`` axis shards the *sequence* dim of the
# same examples (Ulysses): for batch arithmetic it multiplies nothing, but
# gradient reduction spans data x expert x seq — the reference's "ZeRO dp
# group becomes seq x dp" wiring (engine.py:1116-1122) falls out of XLA's
# partial-sum handling automatically.
BATCH_AXES = ("data", "zero", "expert")
SEQ_AXIS = "seq"


@dataclasses.dataclass
class MeshSpec:
    """Logical parallelism degrees. ``data=-1`` absorbs remaining devices."""

    data: int = -1
    model: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1
    zero: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {"pipe": self.pipe, "data": self.data, "zero": self.zero,
                 "expert": self.expert, "seq": self.seq, "model": self.model}
        fixed = int(np.prod([v for v in sizes.values() if v != -1]))
        n_auto = sum(1 for v in sizes.values() if v == -1)
        if n_auto > 1:
            raise ValueError("at most one mesh axis may be -1 (auto)")
        if n_auto == 1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by fixed axes product {fixed}")
            auto = n_devices // fixed
            sizes = {k: (auto if v == -1 else v) for k, v in sizes.items()}
        total = int(np.prod(list(sizes.values())))
        if total != n_devices:
            raise ValueError(
                f"mesh {sizes} requires {total} devices but {n_devices} are available")
        return sizes


def build_mesh(spec: MeshSpec | None = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    spec = spec or MeshSpec()
    if devices is None:
        devices = jax.devices()
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if devices[0].platform == "cpu":
        # host-platform devices carry no topology to lay a mesh out on
        dev_array = np.asarray(list(devices)).reshape(shape)
    else:
        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    mesh = Mesh(dev_array, AXIS_ORDER)
    logger.info(f"mesh: {dict(zip(AXIS_ORDER, shape))} over {len(devices)} devices")
    return mesh


# --------------------------------------------------------------------- helpers
def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def dp_world_size(mesh: Mesh) -> int:
    """Examples-parallel world size (data × expert), the divisor in the
    reference's train_batch = micro_batch × GAS × dp_world arithmetic."""
    return int(np.prod([mesh.shape[a] for a in BATCH_AXES]))


def batch_pspec() -> PartitionSpec:
    """Batch-dim sharding over all example-parallel axes."""
    return PartitionSpec(BATCH_AXES)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def local_batch_slice(mesh: Mesh) -> tuple[int, int]:
    """(index, count) of this host's shard of the global batch dimension."""
    # Per-host data loading: each process owns an equal contiguous slice.
    return jax.process_index(), jax.process_count()


def current_mesh():
    """The mesh active in this trace/context, or None. Checks the abstract
    mesh first (``jax.set_mesh`` / inside-jit / a shard_map body), then the
    ``with mesh:`` thread resources the engines enter."""
    from jax._src.mesh import thread_resources
    from jax.sharding import get_abstract_mesh

    ctx = get_abstract_mesh()
    if ctx is not None and not ctx.empty:
        return ctx
    ctx = thread_resources.env.physical_mesh
    return None if (ctx is None or ctx.empty) else ctx


def manual_axes_of(mesh) -> frozenset:
    """Axis names that are *manual* in the current trace context — i.e.
    the caller already holds a per-device block of them (inside a
    shard_map body)."""
    return frozenset(mesh.manual_axes)


def constrain(x, *spec_or_pspec):
    """``with_sharding_constraint`` that no-ops when no mesh is in context
    (single-chip / un-meshed execution) and ignores axes the context mesh
    doesn't carry — or that are *manual* in the current ``shard_map`` body
    (the caller already holds a per-device block of those). Models use this
    so the same code runs on a bare chip, on any parallel mesh, and inside
    partially-manual shard_maps (e.g. the compressed-gradient data axis)."""
    ctx = current_mesh()
    if ctx is None:
        return x
    spec = spec_or_pspec[0] if len(spec_or_pspec) == 1 and isinstance(
        spec_or_pspec[0], PartitionSpec) else PartitionSpec(*spec_or_pspec)
    filtered = fit_spec(filter_spec(spec), x.shape, ctx)
    # Inside a manual region a fully-filtered (all-None) constraint is a
    # no-op intent-wise — skip it outright.
    if manual_axes_of(ctx) and all(e is None for e in filtered):
        return x
    return jax.lax.with_sharding_constraint(x, filtered)


def fit_spec(spec: Optional[PartitionSpec], shape, mesh) -> PartitionSpec:
    """Drop the entries of ``spec`` whose mesh-axis product does not divide
    the dimension they shard: that dimension stays replicated. A published
    width need not divide the mesh — GPT-2's vocabulary of 50257 is odd, so
    its embedding cannot be vocab-split over any ``model`` axis — and JAX
    refuses an uneven ``NamedSharding`` outright."""
    entries = list(spec) if spec is not None else []

    def fits(e, dim):
        names = e if isinstance(e, (tuple, list)) else (e,)
        n = int(np.prod([mesh.shape[a] for a in names
                         if a in mesh.axis_names]))
        return dim % n == 0

    return PartitionSpec(*(
        e if e is None or (i < len(shape) and fits(e, shape[i])) else None
        for i, e in enumerate(entries)))


def kernel_mesh():
    """The context mesh when a Pallas call under it needs a ``shard_map``
    — GSPMD cannot partition a Mosaic kernel — else None: no mesh, one
    device, or already inside a manual region."""
    mesh = current_mesh()
    if mesh is None or mesh.empty or mesh.size == 1 or manual_axes_of(mesh):
        return None
    return mesh


def attention_shard_axes(batch: int, heads: int, kv_heads: int):
    """``(mesh, batch_axes, head_axes)`` to shard_map an attention kernel
    over, or None where :func:`kernel_mesh` is: batch over the
    example-parallel axes, heads over ``model``/``seq`` (the layout
    ``_attention_block`` constrains to). An axis group that does not divide
    its dim is left out (replicated)."""
    mesh = kernel_mesh()
    if mesh is None:
        return None

    def group(names, *dims):
        names = tuple(a for a in names
                      if a in mesh.axis_names and mesh.shape[a] > 1)
        n = int(np.prod([mesh.shape[a] for a in names])) if names else 1
        return names if names and all(d % n == 0 for d in dims) else None

    return (mesh, group(BATCH_AXES, batch),
            group(("model", "seq"), heads, kv_heads))


def fit_specs(specs, shapes, mesh):
    """:func:`fit_spec` over a ``param_specs()`` tree and the matching tree
    of shapes (tuples) or arrays."""
    return jax.tree.map(
        lambda s, a: fit_spec(s, tuple(getattr(a, "shape", a)), mesh),
        specs, shapes,
        is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))


def filter_spec(spec: PartitionSpec) -> PartitionSpec:
    """Drop axes the context mesh doesn't carry or that are manual."""
    ctx = current_mesh()
    if ctx is None:
        return spec
    manual = manual_axes_of(ctx)

    def filter_entry(e):
        if e is None:
            return None
        names = e if isinstance(e, (tuple, list)) else (e,)
        kept = tuple(n for n in names
                     if n in ctx.axis_names and n not in manual)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    return PartitionSpec(*(filter_entry(e) for e in spec))


def to_device_memory(tree, spec_tree=None):
    """Copy a (host-memory-resident) pytree into device HBM inside jit —
    the per-layer page-in of ZeRO-Infinity param offload. No-op outside a
    mesh context. ``spec_tree`` preserves each leaf's sharding across the
    memory-space move (device_put needs an explicit sharding in-jit)."""
    ctx = current_mesh()
    if ctx is None:
        return tree

    def put(x, spec):
        spec = filter_spec(spec if isinstance(spec, PartitionSpec)
                           else PartitionSpec())
        try:
            return jax.device_put(
                x, NamedSharding(ctx, spec, memory_kind="device"))
        except ValueError:
            # backends without an addressable "device" memory kind (older
            # JAX CPU exposes only unpinned_host): the page-in is a no-op
            # placement-wise but keeps the sharding
            return jax.device_put(x, NamedSharding(ctx, spec))

    if spec_tree is None:
        return jax.tree.map(lambda x: put(x, None), tree)
    return jax.tree.map(put, tree, spec_tree)

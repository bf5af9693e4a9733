"""``dstpu_bench``: collective micro-benchmark CLI.

Analog of the reference's ``bin/ds_bench`` (→ ``benchmarks/communication``):
sweep message sizes over the core collectives and report measured
algorithmic bandwidth per op. Runs on whatever devices JAX sees — the
virtual CPU mesh for plumbing checks, a TPU slice for real ICI numbers.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _bench_op(op_name: str, mesh: Mesh, n_elems: int, iters: int,
              dtype=jnp.float32) -> dict:
    """One (op, size) cell: compile, warm up, time, compute busbw."""
    D = mesh.devices.size
    axis = "x"

    # route through the package's own comm wrappers so the CommsLogger
    # ledger sees the traffic and the call conventions live in one place
    from . import comm as dcomm

    def body(x):
        if op_name == "all_reduce":
            return dcomm.all_reduce(x, axis)
        if op_name == "all_gather":
            return dcomm.all_gather(x, axis)
        if op_name == "reduce_scatter":
            return dcomm.reduce_scatter(x, axis)
        if op_name == "all_to_all":
            return dcomm.all_to_all(x.reshape(D, -1), axis, split_axis=0,
                                    concat_axis=0).reshape(-1)
        raise ValueError(op_name)

    per_dev = max(D * 8, n_elems // D)
    if op_name == "reduce_scatter":
        per_dev = max(per_dev, D)
    per_dev = per_dev // D * D          # a2a/scatter need divisibility
    sharding = NamedSharding(mesh, P(axis))
    x = jax.device_put(
        jnp.arange(per_dev * D, dtype=dtype) / (per_dev * D), sharding)
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                               out_specs=(P() if op_name == "all_reduce"
                                          else P(axis)),
                               check_vma=False))
    def _sync(o):
        # readback of the local shard only: works on multi-host slices
        # (a full np.asarray of a global array spanning non-addressable
        # devices would raise)
        leaf = jax.tree.leaves(o)[0]
        float(np.asarray(leaf.addressable_shards[0].data).ravel()[0])

    out = fn(x)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    _sync(out)
    dt = (time.perf_counter() - t0) / iters

    nbytes = per_dev * D * jnp.dtype(dtype).itemsize
    # standard busbw factors (NCCL-tests convention)
    factor = {"all_reduce": 2 * (D - 1) / D, "all_gather": (D - 1) / D,
              "reduce_scatter": (D - 1) / D, "all_to_all": (D - 1) / D}[op_name]
    busbw = nbytes * factor / dt if dt > 0 else float("inf")
    return {"op": op_name, "bytes": nbytes, "ms": dt * 1e3,
            "busbw_gbps": busbw / 1e9}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        prog="dstpu_bench", description="collective micro-benchmarks")
    p.add_argument("--ops", default="all_reduce,all_gather,reduce_scatter,"
                                    "all_to_all")
    p.add_argument("--min_elems", type=int, default=1 << 14)
    p.add_argument("--max_elems", type=int, default=1 << 24)
    p.add_argument("--iters", type=int, default=5)
    args = p.parse_args(argv)
    if args.iters < 1:
        p.error("--iters must be >= 1")
    if args.min_elems < 1 or args.max_elems < args.min_elems:
        p.error("need 1 <= min_elems <= max_elems")

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("x",))
    print(f"devices: {len(devices)} × {devices.ravel()[0].platform} | "
          f"iters={args.iters}")
    print(f"{'op':<16} {'bytes':>12} {'latency':>10} {'busbw':>12}")
    for op in args.ops.split(","):
        n = args.min_elems
        while n <= args.max_elems:
            r = _bench_op(op.strip(), mesh, n, args.iters)
            print(f"{r['op']:<16} {r['bytes']:>12,} {r['ms']:>8.2f}ms "
                  f"{r['busbw_gbps']:>9.2f} GB/s")
            n *= 16
    print("done")


if __name__ == "__main__":
    main()

"""Device time of ``ssm_state_step`` by the state a program takes.

    python examples/ssm_step_microbench.py [--shapes nemotron,falcon]
        [--groups 1,2,4,8] [--running 64,60]

One call of the kernel as a serving step makes it (the whole carried state,
a traced layer) at a cell's shape, for each number of groups of heads a
program takes and each count of running slots (the others stand at length
0, spread over the batch): the least of eight calls' device durations read
from a profiler capture by the benchmark's own reducer, that time a
program, and the share of the least the chip could take for the call's
bytes (``benchmark/kernels/ssm_state_step.py`` over ``benchmark/peaks.json``).
``--groups`` sets the block by ``_BLOCK_BYTES`` (the kernel takes its own
from the shapes: ``groups_per_program``); a block over 2 MiB is past what
the kernel ships and gets a raised ``vmem_limit_bytes`` here, to show where
the curve flattens. Needs the chip: a CPU run proves nothing about a
kernel's time.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from benchmark.harness import peaks_for
from benchmark.kernels.ssm_state_step import ops_and_bytes
from benchmark.reduce import load_trace
from deepspeed_tpu.ops import ssm_step

CALLS = 8
# Mamba-2 layers, slots, heads, groups, head width, state width: the two
# cells that run the kernel (PERF.md §4)
SHAPES = {
    "nemotron": (5, 64, 128, 8, 64, 128),
    "falcon": (6, 96, 32, 2, 128, 256),
}


def device_us(fn, S, args):
    """Least device duration in us of the kernel over CALLS calls of ``fn``,
    which hands the state back for the next call."""
    _, S = fn(S, *args)                           # compiled before the capture
    jax.block_until_ready(S)
    d = tempfile.mkdtemp(prefix="ssm_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            _, S = fn(S, *args)
        jax.block_until_ready(S)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    took = [(t1 - t0) * 1e6 for op, t0, t1 in trace.ops[trace.devices[0]]
            if op.split(".")[0] == "ssm_state_step"]
    assert len(took) == CALLS, len(took)
    return min(took)


def raised(limit: int):
    """``pallas`` as the kernel's module sees it, its calls given ``limit``
    bytes of scoped VMEM: for blocks past what the kernel ships."""
    def pallas_call(*a, **kw):
        return pl.pallas_call(*a, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=limit), **kw)

    return types.SimpleNamespace(**{**vars(pl), "pallas_call": pallas_call})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="nemotron")
    ap.add_argument("--groups", default="1,2,4,8")
    ap.add_argument("--running", default="",
                    help="counts of running slots, those a shape has "
                    "(all of its slots if none)")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a kernel's time comes from a chip"
    peak = peaks_for(jax.devices()[0].device_kind)
    shipped = ssm_step._BLOCK_BYTES
    f32 = jnp.float32
    for shape in a.shapes.split(","):
        L, B, H, G, P, N = SHAPES[shape]
        keys = jax.random.split(jax.random.PRNGKey(a.seed), 6)
        x = jax.random.normal(keys[1], (B, H, P), f32)
        dt = jax.nn.softplus(jax.random.normal(keys[2], (B, H), f32))
        A = -jnp.exp(jax.random.normal(keys[3], (H,), f32))
        Bv = jax.random.normal(keys[4], (B, G, N), f32)
        Cv = jax.random.normal(keys[5], (B, G, N), f32)
        one = (H // G) * P * N * 4
        ships = ssm_step.groups_per_program(H, G, P, N)
        for gb in (int(g) for g in a.groups.split(",")):
            if G % gb:
                continue
            ssm_step._BLOCK_BYTES = gb * one
            assert ssm_step.groups_per_program(H, G, P, N) == gb
            ssm_step.pl = raised(4 * gb * one + (8 << 20)) \
                if gb * one > shipped else pl
            for running in [int(r) for r in a.running.split(",")
                            if r and int(r) <= B] or [B]:
                n = np.ones(B, np.int32)
                n[np.linspace(0, B - 1, B - running).astype(int)] = 0
                _, nbytes = ops_and_bytes(running=running, H=H, P=P, N=N,
                                          G=G)["ssm_state_step"]
                least = nbytes / peak["hbm_bytes_per_s"] * 1e6

                # a fresh function a block: the groups are read while tracing
                def call(S, n):
                    return ssm_step.ssm_state_step(
                        S, jnp.int32(1), x, dt, A, Bv, Cv, n, interpret=False)

                S = jax.random.normal(keys[0], (L, B, H, P, N), f32)
                us = device_us(jax.jit(call, donate_argnums=0), S,
                               (jnp.asarray(n),))
                programs = (G // gb) * B
                print(json.dumps({
                    "shape": shape, "groups_a_program": gb,
                    "block_kib": gb * one // 1024, "shipped": gb == ships,
                    "running": running, "slots": B, "programs": programs,
                    "us_a_call": round(us, 2),
                    "us_a_program": round(us / programs, 3),
                    "us_a_running_program": round(
                        us / ((G // gb) * running), 3),
                    "least_us_a_call": round(least, 2),
                    "pct_of_least": round(100 * least / us, 2),
                    "gb_per_s": round(nbytes / us / 1e3, 1)}), flush=True)
        ssm_step._BLOCK_BYTES, ssm_step.pl = shipped, pl


if __name__ == "__main__":
    main()

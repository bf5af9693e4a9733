"""Device time of a KDA layer's chunkwise scan: the kernel beside the scan it
replaced.

    python examples/kda_chunk_scan_microbench.py [--tokens 64,256,512]
        [--heads 1,2,4,8] [--batch 1]

The KDA layers of Solar-Open2's and GLM-5.3-Flash's cells: a chunk's T tokens
of 64 heads of 128 x 128, float32, beta in (0, 2), a gate with no floor (one
head in eight forgets within a token). ``scan`` is ``kda.scan_chunked`` ALONE
in its program: its best case (a walk of plain ``jnp`` can lose XLA's VMEM
placements inside a larger program: PERF.md §6 "PR 58" (d)); this scan cost
the chunk's program what it costs here, ~3.6 ms at 512 tokens (PERF.md §6
"PR 61"). ``kernel`` is ``kda_chunk_scan`` by the heads a program takes. ms a
call: the device's busy time in a profiler capture of :data:`CALLS` calls
(the union of its ops' intervals, read by the benchmark's own reducer);
``ms_block``: that over the blocks of 64 tokens; ``err_over_max``: the
kernel's worst difference from the scan over the scan's largest value, o and
the state. Needs the chip: a CPU run proves nothing about a time.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark.reduce import load_trace, merge, total
from deepspeed_tpu.models import kda
from deepspeed_tpu.ops.kda_chunk import CHUNK, kda_chunk_scan

CALLS = 6
H, D = 64, 128


def device_ms(fn, *args):
    """Busy device time in ms a call of jitted ``fn`` over CALLS calls."""
    jax.block_until_ready(fn(*args))              # compiled before the capture
    d = tempfile.mkdtemp(prefix="kda_chunk_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    busy = total(merge((t0, t1) for _, t0, t1 in trace.ops[trace.devices[0]]))
    return round(busy * 1e3 / CALLS, 4)


def inputs(B: int, T: int):
    ks = jax.random.split(jax.random.PRNGKey(T), 6)

    def l2(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q, k, v = (jax.random.normal(x, (B, T, H, D)) for x in ks[:3])
    g = -jnp.exp(2.0 * jax.random.normal(ks[3], (B, T, H, D)) - 3.0)
    g = jnp.where((jnp.arange(H) % 8 == 7)[:, None], g - 40.0, g)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = 0.3 * jax.random.normal(ks[5], (B, H, D, D))
    return l2(q), l2(k), v, g, beta, S0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="64,256,512")
    ap.add_argument("--heads", default="1,2,4,8")
    ap.add_argument("--batch", type=int, default=1)
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a time comes from a chip"
    ints = lambda text: [int(x) for x in text.split(",") if x]   # noqa: E731
    scan = jax.jit(kda.scan_chunked)
    for T in ints(a.tokens):
        args = inputs(a.batch, T)
        want = scan(*args)
        ms = device_ms(scan, *args)
        blocks = -(-T // CHUNK)
        print(json.dumps({"T": T, "path": "scan", "ms": ms,
                          "ms_block": round(ms / blocks, 4)}), flush=True)
        for heads in ints(a.heads):
            out = {"T": T, "path": "kernel", "heads": heads}
            try:
                fn = jax.jit(lambda *x, heads=heads:
                             kda_chunk_scan(*x, heads=heads))
                got = fn(*args)
                ms = device_ms(fn, *args)
                out.update(ms=ms, ms_block=round(ms / blocks, 4),
                           err_over_max=[round(float(
                               jnp.abs(x - y).max() / jnp.abs(y).max()), 8)
                               for x, y in zip(got, want)])
            except Exception as e:              # a tiling Mosaic refuses
                out["refused"] = str(e).strip().splitlines()[-1][:200]
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""Device time of a chunk's attention over whole K/V planes: the kernel
beside the walk it replaced.

    python examples/gqa_chunk_attention_microbench.py
        [--live 8,24,44,64,110] [--buckets 8,64] [--tiles 256,512,1024]
        [--blocks 512,1024,2048] [--rows 512,4096]

The attention layer of Solar-Open2's cell
(``solar-open2-250b-l4-e40.serve-backlog-longreason``): a chunk's 512 queries
of 64 heads over 8 KV heads of 128 / 128 at the end of ``live`` blocks of 512
positions of planes of 65 536. ``walk`` is ``windowed.attend_blocks`` as
``inference/kinds/delta_gqa.py`` called it, ALONE in its program — where
XLA's memory-space assignment finds room in VMEM for a block's 64 MB of
float32 scores (``S(1)`` on ``f32[1,8,8,512,512]`` in the compiled text) and
the walk runs at half the MXU's rate; inside the chunk's program the same
array stands in HBM and the walk takes 0.31 ms a block (PERF.md §6 "PR 57",
"PR 58"): the walk's row here is its best case, not what the kernel
replaced. ``kernel`` is ``gqa_chunk_attention``
by the rows a product takes (``tile``), the keys a turn (``block``) and the
rows a program owns (``rows``); a bucket is a final chunk's T queries at the
end of 44 blocks. ms a call: the device's busy time in a profiler capture of
:data:`CALLS` calls (the union of its ops' intervals, read by the benchmark's
own reducer) over the calls; ``ms_block``: that over the live blocks of 512;
``mxu_share``: ``q . k`` and ``p . v`` over the live blocks at 197 TFLOP/s
over that time. Needs the chip: a CPU run proves nothing about a time.
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
import numpy as np

from benchmark.reduce import load_trace, merge, total
from deepspeed_tpu.models import windowed
from deepspeed_tpu.ops.chunk_attention import gqa_chunk_attention

CALLS = 6
S, T, H, KV, HD, VD, BLOCK = 65536, 512, 64, 8, 128, 128, 512
PEAK = 197e12


def device_ms(fn, *args):
    """Busy device time in ms a call of jitted ``fn`` over CALLS calls."""
    jax.block_until_ready(fn(*args))              # compiled before the capture
    d = tempfile.mkdtemp(prefix="gqa_chunk_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    busy = total(merge((t0, t1) for _, t0, t1 in trace.ops[trace.devices[0]]))
    return round(busy * 1e3 / CALLS, 4)


def figures(ms: float, live: int, queries: int) -> dict:
    """ms a live block of 512 keys, and the two products' share of the
    MXU's peak."""
    flops = 2.0 * H * queries * live * BLOCK * (HD + VD)
    return {"ms": ms, "ms_block": round(ms / live, 4),
            "mxu_share": round(flops / PEAK / ms * 1e3, 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="8,24,44,64,110")
    ap.add_argument("--buckets", default="8,64")
    ap.add_argument("--tiles", default="256,512,1024")
    ap.add_argument("--blocks", default="512,1024,2048")
    ap.add_argument("--rows", default="512,4096")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a time comes from a chip"
    ints = lambda text: [int(x) for x in text.split(",") if x]   # noqa: E731
    bf = jnp.bfloat16
    rng = np.random.default_rng(0)
    ck = jnp.asarray(rng.standard_normal((1, 1, KV, HD, S)), bf)
    cv = jnp.asarray(rng.standard_normal((1, 1, KV, VD, S)), bf)
    layer = jnp.int32(0)
    walk_ = jax.jit(lambda q, ck, cv, start, layer: windowed.attend_blocks(
        q, ck, cv, (start + jnp.arange(q.shape[1], dtype=jnp.int32))[None],
        start + q.shape[1], layer=layer))

    def walk(q, start):
        return walk_(q, ck, cv, start, layer)

    def kernel(**how):
        fn = jax.jit(lambda q, ck, cv, start, layer: gqa_chunk_attention(
            q, ck, cv, start, layer=layer, **how))
        return lambda q, start: fn(q, ck, cv, start, layer)

    def row(q, start, live, **how):
        out = {"live": live, "T": q.shape[1], "path": "kernel", **how}
        try:
            fn = kernel(**how)
            got = fn(q, start).astype(jnp.float32)
            want = walk(q, start).astype(jnp.float32)
            out.update(figures(device_ms(fn, q, start), live, q.shape[1]),
                       err_over_max=round(float(
                           jnp.abs(got - want).max() / jnp.abs(want).max()),
                           5))
        except Exception as e:              # a tiling Mosaic refuses
            out["refused"] = str(e).strip().splitlines()[-1][:200]
        print(json.dumps(out), flush=True)

    q = jnp.asarray(rng.standard_normal((1, T, H, HD)), bf)
    for live in ints(a.live):
        start = jnp.int32(live * BLOCK - T)
        print(json.dumps({"live": live, "T": T, "path": "walk", **figures(
            device_ms(walk, q, start), live, T)}), flush=True)
        row(q, start, live)                             # as the kind calls it
        for block in ints(a.blocks):
            for tile in ints(a.tiles):
                for rows in ints(a.rows):
                    row(q, start, live, tile=tile, block=block, rows=rows)
    for bucket in ints(a.buckets):              # a final chunk, right-padded
        live = 44
        qb = q[:, :bucket]
        start = jnp.int32(live * BLOCK - bucket)
        print(json.dumps({"live": live, "T": bucket, "path": "walk",
                          **figures(device_ms(walk, qb, start), live,
                                    bucket)}), flush=True)
        row(qb, start, live)


if __name__ == "__main__":
    main()

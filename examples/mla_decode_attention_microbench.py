"""Device time of ``mla_decode_attention`` by a slot's live length, by the
cache's length behind it, and by the width of the kernel's turn.

    python examples/mla_decode_attention_microbench.py [--shapes kanana,ling]
        [--live 0,128,1024,2816,5248,12288,max] [--max-len 8192,24576]
        [--turn-kib 144,288,576,1152,2304]

One layer's call as a serving step makes it (the whole cache, a traced
layer, the lengths a slot) at the two cells' shapes — ``kanana``
(``kanana-2-30b-a3b-l7.serve-backlog-longdoc``): 48 slots of 8192 positions;
``ling`` (``ling-3.0-flash-l6-e64.serve-backlog-reasontail``): 160 slots of
24 576 — 32 heads over 576 values a position in bf16. Three sweeps a shape:
every slot at ``live`` positions in the shape's own cache (what a live
position costs); the shape's usual live length in a cache of each
``--max-len`` (what the positions BEHIND the live length cost: nothing, if
the kernel's work follows the live length); and, where the module has a
byte target for its turn (``_TURN_BYTES``), the usual live length by that
target (``turn_blocks``: 128-lane blocks a turn). us a call: the kernel's
own device time in a profiler capture of :data:`CALLS` calls, read by the
benchmark's reducer, beside the least the chip could take for the call's
bytes (``benchmark/kernels/mla_decode_attention.py`` over
``benchmark/peaks.json``: the live latents once at 1152 B a position, q and
o). Needs the chip: a CPU run proves nothing about a kernel's time.
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

from benchmark.harness import peaks_for
from benchmark.kernels.mla_decode_attention import ops_and_bytes
from benchmark.reduce import base_name, load_trace
from deepspeed_tpu.ops import mla_attention as mla

CALLS = 6
KERNEL = "mla_decode_attention"
H, RANK, ROPE = 32, 512, 64
# slots, max_len, the cell's usual live length a slot (PERF.md §5)
SHAPES = {"kanana": (48, 8192, 2816), "ling": (160, 24576, 5248)}


def kernel_us(fn, *args):
    """us a call of the kernel's own device time over CALLS calls."""
    jax.block_until_ready(fn(*args))              # compiled before the capture
    d = tempfile.mkdtemp(prefix="mla_decode_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            o = fn(*args)
        jax.block_until_ready(o)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    took = [t1 - t0 for name, t0, t1 in trace.ops[trace.devices[0]]
            if base_name(name) == KERNEL]
    assert len(took) == CALLS, (len(took), CALLS)
    return sum(took) * 1e6 / CALLS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="kanana,ling")
    ap.add_argument("--live", default="0,128,1024,2816,5248,12288,max")
    ap.add_argument("--max-len", default="8192,24576")
    ap.add_argument("--turn-kib", default="")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a kernel's time comes from a chip"
    peak = peaks_for(jax.devices()[0].device_kind)
    bf, D = jnp.bfloat16, RANK + ROPE
    rng = np.random.default_rng(0)
    target = getattr(mla, "_TURN_BYTES", None)

    def row(shape, B, S, live, cache, q, **more):
        # a fresh function a row: the turn's width is read while tracing
        fn = jax.jit(lambda q, c, n: mla.mla_decode_attention(
            q, c, n, layer=jnp.int32(cache.shape[0] - 1), rank=RANK,
            scale=D ** -0.5, interpret=False))
        us = kernel_us(fn, q, cache, jnp.full((B,), live, jnp.int32))
        _, nbytes = ops_and_bytes(live_tokens=B * live, slots=B, heads=H,
                                  rank=RANK, rope=ROPE)
        least = nbytes / peak["hbm_bytes_per_s"] * 1e6
        print(json.dumps({
            "shape": shape, "slots": B, "max_len": S, "live_a_slot": live,
            **more, "us_a_call": round(us, 2),
            "us_a_slot": round(us / B, 3),
            "least_us_a_call": round(least, 2),
            "pct_of_least": round(100 * least / us, 2)}), flush=True)

    for shape in a.shapes.split(","):
        B, own, usual = SHAPES[shape]
        q = jnp.asarray(rng.standard_normal((B, H, D)), bf)
        for S in dict.fromkeys([own] + [int(s) for s in a.max_len.split(",")]):
            # Kanana's step reads a layer other than 0; Ling's has the one
            L = 2 if B * S * D * 2 < 2 ** 31 else 1
            one = jnp.asarray(rng.standard_normal((1, 1, D, S)), bf)
            cache = jnp.tile(one, (L, B, 1, 1))
            if S != own:
                row(shape, B, S, usual, cache, q)
                continue
            for live in a.live.split(","):
                live = S if live == "max" else int(live)
                if live <= S:
                    row(shape, B, S, live, cache, q)
            for kib in (int(k) for k in a.turn_kib.split(",") if k):
                assert target is not None, "this kernel's turn has no width"
                mla._TURN_BYTES = kib * 1024
                row(shape, B, S, usual, cache, q, turn_kib=kib,
                    blocks_per_turn=mla.turn_blocks(D, S, bf))
            if target is not None:
                mla._TURN_BYTES = target
            del cache


if __name__ == "__main__":
    main()

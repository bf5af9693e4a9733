"""Device time of the appending ``decode_attention`` by the width of its turn.

    python examples/decode_attention_microbench.py [--shapes zaya,gpt2]
        [--turn-kib 64,256,512,1024] [--tail]

One call of the kernel as a serving step makes it (the whole cache, a traced
layer, the step's new K/V appended) at a cell's shape and over lengths like
the cell's, for each per-turn byte target: the blocks a turn takes (W, from
``blocks_per_turn``), the least of eight calls' device durations read from a
profiler capture by the benchmark's own reducer, that time a program, and
the share of the least the chip could take for the call's bytes
(``benchmark/kernels/full_decode_attention.py`` over ``benchmark/peaks.json``:
the live K and V once, the block written back a slot, q and o). A wall clock
around the call reads 0.7 ms more than the kernel takes. ``--tail``: the same
call with the cache's deferred tail beside it (``tail=``: rows into a tile
every step, the block back once a tile; what the ``Dense`` kind passes), its
time next to the call's without (``us_a_call_tail``, ``tail_gain_pct``), at
lengths of which one in T completes its group as in a running batch. Needs
the chip: a CPU run proves nothing about a kernel's time.
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
from benchmark.kernels.full_decode_attention import ops_and_bytes
from benchmark.reduce import load_trace
from deepspeed_tpu.ops import decode_attention as da

CALLS = 8
LAYERS = 2
# slots, query heads, KV heads, key width, value width, max_len, and the
# live lengths' range: the serving cells' shapes (PERF.md §4); an eighth
# entry: how many of the slots run (the others stand at length 0)
SHAPES = {
    "zaya": (48, 8, 2, 128, 128, 4096, (128, 2272)),
    "nemotron": (64, 32, 2, 128, 128, 6144, (192, 3008)),
    "mimo_full": (32, 64, 4, 192, 128, 8192, (512, 6144)),   # the cell's 32768
    "gpt2": (48, 20, 20, 64, 64, 1024, (64, 520)),
    "gpt2_chat": (48, 20, 20, 64, 64, 1024, (64, 520), 4),   # the chat cells
    "ouro": (12, 16, 16, 128, 128, 384, (64, 224)),
}


def device_us(fn, args, name):
    """Least device duration in us of kernel ``name`` over CALLS calls of
    ``fn``, which hands the caches (and the tail, if one came) back for the
    next call."""
    q, n, k, v, *held = args
    _, *held = fn(q, n, k, v, *held)              # compiled before the capture
    jax.block_until_ready(held)
    d = tempfile.mkdtemp(prefix="decode_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            _, *held = fn(q, n, k, v, *held)
        jax.block_until_ready(held)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    took = [(t1 - t0) * 1e6 for op, t0, t1 in trace.ops[trace.devices[0]]
            if op.split(".")[0] == name]
    assert len(took) == CALLS, (name, len(took))
    return min(took)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="zaya")
    ap.add_argument("--turn-kib", default="64,128,256,512,1024,2048")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tail", action="store_true")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a kernel's time comes from a chip"
    peak = peaks_for(jax.devices()[0].device_kind)
    for shape in a.shapes.split(","):
        B, H, KV, hd, vd, S, (lo, hi), *running = SHAPES[shape]
        rng = np.random.default_rng(a.seed)
        keys = jax.random.split(jax.random.PRNGKey(a.seed), 6)
        q = jax.random.normal(keys[0], (B, 1, H, hd), jnp.bfloat16)
        k = jax.random.normal(keys[1], (B, 1, KV, hd), jnp.bfloat16)
        v = jax.random.normal(keys[2], (B, 1, KV, vd), jnp.bfloat16)
        n = rng.integers(lo, hi + 1, (B,))
        n[running[0] if running else B:] = 0
        n = jnp.asarray(n, jnp.int32)
        live = float(np.asarray(n).sum())
        _, nbytes = ops_and_bytes(live=live, running=int((n > 0).sum()),
                                  heads=H, kv_heads=KV,
                                  head_dim=hd, v_dim=vd)
        least = nbytes / peak["hbm_bytes_per_s"] * 1e6
        hb = da._heads_per_program(KV, hd, da.LANES, jnp.bfloat16)
        for kib in (int(x) for x in a.turn_kib.split(",")):
            da._ATTEND_TURN_BYTES = kib * 1024
            W = da.blocks_per_turn(KV, hd, vd, S, jnp.bfloat16)
            def held(tail):
                shapes = [(LAYERS, B, KV, hd, S), (LAYERS, B, KV, vd, S)] \
                    + [(LAYERS, B, KV, da.tail_rows(jnp.bfloat16), hd + vd)
                       ] * tail
                return [jax.random.normal(key, shape, jnp.bfloat16)
                        for key, shape in zip(keys[3:], shapes)]

            # a fresh function a target: the width is read while tracing
            def call(q, n, k, v, ck, cv, tail=None):
                return da.decode_attention(
                    q, ck, cv, n, k=k, v=v, tail=tail, layer=jnp.int32(1),
                    interpret=False, name="decode_attention")

            us = device_us(jax.jit(call, donate_argnums=(4, 5)),
                           (q, n, k, v, *held(False)), "decode_attention")
            with_tail = {}
            if a.tail:
                tailed = device_us(jax.jit(call, donate_argnums=(4, 5, 6)),
                                   (q, n, k, v, *held(True)),
                                   "decode_attention")
                with_tail = {"us_a_call_tail": round(tailed, 2),
                             "tail_gain_pct": round(100 * (1 - tailed / us),
                                                    2)}
            blocks = -(-np.asarray(n) // da.LANES)
            print(json.dumps({
                "shape": shape, "turn_kib": kib, "blocks_per_turn": W,
                "block_kib": hb * max(hd, vd) * da.LANES * 2 // 1024,
                "live_a_slot": round(live / B, 1),
                "turns_a_slot": round(float((-(-blocks // W)).mean()), 2),
                "us_a_call": round(us, 2), **with_tail,
                "us_a_program": round(us / (B * (KV // hb)), 3),
                "least_us_a_call": round(least, 2),
                "pct_of_least": round(100 * least / us, 2)}), flush=True)


if __name__ == "__main__":
    main()

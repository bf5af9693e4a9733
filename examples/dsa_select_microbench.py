"""Device time of ``dsa.select`` beside the sort it replaced.

    python examples/dsa_select_microbench.py [--live 4096,9216,16384,30720]
        [--parts] [--blocks 0,4096] [--bits 1,2,4]

The selection of GLM-5.2's cell (``index_topk`` 2048 of ``max_len`` 32 768)
as its programs make it twice an iteration: a chunk's 512 queries at the end
of ``live`` positions, ``(1, 512, 32768)``, with the mask, and the step's
``(10, 1, 32768)`` without. ``old`` is ``lax.top_k`` and the tie rule as
``models/dsa.py`` had them until PR 52; ``new`` is ``dsa.select``: the
threshold by bisection and the indices counted out of the mask. ms a call:
the device's busy time in a profiler capture of :data:`CALLS` calls (the
union of its ops' intervals, read by the benchmark's own reducer) over the
calls; a wall clock around a jitted call reads 0.7 ms more than the call
takes. ``--parts``: the threshold alone by the keys a step of its counting
walk takes (0: no walk, every pass counts all 32 768) and the bits a pass
settles, and the indices alone. Needs the chip: a CPU run proves nothing
about a time.
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
from jax import lax

from benchmark.reduce import load_trace, merge, total
from deepspeed_tpu.models import dsa

CALLS = 6
S, K, CHUNK, SLOTS = 32768, 2048, 512, 10


def old_select(score, q_pos, topk, want_mask=True):
    """``dsa.select`` as it stood before PR 52: one sort, the mask from its
    K-th value."""
    causal = jnp.arange(S, dtype=jnp.int32)[None, None] <= q_pos[..., None]
    masked = jnp.where(causal, score, -jnp.inf)
    vals, idx = lax.top_k(masked, topk)
    idx = jnp.where(vals > -jnp.inf, idx, -1).astype(jnp.int32)
    if not want_mask:
        return idx, None
    thr = vals[..., -1:]
    above, tied = masked > thr, (masked == thr) & causal
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return idx, above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


def device_ms(fn, *args):
    """Busy device time in ms a call of jitted ``fn`` over CALLS calls."""
    jax.block_until_ready(fn(*args))              # compiled before the capture
    d = tempfile.mkdtemp(prefix="dsa_select_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    busy = total(merge((t0, t1) for _, t0, t1 in trace.ops[trace.devices[0]]))
    return round(busy * 1e3 / CALLS, 4)


def scores(seed, rows, live):
    """What an indexer gives: weighted sums of ReLUs, a share of them exact
    zeros; nothing behind ``live`` (a chunk's walk leaves 0 there)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, S)).astype(np.float32)
    x = np.where(rng.random((rows, S)) < 0.2, 0.0, x * x - 0.3)
    return np.where(np.arange(S) < live, x, 0.0).astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="4096,9216,16384,30720")
    ap.add_argument("--parts", action="store_true")
    ap.add_argument("--blocks", default="0,4096")
    ap.add_argument("--bits", default="1,2,4")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a time comes from a chip"
    ints = lambda text: [int(x) for x in text.split(",")]   # noqa: E731

    old = jax.jit(lambda s, p: old_select(s, p, K))
    new = jax.jit(lambda s, p, n: dsa.select(s, p, K, n_keys=n))
    for live in ints(a.live):
        sc = jnp.asarray(scores(live, CHUNK, live))[None]
        pos = (live - CHUNK + jnp.arange(CHUNK, dtype=jnp.int32))[None]
        n = jnp.int32(live)
        (_, m0), (i1, m1) = old(sc, pos), new(sc, pos, n)
        same = bool(jnp.array_equal(m0, m1)) and bool(jnp.array_equal(
            jnp.sort(jnp.where(m1, jnp.arange(S), S), -1)[..., :K],
            jnp.where(i1 >= 0, i1, S)))
        print(json.dumps({"shape": [1, CHUNK, S], "live": live,
                          "old_ms": device_ms(old, sc, pos),
                          "new_ms": device_ms(new, sc, pos, n),
                          "same_set": same}), flush=True)
        if not a.parts:
            continue
        causal = jnp.arange(S, dtype=jnp.int32)[None, None] <= pos[..., None]
        key = dsa._order_keys(sc, causal)
        for block in ints(a.blocks):
            for bits in ints(a.bits):
                kth = jax.jit(lambda k, n, block=block, bits=bits:
                              dsa._kth_largest(k, K, n if block else None,
                                               block or S, bits))
                print(json.dumps({"live": live, "part": "threshold",
                                  "block": block, "bits": bits,
                                  "ms": device_ms(kth, key, n)}), flush=True)
        count = jax.jit(lambda m: dsa._positions(m, K))
        print(json.dumps({"live": live, "part": "indices",
                          "ms": device_ms(count, m1)}), flush=True)

    # the step: ten slots, a query each at the end of its own length
    lens = np.random.default_rng(0).integers(4096, 30720, (SLOTS,))
    sc = jnp.asarray(np.stack([scores(i, 1, n)
                               for i, n in enumerate(lens)]))
    pos = jnp.asarray(lens - 1, jnp.int32)[:, None]
    step_old = jax.jit(lambda s, p: old_select(s, p, K, want_mask=False)[0])
    step_new = jax.jit(lambda s, p: dsa.select(s, p, K, want_mask=False)[0])
    i0, i1 = step_old(sc, pos), step_new(sc, pos)
    print(json.dumps({"shape": [SLOTS, 1, S],
                      "old_ms": device_ms(step_old, sc, pos),
                      "new_ms": device_ms(step_new, sc, pos),
                      "same_set": bool(jnp.array_equal(jnp.sort(i0, -1),
                                                       jnp.sort(i1, -1)))}),
          flush=True)


if __name__ == "__main__":
    main()

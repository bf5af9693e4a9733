"""Device time of the step's read of an indexer's selection: the two ways
``sparse_mla_decode_attention`` brings a slot's latents into VMEM.

    python examples/sparse_mla_decode_attention_microbench.py
        [--shapes glm52,glm53] [--ratios 1,1.4,4,8.6,16,32,64]
        [--blocks 512,1024,2048]

One layer's call at a cell's shape — ``glm52``
(``glm-5.2-l7-e16.serve-backlog-longctx``): 10 slots of a cache of 32 768
rows of 384 words (576 values, 1536 B), up to 2048 selected, a descriptor a
position; ``glm53`` (``glm-5.3-flash-l5-e36.serve-backlog-longgen``): 160
slots of 8192 rows of 256 words (512 values, 1024 B), up to 2052 selected in
aligned groups of 4, a descriptor a group — every slot at ``live`` positions
of which ``selected`` are picked at random, over live / selected of
``--ratios`` (``selected`` the shape's most while ``live`` fits the cache,
fewer behind that). ``gathered``: every slot fetches by ``idx``;
``dense``: every slot walks its live blocks of ``block`` positions under the
mask (the rule that chooses between them, ``sparse.reads_dense``, is held to
one side for the measurement, here and nowhere in the program). us a call:
the kernel's own device time in a profiler capture of :data:`CALLS` calls,
read by the benchmark's reducer; ``ns_a_selected`` and ``ns_a_live`` the same
over the rows each side brings in; ``gbps``: the live rows' bytes over the
dense side's time. ``crossover``: live / selected where the two sides' fitted
costs are equal — a descriptor's ns (gathered, a selected row) over a live
row's ns (dense) — which is what ``DESCRIPTOR_NS`` and
``DENSE_BYTES_PER_NS`` of ``ops/sparse_mla_attention.py`` hold. Needs the
chip: a CPU run proves nothing about a time.
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

from benchmark.reduce import base_name, load_trace
from deepspeed_tpu.ops import sparse_mla_attention as sparse

CALLS = 6
KERNEL = "sparse_mla_decode_attention"
H, RANK = 64, 512
SHAPES = {      # slots, cache rows, values a row, most selected, run
    "glm52": (10, 32768, 576, 2048, 1),
    "glm53": (160, 8192, 512, 2052, 4),
}


def kernel_us(fn, cache, *args):
    """(us a call of the kernel's own device time over CALLS calls of
    jitted ``fn(cache, *args) -> (o, cache)``, the last ``o``, the cache)."""
    o, cache = fn(cache, *args)                   # compiled before the capture
    jax.block_until_ready(o)
    d = tempfile.mkdtemp(prefix="sparse_mla_decode_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            o, cache = fn(cache, *args)
        jax.block_until_ready(o)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    took = [t1 - t0 for name, t0, t1 in trace.ops[trace.devices[0]]
            if base_name(name) == KERNEL]
    assert len(took) == CALLS, (len(took), CALLS)
    return round(sum(took) * 1e6 / CALLS, 2), o, cache


def selection(rng, B, S, K, run, live, selected):
    """``selected`` of the first ``live`` positions of every slot, whole
    aligned groups of ``run``, the slot's last position among them (a step's
    query reads its own group): (idx (B, K) ascending, -1 behind, mask
    (B, 1, S) bool)."""
    idx = np.full((B, K), -1, np.int32)
    mask = np.zeros((B, 1, S), bool)
    groups, last = -(-live // run), (live - 1) // run
    for b in range(B):
        take = rng.choice(groups - 1, size=-(-selected // run) - 1,
                          replace=False) if groups > 1 else []
        pos = (np.sort(np.append(take, last))[:, None] * run
               + np.arange(run)).reshape(-1)
        pos = pos[pos < live][:K]
        idx[b, :len(pos)] = pos
        mask[b, 0, pos] = True
    return idx, mask


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="glm52,glm53")
    ap.add_argument("--ratios", default="1,1.4,4,8.6,16,32,64")
    ap.add_argument("--blocks", default="512,1024,2048")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a time comes from a chip"
    bf = jnp.bfloat16
    rng = np.random.default_rng(0)
    rule = sparse.reads_dense
    for shape in a.shapes.split(","):
        B, S, D, K, run = SHAPES[shape]
        lat = jnp.asarray(rng.standard_normal((1, 1, S, D)), bf)
        cache = jnp.tile(sparse.pack_rows(lat, bf), (1, B, 1, 1, 1))
        row_bytes = cache.shape[-1] * 4
        q = jnp.asarray(rng.standard_normal((B, H, D)), bf)
        new = jnp.asarray(rng.standard_normal((B, D)), bf)
        fits = {"gathered": [], "dense": {}}
        for ratio in (float(r) for r in a.ratios.split(",")):
            live = min(S, int(round(ratio * K)) // run * run)
            selected = max(run, int(round(live / ratio)) // run * run)
            idx, mask = selection(rng, B, S, K, run, live, selected)
            n = jnp.asarray((idx >= 0).sum(-1), jnp.int32)
            length = jnp.full((B,), live, jnp.int32)
            idx, mask = jnp.asarray(idx), sparse.step_mask(jnp.asarray(mask))
            row = {"shape": shape, "ratio": ratio, "live": live,
                   "selected": int(n[0])}

            def call(**kw):
                return jax.jit(lambda c, q, new, idx, length, n, *m:
                               sparse.sparse_mla_decode_attention(
                                   q, c, new, idx, length, layer=0, rank=RANK,
                                   scale=D ** -0.5, n=n, run=run,
                                   mask=m[0] if m else None, **kw),
                               donate_argnums=(0,))

            us, want, cache = kernel_us(call(), cache, q, new, idx, length, n)
            per = us * 1e3 / (B * int(n[0]))
            fits["gathered"].append(per * run)
            print(json.dumps({**row, "path": "gathered", "us": us,
                              "ns_a_selected": round(per, 2)}), flush=True)
            sparse.reads_dense = lambda length, n, *_: length >= 0
            try:
                for block in (int(x) for x in a.blocks.split(",")):
                    out = {**row, "path": "dense", "block": block}
                    try:
                        us, got, cache = kernel_us(
                            call(block=block), cache, q, new, idx, length, n,
                            mask)
                        rows = B * -(-live // block) * block
                        per = us * 1e3 / rows
                        fits["dense"].setdefault(block, []).append(per)
                        out.update(
                            us=us, ns_a_live=round(per, 3),
                            gbps=round(rows * row_bytes / us / 1e3, 1),
                            err_over_max=round(float(
                                jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32)).max()
                                / jnp.abs(want.astype(jnp.float32)).max()),
                                5))
                    except Exception as e:      # a tiling Mosaic refuses
                        out["refused"] = str(e).strip().splitlines()[-1][:200]
                    print(json.dumps(out), flush=True)
            finally:
                sparse.reads_dense = rule
        # a descriptor's cost where the selected rows are many (the first
        # ratios), a live row's where the live rows are (the last)
        desc = float(np.median(fits["gathered"]))
        for block, per in fits["dense"].items():
            live_ns = float(np.median(per))
            print(json.dumps({
                "shape": shape, "block": block, "run": run,
                "descriptor_ns": round(desc, 1),
                "dense_ns_a_row": round(live_ns, 3),
                "dense_bytes_per_ns": round(row_bytes / live_ns, 1),
                "crossover": round(desc / run / live_ns, 1),
                "rule_now": round(sparse.crossover(run, row_bytes), 1)}),
                flush=True)


if __name__ == "__main__":
    main()

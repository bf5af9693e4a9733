"""Device time of a chunk's attention over an indexer's selection: the
kernel beside the walk it replaced.

    python examples/sparse_mla_chunk_attention_microbench.py
        [--live 4096,9216,16384,30720] [--heads 1,2,4,8] [--blocks 256,512,1024]

One layer of GLM-5.2's cell (``glm-5.2-l7-e16.serve-backlog-longctx``): a
chunk's 512 queries of 64 heads (``nope`` 192 + ``rope`` 64, ``v`` 256, rank
512) at the end of ``live`` positions of a cache of 32 768 packed rows, a
random half of the causal positions selected. ``walk`` is
``mla.attend_expanded(selected=)`` as ``inference/kinds/sparse_latent.py``
calls it (plain ``jnp``: the running accumulator, the probabilities and the
expanded block pass through HBM every 512 keys); ``kernel`` is
``sparse_mla_chunk_attention`` by the heads a program takes and the keys a
turn of its walk. ms a call: the device's busy time in a profiler capture of
:data:`CALLS` calls (the union of its ops' intervals, read by the
benchmark's own reducer) over the calls; ``mxu_share``: the expanded form's
products over the live blocks at 197 TFLOP/s over that time. Needs the
chip: a CPU run proves nothing about a time.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reduce import load_trace, merge, total
from deepspeed_tpu.models import mla
from deepspeed_tpu.ops import sparse_mla_attention as sparse

CALLS = 6
S, T, H, NOPE, ROPE, VD, RANK = 32768, 512, 64, 192, 64, 256, 512
PEAK = 197e12
CFG = SimpleNamespace(kv_lora_rank=RANK, n_head=H, qk_nope_head_dim=NOPE,
                      qk_rope_head_dim=ROPE, v_dim=VD)


def device_ms(fn, *args):
    """Busy device time in ms a call of jitted ``fn`` over CALLS calls."""
    jax.block_until_ready(fn(*args))              # compiled before the capture
    d = tempfile.mkdtemp(prefix="sparse_mla_chunk_microbench_")
    with jax.profiler.trace(d):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    busy = total(merge((t0, t1) for _, t0, t1 in trace.ops[trace.devices[0]]))
    return round(busy * 1e3 / CALLS, 4)


def flops(live: int, block: int = 512) -> float:
    """The expanded form's products over the live blocks of ``block`` keys:
    the expansion, ``q . k`` and ``p . v``."""
    keys = -(-live // block) * block
    return 2.0 * keys * H * (RANK * (NOPE + VD) + T * (NOPE + ROPE) + T * VD)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="4096,9216,16384,30720")
    ap.add_argument("--heads", default="1,2,4,8")
    ap.add_argument("--blocks", default="256,512,1024")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a time comes from a chip"
    ints = lambda text: [int(x) for x in text.split(",")]   # noqa: E731
    bf = jnp.bfloat16
    rng = np.random.default_rng(0)
    lat = jnp.asarray(rng.standard_normal((1, S, RANK + ROPE)), bf)
    cache = sparse.pack_rows(lat, bf)[None]                 # one layer
    p = {"wkv_b": jnp.asarray(
        rng.standard_normal((RANK, H * (NOPE + VD))) / np.sqrt(RANK), bf)}
    qn = jnp.asarray(rng.standard_normal((1, T, H, NOPE)), bf)
    qr = jnp.asarray(rng.standard_normal((1, T, H, ROPE)), bf)
    half = jnp.asarray(rng.random((1, T, S)) < 0.5)

    def read(j, blk):
        rows = lax.dynamic_slice(cache, (0, 0, j * blk, 0, 0),
                                 (1, 1, blk) + cache.shape[3:])[0]
        return sparse.unpack_rows(rows, RANK + ROPE, bf).transpose(0, 2, 1)

    walk = jax.jit(lambda qn, qr, pos, n, mask: mla.attend_expanded(
        CFG, p, qn, qr, (read, S), pos, n, selected=mask))
    for live in ints(a.live):
        pos = (live - T + jnp.arange(T, dtype=jnp.int32))[None]
        mask = half & (jnp.arange(S, dtype=jnp.int32)[None, None]
                       <= pos[..., None])
        keep, n = mask.astype(jnp.int8), jnp.int32(live)
        want = walk(qn, qr, pos, n, mask).astype(jnp.float32)
        ms = device_ms(walk, qn, qr, pos, n, mask)
        print(json.dumps({"live": live, "path": "walk", "ms": ms,
                          "mxu_share": round(flops(live) / PEAK / ms * 1e3,
                                             4)}), flush=True)
        for block in ints(a.blocks):
            for heads in ints(a.heads):
                kernel = jax.jit(
                    lambda qn, qr, keep, n, heads=heads, block=block:
                    sparse.sparse_mla_chunk_attention(
                        qn, qr, mla._wkv_b(CFG, p, bf), cache, keep, n,
                        layer=0, rank=RANK, scale=mla.softmax_scale(CFG),
                        heads=heads, block=block))
                row = {"live": live, "path": "kernel", "heads": heads,
                       "block": block}
                try:
                    got = kernel(qn, qr, keep, n).astype(jnp.float32)
                    ms = device_ms(kernel, qn, qr, keep, n)
                    row.update(ms=ms, mxu_share=round(
                        flops(live) / PEAK / ms * 1e3, 4),
                        err_over_max=round(float(
                            jnp.abs(got - want).max()
                            / jnp.abs(want).max()), 5))
                except Exception as e:      # a tiling Mosaic refuses
                    row["refused"] = str(e).strip().splitlines()[-1][:200]
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

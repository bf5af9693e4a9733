"""Device time of the three flash-attention kernels at the train cells' shapes.

    python examples/flash_attention_microbench.py [--heads 20,25] [--blocks 512]

One call of ``flash_attention_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` on causal
(16, H, 1024, 64) bf16 operands, as the ``gpt2-774m`` (H 20) and ``gpt2-1.5b``
(H 25) train steps make it once a layer: the least of six calls' device
durations, read from a profiler capture by the benchmark's own reducer, and
that time's share of the least the chip could take
(``benchmark/kernels/flash_attention.py`` over ``benchmark/peaks.json``).
A wall clock around the call reads 0.7 ms more than the kernel takes. Needs
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

from benchmark.harness import peaks_for
from benchmark.kernels.flash_attention import ops_and_bytes
from benchmark.reduce import load_trace
from deepspeed_tpu.ops import flash_attention as fa

CALLS = 6
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def device_ms(fns_args):
    """Least device duration in ms of each kernel over CALLS calls."""
    for fn, args in fns_args:
        jax.block_until_ready(fn(*args))          # compiled before the capture
    d = tempfile.mkdtemp(prefix="flash_microbench_")
    with jax.profiler.trace(d):
        for fn, args in fns_args:
            for _ in range(CALLS):
                out = fn(*args)
            jax.block_until_ready(out)
    trace = load_trace(d)
    shutil.rmtree(d, ignore_errors=True)
    least = {}
    for name, t0, t1 in trace.ops[trace.devices[0]]:
        base = name.split(".")[0]
        if base in KERNELS:
            least[base] = min(least.get(base, float("inf")), (t1 - t0) * 1e3)
    return least


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="20,25")
    ap.add_argument("--blocks", default="512")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", "a kernel's time comes from a chip"
    peak = peaks_for(jax.devices()[0].device_kind)
    B, S, hd = 16, 1024, 64
    for H in (int(h) for h in a.heads.split(",")):
        q, k, v, do = (jax.random.normal(x, (B, H, S, hd), jnp.bfloat16)
                       for x in jax.random.split(jax.random.PRNGKey(H), 4))
        for block in (int(b) for b in a.blocks.split(",")):
            kw = dict(block=block, causal=True, interpret=False)
            fwd = jax.jit(lambda q, k, v: fa._fwd_call(q, k, v, None, None,
                                                       **kw))
            bwd = jax.jit(lambda q, k, v, do, lse, delta: fa._bwd_call(
                q, k, v, lse, delta, do, None, None, **kw)[:3])
            o, lse = fwd(q, k, v)
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1)
            ms = device_ms([(fwd, (q, k, v)),
                            (bwd, (q, k, v, do, lse[:, :, 0], delta))])
            for kernel in KERNELS:
                flops, nbytes = ops_and_bytes(kernel, batch=B, heads=H, seq=S,
                                              head_dim=hd)
                least = max(flops / peak["bf16_flops_per_s"],
                            nbytes / peak["hbm_bytes_per_s"]) * 1e3
                print(json.dumps({
                    "heads": H, "block": block, "kernel": kernel,
                    "ms": round(ms[kernel], 4),
                    "pct_of_least": round(100 * least / ms[kernel], 2)}),
                    flush=True)


if __name__ == "__main__":
    main()

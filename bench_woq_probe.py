"""Operator probe: does the fused WOQ GEMM save decode HBM traffic?

Decode is weight-re-read bound. Round 5 measured the XLA-only WOQ path
(dequantize in the scan body, hope the convert fuses into the operand
load): XLA hoisted the loop-invariant dequant, decode re-read a bf16 copy,
and int8 was *slower* than bf16 — verdict "hoisted/not-fused". The fused
Pallas kernel (``ops/woq_matmul.py``) makes the question moot by
construction: the custom call consumes int8 tiles directly, so there is
nothing for XLA to hoist. This probe measures a weight-stationary scan
y_{t+1} = tanh(y_t @ W) four ways — bf16 dense, legacy XLA in-loop
dequant, fused int8, fused int4 — and emits a per-step HBM-bytes model
next to the times so the bandwidth win is attributable: the byte ratio is
the roofline speedup ceiling, the time ratio is what we achieved.

``--smoke`` runs the CPU/interpret tier-1 gate instead: kernel-vs-
reference parity (int8/int4, both consumption modes) plus the bytes-model
thresholds (>= 1.9x int8, >= 3.5x int4 weight-read reduction). It prints
one JSON line ending in "smoke-pass" and exits nonzero on any failure, so
kernel/consumer drift fails on CPU before any chip time is spent.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def timed(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _quantize(w, gs, bits):
    from deepspeed_tpu.inference.quantization import quantize

    return quantize(w, group_size=gs, bits=bits)


def step_weight_bytes(shape, gs, kind):
    """HBM bytes one scan step re-reads for the (K, N) weight operand."""
    K, N = shape
    if kind == "bf16":
        return K * N * 2
    scale = (K // gs) * N * 4
    return (K * N if kind == "int8" else K * N // 2) + scale


# ------------------------------------------------------------------ smoke
def smoke():
    """CPU interpret-mode gate: parity + bytes model. Tier-1-wired."""
    from deepspeed_tpu.inference.quantization import dequantize
    from deepspeed_tpu.ops.woq_matmul import woq_matmul, woq_matmul_t

    rng = np.random.default_rng(0)
    max_err = 0.0
    for bits in (8, 4):
        for K, N, gs in ((256, 384, 128), (256, 384, 64), (192, 256, 192)):
            w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
            qt = _quantize(w, gs, bits)
            x = jnp.asarray(rng.standard_normal((8, K)), jnp.float32)
            want = x @ dequantize(qt, jnp.float32)
            got = woq_matmul(x, qt.q, qt.scale, group_size=qt.group_size,
                             bits=qt.bits, interpret=True)
            max_err = max(max_err, float(jnp.max(jnp.abs(got - want))))
        # transposed (tied-head) mode, incl. an odd degraded vocab
        for V, d, gs in ((512, 128, 128), (250, 128, 128)):
            w = jnp.asarray(rng.standard_normal((V, d)), jnp.float32)
            qt = _quantize(w, gs, bits)
            x = jnp.asarray(rng.standard_normal((4, d)), jnp.float32)
            want = x @ dequantize(qt, jnp.float32).T
            got = woq_matmul_t(x, qt.q, qt.scale, group_size=qt.group_size,
                               bits=qt.bits, interpret=True)
            max_err = max(max_err, float(jnp.max(jnp.abs(got - want))))
    assert max_err < 1e-4, f"kernel parity drifted: {max_err}"

    shape, gs = (4096, 8192), 128
    b16 = step_weight_bytes(shape, gs, "bf16")
    r8 = b16 / step_weight_bytes(shape, gs, "int8")
    r4 = b16 / step_weight_bytes(shape, gs, "int4")
    assert r8 >= 1.9, f"int8 weight-read reduction {r8:.2f} < 1.9"
    assert r4 >= 3.5, f"int4 weight-read reduction {r4:.2f} < 3.5"
    print(json.dumps({
        "smoke": True, "parity_max_err": round(max_err, 8),
        "int8_read_reduction": round(r8, 3),
        "int4_read_reduction": round(r4, 3),
        "verdict": "smoke-pass",
    }))


# -------------------------------------------------------------------- TPU
def main():
    import bench_common as bc

    bc.require_tpu("woq-probe")
    from deepspeed_tpu.ops.woq_matmul import woq_matmul

    d, steps, gs = 4096, 64, 128
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (d, 2 * d), jnp.float32) / (d ** 0.5)
    w_bf16 = w.astype(jnp.bfloat16)
    qt8 = _quantize(w, gs, 8)
    qt4 = _quantize(w, gs, 4)
    x = jax.random.normal(key, (8, d), jnp.bfloat16)

    @jax.jit
    def run_bf16(x, w):
        def body(y, _):
            y = jnp.tanh(y @ w)[:, :d].astype(jnp.bfloat16)
            return y, ()
        y, _ = lax.scan(body, x, None, length=steps)
        return y

    @jax.jit
    def run_xla_dequant(x, wq, s):
        # the round-5 loser, kept as the control: XLA hoists this convert
        def body(y, _):
            wd = (wq.astype(jnp.float32)
                  * jnp.repeat(s, gs, axis=0)).astype(jnp.bfloat16)
            y = jnp.tanh(y @ wd)[:, :d].astype(jnp.bfloat16)
            return y, ()
        y, _ = lax.scan(body, x, None, length=steps)
        return y

    def run_fused(qt):
        @jax.jit
        def f(x, wq, s):
            def body(y, _):
                z = woq_matmul(y, wq, s, group_size=qt.group_size,
                               bits=qt.bits)
                y = jnp.tanh(z)[:, :d].astype(jnp.bfloat16)
                return y, ()
            y, _ = lax.scan(body, x, None, length=steps)
            return y
        return f

    res = {
        "bf16_ms": round(timed(run_bf16, x, w_bf16) * 1e3, 2),
        "xla_dequant_ms": round(timed(run_xla_dequant, x, qt8.q,
                                      qt8.scale) * 1e3, 2),
        "fused_int8_ms": round(timed(run_fused(qt8), x, qt8.q,
                                     qt8.scale) * 1e3, 2),
        "fused_int4_ms": round(timed(run_fused(qt4), x, qt4.q,
                                     qt4.scale) * 1e3, 2),
        "steps": steps, "gs": gs,
    }
    shape = (d, 2 * d)
    bf, b8, b4 = (step_weight_bytes(shape, gs, k)
                  for k in ("bf16", "int8", "int4"))
    res["bytes_model"] = {
        "bf16_step_mib": round(bf / 2**20, 2),
        "int8_step_mib": round(b8 / 2**20, 2),
        "int4_step_mib": round(b4 / 2**20, 2),
        "int8_read_reduction": round(bf / b8, 3),
        "int4_read_reduction": round(bf / b4, 3),
    }
    # achieved HBM GB/s per variant: step weight bytes / step time — the
    # attribution row: fused variants should track their byte reduction
    for tag, ms, byt in (("bf16", res["bf16_ms"], bf),
                         ("fused_int8", res["fused_int8_ms"], b8),
                         ("fused_int4", res["fused_int4_ms"], b4)):
        res[f"{tag}_gbps"] = round(byt * steps / ms / 1e6, 1)
    res["verdict"] = ("fused: in-VMEM int8 dequant wins decode bandwidth"
                      if res["fused_int8_ms"] < 0.75 * res["bf16_ms"]
                      else "hoisted/not-fused: no decode bandwidth win")
    res["platform"] = "tpu"
    import os

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "WOQ_PROBE.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()

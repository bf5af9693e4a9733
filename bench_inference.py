"""Inference decode benchmark: steady-state generation throughput + MBU.

Autoregressive decode is HBM-bandwidth-bound (every generated token
re-reads the weights), so the honest utilization metric is MBU —
tokens/s x bytes-read-per-token / peak HBM bandwidth — the decode analog
of MFU. The reference publishes no machine-readable inference numbers
(SURVEY §6), so ``vs_baseline`` here is the fraction of the chip's own
HBM roofline (1.0 = saturating memory bandwidth, the physical ceiling).

Measures bf16, int8-WOQ, and int4-WOQ serving (reference
``init_inference`` + quantization story) on GPT-2-350M. Quantized decode
streams int8/int4 weights through the fused Pallas GEMM
(``ops/woq_matmul.py``), so each row carries its OWN per-step HBM-bytes
model (``weight_bytes_per_step``, achieved GB/s, byte-ratio vs bf16) —
the attribution that separates a bandwidth win from a compute win.
Steady-state decode is isolated by timing generate() at two output
lengths and using the delta (subtracts prefill + dispatch).

Writes ``INFERENCE_BENCH.json``. Runs in this process and exits non-zero
without a TPU.
"""

import json
import os
import time

import bench_common as bc

_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_ROOT, "INFERENCE_BENCH.json")


def _measure(engine, prompt, short, long_, bytes_per_token, peak_bw):
    import jax

    def gen(n):
        jax.block_until_ready(
            engine.generate(prompt, max_new_tokens=n, greedy=True))

    gen(short)                          # compile both shapes, then time
    gen(long_)
    t0 = time.perf_counter()
    gen(short)
    t1 = time.perf_counter()
    gen(long_)
    t2 = time.perf_counter()
    dt = (t2 - t1) - (t1 - t0)          # steady-state decode window
    toks = prompt.shape[0] * (long_ - short)
    tokens_per_sec = toks / dt
    mbu = tokens_per_sec / prompt.shape[0] * bytes_per_token / peak_bw
    return tokens_per_sec, mbu


def _row(engine, prompt, short, long_, peak_bw):
    """Measure one serving config and attach its HBM-bytes model: the
    per-step weight read (quantized leaves count their int8/int4 bytes +
    scales — decode now streams those, never a dequantized copy), the
    achieved GB/s that implies, and the byte-model MBU against the chip
    roofline. KV-cache traffic at these lengths is <4% of the weight read
    and is left uncounted (under-reporting MBU slightly — conservative)."""
    from deepspeed_tpu.inference.quantization import decode_weight_bytes

    bpt = decode_weight_bytes(engine.params)
    tps, mbu = _measure(engine, prompt, short, long_, bpt, peak_bw)
    return {"tokens_per_sec": round(tps), "mbu": round(mbu, 4),
            "weight_bytes_per_step": int(bpt),
            "achieved_gbps": round(tps / prompt.shape[0] * bpt / 1e9, 1)}


def _run_workload(devices):
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2
    from deepspeed_tpu.utils.timer import peak_hbm_bw_for

    size, B, prompt_len, short, long_ = "350m", 8, 128, 16, 144

    cfg = gpt2(size, max_seq=prompt_len + long_)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (B, prompt_len)).astype(np.int32)
    peak_bw = peak_hbm_bw_for(devices[0])

    rows = {}
    for tag, icfg in (("bf16", {"dtype": "bfloat16"}),
                      # decode keeps weights int8/int4 END-TO-END: the
                      # fused Pallas GEMM streams quantized tiles and
                      # dequantizes in VMEM, so these rows' bytes model
                      # counts quantized bytes — the tok/s delta vs bf16
                      # against the byte ratio (~1.94x / ~3.76x) is the
                      # bandwidth-win attribution.
                      ("int8", {"dtype": "bfloat16", "quantize": True,
                                "quant_bits": 8}),
                      ("int4", {"dtype": "bfloat16", "quantize": True,
                                "quant_bits": 4})):
        engine = ds.init_inference(model, params, dict(icfg))
        rows[tag] = _row(engine, prompt, short, long_, peak_bw)
        del engine
        jax.clear_caches()
    rows["int8"]["weight_read_reduction_vs_bf16"] = round(
        rows["bf16"]["weight_bytes_per_step"]
        / rows["int8"]["weight_bytes_per_step"], 3)
    rows["int4"]["weight_read_reduction_vs_bf16"] = round(
        rows["bf16"]["weight_bytes_per_step"]
        / rows["int4"]["weight_bytes_per_step"], 3)

    # MoE decode (reference DeepSpeedMoEInference): single-group expert
    # dispatch inside the KV-cache scan (models/moe.py _mlp_block_infer).
    # bytes/token counts ALL params — the dispatch einsum streams every
    # expert bank each step even though only top-k do useful work, so the
    # full bank read is the honest roofline denominator.
    from deepspeed_tpu.models import mixtral

    moe_kw = dict(n_layer=8, n_head=8, n_kv_head=4, d_model=512, d_ff=2048,
                  num_experts=8)
    moe_cfg = mixtral("tiny", max_seq=prompt_len + long_,
                      moe_drop_tokens=False, **moe_kw)
    moe_model = build_model(moe_cfg)
    moe_params = jax.jit(moe_model.init)(jax.random.PRNGKey(1))
    moe_prompt = rng.integers(0, moe_cfg.vocab_size,
                              (B, prompt_len)).astype(np.int32)
    engine = ds.init_inference(moe_model, moe_params, {"dtype": "bfloat16"})
    rows["moe"] = _row(engine, moe_prompt, short, long_, peak_bw)
    rows["moe"].update(experts=moe_cfg.num_experts, top_k=moe_cfg.moe_top_k)
    del engine
    jax.clear_caches()

    result = {
        "metric": f"gpt2_{size}_decode_mbu_int8",
        "value": rows["int8"]["mbu"],
        "unit": (f"MBU (int8 WOQ {rows['int8']['tokens_per_sec']} tok/s "
                 f"@ {rows['int8']['weight_read_reduction_vs_bf16']}x fewer "
                 f"weight bytes, bf16 {rows['bf16']['tokens_per_sec']} tok/s"
                 f" mbu={rows['bf16']['mbu']}, int4 "
                 f"{rows['int4']['tokens_per_sec']} tok/s, "
                 f"moe {rows['moe']['tokens_per_sec']} tok/s "
                 f"mbu={rows['moe']['mbu']}, batch={B}, "
                 f"platform={devices[0].platform}, "
                 f"device_kind={devices[0].device_kind})"),
        "vs_baseline": rows["int8"]["mbu"],   # fraction of HBM roofline
        "rows": rows,
    }
    return result


def main():
    result = _run_workload(bc.require_tpu("infer-bench"))
    with open(_OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

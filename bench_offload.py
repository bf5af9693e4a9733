"""Offload benchmark: ZeRO-Offload training throughput + host-step overlap.

Writes ``OFFLOAD_BENCH.json`` and prints it: tokens/s, params-per-chip
ratio (model params vs HBM-resident bytes), and the bwd-vs-host-step time
split — the round-2 verdict's "host-step time < backward time" target for
the pipelined host update (reference ``stage_1_and_2.py:1096`` overlap).

Runs in this process and exits non-zero without a TPU. Model size via
DSTPU_OFFLOAD_BENCH_SIZE (default 125m; 1.5b/7b are the sizes offload is
for).
"""

import json
import math
import os
import time

import bench_common as bc

_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "OFFLOAD_BENCH.json")


def _run_workload(devices):
    import numpy as np

    def jnp_dtype_size(dt):
        return np.dtype(dt).itemsize

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset

    size = os.environ.get("DSTPU_OFFLOAD_BENCH_SIZE", "125m")
    seq, micro, n_steps = 512, 8, 5

    cfg = {
        "train_batch_size": micro * len(devices),
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "gradient_clipping": 1.0,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1,
                              "offload_optimizer": {"device": "cpu"}},
        "remat": {"enabled": True, "policy": "dots_saveable"},
    }
    model_cfg = gpt2(size, max_seq=seq)
    engine = ds.initialize(cfg, build_model(model_cfg))
    data = random_token_dataset(engine.train_batch_size, seq_len=seq,
                                vocab_size=model_cfg.vocab_size)
    batch = DataLoader(data, local_batch_size=engine.train_batch_size,
                       shuffle=False).collate_fn(data)

    m = engine.train_batch(batch)          # warmup/compile
    assert math.isfinite(m["loss"]), m
    bwd, host, t0 = [], [], time.perf_counter()
    for _ in range(n_steps):
        m = engine.train_batch(batch)
        bwd.append(m["bwd_s"])
        host.append(m["host_step_s"])
    assert math.isfinite(m["loss"]), m
    dt = (time.perf_counter() - t0) / n_steps

    n_params = engine.param_count
    tokens_per_sec = engine.train_batch_size * seq / dt
    result = {
        "metric": "gpt2_offload_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": (f"tokens/s ({size}, {n_params / 1e6:.0f}M params, "
                 f"platform={devices[0].platform}, "
                 f"device_kind={devices[0].device_kind})"),
        "params": n_params,
        "step_s": round(dt, 4),
        "bwd_s": round(float(np.mean(bwd)), 4),
        "host_step_s": round(float(np.mean(host)), 4),
        "host_lt_bwd": bool(np.mean(host) < np.mean(bwd)),
        "hbm_resident_bytes": int(
            n_params * jnp_dtype_size(engine.compute_dtype)),  # compute copy
        "host_state_bytes": int(n_params * 4 * 3),  # fp32 master + 2 moments
    }
    return result


def main():
    result = _run_workload(bc.require_tpu("offload-bench"))
    with open(_OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

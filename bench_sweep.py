"""One-off MFU sweep on the chip: find the best training config.

Grid of (size, micro, seq, remat, flash) 5-tuples, run one after another in
this process, emitting a JSON line per config to stderr and appending to
SWEEP_RESULTS.jsonl as it goes. A config that fails (an OOM) is recorded as
an error row and the grid goes on. Exits non-zero without a TPU.

Not part of the test suite — an operator tool for tuning bench.py's
workload.
"""

import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RESULTS = os.path.join(ROOT, "SWEEP_RESULTS.jsonl")


def log(msg):
    print(f"[sweep] {msg}", file=sys.stderr, flush=True)


def measure(size, micro, seq, remat, flash=False, n_steps=10):
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2
    from deepspeed_tpu.ops.flash_attention import make_flash_attention
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset
    from deepspeed_tpu.utils.timer import peak_flops_for

    devices = jax.devices()
    n_dev = len(devices)
    cfg = {
        "train_batch_size": micro * n_dev,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1},
    }
    if remat:
        cfg["remat"] = {"enabled": True, "policy": remat}
    model_cfg = gpt2(size, max_seq=seq)
    model = build_model(model_cfg,
                        attention_fn=make_flash_attention() if flash else None)
    engine = ds.initialize(cfg, model)

    data = random_token_dataset(engine.train_batch_size * 2, seq_len=seq,
                                vocab_size=model_cfg.vocab_size)
    batch = DataLoader(data, local_batch_size=engine.train_batch_size,
                       shuffle=False).collate_fn(data[:engine.train_batch_size])

    jax.block_until_ready(engine.train_batch(batch)["loss"])   # compile
    t0 = time.perf_counter()
    for _ in range(n_steps):
        m = engine.train_batch(batch)
    final_loss = float(jax.block_until_ready(m["loss"]))
    dt = (time.perf_counter() - t0) / n_steps
    if not math.isfinite(final_loss):
        raise RuntimeError("diverged")

    tokens_per_sec = engine.train_batch_size * seq / dt
    mfu = tokens_per_sec * model_cfg.flops_per_token() / (
        peak_flops_for(devices[0]) * n_dev)
    return {"size": size, "micro": micro, "seq": seq, "remat": remat or "off",
            "flash": flash, "mfu": round(mfu, 4),
            "tokens_per_sec": round(tokens_per_sec),
            "step_ms": round(dt * 1000, 1)}


# No-remat graphs at these sizes do not fit 16 GiB (compile-time OOM on
# every size tried, 2026-08-01 runs), so the grid stays on dots_saveable
# and explores batch/size/seq/flash instead.
GRID = [
    ("350m", 32, 512, "dots_saveable", False),
    ("350m", 16, 512, "dots_saveable", True),
    ("350m", 16, 1024, "dots_saveable", True),
    ("774m", 16, 512, "dots_saveable", False),
    ("774m", 8, 1024, "dots_saveable", True),
    ("1.5b", 4, 512, "dots_saveable", False),
    ("1.5b", 8, 512, "dots_saveable", True),
]


def main():
    import jax

    import bench_common as bc

    bc.require_tpu("sweep")
    results = []
    for size, micro, seq, remat, flash in GRID:
        log(f"config {size} mbs{micro} seq{seq} remat={remat or 'off'} "
            f"flash={flash}")
        try:
            r = measure(size, micro, seq, remat, flash)
        except Exception as e:
            r = {"size": size, "micro": micro, "seq": seq,
                 "remat": remat or "off", "flash": flash,
                 "error": f"{type(e).__name__}: {str(e)[:200]}"}
        log(json.dumps(r))
        results.append(r)
        with open(RESULTS, "a") as f:
            f.write(json.dumps(r) + "\n")
        gc.collect()
        jax.clear_caches()
    ok = [r for r in results if "mfu" in r]
    best = max(ok, key=lambda r: r["mfu"]) if ok else None
    print(json.dumps({"grid_done": len(results), "best": best}), flush=True)
    if best is None:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

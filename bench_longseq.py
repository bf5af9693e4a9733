"""Long-sequence benchmark: GPT-2 training MFU at 4k–8k tokens with the
Pallas flash-attention kernel (BASELINE config 4's single-chip leg).

Anchor: the reference's long-context headline is DeepSpeed-Ulysses at
175 TFLOPS/GPU sustained = 54% of an A100's younger peak
(``blogs/deepspeed-ulysses/README.md:78-83``). vs_baseline = achieved
MFU / 0.54 — ≥1.0 means this framework sustains a higher fraction of its
chip at long sequence than the reference's flagship long-context number.
(The multi-chip Ulysses/ring sequence-parallel path is exercised by
test_sequence.py; this bench measures the per-chip kernel side.)

The headline is the longest sequence (``DSTPU_LONGSEQ`` or 32768); two
shorter lengths attach as ``rows`` so the artifact shows the
MFU-vs-sequence curve. Everything runs in this process, one length after
another; a length that fails raises, and without a TPU the script exits
non-zero.

Writes ``LONGSEQ_BENCH.json``.
"""

import json
import math
import os
import time

import bench_common as bc

_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "LONGSEQ_BENCH.json")
# (seq, flash block), longest first
_LENGTHS = ((32768, 512), (16384, 512), (4096, 512))


def _measure(seq, blk, devices):
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2
    from deepspeed_tpu.ops.flash_attention import make_flash_attention
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset
    from deepspeed_tpu.utils.timer import peak_flops_for

    # 16-32k rows (the Ulysses-story lengths): one sample per step — the
    # attention term dominates tokens/step anyway
    micro, n_steps, size = (1 if seq >= 16384 else 2), 5, "125m"
    attn = make_flash_attention(block=blk)

    cfg = {
        "train_batch_size": micro * len(devices),
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1},
        "remat": {"enabled": True, "policy": "dots_saveable"},
        "steps_per_print": 10 ** 9,
    }
    model_cfg = gpt2(size, max_seq=seq)
    engine = ds.initialize(cfg, build_model(model_cfg, attention_fn=attn))
    data = random_token_dataset(engine.train_batch_size, seq_len=seq,
                                vocab_size=model_cfg.vocab_size)
    batch = DataLoader(data, local_batch_size=engine.train_batch_size,
                       shuffle=False).collate_fn(data)

    assert math.isfinite(float(engine.train_batch(batch)["loss"]))  # compile
    t0 = time.perf_counter()
    for _ in range(n_steps):
        m = engine.train_batch(batch)
    final = float(jax.block_until_ready(m["loss"]))
    dt = (time.perf_counter() - t0) / n_steps
    assert math.isfinite(final)

    tokens_per_sec = engine.train_batch_size * seq / dt
    flops_per_token = model_cfg.flops_per_token()   # fwd+bwd incl. attention
    mfu = tokens_per_sec * flops_per_token / (
        peak_flops_for(devices[0]) * len(devices))
    result = {
        "metric": f"gpt2_flash_seq{seq}_mfu",
        "value": round(mfu, 4),
        "unit": (f"MFU (tokens/s={tokens_per_sec:.0f}, seq={seq}, "
                 f"step={dt * 1000:.1f}ms, platform={devices[0].platform}, "
                 f"device_kind={devices[0].device_kind})"),
        "vs_baseline": round(mfu / 0.54, 4),   # Ulysses 54%-of-peak anchor
    }
    print(json.dumps(result), flush=True)
    return result


def main():
    import gc

    import jax

    devices = bc.require_tpu("longseq-bench")
    env_seq = os.environ.get("DSTPU_LONGSEQ")
    lengths = ((int(env_seq), 512),) if env_seq else _LENGTHS
    result = _measure(*lengths[0], devices)
    rows = {}
    for seq, blk in lengths[1:]:
        gc.collect()
        jax.clear_caches()
        rows[f"seq{seq}"] = _measure(seq, blk, devices)
    if rows:
        result["rows"] = rows
    with open(_OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

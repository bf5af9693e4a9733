"""Benchmark: flagship training throughput (MFU) on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline anchor (BASELINE.md): the reference reports 64 TFLOPS for its
fused-kernel BERT-large on 1x V100 (seq128), i.e. 51.2% kernel utilization
(64/125 fp16 peak).  vs_baseline = achieved MFU / 0.512 — >1.0 means better
hardware utilization than the reference's flagship kernel numbers.

The workload is therefore BERT-large seq128 MLM (LAMB, ZeRO-1) — the SAME
model/seq/objective as the anchor row, apples-to-apples per-chip utilization
(reference docs/_tutorials/bert-pretraining.md:392).

The workload runs in this process; without a TPU the script exits non-zero,
and a failing workload raises — there is no smaller candidate to fall back
to and no cached number to replay.
"""

import json
import math
import time

import bench_common as bc

# (family, size, micro, seq, remat, fused_xent): the baseline anchor's own
# workload. fused None = auto → Pallas fused loss on TPU. No-remat MEASURED
# (2026-08-01 runs) and rejected: mbs64 no-remat compiles to 19.32 GiB
# (OOM), and the largest fitting no-remat shape (mbs32) measured 0.4392 MFU
# vs 0.5495 for remat-on mbs64 — at seq128 the bigger micro-batch feeds the
# MXU better than skipping the backward recompute.
_WORKLOAD = ("bert", "large", 64, 128, True, None)
_N_STEPS = 10


def _measure(family, size, micro, seq, n_steps, devices,
             remat: bool = True, fused=None):
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import bert, build_model, gpt2
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset
    from deepspeed_tpu.utils.timer import peak_flops_for

    n_dev = len(devices)
    is_bert = family == "bert"
    cfg = {
        "train_batch_size": micro * n_dev,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        # LAMB for the BERT row (what the reference's BERT pretraining
        # recipe uses); AdamW for the decoder fallbacks.
        "optimizer": ({"type": "lamb", "params": {"lr": 1e-4}} if is_bert else
                      {"type": "adamw", "params": {"lr": 3e-4,
                                                   "weight_decay": 0.01}}),
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1},
        "remat": {"enabled": remat, "policy": "dots_saveable"},
    }
    model_cfg = (bert if is_bert else gpt2)(size, max_seq=seq,
                                            fused_xent=fused)
    model = build_model(model_cfg)
    engine = ds.initialize(cfg, model)

    if is_bert:
        batch = bc.mlm_batch(np.random.default_rng(0),
                             engine.train_batch_size, seq,
                             model_cfg.vocab_size)
    else:
        data = random_token_dataset(engine.train_batch_size * 2, seq_len=seq,
                                    vocab_size=model_cfg.vocab_size)
        batch = DataLoader(data, local_batch_size=engine.train_batch_size,
                           shuffle=False).collate_fn(
                               data[:engine.train_batch_size])

    def _sync(metrics) -> float:
        # the last step's loss transitively forces the whole donated-state
        # chain
        return float(jax.block_until_ready(metrics["loss"]))

    # warmup/compile
    _sync(engine.train_batch(dict(batch)))

    t0 = time.perf_counter()
    for _ in range(n_steps):
        m = engine.train_batch(dict(batch))
    final_loss = _sync(m)
    dt = (time.perf_counter() - t0) / n_steps
    if not math.isfinite(final_loss):
        raise RuntimeError(f"non-finite loss {final_loss}: diverged run, "
                           "refusing to report an MFU artifact")

    tokens_per_sec = engine.train_batch_size * seq / dt
    # flops_per_token() is already fwd+bwd (6N + 12*L*d*S + 6*d*V logit
    # projection — Megatron model-FLOPs convention): the previous extra x3
    # triple-counted and inflated MFU 3x — including round 2's "78.7% MFU"
    # measurement, which was really ~26%. Honest accounting.
    flops_per_token = model_cfg.flops_per_token()
    achieved = tokens_per_sec * flops_per_token
    peak = peak_flops_for(devices[0]) * n_dev
    mfu = achieved / peak
    # Reference anchor: 64 TFLOPS / 125 TFLOPS fp16 peak V100 = 51.2% kernel MFU
    vs_baseline = mfu / 0.512

    xent = bc.xent_label(fused)
    unit = (f"MFU (tokens/s={tokens_per_sec:.0f}, step={dt * 1000:.1f}ms, "
            f"seq={seq}, remat={'on' if remat else 'off'}, xent={xent}, "
            f"devices={n_dev}, platform={devices[0].platform}, "
            f"device_kind={devices[0].device_kind})")

    metric = (f"bert_{size}_seq{seq}_mlm_mfu" if family == "bert"
              else f"gpt2_{size}_zero1_mfu")
    if not remat:
        # config-distinct metric name: a no-remat number must never
        # masquerade as the remat=on row in round-over-round comparisons
        metric += "_noremat"
    result = {
        "metric": metric,
        "value": round(mfu, 4),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 4),
    }
    print(json.dumps(result), flush=True)


def main() -> None:
    devices = bc.require_tpu()
    family, size, micro, seq, remat, fused = _WORKLOAD
    _measure(family, size, micro, seq, _N_STEPS, devices, remat=remat,
             fused=fused)


if __name__ == "__main__":
    main()

"""Activation-offload memory probe: is offload_dots a real memory lever?

Round-3 verdict item #4: the offload_dots remat knob must be proven with a
measured headroom delta, not a policy name. This probe AOT-compiles the
SAME decoder train step under three remat policies —

  - ``dots_saveable``   (save matmul outputs in HBM; the default)
  - ``save_nothing``    (full remat)
  - ``offload_dots``    (full remat + layer_in/attn_out offloaded to
                         pinned host, models/transformer.py _layer tags)

— and reads the compiler's own buffer assignment (``memory_analysis()``):
device temp bytes, host temp bytes, and the derived max micro-batch that
fits the chip's HBM (activation temp scales ~linearly in micro-batch; the
headroom ratio is temp_baseline/temp_offload). Compile-only by default:
the proof is the buffer assignment (set DSTPU_ACT_OFFLOAD_EXEC=1 to also
run one real step under the offload policy). Runs in this process and exits
non-zero without a TPU: XLA:CPU strips the host memory spaces, so the
deltas mean nothing elsewhere.

Reference anchor: cpu_checkpointing + contiguous_memory_optimization
(``runtime/activation_checkpointing/checkpointing.py:1036``) exist for
exactly this trade. Writes ``ACT_OFFLOAD_BENCH.json``.
"""

import json
import os

import bench_common as bc

_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_ROOT, "ACT_OFFLOAD_BENCH.json")


def _run_workload(devices):
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset

    # seq512: the dots_saveable BASELINE must itself fit (at seq1024 it
    # saves ~6 GiB of (B,H,S,S) probs and compiles to 16.1 GiB; the probe's
    # value is the POLICY DELTA, which any fitting shape measures)
    size, kw, micro, seq = "350m", {}, 8, 512

    rows = {}
    for policy in ("dots_saveable", "save_nothing", "offload_dots"):
        model_cfg = gpt2(size, max_seq=seq, **kw)
        engine = ds.initialize({
            "train_batch_size": micro * len(devices),
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "remat": {"enabled": True, "policy": policy},
        }, build_model(model_cfg))
        data = random_token_dataset(engine.train_batch_size, seq_len=seq,
                                    vocab_size=model_cfg.vocab_size)
        batch = DataLoader(data, local_batch_size=engine.train_batch_size,
                           shuffle=False).collate_fn(
                                data[:engine.train_batch_size])
        ma = engine.compile_train_step(batch)   # AOT compile, no execution
        rows[policy] = {
            "temp_mb": round(ma["temp_size_in_bytes"] / 2**20, 1),
            "host_temp_mb": round(ma.get("host_temp_size_in_bytes", 0) / 2**20, 1),
            "peak_mb": round(ma.get("peak_memory_in_bytes", 0) / 2**20, 1),
        }
        if policy == "offload_dots" and os.environ.get(
                "DSTPU_ACT_OFFLOAD_EXEC") == "1":
            loss = float(jax.block_until_ready(
                engine.train_batch(dict(batch))["loss"]))
            rows[policy]["step_loss"] = round(loss, 4)
        del engine
        jax.clear_caches()

    base = rows["dots_saveable"]["temp_mb"]
    offl = rows["offload_dots"]["temp_mb"]
    headroom = round(base / max(offl, 1e-6), 3)
    result = {
        "metric": f"act_offload_headroom_gpt2_{size}_seq{seq}",
        "value": headroom,
        "unit": (f"x device-temp reduction vs dots_saveable (compile-time "
                 f"buffer assignment; dots={base}MB full_remat="
                 f"{rows['save_nothing']['temp_mb']}MB offload={offl}MB "
                 f"host={rows['offload_dots']['host_temp_mb']}MB, "
                 f"micro={micro}, platform={devices[0].platform}, "
                 f"device_kind={devices[0].device_kind})"),
        "vs_baseline": headroom,
        "rows": rows,
    }
    return result


def main():
    result = _run_workload(bc.require_tpu("actoff-bench"))
    with open(_OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

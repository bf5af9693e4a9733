"""Train a 1.00 B-param decoder on ONE 16 GiB TPU chip (see docs/TUNING.md
"Remat"; the benchmark's train cells, PERF.md §4, measure a step like it).

The three knobs that make 1 B fit and run fast on a single v5e:

1. ``remat save_names`` — saves only the tagged layer-boundary residuals
   (~4x less HBM than dots_saveable; the difference between fitting and
   an 18.3 GiB compile).
2. Lion — one fp32 moment (14 bytes/param total vs AdamW's 18; 1.004 B
   params x 14 = 14.1 GiB, leaving room for activations).
3. flash attention at the block-512 default — bf16 operands on the MXU
   and wide tiles (measured: 305.5 ms/step vs 410.5 for XLA attention).

Run (single chip):  python examples/billion_param_single_chip.py
Smallest smoke:     DSTPU_EXAMPLE_SMOKE=1 python examples/billion_param_single_chip.py
"""

import os

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, gpt2, tiny_test
from deepspeed_tpu.ops.flash_attention import make_flash_attention
from deepspeed_tpu.runtime.dataloader import (DataLoader, RepeatingLoader,
                                              random_token_dataset)

SMOKE = os.environ.get("DSTPU_EXAMPLE_SMOKE") == "1"

config = {
    # mbs 4 at seq 1024: the largest micro-batch the save_names policy
    # fits beside 14.1 GiB of param state on a 16 GiB chip
    "train_batch_size": 8 if SMOKE else 4,
    "train_micro_batch_size_per_gpu": "auto" if SMOKE else 4,
    "optimizer": {"type": "lion", "params": {"lr": 1e-4}},
    "gradient_clipping": 1.0,
    "zero_optimization": {"stage": 1},
    "remat": {"enabled": True, "policy": "save_names"},
    "steps_per_print": 5,
}

# GPT-2-XL width at 30 layers = 1.004 B params
model_cfg = (tiny_test(max_seq=64) if SMOKE else
             gpt2("1.5b", n_layer=30, max_seq=1024))
model = build_model(model_cfg, attention_fn=make_flash_attention())
engine = ds.initialize(config, model)

data = random_token_dataset(2 * engine.train_batch_size,
                            seq_len=model_cfg.max_seq,
                            vocab_size=model_cfg.vocab_size, learnable=True)
loader = DataLoader(data, local_batch_size=engine.train_batch_size)

steps = 4 if SMOKE else 1000
it = iter(RepeatingLoader(loader))
for step in range(steps):
    metrics = engine.train_batch(next(it))
print(f"final loss {float(metrics['loss']):.4f} over {steps} steps")

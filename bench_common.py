"""What the bench entry points share: logging, the TPU requirement, the
compile cache, the MLM batch and the loss-path label.

Every bench runs its workload in the calling process — one process per chip,
nothing re-executes itself. A measurement path that finds no TPU exits
non-zero: a CPU run is a correctness check (the ``--smoke`` modes, which
print no rates), never a number under a device metric's name.
"""

from __future__ import annotations

import sys

# Version of the model-FLOPs formula behind every MFU number.
# v2: + 6*d*V logit-projection term (Megatron model-FLOPs convention) and
# the T5 enc/dec split. Numbers taken under different versions are not
# comparable.
FLOPS_FORMULA_VERSION = 2


def log(msg: str, tag: str = "bench") -> None:
    print(f"[{tag}] {msg}", file=sys.stderr, flush=True)


def require_tpu(tag: str = "bench"):
    """Place the compile cache, then return ``jax.devices()`` — or exit 2
    when the first device is not a TPU. Call once, before the first
    compile, from a measurement entry point (never from a ``--smoke``)."""
    from deepspeed_tpu.platform import configure_compile_cache

    configure_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU (jax.devices()[0].platform == "
            f"{devices[0].platform!r}): this bench measures the chip and "
            "does not fall back to another backend", tag)
        raise SystemExit(2)
    return devices


def mlm_batch(rng, batch_size: int, seq: int, vocab: int,
              mask_frac: float = 0.15, mask_id: int = 103):
    """BERT-style MLM batch: random labels, mask_frac positions replaced by
    [MASK] (id 103, BERT's real mask token). Shared by bench.py and
    bench_bert.py so the two entry points measure the same workload."""
    import numpy as np

    labels = rng.integers(0, vocab, (batch_size, seq), dtype=np.int32)
    mask = rng.random((batch_size, seq)) < mask_frac
    ids = labels.copy()
    ids[mask] = mask_id
    return {"input_ids": ids, "labels": labels,
            "loss_mask": mask.astype(np.float32)}


def xent_label(fused) -> str:
    """Unit-string label for the loss path on a TPU (mirrors
    TransformerConfig's fused_xent auto rule at DP-only bench shapes:
    None = fused)."""
    return "xla" if fused is False else "fused"

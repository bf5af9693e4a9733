"""Autotuner experiment worker: one experiment per child interpreter.

The reference autotuner launches every experiment as a separate scheduler
job (``autotuning/scheduler.py``) precisely so a dead experiment cannot
take down the tune; round-3 review flagged that this tuner ran candidates
in-process instead — one XLA CHECK-crash (native abort, uncatchable) or a
wedging OOM kills the whole search. This worker restores that isolation:
the parent serializes ``(config, model_spec, steps)`` to JSON, the child
builds the model from the spec (a preset name + overrides — engines and
closures don't cross process boundaries), times the steps, and prints ONE
JSON result line. Any crash is the child's problem; the parent records a
failure and moves on.

Invoked as ``python -m deepspeed_tpu.autotuning.worker '<json>'``.
"""

from __future__ import annotations

import json
import sys
import time


def build_model_from_spec(spec: dict):
    """{"family": "gpt2", "size": "125m", "overrides": {...}} → model."""
    from .. import models

    family = getattr(models, spec["family"])
    args = (spec["size"],) if "size" in spec else ()
    cfg = family(*args, **spec.get("overrides", {}))
    return models.build_model(cfg), cfg


def make_batch_for(cfg, batch_size: int, seq: int | None = None):
    """Synthetic batch matching the model's objective."""
    import numpy as np

    S = int(seq or min(getattr(cfg, "max_seq", 128), 512))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch_size, S)).astype(np.int32)
    batch = {"input_ids": ids}
    if getattr(cfg, "objective", "clm") == "mlm":
        labels = ids.copy()
        mask = rng.random((batch_size, S)) < 0.15
        ids = ids.copy()
        ids[mask] = min(103, cfg.vocab_size - 1)
        batch = {"input_ids": ids, "labels": labels,
                 "loss_mask": mask.astype(np.float32)}
    return batch


def run_experiment(payload: dict) -> dict:
    import jax

    import deepspeed_tpu as ds

    model, cfg = build_model_from_spec(payload["model_spec"])
    engine = ds.initialize(payload["config"], model)
    batch = make_batch_for(cfg, engine.train_batch_size,
                           payload.get("seq"))
    for _ in range(int(payload.get("warmup", 1))):
        engine.train_batch(dict(batch))
    jax.block_until_ready(engine.train_batch(dict(batch))["loss"])
    steps = int(payload.get("steps", 3))
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(dict(batch))
    loss = float(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    if not jax.numpy.isfinite(loss):
        return {"ok": False, "error": f"non-finite loss {loss}"}
    return {"ok": True,
            "samples_per_sec": engine.train_batch_size / dt,
            "loss": loss}


def main(argv=None) -> None:
    payload = json.loads((argv or sys.argv[1:])[0])
    try:
        result = run_experiment(payload)
    except Exception as e:        # noqa: BLE001 — the whole point
        result = {"ok": False, "error": f"{type(e).__name__}: {e}"[:300]}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

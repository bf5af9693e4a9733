"""Per-layer metrics from the program's own counters: the total a counter
of the process-wide registry (``deepspeed_tpu.observability.metrics
.get_registry()``) has reached over the process's life, set-up included.
A program that does not keep the counter there has nothing to read:
``None``."""

from __future__ import annotations


def reduce(facts, *, counter: str):
    try:
        from deepspeed_tpu.observability.metrics import get_registry
    except ImportError:
        return None
    value = get_registry().snapshot()["counters"].get(counter)
    return None if value is None else float(value)

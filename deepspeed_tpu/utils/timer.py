"""Wall-clock + throughput timers.

Analog of the reference ``deepspeed/utils/timer.py`` (``SynchronizedWallClockTimer:43``,
``ThroughputTimer:198``). On TPU there are no CUDA events; synchronization is
``jax.block_until_ready`` on the step outputs, which the engine does at timer
boundaries when ``wall_clock_breakdown`` is on.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

from .logging import log_dist


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self.started = False
        self._start = 0.0
        self.elapsed_total = 0.0
        self.count = 0
        # most recent completed interval, as absolute perf_counter instants
        # — the observability span layer re-emits timer windows as spans
        # without adding clock reads of its own
        self.last_start = 0.0
        self.last_stop = 0.0

    def start(self) -> None:
        self._start = time.perf_counter()
        self.started = True

    def stop(self) -> None:
        if not self.started:
            return
        now = time.perf_counter()
        self.elapsed_total += now - self._start
        self.count += 1
        self.last_start = self._start
        self.last_stop = now
        self.started = False

    def elapsed(self, reset: bool = True) -> float:
        e = self.elapsed_total
        if reset:
            self.reset()
        return e

    def mean(self) -> float:
        return self.elapsed_total / max(1, self.count)

    def reset(self) -> None:
        self.elapsed_total = 0.0
        self.count = 0
        self.started = False


class WallClockTimers:
    """Named timer registry (reference ``SynchronizedWallClockTimer``)."""

    def __init__(self, synchronize_fn: Optional[Callable[[], None]] = None):
        self._timers: dict[str, _Timer] = {}
        self._synchronize = synchronize_fn

    def __call__(self, name: str) -> _Timer:
        if name not in self._timers:
            self._timers[name] = _Timer(name)
        return self._timers[name]

    def start(self, name: str) -> None:
        if self._synchronize:
            self._synchronize()
        self(name).start()

    def stop(self, name: str) -> None:
        if self._synchronize:
            self._synchronize()
        self(name).stop()

    def log(self, names: list[str] | None = None, reset: bool = True) -> dict[str, float]:
        names = names or list(self._timers)
        out = {}
        for n in names:
            if n in self._timers:
                out[n] = self._timers[n].elapsed(reset=reset) * 1000.0
        if out:
            msg = " | ".join(f"{k}: {v:.2f}ms" for k, v in out.items())
            log_dist(f"time (ms) | {msg}", ranks=[0])
        return out


class ThroughputTimer:
    """samples/s + TFLOPS/MFU reporting (reference ``utils/timer.py:198``).

    ``flops_per_sample`` comes from the model's cost analysis (see
    ``profiling/flops.py``); ``peak_flops`` from the platform table.
    """

    def __init__(self, batch_size: int, steps_per_output: int = 10,
                 flops_per_sample: float = 0.0, peak_flops: float = 0.0,
                 monitor=None):
        self.batch_size = batch_size
        self.steps_per_output = steps_per_output
        self.flops_per_sample = flops_per_sample
        self.peak_flops = peak_flops
        self.monitor = monitor
        self.epoch_count = 0
        self.global_steps = 0
        self.total_elapsed = 0.0
        self._start = None

    def start(self) -> None:
        self._start = time.perf_counter()

    def stop(self, report: bool = True) -> Optional[dict]:
        if self._start is None:
            return None
        dt = time.perf_counter() - self._start
        self._start = None
        self.global_steps += 1
        self.total_elapsed += dt
        if report and self.global_steps % self.steps_per_output == 0:
            return self.report(dt)
        return None

    def report(self, step_time: float) -> dict:
        samples_per_sec = self.batch_size / max(step_time, 1e-9)
        stats = {"samples_per_sec": samples_per_sec, "step_time_s": step_time}
        if self.flops_per_sample:
            tflops = samples_per_sec * self.flops_per_sample / 1e12
            stats["tflops"] = tflops
            if self.peak_flops:
                stats["mfu"] = tflops * 1e12 / self.peak_flops
        msg = (f"step {self.global_steps}: {samples_per_sec:.1f} samples/s, "
               f"{step_time * 1000:.1f} ms/step")
        if "tflops" in stats:
            msg += f", {stats['tflops']:.1f} TFLOPS"
        if "mfu" in stats:
            msg += f", MFU {stats['mfu'] * 100:.1f}%"
        log_dist(msg, ranks=[0])
        return stats


# Published per-chip peaks, keyed by platform and a substring of
# ``device_kind`` (Google Cloud TPU documentation; v5e: 197 TFLOP/s bf16,
# 819 GB/s HBM, 1600 Gbit/s ICI). A device that is not in a table is an
# error, not a default: a utilization is a measurement against a known peak.

# Peak dense bf16 FLOPS per chip, for MFU accounting.
PEAK_FLOPS_BY_PLATFORM = {
    "tpu": {
        "v4": 275e12,
        "v5 lite": 197e12,  # v5e
        "v5": 459e12,       # v5p
        "v6 lite": 918e12,  # trillium
    },
}


# Peak HBM bandwidth per chip (bytes/s), for decode MBU accounting
# (autoregressive decode is bandwidth-bound: every generated token re-reads
# the weights, so tokens/s * bytes-read-per-token / peak-BW is the honest
# utilization metric — the decode analog of MFU).
PEAK_HBM_BW_BY_PLATFORM = {
    "tpu": {
        "v4": 1228e9,
        "v5 lite": 819e9,   # v5e
        "v5": 2765e9,       # v5p
        "v6 lite": 1640e9,  # trillium
    },
}


def _peak_lookup(device, tables: dict, env_var: str, what: str) -> float:
    """Shared per-chip peak lookup for utilization accounting. MFU/MBU are
    the product's headline numbers, so an unknown device must fail loudly
    rather than silently divide by a guessed peak; override with the named
    env var when running on hardware the table predates."""
    override = os.environ.get(env_var)
    if override:
        return float(override)
    kind = getattr(device, "device_kind", "").lower()
    for key, val in tables.get(device.platform, {}).items():
        if key in kind:
            return val
    raise ValueError(
        f"no {what} entry for {device.platform} device {kind!r} — refusing "
        f"to guess; set {env_var}=<per-chip value> to report a utilization")


def peak_hbm_bw_for(device) -> float:
    """Per-chip peak HBM bandwidth (bytes/s) for decode-MBU accounting.
    Override: ``DSTPU_PEAK_HBM_BW``."""
    return _peak_lookup(device, PEAK_HBM_BW_BY_PLATFORM,
                        "DSTPU_PEAK_HBM_BW", "HBM bandwidth")


def peak_flops_for(device) -> float:
    """Per-chip peak bf16 FLOP/s for MFU accounting.
    Override: ``DSTPU_PEAK_FLOPS``."""
    return _peak_lookup(device, PEAK_FLOPS_BY_PLATFORM,
                        "DSTPU_PEAK_FLOPS", "peak FLOPs")


# Aggregate per-chip interconnect (ICI) bandwidth (bytes/s, all links),
# for the collective bus-bandwidth roofline (observability/commscope.py:
# achieved busbw / this peak is the collective analog of the decode MBU).
# Published aggregates: v4 six 50 GB/s links, v5e four 50 GB/s (1600
# Gbps), v5p 600 GB/s (4800 Gbps), Trillium ~448 GB/s (3584 Gbps).
PEAK_ICI_BW_BY_PLATFORM = {
    "tpu": {
        "v4": 300e9,
        "v5 lite": 200e9,   # v5e
        "v5": 600e9,        # v5p
        "v6 lite": 448e9,   # trillium
    },
}


def peak_ici_bw_for(device) -> float:
    """Per-chip aggregate ICI bandwidth (bytes/s) for the collective
    roofline. Override: ``DSTPU_PEAK_ICI_BW``. Raises ValueError on an
    unknown TPU generation like the other peaks — commscope catches it
    and degrades the roofline ratio to null."""
    return _peak_lookup(device, PEAK_ICI_BW_BY_PLATFORM,
                        "DSTPU_PEAK_ICI_BW", "ICI bandwidth")

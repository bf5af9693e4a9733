"""Hardware/platform abstraction.

TPU-native analog of the reference accelerator layer
(``accelerator/abstract_accelerator.py:10`` and ``real_accelerator.py``): a
single seam through which the rest of the framework asks about devices,
memory, dtypes, and the communication fabric — nothing above this module
touches ``jax.devices()`` directly.

The reference abstracts over CUDA streams/events/RNG; under XLA those concepts
are owned by the compiler, so the surface here is the part that still matters
on TPU: device discovery, platform naming, memory kinds & stats, dtype
support, host/device transfer helpers, and multi-host initialization.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np

from ..utils.logging import logger

_ACCELERATOR: Optional["TpuAccelerator"] = None


@dataclass
class MemoryStats:
    bytes_in_use: int = 0
    peak_bytes_in_use: int = 0
    bytes_limit: int = 0

    @property
    def available_bytes(self) -> int:
        return max(0, self.bytes_limit - self.bytes_in_use)

    def as_dict(self) -> dict:
        """Gauge-ready view (observability HBM watermark sampling)."""
        return {"bytes_in_use": self.bytes_in_use,
                "peak_bytes_in_use": self.peak_bytes_in_use,
                "bytes_limit": self.bytes_limit,
                "available_bytes": self.available_bytes}


class TpuAccelerator:
    """Device/platform facade over JAX.

    Named "Tpu" for the primary target, but transparently backed by whatever
    platform JAX selected (tpu / cpu / gpu), the same
    way the reference probes for the real accelerator at import time
    (``accelerator/real_accelerator.py``).
    """

    def __init__(self, platform: str | None = None):
        self._platform = platform or os.environ.get("DSTPU_ACCELERATOR") or None
        self._devices = None

    # ------------------------------------------------------------------ info
    @property
    def platform(self) -> str:
        return self.devices()[0].platform

    def device_name(self, index: int | None = None) -> str:
        if index is None:
            return self.platform
        return f"{self.platform}:{index}"

    def devices(self) -> list[jax.Device]:
        if self._devices is None:
            self._devices = jax.devices(self._platform) if self._platform else jax.devices()
        return self._devices

    def device_count(self) -> int:
        return len(self.devices())

    def local_devices(self) -> list[jax.Device]:
        plat = self._platform
        return [d for d in (jax.local_devices()) if plat is None or d.platform == plat]

    def local_device_count(self) -> int:
        return len(self.local_devices())

    def process_index(self) -> int:
        return jax.process_index()

    def process_count(self) -> int:
        return jax.process_count()

    def current_device(self) -> jax.Device:
        return self.devices()[0]

    def on_tpu(self) -> bool:
        return self.platform == "tpu"

    # -------------------------------------------------------------- memories
    def memory_kinds(self) -> tuple[str, ...]:
        """Addressable memory kinds: device HBM plus host-pinned staging.

        The host memory kind is the TPU analog of the reference's pinned-memory
        APIs (``abstract_accelerator.py`` pin_memory) and is what the offload
        tiers target.
        """
        try:
            return tuple(m.kind for m in self.current_device().addressable_memories())
        except Exception:
            return ("device",)

    def supports_host_offload(self) -> bool:
        return "pinned_host" in self.memory_kinds()

    def memory_stats(self, device: jax.Device | None = None) -> MemoryStats:
        device = device or self.current_device()
        try:
            ms = device.memory_stats() or {}
        except Exception:
            ms = {}
        return MemoryStats(
            bytes_in_use=ms.get("bytes_in_use", 0),
            peak_bytes_in_use=ms.get("peak_bytes_in_use", ms.get("bytes_in_use", 0)),
            bytes_limit=ms.get("bytes_limit", ms.get("bytes_reservable_limit", 0)),
        )

    def total_memory(self) -> int:
        return self.memory_stats().bytes_limit

    def available_memory(self) -> int:
        return self.memory_stats().available_bytes

    # ---------------------------------------------------------------- dtypes
    def is_bf16_supported(self) -> bool:
        return True  # native on every TPU generation this framework targets

    def is_fp16_supported(self) -> bool:
        return True  # representable; bf16 is preferred on TPU

    def is_fp8_supported(self) -> bool:
        return self.platform == "tpu"

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16

    def supported_dtypes(self):
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    # ------------------------------------------------------------------ comm
    def communication_backend_name(self) -> str:
        """ICI/DCN via XLA collectives (the NCCL analog is the compiler)."""
        return "xla"

    # ------------------------------------------------------------- op lookup
    def create_op_builder(self, name: str):
        from ..ops.registry import get_op_builder

        return get_op_builder(name, platform=self.platform)

    # ----------------------------------------------------------------- misc
    def synchronize(self) -> None:
        """Block until all dispatched device work is complete."""
        try:
            jax.block_until_ready(jax.device_put(np.zeros(())))
        except Exception:  # pragma: no cover - defensive
            pass

    def random_seed(self, seed: int):
        return jax.random.PRNGKey(seed)


def get_accelerator() -> TpuAccelerator:
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = TpuAccelerator()
        logger.info(
            f"deepspeed_tpu accelerator: platform={_ACCELERATOR.platform} "
            f"devices={_ACCELERATOR.device_count()} processes={_ACCELERATOR.process_count()}"
        )
    return _ACCELERATOR


def set_accelerator(acc: TpuAccelerator) -> None:
    global _ACCELERATOR
    _ACCELERATOR = acc


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> None:
    """Multi-host initialization (analog of ``deepspeed.init_distributed``).

    Single-host jobs need not call this. Multi-host jobs call it once per host
    before any JAX computation; afterwards ``jax.devices()`` spans the full
    pod/slice and SPMD programs run over DCN+ICI transparently.
    """
    if num_processes is None:
        num_processes = int(os.environ.get("DSTPU_NUM_PROCESSES", "0")) or None
    if process_id is None:
        pid = os.environ.get("DSTPU_PROCESS_ID")
        process_id = int(pid) if pid is not None else None
    coordinator_address = coordinator_address or os.environ.get("DSTPU_COORDINATOR")
    if num_processes is None or num_processes <= 1:
        logger.info("init_distributed: single-process mode (no coordinator)")
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    logger.info(
        f"init_distributed: process {jax.process_index()}/{jax.process_count()} "
        f"local_devices={len(jax.local_devices())} global_devices={len(jax.devices())}"
    )

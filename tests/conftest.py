"""Test harness configuration.

Analog of the reference's DistributedTest machinery (``tests/unit/common.py``):
where the reference spawns N OS processes with real NCCL over loopback, the
JAX-native trick is a *virtual 8-device CPU mesh* in one process
(``--xla_force_host_platform_device_count``) — every collective, sharding, and
partitioning path compiles and executes exactly as it would across 8 chips.

The tests run on the CPU backend wherever they are started: this module pins
``JAX_PLATFORMS=cpu`` and the eight virtual devices before jax is imported,
and subprocesses spawned by tests (the launcher, the examples, the
checkpoint-chaos script) inherit both. What only the chip can show is
``chip_smoke.py``'s job.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                  if not f.startswith("--xla_force_host_platform_device_count"))
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DSTPU_LOG_LEVEL", "WARNING")

import jax  # noqa: E402

from deepspeed_tpu.platform import configure_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: engine tests recompile near-identical train
# steps; cache hits cut the suite from ~40 min toward ~10.  Keyed by HLO, so
# correctness is XLA's problem, not ours. Placed from outside where
# JAX_COMPILATION_CACHE_DIR is set; tests/.jax_cache otherwise. The variable
# is then set so that the few tests that must start a process of their own
# (test_examples, test_launcher, test_resilience's crash script) compile
# into the same cache.
os.environ["JAX_COMPILATION_CACHE_DIR"] = configure_compile_cache(
    os.path.join(os.path.dirname(__file__), ".jax_cache"))
# threshold 0: tiny-model test programs mostly compile in <0.5s, which the
# default floor would exclude from the cache — exactly the programs this
# suite rebuilds by the hundred
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs

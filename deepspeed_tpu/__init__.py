"""deepspeed_tpu: a TPU-native large-scale training & inference framework.

Brand-new JAX/XLA/Pallas implementation of the full capability set of the
reference (DeepSpeed v0.11.2 — see SURVEY.md): JSON-config-driven training
engine, ZeRO-style optimizer/gradient/parameter sharding with tiered offload,
data/tensor/pipeline/expert/sequence parallelism on one named device mesh,
Pallas kernels for the hot ops, sharded universal checkpoints, inference/
decode engine, and the observability stack.
"""

from .config import Config
from .inference import (InferenceConfig, InferenceEngine, ServingConfig,
                        init_inference)
from .serving import ServingEngine
from .platform import (get_accelerator, init_distributed, build_mesh, MeshSpec)
from .resilience import (ChaosConfig, NonFiniteLossError, PreemptionGuard,
                         QueueFullError, RequestStatus)
from .runtime.engine import Engine, initialize
from .runtime.hybrid_engine import HybridEngine
from .version import __version__

from . import comm  # noqa: F401  (deepspeed.comm analog)
from . import observability  # noqa: F401  (metrics/tracing/sinks layer)
from . import resilience  # noqa: F401  (chaos + guards + checkpoint integrity)

__all__ = ["initialize", "Engine", "HybridEngine", "Config",
           "init_inference", "InferenceEngine", "InferenceConfig",
           "ServingConfig", "ServingEngine",
           "RequestStatus", "QueueFullError", "NonFiniteLossError",
           "ChaosConfig", "PreemptionGuard",
           "get_accelerator", "init_distributed", "build_mesh", "MeshSpec",
           "__version__"]

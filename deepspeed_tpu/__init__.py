"""deepspeed_tpu: a TPU-native large-scale training & inference framework.

Brand-new JAX/XLA/Pallas implementation of the full capability set of the
reference (DeepSpeed v0.11.2 — see SURVEY.md): JSON-config-driven training
engine, ZeRO-style optimizer/gradient/parameter sharding with tiered offload,
data/tensor/pipeline/expert/sequence parallelism on one named device mesh,
Pallas kernels for the hot ops, sharded universal checkpoints, inference/
decode engine, and the observability stack.
"""

import time as _time

_T_IMPORT = _time.perf_counter()   # the ``init.import`` span opens here

from .config import Config  # noqa: E402
from .inference import (InferenceConfig, InferenceEngine, ServingConfig,
                        init_inference)  # noqa: E402
from .serving import ServingEngine  # noqa: E402
from .platform import (get_accelerator, init_distributed, build_mesh, MeshSpec)  # noqa: E402
from .resilience import (ChaosConfig, NonFiniteLossError, PreemptionGuard,
                         QueueFullError, RequestStatus)  # noqa: E402
from .runtime.engine import Engine, initialize  # noqa: E402
from .runtime.hybrid_engine import HybridEngine  # noqa: E402
from .version import __version__  # noqa: E402

from . import comm  # noqa: E402,F401  (deepspeed.comm analog)
from . import observability  # noqa: E402,F401  (metrics/tracing/sinks layer)
from . import resilience  # noqa: E402,F401  (chaos + guards + checkpoint integrity)

# what importing the package cost this process, in the lifecycle ring beside
# the engines' own builds (observability/spans.py; docs/OBSERVABILITY.md)
observability.spans.emit(None, observability.spans.INIT, _T_IMPORT,
                         _time.perf_counter(), phase="import")

__all__ = ["initialize", "Engine", "HybridEngine", "Config",
           "init_inference", "InferenceEngine", "InferenceConfig",
           "ServingConfig", "ServingEngine",
           "RequestStatus", "QueueFullError", "NonFiniteLossError",
           "ChaosConfig", "PreemptionGuard",
           "get_accelerator", "init_distributed", "build_mesh", "MeshSpec",
           "__version__"]

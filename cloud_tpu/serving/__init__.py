"""In-process TPU serving: continuous-batched inference on the
generation path.

The training side got its occupancy engineering in PRs 2-3 (prefetch,
fused dispatch, compile-ahead); this package is the inference
counterpart — a request queue + scheduler that drives
``models.generation``'s slot-grid programs (insert + chunk decode) at
steady-state occupancy, retiring and refilling decode slots between
chunks, while individual callers see a simple future-per-request API.
See ``docs/serving.md`` and :mod:`cloud_tpu.serving.engine`.
"""

from cloud_tpu.serving.engine import (
    DeadlineExceededError,
    DispatchTimeoutError,
    DraftConfig,
    EngineClosedError,
    QueueFullError,
    ServeConfig,
    ServeResult,
    ServingEngine,
    SERVE_DISPATCH_THREAD_NAME,
    SERVE_SCHEDULER_THREAD_NAME,
)
from cloud_tpu.serving.prefix_cache import (
    AFFINITY_PREFIX_TOKENS,
    PrefixCacheManager,
    PrefixHit,
    affinity_key,
)
from cloud_tpu.serving.qos import (
    BrownoutShedError,
    PriorityClass,
    QosConfig,
    QosScheduler,
    QuotaExceededError,
    TenantQuota,
    TokenBucket,
    TokenStream,
)

__all__ = [
    "AFFINITY_PREFIX_TOKENS",
    "affinity_key",
    "BrownoutShedError",
    "DeadlineExceededError",
    "DispatchTimeoutError",
    "DraftConfig",
    "EngineClosedError",
    "PrefixCacheManager",
    "PrefixHit",
    "PriorityClass",
    "QosConfig",
    "QosScheduler",
    "QueueFullError",
    "QuotaExceededError",
    "ServeConfig",
    "ServeResult",
    "ServingEngine",
    "TenantQuota",
    "TokenBucket",
    "TokenStream",
    "SERVE_DISPATCH_THREAD_NAME",
    "SERVE_SCHEDULER_THREAD_NAME",
]

"""Bucket-batched analog serving: shape buckets, AOT executable cache,
pluggable execution tiers (tiers.py: uniform-K, per-layer PrecisionProfile,
and digital/int8 tiers behind one ExecutionTier interface + TierRegistry),
precision-tiered scheduling, persistent per-tier decode slot pools
(continuous batching), fault injection + noise-drift watchdog + streaming
MetricsFeed + graceful degradation (faults.py, monitor.py), a replicated
cluster router with health-checked failover and hedged dispatch
(cluster.py), and the engine tying them to models/lm.py."""
from repro.core.profile import PrecisionProfile
from repro.serving.bucketing import (
    DEFAULT_BATCH_BUCKETS,
    DEFAULT_SEQ_BUCKETS,
    bucket_shape,
    next_bucket,
    pad_to_bucket,
    pool_shape,
)
from repro.serving.cache import ExecutableBuildError, ExecutableCache, aot_compile
from repro.serving.cluster import (
    ClusterGovernor,
    ClusterRouter,
    RequestJournalEntry,
)
from repro.serving.engine import (
    Failed,
    RequestFailure,
    ServingEngine,
    TimedOut,
)
from repro.serving.faults import (
    BoundedLog,
    DriftRamp,
    FaultPlan,
    QueueFull,
    ReplicaCrash,
    ReplicaDegraded,
    ReplicaFault,
    ReplicaHang,
    TransientExecutableFault,
)
from repro.serving.monitor import (
    DriftEvent,
    LoadSignals,
    MetricsFeed,
    NoiseDriftWatchdog,
    WatchdogConfig,
    load_signals,
)
from repro.serving.policy import (
    PolicyConfig,
    PolicyEvent,
    PrecisionGovernor,
    TierSpec,
)
from repro.serving.pool import DecodePool, SlotAllocator, SlotRecord
from repro.serving.scheduler import Request, TierScheduler
from repro.serving.tiers import (
    AnalogProfileTier,
    DigitalTier,
    ExecutionTier,
    Int8DigitalTier,
    TierRegistry,
    UniformKTier,
)

__all__ = [
    "AnalogProfileTier",
    "BoundedLog",
    "ClusterGovernor",
    "ClusterRouter",
    "DEFAULT_BATCH_BUCKETS",
    "DEFAULT_SEQ_BUCKETS",
    "DecodePool",
    "DigitalTier",
    "DriftEvent",
    "DriftRamp",
    "ExecutableBuildError",
    "ExecutableCache",
    "ExecutionTier",
    "Failed",
    "FaultPlan",
    "Int8DigitalTier",
    "LoadSignals",
    "MetricsFeed",
    "NoiseDriftWatchdog",
    "PolicyConfig",
    "PolicyEvent",
    "PrecisionGovernor",
    "PrecisionProfile",
    "QueueFull",
    "ReplicaCrash",
    "ReplicaDegraded",
    "ReplicaFault",
    "ReplicaHang",
    "Request",
    "RequestFailure",
    "RequestJournalEntry",
    "ServingEngine",
    "SlotAllocator",
    "SlotRecord",
    "TierRegistry",
    "TierScheduler",
    "TierSpec",
    "UniformKTier",
    "TimedOut",
    "TransientExecutableFault",
    "WatchdogConfig",
    "aot_compile",
    "bucket_shape",
    "load_signals",
    "next_bucket",
    "pad_to_bucket",
    "pool_shape",
]

"""Rollout serving plane (DESIGN.md §12).

Request queue + capacity-bucket admission, dynamic same-bucket
batching, a bounded compiled-program cache, streaming per-chunk
responses, and serving metrics — layered on
:class:`~repro.rollout.engine.BatchedRolloutEngine`.
"""
from repro.serving.batcher import (DEFAULT_NODE_BUCKETS, AdmissionError,
                                   BucketKey, DynamicBatcher, PendingRequest,
                                   QueueFullError, capacity_bucket)
from repro.serving.metrics import ServingMetrics
from repro.serving.programs import LRUCache, ProgramCache, ProgramKey
from repro.serving.service import (RolloutService, ServiceConfig,
                                   StreamingResponse, validate_scene)

__all__ = [
    "AdmissionError", "BucketKey", "DEFAULT_NODE_BUCKETS", "DynamicBatcher",
    "LRUCache", "PendingRequest", "ProgramCache", "ProgramKey",
    "QueueFullError", "RolloutService", "ServiceConfig", "ServingMetrics",
    "StreamingResponse", "capacity_bucket", "validate_scene",
]

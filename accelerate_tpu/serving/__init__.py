"""Production serving: continuous batching over a paged KV cache, replicated
behind a fault-tolerant router.

The serve-many-concurrent-requests counterpart of ``generation.py``'s
single-stream decode (ROADMAP item 1). Five pillars:

- :mod:`~accelerate_tpu.serving.kv_pager` — fixed-size KV blocks in one
  preallocated device pool: the host-side block allocator (the pool's device
  side, its format, write and paged attention, is ``ops.flash_attention``'s);
- :mod:`~accelerate_tpu.serving.scheduler` — step-granular admission,
  immediate completion/backfill, LIFO preemption with persisted resume;
- :mod:`~accelerate_tpu.serving.engine` — the
  :class:`~accelerate_tpu.serving.engine.ServingEngine` step loop, compiled
  only over the :mod:`~accelerate_tpu.serving.buckets` shape lattice so
  admission churn never recompiles;
- :mod:`~accelerate_tpu.serving.replica` — one warmed engine per unit of
  failure (thread- or subprocess-backed), streaming per-step token progress;
- :mod:`~accelerate_tpu.serving.router` +
  :mod:`~accelerate_tpu.serving.admission` — health-checked
  least-outstanding-tokens dispatch over N replicas with deadlines,
  exactly-once token-exact failover, token-bucket admission, priority
  shedding (distinct ``SHED`` status) and bounded-queue backpressure;
- :mod:`~accelerate_tpu.serving.disagg` +
  :mod:`~accelerate_tpu.serving.autoscaler` — disaggregated prefill/decode:
  role-split engines joined by a content-addressed KV handoff
  (:class:`~accelerate_tpu.serving.disagg.KVHandoff` behind a
  :class:`~accelerate_tpu.serving.disagg.KVTransport`), two-tier dispatch
  (:class:`~accelerate_tpu.serving.disagg.DisaggRouter`), and an
  SLO-burn-driven :class:`~accelerate_tpu.serving.autoscaler.
  AutoscalerPolicy` whose scale-ups join warm via compile-cache
  pre-shipping;
- :mod:`~accelerate_tpu.serving.canary` — bitwise correctness canaries:
  golden requests precomputed from the single-stream reference at startup
  and periodically injected by the router
  (:class:`~accelerate_tpu.serving.canary.CanaryProbe`); a mismatching
  replica emits ``canary_failure`` and counts toward DRAINING pressure
  exactly like an SLO-burning one.

See ``docs/serving.md`` for the guide and ``benchmarks/serving/`` for the
continuous-vs-static and replicated Poisson-load benchmarks
(``make bench-serve``).
"""

from .admission import (
    PRIORITY_BATCH,
    PRIORITY_INTERACTIVE,
    AdmissionController,
    AdmissionVerdict,
    TokenBucket,
)
from .buckets import BucketLattice
from ..models.transformer import llama_paged_forward as paged_forward
from ..ops.flash_attention import (
    NULL_BLOCK,
    NULL_STATE_ROW,
    init_block_pool,
    paged_attention_gather as paged_attention,
)
from .engine import ServingEngine
from .kv_pager import (
    BlockAllocator,
    BlockAllocatorError,
    BlockPoolExhausted,
    PrefixAllocation,
    PrefixPlan,
)
from .autoscaler import AutoscalerPolicy, lattice_fns
from .canary import CanaryGolden, CanaryProbe, precompute_goldens
from .disagg import (
    DecodeEngine,
    DisaggRouter,
    KVHandoff,
    KVTransport,
    LocalBlockCopyTransport,
    PrefillEngine,
)
from .replica import LocalReplica, ProcessReplica, ReplicaSpec, ReplicaState
from .router import RouterRequest, RouterRequestStatus, ServingRouter
from .scheduler import Request, RequestStatus, Scheduler, SchedulingError

__all__ = [
    "BucketLattice",
    "ServingEngine",
    "paged_forward",
    "NULL_BLOCK",
    "NULL_STATE_ROW",
    "BlockAllocator",
    "BlockAllocatorError",
    "BlockPoolExhausted",
    "init_block_pool",
    "paged_attention",
    "PrefixPlan",
    "PrefixAllocation",
    "Request",
    "RequestStatus",
    "Scheduler",
    "SchedulingError",
    "PRIORITY_INTERACTIVE",
    "PRIORITY_BATCH",
    "TokenBucket",
    "AdmissionVerdict",
    "AdmissionController",
    "ReplicaState",
    "ReplicaSpec",
    "LocalReplica",
    "ProcessReplica",
    "RouterRequest",
    "RouterRequestStatus",
    "ServingRouter",
    "KVHandoff",
    "KVTransport",
    "LocalBlockCopyTransport",
    "PrefillEngine",
    "DecodeEngine",
    "DisaggRouter",
    "AutoscalerPolicy",
    "lattice_fns",
    "CanaryGolden",
    "CanaryProbe",
    "precompute_goldens",
]

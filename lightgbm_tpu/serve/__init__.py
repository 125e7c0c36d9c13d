"""Device-resident prediction serving.

The training half of the north star got fast (megastep, donated
buffers); this package is the serving half: trees packed ONCE into the
device-resident stacked tensors ``models/predictor.py`` builds, jitted
traversal with power-of-two row-count bucketing (any request size after
warmup hits the XLA cache — zero recompiles), request micro-batching
with deadline coalescing, and multi-model residency under a bytes
budget.  The shape of the win follows XGBoost's device-resident
predictor (arxiv 1806.11248): keep the model on the accelerator and
amortize dispatch over batched requests.

Layers (docs/Serving.md):

- :class:`ServingEngine` (engine.py) — one packed model: bucketed,
  warmup-compiled device traversal with deterministic
  compile/dispatch counters and graceful degradation to the host walk;
- :class:`MicroBatcher` (batcher.py) — thread-safe request queue with
  ``max_batch_rows`` / ``max_delay_ms`` deadline coalescing, one device
  call per drained micro-batch, future-based responses;
- :class:`ResidencyManager` (residency.py) — N models sharing the
  serve devices under a per-device bytes budget with LRU eviction and
  pin/unpin;
- :class:`BulkScorer` (bulk.py) — row-sharded offline scoring: the
  jitted traversal shard_mapped over the serve mesh with the packed
  stacks as replicated read-only operands
  (``PredictionService.predict_bulk``);
- :class:`PredictionService` (service.py) — the public facade:
  ``PredictionService(boosters_or_paths).predict(model_id, X)``.

Serving fleet (docs/Serving.md "Serving fleet"): with
``serve_devices > 1`` each hot model's packed tensors replicate onto N
local devices, each with its own dispatch lane (queue + worker); the
micro-batcher routes micro-batches to the least-loaded replica, spills
to the coldest lane before shedding, and keeps the per-device
deterministic contract — exactly 1.0 dispatches/request, 0
steady-state recompiles — that ``bench.py --serve`` gates per device.
Rollover swaps all replicas atomically.

Overload hardening (docs/Serving.md "Overload & rollover"): bounded
queues with structured :class:`ServeRejected` admission refusals,
per-request deadlines shed at dequeue (:class:`ServeDeadlineExceeded`),
an adaptive p99-driven :class:`AdmissionController`, client
:class:`RetryPolicy` (shed/reject only, never compute errors),
zero-downtime ``PredictionService.rollover`` with optional shadow
scoring, and wedged-worker detection (:class:`ServeWorkerWedged`).
"""
from .admission import AdmissionController
from .batcher import MicroBatcher
from .bulk import BulkScorer
from .engine import ServingEngine
from .errors import (RetryPolicy, ServeClosed, ServeDeadlineExceeded,
                     ServeError, ServeRejected, ServeWorkerWedged)
from .residency import ResidencyManager
from .service import PredictionService

__all__ = ["PredictionService", "ServingEngine", "MicroBatcher",
           "ResidencyManager", "BulkScorer", "AdmissionController",
           "RetryPolicy", "ServeError", "ServeRejected",
           "ServeDeadlineExceeded", "ServeClosed", "ServeWorkerWedged"]

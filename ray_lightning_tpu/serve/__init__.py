"""Continuous-batching inference serving plane (ISSUE 6).

The "serve heavy traffic" half of the north star: a TPU-shaped serving
engine on the existing actor/queue substrate.  Shape discipline is the
same one the training core lives by — every steady-state program is
compiled ONCE and re-dispatched forever:

* :mod:`.kv_cache` — **paged KV cache**: the per-layer cache is a pool
  of fixed-size token blocks shared by every in-flight sequence, with a
  host-side block allocator and device-side block tables.  Finished
  requests free their blocks immediately; prefill writes whole blocks,
  decode scatters one slot per step;
* :mod:`.scheduler` — **continuous batcher**: bounded admission queue
  with per-request deadlines, join-on-arrival / evict-on-finish between
  decode steps, recompute-style preemption when the block pool runs dry;
* :mod:`.engine` — the driver-side serve loop: bucketed prefill
  programs + ONE fixed-width decode program, SLO stats (TTFT, per-token
  latency, queue depth, occupancy) and OpenMetrics export;
* :mod:`.client` — request submission/streaming over the DriverQueue
  plane, with backpressure surfaced as typed rejections;
* :mod:`.draft` — draft-model construction for **speculative
  decoding**: a small draft proposes K tokens per tick, the target
  verifies them in ONE fixed-width dispatch (``spec_k``/``spec=``
  knobs; lossless for greedy, position-keyed sampling elsewhere);
* :mod:`.lora` — **multi-tenant LoRA multiplexing**: one resident
  lora-free base model, up to ``max_adapters`` tenants' A/B factors
  stacked in resident device buffers, applied per-slot via a gathered
  BGMV with an int32 ``adapter_ids`` operand — any tenant mix shares
  the compiled-once program set (zero steady-state recompiles);
* :mod:`.metrics` — the jax-free SLO stats engine the engine and the
  exporters share;
* :mod:`.dist` — **disaggregated multi-replica serving**: prefill
  workers shipping paged-KV blocks over the queue plane to N decode
  replicas behind a load-aware router with heartbeat failover
  (imported lazily — ``from ray_lightning_tpu.serve.dist import ...``).

See ``docs/SERVING.md`` for architecture and knobs; the serve cells of
``benchmarks/`` (``PERF.md``) are what is measured on the chip.
"""

from ray_lightning_tpu.serve.client import ServeClient, ServeRejected
from ray_lightning_tpu.serve.draft import (
    early_exit_draft,
    pad_identity_layers,
)
from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
from ray_lightning_tpu.serve.lora import (
    AdapterPool,
    decode_adapter,
    encode_adapter,
)
from ray_lightning_tpu.serve.kv_cache import (
    BlockAllocator,
    PagedKVCache,
    paged_decode_step,
    paged_prefill,
    paged_verify_step,
    sample_tokens,
)
from ray_lightning_tpu.serve.metrics import ServeStats
from ray_lightning_tpu.serve.scheduler import (
    Request,
    RequestState,
    Scheduler,
)

__all__ = [
    "ServeEngine",
    "ServeConfig",
    "ServeClient",
    "ServeRejected",
    "ServeStats",
    "PagedKVCache",
    "BlockAllocator",
    "paged_prefill",
    "paged_decode_step",
    "paged_verify_step",
    "sample_tokens",
    "early_exit_draft",
    "pad_identity_layers",
    "AdapterPool",
    "encode_adapter",
    "decode_adapter",
    "Request",
    "RequestState",
    "Scheduler",
]

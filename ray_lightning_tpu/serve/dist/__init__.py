"""Disaggregated multi-replica serving (ISSUE 12).

The distributed half of the serving plane: dedicated **prefill
workers** run prompts on their own devices and ship the resulting
paged-KV blocks over the queue plane (``SegmentStore`` zero-copy
same-host, chunked ``QueueHandle`` frames cross-host) to **decode
replicas** — N independent engines — behind one load-aware **router**
with per-replica admission, heartbeat-based failover (dead replica →
in-flight requests recompute on survivors, streams bitwise-continuous
via the router-stamped sampling seeds + token-index dedup) and a
sliding-window restart governor for prefill workers.

* :mod:`.handoff` — the wire frames (dispatch / KV handoff / adapter
  hot-load / hello / beat; envelopes schema-pinned in
  ``telemetry/schema.py``);
* :mod:`.prefill` — the prefill worker loop (prefill → export →
  handoff);
* :mod:`.replica` — decode-replica runners, in-process and
  ProcessActor deployment shapes, fleet builders;
* :mod:`.router` — placement, admission, fault tolerance, the
  ``router-live.json`` / per-replica OpenMetrics export.

See docs/SERVING.md "Disaggregated serving" for the dataflow diagram,
wire format and failover semantics; ``tools/chaos_serve_sweep.py``
carries the kill-a-replica chaos matrix.  The fleet has not run on the
chip (``PERF.md`` section 7).
"""

from ray_lightning_tpu.serve.dist.handoff import (
    KV_SEGMENT_PREFIX,
    make_adapter_load_item,
    make_beat_item,
    make_dispatch_item,
    make_handoff_item,
    make_hello_item,
    request_fields,
)
from ray_lightning_tpu.serve.dist.prefill import PrefillRunner
from ray_lightning_tpu.serve.dist.replica import (
    ActorPrefill,
    ActorReplica,
    DecodeReplicaRunner,
    InprocPrefill,
    InprocReplica,
    ServeFleet,
    launch_actor_fleet,
    launch_inproc_fleet,
)
from ray_lightning_tpu.serve.dist.router import RestartGovernor, Router

__all__ = [
    "Router",
    "RestartGovernor",
    "ServeFleet",
    "launch_inproc_fleet",
    "launch_actor_fleet",
    "PrefillRunner",
    "DecodeReplicaRunner",
    "InprocReplica",
    "InprocPrefill",
    "ActorReplica",
    "ActorPrefill",
    "KV_SEGMENT_PREFIX",
    "request_fields",
    "make_dispatch_item",
    "make_handoff_item",
    "make_adapter_load_item",
    "make_hello_item",
    "make_beat_item",
]

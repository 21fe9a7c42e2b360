"""Central registry of every ``RLT_*`` environment knob.

One source of truth for the env bus: the knob's name, whether the
strategy layer FORWARDS it to spawned workers (remote workers — node
agents, Ray runtime_env — inherit the AGENT's env, not the driver's,
so a driver-side export that is not bridged here silently never
reaches the fleet; that exact bug class is why this registry exists),
and a one-line description.

Two consumers, which is the point:

* ``parallel/strategies.py`` builds its worker env bridge from
  :func:`forwarded_vars` — the forwarding list can no longer drift
  from the documented knob set;
* ``tools/rlt_lint`` (rule **RLT005**) statically cross-checks every
  literal ``os.environ``/``os.getenv`` read of an ``RLT_*`` name in
  the tree against this registry, so a new knob that someone forgets
  to register (and therefore to forward) fails lint instead of
  silently resolving to its default on every worker.

Adding a knob: one :class:`EnvKnob` line here.  ``forward=True`` puts
it on the worker bridge; ``forward=False`` documents why it is
driver-, agent-, or entry-point-local.  The linter parses this file with
``ast`` (no import), so keep entries as plain ``EnvKnob("NAME", ...)``
calls with a literal first argument.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

__all__ = ["EnvKnob", "KNOBS", "forwarded_vars", "registered_names"]


class EnvKnob(NamedTuple):
    name: str
    #: Bridged into every spawned worker's env (strategies layer)?
    forward: bool
    #: Where the knob is read / why it is (not) forwarded.
    doc: str


KNOBS: Tuple[EnvKnob, ...] = (
    # -- gradient-comm bus (parallel/grad_sync.py, worker-side) ----------
    EnvKnob("RLT_GRAD_COMM", True, "grad compression mode (int8_ef/full)"),
    EnvKnob("RLT_GRAD_BUCKET_MB", True, "all-reduce bucket size"),
    EnvKnob("RLT_GRAD_BLOCK", True, "int8 quantization block length"),
    EnvKnob("RLT_GRAD_DCN_ONLY", True, "compress only across DCN"),
    EnvKnob("RLT_GRAD_OVERLAP", True,
            "backward-overlapped grad sync: trunk segment count G "
            "(0/empty = step-end sync; parallel/overlap.py)"),
    # -- MPMD transport (mpmd/transfer.py, worker-side) ------------------
    EnvKnob("RLT_MPMD_WIRE_DTYPE", True,
            "pipeline DCN payload codec: f32/bf16/int8 or "
            "'act:X,grad:Y' (mpmd/transfer.py WireDtypeConfig)"),
    # -- telemetry bus (telemetry/runtime.py, worker-side) ---------------
    EnvKnob("RLT_TELEMETRY", True, "tier: off/cheap/full"),
    EnvKnob("RLT_TELEMETRY_SAMPLE", True, "step-stats sampling period"),
    EnvKnob("RLT_TELEMETRY_DIR", True, "export directory"),
    EnvKnob("RLT_TELEMETRY_PEAK", True, "device peak-memory probe"),
    EnvKnob("RLT_HEARTBEAT_S", True, "live-plane beat cadence (0=off)"),
    EnvKnob("RLT_FLIGHT_RECORDER", True, "crash-bundle output gate"),
    EnvKnob("RLT_PROGRAM_LEDGER", True,
            "program-ledger kill switch (0/off = bare jax.jit)"),
    EnvKnob("RLT_LOG_RING", True, "forwarded-log ring size"),
    # -- chaos plane (fault/inject.py, worker-side) ----------------------
    EnvKnob("RLT_FAULT", True, "deterministic fault grammar"),
    EnvKnob("RLT_FAULT_STATE", True, "exactly-once marker directory"),
    EnvKnob("RLT_DRAIN_SYNC_EVERY", True, "drain-agreement cadence"),
    # -- loop execution knobs (core/loop.py, worker-side) ----------------
    EnvKnob("RLT_MEGASTEP", True, "fused micro-steps per dispatch"),
    EnvKnob("RLT_UPDATE_SHARDING", True, "cross-replica sharded update"),
    # -- driver-side knobs (never bridged verbatim) ----------------------
    EnvKnob("RLT_ELASTIC_MIN_WORKERS", False, "governor floor (driver)"),
    EnvKnob("RLT_ELASTIC_GROW_AFTER_S", False, "grow-back arm (driver)"),
    EnvKnob("RLT_TPU_CHIPS_PER_HOST", False, "host-topology hint (driver)"),
    EnvKnob("RLT_BACKEND", False, "cluster backend selector (driver)"),
    EnvKnob("RLT_HOSTS", False, "static host list (driver)"),
    EnvKnob("RLT_AGENT_TOKEN", False, "node-agent auth (agent process)"),
    EnvKnob("RLT_SEGMENT_MIN_BYTES", False, "shm threshold (per-process)"),
    EnvKnob("RLT_DISABLE_KERNELS", False, "kernel-probe opt-out (local)"),
    EnvKnob("RLT_DISABLE_NATIVE", False, "native-ext opt-out (local)"),
    EnvKnob("RLT_LORA_BGMV", False,
            "force the multi-LoRA BGMV arm: xla|pallas (resolved once "
            "at engine/worker build; serving actors inherit the local "
            "env, so no strategy bridge)"),
    # -- monitor/prom knobs (telemetry/monitor.py from_env map) ----------
    EnvKnob("RLT_MONITOR_HANG_INTERVALS", False, "stall threshold"),
    EnvKnob("RLT_MONITOR_ABORT_S", False, "hang-abort deadline"),
    EnvKnob("RLT_MONITOR_STRAGGLER_LAG", False, "straggler lag steps"),
    EnvKnob("RLT_MONITOR_DIR", False, "monitor artifact directory"),
    EnvKnob("RLT_PROM_FILE", False, "OpenMetrics textfile path"),
    EnvKnob("RLT_PROM_PORT", False, "OpenMetrics localhost port"),
    # -- entry-point knobs (never reach workers by design) ---------------
    EnvKnob("RLT_DRYRUN_MPMD", False, "graft-entry mpmd flavor gate"),
    # -- SLO & capacity plane (serve entry points + router) --------------
    EnvKnob("RLT_SLO", False, "serve SLO burn-rate evaluator gate"),
    EnvKnob("RLT_CAPACITY", False, "serve capacity/headroom oracle gate"),
    EnvKnob("RLT_TS_INTERVAL_S", False, "time-series store bin width"),
    EnvKnob("RLT_HEADROOM_ROUTING", False,
            "router placement tie-break on reported headroom (resolved "
            "once at router build; router is driver/agent-local)"),
    # -- serving-plane resilience (ISSUE 19) -----------------------------
    EnvKnob("RLT_MIGRATE_ON_DRAIN", True,
            "planned-drain live KV migration gate (0 = recompute "
            "failover only; read by the replica runner, so actor "
            "replicas need the bridge)"),
    EnvKnob("RLT_BROWNOUT", False,
            "router overload brownout ladder gate (resolved once at "
            "router build; router is driver/agent-local)"),
    EnvKnob("RLT_HEDGE", False,
            "client hedged-resubmit gate (ServeClient RetryPolicy; "
            "client-local by definition)"),
    EnvKnob("RLT_RETRY_MAX", False,
            "client retry attempts on typed rejections (client-local)"),
    EnvKnob("RLT_RETRY_BACKOFF_S", False,
            "client retry backoff base seconds (client-local)"),
)


def forwarded_vars() -> Tuple[str, ...]:
    """Names the strategy layer bridges into every worker's env."""
    return tuple(k.name for k in KNOBS if k.forward)


def registered_names() -> Tuple[str, ...]:
    """Every registered knob name (the RLT005 lint contract)."""
    return tuple(k.name for k in KNOBS)

"""Training strategies: the remote-execution lifecycle (the framework's heart).

≙ the reference's L5 plugin layer (``/root/reference/ray_lightning/ray_ddp.py:66-565``,
``ray_horovod.py:35-239``, ``ray_ddp_sharded.py:17-34``): a strategy owns

1. **worker launch** — one actor per TPU host with resource reservation and
   ``init_hook`` (≙ ``_create_worker``/``setup``, ``ray_ddp.py:183-195``);
2. **rendezvous brokering** — driver obtains worker-0's IP + a free port
   *on that node* and broadcasts it as the ``jax.distributed`` coordinator
   (≙ ``_setup_env_vars`` MASTER_ADDR/PORT, ``ray_ddp.py:215-228``);
3. **task shipping** — the (module, datamodule, config, callbacks) package
   is serialized once into the object store and every worker materializes
   its own copy (≙ ``ray.put(model)``, ``ray_ddp.py:339-353``);
4. **the remote loop** — workers run the shared fit loop under a device
   mesh; gradient sync is XLA collectives compiled into the step
   (no process-group objects, no NCCL — SURVEY §2.2);
5. **result recovery** — driver pumps the queue, adopts rank-0's state
   stream/metrics/best-path, tears actors down
   (≙ ``post_dispatch``, ``ray_ddp.py:362-401``).

Flavor map (≙ the reference's three plugins):

* :class:`RayStrategy` — GSPMD data parallel (≙ ``RayPlugin`` DDP).
* :class:`HorovodRayStrategy` — explicit per-device collectives via
  ``shard_map`` + ``lax.pmean`` (≙ ``HorovodRayPlugin``'s ring allreduce).
* :class:`RayShardedStrategy` — ZeRO optimizer/param sharding as
  ``NamedSharding`` annotations (≙ ``RayShardedPlugin``/FairScale OSS).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import random
import shutil
import time
import uuid
import warnings
from typing import Any, Callable, Dict, List, Optional

from ray_lightning_tpu import session as session_mod
from ray_lightning_tpu.cluster import backend as backend_mod
from ray_lightning_tpu.cluster import rpc
from ray_lightning_tpu.cluster.actor import ActorDiedError, RemoteError
from ray_lightning_tpu.core.loop import (
    FitConfig,
    _normalize_megastep,
    _normalize_update_sharding,
    run_eval,
    run_fit,
    run_predict,
)
from ray_lightning_tpu.fault import drain as drain_mod
from ray_lightning_tpu.parallel import env_bus
from ray_lightning_tpu.parallel.overlap import normalize_grad_overlap
from ray_lightning_tpu.fault.drain import PreemptedError
from ray_lightning_tpu.util import process_results
from ray_lightning_tpu.utils.compile_cache import compile_cache_dir

log = logging.getLogger(__name__)

# Distinguishes "no resize happened yet" from "last resize resumed from
# scratch (None)" in the flap guard's progress comparison.
_RESIZE_CKPT_UNSET = object()

__all__ = [
    "TpuStrategy",
    "LocalStrategy",
    "RayStrategy",
    "HorovodRayStrategy",
    "RayShardedStrategy",
    "MpmdStrategy",
    # Reference-name aliases for drop-in familiarity:
    "RayPlugin",
    "HorovodRayPlugin",
    "RayShardedPlugin",
]


# ---------------------------------------------------------------------------
# Worker-side entry (top-level: importable in actor children)
# ---------------------------------------------------------------------------

def _remote_latest_restart_checkpoint(restart_dir: str) -> Dict[str, Any]:
    """Runs on worker 0 (or driver-side on a shared filesystem): newest
    COMPLETE **and verified** restart/drain checkpoint on its node.

    Sharded checkpoints (directories) count only once their META marker
    exists — a crash mid-write must never be resumed from.  Candidates
    are ordered newest-first by completion time (META mtime — drain and
    epoch checkpoints interleave, so name order alone cannot rank them)
    and each is integrity-verified (``sharded_ckpt.verify_checkpoint``):
    a torn or bit-flipped newest checkpoint is WALKED PAST to the
    previous good one instead of bricking every restart attempt.

    Returns ``{"path": newest_verified_or_None, "corrupt": [...]}`` —
    the corrupt list feeds the driver's ``ckpt_corrupt`` telemetry.
    """
    from ray_lightning_tpu.utils.sharded_ckpt import (
        list_restart_candidates,
        verify_checkpoint,
    )

    corrupt: List[Dict[str, Any]] = []
    for _, _, _, path in list_restart_candidates(restart_dir):
        problems = verify_checkpoint(path)
        if not problems:
            return {"path": path, "corrupt": corrupt}
        corrupt.append({"path": path, "problems": problems[:3]})
    return {"path": None, "corrupt": corrupt}


def _remote_find_free_port() -> int:
    """Free port on the *worker's* node (≙ reference ``ray_ddp.py:31-35``,
    executed on worker 0 just like ``_setup_env_vars`` does)."""
    return rpc.find_free_port()


def _execute_remote(task_ref, global_rank: int, queue_handle) -> Dict[str, Any]:
    """Worker-side driver of one training run (≙ ``RayPlugin.execute_remote``,
    reference ``ray_ddp.py:443-523``).

    Order of operations mirrors the reference: install session → join the
    distributed runtime (collective boundary) → build the mesh → run the
    stage → rank 0 returns the heavy result package.
    """
    task = task_ref.get()
    world_size = task["world_size"]

    session_mod.init_session(
        rank=global_rank,
        queue=queue_handle,
        num_workers=world_size,
    )
    try:
        from ray_lightning_tpu.parallel.mesh import (
            MeshSpec,
            bootstrap_distributed,
            build_mesh,
        )

        # Chaos injection point — BEFORE the collective boundary, so a
        # spawn-pinned fault (crash / lose_worker) kills this worker
        # while its peers can still be detected + killed by the driver
        # instead of wedging inside jax.distributed.initialize.
        from ray_lightning_tpu.fault import inject as _chaos

        _chaos.set_rank(global_rank)
        _chaos.fire("spawn", rank=global_rank)

        # ═══ collective boundary (≙ init_process_group, ray_ddp.py:430) ═══
        bootstrap_distributed(
            task.get("coordinator"), world_size, global_rank
        )
        mesh = build_mesh(MeshSpec(task.get("mesh_axes")))

        sess = session_mod.get_session()
        sess.mesh = mesh
        import jax

        sess.local_devices = jax.local_devices()

        kind = task["kind"]
        common = dict(
            module=task["module"],
            datamodule=task["datamodule"],
            config=task["config"],
            global_rank=global_rank,
            world_size=world_size,
            mesh=mesh,
        )
        if kind == "fit":
            try:
                return run_fit(
                    callbacks=task["callbacks"],
                    mode=task["mode"],
                    zero_stage=task["zero_stage"],
                    grad_comm=task.get("grad_comm"),
                    telemetry=task.get("telemetry"),
                    queue=queue_handle,
                    **common,
                )
            except PreemptedError:
                # A drain is an orderly exit, not a crash: the loop
                # already wrote its drain checkpoint and retired the
                # live plane — no flight bundle.
                raise
            except BaseException as err:
                # Crash forensics: persist the flight bundle (spans,
                # step stats, logs, stacks — telemetry/flight_recorder)
                # and announce its path on the queue BEFORE the
                # exception travels back as a bare traceback.  No-op
                # when telemetry is off or no recorder is armed.
                from ray_lightning_tpu.telemetry.flight_recorder import (
                    record_active_crash,
                )

                record_active_crash(err)
                raise
        if kind in ("validation", "test"):
            return run_eval(
                callbacks=task["callbacks"],
                kind=kind,
                mode=task["mode"],
                zero_stage=task["zero_stage"],
                params_stream=task.get("params_stream"),
                ckpt_path=task.get("ckpt_path"),
                telemetry=task.get("telemetry"),
                queue=queue_handle,
                **common,
            )
        if kind == "predict":
            return run_predict(
                zero_stage=task["zero_stage"],
                params_stream=task.get("params_stream"),
                ckpt_path=task.get("ckpt_path"),
                telemetry=task.get("telemetry"),
                **common,
            )
        raise ValueError(f"Unknown stage kind {task['kind']!r}")
    finally:
        session_mod.shutdown_session()
        if world_size > 1:
            # Orderly disconnect from the coordination service — without
            # this, the first worker to exit is seen as "died" and the
            # service fatally terminates its peers mid-teardown.
            try:
                import jax

                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

class TpuStrategy:
    """Base strategy: worker lifecycle + execution loop.

    Constructor signature mirrors ``RayPlugin.__init__`` (reference
    ``ray_ddp.py:118-171``): ``num_workers`` (hosts), per-worker resources,
    ``init_hook``, ``resources_per_worker`` overriding the convenience
    flags.  TPU-specific additions: ``mesh_axes`` (device mesh layout) and
    the compute ``mode``/``zero_stage`` knobs.
    """

    mode: str = "gspmd"
    zero_stage: int = 0
    # Whether this strategy's world may be elastically resized; subclasses
    # with a STRUCTURAL world (MpmdStrategy: the layer split is baked into
    # every stage's program) set False, and the fleet-wide RLT_ELASTIC_*
    # env bus is then ignored instead of crashing their constructors.
    supports_elastic_resize: bool = True

    def __init__(
        self,
        num_workers: int = 1,
        num_cpus_per_worker: int = 1,
        use_tpu: bool = True,
        init_hook: Optional[Callable[[], None]] = None,
        resources_per_worker: Optional[Dict[str, float]] = None,
        backend: Optional[str] = None,
        mesh_axes: Optional[Dict[str, int]] = None,
        env_per_worker: Optional[Dict[str, str]] = None,
        max_restarts: int = 0,
        restart_every_n_epochs: int = 1,
        restart_window_s: float = 3600.0,
        restart_backoff_s: float = 1.0,
        restart_backoff_max_s: float = 60.0,
        grad_comm=None,
        telemetry=None,
        monitor=None,
        megastep=None,
        update_sharding=None,
        grad_overlap_segments=None,
        elastic_min_workers: Optional[int] = None,
        elastic_grow_after_s: Optional[float] = None,
        elastic_capacity_fn: Optional[Callable[[], int]] = None,
    ):
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self.num_workers = num_workers
        self.num_cpus_per_worker = num_cpus_per_worker
        self.use_tpu = use_tpu
        self.init_hook = init_hook
        # resources_per_worker overrides the convenience flags (reference
        # resolution matrix, ray_ddp.py:128-140, tested test_ddp.py:138-176).
        resources = dict(resources_per_worker or {})
        self.num_cpus_per_worker = int(
            resources.pop("CPU", num_cpus_per_worker)
        )
        if "TPU" in resources:
            self.use_tpu = resources.pop("TPU") > 0
        self.additional_resources_per_worker = resources
        self.backend_name = backend
        self.mesh_axes = mesh_axes
        # Gradient-communication config (mode string, dict, or
        # GradCommConfig; None = RLT_GRAD_COMM env bus / full-width).
        # Validated eagerly so a typo'd mode fails at construction, not
        # minutes later on a worker.
        if grad_comm is not None:
            from ray_lightning_tpu.parallel.grad_sync import GradCommConfig

            grad_comm = GradCommConfig.coerce(grad_comm)
        self.grad_comm = grad_comm
        # Telemetry tier/knobs (tier string, dict, or TelemetryConfig;
        # None = RLT_TELEMETRY env bus / cheap default).  Same eager
        # validation discipline as grad_comm: a typo'd tier fails here.
        if telemetry is not None:
            from ray_lightning_tpu.telemetry import TelemetryConfig

            telemetry = TelemetryConfig.coerce(telemetry)
        self.telemetry = telemetry
        # Live-monitor knobs (dict or MonitorConfig; None = RLT_MONITOR_*
        # env bus at fit time).  Validated eagerly like grad_comm, but
        # the RAW value is kept: a dict without heartbeat_s must inherit
        # the telemetry cadence at fit time — coercing it to a frozen
        # MonitorConfig here would bake in the 5s default and make a
        # fast-heartbeat run watchdog at the slow default budget.
        if monitor is not None:
            from ray_lightning_tpu.telemetry import MonitorConfig

            MonitorConfig.coerce(monitor)
        self.monitor = monitor
        # Megastep stride length (core/loop.py megastep mode: K fused
        # micro-steps per jitted dispatch).  None defers to the
        # Trainer's knob / the RLT_MEGASTEP env bus / "auto"; validated
        # eagerly like every other strategy knob.
        _normalize_megastep(megastep)
        self.megastep = megastep
        # Cross-replica sharded weight update (core/loop.py
        # update_sharding mode).  None defers to the Trainer's knob /
        # the RLT_UPDATE_SHARDING env bus / "auto"; validated eagerly
        # like every other strategy knob.
        _normalize_update_sharding(update_sharding)
        self.update_sharding = update_sharding
        # Backward-overlapped grad sync (core/loop.py + parallel/
        # overlap.py: G trunk segments, custom_vjp grad taps).  None
        # defers to the Trainer's knob / the RLT_GRAD_OVERLAP env bus /
        # off; validated eagerly like every other strategy knob.
        normalize_grad_overlap(grad_overlap_segments)
        self.grad_overlap_segments = grad_overlap_segments
        self.env_per_worker = dict(env_per_worker or {})
        # Persistent XLA compilation cache: workers receive the
        # directory (JAX's own variable where set, else the fixed
        # in-checkout path — utils/compile_cache.py) BEFORE their first
        # jax import — exactly the pre-exec env contract actors already
        # provide (≙ the reference's env bus, ray_ddp.py:215-228).  It
        # amortizes the step compile across worker respawns (elastic
        # restarts), tuner trials, and sessions.
        self.env_per_worker.setdefault(
            "JAX_COMPILATION_CACHE_DIR", compile_cache_dir()
        )
        # Worker env bus: every forward-marked knob in the central
        # registry (parallel/env_bus.py) rides the same bridge the
        # compile-cache directory does — remote workers (node agents, Ray
        # runtime_env) inherit the AGENT's env, not the driver's, so
        # without this a driver-side RLT_GRAD_COMM would silently
        # resolve to full-width on exactly the multi-host topology
        # compression targets.  The knob list lives in ONE place; the
        # rlt_lint RLT005 rule cross-checks every env read against it.
        for var in env_bus.forwarded_vars():
            val = os.environ.get(var)
            if val is not None:
                self.env_per_worker.setdefault(var, val)
        # Elastic fault tolerance (extends the reference, which only
        # fails fast — SURVEY §5 "failure detection: ABSENT"): on worker
        # death during fit, respawn the worker set up to ``max_restarts``
        # times and resume from the newest restart checkpoint.
        if max_restarts < 0:
            raise ValueError("max_restarts must be >= 0")
        if restart_every_n_epochs < 1:
            raise ValueError("restart_every_n_epochs must be >= 1")
        if restart_window_s <= 0:
            raise ValueError("restart_window_s must be > 0")
        if restart_backoff_s < 0 or restart_backoff_max_s < 0:
            raise ValueError("restart backoff times must be >= 0")
        self.max_restarts = max_restarts
        self.restart_every_n_epochs = restart_every_n_epochs
        # Restart governance (docs/FAULT_TOLERANCE.md): the failure
        # budget is a SLIDING WINDOW (max_restarts per restart_window_s),
        # not a per-fit lifetime count — a week-long fit may absorb many
        # spread-out failures, while a flapping host still exhausts the
        # budget within the hour it flaps.  Respawns back off
        # exponentially with jitter so a correlated outage doesn't
        # hammer the scheduler in lockstep.
        self.restart_window_s = restart_window_s
        self.restart_backoff_s = restart_backoff_s
        self.restart_backoff_max_s = restart_backoff_max_s
        self.restarts_used = 0
        # Preemption drains recover WITHOUT consuming the failure budget
        # (they are the normal case, not an error — Podracer); counted
        # separately so dashboards can tell churn from failure.
        self.preempt_restarts_used = 0
        # Elastic world sizing (docs/FAULT_TOLERANCE.md "Elastic
        # resume"): with ``elastic_min_workers`` set, the governor may
        # deliberately respawn with M < N SURVIVORS when the fleet lost
        # capacity — a preempted host becomes a shrink, not a wait —
        # and grows back once capacity has been available again for
        # ``elastic_grow_after_s`` seconds (a deliberate drain at the
        # next sync boundary, budget-free).  Capacity comes from
        # ``elastic_capacity_fn`` (a fleet-API probe in production;
        # default: the chaos plane's lost-worker markers, so the whole
        # path is deterministically testable via ``lose_worker@...``).
        if elastic_min_workers is None and self.supports_elastic_resize:
            env = os.environ.get("RLT_ELASTIC_MIN_WORKERS")
            elastic_min_workers = int(env) if env else None
            if elastic_min_workers is not None:
                # The env bus serves fleets of MIXED sizes: clamp into
                # [1, num_workers] rather than reject, so one exported
                # floor never crashes a strategy it doesn't fit.
                elastic_min_workers = min(
                    max(elastic_min_workers, 1), num_workers
                )
        if elastic_grow_after_s is None and self.supports_elastic_resize:
            env = os.environ.get("RLT_ELASTIC_GROW_AFTER_S")
            elastic_grow_after_s = float(env) if env else None
        if elastic_min_workers is not None and not (
                1 <= elastic_min_workers <= num_workers):
            raise ValueError(
                f"elastic_min_workers must be in [1, num_workers="
                f"{num_workers}], got {elastic_min_workers}"
            )
        if elastic_grow_after_s is not None and elastic_grow_after_s < 0:
            raise ValueError("elastic_grow_after_s must be >= 0")
        self.elastic_min_workers = elastic_min_workers
        self.elastic_grow_after_s = elastic_grow_after_s
        self.elastic_capacity_fn = elastic_capacity_fn
        # The CURRENT world size: num_workers is the requested ceiling,
        # active_workers what the governor is actually running.
        self.active_workers = num_workers
        self.resizes_used = 0
        self.last_resize_recover_s: Optional[float] = None
        # Flap-guard progress proxy: a SENTINEL, not None — the first
        # shrink of a fit with no checkpoint yet (resume None) must not
        # pre-seed the streak.
        self._last_resize_ckpt: Any = _RESIZE_CKPT_UNSET
        self._resize_streak = 0
        self._grow_pending = False
        self._capacity_ok_since: Optional[float] = None
        # Recovery events of the fit in flight (backoff delays, restart
        # attempts, checkpoint-corruption fallbacks, preempt restarts):
        # seeded into each attempt's RunMonitor so the final
        # ``trainer.monitor_report`` tells the whole story across
        # respawns, not just the last attempt's.
        self.recovery_events: List[Dict[str, Any]] = []
        self._carried_events: List[Dict[str, Any]] = []
        self._last_monitor = None
        self._drain_broadcast = False
        self._drain_broadcast_at = 0.0

        self._backend: Optional[backend_mod.ClusterBackend] = None
        self._workers: list = []

    # -- rank/world properties (driver side; ≙ ray_ddp.py:525-541) ----------
    @property
    def world_size(self) -> int:
        # The governor's CURRENT size: equals num_workers unless an
        # elastic resize shrank (or re-grew) the fleet mid-fit.
        return self.active_workers

    @property
    def global_rank(self) -> int:
        return 0  # the driver never trains (≙ _is_remote=False branch)

    @property
    def is_distributed(self) -> bool:
        return True

    # -- lifecycle ----------------------------------------------------------
    def setup(self, trainer) -> None:
        """Create workers + run init_hook (≙ ``RayPlugin.setup``,
        reference ``ray_ddp.py:191-195``)."""
        if self._workers:
            return
        # A backend *instance* stays owned by the caller (it may span
        # several trainers); teardown only shuts down backends we built.
        self._owns_backend = not isinstance(
            self.backend_name, backend_mod.ClusterBackend
        )
        self._backend = backend_mod.get_backend(self.backend_name)
        self._spawn_workers()

    def _spawn_workers(self) -> None:
        # Generation-unique names: a Ray named actor is deregistered
        # asynchronously after ray.kill, so a respawn reusing the same
        # name races the teardown.
        gen = getattr(self, "_spawn_generation", 0)
        self._spawn_generation = gen + 1
        suffix = "" if gen == 0 else f"-r{gen}"
        for i in range(self.active_workers):
            worker = self._backend.create_actor(
                name=f"rlt-worker-{i}{suffix}",
                env=self.env_per_worker or None,
                num_cpus=self.num_cpus_per_worker,
                resources=self.additional_resources_per_worker or None,
            )
            self._workers.append(worker)
        if self.use_tpu:
            self._partition_host_chips()
        if self.init_hook is not None:
            futures = [
                w.submit(self.init_hook) for w in self._workers
            ]
            for f in futures:
                f.result()

    def _partition_host_chips(self) -> None:
        """Split ``TPU_VISIBLE_CHIPS`` between co-located workers.

        ≙ reference ``_setup_env_vars``'s per-node device-visibility push
        (``ray_ddp.py:230-274``) with TPU partition semantics (each PJRT
        process must own its chips exclusively — see
        :func:`..mesh.partition_host_chips`).  Pushed BEFORE the worker's
        first jax import (workers import jax lazily when the task runs),
        so visibility is in place at PJRT init.  Sole-owner hosts are
        left untouched.
        """
        from ray_lightning_tpu.parallel.mesh import partition_host_chips

        ips = [w.get_node_ip() for w in self._workers]
        chips_per_host = int(os.environ.get("RLT_TPU_CHIPS_PER_HOST", 4))
        try:
            chip_map = partition_host_chips(ips, chips_per_host)
        except ValueError as err:
            # CPU-simulated meshes co-locate freely; on real TPU an
            # un-partitionable layout will fail at PJRT init anyway, with
            # this warning naming the cause first.
            warnings.warn(f"TPU chip partitioning skipped: {err}")
            return
        for rank, worker in enumerate(self._workers):
            chips = chip_map.get(rank)
            if chips is not None:
                worker.set_env_vars({"TPU_VISIBLE_CHIPS": chips})

    def _kill_workers(self, timeout: Optional[float] = None,
                      why: str = "teardown") -> None:
        """Kill every current worker.  Failures are expected (some are
        already dead) but never SILENT: an unkillable worker is a zombie
        holding TPU chips, and the debug log must say which rank."""
        for w in self._workers:
            w.request_exit()
        for rank, w in enumerate(self._workers):
            try:
                if timeout is None:
                    w.kill()
                else:
                    w.kill(timeout=timeout)
            except Exception as e:  # noqa: BLE001 - already-dead is fine
                log.debug(
                    "%s: kill of worker rank %d (%s) failed: %r",
                    why, rank, getattr(w, "name", "?"), e,
                )
        # Crashed/killed workers (kill -9, monitor aborts, respawns)
        # can't run their own teardown: sweep their orphaned shared-
        # memory segments here so elastic restarts don't leak tmpfs
        # fit-over-fit (ProcessActor.kill sweeps too; this covers
        # backend adapters whose kill path never reaches it).
        try:
            from ray_lightning_tpu.cluster.shm import sweep_stale_segments

            swept = sweep_stale_segments()
            if swept:
                log.debug("%s: swept %d stale shm segments", why, swept)
        except Exception as e:  # noqa: BLE001 - janitorial only
            log.debug("%s: shm sweep failed: %r", why, e)

    def _respawn_workers(self) -> None:
        """Kill every current worker (peers of a dead one may be stuck in
        a collective forever) and start a fresh set."""
        self._kill_workers(why="respawn")
        self._workers = []
        self._spawn_workers()

    def _broker_coordinator(self) -> Optional[str]:
        """Worker-0-node coordinator address (≙ MASTER_ADDR/PORT brokering,
        reference ``ray_ddp.py:215-228``)."""
        if self.active_workers <= 1:
            return None
        if isinstance(self._backend, backend_mod.LocalBackend):
            # All actors share this host; loopback is always routable
            # (the NIC address may be NAT'd/unroutable in sandboxes).
            ip = "127.0.0.1"
        else:
            ip = self._workers[0].get_node_ip()
        port = self._workers[0].execute(_remote_find_free_port)
        return f"{ip}:{port}"

    def run(
        self,
        kind: str,
        module,
        datamodule,
        config: FitConfig,
        callbacks: List,
        trainer=None,
        params_stream: Optional[bytes] = None,
        ckpt_path: Optional[str] = None,
    ) -> List[Dict[str, Any]]:
        """The execution loop (≙ ``RayPlugin.execution_loop``,
        reference ``ray_ddp.py:317-360``): ship → submit → pump → collect.

        With ``max_restarts > 0`` and ``kind="fit"``, worker death does not
        crash the fit: the whole worker set is respawned — after an
        exponential, jittered backoff, within a sliding per-
        ``restart_window_s`` failure budget — and training resumes from
        the newest VERIFIED restart checkpoint (corrupt ones are walked
        past; at most ``restart_every_n_epochs`` epochs of work are
        lost).  A preemption drain (:class:`PreemptedError`) restarts
        from its step-granular drain checkpoint WITHOUT consuming the
        failure budget — unless the drain request came from the driver
        itself (the driver is being preempted too), in which case it
        re-raises cleanly with the checkpoint named.
        """
        assert self._backend is not None, "setup() must run first"
        if config.megastep is None and self.megastep is not None:
            # The strategy's megastep knob fills the unset Trainer
            # default (an explicit Trainer(megastep=...) always wins).
            config = dataclasses.replace(config, megastep=self.megastep)
        if (config.update_sharding is None
                and self.update_sharding is not None):
            config = dataclasses.replace(
                config, update_sharding=self.update_sharding
            )
        if (config.grad_overlap_segments is None
                and self.grad_overlap_segments is not None):
            config = dataclasses.replace(
                config, grad_overlap_segments=self.grad_overlap_segments
            )
        elastic = self.max_restarts > 0 and kind == "fit"
        if elastic and config.restart_every_n_epochs is None:
            # The strategy's cadence fills the unset default wherever the
            # checkpoints land (caller-provided restart_dir included); an
            # explicit Trainer cadence always wins.
            config = dataclasses.replace(
                config, restart_every_n_epochs=self.restart_every_n_epochs
            )
        restart_dir = None
        if elastic and config.restart_dir is None:
            restart_dir = os.path.join(
                config.default_root_dir,
                f".rlt-restart-{uuid.uuid4().hex[:8]}",
            )
            config = dataclasses.replace(config, restart_dir=restart_dir)
        fail_times: List[float] = []   # budget-consuming failures
        last_preempt_step = -1
        preempt_streak = 0
        # Driver-side preemption: SIGTERM/SIGINT on the DRIVER while it
        # pumps results is forwarded to every worker over the control
        # lane (see _pump_tick), so the fleet drains as one.
        drain_installed = False
        preserve_scratch = False  # a raised PreemptedError names its
        # drain checkpoint — deleting the scratch dir would orphan it
        if kind == "fit":
            # Per-FIT recovery state: an eval/predict after a recovered
            # fit must not wipe the fit's recovery record.
            self.recovery_events = []
            self._carried_events = []
            self._last_monitor = None
            self._drain_broadcast = False
            self._drain_broadcast_at = 0.0
            self._grow_pending = False
            self._capacity_ok_since = None
            self._resize_streak = 0
            self._last_resize_ckpt = _RESIZE_CKPT_UNSET
            drain_mod.reset_drain()
            drain_mod.set_fit_active(True)
            drain_installed = drain_mod.install_signal_handlers()
        try:
            while True:
                try:
                    return self._run_once(
                        kind, module, datamodule, config, callbacks,
                        trainer=trainer, params_stream=params_stream,
                        ckpt_path=ckpt_path,
                    )
                except PreemptedError as err:
                    self._capture_attempt_events()
                    t_recover = time.monotonic()
                    grow_drain = self._grow_pending
                    self._grow_pending = False
                    if (not elastic or self._drain_broadcast
                            or drain_mod.drain_requested()):
                        # No elastic recovery, or the DRIVER itself is
                        # being preempted: a clean resumable raise — the
                        # error names the drain checkpoint.
                        preserve_scratch = err.checkpoint is not None
                        raise
                    # Flap guard: consecutive preemption recoveries that
                    # make no forward progress mean the host/quota is
                    # flapping — budget-free must not mean infinite.
                    # Grow drains ride the same guard: a grow that never
                    # advances the step cannot keep draining the fit.
                    step = int(getattr(err, "step", 0) or 0)
                    preempt_streak = (
                        preempt_streak + 1 if step <= last_preempt_step
                        else 0
                    )
                    last_preempt_step = step
                    if preempt_streak >= 2:
                        preserve_scratch = err.checkpoint is not None
                        raise
                    self.preempt_restarts_used += 1
                    # World sizing for the next attempt: a preemption
                    # may shrink the fleet (capacity lost with the
                    # drained host) or — on a deliberate grow drain —
                    # re-expand toward num_workers.
                    target, rejected = self._elastic_resize_decision()
                    if rejected:
                        preserve_scratch = err.checkpoint is not None
                        self._record_resize_rejected(target)
                        raise
                    # Elastic fits always have restart_dir set, and the
                    # drain checkpoint lands inside it — so verified
                    # discovery alone decides the resume point (the
                    # error's own checkpoint claim is the same path,
                    # already verified or rejected by discovery).
                    info = self._discover_resume(config)
                    resume = info["path"]
                    self._record_recovery(
                        "preempt_restart",
                        message=(
                            f"preemption drain at micro_step {step} "
                            f"({err.reason or 'requested'}); respawning "
                            f"without consuming the restart budget"
                        ),
                        ckpt=resume or "",
                    )
                    warnings.warn(
                        f"Preemption drain ({err}); elastic respawn "
                        f"(budget untouched), resuming from "
                        f"{resume or 'scratch'}."
                    )
                    self._respawn_resized(
                        target, t_recover, resume,
                        why="grow-back drain" if grow_drain
                        else "preemption",
                    )
                    if resume is not None:
                        config = dataclasses.replace(
                            config, resume_from_checkpoint=resume
                        )
                # Retry ONLY process death (≙ preemption/OOM).  A Python
                # exception in user code (RemoteError) is deterministic —
                # respawning would retrain epochs just to re-raise it.
                except ActorDiedError as err:
                    self._capture_attempt_events()
                    # Whatever follows, a raise or a respawn, this set
                    # is done, and the dead rank's peers are wedged in
                    # its collective, where neither the exit message
                    # nor SIGTERM (the drain handler takes it) ends
                    # them: the monitor abort's grace, not kill()'s 5 s
                    # twice over for each in turn at the teardown.
                    self._kill_workers(timeout=1.0, why="worker-death")
                    # A death supersedes any in-flight grow drain (the
                    # restart below is itself a grow opportunity); a
                    # stale flag would mislabel the NEXT preemption as
                    # a grow-back drain.
                    self._grow_pending = False
                    if not elastic:
                        raise
                    t_recover = time.monotonic()
                    target, rejected = self._elastic_resize_decision()
                    if rejected:
                        self._record_resize_rejected(target)
                        err.enrich(note=(
                            f"fleet capacity {target} below "
                            f"elastic_min_workers="
                            f"{self.elastic_min_workers} — shrink "
                            "rejected, restart abandoned"
                        ))
                        raise
                    if (target is not None
                            and target < self.active_workers):
                        # Capacity loss EXPLAINS the death: a preempted
                        # host is fleet churn, not a failure — respawn
                        # with the M survivors budget-free (like
                        # preempt_restarts), flap-guarded by forward
                        # progress of the resume point below.  Kill the
                        # doomed set FIRST: the dead rank's peers may be
                        # wedged inside the collective boundary, and
                        # discovery asking a wedged worker 0 would wait
                        # out its entire rendezvous timeout.
                        self._kill_workers(why="elastic-shrink")
                        info = self._discover_resume(config)
                        resume = info["path"]
                        self._resize_streak = (
                            self._resize_streak + 1
                            if resume == self._last_resize_ckpt else 0
                        )
                        self._last_resize_ckpt = resume
                        if self._resize_streak >= 2:
                            err.enrich(note=(
                                "no forward progress across "
                                "consecutive elastic resizes — flap "
                                "guard stopped the shrink loop"
                            ))
                            raise
                        warnings.warn(
                            f"Worker loss with reduced fleet capacity "
                            f"({err}); elastic shrink to {target} "
                            f"survivors (budget untouched), resuming "
                            f"from {resume or 'scratch'}."
                        )
                        self._respawn_resized(
                            target, t_recover, resume,
                            why="capacity loss",
                        )
                        if resume is not None:
                            config = dataclasses.replace(
                                config, resume_from_checkpoint=resume
                            )
                        continue
                    now = time.monotonic()
                    fail_times[:] = [
                        t for t in fail_times
                        if now - t <= self.restart_window_s
                    ]
                    if len(fail_times) >= self.max_restarts:
                        err.enrich(note=(
                            f"restart budget exhausted: "
                            f"{self.max_restarts} failure(s) within "
                            f"{self.restart_window_s:.0f}s"
                        ))
                        raise
                    fail_times.append(now)
                    self.restarts_used += 1
                    # Backoff exponent = failures currently IN the
                    # window (same clock as the budget): two deaths a
                    # day apart each wait the base delay; a flapping
                    # host doubles up within its hour.
                    fail_streak = len(fail_times)
                    delay = self._backoff_delay(fail_streak)
                    if delay > 0:
                        self._record_recovery(
                            "backoff", delay_s=round(delay, 3),
                            attempt=fail_streak,
                            message=(
                                f"waiting {delay:.2f}s before respawn "
                                f"#{fail_streak} (exponential backoff "
                                f"with jitter)"
                            ),
                        )
                        time.sleep(delay)
                    t_recover = time.monotonic()
                    # A restart is also a grow OPPORTUNITY: capacity
                    # that returned while running shrunk re-expands
                    # here without a deliberate grow drain.  The resize
                    # event is booked AFTER discovery so its
                    # recover_s/ckpt reflect the real detour.
                    old_active = self.active_workers
                    grew = target is not None and target != old_active
                    if grew:
                        self.active_workers = int(target)
                    self._respawn_workers()
                    info = self._discover_resume(config)
                    resume = info["path"]
                    if grew:
                        self._record_resize(
                            old_active, int(target), t_recover, resume,
                            why="restart",
                        )
                    self._record_recovery(
                        "elastic_restart", attempt=fail_streak,
                        recover_s=round(time.monotonic() - t_recover, 3),
                        ckpt=resume or "",
                        message=(
                            f"worker failure; elastic restart "
                            f"{len(fail_times)}/{self.max_restarts} in "
                            f"window, resuming from "
                            f"{resume or 'scratch'}"
                        ),
                    )
                    warnings.warn(
                        f"Worker failure ({err}); elastic restart "
                        f"{len(fail_times)}/{self.max_restarts} (window "
                        f"{self.restart_window_s:.0f}s), resuming from "
                        f"{resume or 'scratch'}."
                    )
                    if resume is not None:
                        config = dataclasses.replace(
                            config, resume_from_checkpoint=resume
                        )
        finally:
            if drain_installed:
                drain_mod.uninstall_signal_handlers()
            if kind == "fit":
                drain_mod.set_fit_active(False)
            # The scratch dir is uuid-named and unreachable for manual
            # resume; reclaim it on failure too, not just success —
            # EXCEPT when a raised PreemptedError names a drain
            # checkpoint inside it (the resumable exit's whole value).
            if restart_dir is not None and not preserve_scratch:
                shutil.rmtree(restart_dir, ignore_errors=True)

    def _latest_restart_checkpoint(self, restart_dir) -> Dict[str, Any]:
        """Newest VERIFIED restart/drain checkpoint, looked up ON WORKER
        0's node — the writer's filesystem (restart_dir must be shared
        storage for multi-node elastic recovery, the same assumption the
        reference makes for ModelCheckpoint files, ``ray_ddp.py:
        496-499``).  Falls back to a driver-local scan (valid on shared
        storage and the single-host backend) when worker 0 cannot
        answer."""
        if restart_dir is None:
            return {"path": None, "corrupt": []}
        if self._workers:
            try:
                return self._workers[0].execute(
                    _remote_latest_restart_checkpoint, restart_dir
                )
            except (ActorDiedError, RemoteError):
                pass
        return _remote_latest_restart_checkpoint(restart_dir)

    def _discover_resume(self, config: FitConfig) -> Dict[str, Any]:
        """Restart discovery + the ``ckpt_corrupt`` telemetry promise:
        every checkpoint the walk-back skipped becomes a loud event (and
        a warning) — silent fallback would hide data-eating storage."""
        info = self._latest_restart_checkpoint(config.restart_dir)
        for item in info.get("corrupt", []):
            problems = "; ".join(str(p) for p in item.get("problems", []))
            self._record_recovery(
                "ckpt_corrupt", ckpt=item.get("path", ""),
                message=(
                    f"checkpoint failed verification, falling back to "
                    f"an older one: {problems}"
                ),
            )
            warnings.warn(
                f"corrupt restart checkpoint skipped: "
                f"{item.get('path')} ({problems})"
            )
        return info

    # -- recovery bookkeeping ------------------------------------------------
    def _record_recovery(self, kind: str, **fields: Any) -> None:
        """A schema-shaped recovery event, kept on the strategy AND
        seeded into the next attempt's RunMonitor, so the final
        ``trainer.monitor_report`` narrates the whole fit across
        respawns (backoff delays included — the acceptance criterion)."""
        from ray_lightning_tpu.telemetry.monitor import make_event

        ev = make_event(kind, -1, **fields)
        self.recovery_events.append(ev)
        self._carried_events.append(ev)

    def _capture_attempt_events(self) -> None:
        """Fold the failed attempt's monitor record (stalls, dumps,
        aborts, crashes) into the carried history so the NEXT attempt's
        monitor — and thus the final report — keeps it."""
        if self._last_monitor is not None:
            self._carried_events = list(self._last_monitor.events)
            self._last_monitor = None

    def _backoff_delay(self, streak: int) -> float:
        """Exponential backoff with jitter: base × 2^(streak-1), capped,
        plus up to +25% jitter so a correlated fleet outage doesn't
        respawn every strategy in lockstep."""
        if self.restart_backoff_s <= 0:
            return 0.0
        base = min(
            self.restart_backoff_s * (2 ** max(streak - 1, 0)),
            self.restart_backoff_max_s,
        )
        return base * (1.0 + 0.25 * random.random())

    # -- elastic world sizing (shrink/grow governance) -----------------------
    def _fleet_capacity(self) -> int:
        """Workers the fleet can currently host.  Production installs
        pass ``elastic_capacity_fn`` (a fleet-API probe); the default
        reads the chaos plane's lost-worker markers
        (``fault.inject.lost_worker_count``) so a ``lose_worker@...``
        fault drives the shrink/grow path deterministically."""
        if self.elastic_capacity_fn is not None:
            return int(self.elastic_capacity_fn())
        from ray_lightning_tpu.fault import inject

        return self.num_workers - inject.lost_worker_count()

    def _elastic_resize_decision(self):
        """``(target_world, rejected)``: the size the next attempt
        should run at.  ``target_world`` is ``None`` when elastic
        sizing is off (``elastic_min_workers`` unset — fixed-size
        governance, the pre-elastic behavior); ``rejected`` flags
        capacity below the floor (the caller raises instead of
        training a crippled fleet)."""
        if self.elastic_min_workers is None:
            return None, False
        target = max(min(self._fleet_capacity(), self.num_workers), 0)
        if target < self.elastic_min_workers:
            return target, True
        return target, False

    def _record_resize_rejected(self, target: int) -> None:
        self._record_recovery(
            "resize_rejected",
            old_world=self.active_workers, new_world=target,
            message=(
                f"fleet capacity {target} below elastic_min_workers="
                f"{self.elastic_min_workers}; shrink rejected"
            ),
        )

    def _respawn_resized(self, target: Optional[int], t_recover: float,
                         resume: Optional[str], why: str) -> None:
        """Respawn the worker set, applying an elastic resize when
        ``target`` differs from the active size."""
        old = self.active_workers
        changed = target is not None and target != old
        if changed:
            self.active_workers = int(target)
        self._respawn_workers()
        if changed:
            self._record_resize(old, int(target), t_recover, resume, why)

    def _record_resize(self, old: int, new: int, t_recover: float,
                       resume: Optional[str], why: str) -> None:
        """Book one applied resize: the ``resize`` event (old/new world
        + recover_s) flows through the schema gate into
        ``trainer.monitor_report`` / OpenMetrics / ``rlt_top``, and any
        gang packer holding this trial's sub-mesh is notified so the
        freed devices can host other trials."""
        recover_s = round(time.monotonic() - t_recover, 3)
        self.resizes_used += 1
        self.last_resize_recover_s = recover_s
        self._record_recovery(
            "resize", old_world=old, new_world=new,
            recover_s=recover_s, ckpt=resume or "",
            message=(
                f"elastic resize: world {old} → {new} ({why}); "
                f"recovered in {recover_s}s"
            ),
        )
        warnings.warn(
            f"elastic resize: world {old} → {new} ({why})"
        )
        self._notify_packer_resize(old, new)

    def _notify_packer_resize(self, old: int, new: int) -> None:
        """Gang-packing hook: a trial running inside ``tune_run``'s
        fleet packer frees (or reclaims) sub-mesh devices when its
        governor resizes — best-effort, never costs the restart."""
        try:
            from ray_lightning_tpu.tuning import session as trial_session

            trial_session.notify_world_resize(old, new)
        except Exception as e:  # noqa: BLE001 - observer only
            log.debug("gang-packer resize notify failed: %r", e)

    def _maybe_request_grow(self) -> None:
        """Grow-back arming, run from the result-pump tick: when the
        fit runs below ``num_workers`` and capacity has been back for
        ``elastic_grow_after_s``, request a fleet drain — the resulting
        ``PreemptedError`` respawns budget-free at the larger size from
        the step-granular drain checkpoint."""
        if (self.elastic_min_workers is None
                or self.elastic_grow_after_s is None
                or self._grow_pending
                or self.active_workers >= self.num_workers):
            return
        cap = min(self._fleet_capacity(), self.num_workers)
        now = time.monotonic()
        if cap <= self.active_workers:
            self._capacity_ok_since = None
            return
        if self._capacity_ok_since is None:
            self._capacity_ok_since = now
            return
        if now - self._capacity_ok_since < self.elastic_grow_after_s:
            return
        self._grow_pending = True
        self._capacity_ok_since = None
        warnings.warn(
            f"fleet capacity returned ({cap} > {self.active_workers} "
            "active); draining to grow the worker set back"
        )
        delivered = 0
        for rank, w in enumerate(self._workers):
            request = getattr(w, "request_drain", None)
            if request is None:
                continue
            try:
                request(wait=False)
                delivered += 1
            except Exception as e:  # noqa: BLE001 - a dead worker
                # surfaces through the pump anyway
                log.debug("grow drain to rank %d failed: %r", rank, e)
        if delivered == 0:
            # Nobody heard the drain (backend without the control lane,
            # or every worker mid-death): a pending flag with no drain
            # coming would disarm grow-back for the rest of the fit.
            self._grow_pending = False

    def _maybe_broadcast_drain(self) -> None:
        """Driver-side preemption fan-out: the signal handler only sets
        a flag (no I/O in handlers); the pump tick turns it into one
        control-lane drain request per worker, fire-and-forget.

        RE-SENT every couple of seconds while the drain is pending: a
        worker still inside fit setup when the first request lands
        clears its process-wide flag at ``run_fit`` start (the inline-
        reuse reset), so a one-shot broadcast could be silently
        swallowed and the fleet would train through its grace window.
        ``request_drain`` is idempotent worker-side, so repeats are
        free."""
        if not drain_mod.drain_requested():
            return
        now = time.monotonic()
        if (self._drain_broadcast
                and now - self._drain_broadcast_at < 2.0):
            return
        if not self._drain_broadcast:
            warnings.warn(
                "drain requested on the driver — forwarding to workers"
            )
        self._drain_broadcast = True
        self._drain_broadcast_at = now
        for rank, w in enumerate(self._workers):
            request = getattr(w, "request_drain", None)
            if request is None:
                continue
            try:
                request(wait=False)
            except Exception as e:  # noqa: BLE001 - a dead worker can't
                # drain; its death surfaces through the pump anyway.
                log.debug(
                    "drain forward to rank %d failed: %r", rank, e
                )

    def _run_once(
        self,
        kind: str,
        module,
        datamodule,
        config: FitConfig,
        callbacks: List,
        trainer=None,
        params_stream: Optional[bytes] = None,
        ckpt_path: Optional[str] = None,
    ) -> List[Dict[str, Any]]:
        coordinator = self._broker_coordinator()
        task = {
            "kind": kind,
            "module": module,
            "datamodule": datamodule,
            "config": config,
            "callbacks": callbacks,
            "world_size": self.active_workers,
            "coordinator": coordinator,
            "mesh_axes": self.mesh_axes,
            "mode": self.mode,
            "zero_stage": self.zero_stage,
            "grad_comm": self.grad_comm,
            "telemetry": self.telemetry,
            "params_stream": params_stream,
            "ckpt_path": ckpt_path,
        }
        # Serialize ONCE; each worker materializes its own copy
        # (≙ ray.put(model), ray_ddp.py:339-342).
        task_ref = self._backend.put(task)
        queue = self._backend.create_queue()
        monitor = self._build_monitor(kind, config, trainer)
        futures = []
        try:
            futures = [
                w.submit(_execute_remote, task_ref, rank, queue.handle)
                for rank, w in enumerate(self._workers)
            ]
            on_item = getattr(trainer, "_on_stream_item", None)

            def _tick() -> None:
                # Driver-preemption fan-out rides the pump (signal
                # handlers must not do socket I/O), then the elastic
                # grow-back arming, then the watchdog.
                if kind == "fit":
                    self._maybe_broadcast_drain()
                    self._maybe_request_grow()
                if monitor is not None:
                    monitor.tick()

            results = process_results(
                futures, queue, on_item=on_item,
                on_tick=(
                    _tick if (monitor is not None or kind == "fit")
                    else None
                ),
            )
        except (ActorDiedError, RemoteError) as err:
            self._enrich_failure(err, futures, monitor)
            raise
        finally:
            if monitor is not None:
                monitor.finalize()
                adopt = getattr(trainer, "_adopt_monitor", None)
                if adopt is not None:
                    adopt(monitor)
            queue.shutdown()
            # Segment-backed task payloads are per-fit; without this,
            # repeated fits on one backend (PBT) leak tmpfs ∝ fits × size.
            task_ref.release()
        return results

    # -- live monitoring (telemetry/monitor.py) -----------------------------
    def _build_monitor(self, kind: str, config: FitConfig, trainer):
        """A RunMonitor for fit stages at enabled telemetry tiers —
        ``telemetry="off"`` installs no monitor at all."""
        if kind != "fit":
            return None
        from ray_lightning_tpu.telemetry import (
            MonitorConfig,
            RunMonitor,
            TelemetryConfig,
        )

        tel_cfg = TelemetryConfig.coerce(self.telemetry)
        if tel_cfg.tier == "off" or tel_cfg.heartbeat_s <= 0:
            return None
        mon_cfg = MonitorConfig.coerce(
            self.monitor, heartbeat_s=tel_cfg.heartbeat_s
        )
        if mon_cfg.out_dir is None:
            mon_cfg = dataclasses.replace(
                mon_cfg,
                out_dir=tel_cfg.export_dir or os.path.join(
                    config.default_root_dir, "telemetry"
                ),
            )
        monitor = RunMonitor(
            mon_cfg,
            world_size=self.active_workers,
            dump_cb=self._dump_rank_stacks,
            abort_cb=self._abort_workers,
        )
        # Seed the attempt's monitor with the recovery history so far
        # (previous attempts' stalls/aborts/crashes + the strategy's
        # backoff/restart/ckpt_corrupt events): the LAST adopted report
        # is what lands in trainer.monitor_report, and it must narrate
        # the whole fit, not just the surviving attempt.
        for ev in self._carried_events:
            monitor._record_event(ev)
        self._last_monitor = monitor
        attach = getattr(trainer, "_attach_monitor", None)
        if attach is not None:
            attach(monitor)
        return monitor

    def _dump_rank_stacks(self, rank: int):
        """Monitor dump hook: out-of-band py-stack + device-memory dump
        of one worker (served mid-call via the actor control lane).
        Backends whose workers lack the lane (the Ray adapter) degrade
        to a clear error event instead of a puzzling AttributeError."""
        worker = self._workers[rank]
        dump = getattr(worker, "dump_stacks", None)
        if dump is None:
            raise RuntimeError(
                f"{type(worker).__name__} has no control lane — "
                "out-of-band stack dumps need ProcessActor workers "
                "(use Ray's py-spy tooling on Ray clusters)"
            )
        return dump()

    def _abort_workers(self, reason: str) -> None:
        """Monitor abort hook: kill the worker set so the pump's futures
        fail instead of waiting on a hung collective forever.  With
        ``max_restarts`` set, the resulting ActorDiedError feeds the
        ELASTIC path — a wedged collective becomes a restart, not a
        dead fit."""
        warnings.warn(f"RunMonitor abort: {reason} — killing workers")
        self._kill_workers(timeout=1.0, why="monitor-abort")

    def _enrich_failure(self, err, futures, monitor) -> None:
        """Make a worker-death report say when/how the rank died: rank
        (from the failed future), exit code (agent/subprocess poll),
        last-heartbeat age and flight-bundle paths (from the monitor)."""
        rank = next(
            (
                i for i, f in enumerate(futures)
                if f.done() and f.exception() is err
            ),
            None,
        )
        bundles = monitor.crash_bundles() if monitor is not None else []
        notes = []
        if bundles:
            notes.append("flight bundle(s): " + ", ".join(bundles))
        # A death DURING the drain window must say a drain checkpoint
        # exists and where — the operator's next move is resuming from
        # it, not spelunking the scratch dir (mirrors how crash errors
        # name their flight bundles).
        drains = (
            monitor.drain_checkpoints() if monitor is not None else []
        )
        if drains:
            notes.append("drain checkpoint(s): " + ", ".join(drains))
        note = "; ".join(notes) or None
        if isinstance(err, ActorDiedError):
            fields = {"note": note} if note else {}
            if monitor is not None and monitor.abort_reason:
                fields["note"] = "; ".join(filter(None, [
                    note, f"aborted by RunMonitor: {monitor.abort_reason}"
                ]))
            if rank is not None:
                fields["rank"] = rank
                if rank < len(self._workers):
                    worker = self._workers[rank]
                    fields["exit_code"] = worker._proc.poll()
                if monitor is not None:
                    fields["last_heartbeat_age_s"] = (
                        monitor.last_heartbeat_age_s(rank)
                    )
            if fields:
                err.enrich(**fields)
        elif note:
            # RemoteError: the bundle path must still be in the message
            # a user reads first.
            err.args = (f"{err.args[0]}\n[{note}]",) + err.args[1:]

    def teardown(self) -> None:
        """Kill workers (≙ ``post_dispatch`` teardown, ``ray_ddp.py:398-401``)."""
        if self._backend is not None:
            if getattr(self, "_owns_backend", True):
                self._backend.shutdown()
            else:
                self._kill_workers(why="teardown")
        self._workers = []
        self._backend = None

    # -- introspection -------------------------------------------------------
    def get_worker_device_info(self) -> List[Dict[str, Any]]:
        """Device topology of every worker (rank/mesh mapping input;
        ≙ ``get_node_and_gpu_ids`` sweep at ``ray_ddp.py:230-274``)."""
        return [w.get_device_info() for w in self._workers]

    def get_worker_host_stats(self) -> List[Dict[str, Any]]:
        """Per-worker host load/memory — the straggler-context companion
        to ``trainer.telemetry_report``'s rank-skew view."""
        return [w.get_host_stats() for w in self._workers]


class LocalStrategy(TpuStrategy):
    """In-process execution on the driver's own devices (no actors).

    The analogue of running Lightning without any Ray plugin; used for
    single-host TPU runs (bench) and as ``Trainer()``'s default.  Still
    builds a mesh over the local devices, so data parallelism across the
    chips of one host works identically.
    """

    def __init__(self, mesh_axes: Optional[Dict[str, int]] = None,
                 mode: str = "gspmd", zero_stage: int = 0,
                 grad_comm=None, telemetry=None, monitor=None,
                 megastep=None, update_sharding=None,
                 grad_overlap_segments=None):
        super().__init__(
            num_workers=1, mesh_axes=mesh_axes, grad_comm=grad_comm,
            telemetry=telemetry, monitor=monitor, megastep=megastep,
            update_sharding=update_sharding,
            grad_overlap_segments=grad_overlap_segments,
        )
        if monitor is not None:
            warnings.warn(
                "monitor= has no effect on LocalStrategy: the RunMonitor "
                "rides the driver's result pump, which inline fits never "
                "enter.  Local fits still stream heartbeats to "
                "<root>/telemetry/heartbeats-rank0.jsonl (rlt_top reads "
                "them); use a remote strategy for watchdog/abort."
            )
        self.mode = mode
        self.zero_stage = zero_stage

    @property
    def is_distributed(self) -> bool:
        return False

    def setup(self, trainer) -> None:
        if self.init_hook is not None:
            self.init_hook()

    def run(
        self,
        kind: str,
        module,
        datamodule,
        config: FitConfig,
        callbacks: List,
        trainer=None,
        params_stream: Optional[bytes] = None,
        ckpt_path: Optional[str] = None,
    ) -> List[Dict[str, Any]]:
        from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh

        if config.megastep is None and self.megastep is not None:
            config = dataclasses.replace(config, megastep=self.megastep)
        if (config.update_sharding is None
                and self.update_sharding is not None):
            config = dataclasses.replace(
                config, update_sharding=self.update_sharding
            )
        if (config.grad_overlap_segments is None
                and self.grad_overlap_segments is not None):
            config = dataclasses.replace(
                config, grad_overlap_segments=self.grad_overlap_segments
            )
        # Gang-packing: inside a tune_run trial holding a sub-mesh
        # allocation (tuning/pack.py), build the mesh over exactly the
        # allocated devices — concurrent trials then run on DISJOINT
        # slices of one fleet instead of time-sharing every chip.
        devices = None
        try:
            from ray_lightning_tpu.tuning.session import (
                current_trial_devices,
            )

            indices = current_trial_devices()
        except Exception:  # noqa: BLE001 - tuner not in play
            indices = None
        if indices:
            import jax

            all_devices = jax.devices()
            bad = [i for i in indices if not 0 <= i < len(all_devices)]
            if bad:
                raise ValueError(
                    f"trial sub-mesh allocation names device indices "
                    f"{bad} but only {len(all_devices)} devices exist — "
                    "fleet_devices must not exceed the host's device "
                    "count for LocalStrategy trials"
                )
            devices = [all_devices[i] for i in indices]
        mesh = build_mesh(MeshSpec(self.mesh_axes), devices=devices)
        common = dict(
            module=module, datamodule=datamodule, config=config,
            global_rank=0, world_size=1, mesh=mesh,
        )
        if kind == "fit":
            try:
                return [run_fit(callbacks=callbacks, mode=self.mode,
                                zero_stage=self.zero_stage,
                                grad_comm=self.grad_comm,
                                telemetry=self.telemetry, **common)]
            except PreemptedError:
                # An inline drain is an orderly exit with its checkpoint
                # already written and named — not a crash to record.
                raise
            except BaseException as err:
                # Inline fits get the same crash forensics as remote
                # workers; there is no queue, so name the bundle loudly
                # here instead of on a stream event.
                from ray_lightning_tpu.telemetry.flight_recorder import (
                    record_active_crash,
                )

                bundle = record_active_crash(err)
                if bundle is not None:
                    warnings.warn(f"crash flight bundle written: {bundle}")
                raise
        if kind in ("validation", "test"):
            return [run_eval(callbacks=callbacks, kind=kind, mode=self.mode,
                             zero_stage=self.zero_stage,
                             params_stream=params_stream,
                             ckpt_path=ckpt_path,
                             telemetry=self.telemetry, **common)]
        if kind == "predict":
            return [run_predict(zero_stage=self.zero_stage,
                                params_stream=params_stream,
                                ckpt_path=ckpt_path,
                                telemetry=self.telemetry, **common)]
        raise ValueError(f"Unknown stage kind {kind!r}")

    def teardown(self) -> None:
        pass


class RayStrategy(TpuStrategy):
    """Data-parallel strategy over worker actors (≙ ``RayPlugin``).

    GSPMD flavor: the jitted train step sees the global batch sharded over
    the ``data`` mesh axis; XLA compiles the gradient all-reduce into the
    program and overlaps it with backward compute on ICI — the TPU-native
    equivalent of DDP's bucketed NCCL all-reduce.
    """

    mode = "gspmd"
    zero_stage = 0


class HorovodRayStrategy(TpuStrategy):
    """Explicit-collective flavor (≙ ``HorovodRayPlugin``).

    Per-device SPMD via ``shard_map``: each device computes gradients on
    its batch shard and calls ``lax.pmean`` over the data axis — the same
    ring all-reduce Horovod runs, but compiler-scheduled over ICI.
    """

    mode = "shard_map"
    zero_stage = 0


class RayShardedStrategy(TpuStrategy):
    """ZeRO-sharded data parallel (≙ ``RayShardedPlugin``/FairScale OSS).

    ``zero_stage=1`` shards optimizer state (OSS); ``zero_stage=3`` also
    shards parameters (FSDP-style).  Implemented purely as NamedSharding
    annotations on the train state — no wrapper classes
    (SURVEY §7: "sharding is an annotation").

    ``zero_stage=2`` ("shard gradients too", FairScale SDP /
    ``ray_ddp_sharded.py:17-34``) is accepted for compatibility but
    **normalized to stage 1 with a warning**: under GSPMD, gradients are
    transient values inside one jitted step — they are never materialized
    as persistent per-rank state, and XLA already reduce-scatters them
    where profitable — so there is nothing extra to annotate and no
    distinct stage-2 memory behavior to select.  A benchmark labeled
    stage 2 would measure exactly stage 1; the normalization keeps users
    from misreporting what they ran.
    """

    mode = "gspmd"

    def __init__(self, *args, zero_stage: int = 1, **kwargs):
        super().__init__(*args, **kwargs)
        if zero_stage not in (1, 2, 3):
            raise ValueError("zero_stage must be 1, 2 or 3")
        if zero_stage == 2:
            import warnings

            warnings.warn(
                "zero_stage=2 is equivalent to zero_stage=1 on this "
                "framework (GSPMD gradients are transient inside the "
                "jitted step; XLA reduce-scatters them automatically). "
                "Normalizing to zero_stage=1 — pass 1 or 3 explicitly "
                "to silence this warning."
            )
            zero_stage = 1
        self.zero_stage = zero_stage


class MpmdStrategy(TpuStrategy):
    """MPMD pipeline parallelism: one actor per pipeline stage, each
    with its OWN mesh and separately compiled programs (mesh-of-meshes,
    the JaxPP shape — docs/ARCHITECTURE.md round 12).

    Unlike the SPMD strategies there is no shared jitted program and no
    ``jax.distributed`` world: stage workers exchange activations and
    activation-gradients over the :mod:`~ray_lightning_tpu.mpmd.transfer`
    lane (shared-memory segments same-host, TCP queues across DCN) and
    follow explicit per-worker instruction streams
    (:mod:`~ray_lightning_tpu.mpmd.schedule`).

    Knobs: ``num_stages`` (= worker actors), ``schedule`` ("gpipe" |
    "1f1b"), ``num_microbatches``, ``interleave`` (model chunks per
    worker — the 1F1B-interleaved bubble shrink), ``devices_per_stage``
    (CPU simulation: virtual device count per stage actor),
    ``ckpt_every_n_steps`` (per-stage restart checkpoints — the
    restart governor resumes at the newest step EVERY stage persisted).

    The elastic machinery is inherited: a dead stage actor raises
    ``ActorDiedError`` into the same sliding-window restart governor,
    and a drain request makes every stage write a step-exact drain
    checkpoint and exit with ``PreemptedError``.

    Fit-only: eval/predict have no pipeline formulation here yet (run
    them through an SPMD strategy on the reassembled params).
    """

    mode = "mpmd"
    supports_elastic_resize = False  # the stage count is structural

    def __init__(
        self,
        num_stages: int = 2,
        schedule: str = "1f1b",
        num_microbatches: int = 8,
        interleave: int = 1,
        devices_per_stage: Optional[int] = None,
        recv_timeout_s: float = 120.0,
        ckpt_every_n_steps: int = 1,
        tx_factory: Optional[Callable[[], Any]] = None,
        trace_dir: Optional[str] = None,
        wire_dtype: Any = None,
        **kwargs: Any,
    ):
        from ray_lightning_tpu.mpmd.schedule import SCHEDULES
        from ray_lightning_tpu.mpmd.transfer import WireDtypeConfig

        if wire_dtype is not None:
            # Eager validation (a bad codec string must fail at
            # construction, not inside a stage actor); the validated
            # value still ships as the raw knob so workers re-coerce —
            # None defers to the bridged RLT_MPMD_WIRE_DTYPE env knob.
            WireDtypeConfig.coerce(wire_dtype)

        if num_stages < 1:
            raise ValueError("num_stages must be >= 1")
        if schedule not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {schedule!r} (expected one of "
                f"{SCHEDULES})"
            )
        if interleave < 1:
            raise ValueError("interleave must be >= 1")
        if interleave > 1 and schedule != "1f1b":
            raise ValueError(
                "interleave > 1 requires schedule='1f1b' (interleaved "
                "GPipe would deepen the pipe without shrinking the "
                "bubble)"
            )
        if interleave > 1 and num_stages < 2:
            raise ValueError(
                "interleave > 1 needs num_stages >= 2: a single worker "
                "has no pipeline to overlap, and its chunk handoffs "
                "would need a self-loop transfer lane the actor plane "
                "does not wire"
            )
        if num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1")
        if ckpt_every_n_steps < 1:
            raise ValueError("ckpt_every_n_steps must be >= 1")
        if kwargs.get("elastic_min_workers") is not None:
            raise ValueError(
                "MpmdStrategy cannot resize elastically: the stage "
                "count is structural (the layer split is baked into "
                "every stage's compiled program); run SPMD strategies "
                "for shrink/grow recovery"
            )
        kwargs.setdefault("use_tpu", devices_per_stage is None)
        # supports_elastic_resize = False (class attr below): the
        # fleet-wide RLT_ELASTIC_* env bus is ignored here for the same
        # structural reason, rather than crashing pipeline fits.
        super().__init__(num_workers=num_stages, **kwargs)
        self.schedule = schedule
        self.num_microbatches = num_microbatches
        self.interleave = interleave
        self.devices_per_stage = devices_per_stage
        self.recv_timeout_s = recv_timeout_s
        self.ckpt_every_n_steps = ckpt_every_n_steps
        self.tx_factory = tx_factory
        self.wire_dtype = wire_dtype
        # Distributed step tracing (docs/OBSERVABILITY.md): a SHARED
        # path (same-host fleets or a shared mount) each stage actor
        # exports trace-mpmd-stage<k>.jsonl into at fit end; None =
        # tracing off, nothing installed.
        self.trace_dir = trace_dir
        # Post-fit pipeline report (schedule, per-stage occupancy, the
        # measured-cost bubble decomposition) — the mpmd analogue of
        # trainer.telemetry_report.
        self.mpmd_report: Dict[str, Any] = {}
        self._live_stage_items: Dict[int, Dict[str, Any]] = {}
        self._live_written_at = 0.0
        self._live_dir: Optional[str] = None
        if devices_per_stage is not None:
            # CPU-simulated stage meshes: each stage ACTOR gets its own
            # virtual device count (its private "mesh"), replacing any
            # inherited test-harness value.
            import re as _re

            flags = os.environ.get("XLA_FLAGS", "")
            flags = _re.sub(
                r"--xla_force_host_platform_device_count=\d+", "", flags
            ).strip()
            self.env_per_worker.setdefault(
                "XLA_FLAGS",
                (f"{flags} --xla_force_host_platform_device_count="
                 f"{devices_per_stage}").strip(),
            )

    # The live monitor rides run_fit's heartbeat publisher, which stage
    # workers do not run — the mpmd_stage stream is their live plane.
    def _build_monitor(self, kind, config, trainer):
        return None

    def run(self, kind, module, datamodule, config, callbacks,
            trainer=None, params_stream=None, ckpt_path=None):
        if kind != "fit":
            raise NotImplementedError(
                "MpmdStrategy supports fit only; run validate/test/"
                "predict through an SPMD strategy on the trained params"
            )
        return super().run(
            kind, module, datamodule, config, callbacks, trainer=trainer,
            params_stream=params_stream, ckpt_path=ckpt_path,
        )

    def _latest_restart_checkpoint(self, restart_dir) -> Dict[str, Any]:
        from ray_lightning_tpu.mpmd.worker import latest_mpmd_checkpoint

        return latest_mpmd_checkpoint(restart_dir, self.num_workers)

    # -- live export ---------------------------------------------------------
    def _on_mpmd_item(self, item: Any) -> None:
        if not (isinstance(item, dict)
                and item.get("type") == "mpmd_stage"):
            return
        self._live_stage_items[int(item.get("stage", -1))] = item
        now = time.monotonic()
        if self._live_dir is None or now - self._live_written_at < 0.5:
            return
        self._live_written_at = now
        self._write_live_snapshot()

    def _live_snapshot(self) -> Dict[str, Any]:
        stages = [
            self._live_stage_items[k]
            for k in sorted(self._live_stage_items)
        ]
        return {
            "ts": time.time(),
            "mpmd": {
                "schedule": self.schedule,
                "interleave": self.interleave,
                "n_micro": self.num_microbatches,
                "n_stages": self.num_workers,
                "stages": stages,
            },
        }

    def _write_live_snapshot(self) -> None:
        import json

        if self._live_dir is None:
            return
        try:
            os.makedirs(self._live_dir, exist_ok=True)
            path = os.path.join(self._live_dir, "mpmd-live.json")
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self._live_snapshot(), f)
            os.replace(tmp, path)
        except OSError as e:
            log.debug("mpmd live snapshot write failed: %r", e)

    def _run_once(
        self,
        kind: str,
        module,
        datamodule,
        config: FitConfig,
        callbacks: List,
        trainer=None,
        params_stream: Optional[bytes] = None,
        ckpt_path: Optional[str] = None,
    ) -> List[Dict[str, Any]]:
        import numpy as np

        from ray_lightning_tpu.mpmd import worker as mpmd_worker
        from ray_lightning_tpu.mpmd.plan import (
            StagePlan,
            resolve_mpmd_spec,
        )
        from ray_lightning_tpu.mpmd.schedule import (
            fleet_pipeline_stats,
            measured_schedule_bubble,
            pool_op_costs,
        )

        spec = resolve_mpmd_spec(module)  # fail fast, driver-side
        plan = StagePlan.split(
            spec.n_layers, self.num_workers * self.interleave
        )
        self._live_stage_items = {}
        self._live_dir = os.path.join(
            config.default_root_dir, "telemetry"
        )

        is_local = isinstance(self._backend, backend_mod.LocalBackend)
        addrs = [
            w.execute(mpmd_worker._remote_create_inbox, is_local)
            for w in self._workers
        ]
        task = {
            "module": module,
            "datamodule": datamodule,
            "config": config,
            "n_workers": self.num_workers,
            "interleave": self.interleave,
            "n_micro": self.num_microbatches,
            "schedule": self.schedule,
            "mesh_axes": self.mesh_axes,
            "same_host": is_local,
            "recv_timeout_s": self.recv_timeout_s,
            "restart_dir": config.restart_dir,
            "resume_prefix": config.resume_from_checkpoint,
            "ckpt_every": self.ckpt_every_n_steps,
            "steps": (
                config.max_steps if config.max_steps
                and config.max_steps > 0 else None
            ),
            "tx_factory": self.tx_factory,
            "trace_dir": self.trace_dir,
            "wire_dtype": self.wire_dtype,
        }
        task_ref = self._backend.put(task)
        queue = self._backend.create_queue()
        on_item_trainer = getattr(trainer, "_on_stream_item", None)

        def on_item(item):
            self._on_mpmd_item(item)
            if on_item_trainer is not None:
                on_item_trainer(item)

        def _tick() -> None:
            self._maybe_broadcast_drain()

        futures = []
        try:
            futures = [
                w.submit(
                    mpmd_worker._stage_execute_remote, task_ref, rank,
                    queue.handle,
                    addrs[(rank - 1) % self.num_workers]
                    if self.num_workers > 1 else None,
                    addrs[(rank + 1) % self.num_workers]
                    if self.num_workers > 1 else None,
                )
                for rank, w in enumerate(self._workers)
            ]
            results = process_results(
                futures, queue, on_item=on_item, on_tick=_tick
            )
        except RemoteError as err:
            # A dead stage wedges its PEERS' transfer lanes: a peer's
            # recv-timeout/send-failure can resolve BEFORE the driver
            # notices the death, surfacing as RemoteError — which would
            # bypass the restart governor.  If any worker is actually
            # dead, the death is the root cause: raise it as such.
            dead = next(
                (
                    rank for rank, w in enumerate(self._workers)
                    if not w.is_alive()
                ),
                None,
            )
            if dead is not None:
                raise ActorDiedError(
                    f"stage worker {dead} died mid-fit (peer error: "
                    f"{err.args[0].splitlines()[0] if err.args else err})",
                    rank=dead,
                ) from err
            self._enrich_failure(err, futures, None)
            raise
        except ActorDiedError as err:
            self._enrich_failure(err, futures, None)
            raise
        finally:
            queue.shutdown()
            task_ref.release()

        # -- assemble the rank-0-shaped result package -------------------
        results = sorted(results, key=lambda r: r["rank"])
        n_stages = plan.n_stages
        parts = [
            results[g % self.num_workers]["chunks"][g // self.num_workers]
            for g in range(n_stages)
        ]
        full_params = spec.assemble_params(parts, plan)
        loss_result = next(r for r in results if r.get("hosts_loss"))
        final_step = int(loss_result["final_step"])

        per_stage = [r["stats"] for r in results]
        costs = pool_op_costs([r["op_costs"] for r in results])
        report = {
            "schedule": self.schedule,
            "interleave": self.interleave,
            "n_stages": self.num_workers,
            "n_micro": self.num_microbatches,
            "steps": final_step,
            "losses": list(loss_result["losses"]),
            "per_stage": per_stage,
            "op_costs_ms": {
                k: v * 1e3 for k, v in costs.items()
            },
            **fleet_pipeline_stats(per_stage),
        }
        if costs:
            report["bubble_fraction"] = measured_schedule_bubble(
                self.schedule, self.num_workers, self.num_microbatches,
                self.interleave, costs,
            )
        xfers = [r["xfer"] for r in results if r.get("xfer")]
        if xfers:
            sent = sum(int(x.get("bytes_sent", 0)) for x in xfers)
            full = sum(int(x.get("bytes_full_width", 0)) for x in xfers)
            wire: Dict[str, Any] = {
                "bytes_sent": sent,
                "bytes_full_width": full,
                "wire_ratio": (full / sent) if sent else 1.0,
                "per_stage": xfers,
            }
            enc = next((x["enc"] for x in xfers if x.get("enc")), None)
            if enc is not None:
                wire["enc"] = enc
            report["xfer"] = wire
        self.mpmd_report = report
        self._write_live_snapshot()

        from ray_lightning_tpu.core.module import TrainState
        from ray_lightning_tpu.utils.state_stream import to_state_stream

        state = TrainState(
            params=full_params,
            opt_state=None,  # per-stage moments stay with their stages
            step=np.int32(final_step),
        )
        metrics = dict(loss_result["callback_metrics"])
        metrics.update({
            "bubble_fraction": report.get("bubble_fraction", 0.0),
            "stage_occupancy": report["stage_occupancy"],
        })
        package = {
            "rank": 0,
            "state_stream": to_state_stream(state),
            "callback_metrics": metrics,
            "logged_metrics": dict(metrics),
            "best_model_path": "",
            "epochs_run": 1,
            "global_step": final_step,
            "micro_step": final_step * self.num_microbatches,
            "callback_states": [],
            "comm_stats": {},
            "telemetry": None,
        }
        return [package]


# Reference-name aliases (≙ ray_lightning's public exports, __init__.py:1-5)
RayPlugin = RayStrategy
HorovodRayPlugin = HorovodRayStrategy
RayShardedPlugin = RayShardedStrategy

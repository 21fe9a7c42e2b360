"""The fit/eval/predict loops — run identically inline or on worker actors.

≙ the body of ``trainer.run_stage()`` that the reference executes remotely
on every actor (reference ``ray_ddp.py:487``): epochs × batches of a jitted
train step, callbacks firing between batches/epochs, validation interleaved,
rank-0 returning (state stream, metrics, best path) to the driver
(``ray_ddp.py:490-519``).

The :class:`LoopContext` is the worker-side stand-in for the Trainer that
callbacks and modules see (``trainer`` argument) — a deliberate duck-typed
subset so the same callback code runs on driver-inline and remote paths.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.core.callbacks import Callback, ModelCheckpoint
from ray_lightning_tpu.core.data import TpuDataModule
from ray_lightning_tpu.core.module import TpuModule, TrainState
from ray_lightning_tpu.fault import drain as drain_mod
from ray_lightning_tpu.fault import inject as chaos
from ray_lightning_tpu.fault.drain import PreemptedError
from ray_lightning_tpu.parallel import sharding as shardlib
from ray_lightning_tpu.parallel import step_fns
from ray_lightning_tpu.parallel.overlap import (
    normalize_grad_overlap,
    resolve_grad_overlap,
)
from ray_lightning_tpu.telemetry import Telemetry
from ray_lightning_tpu.telemetry.spans import phase as _detached_phase
from ray_lightning_tpu.telemetry import program_ledger
from ray_lightning_tpu.utils.compile_cache import enable_compile_cache
from ray_lightning_tpu.utils.state_stream import (
    load_state_stream,
    state_stream_from_file,
    state_stream_to_file,
    to_state_stream,
)

__all__ = ["FitConfig", "LoopContext", "run_fit", "run_eval", "run_predict"]


@dataclasses.dataclass
class FitConfig:
    """Picklable trainer configuration shipped to workers.

    ≙ the Trainer args the reference pickles wholesale inside the trainer
    object (``ray_ddp.py:339-342``); we ship only the loop-relevant subset.
    """

    max_epochs: int = 1
    max_steps: int = -1
    check_val_every_n_epoch: int = 1
    limit_train_batches: int = -1
    limit_val_batches: int = -1
    log_every_n_steps: int = 50
    # Apply the optimizer once every k micro-batches (optax.MultiSteps
    # under the hood): k micro-steps of batch B train like one step of
    # batch k*B (≙ Lightning's ``accumulate_grad_batches``).  As in
    # Lightning, ``max_steps`` AND ``global_step`` count OPTIMIZER steps;
    # ``log_every_n_steps`` fires on micro-batches (Lightning's batch
    # cadence).  A partial accumulation window left at epoch end is
    # FLUSHED (one optimizer step from the averaged micro-grads), again
    # matching Lightning.
    accumulate_grad_batches: int = 1
    # Megastep execution (the host-dispatch optimization): fuse K
    # micro-steps into ONE jitted lax.scan per stride, with batches
    # pre-staged K at a time and metric accumulation on device — Python
    # re-enters once per stride instead of once per micro-batch
    # (docs/PERFORMANCE.md "Host dispatch & megastep").  Values:
    # None (read the RLT_MEGASTEP env bus, default "auto"), "auto"
    # (K=8 on TPU backends where per-step dispatch is the ceiling; off
    # on CPU), "off"/1, or an explicit int K >= 1.  Partial strides at
    # epoch/limit/max_steps boundaries fall back to the per-step path,
    # so step-count contracts hold exactly.
    megastep: Optional[Any] = None
    # Cross-replica sharded weight update (arXiv:2004.13336): on a
    # pure-DP mesh with a replicated optimizer (zero_stage=0), annotate
    # the optimizer state — and therefore the update computation —
    # sharded over the batch axes, so each replica updates 1/P of the
    # moments (reduce-scatter → sharded update → all-gather params,
    # inserted by GSPMD from the in/out shardings).  Values: None (read
    # the RLT_UPDATE_SHARDING env bus, default "auto"), "auto" (on for
    # TPU batch-only gspmd meshes, off on CPU), "on", "off"/bools.
    # Gated off wherever ZeRO already shards the state.
    update_sharding: Optional[Any] = None
    # Backward-overlapped gradient sync (parallel/overlap.py): split the
    # model trunk into G sub-scans and run each param group's bucketed
    # quantized all-reduce inside the backward via custom_vjp grad taps,
    # so the collectives hide under remaining backward compute instead
    # of firing serialized after jax.grad.  Values: None (read the
    # RLT_GRAD_OVERLAP env bus, forwarded to workers like
    # RLT_GRAD_COMM), "off"/""/0 (step-end sync, the zero-risk
    # default), or an int G >= 1.  Composes with grad_comm (the wire
    # codec is unchanged — only WHERE the collectives fire moves); with
    # grad_comm=full only the bitwise-neutral trunk segmentation runs.
    grad_overlap_segments: Optional[Any] = None
    seed: int = 0
    precision: str = "f32"
    default_root_dir: str = "."
    resume_from_checkpoint: Optional[str] = None
    fast_dev_run: bool = False
    # Elastic-restart support (strategy-managed): when set, every
    # ``restart_every_n_epochs`` the loop writes a topology-independent
    # checkpoint here so the strategy can respawn dead workers and resume.
    restart_dir: Optional[str] = None
    # None = unset: the strategy's elastic default applies.  An explicit
    # Trainer(restart_every_n_epochs=...) always wins over the strategy.
    restart_every_n_epochs: Optional[int] = None

    def __post_init__(self):
        # Lightning habits: None means "no limit/cap" for these — accept
        # it as a synonym for the framework's -1 sentinel instead of
        # crashing at a `>= 0` comparison deep in the loop.  A None
        # max_epochs additionally requires a real max_steps (otherwise
        # the fit would never terminate); Lightning's default in that
        # case is 1000 epochs, mirrored here as the range bound.
        if self.limit_train_batches is None:
            self.limit_train_batches = -1
        if self.limit_val_batches is None:
            self.limit_val_batches = -1
        if self.max_steps is None:
            self.max_steps = -1
        if self.max_epochs is None:
            self.max_epochs = 1000
        # Precision aliases: Lightning 2.x spellings map onto the two
        # real TPU modes (f32 / bf16 with f32 accumulation).  Anything
        # else — notably fp16, which TPUs don't accelerate — is rejected
        # loudly rather than silently training in f32.
        # Lossy aliases change semantics, not just spelling: Lightning's
        # '-true' means the WEIGHTS are cast to bf16, but this framework
        # only implements mixed bf16 (f32 params + optimizer state, bf16
        # compute) — coerce, but say so, since memory footprint and
        # numerics differ from what was asked for.
        lossy = {"bf16-true": "bf16"}
        aliases = {"32": "f32", "32-true": "f32", "float32": "f32",
                   "bf16-mixed": "bf16", "bfloat16": "bf16", **lossy}
        raw = str(self.precision)
        if raw in lossy:
            import warnings

            warnings.warn(
                f"precision={raw!r} (bf16 weights) is not implemented on "
                f"this framework; using mixed bf16 instead (f32 "
                f"params/optimizer state, bf16 matmuls). Pass "
                f"'bf16-mixed' to silence this warning."
            )
        self.precision = aliases.get(raw, self.precision)
        if self.precision not in ("f32", "bf16"):
            raise ValueError(
                f"precision {self.precision!r} unsupported on TPU: use "
                f"'f32' or 'bf16' (accepted aliases: {sorted(aliases)})"
            )
        # Megastep knob: validated eagerly (a typo'd value must fail at
        # Trainer construction, not minutes later on a worker); the
        # BACKEND-dependent "auto" resolution stays fit-time
        # (_resolve_megastep) — the driver may be CPU-only while the
        # workers run TPUs.
        _normalize_megastep(self.megastep)
        _normalize_update_sharding(self.update_sharding)
        normalize_grad_overlap(self.grad_overlap_segments)
        if self.fast_dev_run:
            self.max_epochs = 1
            self.limit_train_batches = 1
            self.limit_val_batches = 1


def _normalize_megastep(value: Any) -> Optional[Any]:
    """Validate a megastep knob value and return its normal form:
    None, "auto", "off" or an int >= 1 (numeric strings become ints;
    resolution to a concrete K happens at fit time)."""
    if value is None:
        return None
    if isinstance(value, str):
        s = value.strip().lower()
        if s in ("auto", "off", ""):
            return "off" if s == "" else s
        try:
            value = int(s)
        except ValueError:
            raise ValueError(
                f"megastep={value!r}: expected 'auto', 'off' or an "
                "integer K >= 1"
            ) from None
    if isinstance(value, bool) or not isinstance(value, int):
        raise TypeError(
            f"megastep must be None, 'auto', 'off' or an int >= 1; got "
            f"{type(value).__name__}"
        )
    if value < 1:
        raise ValueError(f"megastep must be >= 1, got {value}")
    return value


def _normalize_update_sharding(value: Any) -> Optional[str]:
    """Validate an ``update_sharding`` knob value: None, "auto", "on"
    or "off" (bools accepted as on/off).  Resolution against the real
    mesh/mode happens at fit time (:func:`_resolve_update_sharding`)."""
    if value is None:
        return None
    if isinstance(value, bool):
        return "on" if value else "off"
    if isinstance(value, str):
        s = value.strip().lower()
        if s == "":
            return "off"
        if s in ("auto", "on", "off"):
            return s
    raise ValueError(
        f"update_sharding={value!r}: expected 'auto', 'on', 'off' or a "
        "bool"
    )


def _resolve_update_sharding(
    config: FitConfig, mesh, mode: str, zero_stage: int
) -> bool:
    """Whether THIS fit shards the weight update over the batch axes
    (arXiv:2004.13336 via sharding annotations — see
    :func:`init_train_state`).

    Strongest first: the Trainer/strategy knob → the
    ``RLT_UPDATE_SHARDING`` env bus → ``"auto"``.  The technique only
    exists for replicated-optimizer data-parallel meshes, so it
    requires: a multi-device mesh whose axes are all batch-parallel
    (``data``/``fsdp``), gspmd step mode, and ``zero_stage == 0`` —
    ZeRO already shards the update, shard_map replicates the state by
    contract, and model-parallel axes change what "replica" means.  An
    explicit "on" outside that envelope warns and stays off (the same
    loud-downgrade discipline as grad_comm); "auto" additionally keeps
    CPU meshes off — like megastep, the XLA:CPU collective rendezvous
    costs more than the update traffic it saves, so auto engages on
    TPU backends only.
    """
    value = _normalize_update_sharding(config.update_sharding)
    if value is None:
        value = _normalize_update_sharding(
            os.environ.get("RLT_UPDATE_SHARDING", "auto")
        )
    if value == "off":
        return False
    eligible = (
        mesh is not None
        and getattr(mesh, "size", 1) > 1
        and mode == "gspmd"
        and zero_stage == 0
        and set(mesh.axis_names) <= {"data", "fsdp"}
    )
    if value == "on":
        if not eligible:
            import warnings

            warnings.warn(
                "update_sharding='on' needs a multi-device batch-only "
                "(data/fsdp) gspmd mesh with zero_stage=0 (ZeRO already "
                f"shards the update); got mesh="
                f"{None if mesh is None else tuple(mesh.axis_names)}, "
                f"mode={mode!r}, zero_stage={zero_stage} — running with "
                "a replicated update instead"
            )
            return False
        return True
    # auto
    if not eligible:
        return False
    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:
        return False


def _resolve_megastep(config: FitConfig) -> int:
    """The concrete stride length K for this fit.

    Strongest first: an explicit ``megastep=`` on the Trainer/strategy →
    the ``RLT_MEGASTEP`` env bus (forwarded to workers like
    ``RLT_GRAD_COMM``) → ``"auto"``.  Auto picks K=8 on TPU backends —
    there the ~ms-scale per-step host dispatch is the throughput ceiling
    the MFU telemetry sees (ISSUE 5 / Podracer) — and stays off on
    CPU/other backends, where execution is effectively synchronous and
    fusing strides buys little while coarsening hook/drain granularity.
    """
    value = config.megastep
    if value is None:
        # NB: an empty RLT_MEGASTEP= means "off" (the operator cleared
        # the knob), same as every other normalization path — only a
        # genuinely unset var falls through to auto.
        value = os.environ.get("RLT_MEGASTEP")
        value = "auto" if value is None else value
    value = _normalize_megastep(value)
    if value == "off":
        return 1
    if value == "auto":
        try:
            on_tpu = jax.default_backend() == "tpu"
        except RuntimeError:
            on_tpu = False
        return 8 if on_tpu else 1
    return int(value)


def _phase_opener(tel: Optional[Telemetry]):
    """``Telemetry.phase`` of this rank, or the detached primitive (the
    profiler annotation alone) where no telemetry is attached."""
    return _detached_phase if tel is None else tel.phase


class LoopContext:
    """Worker-side trainer context (the ``trainer`` arg of every hook)."""

    def __init__(
        self,
        config: FitConfig,
        global_rank: int,
        world_size: int,
        mesh=None,
        queue=None,
        tx=None,
    ):
        self.config = config
        self.global_rank = global_rank
        self.world_size = world_size
        self.mesh = mesh
        self.queue = queue
        self.tx = tx
        self._ckpt_queue = None  # lazy async checkpoint writer
        self.current_epoch = 0
        # Lightning convention: global_step counts OPTIMIZER steps;
        # micro_step counts micro-batches (they differ only under
        # gradient accumulation).
        self.global_step = 0
        self.micro_step = 0
        # Live-monitor progress signal (telemetry/heartbeat.py): a
        # counter that advances on ANY forward motion — train
        # micro-batches AND validation batches — plus a coarse phase
        # tag.  The heartbeat publisher reads both from its own thread;
        # the RunMonitor flags a rank whose progress freezes while its
        # beats keep flowing (the wedged-collective signature).
        self.progress = 0
        self.phase = "init"
        self.should_stop = False
        self.callback_metrics: Dict[str, float] = {}
        self.logged_metrics: Dict[str, float] = {}
        # Crash-forensics hook (telemetry/flight_recorder.py): lands any
        # in-flight _AsyncLogFetch boundary into callback_metrics before
        # the bundle snapshots them — without it a crash would freeze
        # the metrics one-to-two log intervals behind where the old
        # synchronous device_get path left them.
        self.pending_log_flush: Optional[Callable[[], None]] = None
        self.state: Optional[TrainState] = None
        self.default_root_dir = config.default_root_dir
        # Gradient-communication status (populated by run_fit): modules
        # consult ``grad_sync_active`` to pick per-device-safe compute
        # paths when their step runs inside the quantized-sync island.
        self.grad_sync_active = False
        self.comm_stats: Dict[str, Any] = {}
        # Backward-overlapped sync (populated by run_fit): the resolved
        # trunk-segment count G (0 = step-end).  Module forwards read it
        # to segment their layer scan; during the overlapped island's
        # differentiation ``grad_tap_plane`` additionally carries the
        # per-trace tap registry (parallel/overlap.py TapPlane).
        self.grad_overlap_segments = 0
        self.grad_tap_plane = None
        # Telemetry runtime for this stage (always present; tier "off"
        # degrades every surface to a no-op).  ``telemetry_dir`` is where
        # exporters (span dumps, ProfilerCallback traces) co-locate.
        self.telemetry: Optional[Telemetry] = None
        self.telemetry_dir: Optional[str] = None

    @property
    def is_global_zero(self) -> bool:
        return self.global_rank == 0

    def log_metrics(self, metrics: Dict[str, Any]) -> None:
        for k, v in metrics.items():
            self.logged_metrics[k] = float(v)
            self.callback_metrics[k] = float(v)

    # -- checkpointing ------------------------------------------------------
    def _gathered_state(self) -> Any:
        """Host-local numpy copy of the full train state.

        Single host: every shard is addressable, ``device_get`` suffices.
        Multi-host: replicate via an identity jit with replicated
        out_shardings (an XLA all-gather over ICI/DCN), then device_get the
        local replica — checkpoints stay topology-independent (SURVEY §7
        hard-part #4).

        **COLLECTIVE**: on a multi-host mesh every rank MUST call this at
        the same point (rank-guarding the caller deadlocks the mesh — only
        the file WRITE may be rank-guarded).
        """
        state = self.state
        if getattr(state, "grad_residual", None) is not None:
            # The EF residual is (n_devices, ~param_count) f32 — one
            # params-sized row PER DEVICE.  Gathering it would blow up
            # every checkpoint payload and the rank-0→driver stream by
            # n_devices × model size (device OOM at pod scale), to
            # preserve at most one step of compression error; resumes
            # re-attach a zero residual instead
            # (``GradSync.reconcile_resumed_state``).  The sharded
            # restart path (``sharded_ckpt.save_shard``) still persists
            # it cheaply — each host writes only its own rows.
            state = TrainState(state.params, state.opt_state, state.step)
        with self.timed("host_transfer"):
            out = shardlib.host_replicated_copy(state, self.mesh)
        if self.telemetry is not None:
            self.telemetry.add_counter("host_transfers", 1)
        return out

    def timed(self, name: str, layer: str = "train", **args):
        """One timed phase of this rank's loop."""
        return _phase_opener(self.telemetry)(name, layer, **args)

    def checkpoint_payload(self, extra: Optional[Dict[str, Any]] = None) -> dict:
        return {
            "state": self._gathered_state(),
            "epoch": self.current_epoch,
            "global_step": self.global_step,
            "micro_step": self.micro_step,
            "callback_metrics": dict(self.callback_metrics),
            **(extra or {}),
        }

    def save_checkpoint(self, path: str, async_write: bool = False) -> None:
        """Gather (all ranks — collective) and write (rank 0 only).

        ``async_write=True`` moves serialization + disk IO to a single
        background writer thread, so the training loop resumes as soon
        as the host gather finishes — at GPT scale the msgpack encode +
        write is seconds per checkpoint that otherwise stall every
        epoch.  The GATHER stays synchronous on all ranks (it is a
        collective; backgrounding it would deadlock the mesh).  Pending
        writes are joined by :meth:`flush_checkpoints` (called at fit
        end, and by consumers before they read/delete checkpoint
        files); a failed background write raises there.
        """
        payload = self.checkpoint_payload()
        if not self.is_global_zero:
            return
        if self.telemetry is not None:
            self.telemetry.add_counter("checkpoint_writes", 1)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        if not async_write:
            with self.timed("checkpoint_write", path=path):
                state_stream_to_file(to_state_stream(payload), path)
            return
        if self._ckpt_queue is None:
            import queue as _q

            # maxsize=1: at most ONE payload (a full host copy of the
            # train state — GBs at LM scale) waits in RAM; a slow disk
            # backpressures the loop instead of accumulating copies.
            self._ckpt_queue = _q.Queue(maxsize=1)
            self._ckpt_errors: List[BaseException] = []
            # Paths with an enqueued-but-unfinished write: consumers that
            # only need to delete a FINISHED file (ModelCheckpoint._prune)
            # consult this instead of joining the whole queue — joining
            # unconditionally turned steady-state save_top_k=1 back into
            # a synchronous write every epoch.
            self._ckpt_pending: set = set()
            self._ckpt_lock = threading.Lock()
            q, errors = self._ckpt_queue, self._ckpt_errors
            pending, lock = self._ckpt_pending, self._ckpt_lock
            # The telemetry holds no device state — safe capture.
            wphase = _phase_opener(self.telemetry)

            def writer():  # captures the queue/list, NOT self — the
                # LoopContext (with its device-side state) must stay
                # collectable once the writer is closed.
                while True:
                    item = q.get()
                    try:
                        if item is None:
                            return
                        p, pl = item
                        with wphase("checkpoint_write", "train", path=p,
                                    **{"async": True}):
                            state_stream_to_file(to_state_stream(pl), p)
                    except BaseException as e:  # noqa: BLE001
                        errors.append(e)
                    finally:
                        if item is not None:
                            with lock:
                                pending.discard(item[0])
                        q.task_done()

            self._ckpt_thread = threading.Thread(
                target=writer, name="rlt-ckpt-writer", daemon=True
            )
            self._ckpt_thread.start()
        with self._ckpt_lock:
            self._ckpt_pending.add(path)
        self._ckpt_queue.put((path, payload))

    def checkpoint_write_pending(self, path: str) -> bool:
        """True while an async write of ``path`` is still enqueued or in
        flight.  False for finished writes, sync writes, and trainer
        facades without the async machinery — so callers can gate a
        flush on it unconditionally."""
        if getattr(self, "_ckpt_queue", None) is None:
            return False
        with self._ckpt_lock:
            return path in self._ckpt_pending

    def flush_checkpoints(self) -> None:
        """Join pending async checkpoint writes; re-raise any failure.
        A checkpoint the user believes exists must exist — a silently
        dropped write is worse than a loud one."""
        if getattr(self, "_ckpt_queue", None) is None:
            return
        self._ckpt_queue.join()
        if self._ckpt_errors:
            err = self._ckpt_errors[:]
            self._ckpt_errors.clear()
            raise RuntimeError(
                f"async checkpoint write failed: {err[0]!r}"
            ) from err[0]

    def close_checkpoint_writer(self) -> None:
        """Flush, then retire the writer thread (one per fit, never one
        per process lifetime — tuner sweeps run many fits)."""
        if getattr(self, "_ckpt_queue", None) is None:
            return
        try:
            self.flush_checkpoints()
        finally:
            self._ckpt_queue.put(None)
            self._ckpt_thread.join(timeout=30)
            self._ckpt_queue = None
            self._ckpt_thread = None


def _call_hooks(callbacks: List[Callback], hook: str, *args) -> None:
    for cb in callbacks:
        getattr(cb, hook)(*args)


def _maybe_export_telemetry(tel: Telemetry, out_dir: Optional[str]) -> None:
    """Full tier: drop this rank's span dump + Chrome trace + snapshot
    beside any ProfilerCallback capture (same output dir family).  A
    failed export warns — telemetry must never cost the stage result."""
    if not (tel.tracer.enabled and out_dir):
        return
    try:
        tel.export(out_dir)
    except OSError as e:
        import warnings

        warnings.warn(f"telemetry export failed ({e})")


def _mesh_barrier(mesh) -> None:
    """Block until every process of the mesh reaches this point: a tiny
    all-reduce over a mesh-sharded vector (completion of the local result
    requires every participant's contribution)."""
    if mesh is None or len(mesh.devices.flat) <= 1:
        return
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(mesh.devices.flat)
    vec = jnp.ones((n,), jnp.int32)
    sharded = NamedSharding(mesh, P(mesh.axis_names))
    total = jax.jit(
        jnp.sum, in_shardings=(sharded,), out_shardings=NamedSharding(
            mesh, P())
    )(jax.device_put(vec, sharded))
    assert int(jax.device_get(total)) == n


def _make_drain_poll(mesh, world_size: int):
    """Mesh-coordinated drain agreement (the Orbax-style preemption
    sync point): every process contributes its local drain flag to a
    tiny all-reduce, so ALL ranks decide to drain at the SAME step —
    a rank draining alone would tear the sharded drain checkpoint and
    deadlock its peers' next collective.

    Single-process fits return ``None`` (the local flag IS the global
    flag — zero overhead on the bench path).  The jitted reduction is
    built once and reused every step; per-step cost is one scalar-ish
    collective dispatch.
    """
    if mesh is None or world_size <= 1:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(mesh.devices.flat)
    sharded = NamedSharding(mesh, P(mesh.axis_names))
    total = jax.jit(
        jnp.sum, in_shardings=(sharded,),
        out_shardings=NamedSharding(mesh, P()),
    )

    def _shard_block(index) -> np.ndarray:
        s = index[0]
        start = 0 if s.start is None else s.start
        stop = n if s.stop is None else s.stop
        return _flag_box[0][: stop - start]

    _flag_box = [np.zeros((n,), np.int32)]

    def poll(local: bool) -> bool:
        _flag_box[0] = np.full((n,), 1 if local else 0, np.int32)
        arr = jax.make_array_from_callback((n,), sharded, _shard_block)
        return int(jax.device_get(total(arr))) > 0

    return poll


def _prune_restart_dir(restart_dir: str, keep: int = 2) -> None:
    """Keep the ``keep`` newest COMPLETE restart/drain checkpoints.

    Two, not one: previous-good fallback (restart discovery walks back
    over a corrupt newest checkpoint) is only possible if the previous
    checkpoint still exists — keeping exactly the newest would convert
    one bit flip into a from-scratch restart.  Candidate enumeration
    and ordering are SHARED with restart discovery
    (``sharded_ckpt.list_restart_candidates``) so pruning can never
    delete what discovery would have resumed from.
    """
    from ray_lightning_tpu.utils.sharded_ckpt import (
        list_restart_candidates,
    )

    import shutil

    for _, _, _, stale in list_restart_candidates(restart_dir)[keep:]:
        shutil.rmtree(stale, ignore_errors=True)
        if os.path.isfile(stale):  # legacy single-file
            try:
                os.unlink(stale)
            except OSError:
                pass


def _build_accum_flush(inner_tx, mesh, state_shardings):
    """Compile the partial-accumulation flush: one optimizer update from
    ``MultiStepsState.acc_grads`` (the running MEAN of the window's
    micro-grads), with the window counters reset.

    Without this, micro-batches left in an unfinished window at epoch/fit
    end were silently dropped (their gradients never reached the params)
    — diverging from Lightning, where the last incomplete window of an
    epoch still steps.
    """
    import optax

    def flush(state: TrainState) -> TrainState:
        ms = state.opt_state
        updates, inner2 = inner_tx.update(
            ms.acc_grads, ms.inner_opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new_ms = optax.MultiStepsState(
            mini_step=jnp.zeros_like(ms.mini_step),
            gradient_step=ms.gradient_step + 1,
            inner_opt_state=inner2,
            acc_grads=jax.tree_util.tree_map(
                jnp.zeros_like, ms.acc_grads
            ),
        )
        return TrainState(
            new_params, new_ms, state.step + 1, state.grad_residual
        )

    if mesh is None or state_shardings is None:
        return jax.jit(flush, donate_argnums=0)
    return jax.jit(
        flush,
        in_shardings=(state_shardings,),
        out_shardings=state_shardings,
        donate_argnums=0,
    )


def _rederive_accum(old_world: int, old_accum: int,
                    new_world: int) -> Optional[int]:
    """The accumulation factor that keeps the GLOBAL batch per optimizer
    step invariant under an elastic world-size change: each host feeds
    ``b`` rows per micro-batch, so ``world × accum × b`` rows reach every
    optimizer update — resuming N→M must scale accum by N/M.  The LR
    schedule indexes optimizer steps, so with the global batch invariant
    it needs no rescaling.  Returns ``None`` when the product does not
    divide (the caller keeps the old accum and warns loudly)."""
    rows = int(old_world) * int(old_accum)
    if new_world <= 0 or rows % int(new_world):
        return None
    return rows // int(new_world)


def _elastic_resume_info(path: str, world_size: int,
                         cfg_accum: int) -> Optional[Dict[str, Any]]:
    """World-size delta between a sharded checkpoint and THIS fit, read
    from META alone (no shard bytes touched).  ``None`` when the
    checkpoint predates the elastic plane (no recorded ``world_size``)
    or the world is unchanged."""
    from ray_lightning_tpu.utils import sharded_ckpt

    try:
        extra = sharded_ckpt.load_meta(path).get("extra", {})
    except Exception:  # noqa: BLE001 - a corrupt META fails later, in
        # load_sharded, with the full verify story
        return None
    old_world = extra.get("world_size")
    if not old_world:
        return None
    old_world = int(old_world)
    recorded_accum = extra.get("accum")
    old_accum = int(recorded_accum or cfg_accum)
    if old_world == int(world_size):
        if recorded_accum is None or int(recorded_accum) == int(cfg_accum):
            return None
        # Same world, but the checkpoint's trajectory ran a DIFFERENT
        # accum — a previous elastic resize re-derived it (shrink at 2
        # writes world_size=1/accum=2; a later same-world crash resume
        # must not silently revert to the config's 1, which would both
        # change the global batch mid-trajectory and hand the
        # congruence-dependent reconciliations a structurally
        # mismatched opt_state).  The recorded value wins, loudly.
        return {
            "old_world": old_world,
            "new_world": int(world_size),
            "old_accum": int(recorded_accum),
            "accum": int(recorded_accum),
            "exact": True,
            "ckpt": path,
        }
    new_accum = _rederive_accum(old_world, old_accum, world_size)
    return {
        "old_world": old_world,
        "new_world": int(world_size),
        "old_accum": old_accum,
        "accum": new_accum if new_accum is not None else old_accum,
        "exact": new_accum is not None,
        "ckpt": path,
    }


def _reconcile_multisteps(host_state: Any, template: Any) -> Any:
    """Elastic accum re-derivation can cross the ``accum == 1``
    boundary, changing the opt_state WRAPPER: accum > 1 wraps the inner
    optimizer state in ``optax.MultiStepsState``.  A checkpoint from
    the other side of the boundary is re-wrapped here so the resumed
    tree stays congruent with this run's state template:

    * bare → MultiSteps (shrink drove accum past 1): fresh window —
      ``mini_step = 0``, zero ``acc_grads``, ``gradient_step`` carried
      from the train step counter;
    * MultiSteps → bare (grow collapsed accum to 1): the inner state is
      unwrapped; a PARTIAL accumulation window is dropped with a loud
      warning (its micro-grads never reached the params — at most
      ``accum - 1`` micro-batches of gradient signal).
    """
    import optax

    from ray_lightning_tpu.core.module import TrainState

    if not isinstance(host_state, TrainState) or not isinstance(
        template, TrainState
    ):
        return host_state
    have = isinstance(host_state.opt_state, optax.MultiStepsState)
    want = isinstance(template.opt_state, optax.MultiStepsState)
    if have == want:
        return host_state
    if want:
        step32 = np.asarray(
            jax.device_get(host_state.step), np.int32
        )
        ms = optax.MultiStepsState(
            mini_step=np.zeros((), np.int32),
            gradient_step=step32,
            inner_opt_state=host_state.opt_state,
            acc_grads=jax.tree_util.tree_map(
                lambda p: np.zeros(
                    getattr(p, "shape", ()),
                    getattr(p, "dtype", np.float32),
                ),
                jax.device_get(host_state.params),
            ),
        )
        return TrainState(
            host_state.params, ms, host_state.step,
            host_state.grad_residual,
        )
    ms = host_state.opt_state
    mini = int(np.asarray(jax.device_get(ms.mini_step)))
    if mini:
        import warnings

        warnings.warn(
            f"elastic resume collapsed accum to 1: the checkpoint's "
            f"partial accumulation window ({mini} micro-grad(s)) is "
            "dropped"
        )
    return TrainState(
        host_state.params, ms.inner_opt_state, host_state.step,
        host_state.grad_residual,
    )


def _reconcile_opt_state_format(host_state: Any, template: Any) -> Any:
    """Reconcile a checkpoint's optimizer-state STORAGE FORMAT with
    this run's template across an ``opt_state_dtype`` policy change
    (models/optim.py): quantized ↔ float moment leaves differ in tree
    STRUCTURE (a :class:`~ray_lightning_tpu.ops.optim_quant.BlockQuantized`
    node vs a bare array), which the dtype-cast reconciliation below
    cannot express.  Float → quantized requantizes (lossy by exactly
    the codec's rounding — the same rounding a fresh step would apply);
    quantized → float dequantizes.  Same-policy resumes pass through
    untouched, so int8 state round-trips drain → resume bit-exactly.
    """
    from ray_lightning_tpu.core.module import TrainState
    from ray_lightning_tpu.ops.optim_quant import (
        dequantize_moment,
        is_block_quantized,
        quantize_moment,
    )

    if not isinstance(host_state, TrainState) or not isinstance(
        template, TrainState
    ):
        return host_state
    tdef = jax.tree_util.tree_structure(template.opt_state)
    hdef = jax.tree_util.tree_structure(host_state.opt_state)
    if tdef == hdef:
        return host_state
    converted = [0]

    def coerce(tmpl_leaf, ckpt_piece):
        t_q = is_block_quantized(tmpl_leaf)
        c_q = is_block_quantized(ckpt_piece)
        if t_q and c_q:
            if (tuple(tmpl_leaf.shape) != tuple(ckpt_piece.shape)
                    or tmpl_leaf.block_size != ckpt_piece.block_size
                    or tmpl_leaf.sqrt_domain != ckpt_piece.sqrt_domain):
                converted[0] += 1
                return quantize_moment(
                    dequantize_moment(ckpt_piece),
                    block_size=tmpl_leaf.block_size,
                    sqrt_domain=tmpl_leaf.sqrt_domain,
                )
            return ckpt_piece
        if t_q:
            converted[0] += 1
            return quantize_moment(
                jnp.asarray(ckpt_piece, jnp.float32),
                block_size=tmpl_leaf.block_size,
                sqrt_domain=tmpl_leaf.sqrt_domain,
            )
        if c_q:
            converted[0] += 1
            return dequantize_moment(ckpt_piece).astype(
                getattr(tmpl_leaf, "dtype", jnp.float32)
            )
        return ckpt_piece

    try:
        new_opt = jax.tree_util.tree_map(
            coerce, template.opt_state, host_state.opt_state,
            is_leaf=is_block_quantized,
        )
    except ValueError:
        # Structures differ beyond moment storage (a genuinely foreign
        # checkpoint) — let the downstream congruence checks raise
        # their own, more specific error.
        return host_state
    if converted[0]:
        import warnings

        warnings.warn(
            f"resume across an opt_state_dtype change: "
            f"{converted[0]} optimizer moment leaves converted to this "
            "run's storage format (float ↔ block-scaled int8; "
            "requantization applies the codec's rounding once)"
        )
    return TrainState(
        host_state.params, new_opt, host_state.step,
        host_state.grad_residual,
    )


def _announce_resize(info: Dict[str, Any], tel: Telemetry, queue,
                     global_rank: int) -> None:
    """Make an elastic N→M resume LOUD: a warning on every rank, an
    ``elastic_resizes`` counter, and (rank 0) a schema-shaped ``resize``
    event on the driver queue — the old/new world sizes flow through
    the monitor into ``trainer.monitor_report``, OpenMetrics and
    ``rlt_top`` like every other recovery event."""
    import warnings

    from ray_lightning_tpu.telemetry.monitor import make_event

    if info["old_world"] == info["new_world"]:
        # No world change — an accum-continuity override (the recorded
        # accum beats the config's): warn, but no resize event.
        warnings.warn(
            f"elastic resume: honoring the checkpoint's recorded "
            f"accum {info['accum']} over the configured value — the "
            f"state's optimizer trajectory (and the global batch per "
            f"optimizer step) continues what a previous elastic "
            f"resize established"
        )
        return
    msg = (
        f"elastic resume: checkpoint from world size {info['old_world']}"
        f" (accum {info['old_accum']}) resuming on {info['new_world']}"
        f" with accum {info['accum']}"
    )
    if not info["exact"]:
        msg += (
            " — old_world*accum does not divide the new world size; the"
            " GLOBAL batch per optimizer step changes and the LR"
            " schedule is no longer step-equivalent"
        )
    warnings.warn(msg)
    tel.add_counter("elastic_resizes", 1)
    if queue is not None and global_rank == 0:
        try:
            queue.put(make_event(
                "resize", global_rank,
                old_world=info["old_world"],
                new_world=info["new_world"],
                message=msg, ckpt=info["ckpt"],
            ))
        except Exception:  # noqa: BLE001 - queue may be mid-teardown
            pass


def _log_lr(ctx: "LoopContext", lr_schedule) -> None:
    """Log the learning rate that the MOST RECENT optimizer step applied
    (Lightning's LearningRateMonitor convention).  An optax schedule is
    indexed by completed updates when the update is computed, so update
    ``k`` used ``schedule(k-1)``."""
    if lr_schedule is None:
        return
    ctx.log_metrics(
        {"lr": float(lr_schedule(max(ctx.global_step - 1, 0)))}
    )


class _RunningMeanLogs:
    """Bounded per-epoch accumulator for device-scalar step logs.

    Keeps ONE live device buffer per metric (a running sum updated
    eagerly each step) instead of one dict of device scalars per
    micro-batch: at 10k steps/epoch the list form is tens of thousands
    of live tiny buffers plus a large end-of-epoch host sync.  The sum
    is carried in f32 regardless of the logged dtype — a bf16 running
    sum would stop absorbing per-step increments once it exceeds ~256x
    their size (7-bit mantissa), silently biasing long-epoch means.

    Non-finite step values (a NaN loss spike, an inf grad-norm log) are
    EXCLUDED from the mean — one poisoned step must not turn the whole
    epoch metric into NaN silently.  The exclusion happens on-device
    (``isfinite`` + ``where``, no host sync per step); the count of
    skipped values surfaces as ``nonfinite_count`` after :meth:`result`
    so telemetry can make the poisoning loud instead of hidden.
    """

    def __init__(self) -> None:
        self._sum: Optional[Dict[str, Any]] = None
        self._cnt: Optional[Dict[str, Any]] = None
        self._n = 0
        self.nonfinite_count = 0  # populated by result()

    def update(self, logs: Dict[str, Any]) -> None:
        if self._sum is None:
            self._sum, self._cnt = {}, {}
            for k, v in logs.items():
                v32 = jnp.asarray(v).astype(jnp.float32)
                finite = jnp.isfinite(v32)
                self._sum[k] = jnp.where(finite, v32, 0.0)
                self._cnt[k] = finite.astype(jnp.float32)
        else:
            for k in self._sum:
                v32 = jnp.asarray(logs[k]).astype(jnp.float32)
                finite = jnp.isfinite(v32)
                self._sum[k] = self._sum[k] + jnp.where(finite, v32, 0.0)
                self._cnt[k] = self._cnt[k] + finite.astype(jnp.float32)
        self._n += 1

    def update_stride(self, sums: Dict[str, Any], cnts: Dict[str, Any],
                      n: int) -> None:
        """Fold a megastep stride's ON-DEVICE accumulation into the
        epoch mean: ``sums``/``cnts`` are the finite-filtered f32 sums
        and finite counts the fused scan already reduced over its ``n``
        inner steps (``make_multi_step`` aux) — same math as ``n``
        :meth:`update` calls, paid as one device add per metric per
        stride instead of one per micro-batch."""
        if self._sum is None:
            self._sum = {k: jnp.asarray(v) for k, v in sums.items()}
            self._cnt = {k: jnp.asarray(v) for k, v in cnts.items()}
        else:
            for k in self._sum:
                self._sum[k] = self._sum[k] + sums[k]
                self._cnt[k] = self._cnt[k] + cnts[k]
        self._n += n

    def result(self) -> Dict[str, float]:
        if self._sum is None:
            return {}
        host_sum, host_cnt = jax.device_get((self._sum, self._cnt))
        out: Dict[str, float] = {}
        nonfinite = 0
        for k, s in host_sum.items():
            c = float(host_cnt[k])
            nonfinite += self._n - int(round(c))
            # Every value non-finite: nothing to average — report NaN
            # (loudly wrong) rather than a fabricated 0.
            out[k] = float(s) / c if c else float("nan")
        self.nonfinite_count = nonfinite
        return out


class _AsyncLogFetch:
    """Log-cadence metrics WITHOUT the host sync.

    The old path ran ``ctx.log_metrics(jax.device_get(logs))`` every
    ``log_every_n_steps`` — a blocking device→host fence that serialized
    the dispatch pipeline at exactly the cadence users log at.  This
    helper starts a device→host copy at the boundary
    (``copy_to_host_async``) and CONSUMES it at the next boundary (by
    which point the producing step has long finished, so ``device_get``
    returns without waiting).  Consequence, documented in
    docs/OBSERVABILITY.md: mid-fit consumers of step-cadence
    ``callback_metrics`` (CSV step rows, tune reports) see values one
    log interval late; epoch-end :meth:`flush` drains the tail, so
    post-fit metrics are identical to the synchronous path.
    """

    def __init__(self, ctx: "LoopContext"):
        self._ctx = ctx
        self._pending: Optional[Tuple[Dict[str, Any], Dict[str, float]]] = (
            None
        )

    def schedule(self, logs: Dict[str, Any],
                 extra: Optional[Dict[str, Any]] = None) -> None:
        """Consume the previous boundary's logs, then start this one's
        copy.  ``extra`` carries side values captured NOW (the lr of
        the step just taken — possibly still a lazy device scalar) so
        they stay paired with these logs when they land; device values
        in it ride the same async copy as the logs."""
        self.flush()
        for v in (*logs.values(), *(extra or {}).values()):
            start = getattr(v, "copy_to_host_async", None)
            if start is not None:
                try:
                    start()
                except Exception:  # noqa: BLE001 - the flush-time
                    # device_get is always correct; async is a hint.
                    pass
        self._pending = (logs, dict(extra or {}))

    def flush(self) -> None:
        """Land any in-flight logs into the context's metrics.  Called
        at the next boundary, at epoch end (BEFORE epoch means are
        logged — stale step values must not overwrite them), and before
        a drain checkpoint snapshots callback_metrics."""
        if self._pending is None:
            return
        logs, extra = self._pending
        self._pending = None
        with self._ctx.timed("log_fetch"):
            logs, extra = jax.device_get((logs, extra))
        self._ctx.log_metrics(logs)
        if extra:
            self._ctx.log_metrics(extra)


def init_train_state(
    module: TpuModule,
    tx,
    mesh,
    zero_stage: int,
    seed: int,
    use_preset: bool = True,
    shard_update: bool = False,
) -> Tuple[TrainState, Any]:
    """Build the (possibly ZeRO-sharded) initial train state.

    ``shard_update`` (the cross-replica sharded weight update,
    arXiv:2004.13336 — docs/PERFORMANCE.md "Optimizer-state precision &
    update sharding") annotates the OPTIMIZER state sharded over the
    batch axes while params stay replicated: on a pure-DP mesh the
    in/out shardings on the jitted step then act as sharding
    constraints on the update computation — GSPMD lowers the gradient
    all-reduce to reduce-scatter, each replica updates only its shard
    of the moments, and the new params all-gather back — so a
    replicated-optimizer mesh stops paying P× the update's HBM+wire
    traffic.  A no-op where ZeRO already shards (``zero_stage >= 1``).

    Params are initialized **on-device under jit** with the target
    shardings as ``out_shardings`` — a ZeRO-3 model never materializes
    unsharded anywhere (contrast: the reference ships full
    ``state_dict`` bytes to every worker, ``ray_ddp.py:339-353``).
    Determinism comes from the broadcast seed (≙ ``PL_GLOBAL_SEED``,
    reference ``ray_ddp.py:223``).
    """
    rng = jax.random.PRNGKey(seed)
    # Warm-start hook: a module with ``initial_params`` set (a host
    # pytree — e.g. weights imported from a torch/HF checkpoint,
    # utils/hf_import.py) starts the fit from those weights instead of
    # init_params(rng).  Passed as a jit ARGUMENT, never a closure
    # constant, so the arrays are transferred once, not baked into the
    # compiled executable.  The caller sets ``use_preset=False`` when a
    # resume checkpoint will overwrite the state anyway — shipping a
    # GPT-scale pytree to the mesh just to discard it is gigabytes of
    # wasted transfer per worker per restart.
    preset = getattr(module, "initial_params", None) if use_preset else None
    import collections.abc

    if preset is not None and isinstance(preset, collections.abc.Mapping):
        from ray_lightning_tpu.models.quant import is_quantized

        if is_quantized(preset):
            # int8 decode storage (models/quant.py) is inference-only:
            # the optimizer cannot step int8 weights, and silently
            # dequantizing would train an already-rounded model.
            raise ValueError(
                "initial_params are int8-quantized (decode storage); "
                "training needs the original float tree — keep it, or "
                "dequantize explicitly before warm-starting"
            )

    def make(r):
        params = module.init_params(r)
        return TrainState.create(params, tx)

    def make_from(params):
        return TrainState.create(params, tx)

    if mesh is None:
        if preset is not None:
            return make_from(jax.device_put(preset)), None
        return make(rng), None
    abstract = jax.eval_shape(make, rng)
    # The sharded-update path reuses the ZeRO-1 sharding computation —
    # stage 1 is exactly "optimizer state sharded, params replicated" —
    # but the run's SEMANTIC zero_stage stays 0 (grad-comm gating,
    # checkpoint metadata and module compute-path selection all key off
    # the semantic stage).
    sharding_stage = max(zero_stage, 1) if shard_update else zero_stage
    shardings = shardlib.state_shardings_for_module(
        module, abstract, mesh, sharding_stage
    )
    if preset is not None:
        placed = jax.device_put(preset, shardings.params)
        state = jax.jit(make_from, out_shardings=shardings)(placed)
    else:
        state = jax.jit(make, out_shardings=shardings)(rng)
    return state, shardings


def _place_batch(batch, mesh):
    if mesh is None:
        return batch
    return shardlib.make_global_batch(batch, mesh)


def _same_batch_shape(a: Any, b: Any) -> bool:
    """Structure + leaf-shape congruence — the stacking precondition."""
    ta, tb = jax.tree_util.tree_structure(a), jax.tree_util.tree_structure(b)
    if ta != tb:
        return False
    return all(
        getattr(x, "shape", None) == getattr(y, "shape", None)
        and getattr(x, "dtype", None) == getattr(y, "dtype", None)
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


def _grouped(loader, stack: int, stack_limit: Optional[int]):
    """Group a batch stream into megastep strides.

    Yields ``("stride", [b0..b{k-1}])`` for full shape-congruent groups
    of ``stack`` batches, ``("single", b)`` otherwise.  ``stack_limit``
    (a multiple of ``stack``, or ``None`` for unlimited) bounds the
    stream POSITION a stride may extend to: every batch emitted —
    strided or not — consumes budget, so a ragged-shape single slipping
    into the stream can never push a later stride across the
    limit/max_steps boundary the caller aligned the budget to.
    """
    if stack <= 1:
        for b in loader:
            yield ("single", b)
        return
    it = iter(loader)
    emitted = 0  # batches yielded so far == stream position of pending[0]
    pending: List[Any] = []
    while True:
        if stack_limit is not None and emitted + stack > stack_limit:
            # Stride budget exhausted: drain, then stream singles.
            for p in pending:
                yield ("single", p)
            emitted += len(pending)
            pending = []
            for b in it:
                yield ("single", b)
            return
        try:
            item = next(it)
        except StopIteration:
            for p in pending:  # partial tail → per-step fallback
                yield ("single", p)
            return
        if pending and not _same_batch_shape(pending[0], item):
            # Ragged boundary (last small batch, shape change): flush
            # what we have as singles; the newcomer may seed a stride.
            for p in pending:
                yield ("single", p)
            emitted += len(pending)
            pending = [item]
        else:
            pending.append(item)
        if len(pending) == stack:
            yield ("stride", pending)
            emitted += stack
            pending = []


def _prefetched(loader, place: Callable[[Any], Any], depth: int = 2,
                telemetry: Optional[Telemetry] = None, stack: int = 1,
                stack_limit: Optional[int] = None,
                place_stride: Optional[Callable[[list], Any]] = None):
    """Iterate ``loader`` with host→device placement running ``depth``
    batches ahead on a background thread.  Yields ``(placed, n)`` pairs:
    ``n == 1`` for ordinary batches, ``n == stack`` for megastep strides
    (``stack > 1``) — where the producer stacked ``stack`` host batches
    and shipped them as ONE device array via ``place_stride``.

    On TPU the step is async-dispatched, so the input pipeline is the
    first serial bottleneck: without prefetch every step pays the numpy
    slice + ``device_put`` latency on the critical path.  A thread is
    enough — placement releases the GIL during the host→HBM DMA.

    ``telemetry`` (producer-side accounting): total host→device
    placement seconds and batch count land in the counters, so the
    consumer's ``data_wait_ms`` (how long the LOOP stalled) can be read
    against how busy the producer actually was — a high place total with
    near-zero data wait means the prefetch depth is doing its job.

    Lifecycle: the generator's ``close()`` (run the loop's ``finally``
    — see ``run_fit``) signals the producer's stop event AND JOINS the
    thread, so a fit that raises mid-epoch (drain, chaos crash, user
    exception) never leaks an ``rlt-prefetch`` thread into the next
    attempt of an elastic respawn or the next fit of a tuner sweep.
    """
    import queue as pyqueue
    import threading

    grouped = _grouped(loader, stack, stack_limit)

    def _place(kind: str, payload: Any):
        if kind == "stride":
            return (place_stride(payload), len(payload))
        return (place(payload), 1)

    if depth < 1:
        yield from (_place(k, p) for k, p in grouped)
        return

    buf: pyqueue.Queue = pyqueue.Queue(maxsize=depth)
    stop = threading.Event()
    sentinel = object()
    errors: List[BaseException] = []

    def producer() -> None:
        try:
            for kind, payload in grouped:
                t0 = time.perf_counter()
                placed = _place(kind, payload)
                if telemetry is not None:
                    # Counter keys are producer-thread-private; the dict
                    # update itself is GIL-atomic.
                    telemetry.add_counter(
                        "prefetch_place_s", time.perf_counter() - t0
                    )
                    telemetry.add_counter("prefetch_batches", placed[1])
                while not stop.is_set():
                    try:
                        buf.put(placed, timeout=0.1)
                        break
                    except pyqueue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 - re-raised on consumer
            errors.append(e)
        finally:
            while not stop.is_set():
                try:
                    buf.put(sentinel, timeout=0.1)
                    break
                except pyqueue.Full:
                    continue

    thread = threading.Thread(
        target=producer, name="rlt-prefetch", daemon=True
    )
    thread.start()
    try:
        while True:
            item = buf.get()
            if item is sentinel:
                if errors:
                    raise errors[0]
                return
            yield item
    finally:
        stop.set()
        # Join, don't just signal: "no thread left behind" is the
        # contract the leak-regression test pins (the producer's put
        # loop polls the stop event every 0.1s, so this is bounded).
        thread.join(timeout=5.0)


def _run_validation(
    module: TpuModule,
    eval_step,
    loader,
    ctx: LoopContext,
    limit: int,
) -> Dict[str, float]:
    acc = _RunningMeanLogs()
    for i, batch in enumerate(loader):
        if limit >= 0 and i >= limit:
            break
        acc.update(
            eval_step(ctx.state.params, _place_batch(batch, ctx.mesh))
        )
        ctx.progress += 1  # liveness: eval batches count as forward motion
    return acc.result()


def run_fit(
    module: TpuModule,
    datamodule: TpuDataModule,
    config: FitConfig,
    callbacks: List[Callback],
    global_rank: int = 0,
    world_size: int = 1,
    mesh=None,
    mode: str = "gspmd",
    zero_stage: int = 0,
    grad_comm=None,
    telemetry=None,
    queue=None,
) -> Dict[str, Any]:
    """The full fit loop.  Returns the rank-0 result package.

    Result shape ≙ reference ``execute_remote``'s rank-0 return tuple
    (``ray_ddp.py:490-519``): state stream + callback metrics + best model
    path (+ callback states so driver-side callback objects reflect what
    happened remotely).  Every rank's package additionally carries its
    telemetry snapshot, so the driver can build the fleet-wide skew view
    (``trainer.telemetry_report``) — not just rank-0's numbers.

    Preemption (SIGTERM/SIGINT, a driver drain request, or the chaos
    plane's ``sigterm`` fault) does not crash the fit: the loop finishes
    the in-flight step, writes a step-granular drain checkpoint
    (``drain-step-*.ckpt``, sharded) and raises :class:`PreemptedError`
    — which the strategy converts into a budget-free elastic restart or
    a clean resumable raise (docs/FAULT_TOLERANCE.md).
    """
    enable_compile_cache()
    # Graceful-drain arming: clear any previous fit's flag (inline
    # strategies run many fits per process), mark a fit as in flight so
    # SIGTERM means "drain" rather than "exit", and — on the driver's
    # main thread only; worker children install theirs in _child_main —
    # take over the signal handlers for the duration of the fit.
    drain_mod.reset_drain()
    drain_mod.set_fit_active(True)
    _signals_installed = drain_mod.install_signal_handlers()
    chaos.set_rank(global_rank)
    try:
        return _run_fit_inner(
            module, datamodule, config, callbacks, global_rank,
            world_size, mesh, mode, zero_stage, grad_comm, telemetry,
            queue,
        )
    finally:
        drain_mod.set_fit_active(False)
        if _signals_installed:
            drain_mod.uninstall_signal_handlers()


def _run_fit_inner(
    module: TpuModule,
    datamodule: TpuDataModule,
    config: FitConfig,
    callbacks: List[Callback],
    global_rank: int,
    world_size: int,
    mesh,
    mode: str,
    zero_stage: int,
    grad_comm,
    telemetry,
    queue,
) -> Dict[str, Any]:
    tx = module.configure_optimizers()
    # configure_optimizers may return (tx, lr_schedule); careful — a bare
    # optax.GradientTransformation is itself a NamedTuple, so test for the
    # optimizer interface rather than tuple-ness.
    lr_schedule = None
    if isinstance(tx, tuple) and not hasattr(tx, "init"):
        tx, lr_schedule = tx[0], (tx[1] if len(tx) > 1 else None)
    accum = max(int(config.accumulate_grad_batches), 1)
    # Elastic resume (reshard-on-load): a sharded checkpoint records the
    # world size and accumulation factor it was trained at; resuming on
    # a DIFFERENT world size re-derives accum here — before the
    # optimizer wraps in MultiSteps — so the global batch per optimizer
    # step (and therefore the LR schedule, which indexes optimizer
    # steps) is invariant under N→M.  Per-step RNG needs no such fix:
    # it folds the resumed micro-step into the base key
    # (``fold_in(base_rng, micro_step)`` below), which never saw the
    # world size.
    resize_info = None
    if config.resume_from_checkpoint:
        from ray_lightning_tpu.utils import sharded_ckpt as _sc

        if _sc.is_sharded_ckpt(config.resume_from_checkpoint):
            resize_info = _elastic_resume_info(
                config.resume_from_checkpoint, world_size, accum
            )
    if resize_info is not None:
        accum = resize_info["accum"]
    inner_tx = tx
    if accum > 1:
        import optax

        # MultiSteps keeps the grad accumulator inside opt_state, so ZeRO
        # sharding, donation and checkpointing all see it as ordinary
        # optimizer state (params-shaped ⇒ the suffix-matching sharding
        # rule reuses the parameter specs).
        tx = optax.MultiSteps(tx, every_k_schedule=accum)

    ctx = LoopContext(config, global_rank, world_size, mesh, queue, tx)
    ctx.step_mode = mode
    ctx.zero_stage = zero_stage
    module.trainer = ctx
    module.precision = config.precision

    # Telemetry: on by default at the cheap tier (counters + step stats);
    # spans/export engage at tier "full" (telemetry= / RLT_TELEMETRY).
    n_chips = len(mesh.devices.flat) if mesh is not None else 1
    tel = Telemetry.build(
        telemetry, global_rank, world_size, n_chips=n_chips
    )
    ctx.telemetry = tel
    ctx.telemetry_dir = (
        tel.export_dir_for(config.default_root_dir) if tel.enabled
        else None
    )
    tel_stats = tel.step_stats
    if tel_stats is not None:
        tel_stats.configure_model(module)
    if resize_info is not None:
        _announce_resize(resize_info, tel, queue, global_rank)

    # Live observability plane (docs/OBSERVABILITY.md "Live monitoring"):
    # a heartbeat publisher thread (queue sink on workers, JSONL sink on
    # queue-less local fits), a rank-tagged log ring, and the crash
    # flight recorder — armed here, disarmed on the success path below;
    # the stage wrappers route uncaught exceptions through
    # ``flight_recorder.record_active_crash``.  Tier "off" installs
    # nothing: no thread, no handler, no files.
    from ray_lightning_tpu.telemetry.flight_recorder import FlightRecorder
    from ray_lightning_tpu.telemetry.heartbeat import HeartbeatPublisher
    from ray_lightning_tpu.telemetry.logs import RankLogHandler

    log_handler = (
        RankLogHandler(global_rank, queue=queue).install()
        if tel.enabled else None
    )
    heartbeat = HeartbeatPublisher.maybe_start(tel, ctx, queue, config)
    flight_recorder = FlightRecorder.maybe_install(
        tel, ctx, queue, log_handler=log_handler, heartbeat=heartbeat,
    )

    module.setup("fit")
    datamodule.set_shard(global_rank, world_size)
    # prepare_data is per-HOST work (downloads land on each host's local
    # filesystem — one actor per host is this framework's deployment
    # model), so every worker runs it; implementations should be
    # idempotent/locked like the reference's init_hook FileLock pattern
    # (examples/ray_ddp_tune.py:22-25).
    datamodule.prepare_data()
    datamodule.setup("fit")
    _call_hooks(callbacks, "setup", ctx, module, "fit")

    # Gradient-communication coercion (str | dict | GradCommConfig | None
    # — None reads the RLT_GRAD_COMM env bus, defaulting to full-width).
    # Resolution happens against the REAL mesh/stage shape and warns on
    # every downgrade; modules consult ``trainer.grad_sync_active`` to
    # pick per-device-safe compute paths inside the sync island.
    from ray_lightning_tpu.parallel import grad_sync as gsync

    # Backward-overlapped sync: the resolved trunk-segment count G is
    # visible to the module's forward via the trainer context even when
    # grad_sync itself is off (grad_comm=full) — pure segmentation is
    # bitwise-neutral, so the knob's schedule shape can be A/B'd
    # independently of the wire codec.
    overlap_segments = resolve_grad_overlap(config.grad_overlap_segments)
    ctx.grad_overlap_segments = overlap_segments
    grad_sync = gsync.maybe_build_grad_sync(
        module, mesh, grad_comm, mode=mode, zero_stage=zero_stage,
        overlap_segments=overlap_segments,
    )
    ctx.grad_sync_active = grad_sync is not None
    tel.set_meta("grad_overlap_segments", overlap_segments)
    # Wire accounting flows through the telemetry counters (the unified
    # report) — ``ctx.comm_stats`` stays as a compatibility view of the
    # same numbers, not a parallel bookkeeping path.
    if grad_sync is not None:
        grad_sync.register_telemetry(tel)
        ctx.comm_stats = grad_sync.stats()
    else:
        tel.set_meta("grad_sync_mode", "full")
        ctx.comm_stats = {"grad_sync_mode": "full"}

    # Cross-replica sharded weight update: resolved against the real
    # mesh/mode/stage (docs/PERFORMANCE.md "Optimizer-state precision &
    # update sharding"); recorded in telemetry so bench artifacts can
    # attribute the arm.
    shard_update = _resolve_update_sharding(config, mesh, mode, zero_stage)
    tel.set_meta("update_sharding", "on" if shard_update else "off")
    ctx.update_sharding_active = shard_update
    state, state_shardings = init_train_state(
        module, tx, mesh, zero_stage, config.seed,
        use_preset=not config.resume_from_checkpoint,
        shard_update=shard_update,
    )
    if grad_sync is not None:
        # Error-feedback residual (int8_ef): attached to BOTH the state
        # and its sharding tree before the step compiles, so the jit's
        # in/out shardings stay congruent with the donated state.
        state, state_shardings = grad_sync.attach_residual(
            state, state_shardings
        )
    start_epoch = 0
    resume_skip_batches = 0
    if config.resume_from_checkpoint:
        from ray_lightning_tpu.utils import sharded_ckpt

        if sharded_ckpt.is_sharded_ckpt(config.resume_from_checkpoint):
            # Sharded restart checkpoint, reshard-on-load: with this
            # run's shardings the index-selective reader places each
            # leaf straight onto the M-device mesh, each host reading
            # only the shard-file byte ranges overlapping its own
            # addressable shards (no full-model reassembly on ZeRO-3).
            # A structure mismatch (EF residual present on one side
            # only) falls back to the full host read; either way resume
            # works on any topology, including fewer workers than
            # wrote it.
            payload = sharded_ckpt.load_sharded(
                config.resume_from_checkpoint,
                shardings=state_shardings,
            )
        else:
            payload = load_state_stream(
                state_stream_from_file(config.resume_from_checkpoint)
            )
        host_state = payload["state"]
        if grad_sync is not None:
            # A stream written without EF (or from another world size)
            # gets a fresh zero residual; one written with EF resuming
            # into a full-width run sheds it — either way the resumed
            # tree stays congruent with this run's state template.
            host_state = grad_sync.reconcile_resumed_state(host_state)
        elif getattr(host_state, "grad_residual", None) is not None:
            from ray_lightning_tpu.core.module import TrainState as _TS

            host_state = _TS(
                host_state.params, host_state.opt_state, host_state.step
            )
        if resize_info is not None:
            # Accum re-derivation may have crossed the accum==1
            # boundary (the optax.MultiSteps wrapper appears or
            # vanishes) — re-wrap before the congruence-dependent
            # reconciliations below.
            host_state = _reconcile_multisteps(host_state, state)
        # Storage-format reconcile: an ``opt_state_dtype`` policy change
        # between runs (f32/bf16 moments ↔ block-scaled int8) changes
        # the opt-state TREE STRUCTURE, not just leaf dtypes — convert
        # before the per-leaf cast below (which requires congruence).
        host_state = _reconcile_opt_state_format(host_state, state)
        # Reconcile checkpoint dtypes with THIS run's state template: a
        # dtype-policy change between runs (e.g. AdamW mu f32 → bf16,
        # models/gpt.py ``mu_dtype``) must not leak the old dtype into
        # the new run — it would silently recompile the step against a
        # mixed-dtype state and diverge from a fresh run's numerics.
        host_state = jax.tree_util.tree_map(
            lambda tmpl, leaf: leaf.astype(tmpl.dtype)
            if (
                hasattr(tmpl, "dtype")
                and hasattr(leaf, "astype")
                and tmpl.dtype != leaf.dtype
            )
            else leaf,
            state,
            host_state,
        )
        if mesh is None:
            state = jax.device_put(host_state)
        else:
            state = jax.device_put(host_state, state_shardings)
        if payload.get("mid_epoch"):
            # Step-granular drain checkpoint: resume INSIDE the epoch it
            # was written in, skipping the micro-batches already trained
            # (loaders are epoch-seeded, so the order replays exactly).
            start_epoch = payload["epoch"]
            resume_skip_batches = int(payload.get("batch_in_epoch", 0))
            if (resize_info is not None and world_size != 1
                    and resize_info["old_world"]
                    != resize_info["new_world"]):
                import warnings

                # Per-host loader shards are keyed off the world size:
                # under N→M the epoch's row→host partition changes, so
                # position-based skipping cannot replay the exact
                # global rows.  Counters stay step-exact; data replay
                # is exact only at equal world size (or world 1).
                warnings.warn(
                    "mid-epoch elastic resume at a different world "
                    "size: this epoch's remaining rows are re-sharded "
                    "over the new worker set — some rows may repeat "
                    "or be skipped within the epoch"
                )
        else:
            start_epoch = payload["epoch"] + 1
            resume_skip_batches = 0
        # If the checkpoint already covers max_epochs the loop body never
        # runs; current_epoch must still report the work as done.
        ctx.current_epoch = max(start_epoch - 1, 0)
        if "micro_step" in payload:
            ctx.global_step = payload["global_step"]
            ctx.micro_step = payload["micro_step"]
        else:
            # Legacy streams predate the optimizer-step convention: their
            # "global_step" stored the MICRO-batch count.
            ctx.micro_step = payload["global_step"]
            ctx.global_step = payload["global_step"] // accum
        ctx.callback_metrics.update(payload.get("callback_metrics", {}))
        # Stateful callbacks (EarlyStopping patience, ModelCheckpoint
        # best-score/path, …) continue rather than reset on resume.
        for cb, cb_state in zip(
            callbacks, payload.get("callback_states", [])
        ):
            cb.load_state_dict(cb_state)
    ctx.state = state

    params_shardings = (
        state_shardings.params if state_shardings is not None else None
    )
    train_step = step_fns.build_train_step(
        module, tx, mesh, mode=mode, zero_stage=zero_stage,
        state_shardings=state_shardings, grad_sync=grad_sync,
    )
    # Megastep execution: fuse K micro-steps into one lax.scan dispatch
    # (docs/PERFORMANCE.md "Host dispatch & megastep").  The single-step
    # jit above stays alive as the exact-semantics fallback for partial
    # strides (epoch/limit/max_steps boundaries) and pinned chaos
    # injections — jit is lazy, so an all-strides fit never compiles it
    # twice... and an all-singles fit never compiles the scan.
    megastep_k = _resolve_megastep(config)
    multi_step = (
        step_fns.make_multi_step(
            module, tx, mesh, megastep_k, mode=mode,
            zero_stage=zero_stage, state_shardings=state_shardings,
            grad_sync=grad_sync,
        )
        if megastep_k > 1 else None
    )
    tel.set_meta("megastep", megastep_k)

    def _place_stride(batches: List[Any]):
        """K host micro-batches → one stacked device array (leaf shape
        (K, B, ...)) — a single transfer per stride."""
        if mesh is None:
            return jax.device_put(shardlib.stack_host_batches(batches))
        return shardlib.make_global_stacked_batch(batches, mesh)
    val_loader = datamodule.val_dataloader()
    eval_step = (
        step_fns.build_eval_step(
            module, mesh, "validation", mode=mode,
            params_shardings=params_shardings,
        )
        if val_loader is not None
        else None
    )

    module.on_fit_start()
    _call_hooks(callbacks, "on_fit_start", ctx, module)

    base_rng = jax.random.PRNGKey(config.seed)
    train_loader = datamodule.train_dataloader()
    stop = False
    flush_step = None  # built lazily on the first partial-window flush
    # Preemption plumbing: the coordinated drain-agreement collective
    # (multi-process meshes only — None is the zero-overhead local path)
    # and the drain finish-line itself.
    drain_poll = _make_drain_poll(mesh, world_size)
    # Async log-cadence fetch (see _AsyncLogFetch): scheduled at log
    # boundaries, consumed one boundary later, flushed before anything
    # that snapshots callback_metrics (epoch means, drain META, and —
    # via ctx.pending_log_flush — the crash flight bundle).
    log_fetch = _AsyncLogFetch(ctx)
    ctx.pending_log_flush = log_fetch.flush

    def _graceful_drain(mid_epoch: bool, batch_in_epoch: int):
        """Preemption finish-line: write the step-granular sharded
        drain checkpoint, retire the live plane with an orderly final
        beat, and exit with the distinguished PreemptedError the
        strategy converts into a budget-free restart or a clean raise.
        COLLECTIVE on multi-host meshes (save_shard + barrier) — only
        reached after every rank agreed to drain at this same step."""
        from ray_lightning_tpu.utils import sharded_ckpt

        ctx.phase = "draining"
        try:
            # In-flight async log fetch lands BEFORE the META snapshot
            # of callback_metrics below.
            log_fetch.flush()
        except Exception:  # noqa: BLE001 - never cost the drain
            pass
        reason = drain_mod.drain_reason() or "requested"
        drain_dir = config.restart_dir or os.path.join(
            config.default_root_dir, "preempt"
        )
        tag = os.path.join(
            drain_dir, f"drain-step-{ctx.micro_step:08d}.ckpt"
        )
        t0 = time.perf_counter()
        ckpt_path = None
        write_err = None
        try:
            ctx.flush_checkpoints()
            sharded_ckpt.save_shard(
                ctx.state, tag, global_rank, world_size
            )
        except Exception as e:  # noqa: BLE001 - the checkpoint is
            # sacrificed, never the drain itself
            write_err = e
        # EVERY rank reaches the barrier, write success or not: a rank
        # skipping it (its disk filled, say) would strand its peers in
        # the collective for the whole grace window.  A failed shard
        # write still yields a META'd-but-incomplete checkpoint, which
        # restart discovery's verification walks past by design.
        try:
            _mesh_barrier(mesh)
        except Exception as e:  # noqa: BLE001 - a peer died mid-drain
            write_err = write_err or e
        if write_err is None:
            try:
                if ctx.is_global_zero:
                    sharded_ckpt.save_meta(
                        ctx.state, tag, world_size,
                        extra={
                            "epoch": ctx.current_epoch,
                            "global_step": ctx.global_step,
                            "micro_step": ctx.micro_step,
                            "mid_epoch": mid_epoch,
                            "batch_in_epoch": batch_in_epoch,
                            # Elastic-resume contract: the world size
                            # and accum this state was trained at, so a
                            # resume on M != N devices can re-derive
                            # accum for global-batch invariance.
                            "world_size": world_size,
                            "accum": accum,
                            "drain_reason": reason,
                            "callback_metrics": dict(
                                ctx.callback_metrics
                            ),
                            "callback_states": [
                                cb.state_dict() for cb in callbacks
                            ],
                        },
                    )
                ckpt_path = tag
            except Exception as e:  # noqa: BLE001
                write_err = e
        if write_err is not None:
            import warnings

            warnings.warn(f"drain checkpoint write failed ({write_err!r})")
        drain_s = round(time.perf_counter() - t0, 4)
        tel.set_counter("drain_checkpoint_s", drain_s)
        if queue is not None:
            try:
                queue.put({
                    "type": "event", "kind": "drain",
                    "rank": global_rank, "ts": time.time(),
                    "message": (
                        f"rank {global_rank} drained on {reason} at "
                        f"micro_step {ctx.micro_step}"
                    ),
                    "ckpt": ckpt_path or "",
                })
            except Exception:  # noqa: BLE001 - queue may be mid-teardown
                pass
        # Final "done" beat: the monitor must read the coming silence
        # as an orderly exit, not flag a lost rank.
        if heartbeat is not None:
            heartbeat.stop(final=True)
        if flight_recorder is not None:
            flight_recorder.uninstall()
        if log_handler is not None:
            log_handler.uninstall()
        raise PreemptedError(
            f"fit preempted ({reason}) at micro_step {ctx.micro_step}; "
            + (f"drain checkpoint: {ckpt_path}" if ckpt_path
               else "no drain checkpoint could be written"),
            checkpoint=ckpt_path, step=ctx.micro_step,
            epoch=ctx.current_epoch, rank=global_rank, reason=reason,
            drain_s=drain_s,
        )

    # Agreement cadence: the multi-process poll is a collective whose
    # device_get would serialize host and device if run per step (the
    # overhead the telemetry sampler explicitly refuses to add), so it
    # runs every K micro-steps — K is a pure function of the shared
    # step counter, keeping every rank's collective call count aligned.
    # Worst-case drain latency is K steps, trivially inside any real
    # preemption grace window.  Single-process fits check the local
    # flag every step for free.
    drain_sync_every = max(
        int(os.environ.get("RLT_DRAIN_SYNC_EVERY", "8") or 8), 1
    )

    def _drain_agreed(local_wanted: bool = True,
                      sync_round: bool = True) -> bool:
        """One coordinated drain-agreement round.  Called at identical
        loop positions on every rank (the collective inside must line
        up across processes — ``sync_round`` must be identical fleet-
        wide at each call site)."""
        local = drain_mod.drain_requested() and local_wanted
        if drain_poll is not None:
            if not sync_round:
                return False  # off-cadence: no collective, no drain
            return drain_poll(local)
        return local
    # Host-side mirror of MultiSteps' window position: micro-batches since
    # the last optimizer update.  `micro_step % accum` is NOT equivalent
    # once a partial-window flush has reset the window mid-cycle.
    since_update = 0
    if config.resume_from_checkpoint and accum > 1:
        try:
            since_update = int(
                jax.device_get(ctx.state.opt_state.mini_step)
            )
        except AttributeError:
            since_update = ctx.micro_step % accum
    # First-use jit compiles of the two train programs (the fused scan
    # and the per-step fallback) can land MID-fit under megastep — a
    # partial tail stride or a chaos-degraded stride compiles the lazy
    # single-step program while progress is frozen for 20-40s at scale.
    # Flag those dispatches as a "compile" phase flip so the monitor's
    # per-phase exemption (telemetry/monitor.py) disarms the stall
    # watchdog instead of raising a false hang on a healthy rank.
    compiled_kinds: set = set()
    for epoch in range(start_epoch, config.max_epochs):
        ctx.current_epoch = epoch
        ctx.phase = "train"
        if hasattr(train_loader, "set_epoch"):
            train_loader.set_epoch(epoch)
        module.on_train_epoch_start(epoch)
        _call_hooks(callbacks, "on_train_epoch_start", ctx, module)

        epoch_mean = _RunningMeanLogs()
        # Mid-epoch drain resume: skip the micro-batches the drained run
        # already trained this epoch (the loader is epoch-seeded, so the
        # order replays identically); batch_idx stays ABSOLUTE within
        # the epoch so the limit checks below keep their meaning.
        skip = resume_skip_batches if epoch == start_epoch else 0
        # Cap the source BEFORE prefetching so the producer thread never
        # device-places batches past the limit/max_steps boundary.  The
        # +1 keeps one sentinel batch flowing so the in-loop checks (which
        # own the stop semantics) still observe the boundary crossing.
        cap = (
            max(config.limit_train_batches - skip, 0)
            if config.limit_train_batches >= 0 else None
        )
        if config.max_steps >= 0:
            # max_steps counts optimizer steps; the loop (and the cap)
            # run in micro-batches.  Position within the current window
            # comes from since_update (flushes reset it mid-cycle).
            remaining = max(
                (config.max_steps - ctx.global_step) * accum - since_update,
                0,
            )
            cap = remaining if cap is None else min(cap, remaining)
        src = iter(train_loader)
        if skip:
            src = itertools.islice(src, skip, None)
        source = src if cap is None else itertools.islice(src, cap + 1)
        # Megastep stride budget: only full K-strides lying ENTIRELY
        # inside the cap are fused (a multiple of K); the remainder —
        # partial strides at epoch/limit/max_steps boundaries — ships
        # per-step, so the in-loop boundary checks keep exact
        # "max_steps means max_steps" semantics.
        if megastep_k > 1:
            stack_limit = (
                None if cap is None else (cap // megastep_k) * megastep_k
            )
        else:
            stack_limit = 0
        last_logs: Dict[str, Any] = {}
        last_batch_idx = -1
        batch_idx = skip - 1  # absolute index of the last COMPLETED batch
        # Telemetry marks: ``t_mark`` is set at the end of each loop body,
        # so the gap to the next batch's arrival is the step stats' data
        # wait; the ``data_wait`` phase is the part of it blocked in the
        # (prefetched) input pipeline's next().
        t_mark = time.perf_counter()
        items = _prefetched(
            source, lambda b: _place_batch(b, mesh),
            telemetry=tel if tel.enabled else None,
            stack=megastep_k, stack_limit=stack_limit,
            place_stride=_place_stride,
        )
        try:
            batches = iter(items)
            while True:
                with tel.phase("data_wait") as wait_ph:
                    item = next(batches, None)
                if item is None:
                    break
                gbatch, n_inner = item
                data_wait_s = wait_ph.t0 + wait_ph.dur - t_mark
                if (
                    config.limit_train_batches >= 0
                    and batch_idx + 1 >= config.limit_train_batches
                ):
                    break
                # Check BEFORE executing: max_steps=0 trains zero steps.
                if (
                    config.max_steps >= 0
                    and ctx.global_step >= config.max_steps
                ):
                    stop = True
                    break
                if n_inner > 1 and chaos.step_fault_in_range(
                    ctx.micro_step, ctx.micro_step + n_inner,
                    epoch=epoch, rank=global_rank,
                ):
                    # A step-pinned chaos fault lands inside this stride:
                    # lower K to 1 around the injection — run the already
                    # -stacked micro-batches singly (device slices) so
                    # the fault fires at its exact inner-step index.
                    sub = [
                        (jax.tree_util.tree_map(
                            lambda x, j=j: x[j], gbatch), 1)
                        for j in range(n_inner)
                    ]
                else:
                    sub = [(gbatch, n_inner)]
                for gb, n in sub:
                    prev_micro = ctx.micro_step
                    # First use of either train program compiles inside
                    # the dispatch call below (host-blocking): flip the
                    # heartbeat phase so the monitor's per-phase stall
                    # arming (telemetry/monitor.py) treats the freeze as
                    # a compile, not a hang.
                    kind = "single" if n == 1 else "fused"
                    first_use = kind not in compiled_kinds
                    if first_use:
                        compiled_kinds.add(kind)
                        ctx.phase = "compile"
                    if n == 1:
                        # -- per-step path (exact boundary semantics) ----
                        # Chaos injection point: crash/hang/slow/sigterm
                        # pinned to (micro_step, epoch, rank) — near-zero
                        # cost unless RLT_FAULT is set.
                        chaos.fire("step", step=ctx.micro_step,
                                   epoch=epoch, rank=global_rank)
                        rng = jax.random.fold_in(base_rng, ctx.micro_step)
                        with tel.phase("compile" if first_use
                                       else "dispatch") as disp_ph:
                            ctx.state, logs = train_step(
                                ctx.state, gb, rng)
                        # Periodic device sampling: make THIS step's wall
                        # time include device execution (async dispatch
                        # hides it otherwise).  Never per-step — that
                        # would serialize host and device and become the
                        # overhead telemetry promises not to add.
                        sampled = (tel_stats is not None
                                   and tel_stats.should_sample())
                        if sampled:
                            with tel.phase("sample_sync"):
                                jax.block_until_ready(logs)
                        epoch_mean.update(logs)
                        ctx.micro_step += 1
                        ctx.progress += 1  # heartbeat liveness counter
                        since_update += 1
                        if since_update == accum:
                            ctx.global_step += 1  # optimizer step done
                            since_update = 0
                        batch_idx += 1
                    else:
                        # -- megastep stride: ONE dispatch, n micro-steps
                        # fused in a lax.scan, metrics accumulated on
                        # device; the host does integer bookkeeping only.
                        with tel.phase("compile" if first_use
                                       else "megastep") as disp_ph:
                            ctx.state, saux = multi_step(
                                ctx.state, gb, base_rng,
                                np.int32(ctx.micro_step),
                            )
                        sampled = (
                            tel_stats is not None
                            and tel_stats.should_sample_stride(n)
                        )
                        if sampled:
                            with tel.phase("sample_sync"):
                                jax.block_until_ready(saux)
                        epoch_mean.update_stride(
                            saux["sum"], saux["cnt"], n
                        )
                        logs = saux["last"]
                        ctx.micro_step += n
                        ctx.progress += n
                        since_update += n
                        ctx.global_step += since_update // accum
                        since_update %= accum
                        batch_idx += n
                        tel.add_counter("megastep_dispatches", 1)
                    tel.add_counter("train_dispatches", 1)
                    if ctx.phase == "compile":
                        ctx.phase = "train"
                    # Log cadence: identical to the old `% == 0` on the
                    # per-step path; a stride rounds the boundary to its
                    # end (stride-final logs).  The fetch is ASYNC —
                    # copy-to-host starts here, lands at the next
                    # boundary/epoch end — so logging never serializes
                    # host and device (docs/OBSERVABILITY.md).
                    n_log = config.log_every_n_steps
                    if n_log and drain_mod.sync_point_crossed(
                        prev_micro, ctx.micro_step, n_log
                    ):
                        extra = (
                            # Lazily-enqueued device scalar: the fetch
                            # materializes it at the NEXT boundary, so
                            # logging lr never fences the just-dispatched
                            # train program (a float() here would).
                            {"lr": lr_schedule(
                                max(ctx.global_step - 1, 0))}
                            if lr_schedule is not None else None
                        )
                        log_fetch.schedule(logs, extra)
                    with tel.phase("callbacks"):
                        _call_hooks(
                            callbacks, "on_train_batch_end", ctx, module,
                            logs, batch_idx,
                        )
                    last_logs, last_batch_idx = logs, batch_idx
                    t_end = time.perf_counter()
                    if tel_stats is not None:
                        leaves = jax.tree_util.tree_leaves(gb)
                        shape = (getattr(leaves[0], "shape", None)
                                 if leaves else None)
                        if n == 1:
                            tel_stats.record_step(
                                step_s=t_end - t_mark,
                                data_wait_s=data_wait_s,
                                dispatch_s=disp_ph.dur,
                                examples=int(shape[0]) if shape else 1,
                                sampled=sampled, compiled=first_use,
                            )
                        else:
                            tel_stats.record_stride(
                                stride_s=t_end - t_mark,
                                data_wait_s=data_wait_s,
                                dispatch_s=disp_ph.dur,
                                examples=(
                                    int(shape[0]) * int(shape[1])
                                    if shape and len(shape) > 1 else n
                                ),
                                k=n, sampled=sampled, compiled=first_use,
                            )
                        if first_use and n == 1:
                            # Roofline cross-check, once per program:
                            # feed the XLA cost_analysis FLOPs the
                            # ledger captured for the program that just
                            # compiled back into StepStats — MFU flips
                            # to a measured basis, and the drift guard
                            # flags a stale analytic accounting (>10%
                            # disagreement).  Fused megasteps are
                            # excluded: XLA costs the scanned body
                            # trip-count-agnostically, which would
                            # poison a per-example basis.
                            flops = (
                                program_ledger.ledger()
                                .site_flops_latest("train/step")
                            )
                            if flops:
                                tel_stats.configure_measured_flops(
                                    flops / max(
                                        int(shape[0]) if shape else 1, 1
                                    )
                                )
                    t_mark = t_end
                    # Chaos-degraded slices after the first: the data was
                    # already resident, only the first slice paid wait.
                    data_wait_s = 0.0
                    # Drain agreement (mesh-coordinated): a SIGTERM on
                    # ANY rank drains every rank at the same boundary.
                    # The multi-process collective runs whenever the
                    # advance crossed the K-step sync cadence (micro_step
                    # is identical across ranks, strides are config-
                    # deterministic — call counts stay aligned);
                    # single-process fits poll the local flag for free.
                    if _drain_agreed(
                        sync_round=drain_mod.sync_point_crossed(
                            prev_micro, ctx.micro_step, drain_sync_every
                        )
                    ):
                        _graceful_drain(
                            mid_epoch=True, batch_in_epoch=batch_idx + 1
                        )
        finally:
            # Deterministic producer shutdown: signal + JOIN the
            # rlt-prefetch thread even when the body raised (drain,
            # chaos, user exception) — a leaked producer would survive
            # into the next elastic attempt / tuner fit.
            items.close()

        # Flush a partial accumulation window (Lightning semantics: the
        # last incomplete window of an epoch still steps, from the mean
        # of the micro-grads seen).  Skipped when stopping at max_steps —
        # that contract promises exactly max_steps optimizer updates.
        if (
            accum > 1
            and not stop
            and int(jax.device_get(ctx.state.opt_state.mini_step)) > 0
        ):
            if flush_step is None:
                flush_step = _build_accum_flush(
                    inner_tx, mesh, state_shardings
                )
            ctx.state = flush_step(ctx.state)
            ctx.global_step += 1
            since_update = 0  # the flush reset MultiSteps' window
            # The flush IS an optimizer step: step-cadence callbacks
            # (EMA shadow updates) must observe it — via the dedicated
            # on_accumulation_flush hook, NOT a re-broadcast of
            # on_train_batch_end, which would double-fire batch-cadence
            # side effects (CSV rows, tune reports) for an event they
            # already saw.  Without this, the final epoch's flushed
            # update never entered the EMA average.
            _call_hooks(
                callbacks, "on_accumulation_flush", ctx, module,
                last_logs, last_batch_idx,
            )

        # Land the tail of the async log fetch BEFORE the epoch means:
        # a stale step value arriving later would overwrite them.
        log_fetch.flush()
        train_metrics = epoch_mean.result()
        ctx.log_metrics(train_metrics)
        _log_lr(ctx, lr_schedule)
        if tel.enabled:
            # NaN/inf step logs were excluded from the epoch means above;
            # surface the count so the exclusion is loud, not silent.
            if epoch_mean.nonfinite_count:
                tel.add_counter(
                    "nonfinite_logs", epoch_mean.nonfinite_count
                )
            # Headline telemetry rides callback_metrics on every plain
            # fit (step_time_ms, data_wait_ms, examples_per_sec, mfu…).
            ctx.log_metrics(tel.headline_metrics())
        module.on_train_epoch_end(epoch, train_metrics)

        # -- validation ----------------------------------------------------
        if (
            eval_step is not None
            and (epoch + 1) % config.check_val_every_n_epoch == 0
        ):
            ctx.phase = "validation"
            with tel.phase("validation", epoch=epoch):
                val_metrics = _run_validation(
                    module, eval_step, val_loader, ctx,
                    config.limit_val_batches,
                )
            ctx.phase = "train"
            ctx.log_metrics(val_metrics)
            module.on_validation_epoch_end(val_metrics)
            _call_hooks(callbacks, "on_validation_epoch_end", ctx, module)

        _call_hooks(callbacks, "on_train_epoch_end", ctx, module)

        # Elastic-restart checkpoint — SHARDED, no all-gather: each host
        # writes only its addressable shards (utils/sharded_ckpt.py), so a
        # ZeRO-3 run's restart cost stays O(state/hosts) per host instead
        # of replicating the world every restart_every_n_epochs.
        if (
            config.restart_dir
            and (epoch + 1) % (config.restart_every_n_epochs or 1) == 0
        ):
            from ray_lightning_tpu.utils import sharded_ckpt

            tag = os.path.join(
                config.restart_dir, f"restart-epoch-{epoch:06d}.ckpt"
            )
            sharded_ckpt.save_shard(
                ctx.state, tag, global_rank, world_size
            )
            # Barrier before the completeness marker: META must only
            # appear once every host's shard file is durable.
            _mesh_barrier(mesh)
            if ctx.is_global_zero:
                sharded_ckpt.save_meta(
                    ctx.state, tag, world_size,
                    extra={
                        "epoch": ctx.current_epoch,
                        "global_step": ctx.global_step,
                        "micro_step": ctx.micro_step,
                        "world_size": world_size,
                        "accum": accum,
                        "callback_metrics": dict(ctx.callback_metrics),
                        "callback_states": [
                            cb.state_dict() for cb in callbacks
                        ],
                    },
                )
                # Keep the newest TWO complete checkpoints (this one +
                # its predecessor): previous-good fallback needs a
                # predecessor to fall back TO when the newest turns out
                # corrupt at resume time.  Anything older is disk growth.
                _prune_restart_dir(config.restart_dir, keep=2)

        # Stream per-epoch metrics to the driver (live callback_metrics on
        # the driver trainer — extends the reference, which only streamed
        # via Tune callbacks).
        if queue is not None and ctx.is_global_zero:
            # ``rank`` rides along so the driver can refuse metric
            # updates from anything but rank 0 (Trainer._on_stream_item
            # routes by type AND origin — a buggy/rogue worker must not
            # clobber driver metrics).
            queue.put(
                {
                    "type": "metrics",
                    "rank": ctx.global_rank,
                    "epoch": epoch,
                    "metrics": dict(ctx.callback_metrics),
                }
            )

        # Epoch-boundary drain point: a request that landed during
        # validation (or between epochs) is honored here — unless the
        # fit is finishing anyway, in which case completing IS the
        # cleanest drain.  `more_epochs` is identical on every rank
        # (config + mesh-global should_stop), keeping the agreement
        # collective aligned.
        more_epochs = (epoch + 1) < config.max_epochs and not (
            stop or ctx.should_stop
        )
        if _drain_agreed(local_wanted=more_epochs):
            _graceful_drain(mid_epoch=False, batch_in_epoch=0)

        if stop or ctx.should_stop:
            break

    # "closing": no step progress from here on is LEGITIMATE (flush,
    # final gather, serialization) — the RunMonitor exempts this phase
    # from stall flagging; the phase change itself counts as progress.
    ctx.phase = "closing"
    # Every async checkpoint write must be durable (and any failure
    # raised) BEFORE on_fit_end consumers run — the standard
    # load-best-at-fit-end pattern reads best_model_path there.
    ctx.flush_checkpoints()
    module.on_fit_end()
    _call_hooks(callbacks, "on_fit_end", ctx, module)
    ctx.close_checkpoint_writer()
    module.teardown("fit")
    _call_hooks(callbacks, "teardown", ctx, module, "fit")
    datamodule.teardown("fit")

    # -- rank-0 result package (≙ ray_ddp.py:490-519) -----------------------
    # The gather is collective: every rank participates, then only rank 0
    # serializes and ships the bytes.
    gathered = ctx._gathered_state()
    state_stream = None
    if ctx.is_global_zero:
        # Seconds at LM scale (PERF.md section 7): a named phase, and
        # before the export and the snapshot below so that both carry it.
        with tel.phase("result_package", "fit"):
            state_stream = to_state_stream(gathered)
    _maybe_export_telemetry(tel, ctx.telemetry_dir)
    # Retire the live plane on the success path: a final "done" beat so
    # the monitor reads the coming silence as completion (not a hang),
    # then disarm the crash recorder and the log ring.
    if heartbeat is not None:
        heartbeat.stop(final=True)
    if flight_recorder is not None:
        flight_recorder.uninstall()
    if log_handler is not None:
        log_handler.uninstall()
    # Snapshots ride EVERY rank's package (small dicts), so the driver
    # can aggregate min/max/mean across the fleet, not just rank 0.
    tel_snapshot = tel.snapshot()
    if not ctx.is_global_zero:
        return {"rank": global_rank, "telemetry": tel_snapshot}
    best_path = ""
    for cb in callbacks:
        if isinstance(cb, ModelCheckpoint):
            best_path = cb.best_model_path
            break
    return {
        "rank": 0,
        "state_stream": state_stream,
        "callback_metrics": {
            k: float(v) for k, v in ctx.callback_metrics.items()
        },
        "logged_metrics": {
            k: float(v) for k, v in ctx.logged_metrics.items()
        },
        "best_model_path": best_path,
        "callback_states": [cb.state_dict() for cb in callbacks],
        "epochs_run": ctx.current_epoch + 1,
        "global_step": ctx.global_step,
        "micro_step": ctx.micro_step,
        "comm_stats": dict(ctx.comm_stats),
        "telemetry": tel_snapshot,
    }


def _resolve_params(
    module: TpuModule,
    config: FitConfig,
    mesh,
    params_stream: Optional[bytes],
    ckpt_path: Optional[str],
    zero_stage: int = 0,
):
    """Parameter source for fit-less eval/predict (≙ test-without-fit,
    reference ``test_ddp_sharded.py:108-116``).

    Placement honors the module's TP specs and ZeRO-3 param sharding —
    a sharded model is never replicated onto every device just to eval
    (returns ``(params, params_shardings)``; shardings are ``None`` off
    -mesh).
    """
    if ckpt_path:
        payload = load_state_stream(state_stream_from_file(ckpt_path))
        host_params = payload["state"].params
    elif params_stream is not None:
        host_params = load_state_stream(params_stream)
    else:
        host_params = None
    if mesh is None:
        if host_params is None:
            params = jax.jit(module.init_params)(
                jax.random.PRNGKey(config.seed)
            )
        else:
            params = jax.device_put(host_params)
        return params, None
    abstract = (
        jax.eval_shape(module.init_params, jax.random.PRNGKey(config.seed))
        if host_params is None
        else jax.eval_shape(lambda: host_params)
    )
    shardings = shardlib.params_shardings_for_module(
        module, abstract, mesh, zero_stage
    )
    if host_params is None:
        params = jax.jit(
            module.init_params, out_shardings=shardings
        )(jax.random.PRNGKey(config.seed))
    else:
        params = jax.device_put(host_params, shardings)
    return params, shardings


def run_eval(
    module: TpuModule,
    datamodule: TpuDataModule,
    config: FitConfig,
    callbacks: List[Callback],
    kind: str = "validation",
    global_rank: int = 0,
    world_size: int = 1,
    mesh=None,
    mode: str = "gspmd",
    zero_stage: int = 0,
    params_stream: Optional[bytes] = None,
    ckpt_path: Optional[str] = None,
    telemetry=None,
    queue=None,
) -> Dict[str, Any]:
    """Validation/test loop (≙ reference ``start_evaluating``,
    ``ray_ddp.py:283-286``)."""
    enable_compile_cache()
    stage = "validate" if kind == "validation" else "test"
    ctx = LoopContext(config, global_rank, world_size, mesh, queue)
    ctx.step_mode = mode
    ctx.zero_stage = zero_stage
    module.trainer = ctx
    n_chips = len(mesh.devices.flat) if mesh is not None else 1
    tel = Telemetry.build(
        telemetry, global_rank, world_size, n_chips=n_chips
    )
    ctx.telemetry = tel
    ctx.telemetry_dir = (
        tel.export_dir_for(config.default_root_dir) if tel.enabled
        else None
    )
    module.setup(stage)
    datamodule.set_shard(global_rank, world_size)
    datamodule.setup(stage)
    _call_hooks(callbacks, "setup", ctx, module, stage)

    params, params_shardings = _resolve_params(
        module, config, mesh, params_stream, ckpt_path, zero_stage
    )
    ctx.state = TrainState(params, None, 0)

    loader = (
        datamodule.val_dataloader()
        if kind == "validation"
        else datamodule.test_dataloader()
    )
    if loader is None:
        raise ValueError(f"datamodule provides no {kind} dataloader")
    eval_step = step_fns.build_eval_step(
        module, mesh, kind, mode=mode, params_shardings=params_shardings
    )
    with tel.phase("validation", kind=kind):
        metrics = _run_validation(
            module, eval_step, loader, ctx, config.limit_val_batches
        )
    ctx.log_metrics(metrics)
    module.teardown(stage)
    _call_hooks(callbacks, "teardown", ctx, module, stage)
    _maybe_export_telemetry(tel, ctx.telemetry_dir)
    if not ctx.is_global_zero:
        return {"rank": global_rank, "telemetry": tel.snapshot()}
    return {
        "rank": 0,
        "callback_metrics": metrics,
        "telemetry": tel.snapshot(),
    }


def run_predict(
    module: TpuModule,
    datamodule: TpuDataModule,
    config: FitConfig,
    global_rank: int = 0,
    world_size: int = 1,
    mesh=None,
    zero_stage: int = 0,
    params_stream: Optional[bytes] = None,
    ckpt_path: Optional[str] = None,
    telemetry=None,
) -> Dict[str, Any]:
    """Prediction loop (≙ reference ``start_predicting``, ``ray_ddp.py:287-289``).

    Every worker returns its host-local output shards; the driver
    concatenates in rank order (an upgrade over the reference, which only
    returned rank-0 results).
    """
    enable_compile_cache()
    tel = Telemetry.build(
        telemetry, global_rank, world_size,
        n_chips=len(mesh.devices.flat) if mesh is not None else 1,
    )
    module.setup("predict")
    datamodule.set_shard(global_rank, world_size)
    datamodule.setup("predict")
    params, params_shardings = _resolve_params(
        module, config, mesh, params_stream, ckpt_path, zero_stage
    )
    predict_step = step_fns.build_predict_step(
        module, mesh, params_shardings=params_shardings
    )
    loader = datamodule.predict_dataloader() or datamodule.test_dataloader()
    if loader is None:
        raise ValueError("datamodule provides no predict/test dataloader")

    outputs: List[np.ndarray] = []
    for batch in loader:
        with tel.phase("dispatch"):
            out = predict_step(params, _place_batch(batch, mesh))
        # Host-local rows only: each host contributes its addressable
        # shards (its own slice of the global batch), ordered by shard
        # index so rows stay in loader order within the host.
        with tel.phase("host_transfer"):
            if mesh is not None and world_size > 1:
                shards = sorted(
                    out.addressable_shards,
                    key=lambda s: s.index[0].start or 0,
                )
                local = [s.data for s in shards]
                outputs.append(np.concatenate(jax.device_get(local)))
            else:
                outputs.append(np.asarray(jax.device_get(out)))
    module.teardown("predict")
    _maybe_export_telemetry(
        tel, tel.export_dir_for(config.default_root_dir)
        if tel.enabled else None,
    )
    # Per-batch arrays (NOT pre-concatenated): each global batch is split
    # host-contiguously by NumpyLoader, so the driver must interleave
    # ranks batch-by-batch to recover dataset row order.
    return {
        "rank": global_rank,
        "prediction_batches": outputs,
        "telemetry": tel.snapshot(),
    }

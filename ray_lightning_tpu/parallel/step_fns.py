"""Jitted step-function builders — the gradient-sync hot path.

≙ the reference's entire L2 collective layer: where torch DDP wraps the
model so backward triggers NCCL bucketed all-reduce (wrap at reference
``ray_ddp.py:483``, backend init ``ray_ddp.py:430-433``), here the
data-parallel mean **is part of the compiled program**:

* **GSPMD flavor** (``mode="gspmd"``, ≙ ``RayPlugin``/DDP): the batch is
  sharded over the ``data`` mesh axis, the loss is a mean over the global
  batch, and ``jax.grad`` of that mean *is* the all-reduced gradient — XLA
  inserts and schedules the collectives (overlapped with compute on ICI).
  ZeRO sharding arrives purely via in/out shardings on the train state.

* **shard_map flavor** (``mode="shard_map"``, ≙ ``HorovodRayPlugin``): the
  per-device program is explicit SPMD — each device computes grads on its
  shard and calls ``jax.lax.pmean`` (the ring-all-reduce analogue of
  ``hvd.allreduce``, reference ``ray_horovod.py:196``).  Numerically
  equivalent; exists as the second execution flavor and as the
  explicitly-scheduled escape hatch.

Both flavors donate the input state (buffers are reused in-place on HBM)
and return (new_state, metrics) with metrics mesh-global, so every host
logs identical values and callbacks (early stopping) agree without extra
broadcasts.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_lightning_tpu.core.module import TpuModule, TrainState
from ray_lightning_tpu.telemetry.program_ledger import ledgered_jit
from . import sharding as shardlib

__all__ = [
    "build_train_step",
    "make_multi_step",
    "build_eval_step",
    "build_predict_step",
]


def _refuse_sharded_state(shardings: Any, where: str) -> None:
    """shard_map flavors replicate params/state on every device; refuse
    non-trivial shardings loudly rather than silently resharding."""
    nontrivial = [
        sh.spec
        for sh in jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        if isinstance(sh, NamedSharding)
        and any(e is not None for e in sh.spec)
    ]
    if nontrivial:
        raise ValueError(
            f"mode='{where}' (HorovodRayStrategy flavor) replicates the "
            f"state and cannot honor shardings (e.g. {nontrivial[0]}); "
            "drop param_partition_specs / model-parallel mesh axes / "
            "zero_stage or use the gspmd flavor."
        )


def _loss_and_grads(module: TpuModule, params, batch, rng):
    def loss_fn(p):
        loss, logs = module.training_step(p, batch, rng)
        return loss, logs

    (loss, logs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    logs = dict(logs)
    logs.setdefault("loss", loss)
    return grads, logs


def _gspmd_raw_step(module: TpuModule, tx, grad_sync: Optional[Any]):
    """The unjitted gspmd step body — shared by the single-step jit and
    the megastep scan (both must train the SAME program or parity dies).
    """
    if grad_sync is not None:
        synced = grad_sync.build_synced_grad_fn()
        wire_bytes = float(grad_sync.bytes_per_step)

        def raw_step(state: TrainState, batch, rng):
            if grad_sync.use_ef:
                grads, logs, new_resid = synced(
                    state.params, state.grad_residual, batch, rng
                )
            else:
                grads, logs = synced(state.params, batch, rng)
                new_resid = state.grad_residual
            logs = dict(logs)
            # Wire accounting rides the step logs so the per-step
            # bytes-on-wire land in callback_metrics/bench artifacts.
            logs["grad_sync_bytes"] = jnp.float32(wire_bytes)
            new_state = state.apply_gradients(grads, tx)
            new_state = TrainState(
                new_state.params, new_state.opt_state, new_state.step,
                new_resid,
            )
            return new_state, logs
    else:
        def raw_step(state: TrainState, batch, rng):
            grads, logs = _loss_and_grads(
                module, state.params, batch, rng
            )
            new_state = state.apply_gradients(grads, tx)
            return new_state, logs

    return raw_step


def _single_device_raw_step(module: TpuModule, tx):
    def raw_step(state: TrainState, batch, rng):
        grads, logs = _loss_and_grads(module, state.params, batch, rng)
        return state.apply_gradients(grads, tx), logs

    return raw_step


def _shard_map_raw_step(
    module: TpuModule, tx, mesh: Mesh, zero_stage: int,
    state_shardings: Optional[Any],
):
    """The unjitted shard_map step (explicit per-device collectives) —
    shared by the single-step jit and the megastep scan."""
    # The shard_map flavor replicates the train state on every device
    # (the Horovod duality: explicit per-device collectives, no state
    # sharding).  Combining it with ZeRO or TP-annotated modules would
    # silently reshard — refuse loudly instead (VERDICT weak #7).
    if zero_stage > 0:
        raise ValueError(
            "mode='shard_map' (HorovodRayStrategy) replicates the "
            f"train state and cannot honor zero_stage={zero_stage}; "
            "use the gspmd flavor (RayShardedStrategy) for ZeRO "
            "sharding."
        )
    _refuse_sharded_state(state_shardings, "shard_map")

    # Shard the batch over every batch-parallel axis the mesh actually
    # has (matching make_global_batch), not a hard-coded "data".
    batch_axes = shardlib.data_axes(mesh)
    if not batch_axes:
        raise ValueError(
            "shard_map mode needs a data/fsdp mesh axis to shard the "
            f"batch over; mesh axes = {mesh.axis_names}"
        )
    data_axis = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    repl_spec = P()
    batch_spec = P(data_axis)

    def per_device_step(state: TrainState, batch, rng):
        # The explicit all-reduce of the Horovod duality: each device
        # differentiates its LOCAL mean loss, then pmean's the grads
        # across the data axis (hvd.allreduce ≙ collective over ICI).
        # check_vma=False makes this formulation version-stable: it
        # disables the automatic replicated-param cotangent psum (so
        # the explicit pmean never double-counts) and skips the
        # output-replication inference, which is satisfied by
        # construction — grads and logs are pmean'd, so every device
        # computes identical updates.
        def loss_fn(p):
            loss, logs = module.training_step(p, batch, rng)
            return loss, logs

        (loss, logs), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        grads = jax.lax.pmean(grads, axis_name=data_axis)
        logs = dict(logs)
        logs.setdefault("loss", loss)
        logs = jax.lax.pmean(logs, axis_name=data_axis)
        new_state = state.apply_gradients(grads, tx)
        return new_state, logs

    return jax.shard_map(
        per_device_step,
        mesh=mesh,
        in_specs=(repl_spec, batch_spec, repl_spec),
        out_specs=(repl_spec, repl_spec),
        check_vma=False,
    )


def build_train_step(
    module: TpuModule,
    tx,
    mesh: Optional[Mesh],
    mode: str = "gspmd",
    zero_stage: int = 0,
    state_shardings: Optional[Any] = None,
    grad_sync: Optional[Any] = None,
) -> Callable[[TrainState, Any, jax.Array], Tuple[TrainState, dict]]:
    """Compile one optimizer step over the mesh.

    Returns ``step(state, batch, rng) -> (new_state, metrics)``.
    ``batch`` must already be device-placed (global jax.Arrays sharded on
    the data axis for gspmd; see :func:`..sharding.make_global_batch`).

    ``grad_sync`` (a resolved :class:`..grad_sync.GradSync`, gspmd mode
    only) replaces the implicit full-width gradient all-reduce with the
    explicit bucketed/quantized pipeline: a shard_map island computes
    per-device partial grads and runs the compressed collectives, then
    the optimizer update continues under GSPMD (ZeRO-1 state sharding
    composes unchanged).  With error feedback the state must already
    carry its residual (``GradSync.attach_residual``).
    """
    if mesh is None:
        # Single-device path (driver-local smoke tests, ≙ non-distributed
        # Lightning fit).
        return ledgered_jit(
            _single_device_raw_step(module, tx), site="train/step",
            arg_names=("state", "batch", "rng"), donate_argnums=0,
        )

    if mode == "gspmd":
        repl = shardlib.replicated(mesh)
        if state_shardings is None:
            # A single sharding acts as a pytree prefix: replicate the
            # whole train state (plain DDP, zero_stage=0).
            state_shardings = repl
        batch_sh = shardlib.batch_sharding(mesh)
        raw_step = _gspmd_raw_step(module, tx, grad_sync)

        # in/out shardings: state keeps its (possibly ZeRO-sharded) layout,
        # batch arrives data-sharded, rng + metrics replicated.
        step = ledgered_jit(
            raw_step, site="train/step",
            arg_names=("state", "batch", "rng"),
            in_shardings=(state_shardings, batch_sh, repl),
            out_shardings=(state_shardings, repl),
            donate_argnums=0,
        )
        return step

    if mode == "shard_map":
        sharded = _shard_map_raw_step(
            module, tx, mesh, zero_stage, state_shardings
        )
        return ledgered_jit(
            sharded, site="train/step",
            arg_names=("state", "batch", "rng"), donate_argnums=0,
        )

    raise ValueError(f"Unknown step mode {mode!r} (expected gspmd|shard_map)")


def make_multi_step(
    module: TpuModule,
    tx,
    mesh: Optional[Mesh],
    k: int,
    mode: str = "gspmd",
    zero_stage: int = 0,
    state_shardings: Optional[Any] = None,
    grad_sync: Optional[Any] = None,
) -> Callable[[TrainState, Any, jax.Array, Any], Tuple[TrainState, dict]]:
    """Compile a **megastep**: ``k`` micro-steps fused into ONE program.

    ``multi(state, kbatch, base_rng, start) -> (new_state, aux)`` where
    ``kbatch`` is ``k`` pre-staged micro-batches stacked on a new leading
    axis (leaf shape ``(k, B, ...)``, sharded ``P(None, data)`` on a
    mesh — :func:`..sharding.make_global_stacked_batch`), ``base_rng`` is
    the fit's base PRNG key and ``start`` the micro-step index of the
    stride's first inner step (a traced int32 scalar — NOT static, so
    every stride reuses one executable).

    The inner step is the SAME raw step the single-step path jits
    (``_gspmd_raw_step`` / ``_shard_map_raw_step``), scanned with
    ``lax.scan``; the per-step RNG is ``fold_in(base_rng, start + i)``
    — exactly what the per-step loop computes on the host — so the
    trained trajectory is identical up to float association order.

    Metric bookkeeping stays ON DEVICE: ``aux`` carries, per log key,
    the finite-filtered f32 ``sum`` and finite ``cnt`` over the stride
    (the running-mean contract of ``_RunningMeanLogs``, summed over the
    stride axis only — non-scalar logs keep their shape) plus ``last``
    (the final inner step's logs, what the boundary logs/hooks see).
    The host touches ONE dispatch per ``k`` micro-batches and zero
    device syncs.
    """
    if k < 2:
        raise ValueError(f"make_multi_step needs k >= 2, got {k}")

    if mesh is None:
        raw_step = _single_device_raw_step(module, tx)
    elif mode == "gspmd":
        raw_step = _gspmd_raw_step(module, tx, grad_sync)
    elif mode == "shard_map":
        raw_step = _shard_map_raw_step(
            module, tx, mesh, zero_stage, state_shardings
        )
    else:
        raise ValueError(
            f"Unknown step mode {mode!r} (expected gspmd|shard_map)"
        )

    def multi(state: TrainState, kbatch, base_rng, start):
        idx = jnp.arange(k, dtype=jnp.int32)

        def body(carry, xs):
            batch_i, i = xs
            rng_i = jax.random.fold_in(base_rng, start + i)
            new_state, logs = raw_step(carry, batch_i, rng_i)
            return new_state, dict(logs)

        state, seq = jax.lax.scan(body, state, (kbatch, idx))
        # On-device metric accumulation over the stride axis (axis 0);
        # everything else keeps the log's own shape, mirroring the host
        # accumulator's elementwise running mean.
        sums, cnts, last = {}, {}, {}
        for key, stacked in seq.items():
            v32 = jnp.asarray(stacked).astype(jnp.float32)
            finite = jnp.isfinite(v32)
            sums[key] = jnp.sum(jnp.where(finite, v32, 0.0), axis=0)
            cnts[key] = jnp.sum(finite.astype(jnp.float32), axis=0)
            last[key] = stacked[-1]
        return state, {"sum": sums, "cnt": cnts, "last": last}

    megastep_names = ("state", "kbatch", "base_rng", "start")
    if mesh is None or mode == "shard_map":
        return ledgered_jit(
            multi, site=f"train/megastep_k{k}", arg_names=megastep_names,
            donate_argnums=0,
        )

    repl = shardlib.replicated(mesh)
    if state_shardings is None:
        state_shardings = repl
    kbatch_sh = shardlib.stacked_batch_sharding(mesh)
    return ledgered_jit(
        multi, site=f"train/megastep_k{k}", arg_names=megastep_names,
        in_shardings=(state_shardings, kbatch_sh, repl, repl),
        out_shardings=(state_shardings, repl),
        donate_argnums=0,
    )


def build_eval_step(
    module: TpuModule,
    mesh: Optional[Mesh],
    kind: str = "validation",
    mode: str = "gspmd",
    params_shardings: Optional[Any] = None,
) -> Callable[[Any, Any], dict]:
    """Compile one metric-producing eval step: ``(params, batch) -> logs``."""
    step_method = (
        module.validation_step if kind == "validation" else module.test_step
    )

    if mesh is None:
        return ledgered_jit(
            lambda params, batch: dict(step_method(params, batch)),
            site=f"eval/{kind}", arg_names=("params", "batch"),
        )

    if mode == "shard_map":
        # Same refusal as the train step: shard_map replicates params, so
        # a ZeRO-3/TP-placed model would silently all-gather here.
        _refuse_sharded_state(params_shardings, "shard_map eval")

        batch_axes = shardlib.data_axes(mesh)
        if not batch_axes:
            raise ValueError(
                "shard_map mode needs a data/fsdp mesh axis to shard the "
                f"batch over; mesh axes = {mesh.axis_names}"
            )
        data_axis = batch_axes if len(batch_axes) > 1 else batch_axes[0]

        def per_device(params, batch):
            logs = dict(step_method(params, batch))
            return jax.lax.pmean(logs, axis_name=data_axis)

        return ledgered_jit(
            jax.shard_map(
                per_device,
                mesh=mesh,
                in_specs=(P(), P(data_axis)),
                out_specs=P(),
                # Outputs are pmean'd — replicated by construction; the
                # inference-based checker can't always prove it.
                check_vma=False,
            ),
            site=f"eval/{kind}", arg_names=("params", "batch"),
        )

    repl = shardlib.replicated(mesh)
    batch_sh = shardlib.batch_sharding(mesh)
    in_sh = (params_shardings if params_shardings is not None else repl,
             batch_sh)
    return ledgered_jit(
        lambda params, batch: dict(step_method(params, batch)),
        site=f"eval/{kind}", arg_names=("params", "batch"),
        in_shardings=in_sh,
        out_shardings=repl,
    )


def build_predict_step(
    module: TpuModule,
    mesh: Optional[Mesh],
    params_shardings: Optional[Any] = None,
):
    """Compile ``(params, batch) -> outputs`` with outputs batch-sharded.

    Outputs keep the data-axis sharding so each host can ``device_get``
    its own slice (addressable shards) for driver-side concatenation.
    """
    if mesh is None:
        return ledgered_jit(
            module.predict_step, site="eval/predict",
            arg_names=("params", "batch"),
        )
    repl = shardlib.replicated(mesh)
    batch_sh = shardlib.batch_sharding(mesh)
    return ledgered_jit(
        module.predict_step, site="eval/predict",
        arg_names=("params", "batch"),
        in_shardings=(params_shardings if params_shardings is not None
                      else repl, batch_sh),
        out_shardings=batch_sh,
    )

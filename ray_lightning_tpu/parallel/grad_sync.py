"""Quantized, bucketed gradient synchronization (the DCN bandwidth layer).

The GSPMD train step syncs gradients implicitly: the loss is a mean over
the global batch, so ``jax.grad`` of it IS the all-reduced gradient — one
compiler-scheduled full-width collective.  On cross-host (DCN) meshes that
wire is the scale-out bound.  This module makes the sync explicit and
compressible:

* **bucketing** — the grad pytree is flattened in layer order and packed
  into size-bounded buckets (~4 MB default), so the sync is several
  independent collectives XLA may overlap with unrelated compute instead
  of one barrier-sized transfer;
* **block-scaled int8 wire** (``mode="int8"``) — each bucket is quantized
  per-block (:mod:`ray_lightning_tpu.ops.collective_quant`) before the
  two-phase compressed all-reduce: ~3.9× fewer bytes on the wire than
  f32 full-width at a bounded per-step rounding error;
* **error feedback** (``mode="int8_ef"``) — every device carries its own
  f32 compression-error residual in the train state
  (``TrainState.grad_residual``, sharded one row per device) and re-adds
  it to the next step's partial before quantizing, so the error
  telescopes instead of accumulating (1-bit-Adam/EF-SGD discipline);
* **wire accounting** — the analytic bytes-on-wire of the chosen mode
  (and of the full-width counterfactual) are recorded per step in the
  loop metrics (``grad_sync_bytes``) and in the fit result package, so a
  claimed traffic cut is an artifact, not a slide.

Mechanically the sync is a ``shard_map`` island inside the jitted step
(the same jit → shard_map pattern as the CE island): per-device partial
grads of the *local* loss (``check_vma=False`` keeps the replicated-param
cotangent un-psummed), quantized collectives over the batch axes, then
the optimizer update continues under GSPMD — ZeRO-1 optimizer-state
sharding composes unchanged.  Activation requires a batch-parallel-only
mesh and replicated params (``zero_stage <= 1``); anything else falls
back to full-width with a warning (quantized ZeRO-3 all-gather is the
named follow-on).  ``dcn_only=True`` (default) additionally keeps
single-host (ICI-only) meshes full-width — ICI is not the bottleneck the
compression pays for.  Env bus: ``RLT_GRAD_COMM``, ``RLT_GRAD_BUCKET_MB``,
``RLT_GRAD_BLOCK``, ``RLT_GRAD_DCN_ONLY``.
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_lightning_tpu.ops import collective_quant as cq
from . import sharding as shardlib

__all__ = [
    "GradCommConfig",
    "Bucket",
    "BucketPlan",
    "build_bucket_plan",
    "sync_leaf_buckets",
    "GradSync",
    "maybe_build_grad_sync",
]

_MODES = ("full", "int8", "int8_ef")


@dataclasses.dataclass(frozen=True)
class GradCommConfig:
    """User-facing gradient-communication knobs.

    ``mode``: ``"full"`` (implicit XLA sync, the default), ``"int8"``
    (block-scaled quantized wire), ``"int8_ef"`` (int8 + error-feedback
    residual).  ``bucket_bytes`` bounds a bucket by its *full-width* f32
    footprint; ``block_size`` is the quantization granularity (elements
    per scale); ``dcn_only`` keeps single-process (ICI-only) meshes at
    full width even when an int8 mode is requested.
    """

    mode: str = "full"
    bucket_bytes: int = 4 * 2**20
    block_size: int = 256
    dcn_only: bool = True

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(
                f"grad_comm mode {self.mode!r}: expected one of {_MODES}"
            )
        if self.bucket_bytes < 4:
            raise ValueError("bucket_bytes must be >= 4 (one f32)")
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")

    @classmethod
    def coerce(cls, value: Any) -> "GradCommConfig":
        """None | str | dict | GradCommConfig → GradCommConfig.

        ``None`` reads the ``RLT_GRAD_COMM`` env bus (workers inherit the
        driver's env through ``env_per_worker``); absent that, the
        default is full-width — compression is always opt-in.
        """
        if isinstance(value, cls):
            return value
        if value is None:
            value = os.environ.get("RLT_GRAD_COMM") or "full"
        if isinstance(value, str):
            kw: dict = {"mode": value}
        elif isinstance(value, dict):
            kw = dict(value)
            if "mode" not in kw:
                # A dict without a mode (tuning knobs alone, or empty)
                # would silently coerce to full-width — the user clearly
                # expected to choose compression.  Pass a mode string or
                # None for the env-bus default instead.
                raise ValueError(
                    "grad_comm dict must name a 'mode' "
                    f"(one of {_MODES}); got keys {sorted(kw)}"
                )
        else:
            raise TypeError(
                f"grad_comm must be a mode string, dict or GradCommConfig; "
                f"got {type(value).__name__}"
            )
        env_mb = os.environ.get("RLT_GRAD_BUCKET_MB")
        if env_mb and "bucket_bytes" not in kw:
            kw["bucket_bytes"] = int(float(env_mb) * 2**20)
        env_block = os.environ.get("RLT_GRAD_BLOCK")
        if env_block and "block_size" not in kw:
            kw["block_size"] = int(env_block)
        env_dcn = os.environ.get("RLT_GRAD_DCN_ONLY")
        if env_dcn is not None and "dcn_only" not in kw:
            kw["dcn_only"] = env_dcn not in ("0", "false", "False", "")
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One sync unit: a contiguous (layer-order) run of grad leaves."""

    indices: Tuple[int, ...]   # flat-leaf positions
    sizes: Tuple[int, ...]     # elements per leaf
    size: int                  # total payload elements
    padded: int                # padded to n_shards * block_size
    offset: int                # start within the flat residual vector


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    buckets: Tuple[Bucket, ...]
    n_shards: int
    block_size: int
    total_elems: int           # un-padded payload elements
    total_padded: int          # residual vector length
    full_width_bytes: int      # f32 footprint of the whole grad pytree

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def wire_bytes_per_step(self, mode: str) -> int:
        """Analytic bytes each device puts on the wire per optimizer
        step.  Ring accounting — ``2(n-1)/n`` traversals of the payload
        (reduce-scatter + all-gather) for both the compressed path and
        the full-width counterfactual, so the ratio isolates the wire
        *width*, not the algorithm."""
        n = self.n_shards
        if n <= 1:
            return 0
        ring = 2.0 * (n - 1) / n
        if mode == "full":
            return int(ring * self.full_width_bytes)
        payload = sum(b.padded for b in self.buckets)          # int8 bytes
        scales = sum(b.padded // self.block_size for b in self.buckets) * 4
        return int(ring * (payload + scales))

    def collectives_per_step(self, mode: str) -> int:
        if mode == "full":
            return max(self.num_buckets, 1)  # XLA's implicit all-reduce(s)
        return 4 * self.num_buckets  # (all_to_all + all_gather) × (q, s)


def build_bucket_plan(
    abstract_grads: Any,
    n_shards: int,
    bucket_bytes: int = 4 * 2**20,
    block_size: int = 256,
) -> BucketPlan:
    """Pack the grad pytree's leaves, in tree (layer) order, into buckets
    bounded by ``bucket_bytes`` of full-width f32 footprint.

    A single leaf larger than the bound gets its own bucket (never
    split); the ragged tail bucket keeps whatever is left.  Each bucket
    is padded up to a multiple of ``n_shards * block_size`` so collective
    chunks align with quantization blocks (zero padding quantizes
    exactly, so it never pollutes the reduction).
    """
    leaves = jax.tree_util.tree_leaves(abstract_grads)
    align = n_shards * block_size
    max_elems = max(bucket_bytes // 4, 1)

    buckets: List[Bucket] = []
    cur_idx: List[int] = []
    cur_sizes: List[int] = []
    cur_total = 0
    offset = 0
    full_width_bytes = 0

    def flush():
        nonlocal cur_idx, cur_sizes, cur_total, offset
        if not cur_idx:
            return
        padded = -(-cur_total // align) * align
        buckets.append(
            Bucket(
                indices=tuple(cur_idx),
                sizes=tuple(cur_sizes),
                size=cur_total,
                padded=padded,
                offset=offset,
            )
        )
        offset += padded
        cur_idx, cur_sizes, cur_total = [], [], 0

    for i, leaf in enumerate(leaves):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        # Scalars are one element; a genuinely EMPTY leaf (a dim of 0 —
        # e.g. a placeholder param) has nothing to sync and must be
        # skipped, not counted as 1: a phantom element would desync the
        # bucket's padding from its actual payload.
        size = int(np.prod(shape)) if shape else 1
        if size == 0:
            continue
        full_width_bytes += size * 4
        if cur_total and cur_total + size > max_elems:
            flush()
        cur_idx.append(i)
        cur_sizes.append(size)
        cur_total += size
        if cur_total >= max_elems:
            flush()
    flush()

    return BucketPlan(
        buckets=tuple(buckets),
        n_shards=n_shards,
        block_size=block_size,
        total_elems=sum(b.size for b in buckets),
        total_padded=offset,
        full_width_bytes=full_width_bytes,
    )


def sync_leaf_buckets(
    leaves: List[Any],
    buckets: Sequence[Bucket],
    resid_vec,
    axes: Tuple[str, ...],
    n_shards: int,
    block_size: int,
    use_ef: bool,
) -> Tuple[List[Any], Optional[Any]]:
    """Per-device bucketed quantized all-reduce of flat grad leaves.

    The one sync kernel both paths share: the step-end
    :meth:`GradSync.build_synced_grad_fn` island runs it over the whole
    grad tree after ``jax.grad`` returns; the backward-overlapped grad
    taps (:mod:`ray_lightning_tpu.parallel.overlap`) run it per group on
    the cotangent, mid-backward.  ``leaves`` are this bucket set's grad
    leaves in plan order; ``resid_vec`` is the bucket set's contiguous
    EF-residual slice (bucket offsets are local to it).  Returns the
    synced leaves (original dtypes) and the concatenated new residual
    (``None`` without EF).  Must run inside ``shard_map`` over ``axes``.
    """
    out_leaves = list(leaves)
    resid_parts = []
    for b in buckets:
        parts = [
            leaves[i].reshape(-1).astype(jnp.float32)
            for i in b.indices
        ]
        flat = (
            jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        )
        if b.padded > b.size:
            flat = jnp.pad(flat, (0, b.padded - b.size))
        if use_ef:
            flat = flat + jax.lax.dynamic_slice(
                resid_vec, (b.offset,), (b.padded,)
            )
        reduced, err = cq.int8_all_reduce(
            flat, axes, n_shards, block_size, want_error=use_ef
        )
        if use_ef:
            resid_parts.append(err)
        pos = 0
        for i, sz in zip(b.indices, b.sizes):
            out_leaves[i] = (
                jax.lax.dynamic_slice(reduced, (pos,), (sz,))
                .reshape(leaves[i].shape)
                .astype(leaves[i].dtype)
            )
            pos += sz
    new_resid = (
        jnp.concatenate(resid_parts)
        if len(resid_parts) > 1
        else (resid_parts[0] if resid_parts else None)
    )
    return out_leaves, new_resid


class GradSync:
    """A resolved, active quantized-sync pipeline for one (module, mesh).

    Built by :func:`maybe_build_grad_sync`; consumed by
    ``step_fns.build_train_step`` (the island) and ``core.loop.run_fit``
    (residual attachment + comm stats).
    """

    def __init__(
        self,
        module: Any,
        mesh,
        cfg: GradCommConfig,
        axes: Tuple[str, ...],
        n_shards: int,
        plan: BucketPlan,
        overlap: Any = None,
    ):
        self.module = module
        self.mesh = mesh
        self.cfg = cfg
        self.axes = axes
        self.n_shards = n_shards
        # Backward-overlapped sync (parallel/overlap.py OverlapPlan):
        # when set, it duck-types BucketPlan's accounting/residual
        # interface and BECOMES the active plan — stats, residual init
        # and checkpoint reconciliation see one layout either way.
        self.overlap = overlap
        self.plan = overlap if overlap is not None else plan
        self.use_ef = cfg.mode == "int8_ef"

    # -- accounting ---------------------------------------------------------
    @property
    def bytes_per_step(self) -> int:
        return self.plan.wire_bytes_per_step(self.cfg.mode)

    def stats(self) -> dict:
        full = self.plan.wire_bytes_per_step("full")
        mine = self.bytes_per_step
        return {
            "grad_sync_mode": self.cfg.mode,
            "grad_sync_bytes": mine,
            "grad_sync_bytes_full_width": full,
            "grad_sync_compression_ratio": (
                round(full / mine, 3) if mine else None
            ),
            "grad_sync_buckets": self.plan.num_buckets,
            "grad_sync_collectives": self.plan.collectives_per_step(
                self.cfg.mode
            ),
            "grad_sync_block_size": self.plan.block_size,
            "grad_sync_devices": self.n_shards,
            # 0 = step-end sync; G >= 1 = backward-overlapped taps over
            # G trunk segments (parallel/overlap.py).
            "grad_sync_overlap_segments": (
                self.overlap.trunk_segments
                if self.overlap is not None else 0
            ),
        }

    def register_telemetry(self, telemetry) -> None:
        """Publish the wire accounting through the unified telemetry
        counters (numbers) / meta (mode strings) instead of a bespoke
        stats dict: ``grad_sync_bytes`` then appears in the fleet report
        (``trainer.telemetry_report``) next to step timings, and a
        grad-sync metadata span marks the plan in exported traces."""
        self._telemetry = telemetry
        for key, value in self.stats().items():
            if isinstance(value, bool) or value is None:
                telemetry.set_meta(key, value)
            elif isinstance(value, (int, float)):
                telemetry.set_counter(key, value)
            else:
                telemetry.set_meta(key, value)
        telemetry.tracer.instant(
            "grad_sync",
            mode=self.cfg.mode,
            buckets=self.plan.num_buckets,
            bytes_per_step=self.bytes_per_step,
        )

    # -- error-feedback residual -------------------------------------------
    def residual_sharding(self) -> NamedSharding:
        """One f32 row per sync participant, row ``d`` living on device
        ``d`` — per-device state expressed as a global array."""
        return NamedSharding(self.mesh, P(self.axes))

    def init_residual(self) -> jax.Array:
        zeros = jnp.zeros(
            (self.n_shards, self.plan.total_padded), jnp.float32
        )
        return jax.device_put(zeros, self.residual_sharding())

    def attach_residual(self, state, state_shardings):
        """Return (state, shardings) carrying the EF residual (no-ops for
        plain int8).  Must run before ``build_train_step`` so the jit's
        in/out sharding trees stay congruent with the state."""
        from ray_lightning_tpu.core.module import TrainState

        if not self.use_ef:
            return state, state_shardings
        new_state = TrainState(
            state.params, state.opt_state, state.step, self.init_residual()
        )
        if state_shardings is None:
            return new_state, None
        new_sh = TrainState(
            state_shardings.params,
            state_shardings.opt_state,
            state_shardings.step,
            self.residual_sharding(),
        )
        return new_state, new_sh

    def reconcile_resumed_state(self, host_state):
        """Normalize a resumed checkpoint against THIS run's residual
        layout: a stream written without EF (or from a different world
        size) gets a fresh zero residual — dropping at most one step of
        compression error; a stream written with EF resuming into a
        full/int8 run sheds it.

        The EF residual is **per-device** state (one row per sync
        participant): restored under a changed device count its rows no
        longer correspond to this run's devices, so a shape-mismatched
        residual is VALIDATED here and dropped — loudly (warning +
        ``grad_residual_dropped`` telemetry counter), never silently
        misapplied as another device's error history.
        """
        from ray_lightning_tpu.core.module import TrainState

        if not isinstance(host_state, TrainState):
            return host_state
        resid = getattr(host_state, "grad_residual", None)
        if not self.use_ef:
            if resid is None:
                return host_state
            return TrainState(
                host_state.params, host_state.opt_state, host_state.step
            )
        want = (self.n_shards, self.plan.total_padded)
        got = tuple(getattr(resid, "shape", ()))
        if resid is not None and got == want:
            return host_state
        if resid is not None:
            warnings.warn(
                f"checkpoint error-feedback residual has shape {got} "
                f"but this run syncs over {self.n_shards} devices "
                f"(want {want}) — the per-device residual does not "
                "survive an elastic world-size change; resetting to "
                "zero (at most one step of compression error is lost)"
            )
            tel = getattr(self, "_telemetry", None)
            if tel is not None:
                tel.add_counter("grad_residual_dropped", 1)
        return TrainState(
            host_state.params,
            host_state.opt_state,
            host_state.step,
            np.zeros(want, np.float32),
        )

    # -- the island ---------------------------------------------------------
    def build_synced_grad_fn(self):
        """The jit-traceable sync pipeline.

        EF: ``(params, residual, batch, rng) -> (grads, logs, residual')``;
        otherwise ``(params, batch, rng) -> (grads, logs)``.  ``grads`` are
        the dequantized world sum of per-device partials of the global
        mean loss — the same quantity the implicit full-width path feeds
        the optimizer.
        """
        if self.overlap is not None:
            return self._build_overlapped_fn()
        module = self.module
        axes = self.axes
        n = self.n_shards
        plan = self.plan
        block = plan.block_size
        use_ef = self.use_ef

        def _sync_buckets(grads, resid_row):
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            out_leaves, new_resid = sync_leaf_buckets(
                leaves, plan.buckets, resid_row, axes, n, block,
                use_ef=use_ef,
            )
            return jax.tree_util.tree_unflatten(treedef, out_leaves), new_resid

        def _local_grads(params, batch, rng):
            def local_loss(p):
                loss, logs = module.training_step(p, batch, rng)
                logs = dict(logs)
                logs.setdefault("loss", loss)
                # Scale so the world SUM of partials equals the gradient
                # of the global-mean loss (equal shard sizes are enforced
                # by make_global_batch's divisibility check).
                return loss / n, logs

            (_, logs), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(params)
            # Per-shard log values (local means) → mesh-global means, so
            # every host logs identical values, same as the gspmd flavor.
            logs = jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, axes), logs
            )
            return grads, logs

        batch_spec = P(axes)
        if use_ef:
            def island(params, residual, batch, rng):
                grads, logs = _local_grads(params, batch, rng)
                grads, new_resid = _sync_buckets(grads, residual[0])
                return grads, logs, new_resid[None]

            return jax.shard_map(
                island,
                mesh=self.mesh,
                in_specs=(P(), P(axes), batch_spec, P()),
                out_specs=(P(), P(), P(axes)),
                check_vma=False,
            )

        def island(params, batch, rng):
            grads, logs = _local_grads(params, batch, rng)
            grads, _ = _sync_buckets(grads, None)
            return grads, logs

        return jax.shard_map(
            island,
            mesh=self.mesh,
            in_specs=(P(), batch_spec, P()),
            out_specs=(P(), P()),
            check_vma=False,
        )

    def _build_overlapped_fn(self):
        """The backward-overlapped sync pipeline — same signature
        contract as the step-end island, but the sync is *part of the
        differentiation*: every param group is wrapped in a custom_vjp
        grad tap (parallel/overlap.py) whose backward runs the group's
        bucketed quantized all-reduce the moment its cotangent
        completes, so XLA can overlap it with the backward compute
        still pending for earlier-completing layers.

        EF residuals ride the cotangent: the residual row is a second
        differentiated argument — each tap consumes its group's slice
        and returns the group's fresh residual as that slice's
        cotangent, so ``d(loss)/d(residual_row)`` *is* the reassembled
        next-step residual (the slices are disjoint, so the VJP's
        scatter-add reassembles exactly).  No post-grad write-back pass,
        and the result is bitwise the same residual layout the step-end
        path checkpoints.
        """
        from ray_lightning_tpu.parallel.overlap import TapPlane

        module = self.module
        axes = self.axes
        n = self.n_shards
        oplan = self.overlap
        use_ef = self.use_ef

        def _pmean_logs(logs):
            return jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, axes), logs
            )

        def _tapped_loss(params, resid_row, batch, rng):
            plane = TapPlane(oplan, axes, n, use_ef, resid_row=resid_row)
            params = plane.apply_entry_taps(params)
            # The module's forward picks the plane up from its trainer
            # context to tap each trunk segment at its sub-scan
            # boundary; cleared in ``finally`` so eval/predict traces
            # never see a stale plane.
            trainer = getattr(module, "trainer", None)
            if trainer is not None:
                trainer.grad_tap_plane = plane
            try:
                loss, logs = module.training_step(params, batch, rng)
            finally:
                if trainer is not None:
                    trainer.grad_tap_plane = None
            plane.check_consumed()
            logs = dict(logs)
            logs.setdefault("loss", loss)
            return loss / n, logs

        batch_spec = P(axes)
        if use_ef:
            def island(params, residual, batch, rng):
                def local_loss(p, rrow):
                    return _tapped_loss(p, rrow, batch, rng)

                (_, logs), (grads, new_resid) = jax.value_and_grad(
                    local_loss, argnums=(0, 1), has_aux=True
                )(params, residual[0])
                return grads, _pmean_logs(logs), new_resid[None]

            return jax.shard_map(
                island,
                mesh=self.mesh,
                in_specs=(P(), P(axes), batch_spec, P()),
                out_specs=(P(), P(), P(axes)),
                check_vma=False,
            )

        def island(params, batch, rng):
            def local_loss(p):
                return _tapped_loss(p, None, batch, rng)

            (_, logs), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(params)
            return grads, _pmean_logs(logs)

        return jax.shard_map(
            island,
            mesh=self.mesh,
            in_specs=(P(), batch_spec, P()),
            out_specs=(P(), P()),
            check_vma=False,
        )


def _batch_only_mesh(mesh) -> bool:
    """True when every mesh axis with extent > 1 is batch-parallel —
    the precondition for replicated-param per-device grad math."""
    return all(
        mesh.shape[a] == 1 or a in ("data", "fsdp")
        for a in mesh.axis_names
    )


def maybe_build_grad_sync(
    module: Any,
    mesh,
    cfg: Any,
    mode: str = "gspmd",
    zero_stage: int = 0,
    abstract_params: Any = None,
    overlap_segments: int = 0,
) -> Optional["GradSync"]:
    """Resolve a grad-comm request against the actual (mesh, strategy)
    shape.  Returns an active :class:`GradSync`, or ``None`` (full-width)
    — every downgrade warns with the reason, never silently.

    ``overlap_segments >= 1`` additionally asks for backward-overlapped
    sync (``grad_overlap_segments`` knob): the module must partition its
    params via ``grad_overlap_groups`` (parallel/overlap.py) — a module
    that can't (returns ``None`` / lacks the hook) warns and keeps the
    step-end sync, never silently changes schedule."""
    cfg = GradCommConfig.coerce(cfg)
    if cfg.mode == "full" or mesh is None:
        return None

    def _downgrade(reason: str) -> None:
        warnings.warn(
            f"grad_comm={cfg.mode!r} requested but {reason}; "
            "gradients sync at full width."
        )

    if mode != "gspmd":
        _downgrade(f"step mode {mode!r} is not 'gspmd'")
        return None
    if zero_stage >= 3:
        _downgrade(
            "zero_stage=3 shards params (quantized ZeRO-3 all-gather is "
            "the follow-on, not this path)"
        )
        return None
    if not _batch_only_mesh(mesh):
        _downgrade(
            f"mesh axes {dict(mesh.shape)} include model-parallel axes"
        )
        return None
    axes = shardlib.data_axes(mesh)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if n_shards <= 1:
        return None  # nothing to sync — not worth a warning
    if cfg.dcn_only and jax.process_count() <= 1:
        _downgrade(
            "the mesh is single-host (ICI-only) and dcn_only=True "
            "(pass dcn_only=False to compress anyway)"
        )
        return None
    if abstract_params is None:
        abstract_params = jax.eval_shape(
            module.init_params, jax.random.PRNGKey(0)
        )
    plan = build_bucket_plan(
        abstract_params, n_shards, cfg.bucket_bytes, cfg.block_size
    )
    if plan.num_buckets == 0:
        _downgrade("the module has no parameters to sync")
        return None
    overlap = None
    if overlap_segments and overlap_segments >= 1:
        from ray_lightning_tpu.parallel import overlap as ovl

        groups_fn = getattr(module, "grad_overlap_groups", None)
        spec = (
            groups_fn(abstract_params, overlap_segments)
            if groups_fn is not None else None
        )
        if spec is None:
            warnings.warn(
                f"grad_overlap_segments={overlap_segments} requested but "
                f"{type(module).__name__} does not partition its params "
                "(grad_overlap_groups is missing or returned None); "
                "gradients sync at step end."
            )
        else:
            overlap = ovl.build_overlap_plan(
                spec, n_shards, cfg.bucket_bytes, cfg.block_size
            )
            if overlap.total_elems != plan.total_elems:
                # A partition that misses (or double-counts) params
                # would silently skip their sync — module bug, fail
                # loudly at build time.
                raise ValueError(
                    f"grad_overlap_groups covers {overlap.total_elems} "
                    f"elements but the module has {plan.total_elems} — "
                    "the groups must partition the whole param tree"
                )
    return GradSync(module, mesh, cfg, axes, n_shards, plan, overlap=overlap)

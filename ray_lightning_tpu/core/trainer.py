"""Trainer — the driver-side facade (≙ ``pl.Trainer`` as the reference uses it).

The user surface mirrors the reference's cardinal usage contract
(``/root/reference/README.md:50-62``): construct a Trainer with a strategy
(``plugins=[RayPlugin(...)]`` also accepted for drop-in familiarity), call
``fit(module, datamodule)``, and afterwards read ``trainer.callback_metrics``
/ ``trainer.best_model_path`` / the trained parameters — all recovered from
rank-0's result package exactly like the reference's ``post_dispatch``
(``ray_ddp.py:362-401``).

Driver discipline (≙ ``DelayedGPUAccelerator``, reference ``util.py:11-37``):
with a remote strategy the driver process never touches an accelerator —
model shipping, queue pumping and state recovery are pure-CPU work, so a
CPU-only laptop can drive a TPU pod.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ray_lightning_tpu.core.callbacks import Callback, ModelCheckpoint
from ray_lightning_tpu.core.data import TpuDataModule
from ray_lightning_tpu.core.loop import FitConfig
from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.telemetry.spans import phase
from ray_lightning_tpu.utils.state_stream import load_state_stream

__all__ = ["Trainer"]


class _ModuleDataModule(TpuDataModule):
    """Adapter: modules may provide their own dataloaders (Lightning-style)."""

    def __init__(self, module: TpuModule):
        super().__init__()
        self._module = module

    def _sharded(self, loader):
        # Propagate the host shard to module-built loaders — without this a
        # multi-worker run would feed every host identical rows (violating
        # the DistributedSampler contract, reference ray_ddp.py:556-561).
        if loader is not None and hasattr(loader, "set_shard"):
            loader.set_shard(self.shard_index, self.num_shards)
        return loader

    def train_dataloader(self):
        return self._sharded(self._module.train_dataloader())

    def val_dataloader(self):
        fn = getattr(self._module, "val_dataloader", None)
        return self._sharded(fn()) if fn is not None else None

    def test_dataloader(self):
        fn = getattr(self._module, "test_dataloader", None)
        return self._sharded(fn()) if fn is not None else None

    def predict_dataloader(self):
        fn = getattr(self._module, "predict_dataloader", None)
        return self._sharded(fn()) if fn is not None else None


class Trainer:
    """Drive training through a :class:`TpuStrategy`.

    Args mirror the ``pl.Trainer`` subset the reference exercises in its
    tests (``tests/utils.py:213-233``): ``max_epochs``, ``max_steps``,
    ``callbacks``, ``limit_*_batches``, ``fast_dev_run``,
    ``resume_from_checkpoint``, plus ``strategy``/``plugins``.
    """

    def __init__(
        self,
        strategy=None,
        plugins=None,
        max_epochs: int = 1,
        max_steps: int = -1,
        callbacks: Optional[List[Callback]] = None,
        default_root_dir: str = "rlt_logs",
        seed: int = 0,
        precision: str = "f32",
        check_val_every_n_epoch: int = 1,
        limit_train_batches: int = -1,
        limit_val_batches: int = -1,
        log_every_n_steps: int = 50,
        accumulate_grad_batches: int = 1,
        megastep=None,
        update_sharding=None,
        grad_overlap_segments=None,
        enable_checkpointing: bool = True,
        fast_dev_run: bool = False,
        resume_from_checkpoint: Optional[str] = None,
        restart_dir: Optional[str] = None,
        restart_every_n_epochs: Optional[int] = None,
    ):
        # Imported here, not at module top: strategies imports the loop,
        # which lives beside this module (cycle otherwise).
        from ray_lightning_tpu.parallel.strategies import (
            LocalStrategy,
            TpuStrategy,
        )

        if strategy is None and plugins:
            # Reference-style: Trainer(plugins=[RayPlugin(...)])
            strategy = next(
                (p for p in plugins if isinstance(p, TpuStrategy)), None
            )
        if (restart_every_n_epochs is not None
                and restart_every_n_epochs < 1):
            raise ValueError("restart_every_n_epochs must be >= 1")
        self.strategy = strategy or LocalStrategy()
        self.callbacks: List[Callback] = list(callbacks or [])
        if enable_checkpointing and not any(
            isinstance(cb, ModelCheckpoint) for cb in self.callbacks
        ):
            self.callbacks.append(ModelCheckpoint(monitor=None))
        self.config = FitConfig(
            max_epochs=max_epochs,
            max_steps=max_steps,
            check_val_every_n_epoch=check_val_every_n_epoch,
            limit_train_batches=limit_train_batches,
            limit_val_batches=limit_val_batches,
            log_every_n_steps=log_every_n_steps,
            accumulate_grad_batches=accumulate_grad_batches,
            # Megastep execution mode (fuse K micro-steps into one
            # compiled scan — docs/PERFORMANCE.md "Host dispatch &
            # megastep").  None defers to the strategy's knob / the
            # RLT_MEGASTEP env bus / "auto".
            megastep=megastep,
            # Cross-replica sharded weight update (optimizer state +
            # update computation sharded over the batch axes on pure-DP
            # meshes — docs/PERFORMANCE.md).  None defers to the
            # strategy's knob / the RLT_UPDATE_SHARDING env bus /
            # "auto".
            update_sharding=update_sharding,
            # Backward-overlapped gradient sync (G trunk segments +
            # custom_vjp grad taps — docs/PERFORMANCE.md "Comm/compute
            # overlap").  None defers to the strategy's knob / the
            # RLT_GRAD_OVERLAP env bus / off.
            grad_overlap_segments=grad_overlap_segments,
            seed=seed,
            precision=precision,
            default_root_dir=default_root_dir,
            resume_from_checkpoint=resume_from_checkpoint,
            fast_dev_run=fast_dev_run,
            # Elastic-restart checkpoint location.  When None, strategies
            # with max_restarts > 0 manage a scratch dir themselves; a
            # caller-provided dir is written to (per-host sharded, see
            # utils/sharded_ckpt.py) and PRESERVED after the fit.
            restart_dir=restart_dir,
            restart_every_n_epochs=restart_every_n_epochs,
        )

        # Post-run artifacts (populated like reference post_dispatch).
        self.callback_metrics: Dict[str, float] = {}
        self.logged_metrics: Dict[str, float] = {}
        self.best_model_path: str = ""
        self.state = None  # host-side TrainState (numpy leaves) after fit
        self.predictions: Optional[np.ndarray] = None
        self.epochs_run: int = 0
        self.global_step: int = 0   # optimizer steps (Lightning convention)
        self.micro_step: int = 0    # micro-batches (= global_step unless
        # gradient accumulation is active)
        # Gradient-sync wire accounting from the workers (grad_sync_mode,
        # grad_sync_bytes, compression ratio — parallel/grad_sync.py).
        # Compatibility view: the same numbers appear as counters in the
        # unified ``telemetry_report`` below.
        self.comm_stats: Dict[str, Any] = {}
        # Fleet-wide telemetry (telemetry/aggregate.py): every worker's
        # snapshot merged into min/max/mean-across-ranks skew views.
        self.telemetry_report: Dict[str, Any] = {}
        # Live-monitor record (telemetry/monitor.py): heartbeat-derived
        # per-rank state, stall/straggler/crash events, flight-bundle
        # paths.  Populated after every monitored fit — the live
        # companion of ``telemetry_report``.
        self.monitor_report: Dict[str, Any] = {}
        self._monitor = None  # the RunMonitor of the fit in flight
        self._state_stream: Optional[bytes] = None

    # -- live stream routing (driver-side queue pump hook) ------------------
    def _attach_monitor(self, monitor) -> None:
        """Called by the strategy when a monitored fit starts."""
        self._monitor = monitor

    def _adopt_monitor(self, monitor) -> None:
        """Called by the strategy when the fit ends (either way)."""
        self.monitor_report = monitor.report()
        self._monitor = None

    def _on_stream_item(self, item: Any) -> None:
        """Route one worker→driver stream item by ``type``.

        ``heartbeat``/``event``/``log`` feed the RunMonitor; ``metrics``
        update ``callback_metrics`` — but ONLY from rank 0 (the same
        rank whose result package wins at post-dispatch).  Before this
        gate any worker could clobber driver metrics with a forged
        ``{"type": "metrics"}`` dict.
        """
        if not isinstance(item, dict):
            return
        if self._monitor is not None:
            self._monitor.on_item(item)
        if (
            item.get("type") == "metrics"
            and int(item.get("rank", 0)) == 0
        ):
            self.callback_metrics.update(item["metrics"])

    # -- stage entry points --------------------------------------------------
    def _resolve_datamodule(
        self, module: TpuModule, datamodule: Optional[TpuDataModule]
    ) -> TpuDataModule:
        if datamodule is not None:
            return datamodule
        if hasattr(module, "train_dataloader") or hasattr(
            module, "val_dataloader"
        ):
            return _ModuleDataModule(module)
        raise ValueError(
            "Provide a datamodule or implement *_dataloader on the module."
        )

    def fit(
        self,
        module: TpuModule,
        datamodule: Optional[TpuDataModule] = None,
    ) -> "Trainer":
        dm = self._resolve_datamodule(module, datamodule)
        # Fresh monitor record per fit: each elastic attempt's monitor is
        # seeded with the prior attempts' events by the strategy, so the
        # LAST adopted report (success or failure) narrates the whole
        # fit — but it must not inherit a previous fit's.
        self.monitor_report = {}
        self.strategy.setup(self)
        try:
            results = self.strategy.run(
                "fit", module, dm, self.config, self.callbacks, trainer=self
            )
        finally:
            self.strategy.teardown()
        self._post_dispatch_fit(results)
        return self

    def _post_dispatch_fit(self, results: List[Dict[str, Any]]) -> None:
        """Adopt rank-0's result package (≙ reference ``post_dispatch``,
        ``ray_ddp.py:362-401``)."""
        rank0 = next(r for r in results if r.get("rank") == 0)
        self._state_stream = rank0["state_stream"]
        with phase("result_unpack", "fit"):
            self.state = load_state_stream(self._state_stream)
        self.callback_metrics.update(rank0["callback_metrics"])
        self.logged_metrics.update(rank0["logged_metrics"])
        self.best_model_path = rank0["best_model_path"]
        self.epochs_run = rank0["epochs_run"]
        self.global_step = rank0["global_step"]
        self.micro_step = rank0.get("micro_step", self.global_step)
        self.comm_stats = dict(rank0.get("comm_stats", {}))
        self._merge_telemetry(results, replace=True)
        # Driver-side callback objects reflect what happened remotely
        # (≙ best_model_path adoption, ray_ddp.py:393-395 — generalized).
        for cb, cb_state in zip(self.callbacks, rank0["callback_states"]):
            cb.load_state_dict(cb_state)

    def _merge_telemetry(self, results: List[Dict[str, Any]],
                         replace: bool = False) -> None:
        """Merge EVERY rank's telemetry snapshot (each result package
        carries one — the non-zero ranks' packages exist for exactly
        this) into the fleet skew report.  Runs for fit, eval AND
        predict.  A fit REPLACES the report (even with an empty one —
        telemetry="off" must read as off); eval/predict update it only
        when they actually produced one, so a quick validate never
        wipes the fit's record."""
        from ray_lightning_tpu.telemetry import merge_snapshots

        report = merge_snapshots([r.get("telemetry") for r in results])
        if report or replace:
            self.telemetry_report = report

    @property
    def params(self):
        """Trained parameters (host numpy pytree) after :meth:`fit`."""
        return None if self.state is None else self.state.params

    def _run_eval(
        self,
        kind: str,
        module: TpuModule,
        datamodule: Optional[TpuDataModule],
        ckpt_path: Optional[str],
    ) -> Dict[str, float]:
        dm = self._resolve_datamodule(module, datamodule)
        self.strategy.setup(self)
        try:
            results = self.strategy.run(
                kind,
                module,
                dm,
                self.config,
                self.callbacks,
                trainer=self,
                params_stream=self._params_stream_for_eval(ckpt_path),
                ckpt_path=ckpt_path,
            )
        finally:
            self.strategy.teardown()
        rank0 = next(r for r in results if r.get("rank") == 0)
        metrics = rank0["callback_metrics"]
        self.callback_metrics.update(metrics)
        self._merge_telemetry(results)
        return metrics

    def _params_stream_for_eval(self, ckpt_path: Optional[str]):
        if ckpt_path is not None:
            return None  # workers load from the checkpoint file directly
        return self._state_stream_params()

    def _state_stream_params(self) -> Optional[bytes]:
        if self.state is None:
            return None
        from ray_lightning_tpu.utils.state_stream import to_state_stream

        return to_state_stream(self.state.params)

    def validate(
        self,
        module: TpuModule,
        datamodule: Optional[TpuDataModule] = None,
        ckpt_path: Optional[str] = None,
    ) -> Dict[str, float]:
        return self._run_eval("validation", module, datamodule, ckpt_path)

    def test(
        self,
        module: TpuModule,
        datamodule: Optional[TpuDataModule] = None,
        ckpt_path: Optional[str] = None,
    ) -> Dict[str, float]:
        return self._run_eval("test", module, datamodule, ckpt_path)

    def predict(
        self,
        module: TpuModule,
        datamodule: Optional[TpuDataModule] = None,
        ckpt_path: Optional[str] = None,
    ) -> np.ndarray:
        dm = self._resolve_datamodule(module, datamodule)
        self.strategy.setup(self)
        try:
            results = self.strategy.run(
                "predict",
                module,
                dm,
                self.config,
                [],
                trainer=self,
                params_stream=self._params_stream_for_eval(ckpt_path),
                ckpt_path=ckpt_path,
            )
        finally:
            self.strategy.teardown()
        self._merge_telemetry(results)
        # Reassemble dataset row order: every global batch was split
        # host-contiguously (NumpyLoader), so interleave ranks per batch —
        # batch b = [rank0's slice, rank1's slice, ...] — then chain
        # batches.  (Upgrade over the reference, which returned rank-0
        # results only.)
        ordered = sorted(results, key=lambda r: r["rank"])
        per_rank = [r["prediction_batches"] for r in ordered]
        counts = {len(b) for b in per_rank}
        if len(counts) > 1:
            # A rank with fewer batches would silently drop the other
            # ranks' tail predictions; make the data-sharding bug loud.
            raise ValueError(
                "Ragged per-rank prediction batch counts "
                f"{[len(b) for b in per_rank]}: every rank must see the "
                "same number of batches (check the datamodule's sharding "
                "/ drop_last handling)."
            )
        num_batches = counts.pop() if counts else 0
        batches = [
            np.concatenate([per_rank[rank][b] for rank in range(len(per_rank))])
            for b in range(num_batches)
        ]
        self.predictions = np.concatenate(batches)
        return self.predictions

    def save_checkpoint(self, path: str) -> None:
        """Persist the post-fit state as a topology-independent stream."""
        if self._state_stream is None:
            raise RuntimeError("No trained state; call fit() first.")
        payload_dir = os.path.dirname(path)
        if payload_dir:
            os.makedirs(payload_dir, exist_ok=True)
        from ray_lightning_tpu.utils.state_stream import (
            state_stream_to_file,
            to_state_stream,
        )

        payload = {
            "state": self.state,
            "epoch": self.epochs_run - 1,
            "global_step": self.global_step,
            "callback_metrics": dict(self.callback_metrics),
        }
        state_stream_to_file(to_state_stream(payload), path)

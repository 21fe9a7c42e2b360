"""Auxiliary subsystems: profiler tracing, input prefetch, downsizing
resume, PBT over the flagship model.

Widens the test taxonomy toward the reference's full grid (SURVEY §4/§5):
profiling (net-new — reference has none), resume-with-fewer-workers
(≙ ``test_ddp_sharded.py:119-138``), and the BASELINE #5 config shape
(PBT sweep of GPT LR) at test scale.
"""

import os

import numpy as np
import pytest

from ray_lightning_tpu.core.callbacks import ProfilerCallback
from ray_lightning_tpu.core.loop import _prefetched
from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models.boring import BoringDataModule, BoringModel
from ray_lightning_tpu.models.gpt import GPT, GPTConfig, SyntheticLMDataModule
from ray_lightning_tpu.parallel.strategies import LocalStrategy, RayStrategy


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_profiler_callback_writes_trace(tmp_path):
    cb = ProfilerCallback(start_step=1, num_steps=2)
    trainer = Trainer(
        strategy=LocalStrategy(),
        max_epochs=1,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
        limit_train_batches=6,
        limit_val_batches=1,
        callbacks=[cb],
    )
    trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
    assert cb.trace_dir is not None
    # jax.profiler writes plugins/profile/<ts>/*.pb under the trace dir.
    found = [
        os.path.join(r, f)
        for r, _, fs in os.walk(cb.trace_dir) for f in fs
    ]
    assert found, "profiler produced no trace files"


def test_profiler_callback_survives_short_run(tmp_path):
    """Window extends past the end of training: teardown closes the trace."""
    cb = ProfilerCallback(start_step=0, num_steps=100)
    trainer = Trainer(
        strategy=LocalStrategy(),
        max_epochs=1,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
        limit_train_batches=2,
        limit_val_batches=0,
        callbacks=[cb],
    )
    trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
    assert not cb._active
    assert cb.trace_dir is not None  # the window did open
    assert any(files for _, _, files in os.walk(cb.trace_dir))


def test_prefetched_preserves_order_and_errors():
    # Items arrive as (placed, n_inner) pairs since the megastep round
    # (n_inner == 1 when no stacking is configured).
    out = list(_prefetched(range(10), lambda x: x * 2))
    assert out == [(2 * i, 1) for i in range(10)]

    def boom():
        yield 1
        raise RuntimeError("loader died")

    it = _prefetched(boom(), lambda x: x)
    assert next(it) == (1, 1)
    with pytest.raises(RuntimeError, match="loader died"):
        list(it)


def test_prefetched_stacks_strides_within_budget():
    """stack=4 over 10 items with an 8-item stride budget: two full
    strides, then per-item singles (the megastep grouping contract)."""
    out = list(_prefetched(
        range(10), lambda x: x, stack=4, stack_limit=8,
        place_stride=lambda xs: tuple(xs),
    ))
    assert out == [
        ((0, 1, 2, 3), 4), ((4, 5, 6, 7), 4), (8, 1), (9, 1),
    ]


def test_prefetched_early_break_stops_cleanly():
    import threading

    before = threading.active_count()
    for item, _n in _prefetched(range(1000), lambda x: x):
        if item == 3:
            break
    import time

    deadline = time.monotonic() + 5
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before


def test_resume_with_fewer_workers(tmp_path):
    """Checkpoints are topology-independent: fit on 2 workers, resume on 1
    (≙ reference downsizing test, test_ddp_sharded.py:119-138)."""
    first = Trainer(
        strategy=RayStrategy(num_workers=2),
        max_epochs=1,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
        limit_train_batches=2,
        limit_val_batches=1,
    )
    first.fit(BoringModel(), BoringDataModule(batch_size=16))
    path = str(tmp_path / "downsize.ckpt")
    first.save_checkpoint(path)

    resumed = Trainer(
        strategy=RayStrategy(num_workers=1),
        max_epochs=3,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
        limit_train_batches=2,
        limit_val_batches=1,
        resume_from_checkpoint=path,
    )
    resumed.fit(BoringModel(), BoringDataModule(batch_size=16))
    assert resumed.epochs_run == 3
    assert resumed.global_step > first.global_step
    assert np.isfinite(resumed.callback_metrics["train_loss"])


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_pbt_sweep_of_gpt_lr(tmp_path):
    """BASELINE #5 shape at test scale: PBT explores GPT learning rates."""
    from ray_lightning_tpu.tune import TuneReportCallback
    from ray_lightning_tpu.tuning import (
        PopulationBasedTraining,
        loguniform,
        tune_run,
    )

    def train_gpt(config):
        cfg = GPTConfig(vocab_size=128, n_layer=1, n_head=2, d_model=32,
                        seq_len=32, lr=config["lr"], warmup_steps=1)
        trainer = Trainer(
            strategy=LocalStrategy(),
            max_epochs=2,
            default_root_dir=str(tmp_path),
            enable_checkpointing=False,
            limit_train_batches=2,
            limit_val_batches=1,
            callbacks=[TuneReportCallback({"loss": "val_loss"},
                                          on="validation_end")],
        )
        trainer.fit(GPT(cfg), SyntheticLMDataModule(cfg, batch_size=8,
                                                    num_batches=2))

    pbt = PopulationBasedTraining(
        metric="loss", mode="min", perturbation_interval=1,
        hyperparam_mutations={"lr": loguniform(1e-4, 1e-2)},
    )
    analysis = tune_run(
        train_gpt,
        config={"lr": loguniform(1e-4, 1e-2)},
        num_samples=3,
        scheduler=pbt,
        metric="loss",
        mode="min",
        local_dir=str(tmp_path / "pbt"),
        verbose=False,
    )
    assert analysis.best_config is not None
    assert np.isfinite(analysis.best_result["loss"])


def test_compile_cache_dir_from_environment(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: that directory is used — by jax
    itself, which reads its own variable — and the package points
    ``jax.config`` nowhere else; workers receive the same directory
    through the strategy's env bus."""
    import subprocess
    import sys

    import jax as _jax

    from ray_lightning_tpu.parallel.strategies import RayStrategy
    from ray_lightning_tpu.utils import compile_cache as cc

    cache = str(tmp_path / "xla_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    assert cc.compile_cache_dir() == cache
    s = RayStrategy(num_workers=1)
    assert s.env_per_worker["JAX_COMPILATION_CACHE_DIR"] == cache
    before = _jax.config.jax_compilation_cache_dir
    cc.enable_compile_cache()
    assert _jax.config.jax_compilation_cache_dir == before

    # A fresh process (jax reads the variable at import): the fit's
    # compiles land in that directory and nowhere else.
    script = (
        "from ray_lightning_tpu.core.trainer import Trainer\n"
        "from ray_lightning_tpu.models import BoringDataModule, "
        "BoringModel\n"
        "import jax\n"
        f"t = Trainer(max_epochs=1, default_root_dir={str(tmp_path)!r}, "
        "enable_checkpointing=False)\n"
        "t.fit(BoringModel(), BoringDataModule())\n"
        "print('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ, JAX_ENABLE_COMPILATION_CACHE="true",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    default_before = os.path.isdir(cc.DEFAULT_CACHE_DIR)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert f"CACHE_DIR {cache}" in proc.stdout
    assert os.path.isdir(cache) and os.listdir(cache), (
        "compilation cache dir not populated"
    )
    assert os.path.isdir(cc.DEFAULT_CACHE_DIR) == default_before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR unset: one fixed path inside the
    checkout — never a temporary, pid- or time-derived one — for this
    process and for its workers."""
    import jax as _jax
    from jax.experimental.compilation_cache import compilation_cache

    from ray_lightning_tpu.parallel.strategies import RayStrategy
    from ray_lightning_tpu.utils import compile_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert cc.compile_cache_dir() == cc.DEFAULT_CACHE_DIR
    s = RayStrategy(num_workers=1)
    assert (s.env_per_worker["JAX_COMPILATION_CACHE_DIR"]
            == cc.DEFAULT_CACHE_DIR)
    # An explicit env_per_worker entry still wins.
    s = RayStrategy(num_workers=1,
                    env_per_worker={"JAX_COMPILATION_CACHE_DIR": "/x"})
    assert s.env_per_worker["JAX_COMPILATION_CACHE_DIR"] == "/x"

    was = _jax.config.jax_compilation_cache_dir
    try:
        _jax.config.update("jax_compilation_cache_dir", None)
        cc.enable_compile_cache()
        assert (_jax.config.jax_compilation_cache_dir
                == cc.DEFAULT_CACHE_DIR)
        # A directory the caller configured is left alone.
        _jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        cc.enable_compile_cache()
        assert _jax.config.jax_compilation_cache_dir == "/elsewhere"
    finally:
        _jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


class TestSWA:
    def test_swa_params_are_epoch_mean(self, tmp_path):
        """The final params equal the running mean of the end-of-epoch
        params from swa_start_epoch onward."""
        import jax

        from ray_lightning_tpu.core.callbacks import (
            Callback, StochasticWeightAveraging,
        )
        from ray_lightning_tpu.core.trainer import Trainer
        from ray_lightning_tpu.models import BoringDataModule, BoringModel
        from ray_lightning_tpu.parallel.strategies import LocalStrategy

        class Spy(Callback):
            def __init__(self):
                self.snaps = []

            def on_train_epoch_end(self, trainer, module):
                self.snaps.append(jax.device_get(trainer.state.params))

        spy, swa = Spy(), StochasticWeightAveraging(swa_start_epoch=1)
        trainer = Trainer(
            strategy=LocalStrategy(), max_epochs=4,
            # Spy FIRST so it snapshots the raw trained params before
            # SWA folds them into its mean.
            callbacks=[spy, swa],
            default_root_dir=str(tmp_path), enable_checkpointing=False,
        )
        trainer.fit(BoringModel(), BoringDataModule())
        tail = spy.snaps[1:]  # epochs 1..3
        expect = jax.tree_util.tree_map(
            lambda *xs: sum(np.asarray(x, np.float64) for x in xs)
            / len(xs), *tail)
        got = jax.device_get(trainer.state.params)
        for a, b in zip(jax.tree_util.tree_leaves(expect),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(b), a, rtol=1e-5,
                                       atol=1e-7)
        # And the SWA point differs from the last epoch's raw params.
        last = jax.tree_util.tree_leaves(spy.snaps[-1])
        assert any(
            np.abs(np.asarray(x) - np.asarray(y)).max() > 1e-8
            for x, y in zip(last, jax.tree_util.tree_leaves(got))
        )

    def test_swa_under_sharded_mesh(self, tmp_path):
        """SWA composes with GSPMD sharding (shard-local averaging)."""
        import jax

        from ray_lightning_tpu.core.callbacks import StochasticWeightAveraging
        from ray_lightning_tpu.core.trainer import Trainer
        from ray_lightning_tpu.models import BoringDataModule, BoringModel
        from ray_lightning_tpu.parallel.strategies import LocalStrategy

        trainer = Trainer(
            strategy=LocalStrategy(mesh_axes={"data": 4, "fsdp": 2},
                                   zero_stage=3),
            max_epochs=3,
            callbacks=[StochasticWeightAveraging(swa_start_epoch=1)],
            default_root_dir=str(tmp_path), enable_checkpointing=False,
        )
        trainer.fit(BoringModel(), BoringDataModule())
        assert np.isfinite(trainer.callback_metrics["train_loss"])
        leaves = jax.tree_util.tree_leaves(trainer.params)
        assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)


def test_swa_resets_between_fits(tmp_path):
    """One SWA instance across two fits must not fold the first model's
    weights into the second fit's average."""
    from ray_lightning_tpu.core.callbacks import StochasticWeightAveraging
    from ray_lightning_tpu.core.trainer import Trainer
    from ray_lightning_tpu.models import BoringDataModule, BoringModel
    from ray_lightning_tpu.parallel.strategies import LocalStrategy

    swa = StochasticWeightAveraging(swa_start_epoch=0)
    for _ in range(2):
        tr = Trainer(strategy=LocalStrategy(), max_epochs=2,
                     callbacks=[swa], default_root_dir=str(tmp_path),
                     enable_checkpointing=False)
        tr.fit(BoringModel(), BoringDataModule())
        assert swa._count == 2  # epochs of THIS fit only


def test_async_checkpoint_writes(tmp_path):
    """ModelCheckpoint(async_write=True): files are durable by fit end,
    top-k pruning holds, and the checkpoint resumes."""
    from ray_lightning_tpu.core.callbacks import ModelCheckpoint
    from ray_lightning_tpu.models import BoringDataModule, BoringModel
    from ray_lightning_tpu.parallel.strategies import LocalStrategy

    ckpt_dir = str(tmp_path / "ckpts")
    cb = ModelCheckpoint(dirpath=ckpt_dir, save_top_k=2,
                         async_write=True)
    trainer = Trainer(strategy=LocalStrategy(), max_epochs=4,
                      callbacks=[cb], default_root_dir=str(tmp_path),
                      enable_checkpointing=False)
    trainer.fit(BoringModel(), BoringDataModule())
    files = sorted(os.listdir(ckpt_dir))
    assert len(files) == 2, files  # top-k pruned, all writes durable
    assert cb.best_model_path and os.path.exists(cb.best_model_path)

    trainer2 = Trainer(strategy=LocalStrategy(), max_epochs=5,
                       default_root_dir=str(tmp_path),
                       enable_checkpointing=False,
                       resume_from_checkpoint=cb.best_model_path)
    trainer2.fit(BoringModel(), BoringDataModule())
    assert trainer2.global_step > trainer.global_step


def test_async_checkpoint_write_failure_raises(tmp_path, monkeypatch):
    """A failed BACKGROUND write (not the sync makedirs) must surface as
    a RuntimeError at flush — the deferred-error machinery itself."""
    import ray_lightning_tpu.core.loop as loop_mod
    from ray_lightning_tpu.core.loop import LoopContext, FitConfig

    def boom(stream, path):
        raise OSError("disk gone")

    monkeypatch.setattr(loop_mod, "state_stream_to_file", boom)
    ctx = LoopContext(FitConfig(max_epochs=1), 0, 1)
    ctx.state = None
    monkeypatch.setattr(ctx, "checkpoint_payload", lambda: {"state": {}})
    ctx.save_checkpoint(str(tmp_path / "x.ckpt"), async_write=True)
    with pytest.raises(RuntimeError, match="async checkpoint write"):
        ctx.flush_checkpoints()
    ctx.close_checkpoint_writer()


def test_async_checkpoint_writer_retires_per_fit(tmp_path):
    """The writer thread is per-fit, not per-process: after fit end no
    rlt-ckpt-writer thread survives (tuner sweeps run many fits)."""
    import threading as _threading

    from ray_lightning_tpu.core.callbacks import ModelCheckpoint
    from ray_lightning_tpu.models import BoringDataModule, BoringModel
    from ray_lightning_tpu.parallel.strategies import LocalStrategy

    for _ in range(2):
        cb = ModelCheckpoint(dirpath=str(tmp_path / "c"), async_write=True)
        tr = Trainer(strategy=LocalStrategy(), max_epochs=1,
                     callbacks=[cb], default_root_dir=str(tmp_path),
                     enable_checkpointing=False)
        tr.fit(BoringModel(), BoringDataModule())
    alive = [t.name for t in _threading.enumerate()
             if t.name == "rlt-ckpt-writer"]
    assert not alive, alive


class TestEMA:
    def test_ema_tracks_exponential_mean(self, tmp_path):
        """The shadow equals the analytically-compounded EMA of the
        per-step params (replayed on host from snapshots)."""
        import jax

        from ray_lightning_tpu.core.callbacks import (
            Callback, ExponentialMovingAverage,
        )
        from ray_lightning_tpu.core.trainer import Trainer
        from ray_lightning_tpu.models import BoringDataModule, BoringModel
        from ray_lightning_tpu.parallel.strategies import LocalStrategy

        class Spy(Callback):
            def __init__(self):
                self.snaps = []

            def on_train_batch_end(self, trainer, module, logs, i):
                self.snaps.append(jax.device_get(trainer.state.params))

        d = 0.9
        spy, ema = Spy(), ExponentialMovingAverage(decay=d)
        trainer = Trainer(strategy=LocalStrategy(), max_epochs=2,
                         callbacks=[spy, ema],  # spy first: raw params
                         default_root_dir=str(tmp_path),
                         enable_checkpointing=False)
        trainer.fit(BoringModel(), BoringDataModule())
        expect = None
        for p in spy.snaps:
            if expect is None:
                expect = jax.tree_util.tree_map(
                    lambda a: np.asarray(a, np.float64), p)
            else:
                expect = jax.tree_util.tree_map(
                    lambda e, a: e * d + np.asarray(a, np.float64) * (1 - d),
                    expect, p)
        got = jax.device_get(trainer.params)  # swap_at_end=True
        for a, b in zip(jax.tree_util.tree_leaves(expect),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(b), a, rtol=1e-5,
                                       atol=1e-7)

    def test_ema_cadence_compounds_decay(self, tmp_path):
        """update_every_n_steps=k: updates fire every k OPTIMIZER steps
        with decay compounded as decay**advanced — verified against an
        analytic host replay of exactly that rule."""
        import jax

        from ray_lightning_tpu.core.callbacks import (
            Callback, ExponentialMovingAverage,
        )
        from ray_lightning_tpu.core.trainer import Trainer
        from ray_lightning_tpu.models import BoringDataModule, BoringModel
        from ray_lightning_tpu.parallel.strategies import LocalStrategy

        class Spy(Callback):
            def __init__(self):
                self.snaps = []  # (global_step, params)

            def on_train_batch_end(self, trainer, module, logs, i):
                self.snaps.append(
                    (trainer.global_step,
                     jax.device_get(trainer.state.params)))

        d, k = 0.9, 2
        spy = Spy()
        ema = ExponentialMovingAverage(decay=d, update_every_n_steps=k,
                                       swap_at_end=False)
        trainer = Trainer(strategy=LocalStrategy(), max_epochs=2,
                         callbacks=[spy, ema],
                         default_root_dir=str(tmp_path),
                         enable_checkpointing=False)
        trainer.fit(BoringModel(), BoringDataModule())

        expect, last = None, None
        for gs, p in spy.snaps:
            if gs == 0 or gs == last:
                continue
            if expect is None:
                expect = jax.tree_util.tree_map(
                    lambda a: np.asarray(a, np.float64), p)
                last = gs
                continue
            if gs - last < k:
                continue
            dd = d ** (gs - last)
            expect = jax.tree_util.tree_map(
                lambda e, a: e * dd + np.asarray(a, np.float64) * (1 - dd),
                expect, p)
            last = gs
        shadow = jax.device_get(ema.ema_params)
        for a, b in zip(jax.tree_util.tree_leaves(expect),
                        jax.tree_util.tree_leaves(shadow)):
            np.testing.assert_allclose(np.asarray(b), a, rtol=1e-5,
                                       atol=1e-7)
        # swap_at_end=False: returned params are the RAW trained ones.
        raw = jax.tree_util.tree_leaves(jax.device_get(trainer.params))
        assert any(
            np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-9
            for a, b in zip(raw, jax.tree_util.tree_leaves(shadow)))

    def test_ema_respects_grad_accumulation(self, tmp_path):
        """Under accumulate_grad_batches the EMA advances per OPTIMIZER
        step, not per micro-batch: the horizon is what the user set."""
        from ray_lightning_tpu.core.callbacks import ExponentialMovingAverage
        from ray_lightning_tpu.core.trainer import Trainer
        from ray_lightning_tpu.models import BoringDataModule, BoringModel
        from ray_lightning_tpu.parallel.strategies import LocalStrategy

        ema = ExponentialMovingAverage(decay=0.5, swap_at_end=False)
        trainer = Trainer(strategy=LocalStrategy(), max_epochs=1,
                         accumulate_grad_batches=2, callbacks=[ema],
                         default_root_dir=str(tmp_path),
                         enable_checkpointing=False)
        trainer.fit(BoringModel(), BoringDataModule())
        # 4 micro-batches -> 2 optimizer steps: seed at gs=1 plus ONE
        # decay update at gs=2.
        assert trainer.global_step == 2
        assert ema._last_step == 2

    def test_ema_shadow_survives_remote_roundtrip(self, tmp_path):
        """swap_at_end=False on a REMOTE strategy: the shadow ships in
        the callback state, so the driver-side callback has it."""
        import jax

        from ray_lightning_tpu.core.callbacks import ExponentialMovingAverage
        from ray_lightning_tpu.core.trainer import Trainer
        from ray_lightning_tpu.models import BoringDataModule, BoringModel
        from ray_lightning_tpu.parallel.strategies import RayStrategy

        ema = ExponentialMovingAverage(decay=0.9, swap_at_end=False)
        trainer = Trainer(strategy=RayStrategy(num_workers=1), max_epochs=2,
                         callbacks=[ema], default_root_dir=str(tmp_path),
                         enable_checkpointing=False)
        trainer.fit(BoringModel(), BoringDataModule())
        assert ema.ema_params is not None  # restored driver-side
        leaves = jax.tree_util.tree_leaves(ema.ema_params)
        assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)

    def test_ema_rejects_bad_args(self):
        from ray_lightning_tpu.core.callbacks import ExponentialMovingAverage

        with pytest.raises(ValueError):
            ExponentialMovingAverage(decay=1.0)
        with pytest.raises(ValueError):
            ExponentialMovingAverage(update_every_n_steps=0)

"""Megastep execution (ISSUE 5): K micro-steps fused into one compiled
scan must train the SAME fit as the per-step loop.

Parity is pinned on the 8-device CPU mesh (conftest) across every
semantic surface the stride touches: loss/metric/params trajectories,
``global_step``/``micro_step`` accounting, gradient accumulation,
partial final strides, checkpoint cadence, EMA shadows, mid-stride
preemption drains, and pinned chaos injections (which lower K to 1
around the fault).  Plus the prefetch-lifecycle regression: a fit that
raises mid-epoch must never leak its ``rlt-prefetch`` producer thread
into the next attempt.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

from ray_lightning_tpu.core.callbacks import (
    Callback,
    CSVLogger,
    ExponentialMovingAverage,
    ModelCheckpoint,
)
from ray_lightning_tpu.core.loop import (
    FitConfig,
    _resolve_megastep,
    init_train_state,
)
from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.fault import drain as drain_mod
from ray_lightning_tpu.fault.drain import PreemptedError, sync_point_crossed
from ray_lightning_tpu.fault.inject import FaultInjected, step_fault_in_range
from ray_lightning_tpu.models.boring import BoringDataModule, BoringModel
from ray_lightning_tpu.parallel import step_fns
from ray_lightning_tpu.parallel import sharding as shardlib
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.parallel.strategies import LocalStrategy

pytestmark = pytest.mark.megastep

K = 4
BATCHES = 16  # micro-batches per epoch (length/batch_size below)


def _fit(tmp_path, megastep, *, lr=0.05, callbacks=None, **kw):
    kw.setdefault("max_epochs", 1)
    trainer = Trainer(
        strategy=LocalStrategy(megastep=megastep),
        enable_checkpointing=False,
        default_root_dir=str(tmp_path),
        callbacks=list(callbacks or []),
        **kw,
    )
    trainer.fit(
        BoringModel(lr=lr), BoringDataModule(length=BATCHES * 16,
                                             batch_size=16)
    )
    return trainer


def _assert_params_close(a, b, tol=1e-5):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, rtol=tol, atol=tol)


# -- make_multi_step vs K single steps ---------------------------------------

def _run_multi_vs_single(mesh):
    module = BoringModel(in_dim=16, out_dim=4, lr=0.1)
    tx = module.configure_optimizers()
    rng = jax.random.PRNGKey(7)
    raw = {"x": np.random.default_rng(0).standard_normal(
        (16, 16), dtype=np.float32)}

    state_s, sh = init_train_state(module, tx, mesh, 0, seed=0)
    state_m = init_train_state(module, tx, mesh, 0, seed=0)[0]
    single = step_fns.build_train_step(module, tx, mesh, state_shardings=sh)
    multi = step_fns.make_multi_step(
        module, tx, mesh, K, state_shardings=sh
    )
    if mesh is None:
        batch = raw
        kbatch = jax.tree_util.tree_map(lambda x: np.stack([x] * K), raw)
    else:
        batch = shardlib.make_global_batch(raw, mesh)
        kbatch = shardlib.make_global_stacked_batch([raw] * K, mesh)

    logs_seq = []
    for i in range(K):
        state_s, logs = single(state_s, batch, jax.random.fold_in(rng, i))
        logs_seq.append(float(logs["train_loss"]))
    state_m, aux = multi(state_m, kbatch, rng, np.int32(0))

    _assert_params_close(
        jax.device_get(state_s.params), jax.device_get(state_m.params)
    )
    # Stride-final logs == the last single step's logs.
    np.testing.assert_allclose(
        float(aux["last"]["train_loss"]), logs_seq[-1], rtol=1e-5
    )
    # On-device sum == sum of the per-step losses; all K finite.
    np.testing.assert_allclose(
        float(aux["sum"]["train_loss"]), sum(logs_seq), rtol=1e-5
    )
    assert float(aux["cnt"]["train_loss"]) == K


def test_multi_step_matches_singles_no_mesh():
    _run_multi_vs_single(None)


def test_multi_step_matches_singles_on_mesh():
    _run_multi_vs_single(build_mesh(MeshSpec()))


def test_multi_step_counts_nonfinite_like_host_accumulator():
    """A NaN loss inside the stride must land in the finite-count, not
    poison the on-device sum (the _RunningMeanLogs contract)."""
    class NaNAtStep(BoringModel):
        def training_step(self, params, batch, rng):
            import jax.numpy as jnp

            loss, logs = super().training_step(params, batch, rng)
            # Poison exactly one inner step: fold_in(rng, step) differs
            # per step, so key on the data instead — first batch row
            # sentinel set by the test below.
            poison = batch["x"][0, 0] > 1e5
            bad = jnp.where(poison, jnp.nan, logs["train_loss"])
            return loss, {"train_loss": bad}

    module = NaNAtStep(in_dim=8, out_dim=2, lr=0.0)
    tx = module.configure_optimizers()
    multi = step_fns.make_multi_step(module, tx, None, K)
    state = init_train_state(module, tx, None, 0, seed=0)[0]
    base = np.random.default_rng(0).standard_normal(
        (K, 4, 8)).astype(np.float32)
    base[2, 0, 0] = 1e6  # poison inner step 2
    _, aux = multi(state, {"x": base}, jax.random.PRNGKey(0), np.int32(0))
    assert float(aux["cnt"]["train_loss"]) == K - 1
    assert np.isfinite(float(aux["sum"]["train_loss"]))


# -- fit-level parity --------------------------------------------------------

def test_fit_parity_bundle(tmp_path):
    """One 2-epoch off/on fit pair carries the aligned-parity surface:
    step counters, epoch-mean metrics, final params, EMA compounding,
    checkpoint cadence, CSV cadence rows and dispatch counters — one
    compile per arm instead of one per concern (tier-1 wall budget)."""
    decay = 0.9
    snapshots = {}

    class SnapParams(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            if trainer.global_step % K == 0:
                snapshots[trainer.global_step] = jax.device_get(
                    trainer.state.params
                )

    arms = {}
    for name, mode, extra in (
        ("off", "off", [SnapParams()]),
        ("on", K, [ExponentialMovingAverage(decay=decay,
                                            swap_at_end=False)]),
    ):
        cbs = extra + [
            ModelCheckpoint(dirpath=str(tmp_path / f"{name}_ck")),
            CSVLogger(dirpath=str(tmp_path / f"csv_{name}")),
        ]
        arms[name] = (_fit(tmp_path / name, mode, max_epochs=2,
                           log_every_n_steps=4, callbacks=cbs), cbs)
    t_off, t_on = arms["off"][0], arms["on"][0]

    # Step accounting + metrics + trained params.
    assert t_on.global_step == t_off.global_step == 2 * BATCHES
    assert t_on.micro_step == t_off.micro_step == 2 * BATCHES
    assert t_on.callback_metrics["train_loss"] == pytest.approx(
        t_off.callback_metrics["train_loss"], rel=1e-5
    )
    _assert_params_close(t_off.state.params, t_on.state.params)

    # EMA follows the documented cadence contract EXACTLY: decay**K
    # compounded against stride-boundary params (== the per-step arm's
    # boundary snapshots, since the trains are param-parity).
    ema = arms["on"][1][0]
    steps = sorted(snapshots)
    expected = snapshots[steps[0]]
    d = decay ** K
    for gs in steps[1:]:
        expected = jax.tree_util.tree_map(
            lambda e, p: e * d + p * (1.0 - d), expected, snapshots[gs]
        )
    for x, y in zip(
        jax.device_get(jax.tree_util.tree_leaves(expected)),
        jax.device_get(jax.tree_util.tree_leaves(ema.ema_params)),
    ):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)

    # Checkpoint cadence: identical (epoch, global_step) filenames.
    assert (
        sorted(os.listdir(tmp_path / "off_ck"))
        == sorted(os.listdir(tmp_path / "on_ck"))
    )
    # CSV cadence (4 divides K): identical row counts.
    assert len(arms["on"][1][2].rows) == len(arms["off"][1][2].rows)

    # Dispatch counters: 2*16 micro-steps in 2*16/K stride dispatches.
    c_on = t_on.telemetry_report["counters"]
    assert c_on["megastep_dispatches"]["mean"] == 2 * BATCHES / K
    assert c_on["train_dispatches"]["mean"] == 2 * BATCHES / K
    c_off = t_off.telemetry_report["counters"]
    assert c_off["train_dispatches"]["mean"] == 2 * BATCHES
    assert "megastep_dispatches" not in c_off


def test_fit_parity_partial_final_stride(tmp_path):
    """limit=7 with K=4: one fused stride + 3 per-step fallbacks."""
    t_off = _fit(tmp_path / "off", "off", limit_train_batches=7)
    t_on = _fit(tmp_path / "on", K, limit_train_batches=7)
    assert t_on.global_step == t_off.global_step == 7
    assert t_on.micro_step == 7
    _assert_params_close(t_off.state.params, t_on.state.params)


def test_fit_parity_with_accumulation(tmp_path):
    """accum=2 runs INSIDE the scan (MultiSteps state is carry);
    global_step advances K/accum per stride."""
    t_off = _fit(tmp_path / "off", "off", accumulate_grad_batches=2)
    t_on = _fit(tmp_path / "on", K, accumulate_grad_batches=2)
    assert t_on.global_step == t_off.global_step == BATCHES // 2
    assert t_on.micro_step == BATCHES
    _assert_params_close(t_off.state.params, t_on.state.params)


def test_max_steps_means_max_steps(tmp_path):
    """max_steps=5 with K=4: one stride (4) + one single (1), exactly
    5 optimizer updates — parity with the per-step loop."""
    t_on = _fit(tmp_path / "on", K, max_epochs=5, max_steps=5)
    t_off = _fit(tmp_path / "off", "off", max_epochs=5, max_steps=5)
    assert t_on.global_step == t_off.global_step == 5
    _assert_params_close(t_off.state.params, t_on.state.params)


def test_epoch_mean_metrics_parity(tmp_path):
    """The epoch train_loss is the mean over ALL micro-steps — the
    on-device stride sums must agree with the host accumulator."""
    t_off = _fit(tmp_path / "off", "off", max_epochs=2)
    t_on = _fit(tmp_path / "on", K, max_epochs=2)
    for key in ("train_loss",):
        assert t_on.callback_metrics[key] == pytest.approx(
            t_off.callback_metrics[key], rel=1e-5
        )


def test_ema_parity(tmp_path):
    """EMA under megastep follows the documented cadence contract
    EXACTLY: the shadow compounds ``decay**K`` against stride-boundary
    params — the same trajectory as ``update_every_n_steps=K`` over the
    per-step fit's params (horizon-preserving; both trains are
    param-parity anyway, pinned above)."""
    decay = 0.9
    snapshots = {}

    class SnapParams(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            if trainer.global_step % K == 0:
                snapshots[trainer.global_step] = jax.device_get(
                    trainer.state.params
                )

    ema_on = ExponentialMovingAverage(decay=decay, swap_at_end=False)
    _fit(tmp_path / "off", "off", callbacks=[SnapParams()])
    _fit(tmp_path / "on", K, callbacks=[ema_on])

    # Expected: init at the first stride boundary, then decay**K blends
    # against each later boundary's params.
    steps = sorted(snapshots)
    expected = snapshots[steps[0]]
    d = decay ** K
    for gs in steps[1:]:
        expected = jax.tree_util.tree_map(
            lambda e, p: e * d + p * (1.0 - d), expected, snapshots[gs]
        )
    la = jax.device_get(jax.tree_util.tree_leaves(expected))
    lb = jax.device_get(jax.tree_util.tree_leaves(ema_on.ema_params))
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_checkpoint_cadence_parity(tmp_path):
    """ModelCheckpoint epochs see identical (epoch, global_step) under
    megastep — same filenames, same best path."""
    cb_off = ModelCheckpoint(dirpath=str(tmp_path / "off_ck"))
    cb_on = ModelCheckpoint(dirpath=str(tmp_path / "on_ck"))
    _fit(tmp_path / "off", "off", max_epochs=2, callbacks=[cb_off])
    _fit(tmp_path / "on", K, max_epochs=2, callbacks=[cb_on])
    assert (
        sorted(os.listdir(tmp_path / "off_ck"))
        == sorted(os.listdir(tmp_path / "on_ck"))
    )
    assert (
        os.path.basename(cb_off.best_model_path)
        == os.path.basename(cb_on.best_model_path)
    )


def test_csv_rows_on_cadence_crossings(tmp_path):
    """The logger fires on cadence CROSSINGS, not `% == 0` (megastep
    strides jump over exact multiples).  With the cadence dividing K
    the two modes produce identical rows; a non-dividing cadence
    rounds to stride boundaries — one row per crossed stride."""
    rows = {}
    for name, mode, cadence in (
        ("off4", "off", 4), ("on4", K, 4), ("on3", K, 3),
    ):
        logger = CSVLogger(dirpath=str(tmp_path / f"csv_{name}"))
        _fit(tmp_path / name, mode, log_every_n_steps=cadence,
             callbacks=[logger])
        rows[name] = len(logger.rows)
    # 16 batches, cadence 4: rows at 4/8/12/16 + epoch row + val row.
    assert rows["on4"] == rows["off4"] == 4 + 2
    # Cadence 3: per-stride rounding — strides end at 4/8/12/16, the
    # 12-boundary covers two cadence points (9 and 12) in one row.
    assert rows["on3"] == 4 + 2


def test_csv_cadence_stays_aligned_across_resume(tmp_path):
    """A resumed fit keeps CSV rows on the log_every_n_steps grid: the
    cadence anchor is the restore point, not zero — no spurious row on
    the first post-resume hook."""
    class DrainMid(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            if (trainer.micro_step >= 6
                    and not drain_mod.drain_requested()):
                drain_mod.request_drain("csv-cadence")

    with pytest.raises(PreemptedError) as err:
        _fit(tmp_path / "a", K, log_every_n_steps=8,
             callbacks=[DrainMid()])
    drain_mod.reset_drain()
    logger = CSVLogger(dirpath=str(tmp_path / "csv"))
    resumed = Trainer(
        strategy=LocalStrategy(megastep=K),
        enable_checkpointing=False,
        default_root_dir=str(tmp_path / "resume"),
        resume_from_checkpoint=err.value.checkpoint,
        log_every_n_steps=8,
        callbacks=[logger],
    )
    resumed.fit(
        BoringModel(lr=0.05),
        BoringDataModule(length=BATCHES * 16, batch_size=16),
    )
    # Drain landed at the stride-2 boundary (micro 8); the remaining
    # strides end at 12 and 16, and the only cadence-8 crossing left is
    # 16 — one step row, plus the epoch and val rows.  An anchor of 0
    # instead of the restore point would fire a spurious extra row on
    # the first post-resume stride (crossing(0, 12, 8) is true).
    assert len(logger.rows) == 1 + 2, [r.get("step") for r in logger.rows]


def test_dispatch_counters(tmp_path):
    """16 micro-steps in 4 stride dispatches — the counter behind the
    benchmark's ``dispatches_per_step.train``."""
    t = _fit(tmp_path, K)
    counters = t.telemetry_report["counters"]
    assert counters["megastep_dispatches"]["mean"] == K
    assert counters["train_dispatches"]["mean"] == K  # all fused
    t2 = _fit(tmp_path / "off", "off")
    assert (
        t2.telemetry_report["counters"]["train_dispatches"]["mean"]
        == BATCHES
    )


# -- drain / chaos -----------------------------------------------------------

def test_mid_stride_drain_and_exact_resume(tmp_path):
    """A drain request landing mid-stride is honored at the next stride
    boundary; the resumed fit replays exactly the remaining batches
    (zero lost steps) and matches the uninterrupted trajectory."""
    class DrainLate(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            if trainer.micro_step >= 6:  # lands inside stride 2
                drain_mod.request_drain("test-preempt")

    with pytest.raises(PreemptedError) as err_info:
        _fit(tmp_path, K, callbacks=[DrainLate()])
    err = err_info.value
    assert err.step == 8, "drain must land at the stride boundary"
    assert err.checkpoint and os.path.exists(err.checkpoint)

    resumed = Trainer(
        strategy=LocalStrategy(megastep=K),
        enable_checkpointing=False,
        default_root_dir=str(tmp_path / "resume"),
        resume_from_checkpoint=err.checkpoint,
    )
    resumed.fit(
        BoringModel(lr=0.05),
        BoringDataModule(length=BATCHES * 16, batch_size=16),
    )
    assert resumed.micro_step == BATCHES
    assert resumed.global_step == BATCHES
    clean = _fit(tmp_path / "clean", K)
    _assert_params_close(clean.state.params, resumed.state.params)


def test_chaos_step_injection_fires_at_exact_inner_step(tmp_path):
    """A pinned exc@step:5 inside stride 2 lowers K to 1 around the
    injection and fires exactly at micro-step 5."""
    seen = []

    class Track(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            seen.append(trainer.micro_step)

    os.environ["RLT_FAULT"] = "exc@step:5,rank:0"
    try:
        with pytest.raises(FaultInjected):
            _fit(tmp_path, K, callbacks=[Track()])
    finally:
        os.environ.pop("RLT_FAULT", None)
    # Stride 1 fused (boundary hook at 4), stride 2 degraded to singles:
    # step 4 trains (hook at 5), then the fault fires BEFORE step 5.
    assert seen == [4, 5]


def test_strides_resume_after_once_fault_fired(tmp_path):
    """An exactly-once fault stops degrading strides after its marker
    lands — chaos runs keep megastep performance post-injection."""
    os.environ["RLT_FAULT"] = "exc@step:2,rank:0"
    os.environ["RLT_FAULT_STATE"] = str(tmp_path / "chaos")
    try:
        with pytest.raises(FaultInjected):
            _fit(tmp_path / "a", K)
        assert not step_fault_in_range(0, 100, epoch=0, rank=0)
        t = _fit(tmp_path / "b", K)  # trains through, fused again
        assert t.telemetry_report["counters"][
            "megastep_dispatches"]["mean"] == K
    finally:
        os.environ.pop("RLT_FAULT", None)
        os.environ.pop("RLT_FAULT_STATE", None)


def test_step_fault_in_range_matching():
    os.environ["RLT_FAULT"] = "crash@step:7,rank:1;hang@point:spawn"
    try:
        assert step_fault_in_range(0, 8, epoch=0, rank=1)
        # Rank pins do NOT narrow the degrade decision: strides shape
        # the compiled program's collective sequence, so every rank must
        # lower K around the injection or the mesh would run divergent
        # programs and hang.  fire() still honors the pin.
        assert step_fault_in_range(0, 8, epoch=0, rank=0)
        assert not step_fault_in_range(8, 16, epoch=0, rank=1)
        assert not step_fault_in_range(8, 16, epoch=0, rank=0)
    finally:
        os.environ.pop("RLT_FAULT", None)


def test_sync_point_crossed():
    # Per-step shape: crossing iff step % every == 0.
    assert [sync_point_crossed(s, s + 1, 8) for s in range(7, 9)] == [
        True, False,
    ]
    # Stride shape: one crossing per covered multiple.
    assert sync_point_crossed(4, 8, 8)
    assert not sync_point_crossed(8, 12, 8)
    assert sync_point_crossed(0, 16, 8)
    assert sync_point_crossed(5, 6, 1)  # every<=1: always


# -- prefetch lifecycle ------------------------------------------------------

def _prefetch_threads():
    return [t for t in threading.enumerate() if t.name == "rlt-prefetch"]


def _await_no_prefetch_threads(timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not _prefetch_threads():
            return True
        time.sleep(0.05)
    return False


def test_prefetch_thread_joined_after_midfit_raise(tmp_path):
    """Drain raises and user exceptions mid-epoch must signal AND join
    the rlt-prefetch producer — the respawn/tuner-sweep leak
    regression: repeated raising fits in one process accumulate zero
    threads."""
    class Boom(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            raise RuntimeError("boom")

    class DrainNow(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            drain_mod.request_drain("leak-test")

    for i in range(3):
        with pytest.raises(RuntimeError):
            _fit(tmp_path / f"boom{i}", K, callbacks=[Boom()])
        assert _await_no_prefetch_threads(), "leaked rlt-prefetch thread"
    err = None
    with pytest.raises(PreemptedError) as err:
        _fit(tmp_path / "drain", K, callbacks=[DrainNow()])
    assert _await_no_prefetch_threads(), "leaked rlt-prefetch thread"
    # The elastic-respawn shape: resume from the drain ckpt in the SAME
    # process — the fresh fit must start with a clean producer slate.
    resumed = Trainer(
        strategy=LocalStrategy(megastep=K),
        enable_checkpointing=False,
        default_root_dir=str(tmp_path / "resume"),
        resume_from_checkpoint=err.value.checkpoint,
    )
    resumed.fit(
        BoringModel(lr=0.05),
        BoringDataModule(length=BATCHES * 16, batch_size=16),
    )
    assert resumed.micro_step == BATCHES
    assert _await_no_prefetch_threads()


# -- crash forensics vs the async log fetch ----------------------------------

def test_crash_bundle_carries_latest_log_boundary(tmp_path):
    """The async log fetch must not cost crash forensics their
    freshness: a fit that dies right after a log boundary was SCHEDULED
    (but not yet landed) must flush it before the flight bundle
    snapshots callback_metrics — the bundle's ``train_loss`` equals the
    loss a clean fit reports when truncated at the crash step, not the
    previous boundary's value."""
    import json

    crash_at = 5

    class Boom(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            if trainer.micro_step >= crash_at:
                raise RuntimeError("boom-forensics")

    with pytest.raises(RuntimeError, match="boom-forensics"):
        _fit(tmp_path / "crash", "off", callbacks=[Boom()],
             log_every_n_steps=1)
    bundle = (tmp_path / "crash" / "telemetry" / "flight"
              / "bundle-rank0.json")
    assert bundle.exists()
    doc = json.loads(bundle.read_text())
    assert doc["micro_step"] == crash_at
    # A clean fit's per-step log trajectory pins the expected value:
    # the bundle must carry the CRASH step's loss (same seed/data ->
    # bitwise equal), not the previous boundary's.
    per_step = []

    class Rec(Callback):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            per_step.append(
                {k: float(v) for k, v in jax.device_get(logs).items()}
            )

    _fit(tmp_path / "clean", "off", callbacks=[Rec()],
         log_every_n_steps=1)
    assert doc["callback_metrics"]["train_loss"] == pytest.approx(
        per_step[crash_at - 1]["train_loss"], abs=0.0
    )
    # The guarded regression: before the crash-path flush, the bundle
    # froze one boundary behind (the step-4 value here).
    assert (doc["callback_metrics"]["train_loss"]
            != per_step[crash_at - 2]["train_loss"])


# -- knob resolution ---------------------------------------------------------

def test_resolve_megastep_env_and_values(monkeypatch):
    monkeypatch.delenv("RLT_MEGASTEP", raising=False)
    assert _resolve_megastep(FitConfig(megastep="off")) == 1
    assert _resolve_megastep(FitConfig(megastep=6)) == 6
    assert _resolve_megastep(FitConfig(megastep="4")) == 4
    # auto on the CPU test backend = off (docs/PERFORMANCE.md).
    assert _resolve_megastep(FitConfig(megastep="auto")) == 1
    assert _resolve_megastep(FitConfig()) == 1
    monkeypatch.setenv("RLT_MEGASTEP", "5")
    assert _resolve_megastep(FitConfig()) == 5
    assert _resolve_megastep(FitConfig(megastep=2)) == 2  # explicit wins
    # An operator CLEARING the knob (RLT_MEGASTEP=) means off, not auto.
    monkeypatch.setenv("RLT_MEGASTEP", "")
    assert _resolve_megastep(FitConfig()) == 1


def test_midfit_first_use_compile_excluded_from_step_aggregates():
    from ray_lightning_tpu.telemetry.step_stats import StepStats

    ss = StepStats(sample_every=1000)
    ss.record_stride(5.0, 0.0, 4.9, examples=32, k=8)     # compile stride
    for _ in range(4):
        ss.record_stride(0.08, 0.001, 0.002, examples=32, k=8)
    # The lazy per-step program compiles at the partial tail: booked as
    # compile, NOT a steady-state outlier in step_time_ms/dispatch_ms.
    ss.record_step(3.0, 0.0, 2.9, examples=4, compiled=True)
    ss.record_step(0.01, 0.001, 0.002, examples=4)
    snap = ss.summary()
    assert snap["compile_ms"] == pytest.approx(5000.0 + 3000.0)
    assert snap["step_max_ms"] < 100.0       # no 3s outlier
    assert snap["dispatch_max_ms"] < 100.0


def test_megastep_validation_is_eager():
    with pytest.raises(ValueError):
        FitConfig(megastep="bogus")
    with pytest.raises(ValueError):
        FitConfig(megastep=0)
    with pytest.raises(ValueError):
        LocalStrategy(megastep=-3)
    with pytest.raises(ValueError):
        Trainer(megastep="nope")


def test_strategy_knob_fills_unset_trainer_default(tmp_path):
    t = _fit(tmp_path, 2)  # via LocalStrategy(megastep=2)
    assert t.telemetry_report["counters"]["megastep_dispatches"][
        "mean"] == BATCHES / 2

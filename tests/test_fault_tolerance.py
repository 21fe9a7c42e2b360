"""Failure detection + elastic restart: the recovery-plane acceptance
matrix (the restarts' own fits, no fault injected: ``test_elastic.py``).

The reference only fails fast (worker death raises out of ``ray.get``,
SURVEY §5 "failure detection: ABSENT"); this framework adds opt-in
elastic recovery: ``max_restarts=N`` respawns the worker set and resumes
from the newest VERIFIED restart checkpoint.  The ``chaos``-marked tests
drive every recovery path end-to-end with deterministically injected
faults (``RLT_FAULT``, fault/inject.py): crash, hang→monitor-abort,
SIGTERM preemption drain, and torn/bit-flipped checkpoints falling back
to the previous good one.
"""

import os

import pytest

from ray_lightning_tpu.cluster.actor import ActorDiedError
from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.fault.drain import PreemptedError
from ray_lightning_tpu.models.boring import BoringDataModule, BoringModel
from ray_lightning_tpu.parallel.strategies import RayStrategy


@pytest.fixture
def chaos_env(tmp_path, monkeypatch):
    """Inject one RLT_FAULT plan with a shared fired-marker dir (so the
    respawned worker set trains through instead of re-dying)."""

    def _arm(fault: str) -> None:
        monkeypatch.setenv("RLT_FAULT", fault)
        monkeypatch.setenv("RLT_FAULT_STATE", str(tmp_path / "chaos"))

    return _arm


def test_a_dead_ranks_peers_are_asked_at_once_and_waited_for_briefly():
    """The survivors of a dead rank are wedged in its collective: the
    exit message cannot end them and the drain handler takes their
    SIGTERM, so each used to cost ``kill()``'s 5 s twice, one after the
    other (10 s a restart or a teardown of two workers, PR 32).  Every
    worker is asked before any is waited for, under the monitor abort's
    grace, as soon as the death is known."""
    from ray_lightning_tpu.core.loop import FitConfig

    strategy = RayStrategy(num_workers=2)
    calls = []

    class Worker:
        def request_exit(self):
            calls.append("asked")

        def kill(self, timeout=5.0):
            calls.append(timeout)

    def dies(*args, **kwargs):
        raise ActorDiedError("rank 1 died")

    strategy._backend, strategy._workers = object(), [Worker(), Worker()]
    strategy._run_once = dies
    with pytest.raises(ActorDiedError):
        strategy.run("validate", None, None, FitConfig(), [])
    assert calls == ["asked", "asked", 1.0, 1.0]


# ---------------------------------------------------------------------------
# Chaos acceptance matrix (deterministic fault injection, fault/inject.py)
# ---------------------------------------------------------------------------

def _chaos_fit(tmp_path, max_epochs=3, max_restarts=1, **strategy_kw):
    """One worker actor, 2 batches/epoch: every scenario below must end
    with exactly ``max_epochs * 2`` optimizer steps after recovering."""
    strategy = RayStrategy(
        num_workers=1, max_restarts=max_restarts,
        restart_backoff_s=0.05, **strategy_kw,
    )
    trainer = Trainer(
        strategy=strategy,
        max_epochs=max_epochs,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
        limit_train_batches=2,
        limit_val_batches=1,
    )
    trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
    return trainer, strategy


def _event_kinds(trainer):
    return [e["kind"] for e in trainer.monitor_report.get("events", [])]


@pytest.mark.remote
@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_crash_recovers_with_backoff(tmp_path, chaos_env):
    """Injected hard crash: the fit completes with the exact step
    count, and the governor's backoff delay is observable in
    monitor_report (the acceptance criterion)."""
    chaos_env("crash@step:3,rank:0")
    trainer, strategy = _chaos_fit(tmp_path)
    assert trainer.global_step == 6
    assert strategy.restarts_used == 1
    kinds = _event_kinds(trainer)
    assert "backoff" in kinds and "elastic_restart" in kinds
    backoff = next(
        e for e in trainer.monitor_report["events"]
        if e["kind"] == "backoff"
    )
    assert backoff["delay_s"] > 0


@pytest.mark.remote
@pytest.mark.chaos
@pytest.mark.slow
def test_monitor_abort_feeds_elastic_restart(tmp_path, chaos_env):
    """A hang injected via the chaos plane: the watchdog stalls→aborts,
    the abort becomes an elastic restart (not a dead fit), the fit
    completes, and monitor_report records the whole story."""
    chaos_env("hang@step:3,rank:0,secs:300")
    trainer, strategy = _chaos_fit(
        tmp_path,
        telemetry={"tier": "cheap", "heartbeat_s": 0.3},
        monitor={"hang_intervals": 2, "abort_after_s": 1.0},
    )
    assert trainer.global_step == 6
    assert strategy.restarts_used == 1
    kinds = _event_kinds(trainer)
    # The failed attempt's watchdog records survive the respawn — the
    # final report narrates the fit, not just the last attempt.  Under
    # CPU contention the wedged rank may read as heartbeat_lost rather
    # than stall (late beats); either way the abort must have fired and
    # fed the elastic path.
    assert "stall" in kinds or "heartbeat_lost" in kinds
    assert "abort" in kinds
    assert "elastic_restart" in kinds


@pytest.mark.remote
@pytest.mark.chaos
@pytest.mark.slow
def test_sigterm_preemption_drains_without_consuming_budget(
    tmp_path, chaos_env
):
    """SIGTERM → graceful drain → step-granular checkpoint → budget-free
    respawn.  The resumed fit replays NOTHING (exact final step count)
    and ``restarts_used`` stays 0."""
    chaos_env("sigterm@step:3,rank:0")
    trainer, strategy = _chaos_fit(tmp_path, max_epochs=2)
    assert trainer.global_step == 4
    assert strategy.restarts_used == 0
    assert strategy.preempt_restarts_used == 1
    kinds = _event_kinds(trainer)
    assert "drain" in kinds and "preempt_restart" in kinds
    drain_ev = next(
        e for e in trainer.monitor_report["events"]
        if e["kind"] == "drain"
    )
    assert "drain-step-" in drain_ev["ckpt"]


@pytest.mark.remote
@pytest.mark.chaos
@pytest.mark.slow
def test_sigterm_without_elastic_raises_resumable(tmp_path, chaos_env):
    """No elastic recovery: the drain surfaces as a TYPED
    PreemptedError (across the actor RPC boundary) naming a drain
    checkpoint that a follow-up fit resumes from with no lost steps."""
    chaos_env("sigterm@step:3,rank:0")
    strategy = RayStrategy(num_workers=1, max_restarts=0)
    trainer = Trainer(
        strategy=strategy, max_epochs=2,
        default_root_dir=str(tmp_path), enable_checkpointing=False,
        limit_train_batches=2, limit_val_batches=1,
    )
    with pytest.raises(PreemptedError) as err:
        trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
    ckpt = err.value.checkpoint
    assert ckpt and os.path.exists(ckpt)
    assert "drain checkpoint" in str(err.value)

    resumed = Trainer(
        strategy=RayStrategy(num_workers=1), max_epochs=2,
        default_root_dir=str(tmp_path), enable_checkpointing=False,
        limit_train_batches=2, limit_val_batches=1,
        resume_from_checkpoint=ckpt,
    )
    resumed.fit(BoringModel(), BoringDataModule(batch_size=16))
    # 3 micro-steps trained pre-drain + 1 after resume = the full 4.
    assert resumed.global_step == 4
    assert resumed.micro_step == 4


@pytest.mark.remote
@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("corruption", ["bitflip", "torn"])
def test_corrupt_newest_checkpoint_falls_back(
    tmp_path, chaos_env, corruption
):
    """The newest restart checkpoint is corrupted (bit flip / torn
    write), then the worker crashes: restart discovery walks back to
    the previous VERIFIED checkpoint — never from scratch — and the
    fallback is loud (``ckpt_corrupt`` event)."""
    chaos_env(
        f"{corruption}@point:ckpt_write,nth:2,rank:0;crash@step:5,rank:0"
    )
    trainer, strategy = _chaos_fit(tmp_path, max_epochs=4)
    assert trainer.global_step == 8
    assert strategy.restarts_used == 1
    kinds = _event_kinds(trainer)
    assert "ckpt_corrupt" in kinds
    restart = next(
        e for e in trainer.monitor_report["events"]
        if e["kind"] == "elastic_restart"
    )
    # Fell back to the epoch-0 checkpoint, not scratch.
    assert "restart-epoch-000000" in restart["ckpt"]

"""Telemetry subsystem tests (ISSUE 2): spans, step stats, MFU,
recompile counters, fleet aggregation, profiler hardening, overhead.
"""

import json
import math
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.callbacks import (
    ProfilerCallback,
    TelemetryCallback,
)
from ray_lightning_tpu.core.loop import _RunningMeanLogs
from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models import BoringDataModule, BoringModel
from ray_lightning_tpu.parallel.strategies import LocalStrategy, RayStrategy
from ray_lightning_tpu.telemetry import (
    SpanTracer,
    StepStats,
    Telemetry,
    TelemetryConfig,
    compile_event_count,
    host_stats,
    merge_snapshots,
    model_flops_per_token,
    straggler_ranks,
)
from ray_lightning_tpu.telemetry.schema import (
    validate_chrome_trace,
    validate_span_jsonl,
)

from utils import get_trainer


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tracer = SpanTracer(enabled=True, maxlen=16, rank=3)
    with tracer.span("outer"):
        time.sleep(0.001)
        with tracer.span("inner"):
            time.sleep(0.001)
    spans = tracer.events()
    # Inner CLOSES first, so it is recorded first; depth encodes nesting.
    assert [s.name for s in spans] == ["inner", "outer"]
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    assert inner.rank == 3 and outer.rank == 3
    # Temporal containment: inner lies inside outer.
    assert outer.ts <= inner.ts
    assert inner.ts + inner.dur <= outer.ts + outer.dur + 1e-6
    assert outer.dur >= inner.dur > 0


def test_span_ring_buffer_bounded():
    tracer = SpanTracer(enabled=True, maxlen=8)
    for i in range(20):
        with tracer.span(f"s{i}"):
            pass
    assert len(tracer.events()) == 8
    assert tracer.dropped == 12
    # Newest spans win.
    assert tracer.events()[-1].name == "s19"


def test_disabled_tracer_is_noop():
    tracer = SpanTracer(enabled=False)
    with tracer.span("x"):
        pass
    tracer.record("y", 0.0, 1.0)
    assert tracer.events() == []


def test_span_exports_schema_validate(tmp_path):
    tracer = SpanTracer(enabled=True, rank=1)
    with tracer.span("checkpoint_write", path="/x"):
        with tracer.span("host_transfer"):
            pass
    tracer.instant("grad_sync", mode="int8")
    jsonl = str(tmp_path / "spans.jsonl")
    chrome = str(tmp_path / "trace.json")
    assert tracer.export_jsonl(jsonl) == 3
    assert tracer.export_chrome(chrome) == 3
    with open(jsonl) as f:
        assert validate_span_jsonl(f.readlines()) == []
    with open(chrome) as f:
        doc = json.load(f)
    assert validate_chrome_trace(doc) == []
    # Chrome events are µs and carry the rank as pid.
    assert all(ev["pid"] == 1 for ev in doc["traceEvents"])


# ---------------------------------------------------------------------------
# Step stats: MFU math, recompiles, config
# ---------------------------------------------------------------------------

def test_mfu_math_on_known_gpt_config():
    """Closed-form check on GPT-2-small: the analytic accounting must
    match the published-MFU convention digit for digit."""
    from ray_lightning_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=50304, n_layer=12, n_head=12, d_model=768,
                    seq_len=1024)
    d, L, s, V = 768, 12, 1024, 50304
    expected = 3.0 * (24 * L * d * d + 4 * L * s * d + 2 * d * V)
    assert model_flops_per_token(cfg) == expected
    # Causal halves only the attention term.
    assert model_flops_per_token(cfg, "causal") == (
        3.0 * (24 * L * d * d + 2 * L * s * d + 2 * d * V)
    )

    # MFU = tokens/s * F / (peak * chips): feed a synthetic run whose
    # numbers make the expected value exact.
    ss = StepStats(flops_per_example=expected * s, tokens_per_example=s,
                   peak_flops=1e12, n_chips=2)
    ss.record_step(0.1, 0.0, 0.0, examples=1)     # compile step
    for _ in range(4):
        ss.record_step(0.05, 0.0, 0.0, examples=8)
    tp = ss.throughput()
    assert tp["tokens_per_sec"] == pytest.approx(
        tp["examples_per_sec"] * s
    )
    assert ss.mfu() == pytest.approx(
        tp["examples_per_sec"] * expected * s / (1e12 * 2)
    )


def test_vit_flops_positive_and_scales():
    from ray_lightning_tpu.models.vit import ViTConfig
    from ray_lightning_tpu.telemetry import vit_flops_per_example

    small, big = ViTConfig.tiny(), ViTConfig()
    assert 0 < vit_flops_per_example(small) < vit_flops_per_example(big)


def test_recompile_counter_increments_on_shape_change():
    ss = StepStats()

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.ones((3,)))
    mid = ss.recompiles
    assert mid >= 1
    f(jnp.ones((5,)))  # shape change → new XLA compile
    assert ss.recompiles >= mid + 1
    # A second StepStats starts from NOW, not from process start.
    ss2 = StepStats()
    assert ss2.recompiles == 0
    assert compile_event_count() >= 2


def test_step_stats_compile_step_excluded():
    ss = StepStats()
    ss.record_step(5.0, 0.0, 4.9, examples=8)      # compile
    ss.record_step(0.01, 0.001, 0.002, examples=8)
    ss.record_step(0.02, 0.002, 0.003, examples=8, sampled=True)
    assert ss.compile_ms == pytest.approx(5000.0)
    head = ss.headline()
    assert head["step_time_ms"] == pytest.approx(15.0)
    assert head["data_wait_ms"] == pytest.approx(1.5)
    assert head["device_step_ms"] == pytest.approx(20.0)
    summary = ss.summary()
    assert summary["steps"] == 3 and summary["examples"] == 16


def test_telemetry_config_coercion(monkeypatch):
    assert TelemetryConfig.coerce(None).tier == "cheap"
    monkeypatch.setenv("RLT_TELEMETRY", "full")
    monkeypatch.setenv("RLT_TELEMETRY_SAMPLE", "7")
    cfg = TelemetryConfig.coerce(None)
    assert cfg.tier == "full" and cfg.sample_every == 7
    assert TelemetryConfig.coerce("off").tier == "off"
    assert TelemetryConfig.coerce({"tier": "cheap", "span_buffer": 9})
    with pytest.raises(ValueError):
        TelemetryConfig.coerce("verbose")
    with pytest.raises(ValueError):
        LocalStrategy(telemetry="typo")  # strategies validate eagerly


# ---------------------------------------------------------------------------
# Fleet aggregation
# ---------------------------------------------------------------------------

def _snap(rank, step_ms, bytes_=1000):
    return {
        "rank": rank,
        "tier": "cheap",
        "counters": {"grad_sync_bytes": bytes_,
                     "grad_sync_compression_ratio": 3.9,
                     "checkpoint_writes": 1},
        "meta": {"grad_sync_mode": "int8"},
        "step_stats": {"step_mean_ms": step_ms, "steps": 10},
    }


def test_merge_snapshots_min_max_mean_skew():
    report = merge_snapshots([_snap(1, 30.0), _snap(0, 10.0)])
    assert report["world_size"] == 2
    view = report["step_stats"]["step_mean_ms"]
    assert view["min"] == 10.0 and view["max"] == 30.0
    assert view["mean"] == 20.0
    assert view["skew_pct"] == pytest.approx(100.0)
    # Per-rank snapshots kept, rank-sorted.
    assert [s["rank"] for s in report["per_rank"]] == [0, 1]
    # grad_sync_* stats are per-device analytic constants — NEVER
    # summed across ranks (a "fleet total" would be a misread); real
    # additive counters are.
    assert "sum" not in report["counters"]["grad_sync_bytes"]
    assert "sum" not in report["counters"]["grad_sync_compression_ratio"]
    assert report["counters"]["checkpoint_writes"]["sum"] == 2
    assert report["meta"]["grad_sync_mode"] == "int8"
    assert straggler_ranks(report, "step_mean_ms", 20.0) == [1]
    assert merge_snapshots([]) == {}
    assert merge_snapshots([{}, None]) == {}


def test_merge_keeps_rank_zero_only_counters():
    """checkpoint_writes (rank-0-guarded file I/O) and nonfinite_logs
    (one poisoned rank) must survive the merge as zero-padded views,
    not vanish exactly when ranks disagree."""
    a = _snap(0, 10.0)
    a["counters"]["nonfinite_logs"] = 4
    b = _snap(1, 10.0)
    del b["counters"]["checkpoint_writes"]
    report = merge_snapshots([a, b])
    ckpt = report["counters"]["checkpoint_writes"]
    assert ckpt["mean"] == 0.5 and ckpt["sum"] == 1
    assert ckpt["ranks_reporting"] == 1
    nan = report["counters"]["nonfinite_logs"]
    assert nan["max"] == 4 and nan["sum"] == 4
    # Fleet-complete rule still applies to step timings: a metric only
    # SOME ranks computed would make the mean lie about the fleet.
    a2, b2 = _snap(0, 10.0), _snap(1, 10.0)
    a2["step_stats"]["mfu"] = 0.4
    partial = merge_snapshots([a2, b2])
    assert "mfu" not in partial["step_stats"]


def test_host_stats_shape():
    stats = host_stats()
    assert isinstance(stats, dict)
    assert stats.get("cpu_count")
    if "mem_total_bytes" in stats:
        assert stats["mem_total_bytes"] > 0


# ---------------------------------------------------------------------------
# Loop integration
# ---------------------------------------------------------------------------

def test_fit_records_headline_metrics(tmp_path):
    """Acceptance: a plain fit() records step_time_ms, data_wait_ms and
    examples_per_sec in callback_metrics, and the trainer carries a
    telemetry report with grad-sync visibility."""
    trainer = get_trainer(LocalStrategy(), max_epochs=2, tmp_path=tmp_path)
    trainer.fit(BoringModel(), BoringDataModule(length=64, batch_size=16))
    cm = trainer.callback_metrics
    for key in ("step_time_ms", "data_wait_ms", "dispatch_ms",
                "examples_per_sec", "recompiles"):
        assert key in cm, f"missing {key}"
        assert np.isfinite(cm[key])
    assert cm["examples_per_sec"] > 0
    report = trainer.telemetry_report
    assert report["world_size"] == 1 and report["tier"] == "cheap"
    assert report["step_stats"]["step_mean_ms"]["mean"] > 0
    # Grad-sync is visible through the SAME report (full-width here).
    assert report["meta"]["grad_sync_mode"] == "full"
    # Checkpoint writes + result-package host transfers were counted.
    assert report["counters"]["checkpoint_writes"]["mean"] >= 1
    assert report["counters"]["host_transfers"]["mean"] >= 1


def test_gpt_fit_records_tokens_and_mfu(tmp_path, monkeypatch):
    """Acceptance: the GPT family additionally gets tokens/sec and an
    MFU (peak pinned via the env override on CPU).  With the program
    ledger live the numerator flips to XLA's measured cost_analysis
    FLOPs (basis "measured"); the consistency check follows the basis
    the report declares."""
    from ray_lightning_tpu.models.gpt import (
        GPT,
        GPTConfig,
        SyntheticLMDataModule,
    )
    from ray_lightning_tpu.telemetry import program_ledger

    monkeypatch.setenv("RLT_TELEMETRY_PEAK", "1e12")
    cfg = GPTConfig.tiny()
    trainer = get_trainer(
        LocalStrategy(), max_epochs=1, tmp_path=tmp_path,
        enable_checkpointing=False, limit_val_batches=0,
    )
    trainer.fit(GPT(cfg), SyntheticLMDataModule(cfg, batch_size=8,
                                                num_batches=4))
    cm = trainer.callback_metrics
    assert cm["tokens_per_sec"] > 0
    assert "mfu" in cm and 0 < cm["mfu"]
    # MFU consistency with the accounting basis the report declares:
    # measured = this fit's train/step cost_analysis FLOPs per example,
    # analytic = the shared per-token model.
    meta = (trainer.telemetry_report or {}).get("meta") or {}
    if meta.get("mfu_basis") == "measured":
        site_flops = program_ledger.ledger().site_flops_latest(
            "train/step"
        )
        assert site_flops is not None
        flops_per_example = site_flops / 8  # batch_size above
    else:
        flops_per_example = model_flops_per_token(cfg) * cfg.seq_len
    expected = cm["examples_per_sec"] * flops_per_example / 1e12
    n_chips = jax.local_device_count()
    assert cm["mfu"] == pytest.approx(expected / n_chips, rel=1e-6)


def test_off_tier_records_nothing_and_overhead_smoke(tmp_path):
    """telemetry="off" leaves callback_metrics clean; the default cheap
    tier's overhead is loosely bounded (not measured on the chip)."""
    def run(tier, sub):
        t0 = time.perf_counter()
        trainer = get_trainer(
            LocalStrategy(telemetry=tier), max_epochs=2,
            tmp_path=tmp_path / sub, enable_checkpointing=False,
            limit_val_batches=0,
        )
        trainer.fit(BoringModel(),
                    BoringDataModule(length=128, batch_size=16))
        return trainer, time.perf_counter() - t0

    t_off, off_wall = run("off", "off")
    t_cheap, cheap_wall = run("cheap", "cheap")
    assert "step_time_ms" not in t_off.callback_metrics
    assert t_off.telemetry_report == {}
    assert "step_time_ms" in t_cheap.callback_metrics
    # LOOSE smoke bound (CI wall clocks are noisy; compile dominates
    # both runs equally): cheap must not change the fit's cost class.
    assert cheap_wall < off_wall * 1.5 + 1.0, (
        f"cheap tier wall {cheap_wall:.2f}s vs off {off_wall:.2f}s"
    )


def test_full_tier_exports_artifacts(tmp_path):
    trainer = get_trainer(
        LocalStrategy(telemetry={"tier": "full",
                                 "export_dir": str(tmp_path / "tel")}),
        max_epochs=1, tmp_path=tmp_path, limit_val_batches=0,
    )
    trainer.fit(BoringModel(), BoringDataModule(length=32, batch_size=16))
    out = tmp_path / "tel"
    jsonl = out / "spans-rank0.jsonl"
    chrome = out / "trace-rank0.json"
    assert jsonl.exists() and chrome.exists()
    with open(jsonl) as f:
        assert validate_span_jsonl(f.readlines()) == []
    with open(chrome) as f:
        doc = json.load(f)
    assert validate_chrome_trace(doc) == []
    names = {ev["name"] for ev in doc["traceEvents"]}
    # The instrumented phases show up: compile + steady-state dispatch,
    # data waits and the checkpoint/host-transfer tail.
    assert {"compile", "dispatch", "data_wait",
            "checkpoint_write", "host_transfer"} <= names
    snap = json.loads((out / "snapshot-rank0.json").read_text())
    assert snap["tier"] == "full" and snap["spans_recorded"] > 0


def test_eval_and_predict_fill_telemetry_report(tmp_path):
    """validate()/predict() without a prior fit still produce a fleet
    report (the snapshots they ship are consumed, not dead weight)."""
    trainer = get_trainer(
        LocalStrategy(), max_epochs=1, tmp_path=tmp_path,
        enable_checkpointing=False,
    )
    module = BoringModel()
    dm = BoringDataModule(length=32, batch_size=16)
    trainer.validate(module, dm)
    assert trainer.telemetry_report.get("world_size") == 1
    assert trainer.telemetry_report["tier"] == "cheap"
    trainer.predict(module, dm)
    assert trainer.telemetry_report.get("world_size") == 1


def test_telemetry_callback_upgrades_cheap_fit(tmp_path):
    cb = TelemetryCallback(dirpath=str(tmp_path / "cbtel"))
    trainer = get_trainer(
        LocalStrategy(), max_epochs=1, tmp_path=tmp_path,
        callbacks=[cb], enable_checkpointing=False, limit_val_batches=0,
    )
    trainer.fit(BoringModel(), BoringDataModule(length=32, batch_size=16))
    # The callback is the per-fit spans opt-in on a cheap-tier run.
    assert (tmp_path / "cbtel" / "spans-rank0.jsonl").exists()
    assert cb.report.get("step_stats", {}).get("steps") == 2
    assert cb.export_paths


# ---------------------------------------------------------------------------
# _RunningMeanLogs non-finite hardening (satellite)
# ---------------------------------------------------------------------------

def test_running_mean_skips_nonfinite():
    acc = _RunningMeanLogs()
    acc.update({"loss": jnp.float32(1.0), "aux": jnp.float32(2.0)})
    acc.update({"loss": jnp.float32(float("nan")),
                "aux": jnp.float32(4.0)})
    acc.update({"loss": jnp.float32(3.0),
                "aux": jnp.float32(float("inf"))})
    out = acc.result()
    assert out["loss"] == pytest.approx(2.0)   # (1+3)/2, NaN excluded
    assert out["aux"] == pytest.approx(3.0)    # (2+4)/2, inf excluded
    assert acc.nonfinite_count == 2


def test_running_mean_all_nonfinite_is_nan_not_zero():
    acc = _RunningMeanLogs()
    acc.update({"loss": jnp.float32(float("nan"))})
    out = acc.result()
    assert math.isnan(out["loss"])
    assert acc.nonfinite_count == 1


def test_fit_surfaces_nonfinite_counter(tmp_path):
    class NaNSpikeModel(BoringModel):
        def training_step(self, params, batch, rng):
            loss, logs = super().training_step(params, batch, rng)
            # Poison a LOGGED metric on every step — training itself
            # stays healthy; only the log stream carries NaN.
            logs["spiky"] = logs["train_loss"] / 0.0 * 0.0
            return loss, logs

    trainer = get_trainer(
        LocalStrategy(), max_epochs=1, tmp_path=tmp_path,
        enable_checkpointing=False, limit_val_batches=0,
    )
    trainer.fit(NaNSpikeModel(),
                BoringDataModule(length=32, batch_size=16))
    counters = trainer.telemetry_report["counters"]
    assert counters["nonfinite_logs"]["mean"] >= 1
    assert np.isfinite(trainer.callback_metrics["train_loss"])


# ---------------------------------------------------------------------------
# ProfilerCallback hardening (satellite)
# ---------------------------------------------------------------------------

class _FakeTrainer:
    def __init__(self, root):
        self.default_root_dir = str(root)
        self.is_global_zero = True
        self.global_rank = 0
        self.global_step = 0
        self.state = None
        self.telemetry_dir = None


class _ProfilerSpy:
    def __init__(self, monkeypatch):
        self.starts = 0
        self.stops = 0
        self.active = False
        monkeypatch.setattr(jax.profiler, "start_trace", self._start)
        monkeypatch.setattr(jax.profiler, "stop_trace", self._stop)

    def _start(self, path):
        if self.active:
            raise RuntimeError("profiler already active")
        self.active = True
        self.starts += 1

    def _stop(self):
        self.active = False
        self.stops += 1


def test_profiler_overlapping_windows_merge(tmp_path, monkeypatch):
    """Regression (satellite): two overlapping schedule windows must
    produce exactly ONE start/stop pair — never a double start_trace."""
    spy = _ProfilerSpy(monkeypatch)
    cb = ProfilerCallback(schedule=[(2, 4), (4, 3)])  # [2,6) ∪ [4,7)
    assert cb._windows == [(2, 5)]  # merged to [2,7)
    trainer = _FakeTrainer(tmp_path)
    cb.setup(trainer, None, "fit")
    for step in range(12):
        trainer.global_step = step
        cb.on_train_batch_end(trainer, None, {}, step)
    assert spy.starts == 1 and spy.stops == 1
    # teardown is idempotent — the window closed already, and calling
    # twice more must not double-stop.
    cb.teardown(trainer, None, "fit")
    cb.teardown(trainer, None, "fit")
    assert spy.stops == 1


def test_profiler_two_disjoint_windows(tmp_path, monkeypatch):
    spy = _ProfilerSpy(monkeypatch)
    cb = ProfilerCallback(schedule=[(1, 2), (6, 2)])
    trainer = _FakeTrainer(tmp_path)
    cb.setup(trainer, None, "fit")
    for step in range(12):
        trainer.global_step = step
        cb.on_train_batch_end(trainer, None, {}, step)
    assert spy.starts == 2 and spy.stops == 2


def test_profiler_resume_never_restores_active(tmp_path, monkeypatch):
    spy = _ProfilerSpy(monkeypatch)
    cb = ProfilerCallback(start_step=0, num_steps=2)
    trainer = _FakeTrainer(tmp_path)
    cb.setup(trainer, None, "fit")
    trainer.global_step = 0
    cb.on_train_batch_end(trainer, None, {}, 0)
    assert cb._active
    # A resume ships the state dict to a fresh process: the restored
    # object must NOT believe a trace is live there.
    cb2 = ProfilerCallback(start_step=0, num_steps=2)
    cb2.load_state_dict(cb.state_dict())
    assert not cb2._active
    # And re-setup on the original resets capture state cleanly.
    cb.teardown(trainer, None, "fit")
    cb.setup(trainer, None, "fit")
    assert not cb._active and cb._win_i == 0
    assert spy.stops == 1


def test_profiler_mid_trace_teardown_closes_once(tmp_path, monkeypatch):
    spy = _ProfilerSpy(monkeypatch)
    cb = ProfilerCallback(start_step=0, num_steps=100)
    trainer = _FakeTrainer(tmp_path)
    cb.setup(trainer, None, "fit")
    cb.on_train_batch_end(trainer, None, {}, 0)
    assert spy.active
    cb.teardown(trainer, None, "fit")
    cb.teardown(trainer, None, "fit")
    assert spy.stops == 1 and not spy.active


def test_profiler_double_start_degrades_to_skip(tmp_path, monkeypatch):
    """An already-active outer trace (or stale resume) must skip the
    window with a warning, not crash the fit."""
    spy = _ProfilerSpy(monkeypatch)
    spy.active = True  # someone else's trace is live
    cb = ProfilerCallback(start_step=0, num_steps=2)
    trainer = _FakeTrainer(tmp_path)
    cb.setup(trainer, None, "fit")
    with pytest.warns(UserWarning, match="start_trace skipped"):
        cb.on_train_batch_end(trainer, None, {}, 0)
    assert not cb._active and spy.starts == 0


def test_profiler_schedule_validation():
    with pytest.raises(ValueError):
        ProfilerCallback(schedule=[])
    with pytest.raises(ValueError):
        ProfilerCallback(schedule=[(2, 0)])
    with pytest.raises(ValueError):
        ProfilerCallback(num_steps=0)


# ---------------------------------------------------------------------------
# Multi-worker aggregation (reuses the test_multiworker harness)
# ---------------------------------------------------------------------------

@pytest.mark.remote
@pytest.mark.multiworker
def test_multiworker_telemetry_aggregation(tmp_path):
    """Acceptance: after a multi-worker fit, trainer.telemetry_report
    merges BOTH ranks' snapshots into min/max/mean views."""
    trainer = get_trainer(
        RayStrategy(num_workers=2), max_epochs=1, tmp_path=tmp_path
    )
    trainer.fit(BoringModel(), BoringDataModule(length=64, batch_size=32))
    report = trainer.telemetry_report
    assert report["world_size"] == 2
    assert [s["rank"] for s in report["per_rank"]] == [0, 1]
    view = report["step_stats"]["step_mean_ms"]
    assert view["min"] <= view["mean"] <= view["max"]
    assert "skew_pct" in view
    assert report["counters"]["host_transfers"]["mean"] >= 1

"""Headline benchmark: flagship GPT training throughput through Trainer.fit().

Prints ONE JSON line ``{"metric", "value", "unit", "vs_baseline", ...}``.

The north-star metric (BASELINE.md) is **``Trainer.fit()`` steps/sec/chip**
— so the timed path is the real user path: ``Trainer`` + strategy + loop +
prefetch + callbacks, NOT a raw ``build_train_step`` call.  The raw-step
path is measured alongside it and reported as ``fit_vs_raw`` (the loop
overhead budget: ≥ 0.95 means the Trainer path gives away <5%).

Noise discipline (VERDICT r3 weak #8): every number is the MEDIAN of
``WINDOWS`` independent steady-state timing windows, and the JSON carries
``spread_pct`` (full min→max range of the windows, % of the median) so a
±2% run-to-run wobble can't be misread as a regression.

The reference (`sxjscience/ray_lightning`) publishes no performance
numbers (BASELINE.md: ``"published": {}``), so ``vs_baseline`` is the
ratio against this framework's own first recorded number for the same
config family (66,010 tokens/s/chip: one chip, 2026-07-29, through a
plug-in since removed), making round-over-round progress visible.

Config: GPT-2-small (124M params), bf16 activations, seq 1024, per-chip
batch 16, Pallas flash attention (fwd + fused bwd kernel), rematerialized
blocks, fused vocab-chunked cross-entropy (no (B,S,V) logits tensor),
full optimizer step (adamw + global-norm clip, donated buffers).

MFU is reported in BOTH conventions (VERDICT r3 weak #5c):
* ``mfu`` — standard 6N+full-attention accounting (the industry-default
  convention; comparable with published numbers and with rounds 1-3);
* ``mfu_executed`` — same accounting but the attention term halved, since
  the causal kernels never compute the masked upper triangle (FLOPs the
  hardware actually ran).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.core.callbacks import Callback
from ray_lightning_tpu.core.module import TrainState
from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models.gpt import GPT, GPTConfig, SyntheticLMDataModule
from ray_lightning_tpu.parallel.step_fns import build_train_step
from ray_lightning_tpu.parallel.strategies import LocalStrategy
# The analytic-FLOPs/peak accounting lives in the telemetry subsystem
# now (telemetry/step_stats.py) — bench and the live fit loop must agree
# on the MFU arithmetic by construction, not by copy.
from ray_lightning_tpu.telemetry import (
    model_flops_per_token,
    peak_flops_per_chip,
)

WARMUP_STEPS = 3
WINDOW_STEPS = 8          # steps per timing window
WINDOWS = 3               # median-of-k windows (k >= 3)
MEGASTEP_K = 8            # the host_overhead block's megastep A/B arm
# First recorded number for this config family (one chip, 2026-07-29,
# round 1: raw-step path, B=8, XLA-recompute attention backward).
R1_TOKENS_PER_SEC = 66010.1


def _median_spread(vals):
    vals = sorted(vals)
    med = vals[len(vals) // 2]
    spread_pct = 100.0 * (vals[-1] - vals[0]) / med if med else 0.0
    return med, spread_pct


class _StepTimer(Callback):
    """Times WINDOWS consecutive steady-state windows inside the fit loop.

    Sync discipline: device->host transfer of the loss — a host copy
    cannot return before the step that produced it has finished.

    Megastep-aware: the hook fires once per stride there, so marks are
    taken at threshold CROSSINGS (step may jump past the exact multiple)
    and each mark records the step count — window throughput divides by
    the steps a window actually covered, not a nominal constant.
    """

    def __init__(self):
        self.marks = []  # [(perf_counter, micro_step)]

    def on_train_batch_end(self, trainer, module, logs, batch_idx):
        step = trainer.micro_step if hasattr(trainer, "micro_step") else (
            trainer.global_step)
        threshold = WARMUP_STEPS + len(self.marks) * WINDOW_STEPS
        if step >= threshold and len(self.marks) <= WINDOWS:
            float(jax.device_get(logs["train_loss"]))
            self.marks.append((time.perf_counter(), step))

    def window_times(self):
        """Per-window (seconds, steps) pairs."""
        return [
            (b[0] - a[0], b[1] - a[1])
            for a, b in zip(self.marks, self.marks[1:])
        ]


def _bench_raw_step(module: GPT, cfg: GPTConfig, batch_size: int):
    """Median tokens/s through a bare build_train_step call (no Trainer)."""
    params = module.init_params(jax.random.PRNGKey(0))
    tx = module.configure_optimizers()
    state = TrainState.create(params, tx)
    step = build_train_step(module, tx, mesh=None)
    rng = jax.random.PRNGKey(0)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch_size, cfg.seq_len + 1)
    ).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens)}
    for _ in range(WARMUP_STEPS):
        state, logs = step(state, batch, rng)
    float(jax.device_get(logs["loss"]))
    windows = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(WINDOW_STEPS):
            state, logs = step(state, batch, rng)
        loss = float(jax.device_get(logs["loss"]))
        windows.append(
            WINDOW_STEPS * batch_size * cfg.seq_len
            / (time.perf_counter() - t0)
        )
    assert np.isfinite(loss), f"non-finite loss {loss}"
    return _median_spread(windows)


def _bench_fit(module: GPT, cfg: GPTConfig, batch_size: int,
               megastep=None):
    """Median tokens/s through the real Trainer.fit() path.  Also
    returns the run's fleet telemetry report (the BENCH_* telemetry
    block, making the perf trajectory machine-comparable).
    ``megastep`` drives the A/B arm of the ``host_overhead`` block
    (None = the default auto resolution)."""
    timer = _StepTimer()
    total = WARMUP_STEPS + WINDOWS * WINDOW_STEPS + 1
    if isinstance(megastep, int) and megastep > 1:
        # Whole strides only: a ragged tail would fall back to the
        # per-step path and pay ITS first-use jit compile inside a
        # timed window — the A/B must measure steady-state strides.
        total = ((total + megastep - 1) // megastep) * megastep
    trainer = Trainer(
        strategy=LocalStrategy(megastep=megastep),
        max_epochs=1,
        limit_train_batches=total,
        limit_val_batches=0,
        enable_checkpointing=False,
        precision="bf16",
        log_every_n_steps=10_000,  # keep host syncs out of the hot loop
        callbacks=[timer],
    )
    dm = SyntheticLMDataModule(
        cfg, batch_size=batch_size, num_batches=total + 1,
    )
    trainer.fit(module, dm)
    times = timer.window_times()
    assert len(times) >= WINDOWS, (
        f"fit ended with {len(times)} timed windows (< {WINDOWS})"
    )
    assert np.isfinite(trainer.callback_metrics["train_loss"])
    # LocalStrategy data-parallels over every local device; the metric is
    # per-chip, so divide whole-host throughput by the device count (the
    # raw-step path is genuinely single-device, mesh=None).
    n_chips = jax.local_device_count()
    tps = [
        steps * batch_size * cfg.seq_len / dt / n_chips
        for dt, steps in times[:WINDOWS]
        if steps > 0
    ]
    med, spread = _median_spread(tps)
    monitor_events = len(trainer.monitor_report.get("events", []))
    return med, spread, trainer.telemetry_report, monitor_events, trainer


def _dispatches_per_opt_step(trainer) -> float:
    """Jit dispatches per optimizer update, from the fit's telemetry
    counters (the host-dispatch acceptance number: ~1.0 per-step,
    ~1/K under megastep)."""
    counters = trainer.telemetry_report.get("counters", {})
    dispatches = (counters.get("train_dispatches") or {}).get("mean")
    if not dispatches or not trainer.global_step:
        return None
    return round(float(dispatches) / trainer.global_step, 4)


def _bench_host_overhead(make_module, cfg, batch_size, fit_tps,
                         raw_tps, headline_trainer) -> dict:
    """The schema-gated ``host_overhead`` block: Trainer-path overhead
    (``fit_vs_raw``), dispatch accounting for the headline fit, and a
    megastep=MEGASTEP_K on/off A/B.  Best-effort per probe — a failed
    arm nulls its fields, never the headline line."""
    block = {
        "fit_vs_raw": round(fit_tps / raw_tps, 3) if raw_tps else None,
        "dispatches_per_opt_step": _dispatches_per_opt_step(
            headline_trainer
        ),
        "megastep_k": MEGASTEP_K,
        "megastep_dispatches_per_opt_step": None,
        "megastep_tokens_per_sec": None,
        "megastep_speedup": None,
    }
    try:
        mega_tps, _, _, _, mega_trainer = _bench_fit(
            make_module(), cfg, batch_size, megastep=MEGASTEP_K
        )
        block["megastep_tokens_per_sec"] = round(mega_tps, 1)
        block["megastep_speedup"] = (
            round(mega_tps / fit_tps, 3) if fit_tps else None
        )
        block["megastep_dispatches_per_opt_step"] = (
            _dispatches_per_opt_step(mega_trainer)
        )
    except Exception as e:  # noqa: BLE001 - probe must not cost the line
        sys.stderr.write(f"megastep A/B skipped: {e}\n")
    return block


def _bench_opt_state_block(cfg: GPTConfig, batch_size: int,
                           fit_tps) -> dict:
    """The schema-gated ``opt_state`` block: analytic persistent AdamW
    moment bytes under f32 vs block-scaled int8 (the >= 3.5x HBM-diet
    acceptance bar), the ACTIVE policy's bytes, a measured tiny-fit
    loss-parity probe (int8 vs f32 arm, the int8_ef grad-comm
    tolerance), and — when an explicit policy is active — the headline
    fit's tokens/s re-recorded under the arm's name (the headline
    already ran WITH the policy).  Best-effort per probe."""
    from dataclasses import replace as _replace

    from ray_lightning_tpu.models.optim import (
        opt_state_bytes,
        resolve_opt_state_dtype,
    )
    from ray_lightning_tpu.ops.optim_quant import DEFAULT_BLOCK_SIZE

    params = jax.eval_shape(
        GPT(cfg).init_params, jax.random.PRNGKey(0)
    )
    osd = resolve_opt_state_dtype(cfg.opt_state_dtype)
    block = {
        "dtype": osd or f"default(mu={cfg.mu_dtype})",
        "block_size": DEFAULT_BLOCK_SIZE,
        "bytes_f32": opt_state_bytes(params, "float32"),
        "bytes_int8": opt_state_bytes(params, "int8"),
        "bytes_active": opt_state_bytes(params, osd),
        "hbm_ratio": None,  # filled below
        "loss_rel_diff_vs_f32": None,
        "tokens_per_sec": None,
        "vs_baseline": None,
        # The sharded-update arm as configured for this invocation
        # (worker-side resolution happens against the real mesh).
        "update_sharding": os.environ.get("RLT_UPDATE_SHARDING", "auto"),
    }
    block["hbm_ratio"] = round(
        block["bytes_f32"] / max(block["bytes_int8"], 1), 3
    )
    try:
        # Parity is a numerics property, not a perf one — probe it on
        # the tiny config regardless of backend so every artifact
        # carries the number.
        def parity_fit(dtype):
            pcfg = _replace(GPTConfig.tiny(), opt_state_dtype=dtype)
            t = Trainer(
                strategy=LocalStrategy(), max_epochs=2,
                enable_checkpointing=False, log_every_n_steps=1,
            )
            t.fit(GPT(pcfg), SyntheticLMDataModule(
                pcfg, batch_size=8, num_batches=8))
            return float(t.callback_metrics["train_loss"])

        ref = parity_fit("float32")
        got = parity_fit("int8")
        block["loss_rel_diff_vs_f32"] = round(
            abs(got - ref) / max(abs(ref), 1e-12), 9
        )
    except Exception as e:  # noqa: BLE001 - probe must not cost the line
        sys.stderr.write(f"opt_state parity probe skipped: {e}\n")
    if osd is not None and fit_tps:
        # The headline fit already ran WITH the active policy (main()
        # bakes RLT_OPT_STATE_DTYPE into cfg before measuring), so it
        # IS this arm's measurement — re-fitting here would compare
        # the arm against itself.  Cross-arm speedups come from one
        # bench.py invocation per RLT_OPT_STATE_DTYPE value, read side
        # by side.
        block["tokens_per_sec"] = round(fit_tps, 1)
    return block


def _bench_residual_policy_block(cfg: GPTConfig, batch_size: int,
                                 remat_policy: str, fit_tps,
                                 on_tpu: bool) -> dict:
    """The schema-gated ``residual_policy`` block: analytic remat-saved
    residual bytes of the active arm vs the ``dots+flash`` baseline
    (models/gpt.py:residual_save_bytes — the profiler's dynamic-
    update-slice lines are the chip truth), plus the measured headline
    tokens/s when the headline actually ran rematerialized (TPU; the
    CPU fallback fits remat=False, so its tokens carry no residual
    signal).  Cross-arm speedups come from running bench.py once per
    RLT_REMAT_POLICY value."""
    from ray_lightning_tpu.models.gpt import residual_save_bytes

    baseline = "dots+flash"
    arm = residual_save_bytes(cfg, batch_size, remat_policy, "bf16")
    base = residual_save_bytes(cfg, batch_size, baseline, "bf16")
    return {
        "policy": remat_policy,
        "baseline_policy": baseline,
        "residual_bytes_per_step": arm,
        "baseline_residual_bytes_per_step": base,
        "bytes_saved_pct": round(100.0 * (1 - arm / base), 2),
        "tokens_per_sec": round(fit_tps, 1) if on_tpu else None,
        "vs_baseline": None,
        # Numerics deltas are tolerance-pinned by tests/test_gpt.py;
        # the artifact records the accounting, not a re-measurement.
        "loss_rel_diff_vs_baseline": None,
    }


def _bench_boring_fit(tier, steps: int = 80) -> float:
    """Steady-state seconds/step of a boring-model fit at one telemetry
    config — tier string or full dict (the overhead probes' arm)."""
    from ray_lightning_tpu.models.boring import (
        BoringDataModule,
        BoringModel,
    )

    timer = _StepTimer()
    trainer = Trainer(
        strategy=LocalStrategy(telemetry=tier),
        max_epochs=1,
        limit_train_batches=steps,
        limit_val_batches=0,
        enable_checkpointing=False,
        log_every_n_steps=10_000,
        callbacks=[timer],
    )
    trainer.fit(
        BoringModel(),
        BoringDataModule(length=steps * 16 + 16, batch_size=16),
    )
    times = timer.window_times()
    assert len(times) >= WINDOWS
    return _median_spread(
        [dt / steps for dt, steps in times[:WINDOWS] if steps > 0]
    )[0]


def _telemetry_overhead_pct() -> float:
    """Measured per-step cost of the default cheap telemetry tier vs a
    telemetry-off run on the boring model — the precise record of the
    <1% acceptance budget (the smoke test asserts it loosely)."""
    off = _bench_boring_fit("off")
    cheap = _bench_boring_fit("cheap")
    return 100.0 * (cheap - off) / off if off else 0.0


def _heartbeat_overhead_pct(repeats: int = 3) -> float:
    """Measured per-step cost of the live heartbeat publisher
    (telemetry/heartbeat.py) vs the same cheap-tier fit with the
    publisher disabled.  Probed at 10x the default cadence (0.5s vs
    5s) so short bench fits see many beats — an upper bound on the
    production cost, recorded so BENCH_r06+ tracks it.

    Best-of-N per arm: single boring-model fits jitter far more than
    the publisher costs (observed ±40% run-to-run on the CPU mesh),
    and min-of-runs is the standard noise-robust floor estimator.
    """
    silent = min(
        _bench_boring_fit({"tier": "cheap", "heartbeat_s": 0})
        for _ in range(repeats)
    )
    beating = min(
        _bench_boring_fit({"tier": "cheap", "heartbeat_s": 0.5})
        for _ in range(repeats)
    )
    return 100.0 * (beating - silent) / silent if silent else 0.0


def _ledger_overhead_pct(repeats: int = 3) -> float:
    """Measured per-step cost of the program-ledger dispatch wrapper
    (telemetry/program_ledger.py) vs the same cheap-tier fit with the
    ledger killed (``RLT_PROGRAM_LEDGER=0`` builds bare ``jax.jit``).
    The steady-state path is one MRU try/except per dispatch (~0.2us
    micro-benchmarked), so this records a noise-floor bound, not a
    measurable cost.  Best-of-N per arm, like the heartbeat probe."""
    def _arm(value: str) -> float:
        prev = os.environ.get("RLT_PROGRAM_LEDGER")
        os.environ["RLT_PROGRAM_LEDGER"] = value
        try:
            return min(
                _bench_boring_fit("cheap") for _ in range(repeats)
            )
        finally:
            if prev is None:
                os.environ.pop("RLT_PROGRAM_LEDGER", None)
            else:
                os.environ["RLT_PROGRAM_LEDGER"] = prev

    bare = _arm("0")
    ledgered = _arm("1")
    return 100.0 * (ledgered - bare) / bare if bare else 0.0


def _bench_programs_block(snap: dict, tel_report: dict,
                          ledger_overhead_pct) -> dict:
    """The schema-gated ``programs`` block (telemetry/schema.py::
    validate_bench_programs): the headline fit's compiled-executable
    inventory — taken right after the fit, before the probe fits
    pollute the process-global ledger — plus the measured wrapper
    overhead and the HBM/roofline accounting for the train step."""
    from ray_lightning_tpu.telemetry import program_ledger

    rows = [
        {k: v for k, v in row.items()}
        for row in snap.get("programs", [])
        if row["site"].startswith(("train/", "eval/"))
    ]
    block: dict = {
        "n_programs": len(rows),
        "compile_time_total_s": round(
            float(snap.get("compile_time_total_s", 0.0)), 3
        ),
        "recompile_events": len(snap.get("recompiles", [])),
        "ledger_overhead_pct": ledger_overhead_pct,
        "rows": rows,
        "hbm": program_ledger.hbm_report(snap),
    }
    roof = program_ledger.roofline("train/step", snap=snap)
    if roof is not None:
        block["roofline"] = roof
    basis = (tel_report.get("meta") or {}).get("mfu_basis")
    if basis:
        block["mfu_basis"] = basis
    if snap.get("dropped"):
        block["dropped"] = snap["dropped"]
    return block


def _bench_fault_block() -> dict:
    """Recovery-cost probes for the schema-gated ``fault`` block
    (docs/FAULT_TOLERANCE.md): ``drain_checkpoint_s`` (step-granular
    drain write on an inline fit), ``time_to_recover_s`` (deterministic
    injected crash → training resumed, measured end-to-end as the wall
    delta against the same fit without the crash — respawn, backoff,
    checkpoint discovery and recompile all included), and ``backoff_s``
    (the jittered delay the governor actually slept).  Every probe is
    best-effort: a None field means the probe failed, never that the
    bench lied."""
    import tempfile

    from ray_lightning_tpu.core.callbacks import Callback as _CB
    from ray_lightning_tpu.fault import drain as drain_mod
    from ray_lightning_tpu.fault.drain import PreemptedError
    from ray_lightning_tpu.models.boring import (
        BoringDataModule,
        BoringModel,
    )
    from ray_lightning_tpu.parallel.strategies import RayStrategy

    block: dict = {"drain_checkpoint_s": None, "time_to_recover_s": None,
                   "backoff_s": None, "resize_time_to_recover_s": None,
                   "resize_old_world": None, "resize_new_world": None}

    class _DrainAt(_CB):
        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            if trainer.micro_step == 5:
                drain_mod.request_drain("bench")

    try:
        with tempfile.TemporaryDirectory(prefix="rlt_bench_drain_") as d:
            trainer = Trainer(
                strategy=LocalStrategy(), max_epochs=2,
                default_root_dir=d, limit_train_batches=8,
                limit_val_batches=0, enable_checkpointing=False,
                callbacks=[_DrainAt()],
            )
            try:
                trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
            except PreemptedError as err:
                if err.drain_s is not None:
                    block["drain_checkpoint_s"] = round(err.drain_s, 4)
    except Exception as e:  # noqa: BLE001 - probe must not cost the line
        sys.stderr.write(f"drain probe skipped: {e}\n")

    def _crash_fit(inject: bool) -> tuple:
        with tempfile.TemporaryDirectory(prefix="rlt_bench_crash_") as d:
            if inject:
                os.environ["RLT_FAULT"] = "crash@step:3,rank:0"
                os.environ["RLT_FAULT_STATE"] = os.path.join(d, "chaos")
            try:
                strategy = RayStrategy(
                    num_workers=1, max_restarts=1, restart_backoff_s=0.1,
                )
                trainer = Trainer(
                    strategy=strategy, max_epochs=3, default_root_dir=d,
                    limit_train_batches=2, limit_val_batches=0,
                    enable_checkpointing=False,
                )
                t0 = time.perf_counter()
                trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
                wall = time.perf_counter() - t0
                assert trainer.global_step == 6, trainer.global_step
                return wall, strategy.recovery_events
            finally:
                os.environ.pop("RLT_FAULT", None)
                os.environ.pop("RLT_FAULT_STATE", None)

    try:
        clean_wall, _ = _crash_fit(inject=False)
        crash_wall, events = _crash_fit(inject=True)
        block["time_to_recover_s"] = round(
            max(crash_wall - clean_wall, 0.0), 3
        )
        backoff = next(
            (e for e in events if e.get("kind") == "backoff"), None
        )
        if backoff is not None:
            block["backoff_s"] = backoff.get("delay_s")
    except Exception as e:  # noqa: BLE001
        sys.stderr.write(f"recovery probe skipped: {e}\n")

    # Elastic shrink probe (docs/FAULT_TOLERANCE.md "Elastic resume"):
    # a 2-worker fit loses worker 1 at spawn (lose_worker fault), the
    # governor respawns with the survivor, and the cost of the whole
    # detour — doomed attempt, kill, resize, re-discovery, recompile —
    # is the wall delta against the same fit run at 1 worker cleanly.
    def _shrink_fit() -> tuple:
        with tempfile.TemporaryDirectory(prefix="rlt_bench_resize_") as d:
            os.environ["RLT_FAULT"] = "lose_worker@point:spawn,rank:1"
            os.environ["RLT_FAULT_STATE"] = os.path.join(d, "chaos")
            try:
                strategy = RayStrategy(
                    num_workers=2, max_restarts=1,
                    restart_backoff_s=0.05, elastic_min_workers=1,
                )
                trainer = Trainer(
                    strategy=strategy, max_epochs=3, default_root_dir=d,
                    limit_train_batches=2, limit_val_batches=0,
                    enable_checkpointing=False,
                )
                t0 = time.perf_counter()
                trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
                wall = time.perf_counter() - t0
                assert trainer.global_step == 6, trainer.global_step
                assert strategy.active_workers == 1
                return wall, strategy.recovery_events
            finally:
                os.environ.pop("RLT_FAULT", None)
                os.environ.pop("RLT_FAULT_STATE", None)

    def _clean_one_worker_fit() -> float:
        with tempfile.TemporaryDirectory(prefix="rlt_bench_resize_") as d:
            strategy = RayStrategy(num_workers=1)
            trainer = Trainer(
                strategy=strategy, max_epochs=3, default_root_dir=d,
                limit_train_batches=2, limit_val_batches=0,
                enable_checkpointing=False,
            )
            t0 = time.perf_counter()
            trainer.fit(BoringModel(), BoringDataModule(batch_size=16))
            return time.perf_counter() - t0

    try:
        clean_wall = _clean_one_worker_fit()
        shrink_wall, events = _shrink_fit()
        block["resize_time_to_recover_s"] = round(
            max(shrink_wall - clean_wall, 0.0), 3
        )
        resize = next(
            (e for e in events if e.get("kind") == "resize"), None
        )
        if resize is not None:
            block["resize_old_world"] = resize.get("old_world")
            block["resize_new_world"] = resize.get("new_world")
    except Exception as e:  # noqa: BLE001
        sys.stderr.write(f"resize probe skipped: {e}\n")
    return block


def _bench_generate(module: GPT, cfg: GPTConfig, on_tpu: bool):
    """Greedy decode throughput (new tokens/s, whole batch) through the
    KV-cache generation path — f32/bf16 weights AND the int8-storage
    tree (models/quant.py), so the weight-traffic win is recorded.
    Strictly best-effort: any failure returns None rather than costing
    the headline training line."""
    try:
        from ray_lightning_tpu.models.generate import generate
        from ray_lightning_tpu.models.quant import quantize_decode_params

        B = 8 if on_tpu else 2
        new = 128 if on_tpu else 8
        t0_len = min(32, cfg.seq_len - new - 1)
        params = module.init_params(jax.random.PRNGKey(0))
        prompt = jnp.ones((B, t0_len), jnp.int32)
        fn = jax.jit(
            lambda p, pr: generate(module, p, pr, max_new_tokens=new)
        )

        def measure(tree):
            jax.block_until_ready(fn(tree, prompt))  # compile
            tps = []
            for _ in range(WINDOWS):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(tree, prompt))
                tps.append(B * new / (time.perf_counter() - t0))
            return round(_median_spread(tps)[0], 1)

        full = measure(params)
        try:
            q8 = measure(quantize_decode_params(params, cfg))
        except Exception as e:  # noqa: BLE001 - int8 arm is optional
            sys.stderr.write(f"int8 decode bench skipped: {e}\n")
            q8 = None
        return full, q8
    except Exception as e:  # pragma: no cover - defensive
        sys.stderr.write(f"generate bench skipped: {e}\n")
        return None, None


def _kernel_paths(module: GPT, batch_size: int, on_tpu: bool) -> dict:
    """Which compute path each optional Pallas kernel takes for THIS
    bench config (the bench artifact must say what it measured): the
    model's own selection predicates — a function of backend, mesh,
    shapes and RLT_DISABLE_KERNELS; nothing is probed.  On CPU the
    kernels run under the Pallas interpreter."""
    if not on_tpu:
        return {"mode": "cpu-interpret"}
    paths = module.kernel_paths(batch_size)
    out: dict = {
        "mode": "tpu-mosaic",
        "ce_pallas": paths["cross_entropy"] != "scan",
        "ln_pallas": paths["layer_norm"] == "pallas",
        "flash_attention": paths["attention"] != "xla",
    }
    disabled = os.environ.get("RLT_DISABLE_KERNELS", "")
    if disabled:
        out["disabled_families"] = disabled
    return out


def _bench_mpmd(on_tpu: bool) -> dict:
    """The ``--mpmd`` A/B arm (schema: ``validate_bench_mpmd``): a
    2-stage mesh-of-meshes fit (in-process harness — same StageRunner
    code path the actor plane drives, minus spawn cost) vs the
    single-mesh SPMD GPipe formulation of the SAME model, plus the
    GPipe-vs-interleaved-1F1B bubble decomposition at measured per-op
    costs (docs/PERFORMANCE.md "Pipeline bubbles")."""
    from ray_lightning_tpu.models.gpt import GPTConfig as _Cfg
    from ray_lightning_tpu.mpmd.inproc import run_inproc_pipeline_fit
    from ray_lightning_tpu.mpmd.plan import _gpt_untie, gpt_mpmd_spec
    from ray_lightning_tpu.mpmd.reference import gpipe_reference_fit
    from ray_lightning_tpu.mpmd.schedule import (
        fleet_pipeline_stats,
        measured_schedule_bubble,
        pool_op_costs,
    )

    cfg = _Cfg(vocab_size=256, n_layer=4, n_head=4, d_model=64,
               seq_len=64, warmup_steps=2)
    module = GPT(cfg, attn_impl="xla")
    module.precision = "f32"
    spec = gpt_mpmd_spec(module)
    full = _gpt_untie(module.init_params(jax.random.PRNGKey(0)))
    steps, bsz, n_micro, interleave = 5, 16, 8, 2
    rng = np.random.default_rng(11)
    data = [
        {"tokens": rng.integers(
            0, cfg.vocab_size, (bsz, cfg.seq_len + 1)).astype(np.int32)}
        for _ in range(steps)
    ]
    devices = jax.devices()
    groups = [devices[0:2], devices[2:4]] if len(devices) >= 4 else None
    tokens_per_step = bsz * cfg.seq_len

    arms = {}
    for name, v in (("gpipe", 1), ("1f1b", interleave)):
        res = run_inproc_pipeline_fit(
            spec, full, spec.tx_factory, lambda s: data[s], steps,
            n_workers=2, n_micro=n_micro, schedule=name, interleave=v,
            device_groups=groups,
        )
        costs = pool_op_costs(res["op_costs"])
        loss_stats = res["step_summaries"][-1][1:]  # loss worker, warm
        wall = sum(s["wall_s"] for s in loss_stats)
        arms[name] = {
            "res": res,
            "costs": costs,
            "bubble": measured_schedule_bubble(name, 2, n_micro, v, costs),
            "tps": tokens_per_step * len(loss_stats) / max(wall, 1e-9),
        }

    # Single-mesh SPMD GPipe reference: warm the compile, then time.
    ref_devices = devices[:2]
    gpipe_reference_fit(spec, full, spec.tx_factory(),
                        lambda s: data[s], 1, 2, n_micro,
                        devices=ref_devices)
    t0 = time.perf_counter()
    ref = gpipe_reference_fit(spec, full, spec.tx_factory(),
                              lambda s: data[s], steps, 2, n_micro,
                              devices=ref_devices)
    ref_wall = time.perf_counter() - t0
    ref_tps = tokens_per_step * steps / max(ref_wall, 1e-9)

    head = arms["1f1b"]
    parity = float(np.max(np.abs(
        np.asarray(head["res"]["losses"]) - np.asarray(ref["losses"])
    )))
    fleet = fleet_pipeline_stats(head["res"]["per_stage_stats"])
    return {
        "schedule": "1f1b",
        "interleave": interleave,
        "n_stages": 2,
        "n_micro": n_micro,
        "bubble_fraction": round(head["bubble"], 4),
        "gpipe_bubble_fraction": round(arms["gpipe"]["bubble"], 4),
        "stage_occupancy": round(fleet["stage_occupancy"], 4),
        "stage_skew_ms": round(fleet["stage_skew_ms"], 3),
        "tokens_per_sec": round(head["tps"], 1),
        "single_mesh_tokens_per_sec": round(ref_tps, 1),
        "vs_single_mesh": round(head["tps"] / max(ref_tps, 1e-9), 3),
        "loss_parity_max_diff": parity,
        "op_costs_ms": {
            k: round(v * 1e3, 3) for k, v in head["costs"].items()
        },
    }


def _collectives_before_last_dot(hlo) -> "int | None":
    """HLO-structural overlap proof: count collective ops scheduled
    BEFORE the program's last matmul.  A step-end sync is data-
    dependence-ordered after every backward dot (count 0); the tapped
    backward interleaves its bucket collectives into the dot stream
    (count > 0).  On the CPU backend sharded collectives lower to
    all-to-all/all-gather; data dependence, not the scheduler, fixes
    their position, so the text order is trustworthy."""
    if not hlo:
        return None
    lines = hlo.splitlines()
    last_dot = max(
        (i for i, line in enumerate(lines) if " dot(" in line),
        default=None,
    )
    if last_dot is None:
        return None
    return sum(
        1 for line in lines[:last_dot]
        if "=" in line and ("all-to-all" in line or "all-gather" in line)
    )


def _bench_comm_overlap(on_tpu: bool) -> dict:
    """The schema-gated ``comm_overlap`` block (round 25): step-end vs
    backward-overlapped grad sync, both arms at grad_comm=int8_ef on a
    mesh over every local device.  Acceptance surface: loss parity at
    the EF tolerance, identical wire volume (bucket re-planning only
    pads), unchanged dispatches/opt-step, zero steady-state recompiles
    in both arms, and the HLO gate proving the overlapped arm's
    collectives are interleaved into the backward."""
    from ray_lightning_tpu.telemetry import program_ledger as _ledger

    cfg = GPTConfig.tiny()
    n_dev = jax.local_device_count()
    segments = 2
    steps = 6
    batch_size = max(8, n_dev)

    class _HloProbe(Callback):
        """Grab the step program's HLO MID-fit: the ledger's site
        registry holds the LedgeredFunction by weak reference, so the
        text is only reachable while the loop's step fn is alive."""

        def __init__(self):
            self.collectives = None

        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            if self.collectives is None:
                self.collectives = _collectives_before_last_dot(
                    _ledger.hlo_text("train/step")
                )

    def run(seg):
        pre = len(_ledger.snapshot().get("recompiles", []))
        probe = _HloProbe()
        module = GPT(cfg, attn_impl="auto" if on_tpu else "xla")
        module.precision = "f32"
        trainer = Trainer(
            strategy=LocalStrategy(
                mesh_axes={"data": n_dev},
                grad_comm={"mode": "int8_ef", "dcn_only": False},
                grad_overlap_segments=seg,
            ),
            max_steps=steps,
            enable_checkpointing=False,
            limit_val_batches=0,
            log_every_n_steps=10_000,
            callbacks=[probe],
        )
        dm = SyntheticLMDataModule(
            cfg, batch_size=batch_size, num_batches=steps + 1,
        )
        trainer.fit(module, dm)
        events = _ledger.snapshot().get("recompiles", [])[pre:]
        return {
            "loss": float(trainer.callback_metrics["train_loss"]),
            "bytes": float(trainer.comm_stats["grad_sync_bytes"]),
            "dispatches": _dispatches_per_opt_step(trainer),
            # variant 0 events are cross-arm first compiles of a fresh
            # LedgeredFunction; steady-state recompiles re-lower an
            # EXISTING function (variant >= 1).
            "recompiles": sum(
                1 for e in events
                if e.get("site") == "train/step"
                and e.get("variant", 0) >= 1
            ),
            "collectives": probe.collectives,
        }

    a = run(0)          # step-end sync (the zero-risk default)
    b = run(segments)   # tapped backward
    rel = abs(b["loss"] - a["loss"]) / max(abs(a["loss"]), 1e-9)
    block = {
        "segments": segments,
        "mode": "int8_ef",
        "devices": n_dev,
        "loss_rel_diff": round(rel, 6),
        "loss_step_end": round(a["loss"], 6),
        "loss_overlap": round(b["loss"], 6),
        "grad_sync_bytes_step_end": a["bytes"],
        "grad_sync_bytes_overlap": b["bytes"],
        "bytes_ratio": round(b["bytes"] / max(a["bytes"], 1e-9), 4),
        "dispatches_per_opt_step_step_end": a["dispatches"],
        "dispatches_per_opt_step_overlap": b["dispatches"],
        "recompiles_step_end": a["recompiles"],
        "recompiles_overlap": b["recompiles"],
        "collectives_before_last_dot_step_end": a["collectives"],
        "collectives_before_last_dot_overlap": b["collectives"],
        "hlo_gate": (
            None if a["collectives"] is None or b["collectives"] is None
            else a["collectives"] == 0 and b["collectives"] > 0
        ),
    }

    # Quantized-DCN-wire probe: the in-proc 2-worker pipeline (the same
    # StageRunner code path the actor plane drives) at f32 vs the
    # bf16-act/int8-grad codec — loss parity + measured byte ratio.
    try:
        from ray_lightning_tpu.mpmd.inproc import run_inproc_pipeline_fit
        from ray_lightning_tpu.mpmd.plan import _gpt_untie, gpt_mpmd_spec

        mcfg = GPTConfig(vocab_size=256, n_layer=4, n_head=4, d_model=64,
                         seq_len=64, warmup_steps=2)
        mmod = GPT(mcfg, attn_impl="xla")
        mmod.precision = "f32"
        spec = gpt_mpmd_spec(mmod)
        full = _gpt_untie(mmod.init_params(jax.random.PRNGKey(0)))
        rng = np.random.default_rng(17)
        data = [
            {"tokens": rng.integers(
                0, mcfg.vocab_size, (8, mcfg.seq_len + 1)
            ).astype(np.int32)}
            for _ in range(3)
        ]
        arms = {
            enc: run_inproc_pipeline_fit(
                spec, full, spec.tx_factory, lambda s: data[s], 3,
                n_workers=2, n_micro=4, wire_dtype=enc,
            )
            for enc in ("f32", "act:bf16,grad:int8")
        }
        ref, q = arms["f32"], arms["act:bf16,grad:int8"]
        sent = sum(x["bytes_sent"] for x in q["xfer"])
        fullw = sum(x["bytes_full_width"] for x in q["xfer"])
        block["mpmd_wire_enc"] = "act:bf16,grad:int8"
        block["mpmd_wire_ratio"] = round(fullw / max(sent, 1), 4)
        block["mpmd_loss_rel_diff"] = round(
            max(
                abs(x - y) / max(abs(x), 1e-9)
                for x, y in zip(ref["losses"], q["losses"])
            ), 6,
        )
    except Exception as e:  # noqa: BLE001 - probe must not cost the block
        sys.stderr.write(f"comm_overlap mpmd wire probe skipped: {e}\n")
        block["mpmd_wire_enc"] = None
        block["mpmd_wire_ratio"] = None
        block["mpmd_loss_rel_diff"] = None
    return block


def main() -> None:
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = GPTConfig(
            vocab_size=50304, n_layer=12, n_head=12, d_model=768,
            seq_len=1024, warmup_steps=10,
        )
        batch_size = 16
    else:
        # CPU fallback so the harness always produces a line (batch must
        # split over however many virtual devices the host exposes).
        cfg = GPTConfig.tiny()
        batch_size = max(4, 2 * jax.local_device_count())

    # On-hardware A/B surface (PERFORMANCE.md prepared experiments):
    # RLT_REMAT_POLICY picks what the remat backward keeps;
    # RLT_OPT_STATE_DTYPE the optimizer-state storage precision
    # (float32 | bfloat16 | int8 — models/optim.py).
    remat_policy = os.environ.get("RLT_REMAT_POLICY", "dots+flash")
    opt_state_dtype = os.environ.get("RLT_OPT_STATE_DTYPE") or None
    if opt_state_dtype is not None:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, opt_state_dtype=opt_state_dtype)

    def make_module():
        m = GPT(cfg, attn_impl="auto", remat=on_tpu,
                remat_policy=remat_policy)
        m.precision = "bf16"
        return m

    kernel_path = _kernel_paths(make_module(), batch_size, on_tpu)
    raw_tps, raw_spread = _bench_raw_step(make_module(), cfg, batch_size)
    # Headline fit pins megastep OFF so the metric stays comparable with
    # every prior round; the host_overhead block carries the fused arm.
    fit_tps, fit_spread, tel_report, monitor_events, fit_trainer = (
        _bench_fit(make_module(), cfg, batch_size, megastep="off")
    )
    # Ledger snapshot NOW: the probe fits below add their own programs
    # (and shape-change recompile events) to the process-global ledger;
    # the artifact's programs block must describe the headline fit.
    from ray_lightning_tpu.telemetry import program_ledger as _ledger

    headline_programs = _ledger.snapshot()
    try:
        host_overhead = _bench_host_overhead(
            make_module, cfg, batch_size, fit_tps, raw_tps, fit_trainer
        )
    except Exception as e:  # noqa: BLE001 - probe must not cost the line
        sys.stderr.write(f"host_overhead probes skipped: {e}\n")
        host_overhead = None
    gen_tps, gen_tps_int8 = _bench_generate(make_module(), cfg, on_tpu)
    try:
        overhead_pct = round(_telemetry_overhead_pct(), 3)
    except Exception as e:  # noqa: BLE001 - probe must not cost the line
        sys.stderr.write(f"telemetry overhead probe skipped: {e}\n")
        overhead_pct = None
    try:
        hb_overhead_pct = round(_heartbeat_overhead_pct(), 3)
    except Exception as e:  # noqa: BLE001 - same discipline
        sys.stderr.write(f"heartbeat overhead probe skipped: {e}\n")
        hb_overhead_pct = None
    try:
        ledger_overhead_pct = round(_ledger_overhead_pct(), 3)
    except Exception as e:  # noqa: BLE001 - same discipline
        sys.stderr.write(f"ledger overhead probe skipped: {e}\n")
        ledger_overhead_pct = None
    try:
        programs_block = _bench_programs_block(
            headline_programs, tel_report, ledger_overhead_pct
        )
    except Exception as e:  # noqa: BLE001 - same discipline
        sys.stderr.write(f"programs block skipped: {e}\n")
        programs_block = None
    try:
        fault_block = _bench_fault_block()
    except Exception as e:  # noqa: BLE001 - same discipline
        sys.stderr.write(f"fault probes skipped: {e}\n")
        fault_block = None
    mpmd_block = None
    if "--mpmd" in sys.argv[1:]:
        try:
            mpmd_block = _bench_mpmd(on_tpu)
        except Exception as e:  # noqa: BLE001 - same discipline
            sys.stderr.write(f"mpmd probes skipped: {e}\n")
    try:
        comm_overlap_block = _bench_comm_overlap(on_tpu)
    except Exception as e:  # noqa: BLE001 - same discipline
        sys.stderr.write(f"comm_overlap probes skipped: {e}\n")
        comm_overlap_block = None
    try:
        opt_state_block = _bench_opt_state_block(cfg, batch_size, fit_tps)
    except Exception as e:  # noqa: BLE001 - same discipline
        sys.stderr.write(f"opt_state probes skipped: {e}\n")
        opt_state_block = None
    try:
        residual_block = _bench_residual_policy_block(
            cfg, batch_size, remat_policy, fit_tps, on_tpu
        )
    except Exception as e:  # noqa: BLE001 - same discipline
        sys.stderr.write(f"residual_policy probes skipped: {e}\n")
        residual_block = None

    peak = peak_flops_per_chip() if on_tpu else None

    def mfu(attn):
        if peak is None:
            return None
        return round(fit_tps * model_flops_per_token(cfg, attn) / peak, 3)

    print(json.dumps({
        "metric": "gpt2_small_trainer_fit_tokens_per_sec_per_chip"
        if on_tpu else "gpt_tiny_trainer_fit_tokens_per_sec_cpu",
        "value": round(fit_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(fit_tps / R1_TOKENS_PER_SEC, 3)
        if on_tpu else 1.0,
        "steps_per_sec": round(fit_tps / (batch_size * cfg.seq_len), 3),
        "raw_step_tokens_per_sec": round(raw_tps, 1),
        "fit_vs_raw": round(fit_tps / raw_tps, 3),
        "mfu": mfu("full"),
        "mfu_executed": mfu("causal"),
        "spread_pct": round(fit_spread, 2),
        "raw_spread_pct": round(raw_spread, 2),
        "generate_tokens_per_sec": gen_tps,
        "generate_tokens_per_sec_int8": gen_tps_int8,
        "kernel_path": {
            **kernel_path,
            # The active state-precision and remat arms ride the
            # kernel-path record: an artifact must say which program it
            # measured or round comparisons silently mix arms.
            "opt_state_dtype": opt_state_dtype or "default",
            "remat_policy": remat_policy,
        },
        "remat_policy": remat_policy,
        # Machine-comparable telemetry block (schema:
        # telemetry/schema.py, gated by tools/check_telemetry_schema.py):
        # the fit run's step-time breakdown + the measured cost of the
        # always-on cheap tier.
        "telemetry": {
            # The tier the fit ACTUALLY ran at (RLT_TELEMETRY may have
            # overridden the cheap default; an artifact claiming a tier
            # that never ran would poison round comparisons).
            "tier": tel_report.get("tier") or "off",
            "overhead_pct": overhead_pct,
            # Live-plane cost + activity (docs/OBSERVABILITY.md "Live
            # monitoring"): publisher overhead measured at 10x the
            # default cadence, and the headline fit's monitor event
            # count.  NOTE: the headline fit runs LocalStrategy, whose
            # inline path has no RunMonitor — this stays 0 until the
            # bench fit moves to a remote strategy; it is recorded so
            # the schema (and any future remote bench) carries it.
            "heartbeat_overhead_pct": hb_overhead_pct,
            "monitor_events": monitor_events,
            "report": {
                "step_stats": tel_report.get("step_stats", {}),
                "counters": tel_report.get("counters", {}),
            },
        },
        # Compiled-executable observatory (schema-gated): the headline
        # fit's program inventory with compile/cost/memory accounting,
        # recompile-forensics count, and the measured dispatch-wrapper
        # overhead (docs/OBSERVABILITY.md "Program ledger").
        "programs": programs_block,
        # Recovery cost in the perf trajectory (schema-gated like the
        # telemetry block): injected-crash recovery wall time, drain-
        # checkpoint write time, observed backoff delay.
        "fault": fault_block,
        # Host-dispatch accounting (schema-gated): the Trainer-path
        # overhead budget, jit dispatches per optimizer step, and the
        # megastep on/off A/B (docs/PERFORMANCE.md "Host dispatch &
        # megastep").
        "host_overhead": host_overhead,
        # MPMD pipeline A/B (--mpmd; schema-gated): mesh-of-meshes
        # tokens/sec vs the single-mesh GPipe formulation + the
        # GPipe-vs-interleaved-1F1B bubble decomposition.
        **({"mpmd": mpmd_block} if mpmd_block is not None else {}),
        # Backward-overlapped grad sync A/B (schema-gated): loss parity,
        # wire-volume invariance, zero-recompile pins, the HLO
        # interleaving proof, and the quantized MPMD wire probe
        # (docs/PERFORMANCE.md "Comm/compute overlap").
        "comm_overlap": comm_overlap_block,
        # HBM-traffic diet (schema-gated): optimizer-state precision
        # accounting + parity, and the scan-residual-compression arm
        # (docs/PERFORMANCE.md "Optimizer-state precision & update
        # sharding").
        "opt_state": opt_state_block,
        "residual_policy": residual_block,
        "windows": WINDOWS,
        "window_steps": WINDOW_STEPS,
        "bottleneck": "attention bwd kernel + scan residual-save HBM "
        "traffic; LM-head matmul (skinny 50304x768 @ ~55% MXU)"
        if on_tpu else "cpu fallback",
    }))


if __name__ == "__main__":
    main()

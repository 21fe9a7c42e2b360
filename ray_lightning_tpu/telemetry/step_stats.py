"""Step-stats engine: where does the step time go, and how fast is it.

Per-step wall time is split into three host-observable phases:

* **data_wait** — time the loop spent blocked on the (prefetched) input
  pipeline before the batch was ready;
* **dispatch** — time inside the jitted step call.  Under async dispatch
  this is host-side tracing/enqueue cost, NOT device compute — on a
  healthy run it is small and roughly constant;
* **device step** — measured on a periodic sampling window: every
  ``sample_every``-th step the engine calls ``block_until_ready`` on the
  step's outputs, so that step's wall time includes device execution.
  Sampling keeps the async-dispatch pipeline intact between samples (a
  per-step sync would serialize host and device and show up as exactly
  the overhead this subsystem promises not to add).

On top of the split: examples/sec + tokens/sec throughput, an analytic
FLOPs MFU estimate for the GPT/ViT model families (computed live
inside any fit), recompile
counters hooked via ``jax.monitoring`` event listeners, and
``jax.local_devices()`` memory stats where the backend exposes them
(TPU yes, CPU no — best-effort by design).

The first step is recorded as **compile** (trace + XLA compile dominate
it) and excluded from steady-state aggregates; without that exclusion a
short fit's ``step_time_ms`` would be mostly compiler.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional, Tuple

__all__ = [
    "StepStats",
    "model_flops_per_token",
    "vit_flops_per_example",
    "flops_for_module",
    "peak_flops_per_chip",
    "compile_event_count",
    "compile_time_total_s",
]


# ---------------------------------------------------------------------------
# Analytic FLOPs (the published-MFU accounting)
# ---------------------------------------------------------------------------

def model_flops_per_token(cfg: Any, attn: str = "full") -> float:
    """Fwd+bwd matmul FLOPs per token for the GPT family (backward = 2x
    forward, no remat-recompute credit).

    ``attn="full"`` charges the full S² attention matrix (the standard
    published-MFU convention); ``attn="causal"`` charges the causal half
    the kernels actually execute.
    """
    d, L, s, V = cfg.d_model, cfg.n_layer, cfg.seq_len, cfg.vocab_size
    mm = 24 * L * d * d          # qkv + proj + mlp weight matmuls
    attn_term = 4 * L * s * d    # QK^T and AV, full square
    if attn == "causal":
        attn_term /= 2
    head = 2 * d * V             # tied LM head
    return 3.0 * (mm + attn_term + head)


def vit_flops_per_example(cfg: Any) -> float:
    """Fwd+bwd matmul FLOPs per image for the ViT family (patch embed +
    transformer blocks over ``n_patches + 1`` tokens + classifier head)."""
    d, L = cfg.d_model, cfg.n_layer
    s = cfg.n_patches + 1        # +1 CLS token
    mm = 24 * L * d * d * s      # block weight matmuls, whole sequence
    attn_term = 4 * L * s * s * d
    embed = 2 * cfg.patch_dim * d * cfg.n_patches
    head = 2 * d * cfg.num_classes
    return 3.0 * (mm + attn_term + embed + head)


def flops_for_module(module: Any) -> Tuple[Optional[float], Optional[int]]:
    """``(flops_per_example, tokens_per_example)`` for a known model
    family, ``(None, None)`` otherwise (MFU is then simply not reported
    — never guessed)."""
    cfg = getattr(module, "cfg", None) or getattr(module, "config", None)
    if cfg is None:
        return None, None
    kind = type(cfg).__name__
    try:
        if kind == "GPTConfig":
            return model_flops_per_token(cfg) * cfg.seq_len, cfg.seq_len
        if kind == "ViTConfig":
            return vit_flops_per_example(cfg), None
    except AttributeError:
        return None, None
    return None, None


# Peak bf16 FLOP/s per chip by device_kind substring (dense MXU peak;
# Google Cloud TPU documentation, per-generation system architecture
# pages).  A TPU that is not in the table is an error, not a default.
_PEAK_FLOPS = (
    ("v5 lite", 197e12),   # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),        # Trillium
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_chip() -> Optional[float]:
    """Dense bf16 peak of the local accelerator, or ``None`` off-TPU
    (CPU meshes: an "MFU" against an arbitrary denominator would be
    noise, so none is reported).  A TPU the table does not know raises.
    ``RLT_TELEMETRY_PEAK`` overrides (also how CPU tests pin the MFU
    math)."""
    env = os.environ.get("RLT_TELEMETRY_PEAK")
    if env:
        return float(env)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no published bf16 peak for TPU device_kind "
        f"{dev.device_kind!r}: add it to telemetry/step_stats.py "
        "_PEAK_FLOPS with its source, or set RLT_TELEMETRY_PEAK"
    )


# ---------------------------------------------------------------------------
# Recompile counter (process-wide jax.monitoring hook)
# ---------------------------------------------------------------------------

# One listener per process, installed on first use: jax.monitoring has no
# per-listener deregistration (clear_event_listeners drops EVERYTHING),
# so a listener per StepStats would accumulate across tuner-sweep fits.
_COMPILES = [0]
_COMPILE_S = [0.0]
_LISTENER = [False]
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _install_listener() -> None:
    if _LISTENER[0]:
        return
    import jax.monitoring

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            _COMPILES[0] += 1
            _COMPILE_S[0] += float(duration)

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENER[0] = True


def compile_event_count() -> int:
    """Process-lifetime XLA backend compiles observed so far.

    Installs the jax.monitoring listener on first call: every consumer
    of this counter measures DELTAS (``before = compile_event_count()``
    … ``assert compile_event_count() - before == 0``), and without the
    eager install a process that never built a :class:`StepStats` —
    a standalone serve test, a bench entry point — would pin
    "zero recompiles" against a counter that was never counting."""
    _install_listener()
    return _COMPILES[0]


def compile_time_total_s() -> float:
    """Process-lifetime seconds spent inside XLA backend compiles (the
    duration side of the same jax.monitoring event
    :func:`compile_event_count` counts).  Heartbeats and the StepStats
    report surface it so a fleet whose wall time is going to the
    compiler says so instead of reading as slow steps."""
    _install_listener()
    return _COMPILE_S[0]


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class _Agg:
    """Running min/max/sum of one per-step duration."""

    __slots__ = ("n", "total", "min", "max")

    def __init__(self):
        self.n = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0

    def add(self, v: float) -> None:
        self.n += 1
        self.total += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v

    def add_scaled(self, total: float, n: int) -> None:
        """Book ``n`` steps observed as ONE wall measurement (a megastep
        stride): the mean stays exact (``total``/``n`` ride the sums);
        min/max see the stride's per-step AVERAGE — inner-step extremes
        are invisible to the host by design."""
        self.n += n
        self.total += total
        per = total / n
        if per < self.min:
            self.min = per
        if per > self.max:
            self.max = per

    def summary_ms(self) -> Dict[str, float]:
        if not self.n:
            return {}
        return {
            "mean_ms": 1e3 * self.total / self.n,
            "min_ms": 1e3 * self.min,
            "max_ms": 1e3 * self.max,
        }


class StepStats:
    """Aggregates the per-step timing split for one fit on one rank.

    The loop owns the clocks (it has the marks anyway) and feeds each
    step via :meth:`record_step`; this class only aggregates — cheap
    float math, no device traffic, no allocation per step beyond the
    aggregator updates.
    """

    def __init__(self, sample_every: int = 32,
                 flops_per_example: Optional[float] = None,
                 tokens_per_example: Optional[int] = None,
                 peak_flops: Optional[float] = None,
                 n_chips: int = 1):
        if sample_every < 1:
            raise ValueError("sample_every must be >= 1")
        self.sample_every = sample_every
        self.flops_per_example = flops_per_example
        self.tokens_per_example = tokens_per_example
        self.measured_flops_per_example: Optional[float] = None
        self.mfu_basis = "analytic"
        self._drift_warned = False
        self.peak_flops = peak_flops
        self.n_chips = max(int(n_chips), 1)
        _install_listener()
        self._compiles_at_start = compile_event_count()
        self._compile_s_at_start = compile_time_total_s()
        self.compile_ms: Optional[float] = None
        self.steps = 0
        self.examples = 0
        self.tokens = 0
        self._step = _Agg()
        self._data_wait = _Agg()
        self._dispatch = _Agg()
        self._device = _Agg()   # sampled (block_until_ready) steps only
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    def configure_model(self, module: Any) -> None:
        """Late-bind the analytic-FLOPs model (the loop knows the module
        after telemetry is built)."""
        if self.flops_per_example is None:
            fpe, tpe = flops_for_module(module)
            self.flops_per_example = fpe
            self.tokens_per_example = tpe
        if self.peak_flops is None:
            self.peak_flops = peak_flops_per_chip()

    def configure_measured_flops(self, flops_per_example: float) -> None:
        """Adopt the program ledger's XLA-measured FLOPs as the MFU
        numerator (``mfu_basis`` flips to ``"measured"``).  The drift
        guard fires once when the measured number disagrees with the
        analytic ``model_flops_per_token`` accounting by more than 10%
        — either the hand-written model drifted from the architecture,
        or XLA is executing work the model does not charge (remat,
        padding); both mean the published MFU needs a second look."""
        if flops_per_example <= 0:
            return
        analytic = self.flops_per_example
        if analytic and not self._drift_warned:
            drift = abs(flops_per_example - analytic) / analytic
            if drift > 0.10:
                self._drift_warned = True
                import logging

                logging.getLogger(
                    "ray_lightning_tpu.telemetry"
                ).warning(
                    "MFU drift: ledger-measured FLOPs/example %.3e vs "
                    "analytic %.3e (%.1f%% apart) — MFU now reports on "
                    "the measured basis",
                    flops_per_example, analytic, 100.0 * drift,
                )
        self.measured_flops_per_example = float(flops_per_example)
        self.mfu_basis = "measured"

    # -- per-step feed ------------------------------------------------------
    def should_sample(self) -> bool:
        """True when the NEXT recorded step should block_until_ready so
        its wall time includes device compute.  Never the compile step
        (step 0), always shortly after it (step 1 gives an early honest
        number), then every ``sample_every``-th."""
        if self.steps == 0:
            return False
        return self.steps == 1 or self.steps % self.sample_every == 0

    def should_sample_stride(self, k: int) -> bool:
        """Stride-shaped :meth:`should_sample`: never the compile stride
        (the first record), always the stride right after it (the early
        honest number), then whenever the stride crosses the
        ``sample_every`` cadence — so megastep fits sample device time at
        the same step frequency the per-step loop does."""
        if self.steps == 0:
            return False
        return (
            self.steps <= k
            or (self.steps // self.sample_every)
            != ((self.steps + k) // self.sample_every)
        )

    def _record_midfit_compile(self, wall_s: float, k: int) -> None:
        """A first-use program compiled MID-fit (megastep's lazy tail /
        chaos-degraded single-step program, or the fused scan after a
        singles-only start): book the wall as compile time and excise
        the interval from the throughput window — steady-state
        ``step_time_ms``/``dispatch_ms``/tokens-per-sec must not carry a
        multi-second XLA outlier the way a hidden ordinary record would.
        """
        self.compile_ms = (self.compile_ms or 0.0) + 1e3 * wall_s
        self.steps += k
        if self._t_first is not None:
            self._t_first += wall_s

    def record_stride(self, stride_s: float, data_wait_s: float,
                      dispatch_s: float, examples: int, k: int,
                      sampled: bool = False, compiled: bool = False) -> None:
        """One megastep stride = ``k`` micro-steps in one dispatch.

        Headline attribution divides by ``k``: ``step_time_ms`` stays a
        PER-MICRO-STEP number (comparable across megastep on/off runs),
        with ``k`` steps booked per call via the scaled aggregators.
        The first stride is booked as compile, like step 0 on the
        per-step path — it is dominated by the scan trace + XLA compile
        (the k-1 fused steps riding along are noise next to it).
        ``compiled=True`` marks a mid-fit first-use compile (see
        :meth:`_record_midfit_compile`).
        """
        if self.steps == 0:
            self.compile_ms = 1e3 * stride_s
            self.steps = k
            self._t_first = time.perf_counter()
            return
        if compiled:
            self._record_midfit_compile(stride_s, k)
            return
        self.steps += k
        self.examples += int(examples)
        if self.tokens_per_example:
            self.tokens += int(examples) * self.tokens_per_example
        self._step.add_scaled(stride_s, k)
        self._data_wait.add_scaled(data_wait_s, k)
        self._dispatch.add_scaled(dispatch_s, k)
        if sampled:
            self._device.add_scaled(stride_s, k)
        self._t_last = time.perf_counter()

    def record_step(self, step_s: float, data_wait_s: float,
                    dispatch_s: float, examples: int,
                    sampled: bool = False, compiled: bool = False) -> None:
        """One loop iteration: total wall, input wait, jit-call time.

        ``sampled=True`` marks a step whose caller synced the device
        before the end mark — its wall time feeds the device-step
        aggregate.  Step 0 is booked as compile time, not steady state;
        ``compiled=True`` marks a mid-fit first-use compile (see
        :meth:`_record_midfit_compile`).
        """
        if self.steps == 0:
            self.compile_ms = 1e3 * step_s
            self.steps = 1
            self._t_first = time.perf_counter()
            return
        if compiled:
            self._record_midfit_compile(step_s, 1)
            return
        self.steps += 1
        self.examples += int(examples)
        if self.tokens_per_example:
            self.tokens += int(examples) * self.tokens_per_example
        self._step.add(step_s)
        self._data_wait.add(data_wait_s)
        self._dispatch.add(dispatch_s)
        if sampled:
            self._device.add(step_s)
        self._t_last = time.perf_counter()

    # -- derived numbers ----------------------------------------------------
    @property
    def recompiles(self) -> int:
        """XLA backend compiles since this fit started (>1 on a shape
        change or donation-layout miss — the silent 20-40s step)."""
        return compile_event_count() - self._compiles_at_start

    def throughput(self) -> Dict[str, float]:
        if self._t_first is None or self._t_last is None:
            return {}
        wall = self._t_last - self._t_first
        if wall <= 0 or not self.examples:
            return {}
        out = {"examples_per_sec": self.examples / wall}
        if self.tokens:
            out["tokens_per_sec"] = self.tokens / wall
        return out

    def mfu(self) -> Optional[float]:
        """Model-FLOPs utilisation vs the chip's dense peak, ``None``
        when either side is unknown.  The numerator is the ledger's
        XLA-measured FLOPs when :meth:`configure_measured_flops` ran
        (``mfu_basis == "measured"``), the analytic model otherwise."""
        fpe = self.measured_flops_per_example or self.flops_per_example
        if not (fpe and self.peak_flops):
            return None
        tp = self.throughput().get("examples_per_sec")
        if not tp:
            return None
        return tp * fpe / (self.peak_flops * self.n_chips)

    def memory_stats(self) -> Dict[str, float]:
        """Device memory stats where the backend exposes them."""
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 - absent on CPU, best-effort
            return {}
        if not stats:
            return {}
        out = {}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                out[key] = float(stats[key])
        return out

    def headline(self) -> Dict[str, float]:
        """The numbers a fit surfaces through ``callback_metrics``."""
        out: Dict[str, float] = {}
        if self._step.n:
            out["step_time_ms"] = 1e3 * self._step.total / self._step.n
            out["data_wait_ms"] = (
                1e3 * self._data_wait.total / self._data_wait.n
            )
            out["dispatch_ms"] = (
                1e3 * self._dispatch.total / self._dispatch.n
            )
        if self._device.n:
            out["device_step_ms"] = 1e3 * self._device.total / self._device.n
        out.update(self.throughput())
        m = self.mfu()
        if m is not None:
            out["mfu"] = m
        out["recompiles"] = float(self.recompiles)
        return out

    def summary(self) -> Dict[str, Any]:
        """Full picklable snapshot (rides the result package)."""
        out: Dict[str, Any] = {
            "steps": self.steps,
            "examples": self.examples,
            "recompiles": self.recompiles,
            "sample_every": self.sample_every,
        }
        if self.tokens:
            out["tokens"] = self.tokens
        if self.compile_ms is not None:
            out["compile_ms"] = self.compile_ms
        # XLA-reported compile seconds for THIS fit (jax.monitoring
        # durations, satellite of the program ledger): compile_ms above
        # is the step-0 wall, this is the compiler's own accounting —
        # including mid-fit lazy programs that never dominate a step.
        compile_s = compile_time_total_s() - self._compile_s_at_start
        if compile_s > 0:
            out["compile_total_s"] = round(compile_s, 6)
        for name, agg in (("step", self._step),
                          ("data_wait", self._data_wait),
                          ("dispatch", self._dispatch),
                          ("device_step", self._device)):
            for k, v in agg.summary_ms().items():
                out[f"{name}_{k}"] = v
        out.update(self.throughput())
        m = self.mfu()
        if m is not None:
            out["mfu"] = m
            out["mfu_basis"] = self.mfu_basis
        mem = self.memory_stats()
        if mem:
            out["memory"] = mem
        return out

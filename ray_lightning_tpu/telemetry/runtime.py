"""Telemetry runtime: config coercion + the per-fit Telemetry object.

One :class:`Telemetry` instance lives on each rank's loop context for
the duration of a stage.  It owns the three collectors:

* :class:`~.spans.SpanTracer` — phase spans (full tier only);
* :class:`~.step_stats.StepStats` — the step-time breakdown engine;
* **counters** — a flat name→number registry (grad-sync wire bytes,
  non-finite log counts, checkpoint writes, …) that replaces the ad-hoc
  per-subsystem stat dicts PR 1 started.

Tiers (``TelemetryConfig.tier``):

* ``off``   — nothing recorded, no listener installed, no metric keys;
* ``cheap`` — **the default**: counters (the loop's phases among them,
  ``<phase>_us``) + step stats + headline metrics in
  ``callback_metrics``.  Budget: <1% per-step overhead (asserted by
  the overhead smoke test; not measured on the chip);
* ``full``  — cheap + span recording + JSONL/Chrome export at fit end.

Config sources, strongest first: an explicit ``telemetry=`` on the
strategy/loop call → the ``RLT_TELEMETRY`` env bus (forwarded to worker
actors exactly like ``RLT_GRAD_COMM``) → the cheap default.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Optional

from .spans import SpanTracer
from .step_stats import StepStats

__all__ = ["TelemetryConfig", "Telemetry", "TIERS"]

TIERS = ("off", "cheap", "full")


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """User-facing telemetry knobs (see module docstring for tiers).

    ``sample_every`` is the ``block_until_ready`` cadence of the device
    -step sampling window; ``span_buffer`` bounds the span ring buffer;
    ``export_dir`` overrides where the full tier drops its artifacts
    (default ``<default_root_dir>/telemetry``); ``heartbeat_s`` is the
    live-heartbeat publish cadence (``telemetry/heartbeat.py`` — 0
    disables the publisher, the tier gates it like everything else).
    """

    tier: str = "cheap"
    sample_every: int = 32
    span_buffer: int = 4096
    export_dir: Optional[str] = None
    heartbeat_s: float = 5.0

    def __post_init__(self):
        if self.tier not in TIERS:
            raise ValueError(
                f"telemetry tier {self.tier!r}: expected one of {TIERS}"
            )
        if self.sample_every < 1:
            raise ValueError("sample_every must be >= 1")
        if self.span_buffer < 1:
            raise ValueError("span_buffer must be >= 1")
        if self.heartbeat_s < 0:
            raise ValueError("heartbeat_s must be >= 0 (0 = disabled)")

    @classmethod
    def coerce(cls, value: Any) -> "TelemetryConfig":
        """None | str | dict | TelemetryConfig → TelemetryConfig.

        ``None`` reads the ``RLT_TELEMETRY`` env bus (tier name), with
        ``RLT_TELEMETRY_SAMPLE`` / ``RLT_TELEMETRY_DIR`` refining it —
        the same env-forwarding contract as ``RLT_GRAD_COMM``.
        """
        if isinstance(value, cls):
            return value
        if value is None:
            value = os.environ.get("RLT_TELEMETRY") or "cheap"
        if isinstance(value, str):
            kw: dict = {"tier": value}
        elif isinstance(value, dict):
            kw = dict(value)
            kw.setdefault("tier", "cheap")
        else:
            raise TypeError(
                "telemetry must be a tier string, dict or TelemetryConfig; "
                f"got {type(value).__name__}"
            )
        env_sample = os.environ.get("RLT_TELEMETRY_SAMPLE")
        if env_sample and "sample_every" not in kw:
            kw["sample_every"] = int(env_sample)
        env_dir = os.environ.get("RLT_TELEMETRY_DIR")
        if env_dir and "export_dir" not in kw:
            kw["export_dir"] = env_dir
        env_hb = os.environ.get("RLT_HEARTBEAT_S")
        if env_hb and "heartbeat_s" not in kw:
            kw["heartbeat_s"] = float(env_hb)
        return cls(**kw)


class Telemetry:
    """Per-rank, per-stage telemetry state (see module docstring)."""

    def __init__(self, config: TelemetryConfig, global_rank: int = 0,
                 world_size: int = 1, n_chips: int = 1):
        self.config = config
        self.global_rank = global_rank
        self.world_size = world_size
        self.enabled = config.tier != "off"
        self.tracer = SpanTracer(
            enabled=config.tier == "full",
            maxlen=config.span_buffer,
            rank=global_rank,
        )
        # StepStats installs the process-wide jax.monitoring listener;
        # the off tier must not touch jax at all.
        self.step_stats: Optional[StepStats] = (
            StepStats(sample_every=config.sample_every, n_chips=n_chips)
            if self.enabled else None
        )
        self.counters: Dict[str, float] = {}
        self.meta: Dict[str, Any] = {}

    @classmethod
    def build(cls, value: Any, global_rank: int = 0, world_size: int = 1,
              n_chips: int = 1) -> "Telemetry":
        return cls(TelemetryConfig.coerce(value), global_rank,
                   world_size, n_chips=n_chips)

    # -- counters -----------------------------------------------------------
    def add_counter(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        self.counters[name] = self.counters.get(name, 0) + value

    def set_counter(self, name: str, value: float) -> None:
        if self.enabled:
            self.counters[name] = value

    def set_meta(self, name: str, value: Any) -> None:
        if self.enabled:
            self.meta[name] = value

    # -- spans (delegation keeps call sites one-attribute deep) -------------
    def span(self, name: str, **args):
        return self.tracer.span(name, **args)

    def phase(self, name: str, layer: str = "train", **args):
        """One timed phase of the loop: profiler annotation
        ``rlt:<layer>/<name>`` always, counter ``<name>_us`` unless the
        tier is ``off``, a span at tier ``full`` (``spans.py``)."""
        return self.tracer.phase(
            name, layer, self.counters if self.enabled else None, **args
        )

    # -- surfaces -----------------------------------------------------------
    def headline_metrics(self) -> Dict[str, float]:
        """The numbers a plain ``fit()`` folds into callback_metrics."""
        if not self.enabled or self.step_stats is None:
            return {}
        return self.step_stats.headline()

    def snapshot(self) -> Dict[str, Any]:
        """Picklable per-rank snapshot — rides the result package the
        way ``comm_stats`` already does; merged fleet-wide by
        :func:`~.aggregate.merge_snapshots`."""
        if not self.enabled:
            return {}
        snap: Dict[str, Any] = {
            "rank": self.global_rank,
            "tier": self.config.tier,
            "counters": dict(self.counters),
            "meta": dict(self.meta),
        }
        if self.step_stats is not None:
            snap["step_stats"] = self.step_stats.summary()
        if self.tracer.enabled:
            snap["spans_recorded"] = (
                len(self.tracer.events()) + self.tracer.dropped
            )
            snap["spans_dropped"] = self.tracer.dropped
        return snap

    # -- export (full tier / TelemetryCallback) -----------------------------
    def export_dir_for(self, default_root_dir: str) -> str:
        return self.config.export_dir or os.path.join(
            default_root_dir, "telemetry"
        )

    def export(self, out_dir: str) -> Dict[str, str]:
        """Write spans (JSONL + Chrome trace) and the snapshot for this
        rank; returns the artifact paths."""
        tag = f"rank{self.global_rank}"
        paths = {
            "spans_jsonl": os.path.join(out_dir, f"spans-{tag}.jsonl"),
            "chrome_trace": os.path.join(out_dir, f"trace-{tag}.json"),
            "snapshot": os.path.join(out_dir, f"snapshot-{tag}.json"),
        }
        self.tracer.export_jsonl(paths["spans_jsonl"])
        self.tracer.export_chrome(paths["chrome_trace"])
        os.makedirs(out_dir, exist_ok=True)
        with open(paths["snapshot"], "w") as f:
            json.dump(self.snapshot(), f, indent=2, sort_keys=True)
        return paths

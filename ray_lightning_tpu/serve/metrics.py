"""SLO stats for the serving plane: TTFT, token latency, occupancy.

jax-free so the schema gate and the exporters can use it without a
backend.  Latency families are bounded reservoirs (newest-N):
a serving process runs for days; unbounded lists would be a slow leak,
and SLO percentiles over the recent window are what an operator acts
on anyway.

Snapshot schema is pinned in ``telemetry/schema.py``
(``validate_serve_snapshot``) and held to it by
``tests/test_wire_schemas.py`` — ``rlt_top`` and the OpenMetrics
exporter parse these dicts long after this producer moves on.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from ray_lightning_tpu.telemetry.spans import PHASES

__all__ = ["ServeStats", "percentile"]

# Newest-N window per latency family.  4096 tokens at serving rates is
# minutes of traffic — enough for a stable p99, small enough to forget.
_RESERVOIR = 4096

_COUNTER_KEYS = (
    "submitted", "admitted", "completed", "rejected", "expired",
    "preempted", "tokens_out", "prefills", "decode_steps",
    # The loop's wall by phase, integer microseconds (engine.step():
    # the phases tile one iteration, so they sum to tick_us), and
    # arrival-to-admission summed over admissions.  Present from the
    # start so that a window delta reads 0, not nothing, for a phase
    # that did not occur in it.
    "ticks", "tick_us", *(f"tick_{p}_us" for p in PHASES["serve"]),
    "queue_wait_us",
    # Per decode tick: blocks that hold the active slots' visible
    # positions, and the W x M entries of the block tables.
    "decode_kv_blocks_read", "decode_kv_blocks_table",
    # Of ``decode_steps``: ticks read from a decode dispatched ahead of
    # them, and those of them whose ``tokens`` operand was the tick
    # before's output where it lay on the device.  Reply frames sent
    # (a tick's replies to one address are one frame).
    "decode_ahead", "decode_fed_on_device", "reply_frames",
    # Of ``prefills`` + ``kv_imports``: admissions whose first token
    # reached the next decode without leaving the device (fetched by the
    # host only after that decode was dispatched).
    "admit_fed_on_device",
)


def percentile(values: List[float], p: float) -> Optional[float]:
    """Nearest-rank percentile (``p`` in [0, 100]); None on empty."""
    if not values:
        return None
    vals = sorted(values)
    k = max(0, min(len(vals) - 1, int(round(p / 100.0 * len(vals))) - 1))
    if p <= 0:
        k = 0
    return vals[k]


class _Reservoir:
    __slots__ = ("_vals", "_n", "_cap")

    def __init__(self, cap: int = _RESERVOIR):
        self._vals: List[float] = []
        self._n = 0
        self._cap = cap

    def add(self, v: float) -> None:
        self._n += 1
        self._vals.append(v)
        if len(self._vals) > self._cap:
            del self._vals[: len(self._vals) - self._cap]

    def summary_ms(self) -> Optional[Dict[str, float]]:
        if not self._vals:
            return None
        return {
            "n": self._n,
            "p50_ms": round(percentile(self._vals, 50) * 1e3, 3),
            "p99_ms": round(percentile(self._vals, 99) * 1e3, 3),
            "max_ms": round(max(self._vals) * 1e3, 3),
        }

    def phase_summary_ms(self) -> Optional[Dict[str, float]]:
        """The per-phase decomposition spelling (p50/p95 — critical-path
        phases are budget lines, and a p99 over a 4096 window is mostly
        noise for the short ones)."""
        if not self._vals:
            return None
        return {
            "n": self._n,
            "p50_ms": round(percentile(self._vals, 50) * 1e3, 3),
            "p95_ms": round(percentile(self._vals, 95) * 1e3, 3),
        }


class ServeStats:
    """Thread-safe counters + latency reservoirs + gauges.

    Engine-fed: the serve loop calls the ``note_*`` hooks; any thread
    (exporter refresh, bench assertions) may snapshot concurrently.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {k: 0 for k in _COUNTER_KEYS}
        self._ttft = _Reservoir()
        self._token = _Reservoir()       # inter-token latency, steady decode
        self._queue_wait = _Reservoir()  # arrival → admission
        self._e2e = _Reservoir()         # arrival → finished
        # Critical-path phase reservoirs (queue_wait, prefill_compute,
        # handoff_transfer, decode_admission, first_token, ...) —
        # lazily created by note_phase so engines that never trace keep
        # snapshots byte-identical to pre-tracing rounds.
        self._phases: Dict[str, _Reservoir] = {}
        # Per-adapter (tenant) accounting — lazily created by
        # note_adapter, so engines without an adapter pool keep
        # snapshots byte-identical to pre-LoRA rounds.  The fairness
        # spread gauge and the rlt_top tenant pane read these.
        self._adapters: Dict[str, Dict[str, int]] = {}
        # Prefix-cache block — lazily set by set_prefix, so engines
        # without the cache keep snapshots byte-identical to pre-cache
        # rounds (same contract as phases/adapters above).
        self._prefix: Optional[Dict[str, float]] = None
        self.gauges: Dict[str, float] = {}

    def bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def bump_many(self, deltas: Dict[str, int]) -> None:
        """Several counters under one lock hold (the engine's phases of
        one tick)."""
        with self._lock:
            counters = self.counters
            for name, n in deltas.items():
                counters[name] = counters.get(name, 0) + n

    def note_admitted(self, wait_s: float,
                      since_receipt_s: Optional[float] = None) -> None:
        """``since_receipt_s`` (queue-plane requests) counts from the
        frame's receipt in the inbox, ``wait_s`` from ``submit()``: the
        counter takes the first where there is one."""
        if since_receipt_s is None:
            since_receipt_s = wait_s
        with self._lock:
            self.counters["admitted"] += 1
            self.counters["queue_wait_us"] += round(since_receipt_s * 1e6)
            self._queue_wait.add(wait_s)

    def note_first_token(self, ttft_s: float) -> None:
        with self._lock:
            self._ttft.add(ttft_s)

    def note_token_latency(self, dt_s: float, n_tokens: int = 1) -> None:
        """One decode-step wall interval, attributed to each of the
        ``n_tokens`` landed in it (they shared the step)."""
        with self._lock:
            self.counters["tokens_out"] += n_tokens
            for _ in range(n_tokens):
                self._token.add(dt_s)

    def note_completed(self, e2e_s: float) -> None:
        with self._lock:
            self.counters["completed"] += 1
            self._e2e.add(e2e_s)

    def note_spec_slot(self, drafted: int, accepted: int,
                       emitted: int) -> None:
        """One slot's accounting for one speculative verify tick.
        Spec counters exist only on engines that actually speculate
        (lazily created), so plain engines' snapshots — and their
        OpenMetrics render — stay byte-identical to pre-spec rounds."""
        if accepted > drafted:
            raise ValueError(
                f"spec accounting bug: accepted {accepted} > drafted "
                f"{drafted}"
            )
        with self._lock:
            for key, n in (("spec_drafted", drafted),
                           ("spec_accepted", accepted),
                           ("spec_emitted", emitted)):
                self.counters[key] = self.counters.get(key, 0) + n

    def note_adapter(self, name: str, tokens: int = 0,
                     completed: int = 0) -> None:
        """Per-tenant accounting for one emission/completion on a
        multi-LoRA engine (``serve/lora.py``) — the fairness surface:
        spread across these token counters is what the
        deficit-round-robin grant policy bounds."""
        with self._lock:
            entry = self._adapters.get(name)
            if entry is None:
                entry = self._adapters[name] = {
                    "tokens_out": 0, "completed": 0,
                }
            entry["tokens_out"] += tokens
            entry["completed"] += completed

    def note_phase(self, phase: str, dur_s: float) -> None:
        """One critical-path phase interval for one request (the
        tracing plane feeds these; see docs/OBSERVABILITY.md
        "Distributed tracing" for the phase definitions)."""
        with self._lock:
            res = self._phases.get(phase)
            if res is None:
                res = self._phases[phase] = _Reservoir()
            res.add(dur_s)

    def adapter_token_counts(self) -> Dict[str, int]:
        """Lifetime emitted tokens per adapter — the engine's fairness
        gauge (min/max spread) reads this each tick."""
        with self._lock:
            return {k: v["tokens_out"] for k, v in self._adapters.items()}

    def set_gauges(self, **gauges: float) -> None:
        with self._lock:
            self.gauges.update(gauges)

    def set_prefix(self, **fields: float) -> None:
        """Replace the prefix-cache block (engine-fed each gauge
        refresh from ``PrefixIndex.stats()``; schema:
        ``telemetry/schema.py`` ``prefix`` block)."""
        with self._lock:
            self._prefix = dict(fields)

    # -- consumption ---------------------------------------------------------
    def capacity_view(self) -> Dict[str, object]:
        """The cheap per-tick slice the SLO/capacity plane ingests:
        counters + gauges + a recent queue-wait p50.  ``snapshot()``
        sorts every 4096-sample reservoir — fine at human export
        cadence, too heavy for the plane's sub-second tick (which
        must stay under its 2% serve-loop overhead budget)."""
        with self._lock:
            out: Dict[str, object] = {
                "ts": time.time(),
                "counters": dict(self.counters),
                "gauges": {k: float(v) for k, v in self.gauges.items()},
            }
            recent = self._queue_wait._vals[-512:]
        p50 = percentile(recent, 50)
        out["latency"] = {} if p50 is None else {
            "queue_wait": {"n": len(recent),
                           "p50_ms": round(p50 * 1e3, 3)},
        }
        return out

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            out: Dict[str, object] = {
                "ts": time.time(),
                "counters": dict(self.counters),
                "gauges": {k: float(v) for k, v in self.gauges.items()},
            }
            latency = {}
            for name, res in (("ttft", self._ttft),
                              ("token", self._token),
                              ("queue_wait", self._queue_wait),
                              ("e2e", self._e2e)):
                s = res.summary_ms()
                if s is not None:
                    latency[name] = s
            out["latency"] = latency
            if self._phases:  # tracing engines only — see __init__
                phases = {}
                for name, res in self._phases.items():
                    s = res.phase_summary_ms()
                    if s is not None:
                        phases[name] = s
                out["phases"] = phases
            if self._adapters:  # multi-LoRA engines only — see __init__
                out["adapters"] = {
                    name: dict(entry)
                    for name, entry in self._adapters.items()
                }
            if self._prefix is not None:  # prefix-cache engines only
                out["prefix"] = dict(self._prefix)
            return out

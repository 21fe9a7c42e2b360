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

import gc
import logging
import resource
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence

from ray_lightning_tpu.telemetry.spans import PHASES

__all__ = ["LoopWatch", "ServeStats", "percentile"]

# A stalled iteration of the serve loop (``LoopWatch``;
# docs/OBSERVABILITY.md "The loop stalled").  Its host part (wall less
# ``idle``, ``decode_wait`` and ``admit_wait``) a dispatch (the decode
# and each admission: a burst of 31 admissions is 32 times the work,
# not a stall) is over STALL_HOST_US and STALL_FACTOR times its running
# median, or one of its two waits on the device (``admit_wait`` an
# admission) is over STALL_WAIT_US and STALL_FACTOR times that wait's
# running median.  STALL_STEP is how far
# one iteration moves a running median (up when above it, down when
# below: an outlier moves it no further than any other reading).
STALL_HOST_US = 100_000
STALL_WAIT_US = 1_000_000
STALL_FACTOR = 8
STALL_STEP = 1 / 16
# How many records a ``ServeStats`` keeps: the longest by ``wall_us``.
STALLS_KEPT = 8

_PHASE_KEYS = frozenset(f"tick_{p}_us" for p in PHASES["serve"])
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)   # Linux

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
    # Per decode tick, over the layers whose attention walks the block
    # table: the walk's online-softmax steps and the positions they
    # compute (``ops.paged_attention.walk_plan``).
    "decode_attn_steps", "decode_attn_positions_computed",
    # Per decode tick, a two-kind pool's: the positions its full layers
    # attend (the active slots' seq_len + 1, over those layers), and
    # those its sliding layers attend where ``rlt_ring_decode`` runs
    # them (min(seq_len, window - 1) + 1, over those layers; 0 where the
    # ring is gathered).
    "decode_full_positions", "decode_ring_positions",
    # Of ``decode_steps``: ticks read from a decode dispatched ahead of
    # them, and those of them whose ``tokens`` operand was the tick
    # before's output where it lay on the device.  Reply frames sent
    # (a tick's replies to one address are one frame).
    "decode_ahead", "decode_fed_on_device", "reply_frames",
    # Of ``prefills`` + ``kv_imports``: admissions whose first token
    # reached the next decode without leaving the device (fetched by the
    # host only after that decode was dispatched).
    "admit_fed_on_device",
    # Positions the prefill programs computed (a bucket, a suffix window
    # or a chunk each) and the prompt positions among them.
    "prefill_bucket_positions", "prefill_prompt_positions",
    # What the loop's thread was doing (``LoopWatch``; every tier but
    # ``off``), window sums like the phases: the thread on a CPU, its
    # involuntary and voluntary context switches, the process's
    # collector pauses (all, and generation 2), and the iterations that
    # stalled with their wall.
    "tick_cpu_us", "tick_invol_switches", "tick_vol_switches",
    "gc_us", "gc_gen2_us", "gc_collections",
    "ticks_stalled", "tick_stalled_us",
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


def _gc_hook(cell: List[int]):
    """A ``gc.callbacks`` entry that sums the collector's pauses into
    ``cell``: microseconds, microseconds of generation 2, collections,
    and the start of the one in progress (collections do not nest)."""
    def hook(phase: str, info: Dict[str, int]) -> None:
        now = time.perf_counter_ns()
        if phase == "start":
            cell[3] = now
        elif cell[3]:
            us = (now - cell[3]) // 1000
            cell[0] += us
            if info.get("generation") == 2:
                cell[1] += us
            cell[2] += 1
            cell[3] = 0
    return hook


def _uninstall(hook) -> None:
    try:
        gc.callbacks.remove(hook)
    except ValueError:
        pass


def stall_verdict(wall_us: int, cpu_us: int, gc_us: int,
                  invol_switches: int) -> str:
    """In a word, what held a stalled iteration: ``collector`` (the
    collector's pauses are over half its wall), ``running`` (its thread
    was on a CPU for over half of it: the loop's own Python), else
    ``preempted`` (the thread was switched out while it could run: the
    host's scheduler), else ``blocked`` (it waited, switched out of its
    own accord: a lock, the interpreter's among them, a socket, the
    device)."""
    if 2 * gc_us > wall_us:
        return "collector"
    if 2 * cpu_us > wall_us:
        return "running"
    return "preempted" if invol_switches else "blocked"


class LoopWatch:
    """What the serve loop's thread was doing, a turn at a time.

    The engine closes every turn of its loop (an iteration with the
    ``between`` before it, or an idle sleep) through :meth:`turn`, on
    the clock read that opens the next.  That adds to the turn's
    counters the thread's CPU time and context switches
    (``time.thread_time_ns``, ``getrusage(RUSAGE_THREAD)``) and the
    process's collector pauses (one ``gc.callbacks`` hook, installed
    here and removed by :meth:`close` or with the object), and returns
    the turn's record when it stalled (the module's constants say
    when).  A turn in which the process compiled a program is never a
    stall and moves no median: the program ledger names it already.
    """

    def __init__(self):
        self._gc: List[int] = [0, 0, 0, 0]
        hook = _gc_hook(self._gc)
        gc.callbacks.append(hook)
        self.close = weakref.finalize(self, _uninstall, hook)
        # Running medians of the host part a dispatch, ``decode_wait``
        # and ``admit_wait`` an admission, microseconds (None: no
        # reading).
        self._med: List[Optional[float]] = [None, None, None]
        self._nv = self._niv = 0
        self.rebase()

    def rebase(self) -> None:
        """Start from this thread's readings as they are now (a turn
        that does not follow another on the same thread)."""
        self._cpu_ns = time.thread_time_ns()
        if _RUSAGE_THREAD is not None:
            ru = resource.getrusage(_RUSAGE_THREAD)
            self._nv, self._niv = ru.ru_nvcsw, ru.ru_nivcsw
        self._gc_seen = tuple(self._gc[:3])
        self._t_ns = time.time_ns()
        self._prev: Optional[tuple] = None      # the turn before

    def _over(self, i: int, x: float, floor: int) -> bool:
        """Whether ``x`` is over ``floor`` and STALL_FACTOR times its
        running median; the reading then moves the median."""
        med = self._med[i]
        if med is None:
            self._med[i] = max(x, 1.0)
            return False
        self._med[i] = max(
            med * (1 + STALL_STEP if x > med else 1 - STALL_STEP), 1.0)
        return x > floor and x > STALL_FACTOR * med

    def turn(self, tick: Dict[str, int], wall_us: int, compiled: bool,
             slots: int, buckets: Sequence[int], ahead: bool,
             fed: bool) -> Optional[Dict[str, Any]]:
        """Close a turn whose phases are in ``tick``: add what the
        thread did to ``tick`` and return the turn's record if it
        stalled.  ``slots``, ``buckets`` (one an admission), ``ahead``
        and ``fed`` are the engine's facts of the turn, kept for the
        record."""
        cpu_ns = time.thread_time_ns()
        nv, niv = self._nv, self._niv
        if _RUSAGE_THREAD is not None:
            ru = resource.getrusage(_RUSAGE_THREAD)
            nv, niv = ru.ru_nvcsw, ru.ru_nivcsw
        gc_now = tuple(self._gc[:3])
        t_ns = time.time_ns()
        cpu_us = (cpu_ns - self._cpu_ns) // 1000
        gc_us = gc_now[0] - self._gc_seen[0]
        invol, vol = niv - self._niv, nv - self._nv
        now = (self._t_ns, wall_us, tick, cpu_us, gc_us, invol, vol,
               slots, buckets, ahead, fed)
        tick["tick_cpu_us"] = cpu_us
        tick["tick_invol_switches"] = invol
        tick["tick_vol_switches"] = vol
        if gc_now[2] != self._gc_seen[2]:
            tick["gc_us"] = gc_us
            tick["gc_gen2_us"] = gc_now[1] - self._gc_seen[1]
            tick["gc_collections"] = gc_now[2] - self._gc_seen[2]
        self._cpu_ns, self._nv, self._niv = cpu_ns, nv, niv
        self._gc_seen, self._t_ns = gc_now, t_ns
        prev, self._prev = self._prev, now
        if compiled:
            return None
        get = tick.get
        decode_wait = get("tick_decode_wait_us", 0)
        admit_wait = get("tick_admit_wait_us", 0)
        host = wall_us - get("tick_idle_us", 0) - decode_wait - admit_wait
        stalled = self._over(0, host / (1 + len(buckets)), STALL_HOST_US)
        if decode_wait:
            stalled |= self._over(1, decode_wait, STALL_WAIT_US)
        if admit_wait:
            stalled |= self._over(
                2, admit_wait / max(len(buckets), 1), STALL_WAIT_US)
        if not stalled:
            return None
        tick["ticks_stalled"] = 1
        tick["tick_stalled_us"] = wall_us
        record = _stall_fields(now)
        phases = record["phases"]
        record["phase"] = max(phases, key=phases.get)[5:-3]
        record["verdict"] = stall_verdict(wall_us, cpu_us, gc_us, invol)
        if prev is not None:
            record["before"] = _stall_fields(prev)
        return record


def _stall_fields(turn: tuple) -> Dict[str, Any]:
    (t_ns, wall_us, tick, cpu_us, gc_us, invol, vol, slots, buckets,
     ahead, fed) = turn
    return {
        "t_ns": t_ns, "wall_us": wall_us,
        "phases": {k: v for k, v in tick.items() if k in _PHASE_KEYS},
        "cpu_us": cpu_us, "gc_us": gc_us,
        "invol_switches": invol, "vol_switches": vol,
        "slots": slots, "buckets": list(buckets),
        "ahead": bool(ahead), "fed": bool(fed),
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
        # The STALLS_KEPT longest stalled iterations (``LoopWatch``
        # records), longest first; empty on a run without one, so its
        # snapshots stay as they were.
        self._stalls: List[Dict[str, Any]] = []
        self.gauges: Dict[str, float] = {}

    def bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def bump_many(self, deltas: Dict[str, int],
                  stall: Optional[Dict[str, Any]] = None) -> None:
        """Several counters under one lock hold (the engine's phases of
        one tick), and the tick's record where it stalled: logged once,
        kept while among the longest."""
        with self._lock:
            counters = self.counters
            for name, n in deltas.items():
                counters[name] = counters.get(name, 0) + n
            if stall is not None:
                self._stalls.append(stall)
                self._stalls.sort(key=lambda r: -r["wall_us"])
                del self._stalls[STALLS_KEPT:]
        if stall is not None:
            logging.getLogger(__name__).warning(
                "serve loop stalled: %.3f s, longest in %s (%.3f s), "
                "verdict %s (thread on a CPU %.3f s, collector %.3f s, "
                "%d involuntary and %d voluntary switches); record: %s",
                stall["wall_us"] / 1e6, stall["phase"],
                stall["phases"][f"tick_{stall['phase']}_us"] / 1e6,
                stall["verdict"], stall["cpu_us"] / 1e6,
                stall["gc_us"] / 1e6, stall["invol_switches"],
                stall["vol_switches"], stall)

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
            if self._stalls:  # a run that met a stall only
                out["stalls"] = [dict(r) for r in self._stalls]
            return out

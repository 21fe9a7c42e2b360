"""Low-overhead span tracer: monotonic-clock phase timing per rank.

The tracing half of the telemetry subsystem (SURVEY §5: the reference
ships zero observability).  A :class:`SpanTracer` records named phases
(:data:`PHASES`) into a bounded ring buffer, one tracer per rank.  Two
export formats:

* **JSONL** — one span object per line (the machine-diffable form
  ``tests/test_wire_schemas.py`` holds to ``telemetry/schema.py``);
* **Chrome ``trace_event``** — a ``{"traceEvents": [...]}`` document of
  ``ph == "X"`` complete events, loadable in Perfetto / ``chrome://tracing``
  next to the ``jax.profiler`` traces ``ProfilerCallback`` captures.

Overhead discipline: the tracer is OFF at the default cheap telemetry
tier, and records nothing then.

:meth:`SpanTracer.phase` is the one context manager there is
(``span()`` and ``start_remote()`` return it too); the engine tick and
the train loop are cut into it.  One timed phase feeds three sinks: a
``jax.profiler.TraceAnnotation`` ``rlt:<layer>/<phase>`` held open for
its duration (always; inert without a profiler session, and on the
device trace's clock with one), an integer-microsecond counter in the
dict it was given (always: the default tier's window deltas), and a
:class:`Span` in the ring (tracer enabled only).  About 1 us a phase
with nothing listening.

This module imports without jax — the schema checker imports it from
``format.sh`` and must not pay (or require) a jax import; ``phase()``
looks ``jax.profiler`` up on first use.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

__all__ = ["PHASES", "Span", "SpanTracer", "phase", "phase_label"]

#: Every phase name the program opens, by layer: the profiler annotation
#: is ``rlt:<layer>/<name>`` (``rlt:<name>`` for the layer ``""``), the
#: span in the ring is ``<name>``.  Free-form names are also accepted —
#: these exist so tests, docs and trace readers agree on spelling.
PHASES = {
    # One ServeEngine loop iteration, in order, without gap or overlap
    # (counters ``tick_<name>_us``; docs/OBSERVABILITY.md has the table).
    # ``between`` runs from the clock read that closes one iteration to
    # the one that opens the next and is counted in that next one, so
    # the loop's whole wall is tiled, not only its iterations.  A
    # stalled iteration is closed by the zero-length mark
    # ``rlt:serve/stall`` (no counter: ``serve/metrics.py``).
    "serve": (
        "inbox", "schedule", "admit_dispatch", "admit_wait", "admit_emit",
        "chunk", "grow", "decode_dispatch", "decode_wait", "emit",
        "housekeep", "idle", "between",
    ),
    # Spans of ONE request (gated on its trace context, nested in or
    # recorded beside the tick phases; ``telemetry/trace_collect.py``).
    "request": (
        "queue_wait", "prefill_compute", "decode_admission", "first_token",
        "handoff_send", "handoff_transfer", "request",
    ),
    # The train loop (counters ``<name>_us``).
    "train": (
        "data_wait", "dispatch", "megastep", "compile", "sample_sync",
        "callbacks", "log_fetch", "validation", "checkpoint_write",
        "host_transfer", "grad_sync",
    ),
    # The end-of-fit state hand-back: rank 0 serialises, the driver
    # (the same process under LocalStrategy) loads.
    "fit": ("result_package", "result_unpack"),
    # ``ledgered_jit`` when it compiles, with ``site=``.
    "": ("compile",),
}


def phase_label(name: str, layer: str = "") -> str:
    """The profiler-annotation name of a phase."""
    return f"rlt:{layer}/{name}" if layer else f"rlt:{name}"


_ANNOTATION: Any = False   # jax.profiler.TraceAnnotation, looked up once


def _annotation_cls():
    global _ANNOTATION
    if _ANNOTATION is False:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # noqa: BLE001 - no jax here: nothing to annotate
            TraceAnnotation = None
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class Span(NamedTuple):
    name: str
    ts: float        # perf_counter seconds at open
    dur: float       # seconds
    rank: int
    tid: int         # python thread id (checkpoint writer ≠ loop thread)
    depth: int       # nesting depth within its thread (0 = top level)
    args: Optional[Dict[str, Any]] = None


class _PhaseCtx:
    """One timed phase with its three sinks (module docstring).  After
    exit ``t0`` and ``dur`` hold the perf_counter reading at open and
    the seconds elapsed, so a caller that needs the number (step stats)
    does not time the same interval twice.  ``ctx`` is the span's own
    :class:`~.propagate.TraceContext` when :meth:`SpanTracer.start_remote`
    opened it (else None): the body injects it into outgoing frames and
    the receiving process parents its spans here."""

    __slots__ = ("_tracer", "_layer", "_sink", "_prefix", "name", "args",
                 "_ann", "_ts", "_depth", "t0", "dur", "ctx")

    def __init__(self, tracer: "SpanTracer", name: str, layer: str,
                 sink: Optional[Dict[str, Any]], prefix: str, args):
        self._tracer = tracer
        self._layer = layer
        self._sink = sink
        self._prefix = prefix
        self.name = name
        self.args = args
        self._ann = None
        self.dur = 0.0
        self.ctx = None

    def _open(self, t: float) -> None:
        cls = _annotation_cls()
        if cls is not None:
            label = phase_label(self.name, self._layer)
            self._ann = cls(label, **self.args) if self.args else cls(label)
            self._ann.__enter__()
        tr = self._tracer
        if tr.enabled:
            self._depth = tr._push(self.name)
            self._ts = tr._clock()
        else:
            self._depth = -1
        self.t0 = t

    def _close(self, t: float) -> None:
        self.dur = dur = t - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._sink is not None:
            key = f"{self._prefix}{self.name}_us"
            self._sink[key] = self._sink.get(key, 0) + round(dur * 1e6)
        if self._depth >= 0:
            self._tracer._pop()
            self._tracer.record(self.name, self._ts, dur,
                                depth=self._depth, args=self.args)

    def __enter__(self):
        self._open(time.perf_counter())
        return self

    def __exit__(self, *exc):
        self._close(time.perf_counter())
        return False

    def then(self, name: str, **args) -> "_PhaseCtx":
        """Close this phase and open ``name`` on ONE clock read: phases
        chained this way tile an iteration without gap or overlap.  The
        same (entered) object is returned; close the last with
        ``__exit__``."""
        t = time.perf_counter()
        self._close(t)
        self.name = name
        self.args = args or None
        self._open(t)
        return self

    def after(self, prev: "_PhaseCtx") -> "_PhaseCtx":
        """Enter this phase on the clock read that closes ``prev``:
        :meth:`then` for two phases that are not one object (another
        tracer, another sink)."""
        t = time.perf_counter()
        prev._close(t)
        self._open(t)
        return self


class SpanTracer:
    """Bounded ring buffer of :class:`Span` records for one rank.

    ``maxlen`` bounds memory (a week-long fit cannot OOM the host on
    telemetry); the *newest* spans win, and ``dropped`` counts evictions
    so exports are honest about truncation.
    """

    def __init__(self, enabled: bool = False, maxlen: int = 4096,
                 rank: int = 0, clock=None):
        if maxlen < 1:
            raise ValueError("maxlen must be >= 1")
        self.enabled = enabled
        self.rank = rank
        self.maxlen = maxlen
        # Default: monotonic perf_counter (per-process phase timing).
        # The DISTRIBUTED tracers pass time.time — cross-process stitch
        # needs one shared epoch, and a perf_counter origin is
        # process-private.
        self._clock = clock or time.perf_counter
        self._buf: collections.deque = collections.deque(maxlen=maxlen)
        self._recorded = 0
        self._local = threading.local()
        # Name of the deepest currently-open span (last writer wins
        # across threads).  Exists so the heartbeat publisher — a
        # DIFFERENT thread, which cannot see the thread-local stack —
        # can report what phase the loop is inside right now.
        self.open_span: Optional[str] = None

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, name: str) -> int:
        """Open ``name`` on this thread's stack; returns its depth."""
        stack = self._stack()
        stack.append(name)
        self.open_span = name
        return len(stack) - 1

    def _pop(self) -> None:
        stack = self._stack()
        stack.pop()
        self.open_span = stack[-1] if stack else None

    # -- recording ----------------------------------------------------------
    def span(self, name: str, **args) -> "_PhaseCtx":
        """:meth:`phase` with no layer and no counter."""
        return self.phase(name, **args)

    def phase(self, name: str, layer: str = "",
              sink: Optional[Dict[str, Any]] = None, prefix: str = "",
              **args) -> _PhaseCtx:
        """Context manager timing one phase into its three sinks: the
        profiler annotation ``rlt:<layer>/<name>`` (``args`` as its
        stats), ``sink["<prefix><name>_us"]`` (integer microseconds,
        when a sink is given) and, when the tracer is enabled, a
        :class:`Span` ``<name>`` in the ring."""
        return _PhaseCtx(self, name, layer, sink, prefix, args or None)

    def record(self, name: str, ts: float, dur: float, depth: int = 0,
               args: Optional[Dict[str, Any]] = None) -> None:
        """Append an already-measured span (the loop measures data-wait
        and dispatch anyway for step stats; re-timing them would skew)."""
        if not self.enabled:
            return
        self._buf.append(
            Span(name, ts, dur, self.rank,
                 threading.get_ident() & 0x7FFFFFFF, depth, args)
        )
        self._recorded += 1

    def start_remote(self, ctx, name: str, **args):
        """Context manager for a span CONTINUING a remote trace: the
        span parents to ``ctx`` (a :class:`~.propagate.TraceContext`
        from another process's wire frame) and carries its own fresh
        span id, exposed as ``.ctx`` on the returned manager so the
        body can propagate further downstream.  With the tracer
        disabled or ``ctx`` None no span is recorded and ``.ctx`` is
        None (the annotation ``rlt:request/<name>`` there is always)."""
        tracer, child = _DETACHED, None
        if self.enabled and ctx is not None:
            from ray_lightning_tpu.telemetry.propagate import (
                child_context, trace_args,
            )

            tracer, child = self, child_context(ctx)
            args = trace_args(child, **args)
        ph = tracer.phase(name, "request", **args)
        ph.ctx = child
        return ph

    def instant(self, name: str, **args) -> None:
        """Zero-duration metadata marker (e.g. the grad-sync plan)."""
        self.record(name, self._clock(), 0.0, args=args or None)

    # -- introspection ------------------------------------------------------
    def events(self) -> List[Span]:
        return list(self._buf)

    @property
    def dropped(self) -> int:
        return self._recorded - len(self._buf)

    def clear(self) -> None:
        self._buf.clear()
        self._recorded = 0

    # -- export -------------------------------------------------------------
    def _span_dict(self, s: Span) -> Dict[str, Any]:
        d = {
            "name": s.name,
            "ts": s.ts,
            "dur": s.dur,
            "rank": s.rank,
            "tid": s.tid,
            "depth": s.depth,
        }
        if s.args:
            d["args"] = s.args
        return d

    def export_jsonl(self, path: str) -> int:
        """One span per line; returns the number of spans written."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        spans = self.events()
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps(self._span_dict(s)) + "\n")
        return len(spans)

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome ``trace_event`` document (``ph=="X"`` complete events,
        microsecond timestamps, pid = rank so a fleet's traces merge into
        one per-rank-lane Perfetto view)."""
        events = []
        for s in self.events():
            ev = {
                "ph": "X",
                "name": s.name,
                "ts": s.ts * 1e6,
                "dur": s.dur * 1e6,
                "pid": s.rank,
                "tid": s.tid,
            }
            if s.args:
                ev["args"] = s.args
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "ray_lightning_tpu.telemetry",
                "rank": self.rank,
                "dropped_spans": self.dropped,
            },
        }

    def export_chrome(self, path: str) -> int:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"])


_DETACHED = SpanTracer(enabled=False, maxlen=1)


def phase(name: str, layer: str = "",
          sink: Optional[Dict[str, Any]] = None, prefix: str = "",
          **args) -> _PhaseCtx:
    """:meth:`SpanTracer.phase` for a call site that has no tracer of
    its own (``ledgered_jit``): annotation and counter, never a span."""
    return _DETACHED.phase(name, layer, sink, prefix, **args)

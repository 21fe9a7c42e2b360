"""Stitch per-process span exports into ONE request-scoped trace.

The distributed tracers (serve engine/router/prefill worker, MPMD
stage runners) each export wall-clock span JSONL named
``trace-<component>.jsonl`` into a shared telemetry dir; every span's
``args`` carries its ``trace_id``/``span_id``/``parent_span_id``
(:mod:`.propagate`).  This module is the consumer side:

* :func:`load_trace_dir` — all component exports under a dir;
* :func:`stitch_chrome` — ONE Perfetto-loadable Chrome ``trace_event``
  document: one pid lane per component, ``ph=="X"`` slices, and
  cross-process **flow arrows** (``ph=="s"``/``"f"`` pairs) wherever a
  span's parent lives in a different component's export;
* :func:`request_traces` / :func:`coverage` /
  :func:`phase_percentiles` / :func:`critical_path` — the per-request
  critical-path decomposition: group spans by ``trace_id``, check each
  completed request for a complete ``queue_wait → … → first_token``
  phase chain (topology-aware: ``placement`` is required only when a
  router traced, ``handoff_transfer`` implies ``decode_admission``),
  and summarize each phase's p50/p95 across the corpus;
* :func:`mpmd_step_report` — per-step per-worker compute vs
  blocked-recv decomposition of MPMD traces.

jax-free, stdlib-only — the schema gate and ``tools/trace_stitch.py``
both import it.
"""

from __future__ import annotations

import collections
import glob
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "SERVE_PHASE_ORDER",
    "load_trace_file",
    "load_trace_dir",
    "stitch_chrome",
    "request_traces",
    "chain_for",
    "chain_complete",
    "coverage",
    "phase_percentiles",
    "critical_path",
    "slowest_requests",
    "mpmd_step_report",
    "format_report",
]

#: The serve critical path, in causal order.  A given request carries
#: the subset its topology produces: a monolith engine has no
#: placement/handoff legs, a disaggregated request has all of them.
SERVE_PHASE_ORDER = (
    "queue_wait",
    "placement",
    "prefill_compute",
    "handoff_transfer",
    "decode_admission",
    "first_token",
)

_MPMD_STEP_NAMES = ("mpmd_step", "mpmd_stage_step")


def load_trace_file(path: str) -> List[Dict[str, Any]]:
    """Spans from one JSONL export, annotated with their source name
    (``_src`` — the stitcher's pid lane key; stripped before schema
    validation)."""
    src = os.path.basename(path)
    if src.startswith("trace-"):
        src = src[len("trace-"):]
    if src.endswith(".jsonl"):
        src = src[: -len(".jsonl")]
    spans = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                span = json.loads(line)
            except ValueError:
                continue  # a torn final line must not fail the stitch
            if isinstance(span, dict):
                span["_src"] = src
                spans.append(span)
    return spans


def load_trace_dir(trace_dir: str) -> List[Dict[str, Any]]:
    """Every component export under ``trace_dir`` (the distributed
    tracers' ``trace-*.jsonl`` family — per-fit ``spans-rank*.jsonl``
    exports are perf_counter-clocked and deliberately excluded: they
    share no epoch with the wall-clock distributed spans)."""
    spans: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(trace_dir,
                                              "trace-*.jsonl"))):
        spans.extend(load_trace_file(path))
    return spans


def _targs(span: Dict[str, Any]) -> Dict[str, Any]:
    args = span.get("args")
    return args if isinstance(args, dict) else {}


def _trace_id(span: Dict[str, Any]) -> Optional[str]:
    tid = _targs(span).get("trace_id")
    return tid if isinstance(tid, str) else None


# ---------------------------------------------------------------------------
# Perfetto stitch
# ---------------------------------------------------------------------------

def stitch_chrome(spans: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """One Chrome ``trace_event`` document from many components' spans.

    Each source gets its own pid lane (named via ``M`` metadata
    events); cross-process parent→child links become flow arrows
    (``s`` at the parent slice, ``f`` binding to the child's enclosing
    slice) — the Perfetto view reads client→router→prefill→replica as
    one connected timeline."""
    sources = sorted({s.get("_src", "?") for s in spans})
    pid_of = {src: i + 1 for i, src in enumerate(sources)}
    events: List[Dict[str, Any]] = []
    for src, pid in pid_of.items():
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": src},
        })
    by_span_id: Dict[str, Dict[str, Any]] = {}
    for span in spans:
        sid = _targs(span).get("span_id")
        if isinstance(sid, str) and sid not in by_span_id:
            by_span_id[sid] = span
    flow_id = 0
    for span in spans:
        pid = pid_of.get(span.get("_src", "?"), 0)
        ev = {
            "ph": "X",
            "name": span.get("name", "?"),
            "ts": float(span.get("ts", 0.0)) * 1e6,
            "dur": max(0.0, float(span.get("dur", 0.0))) * 1e6,
            "pid": pid,
            "tid": int(span.get("tid", 0)),
        }
        args = _targs(span)
        if args:
            ev["args"] = {k: v for k, v in args.items()}
        events.append(ev)
        parent_id = args.get("parent_span_id")
        parent = by_span_id.get(parent_id) if parent_id else None
        if parent is not None and parent.get("_src") != span.get("_src"):
            flow_id += 1
            p_pid = pid_of.get(parent.get("_src", "?"), 0)
            p_ts = float(parent.get("ts", 0.0)) * 1e6
            p_dur = max(0.0, float(parent.get("dur", 0.0))) * 1e6
            # 's' must sit INSIDE the parent slice; 'f' binds to the
            # child's enclosing slice at its start.
            events.append({
                "ph": "s", "id": flow_id, "name": "trace",
                "cat": "trace", "pid": p_pid,
                "tid": int(parent.get("tid", 0)),
                "ts": min(p_ts + p_dur, max(p_ts, ev["ts"] - 1.0)),
            })
            events.append({
                "ph": "f", "id": flow_id, "name": "trace",
                "cat": "trace", "bp": "e", "pid": pid,
                "tid": ev["tid"], "ts": ev["ts"] + 0.5,
            })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "ray_lightning_tpu.telemetry.trace_collect",
            "sources": sources,
        },
    }


# ---------------------------------------------------------------------------
# Serve critical path
# ---------------------------------------------------------------------------

def request_traces(
    spans: Sequence[Dict[str, Any]]
) -> Dict[str, List[Dict[str, Any]]]:
    """Spans grouped by trace_id, serve-request traces only (MPMD step
    traces are excluded — see :func:`mpmd_step_report`)."""
    groups: Dict[str, List[Dict[str, Any]]] = collections.defaultdict(list)
    mpmd_ids = {
        _trace_id(s) for s in spans if s.get("name") in _MPMD_STEP_NAMES
    }
    for span in spans:
        tid = _trace_id(span)
        if tid is not None and tid not in mpmd_ids:
            groups[tid].append(span)
    return dict(groups)


def chain_for(trace_spans: Sequence[Dict[str, Any]]
              ) -> List[Tuple[str, float, float]]:
    """The trace's phase chain as ``(phase, ts, dur)``, causal order.
    Re-emissions (preemption replays, failover re-placements) repeat a
    phase; the FIRST occurrence by timestamp represents the phase in
    the chain."""
    first: Dict[str, Tuple[float, float]] = {}
    for span in trace_spans:
        name = span.get("name")
        if name not in SERVE_PHASE_ORDER:
            continue
        ts = float(span.get("ts", 0.0))
        if name not in first or ts < first[name][0]:
            first[name] = (ts, float(span.get("dur", 0.0)))
    return [(p, *first[p]) for p in SERVE_PHASE_ORDER if p in first]


def chain_complete(trace_spans: Sequence[Dict[str, Any]],
                   require_placement: bool = False) -> bool:
    """True when the trace carries a complete critical path for its
    topology: ``queue_wait`` and ``first_token`` always; a compute
    source (``prefill_compute`` or ``decode_admission``); a
    ``handoff_transfer`` leg implies the import (``decode_admission``)
    landed; and ``placement`` when the corpus shows a tracing router."""
    present = {p for p, _, _ in chain_for(trace_spans)}
    if not {"queue_wait", "first_token"} <= present:
        return False
    if not present & {"prefill_compute", "decode_admission"}:
        return False
    if "handoff_transfer" in present and "decode_admission" not in present:
        return False
    if require_placement and "placement" not in present:
        return False
    return True


def _completed(trace_spans: Sequence[Dict[str, Any]]) -> bool:
    return any(
        s.get("name") == "request"
        and _targs(s).get("status") in ("finished", "completed")
        for s in trace_spans
    )


def coverage(spans: Sequence[Dict[str, Any]]
             ) -> Tuple[int, int, float]:
    """``(complete, completed_total, fraction)`` over COMPLETED
    requests — the stitch-coverage number.  Expired/
    rejected requests legitimately have truncated chains and are not
    counted against coverage."""
    groups = request_traces(spans)
    routed = any(
        s.get("name") == "placement"
        for g in groups.values() for s in g
    )
    total = complete = 0
    for trace_spans in groups.values():
        if not _completed(trace_spans):
            continue
        total += 1
        if chain_complete(trace_spans, require_placement=routed):
            complete += 1
    return complete, total, (complete / total if total else 0.0)


def phase_percentiles(
    spans: Sequence[Dict[str, Any]]
) -> Dict[str, Dict[str, float]]:
    """Corpus-wide per-phase latency summary (p50/p95 ms) — the same
    spelling ``ServeStats`` exports live."""
    from ray_lightning_tpu.serve.metrics import percentile

    durs: Dict[str, List[float]] = collections.defaultdict(list)
    for trace_spans in request_traces(spans).values():
        for phase, _, dur in chain_for(trace_spans):
            durs[phase].append(dur)
    out = {}
    for phase, vals in durs.items():
        out[phase] = {
            "n": len(vals),
            "p50_ms": round(percentile(vals, 50) * 1e3, 3),
            "p95_ms": round(percentile(vals, 95) * 1e3, 3),
        }
    return out


def critical_path(trace_spans: Sequence[Dict[str, Any]]
                  ) -> Dict[str, Any]:
    """One request's decomposition: phase durations in causal order,
    the unattributed remainder against the root span, and any failover
    hops."""
    chain = chain_for(trace_spans)
    root = next(
        (s for s in trace_spans if s.get("name") == "request"), None
    )
    e2e = float(root["dur"]) if root is not None else (
        max((float(s.get("ts", 0)) + float(s.get("dur", 0))
             for s in trace_spans), default=0.0)
        - min((float(s.get("ts", 0)) for s in trace_spans), default=0.0)
    )
    attributed = sum(d for _, _, d in chain)
    failovers = [
        _targs(s) for s in trace_spans if s.get("name") == "failover"
    ]
    return {
        "trace_id": _trace_id(trace_spans[0]) if trace_spans else None,
        "e2e_s": e2e,
        "phases": [(p, d) for p, _, d in chain],
        "unattributed_s": max(0.0, e2e - attributed),
        "failovers": failovers,
        "status": (_targs(root).get("status")
                   if root is not None else None),
    }


def slowest_requests(spans: Sequence[Dict[str, Any]],
                     k: int = 5) -> List[Dict[str, Any]]:
    """Critical paths of the K slowest completed requests by e2e."""
    paths = [
        critical_path(g) for g in request_traces(spans).values()
        if _completed(g)
    ]
    return sorted(paths, key=lambda p: -p["e2e_s"])[:k]


# ---------------------------------------------------------------------------
# MPMD step decomposition
# ---------------------------------------------------------------------------

def mpmd_step_report(spans: Sequence[Dict[str, Any]]
                     ) -> List[Dict[str, Any]]:
    """Per-step per-worker compute vs blocked-recv from MPMD stage
    traces: compute = FWD/BWD/UPDATE span time, blocked = the measured
    mailbox wait inside RECV spans (the bubble signal, now stitched
    across workers under one step trace_id)."""
    steps: Dict[str, Dict[str, Any]] = {}
    for span in spans:
        tid = _trace_id(span)
        if tid is None:
            continue
        args = _targs(span)
        name = span.get("name", "")
        if name in _MPMD_STEP_NAMES:
            entry = steps.setdefault(
                tid, {"trace_id": tid, "step": args.get("step"),
                      "workers": {}},
            )
            entry["step"] = args.get("step")
        elif name in ("fwd", "bwd", "update", "recv_act", "recv_grad",
                      "send_act", "send_grad"):
            entry = steps.setdefault(
                tid, {"trace_id": tid, "step": args.get("step"),
                      "workers": {}},
            )
            w = entry["workers"].setdefault(
                str(args.get("worker", "?")),
                {"compute_s": 0.0, "blocked_s": 0.0, "send_s": 0.0},
            )
            dur = float(span.get("dur", 0.0))
            if name in ("fwd", "bwd", "update"):
                w["compute_s"] += dur
            elif name.startswith("send"):
                w["send_s"] += dur
            else:
                w["blocked_s"] += float(args.get("blocked_s", dur))
    out = [e for e in steps.values() if e["workers"]]
    out.sort(key=lambda e: (e["step"] is None, e["step"]))
    return out


# ---------------------------------------------------------------------------
# Human-readable report
# ---------------------------------------------------------------------------

def format_report(spans: Sequence[Dict[str, Any]],
                  slowest_k: int = 5) -> str:
    """The text report ``tools/trace_stitch.py`` prints."""
    lines: List[str] = []
    complete, total, frac = coverage(spans)
    groups = request_traces(spans)
    if groups:
        lines.append(
            f"serve: {len(groups)} trace(s), {total} completed, "
            f"chain coverage {complete}/{total} ({frac:.1%})"
        )
        pct = phase_percentiles(spans)
        for phase in SERVE_PHASE_ORDER:
            if phase in pct:
                s = pct[phase]
                lines.append(
                    f"  {phase:<17} n={s['n']:<5} "
                    f"p50={s['p50_ms']:>9.3f}ms p95={s['p95_ms']:>9.3f}ms"
                )
        slow = slowest_requests(spans, slowest_k)
        if slow:
            lines.append(f"slowest {len(slow)} request(s):")
            for p in slow:
                phases = " -> ".join(
                    f"{name} {1e3 * d:.2f}ms" for name, d in p["phases"]
                )
                lines.append(
                    f"  {p['trace_id']}: e2e {1e3 * p['e2e_s']:.2f}ms"
                    f" [{phases}]"
                    + (f" +{1e3 * p['unattributed_s']:.2f}ms other"
                       if p["unattributed_s"] > 0 else "")
                    + (f"  FAILOVER x{len(p['failovers'])}"
                       if p["failovers"] else "")
                )
    mpmd = mpmd_step_report(spans)
    if mpmd:
        lines.append(f"mpmd: {len(mpmd)} stitched step(s)")
        for entry in mpmd[:slowest_k]:
            per_w = "  ".join(
                f"w{w}: compute {1e3 * v['compute_s']:.2f}ms"
                f" blocked {1e3 * v['blocked_s']:.2f}ms"
                for w, v in sorted(entry["workers"].items())
            )
            lines.append(f"  step {entry['step']}: {per_w}")
    if not lines:
        lines.append("no distributed-trace spans found")
    return "\n".join(lines)

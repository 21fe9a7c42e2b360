"""Telemetry wire schemas + validators (the drift gate).

What crosses a process or machine boundary from this subsystem: JSONL
span dumps, Chrome ``trace_event`` documents, the stream items the
worker→driver queue carries (``heartbeat``, ``event``, ``log``,
``metrics``), the crash flight bundle ``flight_recorder.py`` persists,
program-ledger rows, the serving plane's request / reply / snapshot /
handoff frames and the MPMD transfer frames.  Downstream consumers
(Perfetto, ``rlt_top``, the router, post-mortem tooling) parse them
long after the producing code has moved on — so the schema is written
down HERE, and ``tests/test_wire_schemas.py`` (tier-1, and layer 4 of
``format.sh``) drives every real producer through its validator and
fails when the two drift apart.

Validators return a list of problem strings (empty = valid) instead of
raising, so a caller can report every problem in one pass.  jax-free.
"""

from __future__ import annotations

from typing import Any, Dict, List

__all__ = [
    "validate_span",
    "validate_span_jsonl",
    "validate_chrome_trace",
    "validate_trace_context",
    "validate_heartbeat",
    "validate_event",
    "validate_log_item",
    "validate_stream_item",
    "validate_flight_bundle",
    "validate_serve_request",
    "validate_serve_reply",
    "validate_serve_snapshot",
    "validate_serve_kv_handoff",
    "validate_serve_adapter_load",
    "validate_serve_migration",
    "validate_router_snapshot",
    "validate_mpmd_stage_item",
    "validate_mpmd_xfer",
    "validate_mpmd_snapshot",
    "validate_program_row",
    "validate_recompile_record",
    "validate_program_snapshot",
    "validate_timeseries_point",
    "validate_slo_alert",
    "validate_capacity_snapshot",
    "FLIGHT_BUNDLE_SCHEMA_ID",
]

# JSONL span schema: required key → allowed types.
_SPAN_REQUIRED = {
    "name": str,
    "ts": (int, float),
    "dur": (int, float),
    "rank": int,
    "tid": int,
    "depth": int,
}
_SPAN_OPTIONAL = {"args": dict}

# Chrome complete-event schema (the subset our exporter emits and
# Perfetto requires).
_CHROME_X_REQUIRED = {
    "name": str,
    "ts": (int, float),
    "dur": (int, float),
    "pid": int,
    "tid": int,
}


def _check_fields(obj: Dict[str, Any], required: dict, optional: dict,
                  where: str) -> List[str]:
    problems = []
    if not isinstance(obj, dict):
        return [f"{where}: expected object, got {type(obj).__name__}"]
    for key, types in required.items():
        if key not in obj:
            problems.append(f"{where}: missing required key {key!r}")
        elif not isinstance(obj[key], types) or isinstance(obj[key], bool):
            problems.append(
                f"{where}: key {key!r} has type "
                f"{type(obj[key]).__name__}"
            )
    for key, types in optional.items():
        if key in obj and not isinstance(obj[key], types):
            problems.append(
                f"{where}: optional key {key!r} has type "
                f"{type(obj[key]).__name__}"
            )
    unknown = set(obj) - set(required) - set(optional)
    if unknown:
        problems.append(f"{where}: unknown keys {sorted(unknown)}")
    return problems


def validate_span(span: Dict[str, Any], where: str = "span") -> List[str]:
    problems = _check_fields(span, _SPAN_REQUIRED, _SPAN_OPTIONAL, where)
    if not problems and span["dur"] < 0:
        problems.append(f"{where}: negative dur {span['dur']}")
    return problems


# ---------------------------------------------------------------------------
# Distributed tracing: the trace-context envelope wire frames carry
# ---------------------------------------------------------------------------

# The "trace" dict riding (OPTIONALLY — old producers stay wire-
# compatible) every queue-plane frame family: serve_request,
# serve_kv_handoff, replica/prefill beats, mpmd_xfer, mpmd_stage,
# heartbeat and event items.  ``ts`` is the producer's wall-clock SEND
# time (epoch seconds) so the consumer can book the transfer interval.
_TRACE_CTX_REQUIRED = {
    "trace_id": str,
    "span_id": str,
}
_TRACE_CTX_OPTIONAL = {
    "parent_span_id": str,
    "ts": (int, float),
}


def validate_trace_context(trace: Any,
                           where: str = "trace") -> List[str]:
    problems = _check_fields(
        trace, _TRACE_CTX_REQUIRED, _TRACE_CTX_OPTIONAL, where
    )
    if not problems:
        if not trace["trace_id"]:
            problems.append(f"{where}: empty trace_id")
        if not trace["span_id"]:
            problems.append(f"{where}: empty span_id")
    return problems


def _check_optional_trace(item: Dict[str, Any], where: str) -> List[str]:
    """Validate the optional trace envelope when a frame carries one."""
    if isinstance(item, dict) and "trace" in item:
        return validate_trace_context(item["trace"], f"{where}.trace")
    return []


def validate_span_jsonl(lines: List[str], where: str = "jsonl") -> List[str]:
    """Validate a span JSONL dump given as decoded lines."""
    import json

    problems = []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except ValueError as e:
            problems.append(f"{where}:{i + 1}: not JSON ({e})")
            continue
        problems.extend(validate_span(obj, f"{where}:{i + 1}"))
    return problems


def validate_chrome_trace(doc: Any, where: str = "trace") -> List[str]:
    """Validate a Chrome ``trace_event`` document (our exporter's
    ``{"traceEvents": [...]}`` form; ``ph=="X"`` events only — other
    phases pass through, Perfetto tolerates them)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"{where}: expected a trace document object"]
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return [f"{where}: missing/invalid traceEvents list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"{where}[{i}]: event is not an object")
            continue
        if ev.get("ph") != "X":
            continue
        for key, types in _CHROME_X_REQUIRED.items():
            if key not in ev:
                problems.append(f"{where}[{i}]: missing {key!r}")
            elif (not isinstance(ev[key], types)
                  or isinstance(ev[key], bool)):
                problems.append(
                    f"{where}[{i}]: {key!r} has type "
                    f"{type(ev[key]).__name__}"
                )
        if isinstance(ev.get("dur"), (int, float)) and ev["dur"] < 0:
            problems.append(f"{where}[{i}]: negative dur")
    return problems


# ---------------------------------------------------------------------------
# Live-monitor stream items (the worker→driver queue wire format)
# ---------------------------------------------------------------------------

# Heartbeat: the compact per-rank liveness/progress record the
# HeartbeatPublisher enqueues every RLT_HEARTBEAT_S seconds.
_HEARTBEAT_REQUIRED = {
    "type": str,          # always "heartbeat"
    "rank": int,
    "seq": int,           # per-publisher monotonic counter
    "ts": (int, float),   # wall-clock (time.time) at compose
    "global_step": int,
    "micro_step": int,
    "epoch": int,
    "progress": int,      # loop progress counter (train + val batches)
    "phase": str,         # coarse loop phase: init/train/validation/closing
}
_HEARTBEAT_OPTIONAL = {
    "step_time_ms": (int, float),
    "data_wait_ms": (int, float),
    "examples_per_sec": (int, float),
    "open_span": str,            # deepest open span (full tier only)
    "device_memory": dict,       # jax memory_stats subset, best-effort
    "host_load": (int, float),   # 1-minute load average
    "done": bool,                # final beat before the publisher stops
    "trace": dict,               # optional trace-context envelope
    "compile_total_s": (int, float),  # process XLA compile seconds so far
}

# Event: structured monitor/worker occurrences (stall, stack_dump,
# heartbeat_lost, straggler, crash, abort — and, since the recovery-
# plane round: drain, preempt_restart, backoff, elastic_restart,
# ckpt_corrupt; since the elastic-world round: resize,
# resize_rejected).  rank == -1 means fleet-wide.
_EVENT_REQUIRED = {
    "type": str,          # always "event"
    "kind": str,
    "rank": int,
    "ts": (int, float),
}
_EVENT_OPTIONAL = {
    "message": str,
    "stacks": str,        # formatted py-stack dump (stack_dump events)
    "bundle": str,        # flight-bundle path (crash events)
    "error": str,
    "lag_steps": int,
    "age_s": (int, float),
    "device_memory": dict,
    "detail": dict,
    "ckpt": str,          # drain / restart / ckpt_corrupt checkpoint path
    "delay_s": (int, float),    # backoff events: the observed delay
    "attempt": int,             # backoff / elastic_restart ordinal
    "recover_s": (int, float),  # elastic_restart/resize: respawn time
    "old_world": int,           # resize/resize_rejected: world before
    "new_world": int,           # resize/resize_rejected: world after
    "trace": dict,              # optional trace-context envelope
}

# Log: a rank-tagged forwarded logging record (warning+ severity).
_LOG_REQUIRED = {
    "type": str,          # always "log"
    "rank": int,
    "ts": (int, float),
    "level": str,
    "logger": str,
    "message": str,
}

FLIGHT_BUNDLE_SCHEMA_ID = "rlt-flight-bundle-v1"

# Crash flight bundle: the post-mortem document flight_recorder.py
# persists under the telemetry dir on uncaught worker exceptions.
_BUNDLE_REQUIRED = {
    "schema": str,        # FLIGHT_BUNDLE_SCHEMA_ID
    "rank": int,
    "ts": (int, float),
    "error": str,         # repr of the exception
    "traceback": str,
    "global_step": int,
    "micro_step": int,
    "epoch": int,
    "phase": str,
    "fingerprint": dict,  # env/device identity (python, jax, RLT_* knobs)
}
_BUNDLE_OPTIONAL = {
    "spans": list,        # last-N span dicts from the ring
    "step_stats": dict,
    "counters": dict,
    "logs": list,         # ring-buffered rank-tagged log lines
    "device_memory": dict,
    "stacks": str,        # all-thread py stacks at crash time
    "callback_metrics": dict,  # metrics at crash time (async log fetch
                               # flushed first — latest boundary landed)
    "programs": dict,     # program-ledger snapshot (what was compiled,
                          # what recompiled, and why — crash forensics)
}


def _validate_typed(obj: Any, expect_type: str, required: dict,
                    optional: dict, where: str) -> List[str]:
    problems = _check_fields(obj, required, optional, where)
    if not problems and obj.get("type") != expect_type:
        problems.append(
            f"{where}: type is {obj['type']!r}, expected {expect_type!r}"
        )
    return problems


def validate_heartbeat(item: Any, where: str = "heartbeat") -> List[str]:
    problems = _validate_typed(
        item, "heartbeat", _HEARTBEAT_REQUIRED, _HEARTBEAT_OPTIONAL, where
    )
    if not problems:
        for key in ("seq", "global_step", "micro_step", "progress"):
            if item[key] < 0:
                problems.append(f"{where}: negative {key} {item[key]}")
        problems += _check_optional_trace(item, where)
    return problems


def validate_event(item: Any, where: str = "event") -> List[str]:
    problems = _validate_typed(
        item, "event", _EVENT_REQUIRED, _EVENT_OPTIONAL, where
    )
    if not problems:
        if item["rank"] < -1:
            problems.append(f"{where}: invalid rank {item['rank']}")
        problems += _check_optional_trace(item, where)
    return problems


def validate_log_item(item: Any, where: str = "log") -> List[str]:
    return _validate_typed(item, "log", _LOG_REQUIRED, {}, where)


def validate_stream_item(item: Any, where: str = "item") -> List[str]:
    """Dispatch on ``item["type"]`` — the one entry point for consumers
    that see the raw queue stream (``metrics`` items are loop-internal
    and intentionally not schema-pinned here beyond the type routing)."""
    if not isinstance(item, dict):
        return [f"{where}: expected object, got {type(item).__name__}"]
    kind = item.get("type")
    if kind == "heartbeat":
        return validate_heartbeat(item, where)
    if kind == "event":
        return validate_event(item, where)
    if kind == "log":
        return validate_log_item(item, where)
    if kind == "metrics":
        return []
    if kind == "mpmd_stage":
        return validate_mpmd_stage_item(item, where)
    return [f"{where}: unknown stream item type {kind!r}"]


def validate_flight_bundle(doc: Any, where: str = "bundle") -> List[str]:
    problems = _check_fields(
        doc, _BUNDLE_REQUIRED, _BUNDLE_OPTIONAL, where
    )
    if problems:
        return problems
    if doc["schema"] != FLIGHT_BUNDLE_SCHEMA_ID:
        problems.append(
            f"{where}: schema is {doc['schema']!r}, expected "
            f"{FLIGHT_BUNDLE_SCHEMA_ID!r}"
        )
    for i, span in enumerate(doc.get("spans", [])):
        problems += validate_span(span, f"{where}.spans[{i}]")
    if "programs" in doc:
        problems += validate_program_snapshot(
            doc["programs"], f"{where}.programs"
        )
    return problems


# ---------------------------------------------------------------------------
# Program ledger (telemetry/program_ledger.py): the compiled-executable
# observatory — per-program cost/memory rows and recompile forensics
# ---------------------------------------------------------------------------

# One compiled executable: identity + the XLA accounting captured at
# first dispatch.  ``signature`` is the compact abstract-argument
# rendering the recompile diff is computed over; accounting keys are
# best-effort (a backend without cost_analysis still gets a row).
_PROGRAM_ROW_REQUIRED = {
    "site": str,          # stable call-site name, e.g. "serve/decode"
    "variant": int,       # 0 = first compile at the site
    "ncalls": int,
    "compile_s": (int, float),   # measured lower()+compile() wall
    "signature": str,
}
_PROGRAM_ROW_OPTIONAL = {
    "backend": str,
    "donated": str,                    # donate_argnums rendering
    "flops": (int, float),             # cost_analysis
    "bytes_accessed": (int, float),    # cost_analysis
    "argument_bytes": int,             # memory_analysis
    "output_bytes": int,
    "temp_bytes": int,
    "alias_bytes": int,
    "generated_code_bytes": int,
}

#: The delta kinds a recompile attribution may carry.
RECOMPILE_KINDS = ("shape", "dtype", "structure", "donation", "static")

# A recompile attribution: which site, which argument, what changed.
_RECOMPILE_REQUIRED = {
    "type": str,          # always "recompile"
    "site": str,
    "kind": str,          # one of RECOMPILE_KINDS
    "argument": str,      # offending argument (leaf path included)
    "ts": (int, float),
}
_RECOMPILE_OPTIONAL = {
    "old": str,
    "new": str,
    "variant": int,       # the variant index the recompile created
    "rank": int,
}

# The full observatory snapshot (flight bundles, rlt_top, serve-live).
_PROGRAM_SNAPSHOT_REQUIRED = {
    "programs": list,
    "recompiles": list,
    "compile_time_total_s": (int, float),
}
_PROGRAM_SNAPSHOT_OPTIONAL = {
    "dropped": int,       # rows past the ring cap
}

def validate_program_row(row: Any, where: str = "program") -> List[str]:
    problems = _check_fields(
        row, _PROGRAM_ROW_REQUIRED, _PROGRAM_ROW_OPTIONAL, where
    )
    if not problems:
        if not row["site"]:
            problems.append(f"{where}: empty site")
        for key in ("variant", "ncalls", "compile_s"):
            if row[key] < 0:
                problems.append(f"{where}: negative {key} {row[key]}")
    return problems


def validate_recompile_record(rec: Any,
                              where: str = "recompile") -> List[str]:
    problems = _validate_typed(
        rec, "recompile", _RECOMPILE_REQUIRED, _RECOMPILE_OPTIONAL, where
    )
    if not problems:
        if rec["kind"] not in RECOMPILE_KINDS:
            problems.append(
                f"{where}: kind {rec['kind']!r} not in "
                f"{RECOMPILE_KINDS}"
            )
        if not rec["argument"]:
            problems.append(f"{where}: empty argument attribution")
        if not rec["site"]:
            problems.append(f"{where}: empty site")
    return problems


def validate_program_snapshot(snap: Any,
                              where: str = "programs") -> List[str]:
    problems = _check_fields(
        snap, _PROGRAM_SNAPSHOT_REQUIRED, _PROGRAM_SNAPSHOT_OPTIONAL, where
    )
    if problems:
        return problems
    for i, row in enumerate(snap["programs"]):
        problems += validate_program_row(row, f"{where}.programs[{i}]")
    for i, rec in enumerate(snap["recompiles"]):
        problems += validate_recompile_record(
            rec, f"{where}.recompiles[{i}]"
        )
    if snap["compile_time_total_s"] < 0:
        problems.append(f"{where}: negative compile_time_total_s")
    return problems


# ---------------------------------------------------------------------------
# Serving plane (serve/): wire items, live snapshot
# ---------------------------------------------------------------------------

# The client → engine submission item (serve/client.py → engine inbox).
_SERVE_REQUEST_REQUIRED = {
    "type": str,              # always "serve_request"
    "rid": str,
    "prompt": list,           # int token ids
    "max_new_tokens": int,
    "reply": list,            # [host, port] of the client's reply queue
}
_SERVE_REQUEST_OPTIONAL = {
    "temperature": (int, float),
    "eos_token_id": (int, type(None)),
    "top_k": (int, type(None)),       # shape-static sampler truncation
    "spec": (int, type(None)),        # per-request draft count cap
    # Multi-tenant LoRA: the adapter (tenant) to decode through
    # (None/absent = the shared base model).
    "adapter": (str, type(None)),
    "deadline_s": (int, float, type(None)),
    # Disaggregated serving: the router's fleet-wide sampling-stream
    # identity (absent/None = the engine assigns its own ordinal).
    "sample_seed": (int, type(None)),
    # Brownout shed class: 0 (default) sheds first under fleet
    # overload, >= 1 survives to the shed rung (router admission).
    "priority": int,
    # Client hedged resubmit: a duplicate submission of an ALREADY
    # in-flight rid — the router places it on a second replica, first
    # terminal wins, the loser is cancelled.
    "hedge": bool,
    # Distributed tracing: the request's trace-context envelope
    # (validate_trace_context; absent on untraced producers).
    "trace": dict,
}

# Engine → client replies: the per-token stream and the completion.
_SERVE_TOKEN_REQUIRED = {
    "type": str,              # "serve_token"
    "rid": str,
    "index": int,             # re-emitted from 0 after a preemption
    "token": int,
}
_SERVE_DONE_REQUIRED = {
    "type": str,              # "serve_done"
    "rid": str,
    # finished/rejected/expired/invalid/error, plus the resilience
    # outcomes: "shed" (brownout overload reply, retryable) and
    # "cancelled" (hedge loser / operator drop, retryable).
    "status": str,
    "tokens": list,
}
_SERVE_DONE_OPTIONAL = {
    # eos/length/rejected/expired/brownout/cancelled
    "reason": (str, type(None)),
    "error": str,                  # invalid submissions only
}


def validate_serve_request(item: Any,
                           where: str = "serve_request") -> List[str]:
    problems = _validate_typed(
        item, "serve_request", _SERVE_REQUEST_REQUIRED,
        _SERVE_REQUEST_OPTIONAL, where,
    )
    if not problems:
        if item["max_new_tokens"] < 1:
            problems.append(f"{where}: max_new_tokens < 1")
        if not item["prompt"]:
            problems.append(f"{where}: empty prompt")
        if len(item["reply"]) != 2:
            problems.append(f"{where}: reply is not [host, port]")
        problems += _check_optional_trace(item, where)
    return problems


def validate_serve_reply(item: Any, where: str = "serve_reply") -> List[str]:
    """Dispatch over the engine → client reply family."""
    if not isinstance(item, dict):
        return [f"{where}: expected object, got {type(item).__name__}"]
    kind = item.get("type")
    if kind == "serve_token":
        problems = _validate_typed(
            item, "serve_token", _SERVE_TOKEN_REQUIRED, {}, where
        )
        if not problems and item["index"] < 0:
            problems.append(f"{where}: negative index")
        return problems
    if kind == "serve_done":
        return _validate_typed(
            item, "serve_done", _SERVE_DONE_REQUIRED,
            _SERVE_DONE_OPTIONAL, where,
        )
    if kind == "serve_batch":
        # One tick's replies to one address in one frame (what the
        # engine sends): tokens and completions only.
        items = item.get("items")
        if not isinstance(items, list) or not items:
            return [f"{where}: serve_batch without items"]
        problems = []
        for i, sub in enumerate(items):
            if isinstance(sub, dict) and sub.get("type") == "serve_batch":
                problems.append(f"{where}.items[{i}]: nested serve_batch")
            else:
                problems += validate_serve_reply(sub, f"{where}.items[{i}]")
        return problems
    return [f"{where}: unknown serve reply type {kind!r}"]


# The live SLO snapshot (ServeStats.snapshot → serve-live.json, the
# OpenMetrics serve gauges and rlt_top's serve pane).
_SERVE_SNAPSHOT_REQUIRED = {
    "ts": (int, float),
    "counters": dict,
    "gauges": dict,
    "latency": dict,
}
# "phases" appears only on TRACING engines (ServeStats.note_phase is
# lazily fed by the request tracer) — per critical-path phase p50/p95;
# "adapters" only on multi-LoRA engines (ServeStats.note_adapter) —
# per-tenant token/completion accounting, the fairness surface.
_SERVE_SNAPSHOT_OPTIONAL = {
    "phases": dict,
    "adapters": dict,
    # Prefix-cache engines only (ServeStats.set_prefix, fed from
    # PrefixIndex.stats each gauge refresh).
    "prefix": dict,
    # Capacity-plane engines only (serve/capacity.py::CapacityOracle —
    # the headroom oracle's latest capacity_snapshot, so beats carry
    # it to the router for free).
    "capacity": dict,
    # Only once an iteration of the loop stalled (serve/metrics.py
    # ``LoopWatch``): the longest few, each a record of what the loop's
    # thread was doing, with the iteration before it.
    "stalls": list,
}
_SERVE_STALL_TURN = {
    "t_ns": int,
    "wall_us": int,
    "phases": dict,
    "cpu_us": int,
    "gc_us": int,
    "invol_switches": int,
    "vol_switches": int,
    "slots": int,
    "buckets": list,
}
_SERVE_STALL_FLAGS = {"ahead": bool, "fed": bool}
_SERVE_STALL_REQUIRED = {**_SERVE_STALL_TURN, "phase": str, "verdict": str}
_SERVE_STALL_VERDICTS = ("blocked", "preempted", "collector", "running")
_SERVE_PREFIX_REQUIRED = {
    "hit_rate": (int, float),
    "lookups": int,
    "hits": int,
    "blocks_claimed": int,
    "blocks_inserted": int,
    "blocks_evicted": int,
    "cached_blocks": int,
}
_SERVE_ADAPTER_ENTRY_FIELDS = {
    "tokens_out": int,
    "completed": int,
}
_SERVE_LATENCY_KEYS = ("ttft", "token", "queue_wait", "e2e")
_SERVE_LATENCY_FIELDS = {
    "n": int,
    "p50_ms": (int, float),
    "p99_ms": (int, float),
    "max_ms": (int, float),
}
_SERVE_PHASE_FIELDS = {
    "n": int,
    "p50_ms": (int, float),
    "p95_ms": (int, float),
}


def _check_stall_turn(turn: Any, required: dict, optional: dict,
                      where: str) -> List[str]:
    """One iteration of a stall record (its two flags are booleans,
    which ``_check_fields`` takes for no required key)."""
    problems = _check_fields(
        turn, required, {**_SERVE_STALL_FLAGS, **optional}, where)
    if isinstance(turn, dict):
        problems += [f"{where}: missing required key {key!r}"
                     for key in _SERVE_STALL_FLAGS if key not in turn]
    return problems


def validate_serve_snapshot(doc: Any,
                            where: str = "serve_snapshot") -> List[str]:
    problems = _check_fields(
        doc, _SERVE_SNAPSHOT_REQUIRED, _SERVE_SNAPSHOT_OPTIONAL, where
    )
    if problems:
        return problems
    for phase, summary in doc.get("phases", {}).items():
        problems += _check_fields(
            summary, _SERVE_PHASE_FIELDS, {},
            f"{where}.phases.{phase}",
        )
    for key, value in doc["counters"].items():
        if not isinstance(value, int) or isinstance(value, bool):
            problems.append(f"{where}: counter {key!r} is not an int")
    for key, value in doc["gauges"].items():
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            problems.append(f"{where}: gauge {key!r} is not numeric")
    rate = doc["gauges"].get("spec_acceptance_rate")
    if isinstance(rate, (int, float)) and not 0.0 <= rate <= 1.0:
        problems.append(
            f"{where}: spec_acceptance_rate {rate} outside [0, 1]"
        )
    spread = doc["gauges"].get("lora_fairness_spread")
    if isinstance(spread, (int, float)) and not 0.0 <= spread <= 1.0:
        problems.append(
            f"{where}: lora_fairness_spread {spread} outside [0, 1]"
        )
    for i, stall in enumerate(doc.get("stalls", ())):
        at = f"{where}.stalls[{i}]"
        stall_problems = _check_stall_turn(
            stall, _SERVE_STALL_REQUIRED, {"before": dict}, at)
        if not stall_problems:
            if stall["verdict"] not in _SERVE_STALL_VERDICTS:
                stall_problems.append(
                    f"{at}: verdict {stall['verdict']!r} is not one of "
                    f"{_SERVE_STALL_VERDICTS}")
            if f"tick_{stall['phase']}_us" not in stall["phases"]:
                stall_problems.append(
                    f"{at}: phase {stall['phase']!r} is not among its "
                    "phases")
            if "before" in stall:
                stall_problems += _check_stall_turn(
                    stall["before"], _SERVE_STALL_TURN, {}, f"{at}.before")
        problems += stall_problems
    if "prefix" in doc:
        prefix_problems = _check_fields(
            doc["prefix"], _SERVE_PREFIX_REQUIRED, {}, f"{where}.prefix"
        )
        if not prefix_problems:
            hr = doc["prefix"]["hit_rate"]
            if not 0.0 <= hr <= 1.0:
                prefix_problems.append(
                    f"{where}.prefix: hit_rate {hr} outside [0, 1]"
                )
            if doc["prefix"]["hits"] > doc["prefix"]["lookups"]:
                prefix_problems.append(
                    f"{where}.prefix: hits > lookups"
                )
        problems += prefix_problems
    for name, entry in doc.get("adapters", {}).items():
        problems += _check_fields(
            entry, _SERVE_ADAPTER_ENTRY_FIELDS, {},
            f"{where}.adapters.{name}",
        )
    counters = doc["counters"]
    if all(isinstance(counters.get(k), int)
           for k in ("spec_accepted", "spec_drafted")):
        if counters["spec_accepted"] > counters["spec_drafted"]:
            problems.append(
                f"{where}: spec_accepted {counters['spec_accepted']} > "
                f"spec_drafted {counters['spec_drafted']}"
            )
    for family, summary in doc["latency"].items():
        if family not in _SERVE_LATENCY_KEYS:
            problems.append(f"{where}: unknown latency family {family!r}")
            continue
        problems += _check_fields(
            summary, _SERVE_LATENCY_FIELDS, {},
            f"{where}.latency.{family}",
        )
    if "capacity" in doc:
        problems += validate_capacity_snapshot(
            doc["capacity"], f"{where}.capacity"
        )
    return problems


# ---------------------------------------------------------------------------
# Fleet SLO & capacity plane (telemetry/timeseries.py, telemetry/slo.py,
# serve/capacity.py): store persistence points, burn-rate alert events,
# headroom-oracle snapshots
# ---------------------------------------------------------------------------

# One retained bin of a TimeSeriesStore series (dump_jsonl / points).
# hist bins surface their per-bin median as ``value`` plus the merged
# sample count ``n``; counter/gauge bins carry the bin value alone.
_TIMESERIES_POINT_REQUIRED = {
    "type": str,          # always "timeseries_point"
    "name": str,
    "kind": str,          # counter | gauge | hist
    "ts": (int, float),   # bin START (bin_index * interval_s)
    "value": (int, float),
}
_TIMESERIES_POINT_OPTIONAL = {
    "n": int,             # hist bins only: merged sample count
}
_TIMESERIES_KINDS = ("counter", "gauge", "hist")


def validate_timeseries_point(point: Any,
                              where: str = "timeseries_point"
                              ) -> List[str]:
    problems = _validate_typed(
        point, "timeseries_point", _TIMESERIES_POINT_REQUIRED,
        _TIMESERIES_POINT_OPTIONAL, where,
    )
    if problems:
        return problems
    if point["kind"] not in _TIMESERIES_KINDS:
        problems.append(f"{where}: unknown kind {point['kind']!r}")
    if not point["name"]:
        problems.append(f"{where}: empty series name")
    if "n" in point:
        if point["kind"] != "hist":
            problems.append(
                f"{where}: sample count n on a "
                f"{point['kind']} bin"
            )
        elif point["n"] < 1:
            problems.append(f"{where}: n < 1")
    return problems


# The slo_alert event's ``detail`` payload (the event envelope itself
# is the stock _EVENT_* shape — alerts ride the existing event plane).
_SLO_ALERT_DETAIL_REQUIRED = {
    "slo": str,
    "mode": str,                        # ratio | threshold
    "target": (int, float),            # the objective, in (0, 1)
    "burn_rate": (int, float),         # budget-burn multiple observed
    "error_rate": (int, float),        # over the slow window, [0, 1]
    "fast_window_s": (int, float),
    "slow_window_s": (int, float),
    "threshold_burn": (int, float),    # the pair's firing bound
}


def validate_slo_alert(item: Any, where: str = "slo_alert") -> List[str]:
    problems = validate_event(item, where)
    if problems:
        return problems
    if item.get("kind") != "slo_alert":
        problems.append(
            f"{where}: kind is {item.get('kind')!r}, expected "
            f"'slo_alert'"
        )
    detail = item.get("detail")
    if not isinstance(detail, dict):
        problems.append(f"{where}: missing detail payload")
        return problems
    problems += _check_fields(
        detail, _SLO_ALERT_DETAIL_REQUIRED, {}, f"{where}.detail"
    )
    if problems:
        return problems
    if not 0.0 < detail["target"] < 1.0:
        problems.append(
            f"{where}.detail: target {detail['target']} outside (0, 1)"
        )
    if not 0.0 <= detail["error_rate"] <= 1.0:
        problems.append(
            f"{where}.detail: error_rate {detail['error_rate']} "
            f"outside [0, 1]"
        )
    if detail["burn_rate"] < 0:
        problems.append(f"{where}.detail: negative burn_rate")
    if detail["fast_window_s"] >= detail["slow_window_s"]:
        problems.append(
            f"{where}.detail: fast window "
            f"{detail['fast_window_s']} not shorter than slow "
            f"{detail['slow_window_s']}"
        )
    if detail["mode"] not in ("ratio", "threshold"):
        problems.append(
            f"{where}.detail: unknown mode {detail['mode']!r}"
        )
    return problems


# The headroom oracle's output (CapacityOracle.snapshot — rides the
# serve snapshot's ``capacity`` block, beats, router snapshots and the
# rlt_capacity_* prom family).  The derived fields are nullable: the
# oracle refuses to guess before the per-slot service rate has data.
_CAPACITY_SNAPSHOT_REQUIRED = {
    "type": str,          # always "capacity_snapshot"
    "ts": (int, float),
    "window_s": (int, float),
    "tokens_per_s": (int, float),
    "service_rate_per_slot": (int, float, type(None)),
    "capacity_tokens_per_s": (int, float, type(None)),
    "headroom_tokens_per_s": (int, float, type(None)),
    "utilization": (int, float, type(None)),
    "kv_exhaustion_eta_s": (int, float, type(None)),
    "queue_wait_slope_ms_per_s": (int, float, type(None)),
    "queue_depth": (int, float),
    "rejection_rate": (int, float),
}


def validate_capacity_snapshot(snap: Any,
                               where: str = "capacity_snapshot"
                               ) -> List[str]:
    problems = _validate_typed(
        snap, "capacity_snapshot", _CAPACITY_SNAPSHOT_REQUIRED, {}, where
    )
    if problems:
        return problems
    if snap["window_s"] <= 0:
        problems.append(f"{where}: window_s <= 0")
    if snap["tokens_per_s"] < 0:
        problems.append(f"{where}: negative tokens_per_s")
    util = snap["utilization"]
    if isinstance(util, (int, float)) and not 0.0 <= util <= 1.0:
        problems.append(f"{where}: utilization {util} outside [0, 1]")
    rej = snap["rejection_rate"]
    if not 0.0 <= rej <= 1.0:
        problems.append(
            f"{where}: rejection_rate {rej} outside [0, 1]"
        )
    head = snap["headroom_tokens_per_s"]
    if isinstance(head, (int, float)) and head < 0:
        problems.append(f"{where}: negative headroom_tokens_per_s")
    eta = snap["kv_exhaustion_eta_s"]
    if isinstance(eta, (int, float)) and eta < 0:
        problems.append(f"{where}: negative kv_exhaustion_eta_s")
    return problems


# ---------------------------------------------------------------------------
# Disaggregated serving (serve/dist/): KV handoff envelope, router
# snapshot
# ---------------------------------------------------------------------------

# The prefill worker → decode replica handoff envelope.  Like the MPMD
# transfer frame, the bulk tensor payload (encode_tree bytes of
# {"kv", "logits"}) rides EXACTLY ONE of data/shm and is deliberately
# outside the schema; the request riding in "req" is a full
# serve_request (validated recursively, sample_seed required — a
# handoff without the router's fleet-wide seed would break failover
# stream stability).
_SERVE_HANDOFF_REQUIRED = {
    "type": str,          # always "serve_kv_handoff"
    "rid": str,
    "bucket": int,        # prefill bucket length (tokens)
    "prompt_len": int,
    "req": dict,
}
_SERVE_HANDOFF_OPTIONAL = {
    "data": bytes,
    "shm": str,
    # The prefill worker's trace envelope (span_id = its prefill span;
    # ts = send time, the replica books handoff_transfer from it).
    "trace": dict,
}


def validate_serve_kv_handoff(item: Any,
                              where: str = "serve_kv_handoff"
                              ) -> List[str]:
    problems = _validate_typed(
        item, "serve_kv_handoff", _SERVE_HANDOFF_REQUIRED,
        _SERVE_HANDOFF_OPTIONAL, where,
    )
    if problems:
        return problems
    if ("data" in item) == ("shm" in item):
        problems.append(
            f"{where}: exactly one of data/shm payload required"
        )
    if item["prompt_len"] < 1:
        problems.append(f"{where}: prompt_len < 1")
    if item["bucket"] < item["prompt_len"]:
        problems.append(
            f"{where}: bucket {item['bucket']} smaller than prompt_len "
            f"{item['prompt_len']}"
        )
    problems += validate_serve_request(item["req"], f"{where}.req")
    seed = item["req"].get("sample_seed") \
        if isinstance(item["req"], dict) else None
    if not isinstance(seed, int) or isinstance(seed, bool):
        problems.append(f"{where}.req: missing/invalid sample_seed")
    problems += _check_optional_trace(item, where)
    return problems


# The draining replica → router → survivor live-migration envelope
# (serve/dist/handoff.py::make_migration_item): one resident
# sequence's KV blocks + scheduler position + the canonical request
# fields, so the survivor resumes decode mid-sequence with zero
# recomputed prefill.  Unlike KV handoffs the payload is ALWAYS inline
# bytes ("data") — migration frames ride the ordered beat lane, and a
# tmpfs segment would dangle if the draining host died mid-drain.
_SERVE_MIGRATION_REQUIRED = {
    "type": str,          # always "serve_migration"
    "rid": str,
    "req": dict,          # request_fields dict (reply + sample_seed)
    "generated": list,    # tokens already emitted to the client
    "cur_token": int,     # last sampled token (next tick's input)
    "seq_len": int,       # KV positions written (prompt+gen-1)
    "data": bytes,        # encode_tree({"kv": ...})
}
_SERVE_MIGRATION_OPTIONAL = {
    "trace": dict,
}


def validate_serve_migration(item: Any,
                             where: str = "serve_migration"
                             ) -> List[str]:
    problems = _validate_typed(
        item, "serve_migration", _SERVE_MIGRATION_REQUIRED,
        _SERVE_MIGRATION_OPTIONAL, where,
    )
    if problems:
        return problems
    if not item["generated"]:
        problems.append(
            f"{where}: empty generated — a sequence with no emitted "
            f"tokens has nothing worth migrating (recompute failover "
            f"covers it)"
        )
    if item["seq_len"] < 1:
        problems.append(f"{where}: seq_len < 1")
    problems += validate_serve_request(item["req"], f"{where}.req")
    req = item["req"] if isinstance(item["req"], dict) else {}
    seed = req.get("sample_seed")
    if not isinstance(seed, int) or isinstance(seed, bool):
        # Without the fleet seed the survivor cannot continue the
        # stream bitwise at temperature > 0.
        problems.append(f"{where}.req: missing/invalid sample_seed")
    prompt = req.get("prompt")
    if isinstance(prompt, list) and item["generated"] \
            and item["seq_len"] >= 1 \
            and item["seq_len"] != len(prompt) \
            + len(item["generated"]) - 1:
        # The invariant the importer's block math depends on: the
        # final sampled token's KV is never written until its own
        # decode tick.
        problems.append(
            f"{where}: seq_len {item['seq_len']} != prompt + "
            f"generated - 1 ({len(prompt) + len(item['generated']) - 1})"
        )
    problems += _check_optional_trace(item, where)
    return problems


# The router/operator → member adapter hot-load envelope (multi-tenant
# LoRA; serve/dist/handoff.py::make_adapter_load_item).  Like KV
# handoffs, the bulk factor payload (encode_adapter bytes) rides
# EXACTLY ONE of data/shm and is deliberately outside the schema.
_SERVE_ADAPTER_LOAD_REQUIRED = {
    "type": str,          # always "serve_adapter_load"
    "name": str,          # tenant name (the pool registry key)
    "rank": int,          # stacked-buffer rank the pool must match
}
_SERVE_ADAPTER_LOAD_OPTIONAL = {
    "data": bytes,
    "shm": str,
}


def validate_serve_adapter_load(item: Any,
                                where: str = "serve_adapter_load"
                                ) -> List[str]:
    problems = _validate_typed(
        item, "serve_adapter_load", _SERVE_ADAPTER_LOAD_REQUIRED,
        _SERVE_ADAPTER_LOAD_OPTIONAL, where,
    )
    if problems:
        return problems
    if ("data" in item) == ("shm" in item):
        problems.append(
            f"{where}: exactly one of data/shm payload required"
        )
    if item["rank"] < 1:
        problems.append(f"{where}: rank must be >= 1")
    if not item["name"]:
        problems.append(f"{where}: empty adapter name")
    return problems


# router-live.json (Router.snapshot — the rlt_top router pane and the
# per-replica rlt_serve_* OpenMetrics labels parse this).
_ROUTER_SNAPSHOT_REQUIRED = {
    "ts": (int, float),
    "counters": dict,
    "replicas": list,
    "workers": list,
}
_ROUTER_REPLICA_OPTIONAL = {
    "last_beat_age_s": (int, float, type(None)),
    "slots_active": (int, float),
    "num_slots": (int, float),
    "queue_depth": (int, float),
    "blocks_free": (int, float),
    "num_blocks": (int, float),
    "spec_acceptance_rate": (int, float),
    "prefix_cache_hit_rate": (int, float),
    "recompiles": int,
    "adapters": int,       # loaded LoRA tenants (pool-capable members)
    # Capacity-plane members only: lifted from the capacity_snapshot
    # riding the beat's serve snapshot (serve/capacity.py).
    "headroom_tokens_per_s": (int, float, type(None)),
    "utilization": (int, float, type(None)),
    "kv_exhaustion_eta_s": (int, float, type(None)),
}
# The fleet-wide capacity roll-up (serve/capacity.py::aggregate_fleet)
# the router attaches when any member reports a capacity block, and
# the brownout ladder's current rung (brownout-enabled routers only;
# 0 = healthy, 1 = spec off, 2 = max_new capped, 3 = shedding).
_ROUTER_SNAPSHOT_OPTIONAL = {
    "capacity": dict,
    "brownout_level": int,
}
_FLEET_CAPACITY_REQUIRED = {
    "replicas_reporting": int,
    "tokens_per_s": (int, float),
    "capacity_tokens_per_s": (int, float, type(None)),
    "headroom_tokens_per_s": (int, float, type(None)),
    "utilization": (int, float, type(None)),
    "kv_exhaustion_eta_s": (int, float, type(None)),
}
_ROUTER_WORKER_OPTIONAL = {
    "last_beat_age_s": (int, float, type(None)),
    "adapters": int,
}


def _validate_router_member(entry: Any, where: str, count_key: str,
                            optional: dict) -> List[str]:
    if not isinstance(entry, dict):
        return [f"{where}: expected object"]
    problems = []
    if not isinstance(entry.get("id"), str):
        problems.append(f"{where}: missing/invalid id")
    if not isinstance(entry.get("alive"), bool):
        problems.append(f"{where}: missing/invalid alive")
    n = entry.get(count_key)
    if not isinstance(n, int) or isinstance(n, bool) or n < 0:
        problems.append(f"{where}: missing/invalid {count_key}")
    for key, types in optional.items():
        if key in entry and not isinstance(entry[key], types):
            problems.append(
                f"{where}: key {key!r} has type "
                f"{type(entry[key]).__name__}"
            )
    unknown = set(entry) - {"id", "alive", count_key} - set(optional)
    if unknown:
        problems.append(f"{where}: unknown keys {sorted(unknown)}")
    rate = entry.get("spec_acceptance_rate")
    if isinstance(rate, (int, float)) and not 0.0 <= rate <= 1.0:
        problems.append(
            f"{where}: spec_acceptance_rate {rate} outside [0, 1]"
        )
    hit = entry.get("prefix_cache_hit_rate")
    if isinstance(hit, (int, float)) and not 0.0 <= hit <= 1.0:
        problems.append(
            f"{where}: prefix_cache_hit_rate {hit} outside [0, 1]"
        )
    util = entry.get("utilization")
    if isinstance(util, (int, float)) and not 0.0 <= util <= 1.0:
        problems.append(f"{where}: utilization {util} outside [0, 1]")
    return problems


def validate_router_snapshot(doc: Any,
                             where: str = "router_snapshot") -> List[str]:
    problems = _check_fields(
        doc, _ROUTER_SNAPSHOT_REQUIRED, _ROUTER_SNAPSHOT_OPTIONAL, where
    )
    if problems:
        return problems
    if "capacity" in doc:
        cap_problems = _check_fields(
            doc["capacity"], _FLEET_CAPACITY_REQUIRED, {},
            f"{where}.capacity",
        )
        if not cap_problems:
            util = doc["capacity"]["utilization"]
            if isinstance(util, (int, float)) \
                    and not 0.0 <= util <= 1.0:
                cap_problems.append(
                    f"{where}.capacity: utilization {util} "
                    f"outside [0, 1]"
                )
            if doc["capacity"]["replicas_reporting"] < 1:
                cap_problems.append(
                    f"{where}.capacity: replicas_reporting < 1"
                )
        problems += cap_problems
    lvl = doc.get("brownout_level")
    if lvl is not None and not 0 <= lvl <= 3:
        problems.append(
            f"{where}: brownout_level {lvl} outside [0, 3]"
        )
    for key, value in doc["counters"].items():
        if not isinstance(value, int) or isinstance(value, bool) \
                or value < 0:
            problems.append(
                f"{where}: counter {key!r} is not a non-negative int"
            )
    for i, entry in enumerate(doc["replicas"]):
        problems += _validate_router_member(
            entry, f"{where}.replicas[{i}]", "inflight",
            _ROUTER_REPLICA_OPTIONAL,
        )
    for i, entry in enumerate(doc["workers"]):
        problems += _validate_router_member(
            entry, f"{where}.workers[{i}]", "pending",
            _ROUTER_WORKER_OPTIONAL,
        )
    return problems


# ---------------------------------------------------------------------------
# MPMD pipeline plane (mpmd/): stream items, transfer frames, live
# snapshot
# ---------------------------------------------------------------------------

# Per-optimizer-step stage beat on the worker→driver queue (the MPMD
# plane's live signal — stage workers run no heartbeat publisher).
_MPMD_STAGE_REQUIRED = {
    "type": str,          # always "mpmd_stage"
    "stage": int,
    "step": int,
    "bubble_fraction": (int, float),
    "stage_occupancy": (int, float),
}
_MPMD_STAGE_OPTIONAL = {
    "loss": (int, float),         # loss-hosting worker only
    "busy_s": (int, float),
    "blocked_s": (int, float),
    "trace": dict,                # the step's trace-context envelope
}

# The inter-stage transfer frame (mpmd/transfer.py wire contract):
# exactly one of ``data`` (inline payload) / ``shm`` (segment path).
_MPMD_XFER_REQUIRED = {
    "type": str,          # always "mpmd_xfer"
    "kind": str,          # "act" | "grad"
    "step": int,
    "mb": int,
    "chunk": int,
}
_MPMD_XFER_OPTIONAL = {
    "data": bytes,
    "shm": str,
    "trace": dict,        # sender's trace envelope (cross-stage stitch)
    "enc": str,           # wire codec ("act:bf16,grad:int8"); absent=f32
}

# mpmd-live.json (MpmdStrategy's live export, the rlt_top mpmd pane).
_MPMD_SNAPSHOT_REQUIRED = {
    "schedule": str,
    "interleave": int,
    "n_micro": int,
    "n_stages": int,
    "stages": list,       # per-stage mpmd_stage items
}


def validate_mpmd_stage_item(item: Any,
                             where: str = "mpmd_stage") -> List[str]:
    problems = _validate_typed(
        item, "mpmd_stage", _MPMD_STAGE_REQUIRED, _MPMD_STAGE_OPTIONAL,
        where,
    )
    if not problems:
        if item["stage"] < 0:
            problems.append(f"{where}: negative stage")
        if not 0.0 <= item["bubble_fraction"] <= 1.0:
            problems.append(
                f"{where}: bubble_fraction {item['bubble_fraction']} "
                "outside [0, 1]"
            )
        problems += _check_optional_trace(item, where)
    return problems


def validate_mpmd_xfer(item: Any, where: str = "mpmd_xfer") -> List[str]:
    problems = _validate_typed(
        item, "mpmd_xfer", _MPMD_XFER_REQUIRED, _MPMD_XFER_OPTIONAL, where
    )
    if problems:
        return problems
    if item["kind"] not in ("act", "grad"):
        problems.append(f"{where}: unknown kind {item['kind']!r}")
    if ("data" in item) == ("shm" in item):
        problems.append(
            f"{where}: exactly one of data/shm payload required"
        )
    for key in ("step", "mb", "chunk"):
        if item[key] < 0:
            problems.append(f"{where}: negative {key}")
    problems += _check_optional_trace(item, where)
    return problems


def validate_mpmd_snapshot(doc: Any,
                           where: str = "mpmd_snapshot") -> List[str]:
    """Validate the ``mpmd`` block of a live snapshot document."""
    problems = _check_fields(doc, _MPMD_SNAPSHOT_REQUIRED, {}, where)
    if problems:
        return problems
    for i, item in enumerate(doc["stages"]):
        problems += validate_mpmd_stage_item(
            item, f"{where}.stages[{i}]"
        )
    return problems

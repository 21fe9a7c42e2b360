"""Telemetry artifact schemas + validators (the drift gate).

Artifact families leaving this subsystem: JSONL span dumps, Chrome
``trace_event`` documents, the ``telemetry`` block inside
``BENCH_*.json``, and — since the live-monitor round — the stream items
the worker→driver queue carries (``heartbeat``, ``event``, ``log``,
``metrics``) plus the crash flight bundle ``flight_recorder.py``
persists.  Downstream consumers (Perfetto, the trace-summary tool,
``rlt_top``, round-over-round bench comparison, post-mortem tooling)
parse them long after the producing code has moved on — so the schema
is written down HERE, and ``tools/check_telemetry_schema.py`` (wired
into ``format.sh``) fails fast when a producer drifts.

Validators return a list of problem strings (empty = valid) instead of
raising, so the CLI can report every problem in one pass.  jax-free.
"""

from __future__ import annotations

from typing import Any, Dict, List

__all__ = [
    "validate_span",
    "validate_span_jsonl",
    "validate_chrome_trace",
    "validate_trace_context",
    "validate_bench_trace",
    "validate_bench_telemetry",
    "validate_bench_fault",
    "validate_bench_host_overhead",
    "validate_bench_opt_state",
    "validate_bench_residual_policy",
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
    "validate_bench_serve",
    "validate_bench_spec_decode",
    "validate_bench_prefix_cache",
    "validate_bench_chunked_prefill",
    "validate_bench_serve_disagg",
    "validate_bench_serve_chaos",
    "validate_bench_multi_lora",
    "validate_mpmd_stage_item",
    "validate_mpmd_xfer",
    "validate_mpmd_snapshot",
    "validate_bench_mpmd",
    "validate_bench_comm_overlap",
    "validate_program_row",
    "validate_recompile_record",
    "validate_program_snapshot",
    "validate_bench_programs",
    "validate_timeseries_point",
    "validate_slo_alert",
    "validate_capacity_snapshot",
    "validate_bench_slo",
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
# observatory — per-program cost/memory rows, recompile forensics, and
# the bench ``programs`` block
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

# The bench ``programs`` block: ledger coverage + the dispatch-overhead
# A/B (``ledger_overhead_pct`` nullable — the probe is best-effort).
_BENCH_PROGRAMS_REQUIRED = {
    "n_programs": int,
    "compile_time_total_s": (int, float),
    "recompile_events": int,
    "ledger_overhead_pct": (int, float, type(None)),
}
_BENCH_PROGRAMS_OPTIONAL = {
    "rows": list,         # program rows (validate_program_row each)
    "hbm": dict,          # program_ledger.hbm_report()
    "roofline": dict,     # program_ledger.roofline(...)
    "mfu_basis": str,     # "analytic" | "measured"
    "dropped": int,
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


def validate_bench_programs(block: Any,
                            where: str = "programs") -> List[str]:
    """Validate the ``programs`` block of a ``BENCH_*.json`` artifact
    (absent on pre-ledger rounds)."""
    problems = _check_fields(
        block, _BENCH_PROGRAMS_REQUIRED, _BENCH_PROGRAMS_OPTIONAL, where
    )
    if problems:
        return problems
    if block["n_programs"] < 0:
        problems.append(f"{where}: negative n_programs")
    if block["recompile_events"] < 0:
        problems.append(f"{where}: negative recompile_events")
    basis = block.get("mfu_basis")
    if basis is not None and basis not in ("analytic", "measured"):
        problems.append(f"{where}: invalid mfu_basis {basis!r}")
    for i, row in enumerate(block.get("rows", [])):
        problems += validate_program_row(row, f"{where}.rows[{i}]")
    return problems


# ---------------------------------------------------------------------------
# Serving plane (serve/): wire items, live snapshot, bench block
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
        # One tick's replies to one address in one frame
        # (ServeConfig.coalesce_replies): tokens and completions only.
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
}
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
# snapshot, bench block
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


# The bench_serve.py artifact block: serving rounds become comparable
# only if every round spells the SLO numbers the same way.  The A/B
# ratio and sweep arms are nullable (best-effort probes), the headline
# latency/throughput numbers are not — a serve bench that cannot
# measure them has failed.
_BENCH_SERVE_REQUIRED = {
    "requests_per_sec": (int, float),
    "p50_token_latency_ms": (int, float),
    "p99_token_latency_ms": (int, float),
    "recompiles_steady_state": int,
}
_BENCH_SERVE_OPTIONAL = {
    "tokens_per_sec": (int, float, type(None)),
    "p50_ttft_ms": (int, float, type(None)),
    "p99_ttft_ms": (int, float, type(None)),
    "continuous_vs_sequential": (int, float, type(None)),
    "sequential_requests_per_sec": (int, float, type(None)),
    "sequential_tokens_per_sec": (int, float, type(None)),
    "num_slots": int,
    "block_size": int,
    "num_blocks": int,
    "completed": int,
    "preempted": int,
    "rejected": int,
    "expired": int,
    "rate_sweep": list,       # per-offered-rate open-loop arms
}
_BENCH_SERVE_SWEEP_REQUIRED = {
    "offered_rps": (int, float),
    "requests_per_sec": (int, float),
    "p50_token_latency_ms": (int, float, type(None)),
    "p99_token_latency_ms": (int, float, type(None)),
}
_BENCH_SERVE_SWEEP_OPTIONAL = {
    "p50_ttft_ms": (int, float, type(None)),
    "p99_ttft_ms": (int, float, type(None)),
    "completed": int,
    "expired": int,
    "rejected": int,
    "queue_depth_max": int,
}


def validate_bench_serve(block: Any, where: str = "serve") -> List[str]:
    """Validate the ``serve`` block of a bench artifact (absent on
    pre-serving rounds)."""
    problems = _check_fields(
        block, _BENCH_SERVE_REQUIRED, _BENCH_SERVE_OPTIONAL, where
    )
    if problems:
        return problems
    if block["recompiles_steady_state"] < 0:
        problems.append(f"{where}: negative recompiles_steady_state")
    for i, arm in enumerate(block.get("rate_sweep", [])):
        problems += _check_fields(
            arm, _BENCH_SERVE_SWEEP_REQUIRED, _BENCH_SERVE_SWEEP_OPTIONAL,
            f"{where}.rate_sweep[{i}]",
        )
    return problems


# The bench_serve.py SLO/capacity-plane block: the oracle-calibration
# gate (predicted saturation knee vs the measured Poisson-sweep knee),
# the burn-rate alert discrimination check (fires hot, silent cold),
# the zero-recompile pin and the plane-overhead A/B.  Headline numbers
# are non-nullable — a round that cannot calibrate has failed; the
# overhead ratio is best-effort (CPU noise floor).
_BENCH_SLO_REQUIRED = {
    "predicted_saturation_rps": (int, float),
    "measured_saturation_rps": (int, float),
    "prediction_error_pct": (int, float),
    "alerts_hot": int,        # slo_alert events in the 1.5x arm
    "alerts_cold": int,       # slo_alert events in the 0.5x arm
    "recompiles_steady_state": int,
}
_BENCH_SLO_OPTIONAL = {
    "overhead_pct": (int, float, type(None)),
    "capacity_tokens_per_s": (int, float, type(None)),
    "service_rate_per_slot": (int, float, type(None)),
    "hot_rps": (int, float),
    "cold_rps": (int, float),
    "hot_utilization": (int, float, type(None)),
    "ts_points": int,         # persisted timeseries_point count
}


def validate_bench_slo(block: Any, where: str = "slo") -> List[str]:
    """Validate the ``slo`` block of a bench artifact (absent on
    pre-capacity-plane rounds)."""
    problems = _check_fields(
        block, _BENCH_SLO_REQUIRED, _BENCH_SLO_OPTIONAL, where
    )
    if problems:
        return problems
    for key in ("predicted_saturation_rps", "measured_saturation_rps"):
        if block[key] <= 0:
            problems.append(f"{where}: {key} must be > 0")
    if block["prediction_error_pct"] < 0:
        problems.append(f"{where}: negative prediction_error_pct")
    for key in ("alerts_hot", "alerts_cold",
                "recompiles_steady_state"):
        if block[key] < 0:
            problems.append(f"{where}: negative {key}")
    return problems


# The bench_serve.py speculative-decoding A/B block: the spec arm and
# its non-spec baseline must both pin their recompile counters (the
# zero-recompile steady state is the contract, not a best-effort), and
# the acceptance sweep scans tokens/s across draft quality.
_BENCH_SPEC_REQUIRED = {
    "spec_k": int,
    "tokens_per_sec": (int, float),            # spec arm, emitted
    "baseline_tokens_per_sec": (int, float),   # non-spec decode arm
    "vs_baseline": (int, float),               # the >= 1.5x headline
    "acceptance_rate": (int, float),
    "recompiles_steady_state": int,
    "baseline_recompiles_steady_state": int,
}
_BENCH_SPEC_OPTIONAL = {
    "draft_layers": int,
    "target_layers": int,
    "drafted": int,
    "accepted": int,
    "emitted": int,
    "greedy_parity": bool,        # spec tokens == non-spec tokens
    "requests": int,
    "max_new_tokens": int,
    "acceptance_sweep": list,     # per-noise arms
}
_BENCH_SPEC_SWEEP_REQUIRED = {
    "noise": (int, float),        # identity-tail perturbation scale
    "acceptance_rate": (int, float),
    "tokens_per_sec": (int, float),
    "vs_baseline": (int, float),
}


def validate_bench_spec_decode(block: Any,
                               where: str = "spec_decode") -> List[str]:
    """Validate the ``spec_decode`` block of a bench artifact (absent
    on pre-speculation rounds)."""
    problems = _check_fields(
        block, _BENCH_SPEC_REQUIRED, _BENCH_SPEC_OPTIONAL, where
    )
    if problems:
        return problems
    if block["spec_k"] < 1:
        problems.append(f"{where}: spec_k must be >= 1")
    if not 0.0 <= block["acceptance_rate"] <= 1.0:
        problems.append(
            f"{where}: acceptance_rate {block['acceptance_rate']} "
            "outside [0, 1]"
        )
    for key in ("recompiles_steady_state",
                "baseline_recompiles_steady_state"):
        if block[key] < 0:
            problems.append(f"{where}: negative {key}")
    for i, arm in enumerate(block.get("acceptance_sweep", [])):
        arm_problems = _check_fields(
            arm, _BENCH_SPEC_SWEEP_REQUIRED, {},
            f"{where}.acceptance_sweep[{i}]",
        )
        # Per-arm guard: an earlier arm's failure must not suppress
        # THIS arm's range check.
        if not arm_problems and not 0.0 <= arm["acceptance_rate"] <= 1.0:
            arm_problems.append(
                f"{where}.acceptance_sweep[{i}]: acceptance_rate "
                "outside [0, 1]"
            )
        problems += arm_problems
    return problems


# The bench_serve.py prefix-cache A/B block: the cached arm serves a
# shared-prefix workload mix against its cache-off baseline.  Both
# arms must pin recompiles_steady_state (sharing is operand-only by
# construction — a recompile would mean the claim leaked into a
# shape), and the parity flag asserts the cached arm's tokens are
# bitwise the baseline's.
_BENCH_PREFIX_REQUIRED = {
    "prefix_share": (int, float),       # fraction of prompt in the shared prefix
    "requests": int,
    "hit_rate": (int, float),
    "blocks_claimed": int,
    "ttft_p50_ms": (int, float),                # cached arm
    "baseline_ttft_p50_ms": (int, float),       # cache-off arm
    "ttft_speedup": (int, float),               # the >= 1.5x headline
    "tokens_per_sec": (int, float),
    "baseline_tokens_per_sec": (int, float),
    "recompiles_steady_state": int,
    "baseline_recompiles_steady_state": int,
}
_BENCH_PREFIX_OPTIONAL = {
    "token_parity": bool,       # cached tokens == baseline tokens
    "blocks_inserted": int,
    "cached_blocks": int,
    "prefill_chunks": int,
    "max_new_tokens": int,
}


def validate_bench_prefix_cache(block: Any,
                                where: str = "prefix_cache") -> List[str]:
    """Validate the ``prefix_cache`` block of a bench artifact (absent
    on pre-cache rounds)."""
    problems = _check_fields(
        block, _BENCH_PREFIX_REQUIRED, _BENCH_PREFIX_OPTIONAL, where
    )
    if problems:
        return problems
    if not 0.0 <= block["hit_rate"] <= 1.0:
        problems.append(
            f"{where}: hit_rate {block['hit_rate']} outside [0, 1]"
        )
    if not 0.0 <= block["prefix_share"] <= 1.0:
        problems.append(
            f"{where}: prefix_share {block['prefix_share']} "
            "outside [0, 1]"
        )
    for key in ("recompiles_steady_state",
                "baseline_recompiles_steady_state"):
        if block[key] < 0:
            problems.append(f"{where}: negative {key}")
    if block["requests"] < 1:
        problems.append(f"{where}: requests < 1")
    return problems


# The bench_long_context.py serving-side chunked-prefill block: a long
# prompt admitted against resident decode traffic, with the no-stall
# contract surfaced as the max per-step emission gap of the resident
# slots (1 = a token landed every step; the acceptance bound).
_BENCH_CHUNKED_REQUIRED = {
    "prompt_len": int,
    "chunk_width": int,
    "chunks": int,
    "resident_max_stall_ticks": int,
    "recompiles_steady_state": int,
}
_BENCH_CHUNKED_OPTIONAL = {
    "ttft_ms": (int, float, type(None)),
    "resident_requests": int,
    "tokens_per_sec": (int, float, type(None)),
}


def validate_bench_chunked_prefill(block: Any,
                                   where: str = "chunked_prefill"
                                   ) -> List[str]:
    """Validate the ``chunked_prefill`` block of a bench artifact."""
    problems = _check_fields(
        block, _BENCH_CHUNKED_REQUIRED, _BENCH_CHUNKED_OPTIONAL, where
    )
    if problems:
        return problems
    if block["chunk_width"] < 1:
        problems.append(f"{where}: chunk_width < 1")
    if block["chunks"] < 1:
        problems.append(f"{where}: chunks < 1")
    if block["prompt_len"] < 1:
        problems.append(f"{where}: prompt_len < 1")
    if block["resident_max_stall_ticks"] < 0:
        problems.append(f"{where}: negative resident_max_stall_ticks")
    if block["recompiles_steady_state"] < 0:
        problems.append(f"{where}: negative recompiles_steady_state")
    return problems


# The bench_serve.py disaggregated-serving block: the disagg-vs-
# monolith A/B plus the kill-a-replica chaos arm.  The chaos arm's
# loss accounting is required when the arm ran — a chaos block that
# cannot say how many requests survived has failed — and
# lost_requests is the zero-lost acceptance surface.
_BENCH_DISAGG_REQUIRED = {
    "replicas": int,
    "prefill_workers": int,
    "requests_per_sec": (int, float),
    "recompiles_steady_state": int,
}
_BENCH_DISAGG_OPTIONAL = {
    "requests": int,
    "tokens_per_sec": (int, float, type(None)),
    "monolith_requests_per_sec": (int, float, type(None)),
    "vs_monolith": (int, float, type(None)),
    "kv_imports": int,
    "prefill_dispatches": int,
    "p50_ttft_ms": (int, float, type(None)),
    "p99_ttft_ms": (int, float, type(None)),
    "chaos": dict,
}
_BENCH_DISAGG_CHAOS_REQUIRED = {
    "killed_replica": str,
    "submitted": int,
    "completed": int,
    "lost_requests": int,
    "failed_over_requests": int,
}
_BENCH_DISAGG_CHAOS_OPTIONAL = {
    "failover_detect_s": (int, float, type(None)),
    "re_emitted_tokens": int,
    "survivor_recompiles_steady_state": int,
    "offered_rps": (int, float),
}


def validate_bench_serve_disagg(block: Any,
                                where: str = "serve_disagg") -> List[str]:
    """Validate the ``serve_disagg`` block of a bench artifact (absent
    on pre-disaggregation rounds)."""
    problems = _check_fields(
        block, _BENCH_DISAGG_REQUIRED, _BENCH_DISAGG_OPTIONAL, where
    )
    if problems:
        return problems
    if block["replicas"] < 1:
        problems.append(f"{where}: replicas must be >= 1")
    if block["prefill_workers"] < 0:
        problems.append(f"{where}: negative prefill_workers")
    if block["recompiles_steady_state"] < 0:
        problems.append(f"{where}: negative recompiles_steady_state")
    chaos = block.get("chaos")
    if chaos is not None:
        chaos_problems = _check_fields(
            chaos, _BENCH_DISAGG_CHAOS_REQUIRED,
            _BENCH_DISAGG_CHAOS_OPTIONAL, f"{where}.chaos",
        )
        if not chaos_problems:
            if chaos["lost_requests"] < 0:
                chaos_problems.append(
                    f"{where}.chaos: negative lost_requests"
                )
            if chaos["completed"] + chaos["lost_requests"] \
                    > chaos["submitted"]:
                chaos_problems.append(
                    f"{where}.chaos: completed + lost > submitted"
                )
        problems += chaos_problems
    return problems


# The bench_serve.py serving-chaos block (ISSUE 19): the
# migration-vs-failover A/B.  Both arms drain/kill a replica
# mid-stream; the migration arm must lose zero requests, re-emit zero
# tokens (the KV moved, nothing was recomputed), and keep token parity
# with the uninterrupted engine — the failover arm is the recompute
# baseline it beats on time-to-recover.  Both arms pin steady-state
# recompiles.
_BENCH_SERVE_CHAOS_REQUIRED = {
    "migrations": int,                      # migration frames landed
    "migration_ttr_s": (int, float),        # drain -> stream resumed
    "failover_ttr_s": (int, float),         # kill -> stream resumed
    "migration_vs_failover": (int, float),  # failover_ttr / migration_ttr
    "lost_requests": int,
    "migration_re_emitted_tokens": int,     # MUST be 0 (no recompute)
    "recompiles_steady_state": int,
}
_BENCH_SERVE_CHAOS_OPTIONAL = {
    # bool keys ride the optional dict (the required-path bool guard
    # exists to catch True-as-int); presence is enforced below.
    "parity": bool,                         # tokens == uninterrupted run
    "failover_re_emitted_tokens": int,
    "requests": int,
    "shed": int,                 # brownout arm: typed shed replies
    "brownout_level_max": int,
    "hedges": int,
    "hedge_cancels": int,
}


def validate_bench_serve_chaos(block: Any,
                               where: str = "serve_chaos") -> List[str]:
    """Validate the ``serve_chaos`` block of a bench artifact (absent
    on pre-chaos rounds)."""
    problems = _check_fields(
        block, _BENCH_SERVE_CHAOS_REQUIRED, _BENCH_SERVE_CHAOS_OPTIONAL,
        where,
    )
    if problems:
        return problems
    if "parity" not in block:
        problems.append(f"{where}: missing required key 'parity'")
    for key in ("migrations", "lost_requests",
                "migration_re_emitted_tokens",
                "recompiles_steady_state"):
        if block[key] < 0:
            problems.append(f"{where}: negative {key}")
    for key in ("migration_ttr_s", "failover_ttr_s",
                "migration_vs_failover"):
        if block[key] < 0:
            problems.append(f"{where}: negative {key}")
    lvl = block.get("brownout_level_max")
    if lvl is not None and not 0 <= lvl <= 3:
        problems.append(
            f"{where}: brownout_level_max {lvl} outside [0, 3]"
        )
    return problems


# The bench_serve.py multi-tenant LoRA block: N adapters multiplexed
# over ONE resident base engine vs the merge-and-swap-per-tenant
# baseline (fold tenant k's factors into the weights, serve its batch,
# swap for the next tenant — the pre-pool serving shape).  Both arms
# pin their steady-state recompile counters (the zero-recompile
# contract covers adapter joins and hot-adds); fairness_spread is
# min/max lifetime tokens across tenants under uniform offered load
# (1.0 = perfectly fair, the DRR grant surface); greedy_parity pins
# every tenant's multiplexed stream token-for-token against its
# merged-model baseline.
_BENCH_MULTI_LORA_REQUIRED = {
    "adapters": int,                           # tenant count (N)
    "rank": int,                               # stacked-buffer rank
    "tokens_per_sec": (int, float),            # multiplexed arm
    "baseline_tokens_per_sec": (int, float),   # merge-and-swap arm
    "vs_baseline": (int, float),               # the >= 3x headline
    "fairness_spread": (int, float),
    "recompiles_steady_state": int,
    "baseline_recompiles_steady_state": int,
}
_BENCH_MULTI_LORA_OPTIONAL = {
    "requests": int,
    "max_new_tokens": int,
    "requests_per_sec": (int, float, type(None)),
    "greedy_parity": bool,
    "hot_adds": int,              # tenants joined AFTER warmup
    "pool_loads": int,
    "bgmv_impl": str,             # "xla" | "pallas" (engine-resolved)
    "completed": int,
}


def validate_bench_multi_lora(block: Any,
                              where: str = "multi_lora") -> List[str]:
    """Validate the ``multi_lora`` block of a bench artifact (absent on
    pre-multi-tenant rounds)."""
    problems = _check_fields(
        block, _BENCH_MULTI_LORA_REQUIRED, _BENCH_MULTI_LORA_OPTIONAL,
        where,
    )
    if problems:
        return problems
    if block["adapters"] < 1:
        problems.append(f"{where}: adapters must be >= 1")
    if block["rank"] < 1:
        problems.append(f"{where}: rank must be >= 1")
    if not 0.0 <= block["fairness_spread"] <= 1.0:
        problems.append(
            f"{where}: fairness_spread {block['fairness_spread']} "
            "outside [0, 1]"
        )
    for key in ("recompiles_steady_state",
                "baseline_recompiles_steady_state"):
        if block[key] < 0:
            problems.append(f"{where}: negative {key}")
    impl = block.get("bgmv_impl")
    if impl is not None and impl not in ("xla", "pallas"):
        problems.append(f"{where}: unknown bgmv_impl {impl!r}")
    return problems


# The bench_serve.py distributed-tracing block: the stitch-coverage /
# per-phase-percentile / overhead acceptance surface.  ``coverage`` is
# the fraction of COMPLETED requests whose stitched trace carries a
# complete queue_wait→…→first_token phase chain (the >=0.95 bar);
# ``overhead_pct`` is the measured closed-loop headline cost of
# cheap-tier tracing (the <2% bar); ``phases`` maps each critical-path
# phase to its p50/p95 over the traced run.
_BENCH_TRACE_REQUIRED = {
    "coverage": (int, float),
    "requests": int,
    "phases": dict,
    "overhead_pct": (int, float, type(None)),
}
_BENCH_TRACE_OPTIONAL = {
    "complete_chains": int,
    "spans": int,
    "traced_requests_per_sec": (int, float, type(None)),
    "baseline_requests_per_sec": (int, float, type(None)),
    "replicas": int,
    "prefill_workers": int,
}


def validate_bench_trace(block: Any, where: str = "trace") -> List[str]:
    """Validate the ``trace`` block of a bench artifact (absent on
    pre-tracing rounds)."""
    problems = _check_fields(
        block, _BENCH_TRACE_REQUIRED, _BENCH_TRACE_OPTIONAL, where
    )
    if problems:
        return problems
    if not 0.0 <= block["coverage"] <= 1.0:
        problems.append(
            f"{where}: coverage {block['coverage']} outside [0, 1]"
        )
    if block["requests"] < 0:
        problems.append(f"{where}: negative requests")
    for phase, summary in block["phases"].items():
        problems += _check_fields(
            summary, _SERVE_PHASE_FIELDS, {}, f"{where}.phases.{phase}"
        )
    return problems


# ---------------------------------------------------------------------------
# MPMD pipeline plane (mpmd/): stream items, transfer frames, live
# snapshot, bench block
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


# The bench mpmd block: the pipeline A/B becomes round-over-round
# comparable only if bubble/throughput are spelled the same way.
# Headline identification is required; each probe arm is nullable.
_BENCH_MPMD_REQUIRED = {
    "schedule": str,
    "n_stages": int,
    "n_micro": int,
}
_BENCH_MPMD_OPTIONAL = {
    "interleave": int,
    "bubble_fraction": (int, float, type(None)),
    "gpipe_bubble_fraction": (int, float, type(None)),
    "stage_occupancy": (int, float, type(None)),
    "stage_skew_ms": (int, float, type(None)),
    "tokens_per_sec": (int, float, type(None)),
    "single_mesh_tokens_per_sec": (int, float, type(None)),
    "vs_single_mesh": (int, float, type(None)),
    "loss_parity_max_diff": (int, float, type(None)),
    "op_costs_ms": dict,
}


def validate_bench_mpmd(block: Any, where: str = "mpmd") -> List[str]:
    """Validate the ``mpmd`` block of a ``BENCH_*.json`` artifact
    (absent on pre-MPMD rounds)."""
    problems = _check_fields(
        block, _BENCH_MPMD_REQUIRED, _BENCH_MPMD_OPTIONAL, where
    )
    if problems:
        return problems
    if block["n_stages"] < 1:
        problems.append(f"{where}: n_stages must be >= 1")
    if block["n_micro"] < 1:
        problems.append(f"{where}: n_micro must be >= 1")
    for key in ("bubble_fraction", "gpipe_bubble_fraction"):
        value = block.get(key)
        if isinstance(value, (int, float)) and not 0 <= value <= 1:
            problems.append(f"{where}: {key} {value} outside [0, 1]")
    return problems


# The bench comm_overlap block: the backward-overlapped grad-sync A/B
# (round 25).  Both arms run the SAME int8_ef grad-comm config on the
# same mesh; only `segments` differs (0 = step-end sync, G >= 1 =
# tapped backward).  ``loss_rel_diff`` is the A/B fit parity at the EF
# tolerance; ``bytes_ratio`` = overlap grad_sync_bytes / step-end
# (bucket re-planning pads per group, so ~1.0 within 10%);
# ``collectives_before_last_dot_*`` is the HLO-structural proof that
# the overlapped arm's bucket collectives are data-dependence-ordered
# INTO the backward rather than appended after it (step-end arm: 0).
# ``mpmd_*`` keys record the quantized-DCN-wire probe.  Probe keys are
# nullable — each arm is best-effort.
_BENCH_COMM_OVERLAP_REQUIRED = {
    "segments": int,
    "mode": str,
    "loss_rel_diff": (int, float),
}
_BENCH_COMM_OVERLAP_OPTIONAL = {
    "devices": (int, type(None)),
    "loss_step_end": (int, float, type(None)),
    "loss_overlap": (int, float, type(None)),
    "grad_sync_bytes_step_end": (int, float, type(None)),
    "grad_sync_bytes_overlap": (int, float, type(None)),
    "bytes_ratio": (int, float, type(None)),
    "dispatches_per_opt_step_step_end": (int, float, type(None)),
    "dispatches_per_opt_step_overlap": (int, float, type(None)),
    "recompiles_step_end": (int, type(None)),
    "recompiles_overlap": (int, type(None)),
    "collectives_before_last_dot_step_end": (int, type(None)),
    "collectives_before_last_dot_overlap": (int, type(None)),
    "hlo_gate": (bool, type(None)),
    "mpmd_wire_enc": (str, type(None)),
    "mpmd_wire_ratio": (int, float, type(None)),
    "mpmd_loss_rel_diff": (int, float, type(None)),
}


def validate_bench_comm_overlap(
    block: Any, where: str = "comm_overlap"
) -> List[str]:
    """Validate the ``comm_overlap`` block of a ``BENCH_*.json``
    artifact (absent on pre-overlap rounds)."""
    problems = _check_fields(
        block, _BENCH_COMM_OVERLAP_REQUIRED,
        _BENCH_COMM_OVERLAP_OPTIONAL, where,
    )
    if problems:
        return problems
    if block["segments"] < 1:
        problems.append(
            f"{where}: segments must be >= 1 (the overlapped arm), got "
            f"{block['segments']}"
        )
    if block["loss_rel_diff"] < 0:
        problems.append(f"{where}: negative loss_rel_diff")
    ratio = block.get("bytes_ratio")
    if isinstance(ratio, (int, float)) and not 0.9 <= ratio <= 1.1:
        problems.append(
            f"{where}: bytes_ratio {ratio} outside [0.9, 1.1] — "
            "overlap bucketing must not change the wire volume"
        )
    if block.get("hlo_gate") is True:
        before = block.get("collectives_before_last_dot_overlap")
        if not isinstance(before, int) or before < 1:
            problems.append(
                f"{where}: hlo_gate claims interleaving but "
                "collectives_before_last_dot_overlap is not a positive "
                "count"
            )
    wire = block.get("mpmd_wire_ratio")
    if isinstance(wire, (int, float)) and wire < 1.0:
        problems.append(
            f"{where}: mpmd_wire_ratio {wire} < 1 (codec inflated the "
            "payload)"
        )
    return problems


# The bench telemetry block contract: BENCH_*.json rounds become
# machine-comparable only if every round spells these the same way.
_BENCH_REQUIRED = {
    "tier": str,
}
_BENCH_OPTIONAL = {
    "overhead_pct": (int, float, type(None)),
    "heartbeat_overhead_pct": (int, float, type(None)),
    "monitor_events": int,
    "report": dict,
    "headline": dict,
    "probe": dict,
}


def validate_bench_telemetry(block: Any,
                             where: str = "telemetry") -> List[str]:
    """Validate the ``telemetry`` block of a ``BENCH_*.json`` artifact
    (absence of the block entirely is the caller's call — pre-telemetry
    rounds legitimately lack it)."""
    return _check_fields(block, _BENCH_REQUIRED, _BENCH_OPTIONAL, where)


# The bench fault block: recovery cost lands in the perf trajectory
# (crash → resumed wall time, drain checkpoint write time, the backoff
# actually slept; since the elastic-world round: lost worker → resumed
# -at-smaller-world wall delta).  Every key is nullable — each probe is
# best-effort.
_BENCH_FAULT_OPTIONAL = {
    "time_to_recover_s": (int, float, type(None)),
    "drain_checkpoint_s": (int, float, type(None)),
    "backoff_s": (int, float, type(None)),
    "resize_time_to_recover_s": (int, float, type(None)),
    "resize_old_world": (int, type(None)),
    "resize_new_world": (int, type(None)),
}


def validate_bench_fault(block: Any, where: str = "fault") -> List[str]:
    """Validate the ``fault`` block of a ``BENCH_*.json`` artifact
    (absent on pre-recovery-plane rounds)."""
    problems = _check_fields(block, {}, _BENCH_FAULT_OPTIONAL, where)
    if not problems and isinstance(block, dict):
        for key in ("resize_old_world", "resize_new_world"):
            value = block.get(key)
            if isinstance(value, int) and value < 0:
                problems.append(f"{where}: negative {key}")
    return problems


# The bench host_overhead block: how much of the step the HOST costs
# (the megastep round's acceptance surface).  ``fit_vs_raw`` is the
# Trainer-path overhead budget; ``dispatches_per_opt_step`` counts jit
# dispatches per optimizer update on the headline (per-step) fit;
# ``megastep_*`` record the K-fused A/B arm.  Nullable per probe — each
# arm is best-effort, a failed probe must never cost the headline line.
_BENCH_HOST_OVERHEAD_OPTIONAL = {
    "fit_vs_raw": (int, float, type(None)),
    "dispatches_per_opt_step": (int, float, type(None)),
    "megastep_k": (int, type(None)),
    "megastep_dispatches_per_opt_step": (int, float, type(None)),
    "megastep_tokens_per_sec": (int, float, type(None)),
    "megastep_speedup": (int, float, type(None)),
}


def validate_bench_host_overhead(block: Any,
                                 where: str = "host_overhead") -> List[str]:
    """Validate the ``host_overhead`` block of a ``BENCH_*.json``
    artifact (absent on pre-megastep rounds)."""
    problems = _check_fields(block, {}, _BENCH_HOST_OVERHEAD_OPTIONAL, where)
    k = block.get("megastep_k") if isinstance(block, dict) else None
    if not problems and isinstance(k, int) and k < 1:
        problems.append(f"{where}: megastep_k must be >= 1, got {k}")
    return problems


# The bench opt_state block: the HBM-traffic diet's acceptance surface.
# ``bytes_*`` are ANALYTIC persistent AdamW moment bytes
# (models/optim.py:opt_state_bytes — the chip truth is the optimizer
# line in the per-op profile); ``hbm_ratio`` =
# bytes_f32 / bytes_int8 (the >= 3.5x acceptance bar);
# ``loss_rel_diff_vs_f32`` is the measured A/B fit parity at the int8_ef
# grad-comm tolerance; ``update_sharding`` records the resolved
# cross-replica sharded-update arm.  Measured keys nullable per probe.
_BENCH_OPT_STATE_REQUIRED = {
    "dtype": str,
    "block_size": int,
    "bytes_f32": (int, float),
    "bytes_int8": (int, float),
    "bytes_active": (int, float),
    "hbm_ratio": (int, float),
}
_BENCH_OPT_STATE_OPTIONAL = {
    "loss_rel_diff_vs_f32": (int, float, type(None)),
    "tokens_per_sec": (int, float, type(None)),
    "vs_baseline": (int, float, type(None)),
    "update_sharding": (str, type(None)),
}


def validate_bench_opt_state(block: Any,
                             where: str = "opt_state") -> List[str]:
    """Validate the ``opt_state`` block of a ``BENCH_*.json`` artifact
    (absent on pre-round-15 artifacts)."""
    problems = _check_fields(
        block, _BENCH_OPT_STATE_REQUIRED, _BENCH_OPT_STATE_OPTIONAL, where
    )
    if not problems:
        if block["hbm_ratio"] <= 0:
            problems.append(f"{where}: hbm_ratio must be > 0")
        if block["block_size"] < 1:
            problems.append(f"{where}: block_size must be >= 1")
    return problems


# The bench residual_policy block: scan-residual compression A/B.
# ``*_bytes_per_step`` are ANALYTIC remat-saved residual bytes
# (models/gpt.py:residual_save_bytes; the chip truth is the profiler's
# dynamic-update-slice lines); ``vs_baseline`` is the measured
# tokens/sec ratio of the active arm against the baseline policy when
# the probe ran (remat fits measure nothing on the CPU container —
# nullable off-chip).
_BENCH_RESIDUAL_REQUIRED = {
    "policy": str,
    "baseline_policy": str,
    "residual_bytes_per_step": (int, float),
    "baseline_residual_bytes_per_step": (int, float),
    "bytes_saved_pct": (int, float),
}
_BENCH_RESIDUAL_OPTIONAL = {
    "tokens_per_sec": (int, float, type(None)),
    "vs_baseline": (int, float, type(None)),
    "loss_rel_diff_vs_baseline": (int, float, type(None)),
}


def validate_bench_residual_policy(
    block: Any, where: str = "residual_policy"
) -> List[str]:
    """Validate the ``residual_policy`` block of a ``BENCH_*.json``
    artifact (absent on pre-round-15 artifacts)."""
    return _check_fields(
        block, _BENCH_RESIDUAL_REQUIRED, _BENCH_RESIDUAL_OPTIONAL, where
    )

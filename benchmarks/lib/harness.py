"""What every driver needs: the run's arguments, the device check, the
compile cache, the profiler session, memory from the program ledger,
and the comparison helpers that decide ``correct``."""

from __future__ import annotations

import json
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from . import manifest, xplane


class BenchFailure(Exception):
    """The run cannot give a result (no chip, too few chips, a broken
    cell).  ``run.py`` exits non-zero and prints no result line."""


@dataclass
class Run:
    cell: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    t_start: float                      # host clock at process start
    work_dir: str                       # scratch inside the checkout
    timeline: Dict[str, float] = field(default_factory=dict)

    def mark(self, name: str) -> None:
        """A point of the set-up's timeline, seconds since process start
        (printed with the driver's summary line)."""
        self.timeline[name] = round(time.time() - self.t_start, 3)

    def note(self, **kw: Any) -> None:
        """An earlier line of the output (never the last)."""
        print(json.dumps(kw, default=str), flush=True)

    # -- data of the cell, with the rehearsal's shrinkage -------------------
    def config_fields(self) -> Dict[str, Any]:
        cfg = dict(self.cell["config_file"]["fields"])
        if self.rehearsal:
            cfg.update(self.cell.get("rehearsal", {}).get("fields", {}))
        return cfg

    def traffic(self) -> Dict[str, Any]:
        t = dict(self.cell["traffic_file"])
        if self.rehearsal:
            t.update(self.cell.get("rehearsal", {}).get("traffic", {}))
        return t

    def system(self) -> Dict[str, Any]:
        s = dict(self.cell.get("system", {}))
        if self.rehearsal:
            s.update(self.cell.get("rehearsal", {}).get("system", {}))
        return s


# ---------------------------------------------------------------------------
# device and cache
# ---------------------------------------------------------------------------

def claim_device(run: Run) -> Dict[str, Any]:
    """The device as JAX reports it in this process.  A run that is not
    an explicit rehearsal fails without a TPU or with fewer chips than
    the cell asks for."""
    import jax

    from ray_lightning_tpu.telemetry.step_stats import compile_event_count
    from ray_lightning_tpu.utils.compile_cache import enable_compile_cache

    # The variable where it is set, else the program's fixed path
    # inside the checkout: never a temporary or per-process directory.
    enable_compile_cache()
    compile_event_count()   # arm the listener before the first compile
    run.mark("imports_done")
    devices = jax.devices()
    run.mark("device_claimed")
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if run.rehearsal:
        return report
    if report["platform"] != "tpu":
        raise BenchFailure(f"no TPU: JAX reports {report}; a CPU run is "
                           "only ever an explicit --rehearsal")
    if report["count"] < int(run.cell["chips"]):
        raise BenchFailure(f"cell {run.cell['name']} asks for "
                           f"{run.cell['chips']} chips, JAX has {report}")
    return report


def memory_report(device: Dict[str, Any]) -> Dict[str, Any]:
    """Peak device memory, written into ``device`` and returned with
    the ledger's rows for an earlier line.  ``memory_stats()['peak_bytes_in_use']``
    counts live buffers only on this runtime (PERF.md, PR 21), so the
    peak is taken as the larger of that and the largest footprint of a
    program that ran (arguments + outputs - aliased + temporaries, from
    XLA's ``memory_analysis()`` in the program ledger)."""
    import jax

    from ray_lightning_tpu.telemetry import program_ledger

    stats_peak = 0
    for d in jax.local_devices():
        stats_peak = max(stats_peak, int(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) or 0))
    programs = {}
    rows = program_ledger.snapshot()["programs"]
    for row in rows:
        if not row.get("ncalls") or row.get("temp_bytes") is None:
            continue
        size = (int(row.get("argument_bytes") or 0)
                + int(row.get("output_bytes") or 0)
                - int(row.get("alias_bytes") or 0)
                + int(row.get("temp_bytes") or 0))
        programs[row["site"]] = max(programs.get(row["site"], 0), size)
    device["memory_peak_bytes"] = max([stats_peak, *programs.values()])
    keys = ("site", "ncalls", "compile_s", "argument_bytes", "temp_bytes",
            "output_bytes", "alias_bytes")
    return {"stats_peak_bytes": stats_peak, "program_bytes": programs,
            "programs": [{k: row.get(k) for k in keys} for row in rows]}


# ---------------------------------------------------------------------------
# the profiler session
# ---------------------------------------------------------------------------

class TraceSession:
    """``jax.profiler`` on for a few seconds of the steady window, the
    Python tracer off (it slows the host it is measuring).  ``start``
    and ``stop`` are called from one thread; the window annotation they
    hold open marks the traced window on the trace's own clock."""

    def __init__(self, run: Run):
        self.dir = os.path.join(run.work_dir, "trace", run.cell["name"])
        self.rehearsal = run.rehearsal
        self.started = False
        self.stopped = False
        self._mark = None
        self.t_started = 0.0

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION)
        self._mark.__enter__()
        self.started = True
        self.t_started = time.time()

    def stop(self) -> None:
        import jax

        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True

    def load(self) -> Optional[xplane.Trace]:
        if not self.stopped:
            return None
        path = xplane.find_xplane(self.dir)
        if path is None:
            return None
        return xplane.load(
            path, "host-xla" if self.rehearsal else "/device:TPU")


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def rel_max_err(a, b) -> float:
    """max|a-b| / max|b| in float32."""
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def finish(run: Run, *, correct: bool, attempted: int, failed: int,
           end_to_end: Dict[str, float], obs: Dict[str, Any],
           device: Dict[str, Any], trace: Optional[xplane.Trace]
           ) -> Dict[str, Any]:
    """The contract's object.  With ``--trace 0`` the metrics are the
    cell's end-to-end metrics, with ``--trace 1`` its per-layer ones."""
    units = run.cell["end_to_end"]
    if run.trace:
        metrics = manifest.read_layer_metrics(run.cell, obs)
    else:
        metrics = {k: {"value": float(end_to_end[k]), "unit": units[k]}
                   for k in units}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if run.trace and trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in trace.idle_gaps(10)],
        }
    return out

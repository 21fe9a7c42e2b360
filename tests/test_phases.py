"""The loop's phases: one primitive (``SpanTracer.phase``), three sinks.

A phase is a ``jax.profiler.TraceAnnotation`` ``rlt:<layer>/<name>``
(always, on the device trace's clock when a profiler session is live),
an integer-microsecond counter (always) and a ``Span`` (tracer enabled).
Here: the primitive alone, the engine tick cut into phases that sum to
its wall, the same names on the host plane of a real ``.xplane.pb``, and
the Pallas kernels' names at their call sites (the names in the program
compiled for the chip are checked in ``test_chip_compile.py``).
"""

from __future__ import annotations

import ast
import gc
import glob
import os
import threading
import time

import jax
import pytest

from ray_lightning_tpu.serve import metrics as serve_metrics
from ray_lightning_tpu.telemetry import PHASES, SpanTracer, Telemetry
from ray_lightning_tpu.telemetry.schema import validate_serve_snapshot
from ray_lightning_tpu.telemetry.spans import phase, phase_label

OPS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "ray_lightning_tpu", "ops")
TICK = PHASES["serve"]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enabled", [False, True], ids=["off", "on"])
def test_phase_counts_always_and_records_a_span_when_on(enabled):
    tracer = SpanTracer(enabled=enabled)
    sink = {}
    with tracer.phase("data_wait", "train", sink, rid="r1") as ph:
        time.sleep(0.002)
    assert 2000 <= sink["data_wait_us"] < 200_000
    assert ph.dur == pytest.approx(sink["data_wait_us"] / 1e6, abs=1e-6)
    with tracer.phase("data_wait", "train", sink):
        pass
    assert sink["data_wait_us"] >= 2000          # added to, not replaced
    spans = tracer.events()
    if not enabled:
        assert spans == []
        return
    assert [s.name for s in spans] == ["data_wait", "data_wait"]
    assert spans[0].dur == ph.dur and spans[0].args == {"rid": "r1"}
    assert spans[1].args is None


@pytest.mark.parametrize("entry", ["span", "phase", "start_remote"])
def test_every_entry_point_is_the_one_context_manager(entry):
    """``span()`` and ``start_remote()`` hand out the same timed phase:
    off it records nothing and carries no trace context, on it records
    one span (``start_remote`` with its own child context)."""
    from ray_lightning_tpu.telemetry.propagate import root_context

    def enter(tracer):
        if entry == "start_remote":
            return tracer.start_remote(root_context("r"), "first_token",
                                       rid="r")
        return getattr(tracer, entry)("first_token", rid="r")

    off, on = SpanTracer(enabled=False), SpanTracer(enabled=True)
    with enter(off) as ph:
        pass
    assert ph.dur >= 0 and ph.ctx is None and off.events() == []
    assert type(ph) is type(off.phase("x"))
    with enter(on) as ph:
        assert on.open_span == "first_token"
    [span] = on.events()
    assert span.name == "first_token" and span.args["rid"] == "r"
    assert (ph.ctx is not None) == (entry == "start_remote")
    if entry == "start_remote":
        assert span.args["span_id"] == ph.ctx.span_id


def test_phases_nest():
    tracer = SpanTracer(enabled=True)
    sink = {}
    with tracer.phase("validation", "train", sink):
        assert tracer.open_span == "validation"
        with tracer.phase("host_transfer", "train", sink):
            assert tracer.open_span == "host_transfer"
        assert tracer.open_span == "validation"
    assert tracer.open_span is None
    inner, outer = tracer.events()
    assert (inner.name, inner.depth) == ("host_transfer", 1)
    assert (outer.name, outer.depth) == ("validation", 0)
    assert sink["validation_us"] >= sink["host_transfer_us"]


def test_chained_phases_tile_their_iteration():
    """``then()`` closes one phase and opens the next on one clock
    read: no gap, no overlap, so the parts sum to the whole."""
    tracer = SpanTracer(enabled=True)
    sink = {}
    t0 = time.perf_counter()
    ph = tracer.phase("inbox", "serve", sink, "tick_").__enter__()
    for name in TICK[1:6]:
        time.sleep(0.001)
        ph.then(name, slot=1)
    ph.__exit__(None, None, None)
    wall_us = (time.perf_counter() - t0) * 1e6
    assert set(sink) == {f"tick_{p}_us" for p in TICK[:6]}
    assert sum(sink.values()) == pytest.approx(wall_us, abs=50)
    spans = tracer.events()
    assert [s.name for s in spans] == list(TICK[:6])
    for a, b in zip(spans, spans[1:]):
        assert a.depth == b.depth == 0
        assert b.args == {"slot": 1}


def test_phase_without_a_session_costs_microseconds():
    """No profiler session, tracer off: a phase is two clock reads, an
    inert TraceMe and a dict add.  The bound is loose (a loaded CI
    host); the engine tick's dozen phases must stay far under a
    millisecond."""
    tracer = SpanTracer(enabled=False)
    sink = {}
    with tracer.phase("emit", "serve", sink, "tick_"):
        pass                                     # the lazy import, once
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.phase("emit", "serve", sink, "tick_", slots=16):
            pass
    per_us = (time.perf_counter() - t0) / n * 1e6
    assert per_us < 25, f"{per_us:.1f} us a phase"
    assert tracer.events() == [] and set(sink) == {"tick_emit_us"}


@pytest.mark.parametrize("tier", ["off", "cheap", "full"])
def test_telemetry_phase_follows_the_tier(tier):
    tel = Telemetry.build(tier)
    with tel.phase("callbacks"):
        pass
    with phase("compile", site="s"):             # no tracer of its own
        pass
    assert ("callbacks_us" in tel.counters) == (tier != "off")
    assert [s.name for s in tel.tracer.events()] == (
        ["callbacks"] if tier == "full" else [])


def test_phase_names_are_spelled_once():
    assert phase_label("emit", "serve") == "rlt:serve/emit"
    assert phase_label("compile") == "rlt:compile"
    for layer, names in PHASES.items():
        assert len(set(names)) == len(names), layer
    assert {"decode_wait", "admit_wait", "idle"} <= set(TICK)
    assert {"data_wait", "sample_sync", "callbacks", "log_fetch"} <= set(
        PHASES["train"])


# ---------------------------------------------------------------------------
# the engine tick
# ---------------------------------------------------------------------------

def _tiny_engine(**kw):
    from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
    from utils import tiny_gpt

    return ServeEngine(*tiny_gpt(),
                       ServeConfig(num_slots=4, block_size=8), **kw)


@pytest.fixture(scope="module")
def ticked():
    """Counters of a tiny engine after 6 requests on 4 slots, with a
    copy taken when half were admitted."""
    eng = _tiny_engine()
    for i in range(6):
        eng.submit(list(range(1, 6 + i)), 8)
    eng.step()
    early = dict(eng.stats.counters)
    eng.run_until_idle(max_steps=200)
    return early, dict(eng.stats.counters)


# chunk (chunked prefill off) and idle (no background thread) stay 0.
@pytest.mark.parametrize("name", [p for p in TICK
                                  if p not in ("chunk", "idle")])
def test_every_tick_phase_has_its_counter(ticked, name):
    _, counters = ticked
    value = counters[f"tick_{name}_us"]
    assert isinstance(value, int) and value > 0


def test_tick_phases_sum_to_the_tick(ticked):
    _, counters = ticked
    assert counters["tick_chunk_us"] == 0 == counters["tick_idle_us"]
    parts = sum(counters[f"tick_{p}_us"] for p in TICK)
    assert counters["ticks"] >= counters["decode_steps"] > 0
    assert parts == pytest.approx(counters["tick_us"], rel=0.02)
    # The capacity oracle's counters are untouched beside them.
    assert counters["decode_us"] > 0 and counters["admit_us"] > 0


def test_a_tick_dispatched_ahead_tiles_its_iteration():
    """On an iteration whose tick dispatches the next decode before it
    fetches its own, the phases still tile the iteration, and that
    dispatch is booked as ``decode_dispatch``, not inside the tick's
    ``decode_wait``: with a dispatch slowed to 200 ms the wait stays the
    device's (at 20 ms a busy machine's wait of 27 ms failed, PR 32)."""
    eng = _tiny_engine()
    slow_s, calls = 0.2, []
    dispatch = eng._dispatch_decode

    def slow(*a, **kw):
        calls.append(kw.get("tokens") is not None)
        time.sleep(slow_s)
        return dispatch(*a, **kw)

    eng._dispatch_decode = slow
    eng.submit(list(range(1, 7)), 8)
    ahead_iterations = 0
    for _ in range(50):
        before = dict(eng.stats.counters)
        eng.step()
        delta = {k: v - before.get(k, 0)
                 for k, v in eng.stats.counters.items()}
        if eng._ahead is not None:
            ahead_iterations += 1
            parts = sum(delta[f"tick_{p}_us"] for p in TICK)
            assert parts == pytest.approx(delta["tick_us"], rel=0.02)
            assert delta["tick_decode_dispatch_us"] >= 0.9e6 * slow_s
            assert delta["tick_decode_wait_us"] < 0.5e6 * slow_s
        if not eng.scheduler.has_work():
            break
    counters = eng.stats.counters
    # 7 decodes for 8 tokens, all but the first fed on the device.
    assert calls == [False] + [True] * 6 and ahead_iterations == 6
    assert counters["decode_fed_on_device"] == 6 == counters["decode_ahead"]
    assert counters["tick_decode_dispatch_us"] >= 0.9e6 * slow_s * 6
    assert counters["tick_decode_wait_us"] < 0.5e6 * slow_s * 6


def test_an_admitting_iteration_tiles_and_waits_in_admit_wait():
    """An admission's first token is fetched after the dispatch of the
    decode it feeds: on such an iteration the phases still tile, and a
    first token that is slow to come (0.2 s each) is booked as
    ``admit_wait``, not inside ``decode_dispatch`` or ``decode_wait``."""
    eng = _tiny_engine()
    eng.generate(list(range(1, 5)), 3)      # compiles outside the phases
    slow_s = 0.2

    class SlowFirst:
        def __init__(self, token):
            self.token = token

        def __int__(self):
            time.sleep(slow_s)
            return int(self.token)

    prefill, feed = eng._prefill_fn, eng._feed_fn

    def slow_prefill(*a):
        first, *rest = prefill(*a)
        return (SlowFirst(first), *rest)

    eng._prefill_fn = slow_prefill
    eng._feed_fn = lambda cur, slot, first: feed(cur, slot, first.token)
    eng.submit(list(range(1, 7)), 12)
    admitting = []
    for i in range(50):
        if i == 3:          # two more while a decode is in flight
            eng.submit(list(range(2, 9)), 4)
            eng.submit(list(range(3, 8)), 5)
        before = dict(eng.stats.counters)
        eng.step()
        delta = {k: v - before.get(k, 0)
                 for k, v in eng.stats.counters.items()}
        if delta["prefills"]:
            admitting.append(delta["prefills"])
            parts = sum(delta[f"tick_{p}_us"] for p in TICK)
            assert parts == pytest.approx(delta["tick_us"], rel=0.02)
            waited = 1e6 * slow_s * delta["prefills"]
            assert 0.9 * waited <= delta["tick_admit_wait_us"] < 1.5 * waited
            assert delta["tick_decode_dispatch_us"] < 0.5e6 * slow_s
            assert delta["tick_decode_wait_us"] < 0.5e6 * slow_s
        if not eng.scheduler.has_work():
            break
    assert admitting == [1, 2]
    counters = eng.stats.counters
    assert counters["admit_fed_on_device"] == 4 == counters["prefills"]
    assert counters["admit_us"] >= 0.9e6 * slow_s * 3


def test_queue_wait_grows_with_admissions(ticked):
    early, late = ticked
    assert 0 < early["admitted"] < late["admitted"] == 6
    assert 0 < early["queue_wait_us"] < late["queue_wait_us"]


def test_queue_wait_counts_from_the_frames_receipt():
    """The counter dates a request that came over the queue plane from
    the moment its frame landed in the inbox, not from the tick that
    drained it: on the chip the loop is blocked on the device for a
    whole tick in between (PERF.md, PR 24).  Nothing else moves with
    it: ``arrival_t`` is still the drain, so the time in the inbox
    counts neither against ``deadline_s`` nor into the reservoirs."""
    from ray_lightning_tpu.serve.client import ServeClient

    eng = _tiny_engine()
    client = ServeClient(eng.queue_handle())
    try:
        rid = client.submit([1, 2, 3, 4], 2, deadline_s=0.04)
        time.sleep(0.05)                         # nobody drains meanwhile
        eng.run_until_idle(max_steps=50)
        assert len(client.result(rid, timeout=30)) == 2
    finally:
        client.close()
        eng.stop()
    counters = eng.stats.counters
    assert counters["admitted"] == 1 and counters["expired"] == 0
    assert 50_000 <= counters["queue_wait_us"] < 5_000_000
    assert eng.stats._queue_wait._vals[0] < 0.04


def test_idle_loop_counts_its_sleep():
    eng = _tiny_engine().start()
    try:
        deadline = time.monotonic() + 30
        while (eng.stats.counters["tick_idle_us"] < 5000
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        eng.stop()
    counters = eng.stats.counters
    assert counters["tick_idle_us"] >= 5000
    parts = sum(counters[f"tick_{p}_us"] for p in TICK)
    assert parts == pytest.approx(counters["tick_us"], rel=0.02)


def test_traced_engine_spans_its_ticks_while_a_request_is_in(tmp_path):
    """With ``trace_dir`` the tick's phases are spans in the requests'
    ring, but only while a request is in the engine: an idle loop
    turns every ``idle_wait_s`` and would push the requests' spans
    out."""
    eng = _tiny_engine(trace_dir=str(tmp_path))
    eng.generate(list(range(1, 9)), 4)
    spans = eng.tracer.events()
    request = {"queue_wait", "prefill_compute", "first_token", "request"}
    names = {s.name for s in spans}
    assert request <= names <= request | set(TICK)
    assert {"admit_dispatch", "decode_wait", "emit"} <= names
    ids = {s.args["trace_id"] for s in spans if s.name in request}
    assert len(ids) == 1                         # one request, one trace
    ticks = eng.stats.counters["ticks"]
    for _ in range(20):
        assert eng.step() is False
    assert eng.stats.counters["ticks"] == ticks + 20
    assert len(eng.tracer.events()) == len(spans)
    eng.stop()
    [path] = glob.glob(str(tmp_path / "trace-serve-*.jsonl"))
    with open(path) as f:
        assert len(f.readlines()) == len(spans)


# ---------------------------------------------------------------------------
# the loop's whole wall, and the iteration that stalled
# ---------------------------------------------------------------------------

def _delta(eng, before):
    return {k: v - before.get(k, 0) for k, v in eng.stats.counters.items()}


def test_between_tiles_the_loops_wall_beside_a_busy_thread():
    """``between`` runs from the close of one turn to the open of the
    next, so the phases sum to ``tick_us`` and ``tick_us`` to the serve
    thread's wall, idle turns included, while another thread fights it
    for the interpreter."""
    halt = threading.Event()

    def spin():
        while not halt.is_set():
            sum(range(500))

    other = threading.Thread(target=spin, daemon=True)
    eng = _tiny_engine()
    eng.generate(list(range(1, 9)), 3)           # compiles outside
    before = dict(eng.stats.counters)
    other.start()
    try:
        t0 = time.perf_counter()
        eng.start()
        handles = [eng.submit(list(range(1, 6 + i)), 8) for i in range(6)]
        for h in handles:
            assert len(h.result(timeout=60)) == 8
        time.sleep(0.05)                         # some idle turns
        eng.stop()
        wall_us = (time.perf_counter() - t0) * 1e6
    finally:
        halt.set()
        other.join(timeout=10)
        eng.stop()
    assert not other.is_alive()
    delta = _delta(eng, before)
    parts = sum(delta[f"tick_{p}_us"] for p in TICK)
    assert parts == pytest.approx(delta["tick_us"], rel=0.02)
    assert delta["tick_between_us"] > 0 and delta["tick_idle_us"] > 0
    assert 0.9 * wall_us <= delta["tick_us"] <= wall_us + 1000
    assert 0 < delta["tick_cpu_us"] <= delta["tick_us"]


def test_leaving_the_loop_drops_the_open_between():
    """The time between two ``run_until_idle`` calls is nobody's turn."""
    eng = _tiny_engine()
    eng.generate(list(range(1, 9)), 3)
    assert eng._between is None
    before = dict(eng.stats.counters)
    time.sleep(0.3)
    eng.generate(list(range(1, 9)), 3)
    delta = _delta(eng, before)
    assert delta["tick_between_us"] < 100_000 and delta["ticks_stalled"] == 0
    eng.step()
    assert eng._between is not None              # bare steps chain
    eng.stop()


def _sleep(s):
    time.sleep(s)


def _spin(s):
    t0 = time.thread_time()                      # seconds on a CPU
    while time.thread_time() - t0 < s:
        pass


def _slept_and_spun(after):
    slept, spun = sorted(after["stalls"], key=lambda r: r["t_ns"])
    return slept, spun


@pytest.fixture(scope="module")
def stalled(tmp_path_factory):
    """A tiny engine after 50 plain iterations, then, inside a real
    profiler session, a request in whose fifth iteration the
    ``replica_tick`` hook sleeps 0.4 s, then one in which it spins as
    long: the snapshot before, the one after, the counters' delta over
    the sleeping run and the session's ``rlt:serve/*`` events."""
    from ray_lightning_tpu.serve import engine as engine_mod

    eng = _tiny_engine()
    eng.generate(list(range(1, 9)), 50)
    healthy = eng.stats.snapshot()
    calls, held = [0], [_sleep]
    real = engine_mod._fault_fire

    def hook(point, **kw):
        real(point, **kw)
        if point == "replica_tick":
            calls[0] += 1
            if calls[0] == 5:
                held[0](0.4)

    trace_dir = str(tmp_path_factory.mktemp("stall"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    engine_mod._fault_fire = hook
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        eng.generate(list(range(2, 10)), 12)
        slept = _delta(eng, healthy["counters"])
        calls[0], held[0] = 0, _spin
        eng.generate(list(range(3, 11)), 12)
    finally:
        jax.profiler.stop_trace()
        engine_mod._fault_fire = real
        eng.stop()
    [path] = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    # A trace's events count from the session's start, which the plane
    # ``Task Environment`` gives in nanoseconds of ``time.time_ns()``.
    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    [start_ns] = [dict(p.stats)["profile_start_time"] for p in planes
                  if p.name == "Task Environment"]
    events = []
    for plane in planes:
        if plane.name.startswith("/host:CPU"):
            events += [(ev.name, start_ns + round(ev.start_ns),
                        start_ns + round(ev.start_ns + ev.duration_ns),
                        dict(ev.stats))
                       for line in plane.lines for ev in line.events
                       if ev.name.startswith("rlt:serve/")]
    return healthy, eng.stats.snapshot(), slept, sorted(
        events, key=lambda e: e[1])


def test_a_healthy_run_keeps_no_record(stalled):
    healthy, *_ = stalled
    assert "stalls" not in healthy
    assert healthy["counters"]["ticks_stalled"] == 0
    assert healthy["counters"]["tick_stalled_us"] == 0
    assert healthy["counters"]["decode_steps"] >= 49


def test_an_injected_delay_keeps_exactly_one_record(stalled):
    _, after, slept, _ = stalled
    assert validate_serve_snapshot(after) == []
    rec, _ = _slept_and_spun(after)
    assert slept["ticks_stalled"] == 1
    assert slept["tick_stalled_us"] == rec["wall_us"]
    assert 400_000 <= rec["wall_us"] < 2_000_000
    assert rec["phase"] == "inbox"
    assert rec["phases"]["tick_inbox_us"] >= 400_000
    assert sum(rec["phases"].values()) == pytest.approx(
        rec["wall_us"], abs=50)
    # Asleep: off the CPU of its own accord.  (A loaded machine can
    # still switch the thread out once while it runs: the word follows.)
    assert rec["cpu_us"] < 0.1 * rec["wall_us"] and rec["gc_us"] == 0
    assert rec["vol_switches"] >= 1
    assert rec["verdict"] == (
        "preempted" if rec["invol_switches"] else "blocked")
    assert rec["slots"] == 1 and rec["buckets"] == []
    assert rec["ahead"] is True and rec["fed"] is True
    # The iteration before it, as plain as any.
    before = rec["before"]
    assert set(before) == set(rec) - {"phase", "verdict", "before"}
    assert 0 < before["wall_us"] < 100_000
    assert before["t_ns"] + 1000 * before["wall_us"] == pytest.approx(
        rec["t_ns"], abs=2e6)


def test_a_busy_loop_reads_running(stalled):
    """0.4 s of the thread on a CPU: ``running``, unless the machine
    is so loaded that the thread stood switched out as long again."""
    _, after, *_ = stalled
    _, rec = _slept_and_spun(after)
    assert rec["phase"] == "inbox"
    assert 390_000 <= rec["cpu_us"] <= rec["wall_us"]
    assert rec["verdict"] == (
        "running" if 2 * rec["cpu_us"] > rec["wall_us"] else "preempted")


def test_a_stall_record_is_on_the_profilers_clock(stalled):
    """``t_ns`` is the iteration's open on the clock of the profiler's
    host plane: inside the ``rlt:serve/between`` that the iteration
    counts as its own, which ends where its long ``inbox`` begins; and
    the zero-length ``rlt:serve/stall`` closes it."""
    _, after, _, events = stalled
    rec, _ = _slept_and_spun(after)
    [inbox] = [e for e in events if e[0] == "rlt:serve/inbox"
               and 400e6 <= e[2] - e[1] < 2e9
               and abs(e[1] - rec["t_ns"]) < 50e6]
    between = max((e for e in events if e[0] == "rlt:serve/between"
                   and e[2] <= inbox[1] + 1000), key=lambda e: e[2])
    assert 0 <= inbox[1] - between[2] < 20_000   # one phase, then the next
    assert between[1] <= rec["t_ns"] <= between[2]
    marks = [e for e in events if e[0] == "rlt:serve/stall"]
    assert len(marks) == 2
    mark = min(marks, key=lambda e: e[1])
    assert mark[1] >= inbox[2]
    assert mark[1] == pytest.approx(
        rec["t_ns"] + 1000 * rec["wall_us"], abs=5e6)
    assert int(mark[3]["wall_us"]) == rec["wall_us"]
    assert (mark[3]["phase"], mark[3]["verdict"]) == (
        "inbox", rec["verdict"])


def test_a_traced_engine_records_the_stall_as_a_span(tmp_path, monkeypatch):
    from ray_lightning_tpu.serve import engine as engine_mod

    eng = _tiny_engine(trace_dir=str(tmp_path))
    eng.generate(list(range(1, 9)), 30)
    real, calls = engine_mod._fault_fire, [0]

    def hook(point, **kw):
        real(point, **kw)
        calls[0] += point == "replica_tick"
        if calls[0] == 4 and point == "replica_tick":
            time.sleep(0.3)

    monkeypatch.setattr(engine_mod, "_fault_fire", hook)
    eng.generate(list(range(2, 10)), 8)
    eng.stop()
    [span] = [s for s in eng.tracer.events() if s.name == "stall"]
    [rec] = eng.stats.snapshot()["stalls"]
    assert span.args == rec and span.dur == rec["wall_us"] / 1e6
    assert span.ts == rec["t_ns"] / 1e9          # the tracer's wall clock


def test_the_longest_eight_stalls_are_kept(caplog):
    stats = serve_metrics.ServeStats()
    walls = [300, 900, 100, 1200, 500, 700, 1100, 200, 1000, 400, 800, 600]
    with caplog.at_level("WARNING", logger=serve_metrics.__name__):
        for wall in walls:
            stats.bump_many({"ticks_stalled": 1}, {
                "wall_us": wall * 1000, "phase": "emit",
                "verdict": "blocked", "phases": {"tick_emit_us": wall * 1000},
                "cpu_us": 0, "gc_us": 0, "invol_switches": 0,
                "vol_switches": 1})
    kept = [r["wall_us"] // 1000 for r in stats.snapshot()["stalls"]]
    assert kept == sorted(walls, reverse=True)[:serve_metrics.STALLS_KEPT]
    assert len(kept) == 8 and stats.counters["ticks_stalled"] == 12
    logged = [r.getMessage() for r in caplog.records]
    assert len(logged) == 12 and all("verdict blocked" in m for m in logged)


@pytest.mark.parametrize("wall,cpu,gc_us,invol,word", [
    (1_000_000, 20_000, 0, 0, "blocked"),
    (1_000_000, 300_000, 0, 0, "blocked"),       # mostly off the CPU
    (1_000_000, 20_000, 0, 3, "preempted"),
    (1_000_000, 700_000, 0, 9, "running"),       # switched out at its slice
    (1_000_000, 900_000, 600_000, 0, "collector"),
])
def test_stall_verdicts(wall, cpu, gc_us, invol, word):
    assert serve_metrics.stall_verdict(wall, cpu, gc_us, invol) == word


def test_a_wait_on_the_device_stalls_only_past_a_second():
    """A healthy long prefill (hundreds of milliseconds of
    ``admit_wait`` where the median is tens) trips nothing; 1.5 s
    does, and two admissions of 0.8 s in one iteration do not."""
    watch = serve_metrics.LoopWatch()

    def turn(admit_wait_us, admissions=1):
        tick = {"tick_admit_wait_us": admit_wait_us, "tick_emit_us": 2000,
                "tick_decode_wait_us": 10_000}
        return watch.turn(tick, 12_000 + admit_wait_us, False, 4,
                          [512] * admissions, True, True)

    try:
        for _ in range(40):
            assert turn(40_000) is None
        assert turn(600_000) is None
        assert turn(1_600_000, admissions=2) is None
        rec = turn(1_500_000)
        assert rec is not None and rec["phase"] == "admit_wait"
        assert rec["before"]["phases"]["tick_admit_wait_us"] == 1_600_000
        # A burst (the closed loop's start: 31 admissions, the decode
        # dispatched behind their prefills) is 32 dispatches' work.
        tick = {"tick_admit_dispatch_us": 116_000, "tick_emit_us": 3000,
                "tick_decode_dispatch_us": 1_549_000,
                "tick_admit_wait_us": 13_000}
        assert watch.turn(tick, 1_681_000, False, 32, [512] * 31, 0, 0) is None
        # An iteration in which a program compiled is never a stall.
        tick = {"tick_admit_dispatch_us": 5_000_000}
        assert watch.turn(tick, 5_000_000, True, 4, [512], 0, 0) is None
        assert "ticks_stalled" not in tick and tick["tick_cpu_us"] >= 0
    finally:
        watch.close()


def test_tier_off_installs_none_of_it(monkeypatch):
    """``RLT_TELEMETRY=off``: no collector hook, no ``getrusage``, no
    thread clock, no record; the phases (``between`` among them) count
    as they do at every tier."""
    def never(*a):
        raise AssertionError("read at tier off")

    monkeypatch.setenv("RLT_TELEMETRY", "off")
    monkeypatch.setattr(serve_metrics.resource, "getrusage", never)
    monkeypatch.setattr(serve_metrics.time, "thread_time_ns", never)
    hooks = list(gc.callbacks)
    eng = _tiny_engine()
    assert eng._watch is None
    assert not [h for h in gc.callbacks if h not in hooks]
    eng.generate(list(range(1, 9)), 6)
    eng.stop()
    counters = eng.stats.counters
    assert counters["tick_between_us"] > 0
    assert sum(counters[f"tick_{p}_us"] for p in TICK) == pytest.approx(
        counters["tick_us"], rel=0.02)
    for name in ("tick_cpu_us", "tick_vol_switches", "gc_collections",
                 "ticks_stalled"):
        assert counters[name] == 0
    assert "stalls" not in eng.stats.snapshot()


def test_the_default_tier_installs_one_hook_and_stop_removes_it():
    hooks = list(gc.callbacks)      # earlier engines' may go meanwhile
    eng = _tiny_engine()
    [hook] = [h for h in gc.callbacks if h not in hooks]
    eng.submit(list(range(1, 9)), 6)
    eng.step()
    gc.collect()                                 # in the loop's ``between``
    eng.run_until_idle(max_steps=50)
    eng.stop()
    assert hook not in gc.callbacks
    counters = eng.stats.counters
    assert counters["gc_collections"] >= 1
    assert counters["gc_us"] >= counters["gc_gen2_us"] > 0
    assert counters["tick_cpu_us"] > 0


def test_an_unstopped_engines_hook_goes_with_it():
    hooks = list(gc.callbacks)
    watch = serve_metrics.LoopWatch()
    [hook] = [h for h in gc.callbacks if h not in hooks]
    del watch
    gc.collect()
    assert hook not in gc.callbacks


def test_closing_a_turn_costs_microseconds():
    """What the default tier adds to an iteration: two clock reads, one
    ``getrusage``, the medians.  The bound is the phases' own (a loaded
    CI host); on the chip it is measured in pairs (PERF.md)."""
    watch = serve_metrics.LoopWatch()
    try:
        n = 5000
        t0 = time.perf_counter()
        for _ in range(n):
            tick = {"tick_decode_wait_us": 5000, "tick_emit_us": 1500,
                    "tick_between_us": 10}
            assert watch.turn(tick, 6510, False, 16, (), True, True) is None
        per_us = (time.perf_counter() - t0) / n * 1e6
    finally:
        watch.close()
    assert per_us < 25, f"{per_us:.1f} us a turn"


# ---------------------------------------------------------------------------
# a prefill program a bucket, by name
# ---------------------------------------------------------------------------

class _Lowered(Exception):
    pass


def _family_engine(family):
    from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
    from utils import tiny_family, tiny_gpt

    if family == "gpt":
        return ServeEngine(*tiny_gpt(),
                           ServeConfig(num_slots=4, block_size=8))
    from ray_lightning_tpu.models import exaone_moe, sarvam_mla

    preset, Module, gains = {
        "exaone_moe": (exaone_moe.exaone_moe_tiny, exaone_moe.ExaoneMoE,
                       ("q_norm",)),
        "sarvam_mla": (sarvam_mla.sarvam_mla_tiny, sarvam_mla.SarvamMLA,
                       ("q_norm",)),
    }[family]
    _, module, params = tiny_family(preset, Module, gains)
    return ServeEngine(module, params, ServeConfig(
        num_slots=4, block_size=4, max_model_len=64))


@pytest.mark.parametrize("family", ["gpt", "exaone_moe", "sarvam_mla"])
def test_each_prefill_bucket_lowers_under_its_own_name(family):
    """``jit__prefill_b<bucket>``: a device trace tells the buckets
    apart, ``^jit__prefill`` still matches them all, and the program
    ledger keeps one site."""
    eng = _family_engine(family)
    real, seen = eng._prefill_fn, {}

    def lower_only(*args):
        bucket = args[2].shape[0]
        seen[bucket] = real.lower(*args).as_text().split("\n", 1)[0]
        raise _Lowered

    eng._prefill_fn = lower_only
    for prompt_len in (5, 19):                   # two buckets
        eng.submit(list(range(1, prompt_len + 1)), 2)
        with pytest.raises(_Lowered):
            eng.step()
    eng.stop()
    assert len(seen) == 2 and real.site == "serve/prefill"
    for bucket, head in seen.items():
        assert f"@jit__prefill_b{bucket} " in head, head
    assert sorted(real._named) == sorted(
        f"_prefill_b{bucket}" for bucket in seen)


def test_prefills_count_their_buckets_and_their_prompts(ticked):
    _, counters = ticked
    # Six prompts of 5..10 tokens: 8 + 8 + 8 + 8 + 16 + 16 positions.
    assert counters["prefills"] == 6
    assert counters["prefill_prompt_positions"] == sum(range(5, 11))
    assert counters["prefill_bucket_positions"] == 4 * 8 + 2 * 16


# ---------------------------------------------------------------------------
# the same names on the profiler's clock
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def host_events(tmp_path_factory):
    """A real ``jax.profiler`` session around a tiny engine run and a
    tiny fit: ``{name: [(start_ns, end_ns, stats)]}`` of the host
    plane, and the session's own interval (an annotation opened right
    after the start and closed right before the stop)."""
    from ray_lightning_tpu.models import BoringDataModule, BoringModel
    from utils import get_trainer

    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    eng = _tiny_engine()
    eng.generate([1, 2, 3], 2)                   # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test:session"):
            eng.generate([4, 5, 6, 7], 3)
            get_trainer(
                max_epochs=1, tmp_path=trace_dir, limit_val_batches=0,
                enable_checkpointing=False,
            ).fit(BoringModel(), BoringDataModule())
    finally:
        jax.profiler.stop_trace()
        eng.stop()
    [path] = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("rlt:", "test:")):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return events


@pytest.mark.parametrize("name", [
    "rlt:serve/decode_wait", "rlt:serve/admit_dispatch", "rlt:serve/emit",
    "rlt:train/data_wait", "rlt:train/callbacks", "rlt:fit/result_package",
    "rlt:compile",
])
def test_phase_is_on_the_profilers_host_plane(host_events, name):
    [(lo, hi, _)] = host_events["test:session"]
    assert name in host_events, sorted(host_events)
    for start, end, _ in host_events[name]:
        assert lo <= start <= end <= hi          # the session's clock


def test_phase_arguments_are_the_annotations_stats(host_events):
    [(_, _, stats)] = host_events["rlt:serve/admit_dispatch"]
    assert int(stats["prompt_len"]) == 4 and "rid" in stats
    sites = {s["site"] for _, _, s in host_events["rlt:compile"]}
    assert any(site.startswith("train/") for site in sites), sites


# ---------------------------------------------------------------------------
# the kernels' names at their call sites
# ---------------------------------------------------------------------------

def _pallas_call_names(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            kw = {k.arg: k.value for k in node.keywords}
            name = kw.get("name")
            # One call site may serve two kernels, a literal name each.
            names = ([name.body, name.orelse]
                     if isinstance(name, ast.IfExp) else [name])
            out += [n.value if isinstance(n, ast.Constant) else None
                    for n in names]
    return out


KERNELS = {
    "flash_attention.py": {"rlt_flash_fwd", "rlt_flash_bwd"},
    "cross_entropy.py": {"rlt_ce_fwd", "rlt_ce_bwd_dx", "rlt_ce_bwd_dw"},
    "layer_norm.py": {"rlt_ln_fwd", "rlt_ln_bwd"},
    "lora.py": {"rlt_lora_bgmv"},
    "paged_attention.py": {"rlt_paged_decode", "rlt_ring_decode",
                           "rlt_mla_decode"},
    "moe.py": {"rlt_moe_gate_up", "rlt_moe_down"},
    "gated_delta.py": {"rlt_gdn_decode"},
}


@pytest.mark.parametrize("fname", sorted(KERNELS))
def test_every_pallas_call_carries_its_name(fname):
    names = _pallas_call_names(os.path.join(OPS_DIR, fname))
    assert None not in names, f"{fname}: a pallas_call without name="
    assert len(names) == len(set(names))
    assert set(names) == KERNELS[fname]


def test_no_unnamed_kernel_anywhere_under_ops():
    seen = []
    for path in sorted(glob.glob(os.path.join(OPS_DIR, "*.py"))):
        seen += _pallas_call_names(path)
    assert sorted(seen) == sorted(set().union(*KERNELS.values()))

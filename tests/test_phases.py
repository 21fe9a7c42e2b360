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
import glob
import os
import time

import jax
import pytest

from ray_lightning_tpu.telemetry import PHASES, SpanTracer, Telemetry
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
            out.append(name.value if isinstance(name, ast.Constant)
                       else None)
    return out


KERNELS = {
    "flash_attention.py": {"rlt_flash_fwd", "rlt_flash_bwd"},
    "cross_entropy.py": {"rlt_ce_fwd", "rlt_ce_bwd_dx", "rlt_ce_bwd_dw"},
    "layer_norm.py": {"rlt_ln_fwd", "rlt_ln_bwd"},
    "lora.py": {"rlt_lora_bgmv"},
    "paged_attention.py": {"rlt_paged_decode", "rlt_mla_decode"},
    "moe.py": {"rlt_moe_gate_up", "rlt_moe_down"},
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

"""Request-scoped distributed tracing across the serving + MPMD planes.

Contract under test: a TraceContext born at the router (trace_id ==
rid) rides every wire frame, worker/replica spans parent to it across
processes (``SpanTracer.start_remote``), and the per-component JSONL
exports stitch into ONE timeline (``telemetry/trace_collect.py``) with
a complete ``queue_wait → … → first_token`` phase chain per completed
request; a failover hop shows as a span LINKED under the request root;
recompute-preemption re-emissions share the original trace_id; MPMD
step spans share one trace_id fleet-wide; and with tracing off nothing
is installed (byte-identical snapshots, no files).
"""

import json
import os
import queue as _pyqueue
import sys
import time

import numpy as np
import pytest

from ray_lightning_tpu.telemetry import propagate, trace_collect
from ray_lightning_tpu.telemetry.schema import (
    validate_chrome_trace, validate_serve_request, validate_serve_snapshot,
    validate_span_jsonl, validate_trace_context,
)
from ray_lightning_tpu.telemetry.spans import SpanTracer

pytestmark = pytest.mark.trace


# ---------------------------------------------------------------------------
# jax-free units: propagation, start_remote, outbox, stitcher
# ---------------------------------------------------------------------------

class TestPropagate:
    def test_root_span_id_is_derived(self):
        ctx = propagate.root_context("abc")
        assert ctx.trace_id == "abc"
        assert ctx.span_id == "abc.root"
        assert ctx.parent_span_id is None
        # Any process that knows the trace id agrees on the root.
        assert propagate.root_context("abc").span_id == ctx.span_id

    def test_child_parents_to_caller(self):
        root = propagate.root_context("abc")
        child = propagate.child_context(root)
        assert child.trace_id == "abc"
        assert child.parent_span_id == root.span_id
        assert child.span_id != root.span_id

    def test_inject_extract_roundtrip(self):
        ctx = propagate.child_context(propagate.root_context("r1"))
        item = propagate.inject({"type": "x"}, ctx)
        assert validate_trace_context(item["trace"]) == []
        assert propagate.extract(item) == ctx
        assert propagate.sent_ts(item) == pytest.approx(
            time.time(), abs=5.0
        )

    def test_inject_none_is_noop_and_extract_tolerant(self):
        item = {"type": "x"}
        assert propagate.inject(item, None) is item
        assert "trace" not in item
        # Old/malformed producers must never fail the consumer.
        assert propagate.extract({"trace": "garbage"}) is None
        assert propagate.extract({"trace": {"span_id": "x"}}) is None
        assert propagate.extract(b"bytes") is None

    def test_request_fields_carry_trace(self):
        from ray_lightning_tpu.serve.dist.handoff import request_fields

        ctx = propagate.root_context("rid9")
        req = request_fields("rid9", [1, 2], 4, reply=("h", 1),
                             sample_seed=0, trace=ctx)
        assert validate_serve_request(req) == []
        assert propagate.extract(req) == ctx
        # Untraced producers emit the pre-tracing wire shape.
        bare = request_fields("rid9", [1, 2], 4, reply=("h", 1),
                              sample_seed=0)
        assert "trace" not in bare


class TestStartRemote:
    def test_remote_parent_nesting(self):
        tracer = SpanTracer(enabled=True, clock=time.time)
        root = propagate.root_context("t1")
        with tracer.start_remote(root, "prefill_compute",
                                 rid="t1") as outer:
            assert outer.ctx.parent_span_id == root.span_id
            with tracer.start_remote(outer.ctx, "handoff_send") as inner:
                assert inner.ctx.parent_span_id == outer.ctx.span_id
        spans = tracer.events()
        assert [s.name for s in spans] == ["handoff_send",
                                           "prefill_compute"]
        by_name = {s.name: s.args for s in spans}
        assert by_name["prefill_compute"]["trace_id"] == "t1"
        assert (by_name["handoff_send"]["parent_span_id"]
                == by_name["prefill_compute"]["span_id"])
        # Nesting depth tracked like plain spans.
        assert spans[0].depth == 1 and spans[1].depth == 0

    def test_disabled_or_contextless_is_noop(self):
        tracer = SpanTracer(enabled=False, clock=time.time)
        with tracer.start_remote(propagate.root_context("x"), "a") as sp:
            assert sp.ctx is None
        enabled = SpanTracer(enabled=True, clock=time.time)
        with enabled.start_remote(None, "a") as sp:
            assert sp.ctx is None
        assert tracer.events() == [] and enabled.events() == []

    def test_wall_clock_exports_validate(self, tmp_path):
        tracer = SpanTracer(enabled=True, clock=time.time)
        with tracer.span("queue_wait"):
            pass
        assert tracer.events()[0].ts == pytest.approx(time.time(),
                                                      abs=5.0)
        path = tmp_path / "trace-x.jsonl"
        tracer.export_jsonl(str(path))
        assert validate_span_jsonl(
            path.read_text().splitlines()) == []


class TestMemberOutbox:
    def test_sends_and_on_sent_fires(self):
        from ray_lightning_tpu.cluster.queue import DriverQueue
        from ray_lightning_tpu.serve.dist.handoff import MemberOutbox

        q = DriverQueue()
        sent = []
        box = MemberOutbox((q.handle.host, q.handle.port))
        try:
            box.put({"type": "x", "n": 1}, on_sent=sent.append)
            item = q.get(timeout=5)
            assert item["n"] == 1
            deadline = time.monotonic() + 2
            while not sent and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(sent) == 1  # fired after the wire write
        finally:
            box.close()
            q.shutdown()

    def test_dead_peer_reports_once_and_put_raises(self):
        from ray_lightning_tpu.cluster.queue import DriverQueue
        from ray_lightning_tpu.serve.dist.handoff import MemberOutbox

        q = DriverQueue()
        addr = (q.handle.host, q.handle.port)
        q.shutdown()  # nothing listens: the dead-member shape
        errors = []
        box = MemberOutbox(addr, on_error=errors.append)
        try:
            try:
                box.put({"type": "x"})
            except ConnectionError:
                pass  # racing the error report is fine
            deadline = time.monotonic() + 10
            while not errors and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(errors) == 1
            with pytest.raises(ConnectionError):
                box.put({"type": "x"})  # dead outbox refuses
        finally:
            box.close()

    def test_full_queue_is_backpressure(self):
        from ray_lightning_tpu.serve.dist.handoff import MemberOutbox

        box = MemberOutbox.__new__(MemberOutbox)
        box.addr = ("127.0.0.1", 1)
        box._on_error = None
        box._q = _pyqueue.Queue(maxsize=1)
        box._dead = False
        import threading

        box._closed = threading.Event()
        box._q.put_nowait(({"type": "x"}, None, 0.0))
        with pytest.raises(ConnectionError, match="full"):
            box.put({"type": "y"})


class TestTraceCollect:
    def _span(self, name, ts, dur, src, trace_id, span_id,
              parent=None, **extra):
        args = {"trace_id": trace_id, "span_id": span_id, **extra}
        if parent is not None:
            args["parent_span_id"] = parent
        return {"name": name, "ts": ts, "dur": dur, "rank": 0,
                "tid": 1, "depth": 0, "args": args, "_src": src}

    def _request_spans(self, rid, routed=True, handoff=True,
                       status="finished"):
        root = f"{rid}.root"
        spans = [
            self._span("request", 0.0, 1.0, "router", rid, root,
                       status=status),
            self._span("queue_wait", 0.1, 0.01, "serve-r0", rid, "q1",
                       parent=root),
            self._span("first_token", 0.5, 0.01, "serve-r0", rid, "f1",
                       parent=root),
        ]
        if routed:
            spans.append(self._span("placement", 0.05, 0.01, "router",
                                    rid, "p1", parent=root))
        if handoff:
            spans += [
                self._span("prefill_compute", 0.2, 0.1, "prefill-p0",
                           rid, "pf1", parent=root),
                self._span("handoff_transfer", 0.3, 0.05, "serve-r0",
                           rid, "h1", parent="pf1"),
                self._span("decode_admission", 0.35, 0.1, "serve-r0",
                           rid, "d1", parent=root),
            ]
        else:
            spans.append(self._span("prefill_compute", 0.2, 0.1,
                                    "serve-r0", rid, "pf1",
                                    parent=root))
        return spans

    def test_coverage_complete_and_incomplete(self):
        spans = self._request_spans("a") + self._request_spans("b")
        complete, total, frac = trace_collect.coverage(spans)
        assert (complete, total, frac) == (2, 2, 1.0)
        # Drop b's decode_admission while keeping its handoff leg: the
        # import never landed, so the chain is incomplete.
        broken = [s for s in spans
                  if not (s["args"]["trace_id"] == "b"
                          and s["name"] == "decode_admission")]
        complete, total, frac = trace_collect.coverage(broken)
        assert (complete, total) == (1, 2)

    def test_coverage_requires_placement_only_when_routed(self):
        solo = self._request_spans("a", routed=False, handoff=False)
        assert trace_collect.coverage(solo)[2] == 1.0
        # A routed corpus holds every trace to the placement leg.
        mixed = (self._request_spans("a", routed=False, handoff=False)
                 + self._request_spans("b"))
        complete, total, _ = trace_collect.coverage(mixed)
        assert (complete, total) == (1, 2)

    def test_expired_requests_not_counted(self):
        spans = self._request_spans("a") + [
            self._span("request", 0.0, 0.1, "router", "x", "x.root",
                       status="expired"),
        ]
        assert trace_collect.coverage(spans) == (1, 1, 1.0)

    def test_stitch_emits_cross_process_arrows(self):
        spans = self._request_spans("a")
        doc = trace_collect.stitch_chrome(spans)
        assert validate_chrome_trace(doc) == []
        flows = [e for e in doc["traceEvents"] if e.get("ph") == "s"]
        # handoff_transfer (serve-r0) parents to pf1 (prefill-p0), and
        # the replica/worker spans parent to the router root — every
        # cross-source link gets an arrow.
        assert len(flows) >= 4
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M"}
        assert {"router", "serve-r0", "prefill-p0"} <= names

    def test_phase_percentiles_and_report(self):
        spans = self._request_spans("a") + self._request_spans("b")
        pct = trace_collect.phase_percentiles(spans)
        assert pct["queue_wait"]["n"] == 2
        assert set(pct["queue_wait"]) == {"n", "p50_ms", "p95_ms"}
        assert trace_collect.coverage(spans)[2] == 1.0
        report = trace_collect.format_report(spans)
        assert "chain coverage 2/2" in report
        assert "prefill_compute" in report

    def test_critical_path_reports_failover(self):
        spans = self._request_spans("a")
        spans.append(self._span("failover", 0.4, 0.0, "router", "a",
                                "fo1", parent="a.root",
                                from_replica="r0"))
        paths = trace_collect.slowest_requests(spans, 1)
        assert paths[0]["failovers"][0]["from_replica"] == "r0"

    def test_mpmd_step_report_groups_workers(self):
        tid = "mpmd-x-s0"
        spans = [
            self._span("mpmd_step", 0.0, 1.0, "mpmd-stage0", tid,
                       f"{tid}.root", step=0, worker=0),
            self._span("fwd", 0.1, 0.2, "mpmd-stage0", tid, "s1",
                       parent=f"{tid}.root", step=0, worker=0,
                       blocked_s=0.0),
            self._span("recv_act", 0.1, 0.3, "mpmd-stage1", tid, "s2",
                       parent=f"{tid}.w1", step=0, worker=1,
                       blocked_s=0.25),
        ]
        report = trace_collect.mpmd_step_report(spans)
        assert len(report) == 1
        workers = report[0]["workers"]
        assert workers["0"]["compute_s"] == pytest.approx(0.2)
        assert workers["1"]["blocked_s"] == pytest.approx(0.25)
        # MPMD traces never leak into the serve request grouping.
        assert trace_collect.request_traces(spans) == {}


# ---------------------------------------------------------------------------
# jax-backed: engine, fleet, MPMD end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    from utils import tiny_gpt

    return tiny_gpt()


def _serve_cfg(**kw):
    from ray_lightning_tpu.serve.engine import ServeConfig

    kw.setdefault("num_slots", 2)
    kw.setdefault("block_size", 8)
    return ServeConfig(**kw)


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128,
                         size=(int(rng.integers(3, 14)),)).tolist()
            for _ in range(n)]


class TestEngineTracing:
    def test_off_by_default_installs_nothing(self, model, tmp_path):
        from ray_lightning_tpu.serve.engine import ServeEngine

        m, params = model
        eng = ServeEngine(m, params, _serve_cfg())
        try:
            assert not eng.tracer.enabled
            eng.generate([1, 2, 3], 4)
            snap = eng.snapshot()
            assert "phases" not in snap  # byte-identical to pre-trace
            assert eng.scheduler.queue == eng.scheduler.queue  # alive
        finally:
            eng.stop()
        assert list(tmp_path.iterdir()) == []

    def test_monolith_trace_chain_and_phase_stats(self, model,
                                                  tmp_path):
        from ray_lightning_tpu.serve.engine import ServeEngine
        from ray_lightning_tpu.telemetry.export_prom import (
            render_openmetrics,
        )

        m, params = model
        eng = ServeEngine(m, params, _serve_cfg(),
                          trace_dir=str(tmp_path), trace_name="mono")
        eng.generate([1, 2, 3, 4], 6)
        snap = eng.snapshot()
        assert validate_serve_snapshot(snap) == []
        assert {"queue_wait", "prefill_compute",
                "first_token"} <= set(snap["phases"])
        text = render_openmetrics({"serve": snap})
        assert 'rlt_serve_phase_latency_ms{phase="queue_wait"' in text
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        "..", "tools"))
        import rlt_top

        frame = rlt_top.render({"ts": snap["ts"], "serve": snap}, "x")
        assert "phases:" in frame and "queue_wait" in frame
        eng.stop()
        spans = trace_collect.load_trace_dir(str(tmp_path))
        assert trace_collect.coverage(spans) == (1, 1, 1.0)

    def test_preemption_reemission_shares_trace_id(self, model,
                                                   tmp_path):
        """Recompute preemption: the replayed admission's spans land in
        the ORIGINAL trace (queue_wait appears once per admission, same
        trace_id)."""
        from ray_lightning_tpu.serve.engine import ServeEngine

        m, params = model
        eng = ServeEngine(
            m, params,
            _serve_cfg(num_slots=2, block_size=4, num_blocks=8,
                       max_model_len=24),
            trace_dir=str(tmp_path), trace_name="preempt",
        )
        h1 = eng.submit([3, 1, 4, 1], 16)
        h2 = eng.submit([2, 7, 1], 16)
        eng.run_until_idle()
        assert h1.result(5) and h2.result(5)
        assert eng.snapshot()["counters"]["preempted"] >= 1
        eng.stop()
        spans = trace_collect.load_trace_dir(str(tmp_path))
        groups = trace_collect.request_traces(spans)
        assert len(groups) == 2  # re-emission created NO new trace
        preempted = [
            g for g in groups.values()
            if sum(1 for s in g if s["name"] == "queue_wait") >= 2
        ]
        assert preempted, "no trace carries the re-admission"
        assert trace_collect.coverage(spans)[2] == 1.0


class TestFleetTracing:
    def test_inproc_fleet_full_chain_stitch(self, model, tmp_path):
        """The acceptance shape: disaggregated fleet, every completed
        request stitches a complete queue_wait → placement →
        prefill_compute → handoff_transfer → decode_admission →
        first_token chain across router/worker/replica exports."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet

        m, params = model
        trace_dir = str(tmp_path / "tel")
        # lost_after_s effectively OFF: under full-suite load on this
        # container the beat threads can starve past the 1s default,
        # and a spuriously "dead" prefill worker makes the router fall
        # back to direct submission — correct router behavior, but it
        # would turn this test's all-six-legs assertion flaky.  Death
        # detection has its own test below.
        fleet = launch_inproc_fleet(m, params, _serve_cfg(),
                                    n_replicas=2, n_prefill=1,
                                    lost_after_s=30.0,
                                    trace_dir=trace_dir)
        client = ServeClient(fleet.queue_handle())
        n = 6
        try:
            rids = [client.submit(p, 6) for p in _prompts(n)]
            for rid in rids:
                client.result(rid, timeout=120)
            deadline = time.monotonic() + 10
            while (fleet.router.snapshot()["counters"]["completed"] < n
                   and time.monotonic() < deadline):
                time.sleep(0.05)
        finally:
            client.close()
            fleet.close()
        spans = trace_collect.load_trace_dir(trace_dir)
        complete, total, frac = trace_collect.coverage(spans)
        assert total == n and frac == 1.0
        # Every chain carries every leg of the disagg topology.
        for rid, group in trace_collect.request_traces(spans).items():
            names = {p for p, _, _ in trace_collect.chain_for(group)}
            assert names == {"queue_wait", "placement",
                             "prefill_compute", "handoff_transfer",
                             "decode_admission", "first_token"}, (
                rid, names)
        # Stitch: one Perfetto doc, arrows crossing components.
        doc = trace_collect.stitch_chrome(spans)
        assert validate_chrome_trace(doc) == []
        assert any(e.get("ph") == "s" for e in doc["traceEvents"])

    def test_trace_stitch_cli_smoke(self, model, tmp_path):
        from ray_lightning_tpu.serve.engine import ServeEngine

        m, params = model
        trace_dir = str(tmp_path)
        eng = ServeEngine(m, params, _serve_cfg(),
                          trace_dir=trace_dir, trace_name="cli")
        eng.generate([5, 6, 7], 4)
        eng.stop()
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        "..", "tools"))
        import trace_stitch

        assert trace_stitch.main([trace_dir]) == 0
        merged = os.path.join(trace_dir, "trace-merged.json")
        with open(merged) as f:
            assert validate_chrome_trace(json.load(f)) == []
        # router-live.json discovery: any file inside the dir works.
        marker = os.path.join(trace_dir, "router-live.json")
        with open(marker, "w") as f:
            json.dump({"ts": 0}, f)
        assert trace_stitch.main([marker, "--no-report"]) == 0
        # An empty dir is a loud no-spans exit, not a crash.
        empty = tmp_path / "empty"
        empty.mkdir()
        assert trace_stitch.main([str(empty)]) == 1

    def test_failover_hop_is_linked_span(self, model, tmp_path):
        """A replica death mid-stream: the re-routed request's trace
        shows the failover hop as a span linked under the request root,
        and the survivor's spans land in the SAME trace."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet

        m, params = model
        trace_dir = str(tmp_path / "tel")
        # The death below is a hard kill, which an in-process fleet sees
        # at once (``handle.is_alive()``), not by beat age: lost_after_s
        # only has to stay clear of the beat threads' starvation under
        # full-suite load while both replicas compile (at 0.5 s both
        # were once read as lost, 0.7 s after their last beat, and the
        # request never streamed).
        fleet = launch_inproc_fleet(m, params, _serve_cfg(),
                                    n_replicas=2, n_prefill=0,
                                    lost_after_s=5.0,
                                    trace_dir=trace_dir)
        client = ServeClient(fleet.queue_handle())
        try:
            r1 = client.submit(list(range(1, 9)), 30)
            r2 = client.submit(list(range(9, 17)), 30)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                track = fleet.router._inflight.get(r1)
                if (track is not None and track.replica is not None
                        and len(client._pending[r1].tokens) >= 3):
                    victim = track.replica
                    break
                time.sleep(0.01)
            else:
                pytest.fail("request never started streaming")
            next(r for r in fleet.replicas
                 if r.id == victim).kill(hard=True)
            out1 = client.result(r1, timeout=120)
            assert out1
            client.result(r2, timeout=120)
            assert fleet.router.counters["failovers"] >= 1
            deadline = time.monotonic() + 10
            while (r1 in fleet.router._inflight
                   and time.monotonic() < deadline):
                time.sleep(0.05)
        finally:
            client.close()
            fleet.close()
        spans = trace_collect.load_trace_dir(trace_dir)
        groups = trace_collect.request_traces(spans)
        failed_over = groups[r1]
        hops = [s for s in failed_over if s["name"] == "failover"]
        assert hops, "failover hop missing from the trace"
        assert hops[0]["args"]["parent_span_id"] == f"{r1}.root"
        assert hops[0]["args"]["from_replica"] == victim
        # The replay genuinely crossed replicas within ONE trace: the
        # request's engine-side spans come from two distinct exports.
        engine_srcs = {s["_src"] for s in failed_over
                       if s["name"] == "queue_wait"}
        assert len(engine_srcs) == 2
        # Both placements (original + failover re-route) recorded.
        placements = [s for s in failed_over
                      if s["name"] == "placement"]
        assert len(placements) >= 2

    def test_untraced_fleet_writes_nothing(self, model, tmp_path):
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet

        m, params = model
        fleet = launch_inproc_fleet(m, params, _serve_cfg(),
                                    n_replicas=1, n_prefill=1)
        client = ServeClient(fleet.queue_handle())
        try:
            rid = client.submit([1, 2, 3], 4)
            client.result(rid, timeout=120)
            assert not fleet.router.tracer.enabled
        finally:
            client.close()
            fleet.close()
        assert trace_collect.load_trace_dir(str(tmp_path)) == []


class TestMpmdTracing:
    @pytest.mark.slow  # tier-1 diet (round 20): ~7s 2-worker pipeline
    # fit; the strategy trace-dir unit + untraced-runner pin stay in
    # tier-1, the stitched-timeline fit runs via -m slow
    def test_two_worker_stitched_step_timeline(self, tmp_path):
        """In-proc 2-worker pipeline: both workers' instruction spans
        share one step trace (minted on the embed worker, adopted from
        the wire downstream), and the report decomposes compute vs
        blocked-recv per worker per step."""
        import jax

        from ray_lightning_tpu.models.gpt import GPT, GPTConfig
        from ray_lightning_tpu.mpmd.inproc import run_inproc_pipeline_fit
        from ray_lightning_tpu.mpmd.plan import _gpt_untie, gpt_mpmd_spec

        cfg = GPTConfig(vocab_size=32, n_layer=2, n_head=2, d_model=16,
                        seq_len=8, warmup_steps=2)
        module = GPT(cfg, attn_impl="xla")
        module.precision = "f32"
        spec = gpt_mpmd_spec(module)
        full = _gpt_untie(module.init_params(jax.random.PRNGKey(0)))
        rng = np.random.default_rng(7)
        steps = 2
        data = [
            {"tokens": rng.integers(
                0, cfg.vocab_size,
                (8, cfg.seq_len + 1)).astype(np.int32)}
            for _ in range(steps)
        ]
        trace_dir = str(tmp_path)
        res = run_inproc_pipeline_fit(
            spec, full, spec.tx_factory, lambda s: data[s], steps,
            n_workers=2, n_micro=4, schedule="1f1b",
            trace_dir=trace_dir,
        )
        assert len(res["losses"]) == steps
        files = sorted(os.listdir(trace_dir))
        assert files == ["trace-mpmd-stage0.jsonl",
                         "trace-mpmd-stage1.jsonl"]
        spans = trace_collect.load_trace_dir(trace_dir)
        report = trace_collect.mpmd_step_report(spans)
        assert len(report) == steps
        for entry in report:
            assert set(entry["workers"]) == {"0", "1"}
            w1 = entry["workers"]["1"]
            # The downstream worker's warmup waits ARE its bubble.
            assert w1["blocked_s"] >= 0.0
        # Worker 1's step span links under worker 0's root.
        tid = report[0]["trace_id"]
        stage_steps = [s for s in spans
                       if s["name"] == "mpmd_stage_step"
                       and s["args"]["trace_id"] == tid]
        assert stage_steps
        assert (stage_steps[0]["args"]["parent_span_id"]
                == f"{tid}.root")
        # Stitches into one valid Perfetto doc with flow arrows.
        doc = trace_collect.stitch_chrome(spans)
        assert validate_chrome_trace(doc) == []
        assert any(e.get("ph") == "s" for e in doc["traceEvents"])
        assert "mpmd" in trace_collect.format_report(spans)

    def test_mpmd_strategy_ships_trace_dir(self):
        """The actor path: MpmdStrategy carries the knob its task dict
        ships to `_stage_execute_remote` (None = off)."""
        from ray_lightning_tpu.parallel.strategies import MpmdStrategy

        s = MpmdStrategy(num_stages=2, devices_per_stage=1,
                         trace_dir="/tmp/rlt-trace-x")
        assert s.trace_dir == "/tmp/rlt-trace-x"
        assert MpmdStrategy(num_stages=2,
                            devices_per_stage=1).trace_dir is None

    def test_untraced_runner_unchanged(self):
        """No trace_dir: LocalChannel frames carry no envelope and the
        runner records nothing (wire compat with old producers)."""
        from ray_lightning_tpu.mpmd.transfer import LocalChannel, Mailbox

        box = Mailbox()
        LocalChannel(box).send("act", 0, 0, {"x": np.zeros(2)})
        payload, blocked, trace = box.recv_traced(("act", 0, 0, 0),
                                                  timeout=5)
        assert trace is None and blocked >= 0.0
        np.testing.assert_array_equal(payload["x"], np.zeros(2))

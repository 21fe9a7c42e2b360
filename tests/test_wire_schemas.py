"""Wire schemas: what crosses a process or machine boundary, built by its
REAL producer, must satisfy its validator in ``telemetry/schema.py``.

One case per ``validate_*`` name the schema module exports (plus the
committed flight-bundle fixture).  Each case hands back what the
producer made; the test sends it through the lane it rides (JSON, or
pickle for frames that carry raw bytes), expects no problem, then
removes a required field from a copy and expects a refusal.  A producer
that grows or loses a key without its schema fails here, in tier-1 and
in ``format.sh`` layer 4.
"""

import json
import os
import pickle
import time

import numpy as np
import pytest

from ray_lightning_tpu.mpmd.transfer import (
    QueueChannel, WireCodec, WireDtypeConfig,
)
from ray_lightning_tpu.parallel.strategies import MpmdStrategy
from ray_lightning_tpu.serve.capacity import CapacityOracle
from ray_lightning_tpu.serve.dist.handoff import (
    make_adapter_load_item, make_beat_item, make_handoff_item,
    make_hello_item, make_migration_item, request_fields,
)
from ray_lightning_tpu.serve.client import ServeClient
from ray_lightning_tpu.serve.dist.router import Router
from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
from ray_lightning_tpu.serve.metrics import LoopWatch, ServeStats
from ray_lightning_tpu.telemetry import schema, trace_collect
from ray_lightning_tpu.telemetry.flight_recorder import FlightRecorder
from ray_lightning_tpu.telemetry.heartbeat import make_beat
from ray_lightning_tpu.telemetry.logs import make_log_item
from ray_lightning_tpu.telemetry.monitor import make_event
from ray_lightning_tpu.telemetry.program_ledger import (
    ArgSig, ProgramLedger, ProgramRecord, Signature, diff_signatures,
)
from ray_lightning_tpu.telemetry.propagate import (
    child_context, extract, root_context,
)
from ray_lightning_tpu.telemetry.slo import SloEvaluator, default_serve_slos
from ray_lightning_tpu.telemetry.spans import SpanTracer
from ray_lightning_tpu.telemetry.timeseries import TimeSeriesStore

from test_serve import model  # noqa: F401 - the tiny GPT fixture

FIXTURE_BUNDLE = os.path.join(
    os.path.dirname(__file__), "data", "flight_bundle.json")
# Frames that carry raw bytes ride the pickled lane; the rest are JSON.
PICKLED = {"serve_kv_handoff", "serve_adapter_load", "serve_migration",
           "mpmd_xfer"}


class _Ctx:
    """Loop-context stand-in: the live-plane producers are duck-typed."""

    global_step = 3
    micro_step = 7
    current_epoch = 1
    progress = 9
    phase = "train"
    telemetry_dir = None


class _Stub:
    """Stands in for a queue handle (keeps what a channel puts on the
    wire) and for a fleet member's actor handle."""

    def __init__(self, member_id=None):
        self.id = member_id
        self.sent = []

    def put(self, item):
        self.sent.append(item)

    def is_alive(self):
        return True

    def close(self):
        pass

    kill = close


def _without(key, *path):
    """A mutation: drop ``key`` from the dict found by walking ``path``."""
    def mutate(item):
        node = item
        for step in path:
            node = node[step]
        del node[key]
        return item
    return mutate


def _tracer():
    tracer = SpanTracer(enabled=True, maxlen=64, rank=0)
    with tracer.span("outer", tag="wire"):
        with tracer.span("inner"):
            pass
    tracer.instant("marker", detail=1)
    return tracer


def _request(**kw):
    return request_fields("abc", [1, 2, 3], 8, reply=("127.0.0.1", 12345),
                          sample_seed=7, temperature=0.7, priority=1, **kw)


def _ledger():
    old = Signature(
        args=(ArgSig("state", "PyTreeDef({'p': *})",
                     (("['p']", (8,), "float32"),)),
              ArgSig("batch", "PyTreeDef(*)", (("", (4, 2), "float32"),))),
        statics=(), donate=(0,),
    )
    new = old._replace(args=(
        old.args[0]._replace(leaves=(("['p']", (16,), "float32"),)),
        old.args[1],
    ))
    reg = ProgramLedger()
    reg.record_program(
        ProgramRecord(site="train/step", variant=0,
                      signature="state:f32[8]|batch:f32[4,2]",
                      compile_s=0.25, backend="cpu", ncalls=3, flops=1.0e6,
                      bytes_accessed=2.0e6, argument_bytes=64,
                      output_bytes=32, temp_bytes=16),
        old,
    )
    reg.record_recompile("train/step", diff_signatures(old, new), variant=1)
    snap = reg.snapshot()
    assert snap["recompiles"][0]["argument"] == "state['p']"
    return snap


@pytest.fixture(scope="module")
def served(model):  # noqa: F811
    """One request through a real tiny engine and client: the client's
    frame, every reply the engine sent, and the engine's snapshot."""
    eng = ServeEngine(*model, ServeConfig(
        num_slots=1, block_size=8, coalesce_replies=True))
    replies = []
    send = eng._reply

    def spy(addr, item):
        replies.append(item)
        send(addr, item)

    eng._reply = spy
    client = ServeClient(eng.queue_handle())
    try:
        rid = client.submit([1, 2, 3], 3)
        request = dict(client._pending[rid].item)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and eng.step():
            pass
        eng.run_until_idle()
        assert len(client.result(rid, 10)) == 3
        return {"request": request, "replies": replies,
                "snapshot": eng.stats.snapshot()}
    finally:
        eng.stop()
        client.close()


# -- the cases: case_<validator>() -> (what the producer made, a mutation) ----

def case_span(tmp_path, served):
    tracer = _tracer()
    return [tracer._span_dict(s) for s in tracer.events()], _without("name")


def case_span_jsonl(tmp_path, served):
    path = str(tmp_path / "spans.jsonl")
    _tracer().export_jsonl(path)
    with open(path) as f:
        lines = f.readlines()

    def mutate(lines):
        first = json.loads(lines[0])
        del first["ts"]
        return [json.dumps(first)] + lines[1:]
    return [lines], mutate


def case_chrome_trace(tmp_path, served):
    remote = SpanTracer(enabled=True, rank=0, clock=time.time)
    with remote.start_remote(root_context("rid42"), "prefill_compute"):
        pass
    remote.export_jsonl(str(tmp_path / "trace-worker.jsonl"))
    stitched = trace_collect.stitch_chrome(
        trace_collect.load_trace_dir(str(tmp_path)))
    return [_tracer().chrome_trace(), stitched], _without("traceEvents")


def case_trace_context(tmp_path, served):
    root = root_context("rid42")
    req = _request(trace=root)
    assert extract(req) == root
    return [req["trace"]], _without("trace_id")


def case_heartbeat(tmp_path, served):
    return ([make_beat(rank=0, seq=1, ctx=_Ctx()),
             make_beat(rank=0, seq=2, ctx=_Ctx(), done=True)],
            _without("seq"))


def case_event(tmp_path, served):
    return [
        make_event("stall", 2, age_s=1.5, message="m"),
        make_event("drain", 0, message="m", ckpt="/tmp/drain.ckpt"),
        make_event("backoff", -1, delay_s=1.5, attempt=1, message="m"),
        make_event("elastic_restart", -1, attempt=1, recover_s=0.8,
                   ckpt="/tmp/restart.ckpt", message="m"),
        make_event("ckpt_corrupt", -1, ckpt="/tmp/bad.ckpt", message="m"),
        make_event("resize", -1, old_world=4, new_world=2, recover_s=3.2,
                   ckpt="/tmp/drain.ckpt", message="m"),
        make_event("resize_rejected", -1, old_world=4, new_world=0,
                   message="m"),
    ], _without("kind")


def case_log_item(tmp_path, served):
    return [make_log_item(0, "WARNING", "wire.test", "hello")], \
        _without("level")


def case_stream_item(tmp_path, served):
    """The raw queue stream's one entry point: every family it routes."""
    items = (case_heartbeat(tmp_path, served)[0]
             + case_event(tmp_path, served)[0]
             + case_log_item(tmp_path, served)[0]
             + _mpmd_live()["stages"])
    return items, _without("type")


def case_flight_bundle(tmp_path, served):
    rec = FlightRecorder(rank=0, out_dir=str(tmp_path), ctx=_Ctx())
    try:
        raise ValueError("wire-test crash")
    except ValueError as err:
        path = rec.record_crash(err)
    assert path is not None, "the recorder wrote nothing"
    with open(path) as f:
        return [json.load(f)], _without("schema")


def case_flight_bundle_fixture(tmp_path, served):
    with open(FIXTURE_BUNDLE) as f:
        return [json.load(f)], _without("traceback")


def case_program_row(tmp_path, served):
    return _ledger()["programs"], _without("site")


def case_recompile_record(tmp_path, served):
    return _ledger()["recompiles"], _without("argument")


def case_program_snapshot(tmp_path, served):
    return [_ledger()], _without("compile_s", "programs", 0)


def case_serve_request(tmp_path, served):
    return ([served["request"], _request(adapter="tenant0"),
             dict(_request(), hedge=True)], _without("reply"))


def case_serve_reply(tmp_path, served):
    kinds = {r["type"] for r in served["replies"]}
    inner = {i["type"] for r in served["replies"]
             for i in r.get("items", ())}
    assert {"serve_token", "serve_done"} <= kinds | inner, (kinds, inner)

    def mutate(item):
        target = item["items"][0] if item["type"] == "serve_batch" else item
        del target["rid"]
        return item
    return served["replies"], mutate


def case_serve_snapshot(tmp_path, served):
    stats = ServeStats()
    stats.bump("prefills")
    stats.bump("spec_drafted", 12)
    stats.bump("spec_accepted", 9)
    stats.note_adapter("tenant0", tokens=16, completed=1)
    stats.set_gauges(queue_depth=0, prefix_cache_hit_rate=0.5,
                     lora_fairness_spread=1.0, spec_acceptance_rate=0.75)
    stats.set_prefix(hit_rate=0.5, lookups=4, hits=2, blocks_claimed=4,
                     blocks_inserted=8, blocks_evicted=0, cached_blocks=6)
    watch = LoopWatch()     # a stalled iteration after four plain ones
    for wall in (900, 1000, 1100, 1000, 900_000):
        stall = watch.turn({"tick_emit_us": wall}, wall, False, 2, [16],
                           True, False)
    watch.close()
    stats.bump_many({}, stall)
    carried = stats.snapshot()
    assert carried["stalls"][0]["before"]["wall_us"] == 1000
    carried["capacity"] = _capacity()
    return [served["snapshot"], carried], _without("counters")


def case_serve_kv_handoff(tmp_path, served):
    req = _request(top_k=8, spec=2)
    return [
        make_handoff_item(req, bucket=16, data=b"\x00payload",
                          trace=child_context(root_context("abc"))),
        make_handoff_item(req, bucket=16, shm="/dev/shm/rlt-kv-1-abc"),
    ], _without("sample_seed", "req")


def case_serve_adapter_load(tmp_path, served):
    return [make_adapter_load_item("tenant0", 8, data=b"\x00factors"),
            make_adapter_load_item("tenant0", 8, shm="/dev/shm/rlt-kv-1")], \
        _without("rank")


def case_serve_migration(tmp_path, served):
    return [make_migration_item(_request(), generated=[5, 6], cur_token=6,
                                seq_len=4, data=b"\x00kv")], \
        _without("sample_seed", "req")


def case_router_snapshot(tmp_path, served):
    router = Router(lost_after_s=60.0)
    try:
        router.add_replica(_Stub("r0"))
        router.add_prefill(_Stub("p0"))
        beats = router.beat_handle  # the real wire: TCP loopback
        beats.put(make_hello_item(
            "decode", "r0", ("127.0.0.1", 1), num_slots=8, max_queue=64,
            spec_k=4, max_prompt_len=64, max_model_len=128, block_size=16,
            max_adapters=4))
        beats.put(make_hello_item(
            "prefill", "p0", ("127.0.0.1", 2), max_prompt_len=64,
            max_model_len=128, block_size=16))
        beats.put(make_beat_item(
            "decode", "r0", done=[("x", "finished")],
            snapshot=served["snapshot"], recompiles=12,
            adapters=["tenant0", "tenant1"]))
        router.poll()
        beats.close()
        return [router.snapshot()], _without("replicas")
    finally:
        router.stop()


def case_timeseries_point(tmp_path, served):
    store = TimeSeriesStore(interval_s=1.0, capacity=600, clock=lambda: 1040.0)
    for i in range(40):
        ts = 1000.0 + i
        store.observe("submitted", 10 * i, kind="counter", ts=ts)
        store.observe("queue_wait_p50_ms", 5.0 + i % 3, kind="gauge", ts=ts)
        store.observe("token_ms", 4.0 + i % 5, kind="hist", ts=ts)
    path = str(tmp_path / "ts.jsonl")
    assert store.dump_jsonl(path, window_s=30.0) > 0
    with open(path) as f:
        dumped = [json.loads(line) for line in f if line.strip()]
    points = store.points(window_s=30.0)
    assert {p["kind"] for p in points} == {"counter", "gauge", "hist"}
    return points + dumped, _without("kind")


def case_slo_alert(tmp_path, served):
    store = TimeSeriesStore(interval_s=1.0, capacity=600, clock=lambda: 1200.0)
    for i in range(200):  # half the admissions rejected: every window burns
        store.observe("submitted", 10 * i, kind="counter", ts=1000.0 + i)
        store.observe("rejected", 5 * i, kind="counter", ts=1000.0 + i)
    emitted = []
    alerts = SloEvaluator(store, default_serve_slos(), clock=lambda: 1200.0,
                          emit=emitted.append).evaluate()
    assert alerts and emitted, "a 50% rejection rate fired no alert"
    for alert in alerts:  # alerts ride the event plane too
        assert schema.validate_stream_item(alert) == []
    return alerts, _without("detail")


def _capacity():
    clock = [1000.0]
    oracle = CapacityOracle(interval_s=1.0, window_s=30.0,
                            clock=lambda: clock[0])
    stats = ServeStats()
    for i in range(40):
        stats.bump("tokens_out", 20)
        stats.bump("submitted", 2)
        stats.set_gauges(queue_depth=2, slots_active=4, num_slots=8,
                         blocks_free=100 - 2 * i, num_blocks=200)
        oracle.observe(stats.snapshot(), recompiles=0, ts=1000.0 + i)
    clock[0] = 1040.0
    snap = oracle.snapshot()
    assert snap.get("capacity_tokens_per_s"), "the oracle measured no ceiling"
    return snap


def case_capacity_snapshot(tmp_path, served):
    return [_capacity()], _without("headroom_tokens_per_s")


def _mpmd_live():
    """The stage beat is composed inside a stage worker's fit
    (``mpmd/worker.py`` ``on_step``), which only a pipeline fit reaches
    (slow tier): spelled here with that function's keys and taken through
    the driver's real consumer, ``MpmdStrategy._on_mpmd_item``."""
    strategy = MpmdStrategy(num_stages=2, schedule="1f1b",
                            num_microbatches=8, interleave=2)
    for stage in (1, 0):
        strategy._on_mpmd_item({
            "type": "mpmd_stage", "stage": stage, "step": 4,
            "bubble_fraction": 0.12, "stage_occupancy": 0.88,
            "busy_s": 0.4, "blocked_s": 0.05, "loss": 4.2,
        })
    return strategy._live_snapshot()["mpmd"]


def case_mpmd_stage_item(tmp_path, served):
    return _mpmd_live()["stages"], _without("stage")


def case_mpmd_snapshot(tmp_path, served):
    return [_mpmd_live()], _without("n_stages")


def case_mpmd_xfer(tmp_path, served):
    sink = _Stub()
    QueueChannel(sink).send("act", 3, 1, {"x": [1.0, 2.0]}, chunk=1,
                            trace=root_context("rid42"))
    codec = WireCodec(WireDtypeConfig.coerce("act:bf16,grad:int8"))
    QueueChannel(sink, codec=codec).send(
        "grad", 3, 1, {"g": np.ones(8, np.float32)}, chunk=1)
    assert "trace" in sink.sent[0]
    assert sink.sent[1]["enc"] == "act:bf16,grad:int8"
    return sink.sent, _without("mb")


CASES = {name[len("case_"):]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}
VALIDATOR_OF = {"flight_bundle_fixture": "flight_bundle"}


@pytest.mark.parametrize("case", [pytest.param(c, id=c) for c in CASES])
def test_producer_matches_schema(case, tmp_path, served):
    validate = getattr(schema, "validate_" + VALIDATOR_OF.get(case, case))
    items, mutate = CASES[case](tmp_path, served)
    codec = pickle if case in PICKLED else json
    assert items, "the producer made nothing"
    for item in items:
        wired = codec.loads(codec.dumps(item))
        assert validate(wired) == [], item
    broken = mutate(codec.loads(codec.dumps(items[0])))
    assert validate(broken), f"{case}: a mutated copy was accepted"


def test_every_validator_has_a_producer_case():
    exported = {n[len("validate_"):] for n in schema.__all__
                if n.startswith("validate_")}
    assert exported == {VALIDATOR_OF.get(c, c) for c in CASES}

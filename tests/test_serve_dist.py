"""Disaggregated serving plane: prefill/decode split + router.

Correctness contract: the disaggregated fleet must be INVISIBLE in the
tokens — a request routed through prefill workers, KV handoffs, and
any number of replica deaths produces exactly the stream the
single-host engine produces (greedy and temperature>0; the router's
fleet-wide sample seeds + the position-keyed sampler make failover
re-emissions bitwise), with zero steady-state recompiles on decode
replicas after KV import.  On top: router placement/admission/failover
policy units (jax-free), the handoff wire schema, the kill -9 segment
sweep, and a 2-actor end-to-end smoke.
"""

import os
import queue as _pyqueue
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from ray_lightning_tpu.cluster.queue import DriverQueue
from ray_lightning_tpu.serve.dist.handoff import (
    make_beat_item, make_dispatch_item, make_handoff_item,
    make_hello_item, request_fields,
)
from ray_lightning_tpu.serve.dist.router import RestartGovernor, Router
from ray_lightning_tpu.telemetry.schema import (
    validate_router_snapshot, validate_serve_kv_handoff,
    validate_serve_request,
)

pytestmark = pytest.mark.serve


# ---------------------------------------------------------------------------
# jax-free units: governor, wire items, router policy
# ---------------------------------------------------------------------------

class TestRestartGovernor:
    def test_window_budget(self):
        g = RestartGovernor(max_restarts=2, window_s=10.0)
        assert g.permit(now=0.0)
        assert g.permit(now=1.0)
        assert not g.permit(now=2.0)          # window exhausted
        assert g.permit(now=11.5)             # early attempts aged out
        assert g.permit(now=12.0)             # window has room for two
        assert not g.permit(now=12.5)         # {11.5, 12.0} fill it

    def test_zero_budget_never_permits(self):
        g = RestartGovernor(max_restarts=0)
        assert not g.permit(now=0.0)


class TestWireItems:
    def _req(self, **kw):
        kw.setdefault("reply", ("127.0.0.1", 9))
        kw.setdefault("sample_seed", 3)
        return request_fields("rid1", [1, 2, 3], 8, **kw)

    def test_request_fields_validate_as_serve_request(self):
        assert validate_serve_request(self._req()) == []

    def test_handoff_item_one_of_payload(self):
        req = self._req()
        with pytest.raises(ValueError, match="exactly one"):
            make_handoff_item(req, 8)
        with pytest.raises(ValueError, match="exactly one"):
            make_handoff_item(req, 8, data=b"x", shm="/dev/shm/y")
        item = make_handoff_item(req, 8, data=b"x")
        assert validate_serve_kv_handoff(item) == []

    def test_handoff_schema_negatives(self):
        req = self._req()
        item = make_handoff_item(req, 8, data=b"x")
        assert validate_serve_kv_handoff({**item, "shm": "/x"})
        assert validate_serve_kv_handoff({**item, "bucket": 2})  # < plen
        seedless = dict(item)
        seedless["req"] = {k: v for k, v in req.items()
                           if k != "sample_seed"}
        assert validate_serve_kv_handoff(seedless)

    def test_dispatch_item_shape(self):
        item = make_dispatch_item(self._req(), ("127.0.0.1", 5))
        assert item["type"] == "serve_prefill_dispatch"
        assert item["kv_to"] == ["127.0.0.1", 5]


class _StubHandle:
    def __init__(self, member_id, alive=True):
        self.id = member_id
        self._alive = alive
        self.killed = False

    def is_alive(self):
        return self._alive

    def kill(self):
        self.killed = True


def _drain(q, timeout=2.0, want=1):
    """What is on ``q``: returns once it is empty and holds ``want``
    items (frames of one poll arrive one by one), or at the timeout.
    An engine's tick sends its replies to one address as one
    ``serve_batch`` frame: its items are unpacked, as the client does."""
    items = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            item = q.get_nowait()
            batch = isinstance(item, dict) and item.get(
                "type") == "serve_batch"
            items.extend(item["items"] if batch else [item])
        except _pyqueue.Empty:
            if len(items) >= want:
                return items
            time.sleep(0.01)
    return items


class _RouterRig:
    """Router + stub members with real DriverQueue inboxes."""

    def __init__(self, n_replicas=2, n_workers=0, caps=None, **router_kw):
        router_kw.setdefault("lost_after_s", 60.0)
        self.router = Router(**router_kw)
        self.caps = caps or {"num_slots": 2, "max_queue": 2,
                             "spec_k": 0, "max_prompt_len": 16,
                             "max_model_len": 64, "block_size": 8}
        self.replicas = {}
        self.workers = {}
        self.reply_q = DriverQueue()
        for i in range(n_replicas):
            self.add_replica(f"r{i}")
        for i in range(n_workers):
            self.add_worker(f"p{i}")
        self.router.poll()

    def add_replica(self, rid, **caps_over):
        q = DriverQueue()
        handle = _StubHandle(rid)
        self.router.add_replica(handle)
        caps = {**self.caps, **caps_over}
        self.router.beat_handle.put(make_hello_item(
            "decode", rid, (q.handle.host, q.handle.port), **caps))
        self.replicas[rid] = (handle, q)
        return handle, q

    def add_worker(self, wid):
        q = DriverQueue()
        handle = _StubHandle(wid)
        self.router.add_prefill(handle)
        self.router.beat_handle.put(make_hello_item(
            "prefill", wid, (q.handle.host, q.handle.port),
            max_prompt_len=16, max_model_len=64, block_size=8))
        self.workers[wid] = (handle, q)
        return handle, q

    def submit(self, rid, prompt_len=3, **kw):
        item = {
            "type": "serve_request", "rid": rid,
            "prompt": list(range(1, prompt_len + 1)),
            "max_new_tokens": kw.pop("max_new_tokens", 4),
            "reply": [self.reply_q.handle.host, self.reply_q.handle.port],
            **kw,
        }
        self.router.submit_request(item)
        # Dispatch sends are asynchronous (per-member outbox threads);
        # the rig's assertions want them LANDED.
        self.router.flush_outboxes()

    def beat_done(self, member_id, pairs, role="decode"):
        self.router.beat_handle.put(make_beat_item(
            role, member_id, done=pairs))
        self.router.poll()
        self.router.flush_outboxes()

    def close(self):
        self.router.stop()
        self.reply_q.shutdown()
        for _, q in list(self.replicas.values()) + list(
                self.workers.values()):
            q.shutdown()


class TestRouterPolicy:
    def test_hello_registers_and_wait_ready(self):
        rig = _RouterRig(n_replicas=1, n_workers=1)
        try:
            rig.router.wait_ready(timeout=5)
            snap = rig.router.snapshot()
            assert [r["id"] for r in snap["replicas"]] == ["r0"]
            assert [w["id"] for w in snap["workers"]] == ["p0"]
        finally:
            rig.close()

    def test_least_loaded_placement_direct(self):
        rig = _RouterRig(n_replicas=2)
        try:
            for i in range(4):
                rig.submit(f"q{i}")
            r0 = _drain(rig.replicas["r0"][1])
            r1 = _drain(rig.replicas["r1"][1])
            # Round-robin by in-flight count: 2 each, never 4/0.
            assert len(r0) == len(r1) == 2
            # Fleet-wide seeds: distinct, submission-ordered.
            seeds = sorted(item["sample_seed"] for item in r0 + r1)
            assert seeds == [0, 1, 2, 3]
            assert rig.router.counters["direct_submits"] == 4
        finally:
            rig.close()

    def test_prefix_affinity_prefers_warm_replica(self):
        rig = _RouterRig(n_replicas=2)
        try:
            rig.submit("a0", prompt_len=9)
            rig.submit("a1", prompt_len=9)  # same prompt family
            r0 = _drain(rig.replicas["r0"][1])
            r1 = _drain(rig.replicas["r1"][1])
            # The second request follows the chain to the replica that
            # served the first, even though the other replica is idle.
            assert {item["rid"] for item in r0} == {"a0", "a1"}
            assert r1 == []
            assert rig.router.counters["prefix_affinity_hits"] == 1
        finally:
            rig.close()

    def test_prefix_affinity_yields_when_warm_replica_full(self):
        rig = _RouterRig(n_replicas=2)  # num_slots=2 per replica
        try:
            for i in range(3):
                rig.submit(f"f{i}", prompt_len=9)
            r0 = _drain(rig.replicas["r0"][1])
            r1 = _drain(rig.replicas["r1"][1])
            # Affinity never queues: once the warm replica's slots are
            # full the third same-prefix request places by load.
            assert {item["rid"] for item in r0} == {"f0", "f1"}
            assert [item["rid"] for item in r1] == ["f2"]
        finally:
            rig.close()

    def test_capacity_rejection_typed(self):
        rig = _RouterRig(n_replicas=1,
                         caps={"num_slots": 1, "max_queue": 1,
                               "spec_k": 0, "max_prompt_len": 16,
                               "max_model_len": 64, "block_size": 8})
        try:
            rig.submit("a")
            rig.submit("b")
            rig.submit("c")  # over num_slots + max_queue = 2
            replies = _drain(rig.reply_q)
            assert len(replies) == 1
            assert replies[0]["rid"] == "c"
            assert replies[0]["status"] == "rejected"
            assert rig.router.counters["rejected"] == 1
            assert "c" not in rig.router._inflight
        finally:
            rig.close()

    def test_spec_requests_stick_to_draft_capable(self):
        rig = _RouterRig(n_replicas=1)
        try:
            rig.add_replica("rs", spec_k=4)
            rig.router.poll()
            for i in range(2):
                rig.submit(f"s{i}", spec=2)
            routed = _drain(rig.replicas["rs"][1])
            assert [item["rid"] for item in routed] == ["s0", "s1"]
        finally:
            rig.close()

    def test_spec_without_capable_replica_is_invalid(self):
        rig = _RouterRig(n_replicas=1)
        try:
            rig.submit("s0", spec=2)
            replies = _drain(rig.reply_q)
            assert replies[0]["status"] == "invalid"
            assert "draft-capable" in replies[0]["error"]
        finally:
            rig.close()

    def test_oversized_prompt_is_invalid(self):
        rig = _RouterRig(n_replicas=1)
        try:
            rig.submit("big", prompt_len=40)  # > max_prompt_len 16
            replies = _drain(rig.reply_q)
            assert replies[0]["status"] == "invalid"
            assert rig.router.counters["invalid"] == 1
        finally:
            rig.close()

    def test_malformed_wire_request_gets_invalid_reply(self):
        rig = _RouterRig(n_replicas=1)
        try:
            rig.router.queue_handle().put({
                "type": "serve_request", "rid": "m1",
                "prompt": [1, 2], "max_new_tokens": None,  # int(None)
                "reply": [rig.reply_q.handle.host,
                          rig.reply_q.handle.port],
            })
            rig.router.poll()
            replies = _drain(rig.reply_q)
            assert replies and replies[0]["status"] == "invalid"
            assert replies[0]["rid"] == "m1"
        finally:
            rig.close()

    def test_done_beat_prunes_inflight(self):
        rig = _RouterRig(n_replicas=1)
        try:
            rig.submit("a")
            assert rig.router._inflight
            rig.beat_done("r0", [("a", "finished")])
            assert not rig.router._inflight
            assert rig.router.counters["completed"] == 1
        finally:
            rig.close()

    def test_replica_death_fails_over_inflight(self):
        rig = _RouterRig(n_replicas=2)
        try:
            rig.submit("a")
            rig.submit("b")
            victim = next(
                t.replica for t in rig.router._inflight.values())
            survivor = "r1" if victim == "r0" else "r0"
            _drain(rig.replicas[victim][1])
            _drain(rig.replicas[survivor][1])
            moved = [r for r, t in rig.router._inflight.items()
                     if t.replica == victim]
            rig.replicas[victim][0]._alive = False
            rig.router.poll()
            re_routed = _drain(rig.replicas[survivor][1], want=len(moved))
            assert sorted(i["rid"] for i in re_routed) == sorted(moved)
            # The re-submission carries the ORIGINAL fleet seed — the
            # bitwise-stream guarantee's transport half.
            for item in re_routed:
                assert item["sample_seed"] is not None
            c = rig.router.counters
            assert c["replica_deaths"] == 1 and c["failovers"] == 1
            assert c["failed_over_requests"] == len(moved)
            deadline = time.monotonic() + 2.0
            while (not rig.replicas[victim][0].killed
                   and time.monotonic() < deadline):
                time.sleep(0.01)  # reap runs off the control plane
            assert rig.replicas[victim][0].killed  # corpse reaped
        finally:
            rig.close()

    def test_failover_parks_when_survivor_saturated(self):
        rig = _RouterRig(n_replicas=2,
                         caps={"num_slots": 1, "max_queue": 0,
                               "spec_k": 0, "max_prompt_len": 16,
                               "max_model_len": 64, "block_size": 8})
        try:
            rig.submit("a")
            rig.submit("b")  # one per replica (capacity 1 each)
            victim = rig.router._inflight["a"].replica
            survivor = "r1" if victim == "r0" else "r0"
            rig.replicas[victim][0]._alive = False
            rig.router.poll()
            # Survivor full: "a" parked, NOT rejected/lost.
            assert "a" in rig.router._inflight
            assert not _drain(rig.reply_q, timeout=0.3)
            other = next(r for r in rig.router._inflight
                         if r != "a")
            rig.beat_done(survivor, [(other, "finished")])
            routed = _drain(rig.replicas[survivor][1])
            assert any(i["rid"] == "a" for i in routed)
        finally:
            rig.close()

    def test_closing_beat_is_planned_drain_not_failure(self):
        rig = _RouterRig(n_replicas=2)
        try:
            rig.submit("a")
            rig.submit("b")
            draining = next(
                t.replica for t in rig.router._inflight.values())
            survivor = "r1" if draining == "r0" else "r0"
            _drain(rig.replicas[draining][1])
            _drain(rig.replicas[survivor][1])
            moved = [r for r, t in rig.router._inflight.items()
                     if t.replica == draining]
            rig.router.beat_handle.put(make_beat_item(
                "decode", draining, closing=True))
            rig.router.poll()
            c = rig.router.counters
            assert c["replica_drains"] == 1
            assert c["replica_deaths"] == 0 and c["failovers"] == 0
            re_routed = _drain(rig.replicas[survivor][1], want=len(moved))
            assert sorted(i["rid"] for i in re_routed) == sorted(moved)
            snap = rig.router.snapshot()
            entry = next(r for r in snap["replicas"]
                         if r["id"] == draining)
            assert entry["alive"] is False
        finally:
            rig.close()

    def test_spec_parks_when_capable_replica_excluded(self):
        rig = _RouterRig(n_replicas=1)  # r0 plain
        try:
            rig.add_replica("rs", spec_k=4)
            rig.router.poll()
            rig.submit("s0", spec=2)
            assert _drain(rig.replicas["rs"][1])  # placed on capable
            # Transient handoff-style failure excludes the ONLY capable
            # replica: the accepted request must PARK, never land on a
            # draft-less replica (instant "invalid") nor be dropped.
            rig.router._on_handoff_failure("s0", "ConnectionError()",
                                           now=0.0)
            assert "s0" in rig.router._inflight
            assert not _drain(rig.replicas["r0"][1], timeout=0.3)
            assert not _drain(rig.reply_q, timeout=0.2)
            rig.router.poll()  # retry queue: exclusion was one-shot
            routed = _drain(rig.replicas["rs"][1])
            assert [i["rid"] for i in routed] == ["s0"]
        finally:
            rig.close()

    def test_worker_death_respawns_under_governor(self):
        spawned = []

        def factory():
            handle = _StubHandle(f"px{len(spawned)}")
            spawned.append(handle)
            return handle

        rig = _RouterRig(n_replicas=1, n_workers=1,
                         governor=RestartGovernor(max_restarts=1),
                         prefill_factory=factory)
        try:
            rig.submit("a")
            assert _drain(rig.workers["p0"][1])  # dispatched to worker
            rig.workers["p0"][0]._alive = False
            rig.router.poll()
            c = rig.router.counters
            assert c["worker_deaths"] == 1
            assert c["prefill_respawns"] == 1 and len(spawned) == 1
            # The pending prompt re-dispatched: the respawned worker has
            # no inbox yet, so it falls back to direct submission.
            routed = _drain(rig.replicas["r0"][1])
            assert [i["rid"] for i in routed] == ["a"]
            # Second death exhausts the window: denied, no new spawn.
            spawned[0]._alive = False
            rig.router.poll()
            assert rig.router.counters["prefill_respawns_denied"] == 1
            assert len(spawned) == 1
        finally:
            rig.close()

    def test_worker_failed_handoff_reroutes_excluding_replica(self):
        rig = _RouterRig(n_replicas=2, n_workers=1)
        try:
            rig.submit("a")
            assert _drain(rig.workers["p0"][1])
            bound = rig.router._inflight["a"].replica
            other = "r1" if bound == "r0" else "r0"
            rig.router.beat_handle.put(make_beat_item(
                "prefill", "p0", failed=[("a", "ConnectionError()")]))
            rig.router.poll()
            assert rig.router._inflight["a"].replica == other
        finally:
            rig.close()

    def test_snapshot_schema_and_export(self, tmp_path):
        rig = _RouterRig(n_replicas=2, n_workers=1)
        try:
            rig.submit("a")
            rig.router.beat_handle.put(make_beat_item(
                "decode", "r0",
                snapshot={"ts": 0.0, "counters": {}, "latency": {},
                          "gauges": {"slots_active": 1, "num_slots": 2,
                                     "blocks_free": 5, "num_blocks": 9,
                                     "queue_depth": 0}},
                recompiles=4))
            rig.router.poll()
            snap = rig.router.snapshot()
            assert validate_router_snapshot(snap) == []
            import json

            from ray_lightning_tpu.telemetry.export_prom import (
                render_openmetrics,
            )
            text = render_openmetrics({"router": snap})
            assert 'rlt_serve_replica_inflight{replica=' in text
            assert 'rlt_serve_router_total{kind="routed"} 1' in text
            sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                            "..", "tools"))
            import rlt_top

            frame = rlt_top.render(
                {"ts": snap["ts"], "router": snap}, "x")
            assert "router:" in frame and "r0" in frame
            # Discovery: router-live.json in a telemetry dir.
            path = tmp_path / "router-live.json"
            path.write_text(json.dumps({"ts": snap["ts"],
                                        "router": snap}))
            loaded = rlt_top.load_snapshot(str(tmp_path))
            assert loaded and "router" in loaded
        finally:
            rig.close()


class TestServeResilience:
    """ISSUE 19 units (jax-free): parked-retry FIFO under sustained
    saturation, the migration claim vs beat-loss detection, and the
    brownout ladder's hysteresis.  The fleet-level chaos lives in the
    slow-marked TestInprocFleet drills + tools/chaos_serve_sweep.py."""

    def test_retry_queue_drains_in_submission_order(self):
        """A failover burst onto a saturated survivor parks every
        displaced request; as capacity frees one slot at a time they
        place in original submission order — sustained saturation must
        not reorder (starve) the oldest accepted work."""
        rig = _RouterRig(n_replicas=2,
                         caps={"num_slots": 2, "max_queue": 0,
                               "spec_k": 0, "max_prompt_len": 16,
                               "max_model_len": 64, "block_size": 8})
        try:
            for i in range(4):
                rig.submit(f"q{i}")   # 2 per replica, both full
            victim = rig.router._inflight["q0"].replica
            survivor = "r1" if victim == "r0" else "r0"
            displaced = [r for r, t in rig.router._inflight.items()
                         if t.replica == victim]
            resident = [r for r, t in rig.router._inflight.items()
                        if t.replica == survivor]
            _drain(rig.replicas[survivor][1])
            rig.replicas[victim][0]._alive = False
            rig.router.poll()
            # Survivor full: both displaced requests parked, in order.
            assert list(rig.router._retry) == displaced
            assert not _drain(rig.reply_q, timeout=0.2)  # none rejected
            placed = []
            for done_rid in resident:  # free ONE slot at a time
                rig.beat_done(survivor, [(done_rid, "finished")])
                placed += [i["rid"]
                           for i in _drain(rig.replicas[survivor][1])]
            assert placed == displaced  # FIFO, never newest-first
        finally:
            rig.close()

    def test_migration_claim_suppresses_beat_loss(self):
        """ISSUE 19 bugfix regression: a ``migrating`` beat claims the
        replica for ``migration_claim_s`` — the device->host KV gather
        can silence beats past ``lost_after_s``, and declaring the
        exporter dead mid-export would race recompute failover against
        migration frames already on the wire for the SAME rids.  The
        claim is bounded: once it expires a silent replica dies
        normally and nothing is lost."""
        rig = _RouterRig(n_replicas=2, lost_after_s=0.15,
                         migration_claim_s=0.6)
        try:
            rig.submit("x")
            victim = rig.router._inflight["x"].replica
            survivor = "r1" if victim == "r0" else "r0"
            rig.router.beat_handle.put(make_beat_item(
                "decode", victim, migrating=["x"]))
            rig.router.poll()
            time.sleep(0.25)  # beat-age > lost_after_s, claim active
            # The survivor beats on; ONLY the exporter goes silent.
            rig.router.beat_handle.put(make_beat_item(
                "decode", survivor))
            rig.router.poll()
            assert rig.router._replicas[victim].alive
            assert rig.router.counters["failovers"] == 0
            assert rig.router._inflight["x"].replica == victim
            time.sleep(0.5)   # claim expired, still no beat: dead now
            rig.router.beat_handle.put(make_beat_item(
                "decode", survivor))
            rig.router.poll()
            rig.router.flush_outboxes()
            assert not rig.router._replicas[victim].alive
            assert rig.router.counters["failovers"] == 1
            # The orphan finished the normal way: recompute failover.
            assert rig.router._inflight["x"].replica != victim
        finally:
            rig.close()

    def test_brownout_ladder_hysteresis_and_probe(self):
        """Thin unit beside tools/chaos_serve_sweep.py --selftest: one
        rung per observation, dwell between moves, descent needs the
        exit margin, one half-open probe per window."""
        from ray_lightning_tpu.serve.brownout import BrownoutLadder

        t = [0.0]
        b = BrownoutLadder(min_dwell_s=1.0, probe_every_s=5.0,
                           clock=lambda: t[0])
        assert b.observe(0.90) == 1   # first climb off 0 is immediate
        assert b.observe(0.99) == 1   # dwell holds the rung
        t[0] = 1.1
        assert b.observe(0.99) == 2
        t[0] = 2.2
        assert b.observe(1.00) == 3
        t[0] = 3.3
        assert b.observe(0.94) == 3   # within exit margin: no descent
        assert b.observe(0.10) == 2   # one rung down, never straight 0
        t[0] = 10.0
        assert b.allow_probe()        # opens the half-open window
        assert not b.allow_probe()    # window closed until it elapses
        t[0] = 15.1
        assert b.allow_probe()


# ---------------------------------------------------------------------------
# Segment lifetime: dead prefill handoffs must not leak tmpfs
# ---------------------------------------------------------------------------

class TestSegmentSweep:
    def _orphan_segment(self):
        """Write an rlt-kv segment from a subprocess and SIGKILL it —
        the dead-prefill-worker shape (owner pid gone, segment never
        consumed)."""
        code = (
            "import sys, time\n"
            "from ray_lightning_tpu.cluster.shm import SegmentStore\n"
            "import atexit\n"
            "store = SegmentStore(prefix='rlt-kv')\n"
            "atexit.unregister(store.unlink_all)\n"  # simulate -9: no cleanup
            "print(store.put(b'x' * 2048), flush=True)\n"
            "time.sleep(60)\n"
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", code], stdout=subprocess.PIPE,
            text=True,
        )
        path = proc.stdout.readline().strip()
        assert os.path.exists(path)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        return path

    def test_router_teardown_sweeps_killed_producer(self):
        path = self._orphan_segment()
        router = Router(lost_after_s=60.0)
        router.stop()  # teardown sweep (same path failover takes)
        assert not os.path.exists(path)

    def test_engine_close_sweeps_killed_producer(self, dist_model):
        from ray_lightning_tpu.serve.engine import (
            ServeConfig, ServeEngine,
        )

        m, params = dist_model
        eng = ServeEngine(m, params, ServeConfig(num_slots=1,
                                                 block_size=8))
        path = self._orphan_segment()
        eng.stop()
        assert not os.path.exists(path)


# ---------------------------------------------------------------------------
# jax-backed: KV export/import, handoff admission, fleets
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dist_model():
    from utils import tiny_gpt

    return tiny_gpt()


def _prompts(n, seed=0, vocab=128, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab,
                         size=(int(rng.integers(lo, hi)),)).tolist()
            for _ in range(n)]


def _serve_cfg(**kw):
    from ray_lightning_tpu.serve.engine import ServeConfig

    kw.setdefault("num_slots", 2)
    kw.setdefault("block_size", 8)
    return ServeConfig(**kw)


def _reference_tokens(model, prompts, temps, max_new=8, **engine_kw):
    """Monolith engine run with the same submission order — the token
    stream the fleet must reproduce bitwise."""
    from ray_lightning_tpu.serve.engine import ServeEngine

    m, params = model
    eng = ServeEngine(m, params, _serve_cfg(**engine_kw.pop("cfg", {})),
                      **engine_kw)
    try:
        return [eng.generate(p, max_new, temperature=t)
                for p, t in zip(prompts, temps)]
    finally:
        eng.stop()


class TestKVExportImport:
    def test_roundtrip_distinct_block_ids(self, dist_model):
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.serve.kv_cache import (
            PagedKVCache, import_blocks,
        )

        m, _ = dist_model
        cache = PagedKVCache(m.config, num_blocks=9, block_size=4)
        pool = cache.init_pool()
        rng = np.random.default_rng(0)
        content = {
            k: rng.normal(size=(m.config.n_layer, 2, 4, m.config.n_head,
                                m.config.head_dim)).astype(np.float32)
            for k in ("k", "v")
        }
        src_ids = [3, 5]
        # The wire keeps heads apart; a pool row holds them side by side.
        pool = {k: pool[k].at[:, jnp.asarray(src_ids)].set(
                    content[k].reshape(content[k].shape[:3] + (-1,)))
                for k in pool}
        exported = cache.export_blocks(pool, src_ids)
        for k in ("k", "v"):
            assert isinstance(exported[k], np.ndarray)
            np.testing.assert_array_equal(exported[k], content[k])
        # Import into DIFFERENT physical ids of a fresh pool.
        dst = PagedKVCache(m.config, num_blocks=9, block_size=4)
        dst_pool = dst.init_pool()
        dst_ids = jnp.asarray([7, 1], jnp.int32)
        dst_pool = jax.jit(import_blocks)(
            dst_pool, {k: jnp.asarray(v) for k, v in exported.items()},
            dst_ids,
        )
        again = dst.export_blocks(dst_pool, [7, 1])
        for k in ("k", "v"):
            np.testing.assert_array_equal(again[k], content[k])
        # Untouched blocks (trash included) stayed zero.
        assert float(jnp.abs(dst_pool["k"][:, 0]).max()) == 0.0

    def test_export_rejects_trash_and_oob(self, dist_model):
        from ray_lightning_tpu.serve.kv_cache import PagedKVCache

        m, _ = dist_model
        cache = PagedKVCache(m.config, num_blocks=5, block_size=4)
        pool = cache.init_pool()
        with pytest.raises(ValueError, match="ids outside"):
            cache.export_blocks(pool, [0])
        with pytest.raises(ValueError, match="ids outside"):
            cache.export_blocks(pool, [5])


class TestHandoffAdmission:
    """One engine fed real serve_kv_handoff frames — the decode-replica
    half of the split, without the fleet around it."""

    def _handoff_via_worker(self, model, req, serve_cfg, kv_to,
                            same_host=True, check=None):
        from ray_lightning_tpu.serve.dist.prefill import PrefillRunner

        m, params = model
        beats = DriverQueue()
        worker = PrefillRunner("pw", m, params, serve_cfg,
                               beats.handle, beat_s=60.0)
        try:
            if check is not None:
                check(worker)
            worker._inbox.handle.put(make_dispatch_item(
                req, kv_to, same_host=same_host))
            assert worker.step(timeout=5)
        finally:
            worker.close()
            beats.shutdown()

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_import_admission_matches_local_prefill(self, dist_model,
                                                    temperature):
        from ray_lightning_tpu.serve.engine import ServeEngine

        m, params = dist_model
        cfg = _serve_cfg()
        prompt = list(range(1, 11))
        ref = _reference_tokens(dist_model, [prompt], [temperature])
        eng = ServeEngine(m, params, _serve_cfg())
        replies = DriverQueue()
        try:
            req = request_fields(
                "h1", prompt, 8,
                reply=(replies.handle.host, replies.handle.port),
                sample_seed=0, temperature=temperature,
            )
            self._handoff_via_worker(
                dist_model, req, cfg,
                (eng.queue_handle().host, eng.queue_handle().port),
            )
            eng.run_until_idle()
            done = [i for i in _drain(replies, timeout=5)
                    if i["type"] == "serve_done"]
            assert done and done[0]["status"] == "finished"
            assert done[0]["tokens"] == ref[0]
            assert eng.stats.counters["kv_imports"] == 1
            assert eng.stats.counters["prefills"] == 0
            # An imported admission joins the decode loop's pipeline as
            # a local prefill does: its first token is sampled on the
            # device and feeds the decode there.
            assert eng._pipelined
            assert eng.stats.counters["admit_fed_on_device"] == 1
        finally:
            eng.stop()
            replies.shutdown()

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_bf16_worker_and_engine_hold_equal_trees(self, dist_model,
                                                     temperature):
        """One float32 tree, bf16 compute: the worker and the engine
        prepare it through the same method of the same family, so they
        hold equal dtypes leaf for leaf (the weights in bf16, LayerNorm
        in float32), and a handed-off prefill decodes to the monolith's
        tokens."""
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.models.gpt import GPT
        from ray_lightning_tpu.serve.engine import ServeEngine

        m32, params = dist_model
        m = GPT(m32.config, attn_impl="xla")
        m.precision = "bf16"
        prompt = list(range(1, 11))
        ref = _reference_tokens((m, params), [prompt], [temperature])
        eng = ServeEngine(m, params, _serve_cfg())
        replies = DriverQueue()

        def same_dtypes(worker):
            held = jax.tree.map(lambda a: a.dtype, eng.params)
            assert held == jax.tree.map(lambda a: a.dtype, worker.params)
            assert held["blocks"]["qkv_w"] == jnp.bfloat16
            assert held["wte"] == jnp.bfloat16
            assert held["blocks"]["ln1_g"] == jnp.float32

        try:
            req = request_fields(
                "h1", prompt, 8,
                reply=(replies.handle.host, replies.handle.port),
                sample_seed=0, temperature=temperature,
            )
            self._handoff_via_worker(
                (m, params), req, _serve_cfg(),
                (eng.queue_handle().host, eng.queue_handle().port),
                check=same_dtypes,
            )
            eng.run_until_idle()
            done = [i for i in _drain(replies, timeout=5)
                    if i["type"] == "serve_done"]
            assert done and done[0]["status"] == "finished"
            assert done[0]["tokens"] == ref[0]
            assert eng.stats.counters["kv_imports"] == 1
        finally:
            eng.stop()
            replies.shutdown()

    def test_import_steady_state_zero_recompiles(self, dist_model):
        """Steady state = long-lived worker + long-lived replica: once
        a bucket's prefill/import/first-token programs are warm, every
        further handoff of that bucket compiles NOTHING on either
        side."""
        from ray_lightning_tpu.serve.dist.prefill import PrefillRunner
        from ray_lightning_tpu.serve.engine import ServeEngine
        from ray_lightning_tpu.telemetry import compile_event_count

        m, params = dist_model
        eng = ServeEngine(m, params, _serve_cfg())
        replies = DriverQueue()
        beats = DriverQueue()
        worker = PrefillRunner("pw", m, params, _serve_cfg(),
                               beats.handle, beat_s=60.0)
        kv_to = (eng.queue_handle().host, eng.queue_handle().port)
        try:
            def one(rid, prompt, seed):
                req = request_fields(
                    rid, prompt, 4,
                    reply=(replies.handle.host, replies.handle.port),
                    sample_seed=seed,
                )
                worker._inbox.handle.put(make_dispatch_item(req, kv_to))
                assert worker.step(timeout=5)
                eng.run_until_idle()

            one("w1", list(range(1, 7)), 0)      # warms the import path
            before = compile_event_count()
            one("w2", list(range(2, 8)), 1)      # same bucket: steady
            assert compile_event_count() - before == 0
        finally:
            worker.close()
            beats.shutdown()
            eng.stop()
            replies.shutdown()

    def test_shm_handoff_consumed_and_unlinked(self, dist_model):
        """Same-host zero-copy: with the threshold forced to 0 the
        payload rides a tmpfs segment, the replica reads it once and
        unlinks it (consumer-owned lifetime) — and the tokens are the
        same as the inline path's."""
        import glob

        from ray_lightning_tpu.cluster.shm import segment_dir
        from ray_lightning_tpu.serve.dist.prefill import PrefillRunner
        from ray_lightning_tpu.serve.engine import ServeEngine

        m, params = dist_model
        prompt = list(range(1, 11))
        ref = _reference_tokens(dist_model, [prompt], [0.0])
        eng = ServeEngine(m, params, _serve_cfg())
        replies = DriverQueue()
        beats = DriverQueue()
        worker = PrefillRunner("pw", m, params, _serve_cfg(),
                               beats.handle, beat_s=60.0,
                               shm_threshold=0)
        try:
            req = request_fields(
                "shm1", prompt, 8,
                reply=(replies.handle.host, replies.handle.port),
                sample_seed=0,
            )
            worker._inbox.handle.put(make_dispatch_item(
                req, (eng.queue_handle().host,
                      eng.queue_handle().port), same_host=True))
            assert worker.step(timeout=5)
            assert len(worker._live_segments) == 1
            shm_path = worker._live_segments[0][0]
            assert os.path.exists(shm_path)
            eng.run_until_idle()
            done = [i for i in _drain(replies, timeout=5)
                    if i["type"] == "serve_done"]
            assert done and done[0]["tokens"] == ref[0]
            assert not os.path.exists(shm_path)  # consumer unlinked
        finally:
            worker.close()
            beats.shutdown()
            eng.stop()
            replies.shutdown()
            leftovers = glob.glob(os.path.join(segment_dir(),
                                               "rlt-kv-*"))
            assert not leftovers

    def test_prefill_graceful_drain_sends_closing_beat(self,
                                                       dist_model):
        """A planned worker stop must flag its final beat ``closing``
        (the router's drain-vs-death discriminator) — and a hard kill
        must NOT (a dead process sends nothing)."""
        import threading

        from ray_lightning_tpu.serve.dist.prefill import PrefillRunner

        m, params = dist_model
        beats = DriverQueue()
        worker = PrefillRunner("pw", m, params, _serve_cfg(),
                               beats.handle, beat_s=0.05)
        stop = threading.Event()
        thread = threading.Thread(target=worker.run,
                                  args=(stop.is_set,), daemon=True)
        thread.start()
        time.sleep(0.2)
        stop.set()
        thread.join(timeout=10)
        items = _drain(beats, timeout=2.0)
        beats.shutdown()
        assert items[0]["type"] == "serve_replica_hello"
        closing = [i for i in items
                   if i.get("type") == "serve_replica_beat"
                   and i.get("closing")]
        assert len(closing) == 1 and items[-1] is closing[0]

    def test_geometry_mismatch_is_typed_invalid(self, dist_model):
        from ray_lightning_tpu.mpmd.transfer import encode_tree
        from ray_lightning_tpu.serve.engine import ServeEngine

        m, params = dist_model
        eng = ServeEngine(m, params, _serve_cfg())
        replies = DriverQueue()
        try:
            req = request_fields(
                "bad", [1, 2, 3], 4,
                reply=(replies.handle.host, replies.handle.port),
                sample_seed=0,
            )
            payload = encode_tree({
                "kv": {k: np.zeros((m.config.n_layer, 3, 8,
                                    m.config.n_head,
                                    m.config.head_dim), np.float32)
                       for k in ("k", "v")},
                "logits": np.zeros((m.config.vocab_size,), np.float32),
            })
            # 3 blocks of 8 = 24 tokens, but a 3-token prompt buckets
            # at 8 — geometry drift must be loud, not a hang.
            eng.queue_handle().put(
                make_handoff_item(req, bucket=24, data=payload))
            eng.run_until_idle()
            eng.step()
            done = _drain(replies, timeout=5)
            assert done and done[0]["status"] == "invalid"
            assert "geometry" in done[0]["error"]
            assert ("bad", "invalid") in eng.drain_done()
        finally:
            eng.stop()
            replies.shutdown()


class TestInprocFleet:
    """Full dataflow on driver threads: client → router → prefill
    worker → KV handoff → decode replica → token stream."""

    def test_fleet_parity_and_zero_recompiles(self, dist_model):
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet
        from ray_lightning_tpu.telemetry import compile_event_count

        m, params = dist_model
        prompts = _prompts(6)
        temps = [0.0, 0.8, 0.0, 0.8, 0.0, 0.8]
        ref = _reference_tokens(dist_model, prompts, temps)
        fleet = launch_inproc_fleet(m, params, _serve_cfg(),
                                    n_replicas=2, n_prefill=1)
        client = ServeClient(fleet.queue_handle())
        try:
            rids = [client.submit(p, 8, temperature=t)
                    for p, t in zip(prompts, temps)]
            out = [client.result(r, timeout=120) for r in rids]
            assert out == ref
            # Steady state (all programs warmed, every bucket seen):
            # a second wave triggers ZERO compiles anywhere in the
            # fleet — replicas, worker, router, client all share this
            # process, so the process counter bounds them all.
            before = compile_event_count()
            rids = [client.submit(p, 8, temperature=t)
                    for p, t in zip(_prompts(6, seed=5), temps)]
            out2 = [client.result(r, timeout=120) for r in rids]
            assert len(out2) == 6
            assert compile_event_count() - before == 0
            # The requests genuinely rode the handoff path.
            snap = fleet.router.snapshot()
            assert validate_router_snapshot(snap) == []
            assert snap["counters"]["prefill_dispatches"] == 12
            assert snap["counters"]["worker_deaths"] == 0
        finally:
            client.close()
            fleet.close()

    @pytest.mark.slow  # tier-1 diet (round 20): ~8s fleet fit; the
    # router-rig failover units + fleet_parity smoke stay in tier-1
    def test_client_failover_dedup_mid_stream(self, dist_model):
        """Satellite: engineered replica death mid-stream — the
        survivor's re-emission is deduped by token index and the final
        stream is bitwise the no-failure stream, greedy AND
        temperature>0 (the round-16 position-keyed sampler + the
        router's fleet seeds)."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet

        m, params = dist_model
        p1, p2 = list(range(1, 9)), list(range(9, 17))
        ref = _reference_tokens(dist_model, [p1, p2], [0.7, 0.0],
                                max_new=30)
        fleet = launch_inproc_fleet(m, params, _serve_cfg(),
                                    n_replicas=2, n_prefill=0,
                                    lost_after_s=0.5)
        client = ServeClient(fleet.queue_handle())
        try:
            r1 = client.submit(p1, 30, temperature=0.7)
            r2 = client.submit(p2, 30)
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
            out2 = client.result(r2, timeout=120)
            assert out1 == ref[0]          # bitwise across the failover
            assert out2 == ref[1]
            assert client.re_emitted_tokens > 0  # dedup genuinely hit
            c = fleet.router.counters
            assert c["failovers"] >= 1 and c["replica_deaths"] == 1
            assert c["failed_over_requests"] >= 1
        finally:
            client.close()
            fleet.close()

    @pytest.mark.slow  # tier-1 budget audit (round 19): ~10s fleet
    # fit; the migration-claim + closing-beat router units carry the
    # drain semantics in tier-1, tools/chaos_serve_sweep.py is the
    # full-matrix gate
    def test_drain_migration_parity_zero_reemit(self, dist_model):
        """Tentpole acceptance: planned drain live-migrates resident
        sequences — decode resumes mid-sequence on the survivor with
        ZERO recomputed prefill (re_emitted_tokens == 0, the failover
        path's signature) and bitwise token parity vs an uninterrupted
        engine, greedy AND temperature>0."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet

        m, params = dist_model
        p1, p2 = list(range(1, 9)), list(range(9, 17))
        ref = _reference_tokens(dist_model, [p1, p2], [0.7, 0.0],
                                max_new=30)
        os.environ["RLT_MIGRATE_ON_DRAIN"] = "1"
        fleet = launch_inproc_fleet(m, params, _serve_cfg(),
                                    n_replicas=2, n_prefill=0,
                                    lost_after_s=0.5)
        client = ServeClient(fleet.queue_handle())
        try:
            r1 = client.submit(p1, 30, temperature=0.7)
            r2 = client.submit(p2, 30)
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
                 if r.id == victim).kill(hard=False)
            out1 = client.result(r1, timeout=120)
            out2 = client.result(r2, timeout=120)
            assert out1 == ref[0]         # bitwise across the drain
            assert out2 == ref[1]
            assert client.re_emitted_tokens == 0  # nothing recomputed
            c = fleet.router.counters
            assert c["migrations"] >= 1
            assert c["failovers"] == 0 and c["replica_deaths"] == 0
        finally:
            os.environ.pop("RLT_MIGRATE_ON_DRAIN", None)
            client.close()
            fleet.close()

    @pytest.mark.slow  # tier-1 budget audit (round 19): ~10s fleet
    # fit; hedge admission/cancel policy units ride the router rig in
    # tier-1, this drill proves the wire + dedup end to end
    def test_hedge_first_winner_cancels_loser(self, dist_model):
        """A hedged duplicate races a fault-slowed replica: first
        finisher wins, the router cancels the loser's copy, and the
        duplicate stream merges bitwise through the token-index dedup
        (re_emitted_tokens counts the merged copies)."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet

        m, params = dist_model
        p1 = list(range(1, 9))
        ref = _reference_tokens(dist_model, [p1], [0.7], max_new=30)
        fleet = launch_inproc_fleet(m, params, _serve_cfg(),
                                    n_replicas=2, n_prefill=0,
                                    lost_after_s=5.0)
        client = ServeClient(fleet.queue_handle())
        try:
            r1 = client.submit(p1, 30, temperature=0.7)
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
            # Stall the placed replica's decode ticks (the straggler
            # hedging exists for), then duplicate onto a survivor.
            os.environ["RLT_FAULT"] = (
                f"slow@point:replica_tick,replica:{victim},"
                f"secs:0.3,once:0")
            assert client.hedge(r1)
            out1 = client.result(r1, timeout=120)
            assert out1 == ref[0]          # merged stream is bitwise
            assert client.re_emitted_tokens > 0  # copies really merged
            c = fleet.router.counters
            assert c["hedges"] >= 1
            # The router learns the winner from the next done beat —
            # wait out the beat lag before asserting the cancel.
            deadline = time.monotonic() + 15
            while (c["hedge_cancels"] < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert c["hedge_cancels"] >= 1  # loser copy cancelled
        finally:
            os.environ.pop("RLT_FAULT", None)
            client.close()
            fleet.close()

    @pytest.mark.slow  # tier-1 diet (round 20): ~16s, the largest
    # serve_dist fit; spec x fleet composition is covered via -m slow,
    # fleet_parity_and_zero_recompiles is the tier-1 fleet smoke
    def test_spec_fleet_parity(self, dist_model):
        """Disagg x speculation: draft-capable replicas serve spec
        requests token-for-token like the monolith spec engine (KV
        import feeds the target pool; the draft prefills locally from
        the shipped prompt)."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet
        from ray_lightning_tpu.serve.draft import early_exit_draft

        m, params = dist_model
        draft, draft_params = early_exit_draft(m, params, 1)
        prompts = _prompts(4, seed=3)
        temps = [0.0, 0.8, 0.0, 0.8]
        cfg = {"cfg": {"spec_k": 2}}
        ref = _reference_tokens(dist_model, prompts, temps,
                                draft_module=draft,
                                draft_params=draft_params, **cfg)
        fleet = launch_inproc_fleet(
            m, params, _serve_cfg(spec_k=2), n_replicas=2, n_prefill=1,
            draft_module=draft, draft_params=draft_params,
        )
        client = ServeClient(fleet.queue_handle())
        try:
            rids = [client.submit(p, 8, temperature=t, spec=2)
                    for p, t in zip(prompts, temps)]
            out = [client.result(r, timeout=120) for r in rids]
            assert out == ref
        finally:
            client.close()
            fleet.close()


# ---------------------------------------------------------------------------
# Actor fleet: the 2-actor smoke (tier-1) + chaos (slow)
# ---------------------------------------------------------------------------

@pytest.mark.remote
class TestActorFleet:
    @pytest.mark.slow  # tier-1 diet (round 20): ~15s actor spawn +
    # model build x2; the inproc fleet smoke covers the dataflow in
    # tier-1, the actor shapes run via -m slow with the chaos arm
    def test_two_actor_smoke(self, dist_model, tmp_path):
        """1 prefill actor + 1 decode actor — the full cross-process
        dataflow (dispatch → prefill → segment/queue handoff → import
        → stream) with token parity against the monolith."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_actor_fleet

        m, params = dist_model
        prompts = _prompts(3, seed=7)
        temps = [0.0, 0.7, 0.0]
        ref = _reference_tokens(dist_model, prompts, temps)
        fleet = launch_actor_fleet(
            m, params, _serve_cfg(), n_replicas=1, n_prefill=1,
            telemetry_dir=str(tmp_path),
        )
        client = ServeClient(fleet.queue_handle())
        try:
            rids = [client.submit(p, 8, temperature=t)
                    for p, t in zip(prompts, temps)]
            out = [client.result(r, timeout=300) for r in rids]
            assert out == ref
            snap = fleet.router.snapshot()
            assert validate_router_snapshot(snap) == []
            assert snap["counters"]["prefill_dispatches"] == 3
        finally:
            client.close()
            fleet.close()

    @pytest.mark.slow
    def test_actor_chaos_kill_replica_zero_lost(self, dist_model):
        """SIGKILL one of two decode actors under load: every request
        still completes (failover onto the survivor), bitwise-equal to
        the monolith run."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_actor_fleet

        m, params = dist_model
        prompts = _prompts(8, seed=11)
        temps = [0.0, 0.6] * 4
        ref = _reference_tokens(dist_model, prompts, temps, max_new=16)
        fleet = launch_actor_fleet(
            m, params, _serve_cfg(), n_replicas=2, n_prefill=0,
            lost_after_s=1.5,
        )
        client = ServeClient(fleet.queue_handle())
        try:
            rids = [client.submit(p, 16, temperature=t)
                    for p, t in zip(prompts, temps)]
            deadline = time.monotonic() + 120
            victim = None
            while time.monotonic() < deadline and victim is None:
                with fleet.router._lock:
                    loads = {}
                    for t in fleet.router._inflight.values():
                        if t.replica:
                            loads[t.replica] = loads.get(t.replica,
                                                         0) + 1
                    started = sum(len(p.tokens) for p in
                                  client._pending.values())
                    if loads and started >= 4:
                        victim = max(loads, key=loads.get)
                time.sleep(0.05)
            assert victim is not None, "load never materialized"
            next(r for r in fleet.replicas
                 if r.id == victim).kill(hard=True)
            out = [client.result(r, timeout=300) for r in rids]
            assert out == ref
            c = fleet.router.counters
            assert c["replica_deaths"] == 1
            assert c["failed_over_requests"] >= 1
        finally:
            client.close()
            fleet.close()

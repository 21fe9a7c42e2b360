"""Live observability plane: heartbeats, RunMonitor watchdog, crash
flight recorder, OpenMetrics export, rlt_top.

Unit tier drives the monitor with a fake clock and synthetic beats;
integration tier (marked ``remote``) injects real hangs/crashes into
worker actors and asserts the acceptance criteria of ISSUE 3: stall
detected within K heartbeat intervals, a stack-dump event naming the
stalled rank in ``trainer.monitor_report``, clean abort at the
deadline, and a schema-valid flight bundle named by the raised error.
"""

import glob
import json
import logging
import os
import sys
import threading
import time
import urllib.request

import pytest

from ray_lightning_tpu.cluster.actor import ActorDiedError, RemoteError
from ray_lightning_tpu.core.callbacks import Callback
from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models.boring import BoringDataModule, BoringModel
from ray_lightning_tpu.parallel.strategies import LocalStrategy, RayStrategy
from ray_lightning_tpu.telemetry import (
    MonitorConfig,
    RunMonitor,
    Telemetry,
    TelemetryConfig,
)
from ray_lightning_tpu.telemetry.export_prom import (
    PromExporter,
    render_openmetrics,
)
from ray_lightning_tpu.telemetry.flight_recorder import FlightRecorder
from ray_lightning_tpu.telemetry.heartbeat import (
    HeartbeatPublisher,
    make_beat,
)
from ray_lightning_tpu.telemetry.logs import RankLogHandler
from utils import rlt_top_once
from ray_lightning_tpu.telemetry.schema import (
    validate_event,
    validate_flight_bundle,
    validate_heartbeat,
    validate_stream_item,
)


class _Ctx:
    """Duck-typed LoopContext stand-in for worker-side unit tests."""

    def __init__(self):
        self.global_step = 0
        self.micro_step = 0
        self.current_epoch = 0
        self.progress = 0
        self.phase = "train"
        self.telemetry_dir = None


class _ListSink:
    def __init__(self):
        self.items = []

    def put(self, item):
        self.items.append(item)


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _beat(rank=0, seq=1, step=0, progress=0, phase="train", done=False):
    ctx = _Ctx()
    ctx.global_step = step
    ctx.micro_step = step
    ctx.progress = progress
    ctx.phase = phase
    return make_beat(rank, seq, ctx, done=done)


def _monitor(clock, heartbeat_s=1.0, hang_intervals=2, **cfg_kw):
    cfg = MonitorConfig(
        heartbeat_s=heartbeat_s, hang_intervals=hang_intervals, **cfg_kw
    )
    return RunMonitor(cfg, world_size=2, now_fn=clock)


# ---------------------------------------------------------------------------
# Heartbeat publisher
# ---------------------------------------------------------------------------

class TestHeartbeat:
    def test_beat_schema_valid(self):
        beat = _beat(rank=3, seq=7, step=12, progress=40)
        assert validate_heartbeat(beat) == []
        assert beat["rank"] == 3 and beat["global_step"] == 12

    def test_publisher_beats_and_final_done(self):
        ctx, sink = _Ctx(), _ListSink()
        tel = Telemetry(TelemetryConfig(tier="cheap", heartbeat_s=0.05))
        pub = HeartbeatPublisher(0, ctx, sink, 0.05, telemetry=tel)
        pub.start()
        deadline = time.time() + 5
        while len(sink.items) < 3 and time.time() < deadline:
            ctx.progress += 1
            time.sleep(0.02)
        pub.stop(final=True)
        assert len(sink.items) >= 3, "publisher produced too few beats"
        problems = [
            p for b in sink.items for p in validate_stream_item(b)
        ]
        assert problems == []
        assert sink.items[-1].get("done") is True
        seqs = [b["seq"] for b in sink.items]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)

    def test_maybe_start_gates(self, tmp_path):
        ctx = _Ctx()
        off = Telemetry(TelemetryConfig(tier="off"))
        assert HeartbeatPublisher.maybe_start(off, ctx, None, None) is None
        disabled = Telemetry(TelemetryConfig(tier="cheap", heartbeat_s=0))
        assert (
            HeartbeatPublisher.maybe_start(disabled, ctx, None, None)
            is None
        )
        # No queue AND no telemetry dir: nowhere to publish.
        cheap = Telemetry(TelemetryConfig(tier="cheap", heartbeat_s=1))
        assert HeartbeatPublisher.maybe_start(cheap, ctx, None, None) is None
        # File sink engages once the dir exists.
        ctx.telemetry_dir = str(tmp_path)
        pub = HeartbeatPublisher.maybe_start(cheap, ctx, None, None)
        assert pub is not None
        pub.stop()
        assert (tmp_path / "heartbeats-rank0.jsonl").exists()

    def test_publisher_survives_dead_sink(self):
        class DeadSink:
            def put(self, item):
                raise ConnectionError("queue gone")

        ctx = _Ctx()
        pub = HeartbeatPublisher(0, ctx, DeadSink(), 0.01)
        pub.start()
        time.sleep(0.1)
        pub.stop(final=True)  # must not raise
        assert pub.beats_sent == 0


# ---------------------------------------------------------------------------
# RunMonitor watchdog rules (fake clock)
# ---------------------------------------------------------------------------

class TestRunMonitor:
    def test_tracks_ranks_and_progress(self):
        clock = _Clock()
        mon = _monitor(clock)
        mon.on_item(_beat(rank=0, seq=1, step=1, progress=1))
        mon.on_item(_beat(rank=1, seq=1, step=1, progress=1))
        snap = mon.snapshot()
        assert snap["ranks_reporting"] == 2
        assert snap["ranks"]["0"]["status"] == "ok"
        assert mon.beats_received == 2

    def test_stall_detected_within_k_intervals_and_dump_requested(self):
        clock = _Clock()
        dumps = []

        def dump_cb(rank):
            dumps.append(rank)
            return {"stacks": "thread 1: stuck in collective",
                    "device_memory": {"bytes_in_use": 5.0}}

        cfg = MonitorConfig(heartbeat_s=1.0, hang_intervals=2)
        mon = RunMonitor(cfg, world_size=2, now_fn=clock, dump_cb=dump_cb)
        # Both ranks make progress, then rank 1 freezes while its beats
        # keep flowing (the wedged-collective signature).
        for seq in range(1, 3):
            mon.on_item(_beat(rank=0, seq=seq, step=seq, progress=seq))
            mon.on_item(_beat(rank=1, seq=seq, step=seq, progress=seq))
            clock.advance(1.0)
            mon.tick()
        for seq in range(3, 7):
            mon.on_item(_beat(rank=0, seq=seq, step=seq, progress=seq))
            mon.on_item(_beat(rank=1, seq=seq, step=2, progress=2))
            clock.advance(1.0)
            mon.tick()
        kinds = [(e["kind"], e["rank"]) for e in mon.events]
        assert ("stall", 1) in kinds
        assert dumps == [1]
        dump_ev = next(e for e in mon.events if e["kind"] == "stack_dump")
        assert dump_ev["rank"] == 1
        assert "collective" in dump_ev["stacks"]
        assert dump_ev["device_memory"] == {"bytes_in_use": 5.0}
        assert all(validate_event(e) == [] for e in mon.events)
        # rank 0 kept advancing: never flagged
        assert ("stall", 0) not in kinds

    def test_heartbeat_lost_when_beats_stop(self):
        clock = _Clock()
        mon = _monitor(clock, heartbeat_s=1.0, hang_intervals=3)
        mon.on_item(_beat(rank=0, seq=1, step=1, progress=1))
        clock.advance(3.5)
        mon.tick()
        kinds = [e["kind"] for e in mon.events]
        assert "heartbeat_lost" in kinds
        assert mon.snapshot()["ranks"]["0"]["status"] == "lost"

    def test_compile_phase_never_flags(self):
        """Detection arms only after real progress — a long first
        compile (progress == 0) must not read as a hang."""
        clock = _Clock()
        mon = _monitor(clock, heartbeat_s=1.0, hang_intervals=2)
        for seq in range(1, 10):
            mon.on_item(_beat(rank=0, seq=seq, step=0, progress=0))
            clock.advance(1.0)
            mon.tick()
        assert [e for e in mon.events if e["kind"] == "stall"] == []

    def test_phase_change_rearms_detection(self):
        """A phase flip (train→validation) resets the arming: the first
        validation batch may hide a 20-40s eval compile that must not
        read as a hang.  Detection re-engages once the new phase shows
        progress and then freezes."""
        clock = _Clock()
        mon = _monitor(clock, heartbeat_s=1.0, hang_intervals=2)
        for seq in (1, 2):
            mon.on_item(_beat(rank=0, seq=seq, step=seq, progress=seq))
            clock.advance(1.0)
            mon.tick()
        # Validation starts; progress frozen through a long compile.
        for seq in range(3, 10):
            mon.on_item(_beat(rank=0, seq=seq, step=2, progress=2,
                              phase="validation"))
            clock.advance(1.0)
            mon.tick()
        assert [e for e in mon.events if e["kind"] == "stall"] == []
        # Progress inside validation, THEN a freeze: now it is a hang.
        mon.on_item(_beat(rank=0, seq=10, step=2, progress=3,
                          phase="validation"))
        for seq in range(11, 16):
            mon.on_item(_beat(rank=0, seq=seq, step=2, progress=3,
                              phase="validation"))
            clock.advance(1.0)
            mon.tick()
        assert [e for e in mon.events if e["kind"] == "stall"] != []

    def test_closing_phase_exempt_and_done_retires(self):
        clock = _Clock()
        mon = _monitor(clock, heartbeat_s=1.0, hang_intervals=2)
        mon.on_item(_beat(rank=0, seq=1, step=4, progress=9))
        clock.advance(1.0)
        mon.on_item(_beat(rank=0, seq=2, step=4, progress=9,
                          phase="closing"))
        for _ in range(5):
            clock.advance(1.0)
            mon.tick()
            mon.on_item(_beat(rank=0, seq=3, step=4, progress=9,
                              phase="closing"))
        assert [e for e in mon.events if e["kind"] == "stall"] == []
        mon.on_item(_beat(rank=0, seq=4, step=4, progress=9, done=True))
        clock.advance(10.0)
        mon.tick()
        assert [e for e in mon.events if e["kind"] == "heartbeat_lost"] == []
        assert mon.snapshot()["ranks"]["0"]["status"] == "done"

    def test_straggler_flagged_live(self):
        clock = _Clock()
        cfg = MonitorConfig(heartbeat_s=1.0, straggler_lag_steps=10)
        mon = RunMonitor(cfg, world_size=2, now_fn=clock)
        mon.on_item(_beat(rank=0, seq=1, step=100, progress=100))
        mon.on_item(_beat(rank=1, seq=1, step=50, progress=50))
        clock.advance(1.0)
        mon.tick()
        stragglers = [
            e for e in mon.events if e["kind"] == "straggler"
        ]
        assert len(stragglers) == 1 and stragglers[0]["rank"] == 1
        assert stragglers[0]["lag_steps"] >= 10

    def test_abort_after_deadline(self):
        clock = _Clock()
        aborts = []
        cfg = MonitorConfig(heartbeat_s=1.0, hang_intervals=2,
                            abort_after_s=3.0)
        mon = RunMonitor(cfg, world_size=1, now_fn=clock,
                         abort_cb=aborts.append)
        mon.on_item(_beat(rank=0, seq=1, step=1, progress=1))
        for seq in range(2, 10):
            mon.on_item(_beat(rank=0, seq=seq, step=1, progress=1))
            clock.advance(1.0)
            mon.tick()
        assert mon.aborted
        assert len(aborts) == 1 and "abort_after_s" in aborts[0]
        assert any(e["kind"] == "abort" for e in mon.events)
        report = mon.report()
        assert report["aborted"] and "abort_reason" in report

    def test_crash_event_tracks_bundle(self):
        clock = _Clock()
        mon = _monitor(clock)
        mon.on_item({"type": "event", "kind": "crash", "rank": 1,
                     "ts": time.time(), "error": "boom",
                     "bundle": "/tmp/b.json"})
        assert mon.crash_bundles() == ["/tmp/b.json"]
        assert mon.snapshot()["ranks"]["1"]["status"] == "crashed"

    def test_log_items_land_in_report(self):
        clock = _Clock()
        mon = _monitor(clock)
        mon.on_item({"type": "log", "rank": 0, "ts": 1.0,
                     "level": "WARNING", "logger": "x", "message": "m"})
        assert mon.report()["logs"]["0"][0]["message"] == "m"

    def test_live_json_written(self, tmp_path):
        clock = _Clock()
        cfg = MonitorConfig(heartbeat_s=1.0, out_dir=str(tmp_path),
                            live_every_s=0.0)
        mon = RunMonitor(cfg, world_size=1, now_fn=clock)
        mon.on_item(_beat(rank=0, seq=1, step=2, progress=2))
        clock.advance(1.0)
        mon.tick()
        mon.finalize()
        live = json.load(open(tmp_path / "live.json"))
        assert live["ranks"]["0"]["global_step"] == 2


# ---------------------------------------------------------------------------
# OpenMetrics export + rlt_top
# ---------------------------------------------------------------------------

class TestPromExport:
    def _snapshot(self):
        clock = _Clock()
        mon = _monitor(clock)
        mon.on_item(_beat(rank=0, seq=1, step=5, progress=5))
        return mon.snapshot(), mon.event_counts()

    def test_render_openmetrics(self):
        snap, counts = self._snapshot()
        text = render_openmetrics(snap, counts)
        assert 'rlt_rank_global_step{rank="0"} 5' in text
        assert "# TYPE rlt_fleet_ranks gauge" in text
        assert text.rstrip().endswith("# EOF")

    def test_textfile_and_http(self, tmp_path):
        snap, counts = self._snapshot()
        out = tmp_path / "rlt.prom"
        exporter = PromExporter(textfile=str(out), port=0)
        try:
            exporter.update(snap, counts)
            assert "rlt_rank_global_step" in out.read_text()
            assert exporter.port is not None
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics", timeout=10
            ).read().decode()
            assert 'rlt_rank_global_step{rank="0"} 5' in body
        finally:
            exporter.close()

    def test_rlt_top_renders_live_json(self, tmp_path):
        snap, _ = self._snapshot()
        (tmp_path / "live.json").write_text(json.dumps(snap, default=str))
        out = rlt_top_once(tmp_path)
        assert "rank" in out.stdout and "ok" in out.stdout


# ---------------------------------------------------------------------------
# Log ring + flight recorder (worker side, no actors)
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_log_ring_and_forwarding(self):
        sink = _ListSink()
        handler = RankLogHandler(2, queue=sink, ring_size=3,
                                 forward_cap=2).install()
        try:
            log = logging.getLogger("rlt.test.ring")
            for i in range(5):
                log.warning("w%d", i)
        finally:
            handler.uninstall()
        records = handler.records()
        assert [r["message"] for r in records] == ["w2", "w3", "w4"]
        assert len(sink.items) == 2  # forward cap holds
        assert all(validate_stream_item(i) == [] for i in sink.items)
        assert sink.items[0]["rank"] == 2

    def test_bundle_schema_and_contents(self, tmp_path, monkeypatch):
        # The bundle caps its stacks blob and lists threads by id, this
        # (the main) thread last: threads that earlier tests of the same
        # xdist worker left alive push it past the cap and cut this
        # thread's stack off.  Show the recorder this thread alone.
        me, frames = threading.get_ident(), sys._current_frames
        monkeypatch.setattr(sys, "_current_frames",
                            lambda: {me: frames()[me]})
        ctx = _Ctx()
        ctx.global_step, ctx.micro_step, ctx.progress = 4, 8, 12
        tel = Telemetry(TelemetryConfig(tier="full", heartbeat_s=0))
        with tel.span("dispatch"):
            pass
        tel.add_counter("checkpoint_writes", 1)
        handler = RankLogHandler(0, ring_size=5)
        handler.install()
        logging.getLogger("rlt.test.fr").warning("about to die")
        handler.uninstall()
        rec = FlightRecorder(0, str(tmp_path), ctx, telemetry=tel,
                             log_handler=handler)
        sink = _ListSink()
        rec._queue = sink
        try:
            raise RuntimeError("synthetic crash")
        except RuntimeError as err:
            path = rec.record_crash(err)
        doc = json.load(open(path))
        assert validate_flight_bundle(doc) == []
        assert "synthetic crash" in doc["error"]
        assert doc["global_step"] == 4 and doc["micro_step"] == 8
        assert doc["counters"]["checkpoint_writes"] == 1
        assert any(s["name"] == "dispatch" for s in doc["spans"])
        assert any("about to die" in r["message"] for r in doc["logs"])
        assert "test_bundle_schema_and_contents" in doc["stacks"]
        # The crash also travelled as an event naming the bundle.
        assert sink.items and sink.items[0]["bundle"] == path
        assert validate_stream_item(sink.items[0]) == []

    def test_bundles_disabled_still_cleans_up_plane(self, tmp_path):
        """RLT_FLIGHT_RECORDER=off gates the OUTPUT only: a crash must
        still stop the heartbeat thread and remove the log handler, or
        a disabled recorder would leak a publisher per failed fit."""

        class StubHeartbeat:
            stopped = None

            def stop(self, final=True, **kw):
                self.stopped = final

        ctx = _Ctx()
        handler = RankLogHandler(0, ring_size=5).install()
        hb = StubHeartbeat()
        rec = FlightRecorder(0, str(tmp_path), ctx, log_handler=handler,
                             heartbeat=hb, bundles_enabled=False)
        rec.install()
        try:
            raise RuntimeError("crash with output disabled")
        except RuntimeError as err:
            path = rec.record_crash(err)
        assert path is None
        assert list(tmp_path.iterdir()) == []  # no bundle, no fatal log
        assert hb.stopped is False  # stopped, without a "done" beat
        assert handler not in logging.getLogger().handlers

    def test_fixture_bundle_schema_valid(self):
        fixture = os.path.join(
            os.path.dirname(__file__), "data", "flight_bundle.json"
        )
        doc = json.load(open(fixture))
        assert validate_flight_bundle(doc) == []

    def test_off_tier_installs_nothing(self, tmp_path):
        tel = Telemetry(TelemetryConfig(tier="off"))
        ctx = _Ctx()
        ctx.telemetry_dir = str(tmp_path)
        assert FlightRecorder.maybe_install(tel, ctx, None) is None


# ---------------------------------------------------------------------------
# Trainer stream routing (the metrics rank-guard satellite)
# ---------------------------------------------------------------------------

class TestStreamRouting:
    def test_non_rank0_metrics_rejected(self):
        trainer = Trainer(strategy=LocalStrategy())
        trainer._on_stream_item(
            {"type": "metrics", "rank": 1, "metrics": {"loss": 99.0}}
        )
        assert "loss" not in trainer.callback_metrics
        trainer._on_stream_item(
            {"type": "metrics", "rank": 0, "metrics": {"loss": 1.0}}
        )
        assert trainer.callback_metrics["loss"] == 1.0

    def test_typed_items_route_to_monitor_not_metrics(self):
        trainer = Trainer(strategy=LocalStrategy())
        clock = _Clock()
        mon = _monitor(clock)
        trainer._attach_monitor(mon)
        trainer._on_stream_item(_beat(rank=0, seq=1, step=1, progress=1))
        trainer._on_stream_item({"type": "event", "kind": "stall",
                                 "rank": 0, "ts": 1.0})
        assert trainer.callback_metrics == {}
        assert mon.beats_received == 1 and len(mon.events) == 1
        trainer._adopt_monitor(mon)
        assert trainer.monitor_report["beats"] == 1
        assert trainer._monitor is None


# ---------------------------------------------------------------------------
# Integration: real worker actors (the ISSUE 3 acceptance criteria)
# ---------------------------------------------------------------------------

class _StallAt(Callback):
    """Wedge the loop thread mid-training — the observable behavior of
    a sleep inside training_step, injected host-side so it hits every
    step boundary deterministically."""

    def __init__(self, epoch=1, batch=0, sleep_s=300.0):
        self.epoch = epoch
        self.batch = batch
        self.sleep_s = sleep_s

    def on_train_batch_end(self, trainer, module, logs, batch_idx):
        if trainer.current_epoch == self.epoch and batch_idx == self.batch:
            time.sleep(self.sleep_s)


class _CrashAt(Callback):
    def on_train_batch_end(self, trainer, module, logs, batch_idx):
        if batch_idx == 1:
            raise RuntimeError("injected mid-fit crash")


@pytest.mark.remote
class TestLivePlaneIntegration:
    @pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
    def test_hang_detected_dumped_and_aborted(self, tmp_path):
        """Acceptance: a stalled worker is detected within K heartbeat
        intervals, a stack-dump event names the stalled rank in
        ``trainer.monitor_report["events"]``, and the fit aborts
        cleanly when the deadline is set."""
        trainer = Trainer(
            strategy=RayStrategy(
                num_workers=1,
                telemetry={"tier": "cheap", "heartbeat_s": 0.2},
                monitor={"hang_intervals": 2, "abort_after_s": 1.0},
            ),
            max_epochs=1,
            default_root_dir=str(tmp_path),
            # batch 1: the rank has shown progress, so stall detection
            # is armed (batch 0 would read as a long compile).
            callbacks=[_StallAt(epoch=0, batch=1)],
        )
        with pytest.raises(ActorDiedError) as excinfo:
            trainer.fit(BoringModel(), BoringDataModule())
        report = trainer.monitor_report
        kinds = [(e["kind"], e["rank"]) for e in report["events"]]
        assert ("stall", 0) in kinds
        dump = next(
            e for e in report["events"] if e["kind"] == "stack_dump"
        )
        assert dump["rank"] == 0
        # The dump reached INTO the wedged call: the fit loop's frames
        # are visible even though the actor was mid-call.
        assert "run_fit" in dump["stacks"]
        assert report["aborted"]
        assert "RunMonitor" in str(excinfo.value)
        assert excinfo.value.rank == 0
        assert excinfo.value.last_heartbeat_age_s is not None

    def test_crash_leaves_bundle_and_error_names_it(self, tmp_path):
        """Acceptance: a worker raising mid-fit leaves a schema-valid
        flight bundle on disk and the driver-side error names it."""
        trainer = Trainer(
            strategy=RayStrategy(
                num_workers=1,
                telemetry={"tier": "cheap", "heartbeat_s": 0.2},
            ),
            max_epochs=1,
            default_root_dir=str(tmp_path),
            callbacks=[_CrashAt()],
        )
        with pytest.raises(RemoteError) as excinfo:
            trainer.fit(BoringModel(), BoringDataModule())
        bundles = glob.glob(
            str(tmp_path / "telemetry" / "flight" / "bundle-*.json")
        )
        assert len(bundles) == 1
        doc = json.load(open(bundles[0]))
        assert validate_flight_bundle(doc) == []
        assert "injected mid-fit crash" in doc["traceback"]
        assert bundles[0] in str(excinfo.value)
        assert trainer.monitor_report["crash_bundles"] == bundles

    def test_worker_death_report_enriched(self, tmp_path):
        """Satellite: ActorDiedError carries exit code + rank +
        last-heartbeat age, so the report says when/how, not just that."""

        class Die(Callback):
            def on_train_batch_end(self, trainer, module, logs, batch_idx):
                if batch_idx == 1:
                    os._exit(7)

        trainer = Trainer(
            strategy=RayStrategy(
                num_workers=1,
                telemetry={"tier": "cheap", "heartbeat_s": 0.2},
            ),
            max_epochs=1,
            default_root_dir=str(tmp_path),
            callbacks=[Die()],
        )
        with pytest.raises(ActorDiedError) as excinfo:
            trainer.fit(BoringModel(), BoringDataModule())
        err = excinfo.value
        assert err.rank == 0
        assert err.exit_code == 7
        assert err.last_heartbeat_age_s is not None
        assert "exit_code=7" in str(err)

    def test_off_tier_installs_no_plane(self, tmp_path):
        """Acceptance: telemetry="off" → no publisher, no monitor, no
        new metric keys, no live artifacts."""
        trainer = Trainer(
            strategy=RayStrategy(num_workers=1, telemetry="off"),
            max_epochs=1,
            default_root_dir=str(tmp_path),
        )
        trainer.fit(BoringModel(), BoringDataModule())
        assert trainer.monitor_report == {}
        assert "step_time_ms" not in trainer.callback_metrics
        tel_dir = tmp_path / "telemetry"
        assert not list(tel_dir.glob("heartbeats-*")) if tel_dir.exists() \
            else True
        assert not (tel_dir / "live.json").exists()
        assert not (tel_dir / "flight").exists()

    def test_healthy_fit_clean_report_and_live_json(self, tmp_path):
        """A healthy monitored fit: beats arrive, no events, live.json
        reflects the final state, the rank retires as done."""
        trainer = Trainer(
            strategy=RayStrategy(
                num_workers=1,
                # Beats of 0.1 s: a worker starved for 0.3 s under
                # tier-1's load read as a lost heartbeat (PR 32).
                telemetry={"tier": "cheap", "heartbeat_s": 0.5},
            ),
            max_epochs=1,
            default_root_dir=str(tmp_path),
        )
        trainer.fit(BoringModel(), BoringDataModule())
        report = trainer.monitor_report
        assert report["beats"] >= 1
        assert report["events"] == []
        assert not report["aborted"]
        live = json.load(open(tmp_path / "telemetry" / "live.json"))
        assert live["ranks"]["0"]["status"] == "done"

    def test_heartbeat_overhead_smoke(self, tmp_path):
        """LOOSE wall-clock bound (the publisher's cost on the chip is
        not measured): an aggressive 20ms cadence must
        not change the fit's cost class vs a publisher-less run."""

        def run(hb, sub):
            t0 = time.time()
            trainer = Trainer(
                strategy=LocalStrategy(
                    telemetry={"tier": "cheap", "heartbeat_s": hb}
                ),
                max_epochs=2,
                default_root_dir=str(tmp_path / sub),
                enable_checkpointing=False,
                limit_val_batches=0,
            )
            trainer.fit(BoringModel(),
                        BoringDataModule(length=128, batch_size=16))
            return time.time() - t0

        silent = run(0, "off")
        beating = run(0.02, "on")
        assert beating < silent * 1.5 + 1.0, (
            f"heartbeat wall {beating:.2f}s vs silent {silent:.2f}s"
        )

    def test_dump_stacks_control_lane_mid_call(self):
        """The control lane answers while a call is in flight — the
        mechanism the watchdog's dumps depend on."""
        from ray_lightning_tpu.cluster.actor import ProcessActor

        actor = ProcessActor(name="ctl-actor")
        try:
            fut = actor.submit(time.sleep, 1.5)
            time.sleep(0.2)  # let the call start
            dump = actor.dump_stacks(timeout=10)
            assert "rlt-actor-calls" in dump["stacks"]
            assert not fut.done()  # dump answered while call still ran
            fut.result(timeout=30)
        finally:
            actor.kill()

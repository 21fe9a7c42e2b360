"""Fleet SLO & capacity plane tests (ISSUE 18): the bounded
time-series store's windowed queries, Google-SRE multi-window
burn-rate alerting (fire / dedup / re-arm / fast-spike silence), the
headroom oracle's measured-phase-cost tick model with its sampled-gauge
fallback, the fleet fold, the engine integration (plane on → schema-
valid ``capacity`` block on every snapshot + ``rlt_capacity_*`` /
``rlt_slo_*`` prom families) and the rlt_top capacity pane with its
staleness tag.

Everything below the engine class is jax-free and clock-driven
(RLT004): no sleeps, no wall-clock flake.  The saturation prediction
against a measured Poisson knee has not been measured on the chip (no
open-loop cell yet) — here we pin the math on synthetic counters.
"""

import time

import pytest

from ray_lightning_tpu.serve.capacity import (
    CapacityOracle, aggregate_fleet,
)
from ray_lightning_tpu.serve.metrics import ServeStats
from ray_lightning_tpu.telemetry.export_prom import render_openmetrics
from ray_lightning_tpu.telemetry.schema import (
    validate_capacity_snapshot,
    validate_serve_snapshot,
    validate_slo_alert,
    validate_timeseries_point,
)
from ray_lightning_tpu.telemetry.slo import (
    SloEvaluator, SloSpec, default_serve_slos,
)
from ray_lightning_tpu.telemetry.timeseries import TimeSeriesStore

pytestmark = pytest.mark.serve


class _Clock:
    """Injectable wall clock — tests advance time explicitly."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# TimeSeriesStore: binning, windowed queries, persistence shape
# ---------------------------------------------------------------------------

class TestTimeSeriesStore:
    def test_fixed_interval_binning_is_bounded(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, capacity=4, clock=clock)
        for i in range(10):
            clock.t = 1000.0 + i
            store.observe("g", float(i))
        points = store.series("g")
        assert len(points) == 4          # ring dropped the oldest bins
        assert [v for _, v in points] == [6.0, 7.0, 8.0, 9.0]
        assert points[-1][0] == 1009.0   # bin_start_ts, not raw ts

    def test_gauge_last_write_wins_within_bin(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, clock=clock)
        store.observe("g", 1.0)
        store.observe("g", 2.0)          # same bin
        assert store.last("g") == 2.0
        assert len(store.series("g")) == 1

    def test_counter_rate_is_reset_safe(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, clock=clock)
        # Cumulative 0, 10, 20, then a restart back to 5: the ramp
        # restarts at 0, so the window saw 10+10+5 increments over 3s.
        for i, total in enumerate((0.0, 10.0, 20.0, 5.0)):
            clock.t = 1000.0 + i
            store.observe("c", total, kind="counter")
        assert store.rate("c", 10.0) == pytest.approx(25.0 / 3.0)

    def test_rate_wants_a_counter(self):
        store = TimeSeriesStore(clock=_Clock())
        store.observe("g", 1.0)
        store.observe("g", 2.0, ts=1002.0)
        with pytest.raises(ValueError, match="wants a counter"):
            store.rate("g", 10.0)

    def test_kind_mismatch_raises(self):
        store = TimeSeriesStore(clock=_Clock())
        store.observe("x", 1.0, kind="gauge")
        with pytest.raises(ValueError, match="is a gauge"):
            store.observe("x", 1.0, kind="counter")

    def test_out_of_order_past_live_bin_dropped(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, clock=clock)
        store.observe("g", 1.0, ts=1005.0)
        store.observe("g", 9.0, ts=1001.0)   # older than the live bin
        assert store.series("g") == [(1005.0, 1.0)]

    def test_hist_percentile_merges_bins(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, clock=clock)
        for i in range(10):
            store.observe("h", float(i), kind="hist",
                          ts=1000.0 + i * 0.5)
        assert store.percentile("h", 0.0, 60.0) == 0.0
        assert store.percentile("h", 100.0, 60.0) == 9.0
        assert store.percentile("h", 50.0, 60.0) in (4.0, 5.0)

    def test_slope_and_eta_to_threshold(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, clock=clock)
        for i in range(5):
            store.observe("free", 100.0 - 10.0 * i, ts=1000.0 + i)
        assert store.slope("free", 60.0) == pytest.approx(-10.0)
        # 60 units above zero, draining 10/s → 6s out.
        assert store.eta_to("free", 0.0, 60.0) == pytest.approx(6.0)
        # Trend pointing AWAY from the threshold: no crossing.
        assert store.eta_to("free", 200.0, 60.0) is None

    def test_points_are_schema_valid(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, clock=clock)
        store.observe("c", 5.0, kind="counter")
        store.observe("g", 1.5)
        store.observe("h", 3.0, kind="hist")
        points = store.points()
        assert len(points) == 3
        for point in points:
            assert validate_timeseries_point(point, "test") == []

    def test_dump_jsonl_appends(self, tmp_path):
        store = TimeSeriesStore(clock=_Clock())
        store.observe("g", 1.0)
        path = str(tmp_path / "ts.jsonl")
        assert store.dump_jsonl(path) == 1
        assert store.dump_jsonl(path) == 1
        assert len(open(path).read().splitlines()) == 2


# ---------------------------------------------------------------------------
# SloEvaluator: multi-window burn-rate semantics
# ---------------------------------------------------------------------------

def _ratio_spec(windows=((2.0, 6.0, 1.0),)):
    # target 0.5 → budget 0.5 → burn = 2·error_rate; fires at err ≥ 0.5
    # in BOTH the 2s and the 6s window.
    return SloSpec(name="avail", target=0.5, mode="ratio",
                   bad="rejected", total="submitted", windows=windows)


class _SloRig:
    """Store + evaluator on a fake clock, with a per-second feeder."""

    def __init__(self, spec):
        self.clock = _Clock()
        self.store = TimeSeriesStore(interval_s=1.0, clock=self.clock)
        self.emitted = []
        self.ev = SloEvaluator(self.store, [spec], clock=self.clock,
                               emit=self.emitted.append)
        self._submitted = 0.0
        self._rejected = 0.0

    def tick(self, submitted=10.0, rejected=0.0):
        self.clock.t += 1.0
        self._submitted += submitted
        self._rejected += rejected
        self.store.observe("submitted", self._submitted, kind="counter")
        self.store.observe("rejected", self._rejected, kind="counter")
        return self.ev.evaluate()


class TestSloEvaluator:
    def test_fires_when_both_windows_burn(self):
        rig = _SloRig(_ratio_spec())
        alerts = []
        for _ in range(8):
            alerts += rig.tick(rejected=10.0)   # 100% errors
        assert len(alerts) == 1                 # deduplicated while firing
        assert rig.emitted == alerts
        assert validate_slo_alert(alerts[0], "test") == []
        detail = alerts[0]["detail"]
        assert detail["slo"] == "avail"
        assert detail["burn_rate"] >= 1.0
        assert rig.ev.alerts_total == 1

    def test_fast_spike_alone_stays_silent(self):
        rig = _SloRig(_ratio_spec())
        alerts = []
        for _ in range(7):
            alerts += rig.tick()                # clean history
        for _ in range(2):
            alerts += rig.tick(rejected=10.0)   # 2s burst: fast burns,
        assert alerts == []                     # slow window holds it

    def test_rearm_after_recovery_fires_again(self):
        rig = _SloRig(_ratio_spec())
        for _ in range(8):
            rig.tick(rejected=10.0)
        assert rig.ev.alerts_total == 1
        for _ in range(10):
            rig.tick()                          # recover: burn → 0
        assert rig.ev.snapshot()["avail"]["firing"] is False
        fired = []
        for _ in range(8):
            fired += rig.tick(rejected=10.0)
        assert len(fired) == 1                  # re-armed, new alert
        assert rig.ev.alerts_total == 2

    def test_threshold_mode_counts_over_bins(self):
        clock = _Clock()
        store = TimeSeriesStore(interval_s=1.0, clock=clock)
        spec = SloSpec(name="wait", target=0.5, mode="threshold",
                       gauge="queue_wait_p50_ms", threshold=100.0,
                       windows=((2.0, 6.0, 1.0),))
        ev = SloEvaluator(store, [spec], clock=clock)
        for i in range(8):
            clock.t += 1.0
            store.observe("queue_wait_p50_ms", 500.0)
            out = ev.evaluate()
        assert len(out) == 0                    # fired on an EARLIER pass
        assert ev.alerts_total == 1
        snap = ev.snapshot()["wait"]
        assert snap["firing"] is True
        assert snap["burn_rate"] == pytest.approx(2.0)

    def test_no_data_means_no_alert(self):
        rig = _SloRig(_ratio_spec())
        assert rig.ev.evaluate() == []
        assert rig.ev.snapshot()["avail"]["burn_rate"] == 0.0

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="outside"):
            SloSpec(name="bad", target=1.5)
        with pytest.raises(ValueError, match="needs bad"):
            SloSpec(name="bad", target=0.9, mode="ratio")
        with pytest.raises(ValueError, match="needs gauge"):
            SloSpec(name="bad", target=0.9, mode="threshold")
        with pytest.raises(ValueError, match="unknown mode"):
            SloSpec(name="bad", target=0.9, mode="latency")
        store = TimeSeriesStore(clock=_Clock())
        with pytest.raises(ValueError, match="duplicate"):
            SloEvaluator(store, [_ratio_spec(), _ratio_spec()])

    def test_default_serve_slos_cover_both_modes(self):
        specs = default_serve_slos()
        modes = {s.mode for s in specs}
        assert modes == {"ratio", "threshold"}


# ---------------------------------------------------------------------------
# CapacityOracle: tick-cost model, fallback, prediction, fleet fold
# ---------------------------------------------------------------------------

class _OracleRig:
    def __init__(self, interval_s=1.0):
        self.clock = _Clock()
        self.oracle = CapacityOracle(interval_s=interval_s,
                                     window_s=60.0, clock=self.clock)
        self.counters = {}

    def feed(self, gauges=None, **deltas):
        """Advance 1s and feed one stats view with counter DELTAS
        (accumulated here into the cumulative totals the oracle
        differences back out)."""
        self.clock.t += 1.0
        for name, d in deltas.items():
            self.counters[name] = self.counters.get(name, 0) + d
        self.oracle.observe({
            "ts": self.clock.t,
            "counters": dict(self.counters),
            "gauges": dict(gauges or {}),
            "latency": {},
        })


class TestCapacityOracle:
    # Synthetic ground truth for the affine tick-cost model:
    # tick_us = C + H·busy, one admission costs ADMIT_US.
    C_US, H_US, ADMIT_US = 20000.0, 1000.0, 5000.0

    def _feed_tick_bins(self, rig, busies, ticks=10, admitted=2):
        for busy in busies:
            rig.feed(
                gauges={"num_slots": 8.0, "slots_active": float(busy)},
                decode_steps=ticks,
                decode_us=ticks * (self.C_US + self.H_US * busy),
                tokens_out=ticks * busy + admitted,
                admitted=admitted,
                admit_us=admitted * self.ADMIT_US,
                submitted=admitted,
            )

    def test_tick_model_recovers_synthetic_costs(self):
        rig = _OracleRig()
        self._feed_tick_bins(rig, [1, 3, 5, 7, 2, 4, 6, 8, 1, 5, 3, 7])
        model = rig.oracle._tick_model(60.0)
        assert model is not None
        assert model["c_us"] == pytest.approx(self.C_US, rel=1e-6)
        assert model["h_us"] == pytest.approx(self.H_US, rel=1e-6)
        assert model["admit_s"] == pytest.approx(self.ADMIT_US / 1e6)

        snap = rig.oracle.snapshot(60.0)
        assert validate_capacity_snapshot(snap, "test") == []
        # Full-width tick: 20000 + 1000·8 = 28ms for 8 tokens.
        assert snap["capacity_tokens_per_s"] == \
            pytest.approx(8.0 / 0.028, rel=1e-6)

        # Knee: admit + 15 full-width tick shares per request.
        pred = rig.oracle.predict_saturation_rps(16, window_s=60.0)
        per_req = self.ADMIT_US / 1e6 + 15 * 0.028 / 8
        assert pred == pytest.approx(1.0 / per_req, rel=1e-6)

    def test_saturated_window_degrades_to_median_tick(self):
        rig = _OracleRig()
        self._feed_tick_bins(rig, [8] * 10)     # zero occupancy spread
        model = rig.oracle._tick_model(60.0)
        assert model is not None
        assert model["h_us"] == 0.0
        assert model["c_us"] == pytest.approx(
            self.C_US + self.H_US * 8, rel=1e-6)

    def test_counter_reset_rows_are_skipped(self):
        rig = _OracleRig()
        self._feed_tick_bins(rig, [1, 3, 5, 7, 2, 4])
        rig.counters = {}                       # engine restart
        self._feed_tick_bins(rig, [6, 8, 1, 5, 3, 7])
        model = rig.oracle._tick_model(60.0)
        assert model is not None                # reset row dropped, not
        assert model["c_us"] == pytest.approx(  # poisoning the fit
            self.C_US, rel=1e-6)

    def test_gauge_fallback_without_tick_counters(self):
        rig = _OracleRig()
        for _ in range(6):
            rig.feed(gauges={"num_slots": 8.0, "slots_active": 2.0},
                     tokens_out=20, submitted=2)
        snap = rig.oracle.snapshot(60.0)
        assert validate_capacity_snapshot(snap, "test") == []
        # 20 tok/s over 2 busy slots → 10/slot → 80 at full width.
        assert snap["service_rate_per_slot"] == pytest.approx(10.0)
        assert snap["capacity_tokens_per_s"] == pytest.approx(80.0)
        assert snap["utilization"] == pytest.approx(0.25)
        assert snap["headroom_tokens_per_s"] == pytest.approx(60.0)
        # No phase-cost model → token-capacity fallback prediction.
        assert rig.oracle.predict_saturation_rps(16, window_s=60.0) \
            == pytest.approx(5.0)

    def test_kv_eta_and_rejection_rate(self):
        rig = _OracleRig()
        free = 120.0
        for _ in range(6):
            rig.feed(gauges={"num_slots": 8.0, "slots_active": 2.0,
                             "blocks_free": free},
                     tokens_out=20, submitted=10, rejected=1)
            free -= 10.0
        snap = rig.oracle.snapshot(60.0)
        assert snap["kv_exhaustion_eta_s"] == pytest.approx(7.0)
        assert snap["rejection_rate"] == pytest.approx(0.1)

    def test_fresh_oracle_refuses_to_guess(self):
        oracle = CapacityOracle(clock=_Clock())
        assert oracle.predict_saturation_rps(16) is None
        snap = oracle.snapshot()
        assert snap["capacity_tokens_per_s"] is None
        assert validate_capacity_snapshot(snap, "test") == []

    def test_aggregate_fleet_folds_and_takes_worst_eta(self):
        a = {"tokens_per_s": 100.0, "capacity_tokens_per_s": 200.0,
             "kv_exhaustion_eta_s": 30.0}
        b = {"tokens_per_s": 50.0, "capacity_tokens_per_s": 100.0,
             "kv_exhaustion_eta_s": 12.0}
        fleet = aggregate_fleet([a, None, b])
        assert fleet["replicas_reporting"] == 2
        assert fleet["tokens_per_s"] == pytest.approx(150.0)
        assert fleet["capacity_tokens_per_s"] == pytest.approx(300.0)
        assert fleet["headroom_tokens_per_s"] == pytest.approx(150.0)
        assert fleet["utilization"] == pytest.approx(0.5)
        assert fleet["kv_exhaustion_eta_s"] == 12.0   # first to exhaust
        assert aggregate_fleet([None, 3, "x"]) is None

    def test_capacity_view_is_the_cheap_slice(self):
        stats = ServeStats()
        stats.bump("tokens_out", 7)
        view = stats.capacity_view()
        assert view["counters"]["tokens_out"] == 7
        assert "gauges" in view and "ts" in view
        assert view["latency"] == {}            # no reservoir sorts


# ---------------------------------------------------------------------------
# Engine integration: plane on → schema-valid snapshot + prom families
# ---------------------------------------------------------------------------

class TestEnginePlane:
    @pytest.fixture(scope="class")
    def model(self):
        import jax

        from ray_lightning_tpu.models.gpt import GPT, GPTConfig

        cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=4,
                        d_model=64, seq_len=64, warmup_steps=1)
        m = GPT(cfg, attn_impl="xla")
        return m, m.init_params(jax.random.PRNGKey(0))

    def _engine(self, model, **kw):
        from ray_lightning_tpu.serve.engine import (
            ServeConfig, ServeEngine,
        )

        m, params = model
        cfg = ServeConfig(num_slots=2, num_blocks=24, block_size=8,
                          export_every_s=0.05, **kw)
        return ServeEngine(m, params, cfg)

    def test_plane_on_snapshot_and_prom(self, model):
        eng = self._engine(model, capacity=True, slo=True,
                           ts_interval_s=0.1)
        try:
            assert eng.capacity_oracle is not None
            assert eng.slo_evaluator is not None
            for seed in range(3):
                eng.generate([seed + 1, 5, 9], 4)
            counters = eng.stats.snapshot()["counters"]
            # The engine feeds the oracle real phase costs.
            assert counters["decode_us"] > 0
            assert counters["admit_us"] > 0
            eng.slo_evaluator.evaluate()
            eng._maybe_export(force=True)

            snap = eng.snapshot()
            assert validate_serve_snapshot(snap, "test") == []
            assert "capacity" in snap
            assert validate_capacity_snapshot(snap["capacity"],
                                              "test") == []

            text = render_openmetrics(
                {"serve": snap, "slo": eng.slo_evaluator.snapshot()}
            )
            assert "rlt_capacity_tokens_per_sec" in text
            assert "rlt_capacity_rejection_rate" in text
            assert 'rlt_slo_burn_rate{slo="serve_availability"}' in text
        finally:
            eng.stop()

    def test_plane_off_has_no_capacity_block(self, model):
        eng = self._engine(model)
        try:
            eng.generate([1, 5, 9], 4)
            assert eng.capacity_oracle is None
            assert eng.slo_evaluator is None
            snap = eng.snapshot()
            assert "capacity" not in snap
            assert validate_serve_snapshot(snap, "test") == []
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# rlt_top capacity pane + staleness tag; fleet fold in the router pane
# ---------------------------------------------------------------------------

class TestRltTopPane:
    def _serve_snapshot(self):
        return {
            "ts": 1000.0,
            "serve": {
                "counters": {"completed": 4, "submitted": 5},
                "gauges": {"slots_active": 1.0},
                "latency": {},
                "capacity": {
                    "tokens_per_s": 40.0,
                    "capacity_tokens_per_s": 80.0,
                    "headroom_tokens_per_s": 40.0,
                    "utilization": 0.5,
                    "kv_exhaustion_eta_s": 12.0,
                    "queue_depth": 2.0,
                },
            },
            "slo": {"avail": {"firing": True, "burn_rate": 3.2,
                              "error_rate": 0.04, "target": 0.99,
                              "alerts_total": 1}},
        }

    def test_capacity_pane_renders_with_sparkline(self):
        from tools import rlt_top

        snap = self._serve_snapshot()
        history = {}
        for load in (10.0, 20.0, 40.0):
            snap["serve"]["capacity"]["tokens_per_s"] = load
            rlt_top.note_history(snap, history)
        text = rlt_top.render(snap, "test", history=history,
                              now=1001.0)
        assert "capacity:" in text
        assert "ceiling 80.0" in text
        assert "avail" in text and "3.2" in text   # SLO line
        assert "STALE" not in text

    def test_stale_tag_marks_dead_source(self):
        from tools import rlt_top

        text = rlt_top.render(self._serve_snapshot(), "test",
                              now=1000.0 + 3600.0)
        assert "STALE" in text

    def test_router_pane_renders_fleet_fold(self):
        from tools import rlt_top

        snap = {
            "ts": 1000.0,
            "router": {
                "replicas": {}, "counters": {},
                "capacity": aggregate_fleet([
                    {"tokens_per_s": 100.0,
                     "capacity_tokens_per_s": 200.0},
                    {"tokens_per_s": 60.0,
                     "capacity_tokens_per_s": 100.0},
                ]),
            },
        }
        text = rlt_top.render(snap, "test", now=1001.0)
        assert "ceiling 300.0" in text

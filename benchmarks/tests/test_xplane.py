"""Interval, idle-gap and self-time arithmetic on hand-made intervals,
and the ``.xplane.pb`` adapter on a trace recorded here on the CPU."""

import time

import pytest

from benchmarks.lib import xplane


def test_union_counts_nested_and_overlapping_once():
    ivs = [(0, 10), (2, 4), (8, 15), (20, 30), (30, 31)]
    assert xplane.union(ivs) == [(0, 15), (20, 31)]
    assert xplane.total(xplane.union(ivs)) == 26


def test_clip_and_gaps():
    busy = xplane.union(xplane.clip([(0, 10), (12, 18), (25, 40)], (5, 30)))
    assert busy == [(5, 10), (12, 18), (25, 30)]
    assert xplane.gaps(busy, (5, 30)) == [(10, 12), (18, 25)]
    assert xplane.gaps([], (0, 7)) == [(0, 7)]
    assert xplane.gaps([(2, 3)], (0, 7)) == [(0, 2), (3, 7)]


def test_self_time_does_not_count_a_loop_body_twice():
    events = [("while", 0, 100), ("fusion.1", 0, 40), ("fusion.2", 50, 90),
              ("inner", 55, 60), ("copy", 100, 110)]
    got = dict(xplane.self_times(events))
    assert got == {"while": 20, "fusion.1": 40, "fusion.2": 35,
                   "inner": 5, "copy": 10}


def _trace():
    dev = xplane.DeviceTrace(
        "/device:TPU:0",
        modules=[("jit_a(1)", 0, 40), ("jit_b(2)", 50, 90),
                 ("jit_a(1)", 95, 130)],
        ops=[("while.3", 0, 40), ("fusion.7", 0, 30), ("fusion.8", 50, 90),
             ("fusion.7", 95, 120)],
    )
    return xplane.Trace([dev], [("bench:window", 0, 100),
                                ("bench:sleep", 41, 49)], (0.0, 100.0))


def test_busy_idle_and_modules_of_a_reduced_trace():
    t = _trace()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx((40 + 40 + 5) * 1e-9)
    # the third jit_a run crosses the window's end: not a whole run
    assert t.module_runs("^jit_a") == [pytest.approx(40e-9)]
    assert t.op_runs(r"^fusion\.7") == [pytest.approx(30e-9)]
    gaps = dict(t.idle_gaps())
    assert any(k.startswith("bench:sleep|after:jit_a") for k in gaps)
    assert sum(gaps.values()) == pytest.approx(15e-9)
    top = dict(t.top_ops())
    assert top["fusion"] == pytest.approx(70e-9)
    assert top["while"] == pytest.approx(10e-9)


def test_loader_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION):
        for _ in range(3):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:pause"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    assert path is not None
    t = xplane.load(path, "host-xla")
    assert t.window_s > 0.03
    assert 0 < t.busy_s < t.window_s
    assert {n for n, _, _ in t.annotations} >= {"bench:window", "bench:pause"}
    assert t.top_ops()
    assert any("bench:pause" in n for n, _ in t.idle_gaps())
    # a TPU-plane read of a CPU trace finds no device: nothing to report
    none = xplane.load(path)
    assert none.devices == [] and none.busy_s == 0.0

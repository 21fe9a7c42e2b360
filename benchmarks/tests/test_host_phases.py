"""Idle gaps against the program's ``rlt:`` phases, on hand-made
intervals."""

import pytest

from benchmarks.lib import host_phases, xplane


def _case():
    # Device busy 0-100 and 160-300; idle 100-160 (60) and 300-320 (20).
    dev = xplane.DeviceTrace(
        "/device:TPU:0", modules=[("jit__decode(1)", 0, 100),
                                  ("jit__decode(1)", 160, 300)],
        ops=[("fusion.1", 0, 100), ("fusion.2", 160, 300)])
    trace = xplane.Trace([dev], [], (0.0, 320.0))
    phases = sorted([
        ("rlt:serve/decode_wait", 0, 101), ("rlt:serve/emit", 101, 141),
        ("rlt:serve/housekeep", 141, 145),
        ("rlt:serve/decode_dispatch", 145, 165),
        ("rlt:request/first_token", 110, 120),          # nested in emit
        ("rlt:serve/decode_wait", 165, 301), ("rlt:serve/emit", 301, 330),
    ], key=lambda r: r[1])
    return trace, phases


def test_a_gap_belongs_to_the_phase_open_at_its_middle():
    trace, phases = _case()
    got = host_phases.idle_by_phase(trace, phases)
    # Middle of 100-160 is 130: emit (the nested span closed at 120);
    # middle of 300-320 is 310: the second emit.
    assert got == {"rlt:serve/emit": (2, pytest.approx(80e-9))}
    starts = [a for _, a, _ in phases]
    assert host_phases.phase_at(phases, starts, 115) == \
        "rlt:request/first_token"                       # the innermost
    assert host_phases.phase_at(phases, starts, 400) == "unattributed"


def test_overlap_splits_a_gap_over_the_phases_it_spans():
    trace, phases = _case()
    over = host_phases.idle_overlap(trace, phases)
    assert over["rlt:serve/decode_wait"] == pytest.approx(2e-9)   # 100-101, 300-301
    assert over["rlt:serve/emit"] == pytest.approx((40 + 19) * 1e-9)
    assert over["rlt:serve/housekeep"] == pytest.approx(4e-9)
    assert over["rlt:serve/decode_dispatch"] == pytest.approx(15e-9)
    tiled = sum(v for k, v in over.items() if k.startswith("rlt:serve/"))
    assert tiled == pytest.approx(80e-9)                 # all of the idle


def test_host_seconds_clip_to_the_window():
    trace, phases = _case()
    host = host_phases.host_seconds(trace, phases)
    assert host["rlt:serve/emit"] == (2, pytest.approx((40 + 19) * 1e-9))
    assert host["rlt:serve/decode_wait"][0] == 2

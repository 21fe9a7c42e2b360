"""The per-layer metrics that read what the serve loop records of
itself (``serve/metrics.py`` ``LoopWatch``: thread CPU, collector
pauses, stalled iterations; the prefills' positions) and the prefill
programs' names (``jit__prefill_b<bucket>``).  Their files are here,
ready; no accepted cell lists them yet (a cell prints only what its own
workload file lists, and that file is not a later PR's to edit).  So:
on a temporary copy whose serve workload files list them, each file
loads, agrees with its reader, and reads the expected number from
made-up counters and a made-up trace; a missing counter or a prefill
without its bucket in its name leaves the metric out, never 0.
"""

import json
import shutil

import pytest

from benchmarks.lib import manifest, xplane

COUNTER = ["stall_share_pct.serve", "loop_cpu_share_pct.serve",
           "gc_pause_ms_per_s.serve", "prefill_padding_pct.serve"]
TRACE = ["prefill_share_pct.serve", "prefill_us_per_position.serve"]
CELLS = ["gpt2-large.serve-long", "k-exaone-236b-a23b-ep8.serve-mixed",
         "sarvam-105b-ep8.serve-longctx"]
FILES = {
    "stall_share_pct.serve": ("%", "program_span", "serve_tokens_per_s"),
    "loop_cpu_share_pct.serve": ("%", "program_span", "serve_tokens_per_s"),
    "gc_pause_ms_per_s.serve": ("ms/s", "program_span", "itl_p95_ms"),
    "prefill_padding_pct.serve": ("%", "program_counter",
                                  "serve_tokens_per_s"),
    "prefill_share_pct.serve": ("%", "device_trace", "serve_tokens_per_s"),
    "prefill_us_per_position.serve": ("us/position", "device_trace",
                                      "itl_p95_ms"),
}

# One window of a made-up serve run: 45 s of the loop's wall of which 5
# idle, one iteration of 3 s stalled, the thread on a CPU for 10 s, the
# collector for 90 ms; 100 prefills of 300,000 positions for prompts of
# 225,000.
COUNTERS = {
    "ticks": 4000, "tick_us": 45_000_000, "tick_idle_us": 5_000_000,
    "ticks_stalled": 1, "tick_stalled_us": 3_000_000,
    "tick_cpu_us": 10_000_000, "gc_us": 90_000, "gc_collections": 30,
    "prefills": 100, "prefill_bucket_positions": 300_000,
    "prefill_prompt_positions": 225_000,
}
EXPECTED = {
    "stall_share_pct.serve": 100 * 3 / 45,
    "loop_cpu_share_pct.serve": 25.0,           # 10 s of 45 - 5
    "gc_pause_ms_per_s.serve": 2.0,             # 90 ms in 45 s
    "prefill_padding_pct.serve": 25.0,
}


@pytest.fixture(scope="module")
def listed(tmp_path_factory):
    """A copy of ``benchmarks/`` whose three serve workload files list
    the new metrics after the ones they have."""
    copy = tmp_path_factory.mktemp("bench") / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for cell in CELLS:
        path = copy / "workloads" / f"{cell}.json"
        spec = json.loads(path.read_text())
        spec["per_layer"] = spec["per_layer"] + COUNTER + TRACE
        path.write_text(json.dumps(spec))
    return str(copy)


def _made_up_trace(names=("jit__prefill_b512(3)", "jit__prefill_b2048(4)")):
    """A window of 1 s: decodes of 10 ms, two prefills of the 512
    bucket (20 ms each), one of the 2048 bucket (100 ms) and a second of
    it that the window's end cuts."""
    ms = 1_000_000
    small, large = names
    modules = [(small, 100 * ms, 120 * ms), ("jit__decode(2)", 120 * ms,
                                             130 * ms),
               (large, 300 * ms, 400 * ms), ("jit__feed(5)", 400 * ms,
                                             400 * ms + 3000),
               (small, 500 * ms, 520 * ms), ("jit__decode(2)", 520 * ms,
                                             530 * ms),
               (large, 950 * ms, 1050 * ms)]
    dev = xplane.DeviceTrace("/device:TPU:0", modules=modules)
    return xplane.Trace([dev], [], (0.0, 1000.0 * ms))


@pytest.mark.parametrize("name", COUNTER + TRACE)
def test_file_loads_and_agrees_with_its_reader(listed, name):
    spec = manifest.load_json("layer_metrics", name, listed)
    unit, source, moves = FILES[name]
    assert manifest.NAME_RE.match(name) and manifest.UNIT_RE.match(spec["unit"])
    assert (spec["unit"], spec["source"], spec["moves"]) == (
        unit, source, moves)
    assert spec["better"] == "lower" and spec["layer"] == "serving"
    for cell_name in CELLS:
        cell = manifest.load_cell(cell_name, listed)
        assert name in cell["layer_metric_files"]
        assert spec["moves"] in cell["end_to_end"]
    reader = manifest.load_reader(spec["reader"], listed)
    # The file's arguments are the reader's: a call with nothing to
    # read returns None, a misspelt argument would raise.
    assert reader.read({}, **spec.get("args", {})) is None


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("name", COUNTER)
def test_counter_metric_reads_the_expected_number(listed, cell_name, name):
    cell = manifest.load_cell(cell_name, listed)
    got = manifest.read_layer_metrics(cell, {"counters": COUNTERS}, listed)
    assert got[name] == {"value": pytest.approx(EXPECTED[name]),
                         "unit": FILES[name][0]}


@pytest.mark.parametrize("name,expected", [
    # (20 + 100 + 20) ms of whole prefills in a window of 1 s.
    ("prefill_share_pct.serve", 14.0),
    # 140 ms over 512 + 2048 + 512 positions.
    ("prefill_us_per_position.serve", 140_000 / 3072)])
def test_trace_metric_reads_the_expected_number(listed, name, expected):
    cell = manifest.load_cell("sarvam-105b-ep8.serve-longctx", listed)
    obs = {"trace": _made_up_trace(), "counters": COUNTERS,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    got = manifest.read_layer_metrics(cell, obs, listed)
    assert got[name] == {"value": pytest.approx(expected),
                         "unit": FILES[name][0]}
    # The accepted median still reads every bucket under ``^jit__prefill``.
    assert got["prefill_ms.serve"]["value"] == pytest.approx(20.0)


def test_a_window_without_a_stall_or_a_collection_reads_zero(listed):
    cell = manifest.load_cell("gpt2-large.serve-long", listed)
    quiet = {**COUNTERS, "ticks_stalled": 0, "tick_stalled_us": 0,
             "gc_us": 0, "gc_collections": 0}
    got = manifest.read_layer_metrics(cell, {"counters": quiet}, listed)
    assert got["stall_share_pct.serve"]["value"] == 0.0
    assert got["gc_pause_ms_per_s.serve"]["value"] == 0.0


def test_missing_counter_or_name_leaves_the_metric_out(listed):
    cell = manifest.load_cell("k-exaone-236b-a23b-ep8.serve-mixed", listed)
    # The parent's counters: the phases, none of what this PR counts.
    parent = {k: v for k, v in COUNTERS.items()
              if k in ("ticks", "tick_us", "tick_idle_us", "prefills")}
    got = manifest.read_layer_metrics(cell, {"counters": parent}, listed)
    assert not set(COUNTER) & set(got)
    # A window without a prefill: no share of positions to take.
    none = {**COUNTERS, "prefill_bucket_positions": 0,
            "prefill_prompt_positions": 0}
    got = manifest.read_layer_metrics(cell, {"counters": none}, listed)
    assert "prefill_padding_pct.serve" not in got
    # The parent's prefills, all under one name: their share of the
    # window reads (the pattern matches them), their time a position
    # has no positions to divide by.
    trace = _made_up_trace(("jit__prefill(3)", "jit__prefill(3)"))
    got = manifest.read_layer_metrics(
        cell, {"trace": trace, "counters": parent}, listed)
    assert got["prefill_share_pct.serve"]["value"] == pytest.approx(14.0)
    assert "prefill_us_per_position.serve" not in got
    # No trace at all (``--trace 0``): neither.
    got = manifest.read_layer_metrics(cell, {"counters": COUNTERS}, listed)
    assert not set(TRACE) & set(got)

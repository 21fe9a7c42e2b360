"""The per-layer metrics that read the program's own phase counters and
kernel names (``ray_lightning_tpu/telemetry/spans.py`` ``PHASES``,
``pl.pallas_call(name="rlt_...")``).  Their files are here, ready; no
accepted cell lists them yet (a cell prints only what its own workload
file lists, and that file is not a later PR's to edit).  So: on a
temporary copy whose two workload files list them, each file loads,
agrees with its reader, and reads the expected number from made-up
counters and a made-up trace; a missing counter leaves the metric out.
"""

import json
import re
import shutil

import pytest

from benchmarks.lib import manifest, xplane

SERVE = ["host_gap_ms.serve", "host_emit_ms.serve", "host_dispatch_ms.serve",
         "host_admit_ms.serve", "queue_wait_mean_ms.serve"]
TRAIN = ["ce_ms.train", "ln_ms.train"]
CELLS = {"gpt2-large.serve-long": SERVE, "gpt2-medium.fit": TRAIN}
SOURCES = {**{m: "program_span" for m in SERVE},
           **{m: "device_trace" for m in TRAIN}}

# One window of a made-up serve run: 100 decode ticks of 170 ms, ten of
# them with an admission, integer microseconds as the engine counts.
COUNTERS = {
    "ticks": 100, "decode_steps": 100, "prefills": 10, "admitted": 10,
    "tick_us": 17_500_000, "tick_decode_wait_us": 16_000_000,
    "tick_admit_wait_us": 500_000, "tick_idle_us": 0,
    "tick_emit_us": 400_000, "tick_decode_dispatch_us": 300_000,
    "tick_admit_dispatch_us": 30_000, "tick_admit_emit_us": 5_000,
    "queue_wait_us": 850_000,
}
EXPECTED = {
    "host_gap_ms.serve": 10.0,          # (17.5 - 16 - 0.5 - 0) s / 100
    "host_emit_ms.serve": 4.0,
    "host_dispatch_ms.serve": 3.0,
    "host_admit_ms.serve": 3.5,         # (30 + 5) ms / 10 prefills
    "queue_wait_mean_ms.serve": 85.0,
}


@pytest.fixture(scope="module")
def listed(tmp_path_factory):
    """A copy of ``benchmarks/`` whose two workload files list the new
    metrics after the ones they have."""
    copy = tmp_path_factory.mktemp("bench") / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for cell, names in CELLS.items():
        path = copy / "workloads" / f"{cell}.json"
        spec = json.loads(path.read_text())
        spec["per_layer"] = spec["per_layer"] + names
        path.write_text(json.dumps(spec))
    return str(copy)


def _made_up_trace():
    """Two whole megastep programs inside the window and one cut by its
    end; kernels under the names the compiler gives them (PERF.md, PR
    24), one of them only mentioned as another operation's operand."""
    ce_f = ("%jvp_rlt_ce_fwd_.1 = (f32[8192,128]{1,0}, f32[8192,128]{1,0}) "
            "custom-call(%a, %b)")
    ce_dx = ("%transpose_jvp_rlt_ce_bwd_dx__.1 = f32[8192,1024]{1,0} "
             "custom-call(%a)")
    ce_dw = ("%transpose_jvp_rlt_ce_bwd_dw__.1 = f32[50304,1024]{1,0} "
             "custom-call(%a)")
    ln_f = ("%rlt_ln_fwd.24 = (bf16[8192,1024]{1,0}, f32[8192,8]{1,0}) "
            "custom-call(%x)")
    ln_b = ("%rlt_ln_bwd.18 = (bf16[8192,1024]{1,0}, f32[1,1024]{1,0}) "
            "custom-call(%x)")
    user = ("%fusion.9 = bf16[8192,1024]{1,0} "
            "fusion(%rlt_ln_fwd.24, %jvp_rlt_ce_fwd_.1)")
    ms = 1_000_000
    ops = []
    for base in (0, 1000 * ms, 2000 * ms):       # the third is cut
        ops += [("%while.1 = () while()", base, base + 900 * ms),
                (ln_f, base + 10 * ms, base + 12 * ms),
                (ln_f, base + 20 * ms, base + 22 * ms),
                (ln_b, base + 30 * ms, base + 34 * ms),
                (user, base + 40 * ms, base + 90 * ms),
                (ce_f, base + 100 * ms, base + 110 * ms),
                (ce_dx, base + 120 * ms, base + 140 * ms),
                (ce_dw, base + 150 * ms, base + 180 * ms)]
    dev = xplane.DeviceTrace(
        "/device:TPU:0", ops=ops,
        modules=[("jit_multi(7)", 0, 900 * ms),
                 ("jit_multi(7)", 1000 * ms, 1900 * ms),
                 ("jit_add(9)", 1900 * ms, 1901 * ms),
                 ("jit_multi(7)", 2000 * ms, 2900 * ms)])
    return xplane.Trace([dev], [], (0.0, 2500.0 * ms))


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_file_loads_and_agrees_with_its_reader(listed, name):
    spec = manifest.load_json("layer_metrics", name, listed)
    assert manifest.NAME_RE.match(name) and manifest.UNIT_RE.match(spec["unit"])
    assert spec["better"] == "lower" and spec["source"] == SOURCES[name]
    assert spec["layer"] == ("serving" if name in SERVE else "kernels")
    cell_name = next(c for c, names in CELLS.items() if name in names)
    cell = manifest.load_cell(cell_name, listed)
    assert name in cell["layer_metric_files"]
    assert spec["moves"] in cell["end_to_end"]
    reader = manifest.load_reader(spec["reader"], listed)
    # The file's arguments are the reader's: a call with nothing to
    # read returns None, a misspelt argument would raise.
    assert reader.read({}, **spec.get("args", {})) is None


@pytest.mark.parametrize("name", SERVE)
def test_counter_metric_reads_the_expected_number(listed, name):
    cell = manifest.load_cell("gpt2-large.serve-long", listed)
    got = manifest.read_layer_metrics(cell, {"counters": COUNTERS}, listed)
    assert got[name] == {"value": pytest.approx(EXPECTED[name]), "unit": "ms"}


@pytest.mark.parametrize("name,expected", [
    # per megastep (10 + 20 + 30) ms of cross-entropy, (2 + 2 + 4) ms
    # of LayerNorm; K = 8 steps a program.
    ("ce_ms.train", 60.0 / 8), ("ln_ms.train", 8.0 / 8)])
def test_kernel_metric_reads_the_expected_number(listed, name, expected):
    cell = manifest.load_cell("gpt2-medium.fit", listed)
    obs = {"trace": _made_up_trace(), "counters": {"megastep_k": 8},
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    got = manifest.read_layer_metrics(cell, obs, listed)
    assert got[name] == {"value": pytest.approx(expected), "unit": "ms/step"}


def test_missing_counter_or_name_leaves_the_metric_out(listed):
    serve = manifest.load_cell("gpt2-large.serve-long", listed)
    parent = {k: v for k, v in COUNTERS.items()
              if not k.startswith(("tick", "queue_wait"))}
    got = manifest.read_layer_metrics(serve, {"counters": parent}, listed)
    assert not set(SERVE) & set(got)
    # A program whose kernels carry no name (the parent's): the same
    # trace under the names the transformations used to give.
    trace = _made_up_trace()
    dev = trace.devices[0]
    dev.ops = [(re.sub(r"rlt_ce_\w+?(_+\.)", r"\1",
                       re.sub(r"rlt_ln_\w+?\.", "pallas_call.", n)), a, b)
               for n, a, b in dev.ops]
    assert not any("rlt_" in n.split(" = ")[0] for n, _, _ in dev.ops)
    fit = manifest.load_cell("gpt2-medium.fit", listed)
    got = manifest.read_layer_metrics(
        fit, {"trace": trace, "counters": {"megastep_k": 8},
              "device": {"platform": "tpu", "kind": "TPU v5 lite"}}, listed)
    assert not set(TRAIN) & set(got)


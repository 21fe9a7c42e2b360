"""The two per-layer metrics of the paged decode kernel (PR 25): the
kernel's device time in a decode tick, read by its name
(``pl.pallas_call(name="rlt_paged_decode")``), and the share of the block
tables a tick had to read, from the engine's two counters.  Their files
are here, ready, like PR 24's seven; no accepted cell lists them yet.
So, as ``test_layer_metric_files.py`` does: on a temporary copy whose
serve workload file lists them, each loads, agrees with its reader and
reads the expected number from made-up counters and a made-up trace; a
program without the kernel or the counters (the parent) leaves them out.
"""

import json
import shutil

import pytest

from benchmarks.lib import manifest, xplane

CELL = "gpt2-large.serve-long"
NEW = {"decode_attn_ms.serve": ("device_trace", "ms"),
       "kv_read_share_pct.serve": ("program_counter", "%")}

# 100 ticks of 16 slots x 32 table entries; 13 blocks a slot resident.
COUNTERS = {"decode_steps": 100, "decode_kv_blocks_read": 100 * 16 * 13,
            "decode_kv_blocks_table": 100 * 16 * 32}


@pytest.fixture(scope="module")
def listed(tmp_path_factory):
    copy = tmp_path_factory.mktemp("bench") / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = copy / "workloads" / f"{CELL}.json"
    spec = json.loads(path.read_text())
    spec["per_layer"] = spec["per_layer"] + sorted(NEW)
    path.write_text(json.dumps(spec))
    return str(copy)


def _made_up_trace(kernel="%rlt_paged_decode.4"):
    """Two whole decode ticks inside the window, one cut by its end and a
    prefill; in a tick the layer loop calls the kernel (here three times,
    2 ms each) among other operations, one of which only mentions it."""
    call = (f"{kernel} = f32[16,1,1280]{{2,1,0}} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"')
    user = "%fusion.7 = bf16[16,1280]{1,0} fusion(%rlt_paged_decode.4)"
    ms = 1_000_000
    ops, modules = [], []
    for base in (0, 100 * ms, 200 * ms):         # the third is cut
        modules.append(("jit__decode(3)", base, base + 30 * ms))
        ops.append(("%while.1 = () while()", base, base + 29 * ms))
        for i in range(3):
            ops.append((call, base + (1 + 8 * i) * ms, base + (3 + 8 * i) * ms))
            ops.append((user, base + (4 + 8 * i) * ms, base + (7 + 8 * i) * ms))
    modules.append(("jit__prefill(5)", 40 * ms, 90 * ms))
    ops.append((call.replace("rlt_paged_decode", "rlt_flash_fwd"),
                41 * ms, 60 * ms))
    dev = xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)
    return xplane.Trace([dev], [], (0.0, 215.0 * ms))


def _obs(**kw):
    return {"device": {"platform": "tpu", "kind": "TPU v5 lite"}, **kw}


@pytest.mark.parametrize("name", sorted(NEW))
def test_file_loads_and_agrees_with_its_reader(listed, name):
    spec = manifest.load_json("layer_metrics", name, listed)
    source, unit = NEW[name]
    assert manifest.NAME_RE.match(name) and manifest.UNIT_RE.match(spec["unit"])
    assert (spec["source"], spec["unit"]) == (source, unit)
    assert spec["layer"] == "serving" and spec["better"] == "lower"
    cell = manifest.load_cell(CELL, listed)
    assert name in cell["layer_metric_files"]
    assert spec["moves"] == "itl_p95_ms" and spec["moves"] in cell["end_to_end"]
    reader = manifest.load_reader(spec["reader"], listed)
    assert reader.read({}, **spec.get("args", {})) is None


@pytest.mark.parametrize("name,obs,expected", [
    ("decode_attn_ms.serve", {"trace": _made_up_trace()}, 6.0),
    ("kv_read_share_pct.serve", {"counters": COUNTERS}, 100.0 * 13 / 32),
], ids=["decode_attn_ms.serve", "kv_read_share_pct.serve"])
def test_metric_reads_the_expected_number(listed, name, obs, expected):
    cell = manifest.load_cell(CELL, listed)
    got = manifest.read_layer_metrics(cell, _obs(**obs), listed)
    assert got[name] == {"value": pytest.approx(expected),
                         "unit": NEW[name][1]}


@pytest.mark.parametrize("obs", [
    # The parent: a decode program with no such kernel, an engine with
    # no such counters.
    {"trace": _made_up_trace(kernel="%fusion.88"),
     "counters": {"decode_steps": 100}},
    # An idle window: the counters are there and read 0.
    {"counters": {"decode_steps": 0, "decode_kv_blocks_read": 0,
                  "decode_kv_blocks_table": 0}},
], ids=["parent", "idle_window"])
def test_nothing_to_read_leaves_the_metric_out(listed, obs):
    cell = manifest.load_cell(CELL, listed)
    got = manifest.read_layer_metrics(cell, _obs(**obs), listed)
    assert not set(NEW) & set(got)

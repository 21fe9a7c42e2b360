"""``ring_decode_ms.serve``: the device time of the window layers' decode
kernel in a decode tick, read by its name
(``pl.pallas_call(name="rlt_ring_decode")``).  Its file is here, ready;
no accepted cell lists it yet.  So, as ``test_paged_metric_files.py``
does: on a temporary copy whose MiMo workload file lists it, it loads,
agrees with its reader and reads the expected number from a made-up
trace; a program without the kernel (the parent) leaves it out.
"""

import json
import shutil

import pytest

from benchmarks.lib import manifest, xplane

CELL = "mimo-v2-flash-ep16.serve-longprompt"
NAME = "ring_decode_ms.serve"


@pytest.fixture(scope="module")
def listed(tmp_path_factory):
    copy = tmp_path_factory.mktemp("bench") / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = copy / "workloads" / f"{CELL}.json"
    spec = json.loads(path.read_text())
    spec["per_layer"] = spec["per_layer"] + [NAME]
    path.write_text(json.dumps(spec))
    return str(copy)


def _made_up_trace(kernel="%rlt_ring_decode.9"):
    """Two whole decode ticks inside the window and one cut by its end;
    in a tick the layer loop calls the ring kernel nine times (40 us
    each) beside the full layers' kernel, and one operation only
    mentions it."""
    call = (f"{kernel} = f32[32,64,128]{{2,1,0}} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"')
    full = call.replace(kernel, "%rlt_paged_decode.3")
    user = "%fusion.7 = bf16[32,8192]{1,0} fusion(%rlt_ring_decode.9)"
    us = 1_000
    ops, modules = [], []
    for base in (0, 20_000 * us, 40_000 * us):   # the third is cut
        modules.append(("jit__decode(3)", base, base + 15_000 * us))
        for i in range(9):
            at = base + (1 + i) * 1_000 * us
            ops += [(call, at, at + 40 * us), (user, at + 50 * us,
                                                at + 90 * us)]
        ops.append((full, base + 11_000 * us, base + 11_400 * us))
    dev = xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)
    return xplane.Trace([dev], [], (0.0, 50_000.0 * us))


def _obs(**kw):
    return {"device": {"platform": "tpu", "kind": "TPU v5 lite"}, **kw}


def test_file_loads_and_agrees_with_its_reader(listed):
    spec = manifest.load_json("layer_metrics", NAME, listed)
    assert manifest.NAME_RE.match(NAME) and manifest.UNIT_RE.match(spec["unit"])
    assert (spec["source"], spec["unit"]) == ("device_trace", "ms")
    assert spec["layer"] == "kernels" and spec["better"] == "lower"
    cell = manifest.load_cell(CELL, listed)
    assert NAME in cell["layer_metric_files"]
    assert spec["moves"] == "itl_p95_ms" and spec["moves"] in cell["end_to_end"]
    reader = manifest.load_reader(spec["reader"], listed)
    assert reader.read({}, **spec.get("args", {})) is None


def test_metric_reads_the_kernels_time_a_tick(listed):
    cell = manifest.load_cell(CELL, listed)
    got = manifest.read_layer_metrics(
        cell, _obs(trace=_made_up_trace()), listed)
    assert got[NAME] == {"value": pytest.approx(0.36), "unit": "ms"}


def test_a_program_without_the_kernel_leaves_the_metric_out(listed):
    cell = manifest.load_cell(CELL, listed)
    got = manifest.read_layer_metrics(
        cell, _obs(trace=_made_up_trace(kernel="%fusion.88")), listed)
    assert NAME not in got

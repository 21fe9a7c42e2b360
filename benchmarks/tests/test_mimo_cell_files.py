"""The files added for ``mimo-v2-flash-ep16.serve-longprompt``: the
grouped paged decode's arithmetic, its roofline reader, the traffic mix,
the configuration against its published catalog entry, the cell through the
manifest, the reference against itself, the cell's rehearsal."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import flops, flops_paged
from benchmarks.lib import manifest, traffic, xplane

CELL = "mimo-v2-flash-ep16.serve-longprompt"
CONFIG = "mimo-v2-flash-ep16"
# A JSON-lines catalog of published model configurations, one object per
# line with ``name``, ``source_url`` and ``config``.
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG", "")
ROOT = manifest.checkout_root()
SHAPES = {"n_head": 64, "n_kv_head": 4, "head_dim": 192, "v_head_dim": 128,
          "layers": 3, "itemsize": 2}


def test_paged_decode_cost_by_hand():
    """64 query heads on 4 K/V heads, keys 192 and values 128 in bf16: a
    position costs rows of 768 + 512 lanes, 2,560 B, and 2 x 64 x (192 +
    128) = 40,960 FLOPs, 16 a byte; a slot and layer adds its 64 queries
    in and 64 x 128 float32 out."""
    cost = flops_paged.paged_decode_cost(1000, 0, 64, 4, 192, 128)
    assert cost == {"flops": 40_960_000.0, "bytes": 2_560_000.0}
    io = 64 * 192 * 2 + 64 * 128 * 4
    assert io == 57_344
    both = flops_paged.paged_decode_cost(1000, 3, 64, 4, 192, 128)
    assert both["bytes"] == 2_560_000 + 3 * io
    assert both["flops"] == cost["flops"]
    # A tick of the cell: 32 slots of ~3,200 positions over 3 full layers
    # read 0.79 GB; on a v5e the bytes bind (0.97 ms).
    tick = flops_paged.paged_decode_cost(32 * 3200 * 3, 32 * 3, **{
        k: SHAPES[k] for k in ("n_head", "n_kv_head", "head_dim",
                               "v_head_dim")})
    assert tick["bytes"] == pytest.approx(0.79e9, rel=0.01)
    least = flops.roofline_seconds(tick, flops.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(0.97e-3, rel=0.01)
    assert flops_paged.paged_decode_cost(0, 0, 64, 4, 192, 128) == {
        "flops": 0.0, "bytes": 0.0}


def _trace(modules, ops, window):
    dev = xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)
    return xplane.Trace([dev], [], window)


def _obs(ms_per_tick, platform="tpu"):
    ns = int(ms_per_tick * 1e6)
    modules = [("jit__decode(1)", 0, 10 * ns), ("jit__decode(1)", 20 * ns,
                                                30 * ns)]
    ops = [("%rlt_paged_decode.1 = f32[32,64,128]", 0, ns // 2),
           ("%rlt_paged_decode.2 = f32[32,64,128]", ns, ns + ns // 2),
           ("%rlt_paged_decode.1 = f32[32,64,128]", 20 * ns, 20 * ns + ns),
           ("%fusion.3 = bf16[32,8192] fusion(%rlt_paged_decode.1)", 0, ns),
           ("%rlt_moe_down.1 = bf16[256,4096]", 2 * ns, 3 * ns)]
    return {"trace": _trace(modules, ops, (0.0, 40.0 * ns)),
            "device": {"platform": platform, "kind": "TPU v5 lite"},
            "counters": {"decode_steps": 10, "tokens_out": 330,
                         "prefills": 10,
                         "decode_full_positions": 10 * 32 * 3200 * 3},
            "paged": dict(SHAPES)}


def test_roofline_reads_through_its_own_file():
    cell = manifest.load_cell(CELL)
    cell["layer_metric_files"] = {
        k: v for k, v in cell["layer_metric_files"].items()
        if k in ("decode_attn_ms.serve", "decode_attn_roofline.serve")}
    got = manifest.read_layer_metrics(cell, _obs(ms_per_tick=1.2))
    # Two executions; kernel time 0.5 + 0.5 + 1.0 = 2 x 1.2 ms over 2.
    assert got["decode_attn_ms.serve"]["value"] == pytest.approx(1.2)
    need = flops_paged.paged_decode_cost(
        32 * 3200 * 3, 32 * 3, 64, 4, 192, 128)["bytes"] / 819e9
    share = got["decode_attn_roofline.serve"]
    assert share["value"] == pytest.approx(100 * need / 1.2e-3)
    assert 0 < share["value"] <= 100 and share["unit"] == "%"


@pytest.mark.parametrize("obs", [
    {}, {"trace": None, "counters": {}},
    {**_obs(1.2), "paged": None},
    {**_obs(1.2), "counters": {"decode_steps": 10}},
    {**_obs(1.2), "counters": {**_obs(1.2)["counters"],
                               "decode_full_positions": 0}},
    _obs(1.2, platform="cpu"),
    {**_obs(1.2), "trace": _trace([], [], (0.0, 1.0))},
], ids=["empty", "no_trace", "no_shapes", "no_counters", "no_full_layers",
        "cpu", "no_kernel"])
def test_roofline_reader_gives_nothing_where_there_is_nothing(obs):
    """A program without the kernel, the counter or a chip (the parent,
    another family, a rehearsal) leaves the metric out and does not
    raise."""
    reader = manifest.load_reader("paged_roofline")
    assert reader.read(obs, pattern="rlt_paged_decode",
                       module="^jit__decode") is None


def test_serve_longprompt_is_the_stated_mix():
    mix = manifest.load_json("traffic", "serve-longprompt",
                             manifest.BENCH_DIR)
    sizes = traffic.block_sizes(mix)
    prompts = [p for p, _ in sizes]
    news = [n for _, n in sizes]
    assert len(sizes) == 64 and mix["arrivals"] == {
        "kind": "closed_loop", "callers": 32}
    assert mix["prompt_len"] == {"dist": "log_uniform", "low": 1024,
                                 "high": 6144}
    assert mix["max_new_tokens"] == {"dist": "uniform", "low": 128,
                                     "high": 640}
    assert mix["shared_prefix"] is None
    assert 1024 <= min(prompts) < 1050 and 6000 < max(prompts) <= 6144
    assert np.mean(prompts) == pytest.approx(2857, rel=0.01)
    assert np.mean(news) == pytest.approx(384, abs=2)
    assert max(p + n for p, n in sizes) <= 6784
    reqs = traffic.requests(mix, 2**31 + 11, 19072, 128)
    assert all(1 <= t < 19072 for r in reqs for t in r.prompt)


def test_cell_is_the_stated_engine_and_warms_every_bucket():
    cell = manifest.load_cell(CELL)
    assert cell["driver"] == "serve_mimo_closed" and cell["chips"] == 1
    system = cell["system"]
    # Every other field at the engine's default; a table as long as the
    # longest request (212 blocks: the walk's last chunk of a full slot
    # holds 20 of them).
    assert system["serve_config"] == {
        "num_slots": 32, "block_size": 32, "max_model_len": 6784,
        "prefill_buckets": [1024, 2048, 3072, 4096, 6144]}
    assert system["lead_in_blocks"] * cell["traffic_file"]["block"] == 64
    assert system["order_seed"] == 0
    buckets = system["serve_config"]["prefill_buckets"]

    def bucket(n):
        return min(b for b in buckets if b >= n)

    reached = {bucket(p) for p, _ in traffic.block_sizes(cell["traffic_file"])}
    warmed = {bucket(n) for n in system["warmup_prompt_lens"]}
    assert reached == set(buckets[1:]) and warmed == set(buckets)
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}
    assert len(cell["why"]) <= 200


def test_benchmark_json_lists_the_cell_where_its_line_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = manifest.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "serve-longprompt", "chips": 1,
                     "why": cell["why"]}
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(cell["per_layer"])
    for m in bench["per_layer"]:
        if m["name"] in cell["per_layer"]:
            spec = cell["layer_metric_files"][m["name"]]
            assert (m["unit"], m["layer"], m["moves"], m["source"]) == (
                spec["unit"], spec["layer"], spec["moves"], spec["source"])
    new = next(m for m in bench["per_layer"]
               if m["name"] == "decode_attn_roofline.serve")
    assert new["workloads"] == [CELL]
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
            assert CELL in m.get("workloads", [CELL])
    cfg = next(c for c in bench["configs"] if c["name"] == CONFIG)
    doc = cell["config_file"]
    assert (cfg["reduced"], cfg["source"], cfg["file"]) == (
        doc["reduced"], doc["source"], f"benchmarks/configs/{CONFIG}.json")


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_config_file_holds_every_number_of_the_catalog_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    doc = manifest.load_json("configs", CONFIG, manifest.BENCH_DIR)
    assert doc["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if doc.get(k) != v}
    assert differs == set(doc["reduced"]) == set(doc["reduced_how"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) or "width" in k
                   or "head" in k for k in doc["reduced"]
                   if k != "vocab_size")
    for key in doc["reduced"]:
        assert doc["published"][key] == row["config"][key]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert doc[key] == row["config"][key][:doc["num_hidden_layers"]]
    # ``fields`` (what the program is built from) says the same as the
    # source's own keys.
    f = doc["fields"]
    assert (f["d_model"], f["n_head"], f["n_kv_head"], f["swa_n_kv_head"],
            f["head_dim"], f["v_head_dim"], f["d_ff"], f["d_expert"],
            f["n_experts"], f["top_k"], f["window"], f["rms_eps"],
            f["rope_theta"], f["swa_rope_theta"], f["value_scale"],
            f["seq_len"], f["vocab_size"]) == (
        doc["hidden_size"], doc["num_attention_heads"],
        doc["num_key_value_heads"], doc["swa_num_key_value_heads"],
        doc["head_dim"], doc["v_head_dim"], doc["intermediate_size"],
        doc["moe_intermediate_size"], doc["published"]["n_routed_experts"],
        doc["num_experts_per_tok"], doc["sliding_window"],
        doc["layernorm_epsilon"], doc["rope_theta"], doc["swa_rope_theta"],
        doc["attention_value_scale"], doc["max_position_embeddings"],
        doc["published"]["vocab_size"])
    # The program's sinks: the window layers', none in the full ones.
    assert doc["add_swa_attention_sink_bias"] is True
    assert doc["add_full_attention_sink_bias"] is False
    assert f["rope_dim"] == int(doc["head_dim"] * doc["partial_rotary_factor"])
    assert f["routed_scale"] == 1.0 and doc["routed_scaling_factor"] is None
    assert (f["n_layer"], f["experts_held"], f["vocab_held"]) == (
        doc["num_hidden_layers"], [0, doc["n_routed_experts"]],
        [0, doc["vocab_size"]])
    for item in ("qk_norm", "norm_placement", "sink", "window", "rope",
                 "score_scale", "value_scale", "router_scoring",
                 "router_selection_bias"):
        assert doc["assumed"][item]
    assert "mtp_layers" in doc["changed"]


def test_reference_in_lower_precision_departs_from_itself():
    """What sets the cell's tolerances: the reference with its matmul
    inputs rounded to bf16, and to float8, against itself in float32;
    and the blocks it computes in (memory, not mathematics) change
    nothing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import mimo_v2_ref as ref

    cfg = {"n_head": 4, "n_kv_head": 1, "swa_n_kv_head": 2, "head_dim": 12,
           "v_head_dim": 8, "rope_dim": 4, "rope_theta": 5e6,
           "swa_rope_theta": 1e4, "value_scale": 0.707, "window": 4,
           "top_k": 2,
           "rms_eps": 1e-5, "experts_held": (0, 4),
           "layer_types": ("full", "sliding"),
           "mlp_types": ("dense", "sparse")}
    d, f, e, v = 32, 16, 8, 64
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 40))

    def w(*shape):
        return jax.random.normal(next(ks), shape) * 0.2

    def attn(hkv, sink):
        p = {"wq": w(d, 48), "wk": w(d, 12 * hkv), "wv": w(d, 8 * hkv),
             "wo": w(32, d), "attn_norm": jnp.ones(d),
             "ffn_norm": jnp.ones(d)}
        return dict(p, sink=w(4)) if sink else p

    params = {"embed": w(v, d), "head": w(d, v), "final_norm": jnp.ones(d),
              "layers": [
                  {**attn(1, False), "w_gate": w(d, 48), "w_up": w(d, 48),
                   "w_down": w(48, d)},
                  {**attn(2, True), "router": w(d, e), "router_bias": w(e),
                   "e_gate": w(4, d, f), "e_up": w(4, d, f),
                   "e_down": w(4, f, d)}]}
    toks = jax.random.randint(next(ks), (20,), 0, v)
    full, routing = ref.forward(cfg, params, toks)
    assert full.shape == (20, v) and routing[0][0].shape == (20, e)
    errs = [float(jnp.abs(ref.forward(cfg, params, toks, precision=p)[0]
                          - full).max())
            for p in ("bfloat16", "float8_e4m3fn")]
    assert 0 < errs[0] < errs[1]
    # Causal: a longer sequence leaves the earlier logits alone.
    longer, _ = ref.forward(cfg, params, jnp.concatenate([toks, toks[:4]]))
    assert float(jnp.abs(longer[:20] - full).max()) < 1e-5
    # Narrower blocks of query rows and of the dense width: the same sums.
    was = ref.Q_ROWS, ref.F_COLS
    ref.Q_ROWS, ref.F_COLS = 8, 16
    ref._JITS.clear()
    try:
        blocked, _ = ref.forward(cfg, params, toks)
    finally:
        ref.Q_ROWS, ref.F_COLS = was
        ref._JITS.clear()
    assert float(jnp.abs(blocked - full).max()) < 1e-5


def test_cell_rehearsal_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "2",
         "--trace", "0", "--rehearsal"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    serve = [json.loads(row) for row in out.stdout.splitlines()
             if row.startswith('{"phase": "serve"')][0]
    assert serve["compile_events_in_window"] == 0
    counters = serve["engine_counters_in_window"]
    # Two full layers in the rehearsal's seven: the window's positions
    # attended by them, the slots' own tokens included.
    assert counters["decode_full_positions"] > 2 * (
        counters["tokens_out"] - counters["prefills"])
    checks = {row["phase"]: row for row in map(json.loads, (
        r for r in out.stdout.splitlines()
        if r.startswith('{"phase": "reference_check')))}
    assert checks["reference_check"]["ok"]
    assert checks["reference_check_window"]["ok"]
    assert checks["reference_check"]["lowprec_reference"]["logit_rms"] > 1e-3

"""The files added for ``gigachat3.5-432b-a28b-ep16.serve-longdecode``:
the gated delta rule decode's roofline reader and its metric files, the
traffic mix, the cell, the configuration against its published catalog
entry, the reference against itself, and ``flops_gdn``'s arithmetic by
hand.  (The cell's rehearsal is ``tests/test_serve_families.py``'s, row
``GigaChat``.)"""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, flops_gdn
from benchmarks.lib import manifest, traffic, xplane

CELL = "gigachat3.5-432b-a28b-ep16.serve-longdecode"
CONFIG = "gigachat3.5-432b-a28b-ep16"
# A JSON-lines catalog of published model configurations, one object per
# line with ``name``, ``source_url`` and ``config``.
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG", "")
ROOT = manifest.checkout_root()
SHAPES = {"n_value_heads": 64, "n_key_heads": 32, "key_dim": 128,
          "value_dim": 128, "layers": 4}


def test_gated_delta_decode_cost_by_hand():
    """One slot's step of one linear layer of the cell: 64 value heads'
    float32 states of 128 x 128 read and written (8 MiB), q and k of 32
    heads, v of 64, two gates a head in and 64 x 128 out, all float32;
    7 x 128 x 128 FLOPs a head.  A tick of 128 slots x 4 layers moves
    4.35 GB, 5.31 ms at a v5e's 819 GB/s: the bytes bind."""
    one = flops_gdn.gdn_decode_cost(1, 64, 32, 128, 128)
    io = (2 * 32 * 128 + 2 * 64 * 128 + 2 * 64) * 4
    assert io == 98_816
    assert one == {"flops": 7.0 * 64 * 128 * 128,
                   "bytes": 2 * 64 * 128 * 128 * 4 + io}
    tick = flops_gdn.gdn_decode_cost(128 * 4, 64, 32, 128, 128)
    assert tick["bytes"] == pytest.approx(4.3456e9, rel=1e-3)
    least = flops.roofline_seconds(tick, flops.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(5.306e-3, rel=1e-3)
    assert flops_gdn.gdn_decode_cost(0, 64, 32, 128, 128) == {
        "flops": 0.0, "bytes": 0.0}


def _trace(modules, ops, window):
    dev = xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)
    return xplane.Trace([dev], [], window)


def _obs(ms_per_tick, platform="tpu"):
    """Two decode executions of ``ms_per_tick`` kernel time each, four
    calls a tick; 128 live slots x 4 layers a tick over 10 ticks."""
    ns = int(ms_per_tick * 1e6)
    q = ns // 4
    modules = [("jit__decode(1)", 0, 10 * ns),
               ("jit__decode(1)", 20 * ns, 30 * ns)]
    ops = [(f"%rlt_gdn_decode.{i} = f32[128,64,128]", i * q, (i + 1) * q)
           for i in range(4)]
    ops += [(f"%rlt_gdn_decode.{i} = f32[128,64,128]", 20 * ns + i * q,
             20 * ns + (i + 1) * q) for i in range(4)]
    ops += [("%fusion.3 = f32[128,64,128] fusion(%rlt_gdn_decode.1)", 0, ns),
            ("%rlt_mla_decode.1 = f32[128,64,512]", 2 * ns, 3 * ns)]
    return {"trace": _trace(modules, ops, (0.0, 40.0 * ns)),
            "device": {"platform": platform, "kind": "TPU v5 lite"},
            "counters": {"decode_steps": 10,
                         "decode_state_slot_layers": 10 * 128 * 4},
            "gdn": dict(SHAPES)}


def test_roofline_reads_through_its_own_file():
    cell = manifest.load_cell(CELL)
    cell["layer_metric_files"] = {
        k: v for k, v in cell["layer_metric_files"].items()
        if k in ("gdn_decode_ms.serve", "gdn_decode_roofline.serve")}
    got = manifest.read_layer_metrics(cell, _obs(ms_per_tick=6.5))
    # Two executions of four calls each, 6.5 ms a tick.
    assert got["gdn_decode_ms.serve"]["value"] == pytest.approx(6.5, rel=1e-6)
    need = flops_gdn.gdn_decode_cost(512, 64, 32, 128, 128)["bytes"] / 819e9
    share = got["gdn_decode_roofline.serve"]
    assert share["value"] == pytest.approx(100 * need / 6.5e-3, rel=1e-6)
    assert 0 < share["value"] <= 100 and share["unit"] == "%"
    least = flops.roofline_seconds(
        flops_gdn.gdn_decode_cost(512, 64, 32, 128, 128),
        flops.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"


@pytest.mark.parametrize("obs", [
    {}, {"trace": None, "counters": {}},
    {**_obs(6.5), "gdn": None},
    {**_obs(6.5), "counters": {"decode_steps": 10}},
    _obs(6.5, platform="cpu"),
    {**_obs(6.5), "trace": _trace([], [], (0.0, 1.0))},
], ids=["empty", "no_trace", "no_shapes", "no_counter", "cpu", "no_kernel"])
def test_roofline_reader_gives_nothing_where_there_is_nothing(obs):
    """A program without the kernel, the counter or a chip (the parent,
    another family, a rehearsal) leaves the metric out and does not
    raise."""
    reader = manifest.load_reader("gdn_roofline")
    assert reader.read(obs, pattern="rlt_gdn_decode",
                       module="^jit__decode") is None


def test_serve_longdecode_is_the_stated_mix():
    mix = manifest.load_json("traffic", "serve-longdecode",
                             manifest.BENCH_DIR)
    sizes = traffic.block_sizes(mix)
    prompts = [p for p, _ in sizes]
    news = [n for _, n in sizes]
    assert len(sizes) == 64 and mix["arrivals"] == {
        "kind": "closed_loop", "callers": 128}
    assert mix["prompt_len"] == {"dist": "log_uniform", "low": 256,
                                 "high": 2048}
    assert mix["max_new_tokens"] == {"dist": "uniform", "low": 384,
                                     "high": 1536}
    assert mix["shared_prefix"] is None
    assert 256 <= min(prompts) < 270 and 2000 < max(prompts) <= 2048
    assert np.mean(prompts) == pytest.approx(862, rel=0.02)
    assert np.mean(news) == pytest.approx(960, rel=0.01)
    assert max(p + n for p, n in sizes) <= 3584
    reqs = traffic.requests(mix, 2**31 + 11, 16128, 128)
    assert all(1 <= t < 16128 for r in reqs for t in r.prompt)


def test_cell_is_the_stated_engine_and_warms_every_bucket():
    cell = manifest.load_cell(CELL)
    assert cell["driver"] == "serve_gigachat_closed" and cell["chips"] == 1
    system = cell["system"]
    # Every other field at the engine's default but the queue's bound: the
    # 128 callers' first requests arrive at once, and a queue of the
    # default 64 would turn them away (each rejection a failed request).
    assert system["serve_config"] == {
        "num_slots": 128, "block_size": 32, "max_model_len": 3584,
        "prefill_buckets": [256, 512, 1024, 2048], "max_queue": 128}
    assert system["serve_config"]["max_queue"] >= (
        cell["traffic_file"]["arrivals"]["callers"])
    # The window opens when the callers take request 192: 64 completions.
    assert system["lead_in_blocks"] * cell["traffic_file"]["block"] == 192
    assert system["order_seed"] == 0
    buckets = system["serve_config"]["prefill_buckets"]

    def bucket(n):
        return min(b for b in buckets if b >= n)

    reached = {bucket(p) for p, _ in traffic.block_sizes(cell["traffic_file"])}
    warmed = {bucket(n) for n in system["warmup_prompt_lens"]}
    # The traffic's mid-quantile prompts (262-2037) reach every bucket but
    # the first, which the warm-up's shortest prompt takes.
    assert reached == set(buckets[1:]) and warmed == set(buckets)
    # The window's sample is the longest request it served whole that fits
    # the reference's longer width (the longest warm-up, padded to 128):
    # 2560 positions, under which the seven longest of the traffic's sizes
    # (2273-2542 positions) each decode over 1000 tokens through the state.
    longest = max(system["warmup_prompt_lens"]) + system["warmup_new_tokens"]
    width = -(-longest // 128) * 128
    assert width == 2560
    fits = sorted((p + n, n) for p, n in
                  traffic.block_sizes(cell["traffic_file"]) if p + n <= width)
    assert all(n > 1000 for _, n in fits[-7:])
    # The program's own forward is checked on the shortest warm-up.
    assert (min(system["warmup_prompt_lens"]) + system["warmup_new_tokens"]
            <= system["reference_short_width"])
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}
    assert len(cell["why"]) <= 200


def test_benchmark_json_lists_the_cell_where_its_line_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = manifest.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "serve-longdecode", "chips": 1,
                     "why": cell["why"]}
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(cell["per_layer"])
    for m in bench["per_layer"]:
        if m["name"] in cell["per_layer"]:
            spec = cell["layer_metric_files"][m["name"]]
            assert (m["unit"], m["layer"], m["moves"], m["source"]) == (
                spec["unit"], spec["layer"], spec["moves"], spec["source"])
    for name in ("gdn_decode_ms.serve", "gdn_decode_roofline.serve"):
        new = next(m for m in bench["per_layer"] if m["name"] == name)
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
                   if r["name"] == "GigaChat3.5-432B-A28B")
    doc = manifest.load_json("configs", CONFIG, manifest.BENCH_DIR)
    assert doc["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if doc.get(k) != v}
    assert differs == set(doc["reduced"]) == set(doc["reduced_how"])
    for key in doc["reduced"]:
        assert doc["published"][key] == row["config"][key]


def test_config_file_states_the_cut_and_the_readings():
    doc = manifest.load_json("configs", CONFIG, manifest.BENCH_DIR)
    assert not any(k.endswith(("_dim", "_rank", "_size")) or "width" in k
                   or "head" in k for k in doc["reduced"]
                   if k != "vocab_size")
    f = doc["fields"]
    assert (f["d_model"], f["n_head"], f["kv_lora_rank"], f["q_lora_rank"],
            f["qk_nope_head_dim"], f["qk_rope_head_dim"], f["v_head_dim"],
            f["d_ff"], f["d_expert"], f["top_k"], f["lin_key_heads"],
            f["lin_value_heads"], f["lin_key_dim"], f["lin_value_dim"],
            f["conv_width"], f["swiglu_limit"], f["vocab_size"]) == (
        doc["hidden_size"], doc["num_attention_heads"], doc["kv_lora_rank"],
        doc["q_lora_rank"], doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
        doc["v_head_dim"], doc["intermediate_size"],
        doc["moe_intermediate_size"], doc["num_experts_per_tok"],
        doc["linear_num_key_heads"], doc["linear_num_value_heads"],
        doc["linear_key_head_dim"], doc["linear_value_head_dim"],
        doc["linear_conv_kernel_dim"], doc["swiglu_limit"],
        doc["published"]["vocab_size"])
    assert (f["n_layer"], f["full_layers"], f["first_dense"],
            f["experts_held"], f["vocab_held"], f["n_experts"]) == (
        doc["num_hidden_layers"], doc["full_attention_layers"],
        doc["first_k_dense_replace"], [0, doc["n_routed_experts"]],
        [0, doc["vocab_size"]], doc["published"]["n_routed_experts"])
    for item in ("block_norm_gating", "norm_placement", "linear_layer",
                 "linear_conv", "linear_output_gate", "state_dtype", "rope",
                 "score_scale", "attention_gate", "swiglu_limit",
                 "router_scoring", "router_selection_bias"):
        assert doc["assumed"][item]
    assert "mtp_layers" in doc["changed"]
    assert "16 chips share each layer" in doc["deployment"]


def test_reference_in_lower_precision_departs_from_itself():
    """What sets the cell's tolerances: the reference with its matmul
    inputs (and the recurrence's q, k, v) rounded to bf16, and to
    float8, against itself in float32; the blocks it computes in change
    nothing; causal, the linear layers too."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gigachat35_ref as ref

    cfg = {"n_head": 2, "kv_lora_rank": 8, "qk_nope_head_dim": 4,
           "qk_rope_head_dim": 4, "v_head_dim": 4, "top_k": 2,
           "routed_scale": 2.5, "rms_eps": 1e-6, "rope_theta": 1e5,
           "rope_factor": 8.0, "rope_original_len": 8, "rope_beta_fast": 32.0,
           "rope_beta_slow": 1.0, "rope_mscale": 1.0,
           "rope_mscale_all_dim": 1.0, "experts_held": (0, 4),
           "layer_types": ("linear", "latent"),
           "mlp_types": ("dense", "sparse"), "lin_key_heads": 1,
           "lin_value_heads": 2, "lin_key_dim": 4, "lin_value_dim": 4,
           "conv_width": 4, "lin_norm_eps": 1e-6, "gate_scale": 2.0,
           "swiglu_limit": 10.0}
    d, f, e, v, c = 16, 8, 8, 64, 16
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 40))

    def w(*shape):
        return jax.random.normal(next(ks), shape) * 0.3

    gains = {n: w(d) * 0.1 for n in ("attn_norm", "attn_out_norm",
                                     "ffn_norm", "ffn_out_norm")}
    linear = {"w_qkvz": w(d, c + 8), "w_ba": w(d, 4), "conv_w": w(4, c),
              "A_log": jnp.log(jnp.asarray([0.05, 0.5])),
              "dt_bias": jnp.ones(2), "o_norm": w(4) * 0.1, "w_o": w(8, d),
              "w_gate": w(d, 48), "w_up": w(d, 48), "w_down": w(48, d),
              **gains}
    latent = {"wqa": w(d, 6), "q_a_norm": jnp.ones(6), "wqb": w(6, 16),
              "wkva": w(d, 12), "kv_norm": jnp.ones(8), "wkvb": w(8, 16),
              "w_gate": w(d, 8), "wo": w(8, d), "router": w(d, e),
              "router_bias": w(e) * 0.01, "e_gate": w(4, d, f),
              "e_up": w(4, d, f), "e_down": w(4, f, d), "s_gate": w(d, f),
              "s_up": w(d, f), "s_down": w(f, d), **gains}
    params = {"embed": w(v, d), "head": w(d, v), "final_norm": w(d) * 0.1,
              "layers": [linear, latent]}
    toks = jax.random.randint(next(ks), (20,), 0, v)
    full, routing = ref.forward(cfg, params, toks)
    assert full.shape == (20, v) and routing[0][0].shape == (20, e)
    errs = [float(jnp.abs(ref.forward(cfg, params, toks, precision=p)[0]
                          - full).max())
            for p in ("bfloat16", "float8_e4m3fn")]
    assert 0 < errs[0] < errs[1]
    longer, _ = ref.forward(cfg, params, jnp.concatenate([toks, toks[:4]]))
    assert float(jnp.abs(longer[:20] - full).max()) < 1e-5
    was = ref.Q_ROWS, ref.F_COLS
    ref.Q_ROWS, ref.F_COLS = 8, 16
    ref._JITS.clear()
    try:
        blocked, _ = ref.forward(cfg, params, toks)
    finally:
        ref.Q_ROWS, ref.F_COLS = was
        ref._JITS.clear()
    assert float(jnp.abs(blocked - full).max()) < 1e-5


def test_balanced_bias_evens_the_experts_loads():
    """Scores that share one direction (every row prefers the same few
    experts, as drawn weights give over a long context) are evened by
    the driver's selection bias: no expert then takes more than a tenth
    over its share or less than a tenth under it, and the held experts
    take their share of the choices."""
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers.serve_gigachat_closed import (
        balanced_bias, expert_loads,
    )

    k_common, k_rows = jax.random.split(jax.random.PRNGKey(0))
    shared = jax.random.normal(k_common, (64,))
    z = jax.nn.sigmoid(shared + jax.random.normal(k_rows, (4096, 64)))
    drawn = jnp.zeros((64,), jnp.float32)
    most, least, _ = expert_loads(z, drawn, 4, 8)
    assert most > 2 and least < 0.2
    bias = balanced_bias(z, drawn, 4)
    most, least, held_pct = expert_loads(z, bias, 4, 8)
    assert most < 1.1 and least > 0.9
    assert held_pct == pytest.approx(100 * 8 / 64, abs=1)

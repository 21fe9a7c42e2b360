"""The files PR 30 added for ``sarvam-105b-ep8.serve-longctx``: the
latent decode kernel's arithmetic, its roofline reader, the traffic mix,
the configuration against the guide's catalog, the cell through the
manifest, the reference against itself."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import flops, flops_mla
from benchmarks.lib import manifest, traffic, xplane

CELL = "sarvam-105b-ep8.serve-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROOT = manifest.checkout_root()


def test_mla_decode_cost_by_hand():
    """64 heads on rows of 512 + 64 in bf16: a position costs 1152 B and
    2 x 64 x (576 + 512) = 139,264 FLOPs, 121 a byte; a slot and layer
    adds its 64 queries and its own row in and 64 x 512 float32 out."""
    cost = flops_mla.mla_decode_cost(1000, 0, 64, 512, 64)
    assert cost == {"flops": 139_264_000.0, "bytes": 1_152_000.0}
    assert cost["flops"] / cost["bytes"] == pytest.approx(120.9, abs=0.1)
    io = (64 * 576 + 576) * 2 + 64 * 512 * 4
    assert io == 205_952
    both = flops_mla.mla_decode_cost(1000, 3, 64, 512, 64)
    assert both["bytes"] == 1_152_000 + 3 * io
    assert both["flops"] == cost["flops"]
    # The issue's tick: 64 slots x ~2616 positions over 8 layers reads
    # 1.54 GB and does 186 GFLOP; on a v5e the bytes bind (1.9 ms).
    tick = flops_mla.mla_decode_cost(64 * 2616 * 8, 64 * 8, 64, 512, 64)
    assert tick["bytes"] == pytest.approx(1.54e9, rel=0.08)
    assert tick["flops"] == pytest.approx(186e9, rel=0.01)
    least = flops.roofline_seconds(tick, flops.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert flops_mla.mla_decode_cost(0, 0, 64, 512, 64) == {
        "flops": 0.0, "bytes": 0.0}


def _trace(modules, ops, window):
    dev = xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)
    return xplane.Trace([dev], [], window)


def _obs(ms_per_tick, platform="tpu"):
    ns = int(ms_per_tick * 1e6)
    modules = [("jit__decode(1)", 0, 10 * ns), ("jit__decode(1)", 20 * ns,
                                                30 * ns)]
    ops = [("%rlt_mla_decode.1 = f32[64,64,512]", 0, ns // 2),
           ("%rlt_mla_decode.2 = f32[64,64,512]", ns, ns + ns // 2),
           ("%rlt_mla_decode.1 = f32[64,64,512]", 20 * ns, 20 * ns + ns),
           ("%fusion.3 = bf16[64,8192] fusion(%rlt_mla_decode.1)", 0, ns),
           ("%rlt_moe_down.1 = bf16[512,4096]", 2 * ns, 3 * ns)]
    return {"trace": _trace(modules, ops, (0.0, 40.0 * ns)),
            "device": {"platform": platform, "kind": "TPU v5 lite"},
            "counters": {"decode_steps": 10, "tokens_out": 650,
                         "prefills": 10,
                         "decode_latent_positions": 10 * 64 * 2600 * 8},
            "mla": {"n_head": 64, "rank": 512, "rope_dim": 64, "layers": 8,
                    "itemsize": 2}}


def test_mla_metrics_read_through_their_own_files():
    cell = manifest.load_cell(CELL)
    cell["layer_metric_files"] = {
        k: v for k, v in cell["layer_metric_files"].items() if "mla_" in k}
    assert sorted(cell["layer_metric_files"]) == [
        "mla_decode_ms.serve", "mla_decode_roofline.serve"]
    got = manifest.read_layer_metrics(cell, _obs(ms_per_tick=4.0))
    # Two executions; kernel time 0.5 + 0.5 + 1.0 = 2 x 4 ms over 2.
    assert got["mla_decode_ms.serve"]["value"] == pytest.approx(4.0)
    need = flops_mla.mla_decode_cost(
        64 * 2600 * 8, 64 * 8, 64, 512, 64)["bytes"] / 819e9
    assert got["mla_decode_roofline.serve"]["value"] == pytest.approx(
        100 * need / 4e-3)
    assert 0 < got["mla_decode_roofline.serve"]["value"] < 100
    assert got["mla_decode_roofline.serve"]["unit"] == "%"


@pytest.mark.parametrize("obs", [
    {}, {"trace": None, "counters": {}},
    {**_obs(4.0), "mla": None},
    {**_obs(4.0), "counters": {"decode_steps": 10}},
    {**_obs(4.0), "counters": {**_obs(4.0)["counters"],
                               "decode_latent_positions": 0}},
    _obs(4.0, platform="cpu"),
    {**_obs(4.0), "trace": _trace([], [], (0.0, 1.0))},
], ids=["empty", "no_trace", "no_shapes", "no_counters", "gpt_counters",
        "cpu", "no_kernel"])
def test_roofline_reader_gives_nothing_where_there_is_nothing(obs):
    """A program without the kernel, the counters or a chip (the parent,
    another family, a rehearsal) leaves the metric out and does not
    raise."""
    reader = manifest.load_reader("mla_roofline")
    assert reader.read(obs, pattern="rlt_mla_decode",
                       module="^jit__decode") is None


def test_serve_longctx_is_the_mix_the_issue_gives():
    """The issue's distributions, callers and ``block``: 64 sizes a
    block, the tails of both ranges among them."""
    mix = manifest.load_json("traffic", "serve-longctx", manifest.BENCH_DIR)
    sizes = traffic.block_sizes(mix)
    prompts = [p for p, _ in sizes]
    news = [n for _, n in sizes]
    assert len(sizes) == 64 and mix["arrivals"] == {
        "kind": "closed_loop", "callers": 64}
    assert len(set(prompts)) == 64 and len(set(news)) == 64
    assert mix["prompt_len"] == {"dist": "log_uniform", "low": 512,
                                 "high": 6144}
    assert mix["max_new_tokens"] == {"dist": "uniform", "low": 256,
                                     "high": 1024}
    assert 512 <= min(prompts) < 530 and 6000 < max(prompts) <= 6144
    assert 256 <= min(news) < 270 and 1010 < max(news) <= 1024
    # The distributions' own median and mean are 1774 and 2266.
    assert np.median(prompts) == pytest.approx(1774, rel=0.02)
    assert np.mean(prompts) == pytest.approx(2266, rel=0.01)
    assert np.mean(news) == pytest.approx(640, abs=2)
    assert max(p + n for p, n in sizes) <= 7168      # max_model_len
    assert sum(p > 4096 for p in prompts) >= 1       # past YaRN's original
    reqs = traffic.requests(mix, 2**31 + 11, 32768, 128)
    assert all(1 <= t < 32768 for r in reqs for t in r.prompt)
    # Another seed: the same sizes in another order, block by block.
    other = traffic.requests(mix, 7, 32768, 128)
    assert sorted(len(r.prompt) for r in other[:64]) == sorted(prompts)
    assert sorted(len(r.prompt) for r in other[64:]) == sorted(prompts)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in reqs]


def test_the_cell_offers_its_sizes_in_one_order_and_its_ids_by_seed():
    """``system.order_seed`` (PERF.md section 6, PR 30): every seed's run
    takes the mix's 64 sizes a block in the same order, so the window
    cuts the same requests; the token ids are the run's own seed's."""
    from benchmarks.lib import serve_share

    mix = manifest.load_json("traffic", "serve-longctx", manifest.BENCH_DIR)
    a, b = (serve_share.requests_in_order(mix, 0, seed, 32768, 130)
            for seed in (2**31 + 11, 7))
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == [
        (len(r.prompt), r.max_new_tokens) for r in b]
    assert sorted((len(r.prompt), r.max_new_tokens) for r in a[:64]) \
        == sorted(traffic.block_sizes(mix))
    assert a[0].prompt != b[0].prompt
    assert all(1 <= t < 32768 for r in a for t in r.prompt)
    assert [r.index for r in a] == list(range(130))
    again = serve_share.requests_in_order(mix, 0, 7, 32768, 130)
    assert [r.prompt for r in again] == [r.prompt for r in b]
    # The order is the generator's own under that seed.
    assert [len(r.prompt) for r in a] == [
        len(r.prompt) for r in traffic.requests(mix, 0, 32768, 130)]


def test_window_sample_takes_whole_requests_of_the_window():
    """The longest and the shortest request taken AND finished inside
    the window that the reference's compiled width holds; a cut one, a
    failed one, one from the lead-in and one too long are passed over."""
    from benchmarks.lib import serve_share

    reqs = [traffic.Request(i, [1] * n, m) for i, (n, m) in enumerate(
        [(10, 4), (30, 4), (20, 4), (90, 4), (15, 4), (25, 4), (12, 4)])]
    kept = {id(r.prompt): [7] * r.max_new_tokens for r in reqs}
    kept[id(reqs[5].prompt)] = [7, 7]                       # cut short

    def rec(i, t0, t1, status="ok"):
        return {"index": i, "asked": reqs[i].max_new_tokens,
                "status": status, "t_submit": t0, "t_done": t1}

    records = [rec(0, 0.5, 2.0),        # taken in the lead-in
               rec(1, 1.5, 3.0), rec(2, 2.0, 4.0),
               rec(3, 2.0, 4.0),        # longer than the width
               rec(4, 3.0, 9.5),        # finished after the close
               rec(5, 2.0, 4.0),        # cut short
               rec(6, 2.0, 4.0, "TimeoutError: ...")]
    prompts, served = serve_share.window_sample(
        records, reqs, kept, (1.0, 9.0), longest=64)
    assert [len(p) for p in prompts] == [30, 20]
    assert served == [[7] * 4, [7] * 4]
    one = serve_share.window_sample(records[:2], reqs, kept, (1.0, 9.0), 64)
    assert [len(p) for p in one[0]] == [30]
    assert serve_share.window_sample(records[:1], reqs, kept, (1.0, 9.0),
                                     64) == ([], [])


def test_cell_is_the_engine_the_issue_gives_and_warms_every_bucket():
    cell = manifest.load_cell(CELL)
    assert cell["driver"] == "serve_mla_closed" and cell["chips"] == 1
    system = cell["system"]
    # Every field the issue does not give is the engine's default.
    assert system["serve_config"] == {
        "num_slots": 64, "block_size": 32, "max_model_len": 7168,
        "prefill_buckets": [512, 1024, 2048, 3072, 4096, 6144]}
    assert system["lead_in_blocks"] * cell["traffic_file"]["block"] == 128
    assert system["order_seed"] == 0
    buckets = system["serve_config"]["prefill_buckets"]

    def bucket(n):
        return min(b for b in buckets if b >= n)

    reached = {bucket(p) for p, _ in traffic.block_sizes(cell["traffic_file"])}
    warmed = {bucket(n) for n in system["warmup_prompt_lens"]}
    # (The smallest size of a block is 522: bucket 512 is warmed for the
    # engine the issue gives and no request of the mix reaches it.)
    assert reached == set(buckets[1:]) and warmed == set(buckets)
    # The check runs a served sequence past the original context.
    assert max(system["warmup_prompt_lens"]) > 4096
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}


def test_benchmark_json_lists_the_cell_where_its_line_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = manifest.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": "sarvam-105b-ep8",
                     "traffic": "serve-longctx", "chips": 1,
                     "why": cell["why"]}
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(cell["per_layer"])
    for m in bench["per_layer"]:
        if m["name"] in cell["per_layer"]:
            spec = cell["layer_metric_files"][m["name"]]
            assert (m["unit"], m["layer"], m["moves"], m["source"]) == (
                spec["unit"], spec["layer"], spec["moves"], spec["source"])
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "itl_p95_ms"):
            assert m["workloads"][-1] == CELL
    cfg = next(c for c in bench["configs"] if c["name"] == "sarvam-105b-ep8")
    assert cfg["reduced"] == cell["config_file"]["reduced"]
    assert cfg["source"] == cell["config_file"]["source"]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_config_file_holds_every_number_of_the_catalog_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "sarvam-105b")
    doc = manifest.load_json("configs", "sarvam-105b-ep8",
                             manifest.BENCH_DIR)
    assert doc["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if doc.get(k) != v}
    assert differs == set(doc["reduced"]) == set(doc["reduced_how"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) or "width" in k
                   for k in doc["reduced"] if k != "vocab_size")
    assert doc["rope_scaling"] == row["config"]["rope_scaling"]
    assert set(doc["published"]) >= set(doc["reduced"])
    # ``fields`` (what the program is built from) says the same as the
    # source's own keys.
    f = doc["fields"]
    scaling = doc["rope_scaling"]
    assert (f["d_model"], f["n_head"], f["kv_lora_rank"],
            f["qk_nope_head_dim"], f["qk_rope_head_dim"], f["v_head_dim"],
            f["d_ff"], f["d_expert"], f["n_experts"], f["top_k"],
            f["routed_scale"], f["first_dense"], f["rms_eps"],
            f["rope_theta"], f["seq_len"], f["vocab_size"]) == (
        doc["hidden_size"], doc["num_attention_heads"], doc["kv_lora_rank"],
        doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"],
        doc["intermediate_size"], doc["moe_intermediate_size"],
        doc["published"]["num_experts"], doc["num_experts_per_tok"],
        doc["routed_scaling_factor"], doc["first_k_dense_replace"],
        doc["rms_norm_eps"], doc["rope_theta"],
        doc["max_position_embeddings"], doc["published"]["vocab_size"])
    assert (f["rope_factor"], f["rope_original_len"], f["rope_beta_fast"],
            f["rope_beta_slow"], f["rope_mscale"],
            f["rope_mscale_all_dim"]) == (
        scaling["factor"], scaling["original_max_position_embeddings"],
        scaling["beta_fast"], scaling["beta_slow"], scaling["mscale"],
        scaling["mscale_all_dim"])
    assert (f["n_layer"], f["experts_held"], f["vocab_held"]) == (
        doc["num_hidden_layers"], [0, doc["num_experts"]],
        [0, doc["vocab_size"]])
    assert doc["q_head_dim"] == f["qk_nope_head_dim"] + f["qk_rope_head_dim"]
    assert doc["head_dim"] == f["kv_lora_rank"] + f["qk_rope_head_dim"]
    for item in ("router_scoring", "router_groups", "router_selection_bias",
                 "norm_placement", "rope", "use_qk_norm", "q_lora_rank"):
        assert doc["assumed"][item]


def test_reference_in_lower_precision_departs_from_itself():
    """What sets the cell's tolerances: the reference with its matmul
    inputs rounded to bf16, and to float8, against itself in float32;
    and the blocks it computes in (memory, not mathematics) change
    nothing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import sarvam_mla_ref as ref

    cfg = {"n_head": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
           "qk_rope_head_dim": 8, "v_head_dim": 8, "top_k": 2,
           "routed_scale": 2.5, "rms_eps": 1e-6, "rope_theta": 1e4,
           "rope_factor": 8.0, "rope_original_len": 16,
           "rope_beta_fast": 32.0, "rope_beta_slow": 1.0,
           "rope_mscale": 1.0, "rope_mscale_all_dim": 1.0,
           "experts_held": (0, 4), "mlp_types": ("dense", "sparse")}
    d, f, e, v = 32, 16, 8, 64
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 40))

    def w(*shape):
        return jax.random.normal(next(ks), shape) * 0.2

    def attn():
        return {"wq": w(d, 64), "wkva": w(d, 24), "wkvb": w(16, 64),
                "wo": w(32, d), "q_norm": jnp.ones(16),
                "kv_norm": jnp.ones(16), "attn_norm": jnp.ones(d),
                "ffn_norm": jnp.ones(d)}

    params = {"embed": w(v, d), "head": w(d, v), "final_norm": jnp.ones(d),
              "layers": [
                  {**attn(), "w_gate": w(d, 48), "w_up": w(d, 48),
                   "w_down": w(48, d)},
                  {**attn(), "router": w(d, e), "router_bias": w(e),
                   "e_gate": w(4, d, f), "e_up": w(4, d, f),
                   "e_down": w(4, f, d), "s_gate": w(d, f), "s_up": w(d, f),
                   "s_down": w(f, d)}]}
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
    assert serve["lead_in_requests"] >= 16          # two blocks of 8
    assert serve["order_seed"] == 0
    assert serve["counters_set_at_build"]["latent_row_bytes"] == 24 * 4
    checks = {row["phase"]: row for row in map(json.loads, (
        r for r in out.stdout.splitlines()
        if r.startswith('{"phase": "reference_check')))}
    # The window's own sample is held to the reference after it closes,
    # its gaps counted with the warm-up's.
    in_window = checks["reference_check_window"]
    assert in_window["ok"] and checks["reference_check"]["ok"]
    assert in_window["tokens_counted"] == (
        in_window["tokens"] + checks["reference_check"]["tokens"])
    assert "program_forward" not in in_window
    assert serve["compile_events_in_window"] == 0

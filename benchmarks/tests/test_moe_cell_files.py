"""The files PR 26 added for ``k-exaone-236b-a23b-ep8.serve-mixed``: the
expert kernels' arithmetic, their roofline reader, the traffic mix, the
configuration against the guide's catalog, the reference against
itself."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, flops_moe
from benchmarks.lib import manifest, traffic, xplane

CELL = "k-exaone-236b-a23b-ep8.serve-mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_expert_kernel_cost_counts_hit_experts_only():
    d, f = 6144, 2048
    one = flops_moe.expert_weight_bytes(d, f)
    assert one == 3 * d * f * 2 == 75_497_472
    cost = flops_moe.expert_kernels_cost(32, 14, d, f)
    assert cost["flops"] == 6 * 32 * d * f
    # 14 experts' weights and the rows, never all 16 that are held.
    assert cost["bytes"] == 14 * one + 2 * 32 * (d + f) * 2
    assert cost["bytes"] < 16 * one
    assert flops_moe.expert_kernels_cost(0, 0, d, f) == {
        "flops": 0.0, "bytes": 0.0}
    # A decode tick is bandwidth-bound by three orders of magnitude.
    least = flops.roofline_seconds(cost, flops.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(cost["bytes"] / 819e9)


def _trace(modules, ops, window):
    dev = xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)
    return xplane.Trace([dev], [], window)


def _obs(ms_per_tick, hit_per_tick, platform="tpu"):
    ns = int(ms_per_tick * 1e6)
    modules = [("jit__decode(1)", 0, 10 * ns), ("jit__decode(1)", 20 * ns,
                                                30 * ns)]
    ops = [("%rlt_moe_gate_up.1 = bf16[256,2048]", 0, ns // 2),
           ("%rlt_moe_down.1 = bf16[256,6144]", ns, ns + ns // 2),
           ("%rlt_moe_gate_up.1 = bf16[256,2048]", 20 * ns, 20 * ns + ns),
           ("%fusion.3 = bf16[32,6144] fusion(%rlt_moe_down.1)", 0, ns)]
    return {"trace": _trace(modules, ops, (0.0, 40.0 * ns)),
            "device": {"platform": platform, "kind": "TPU v5 lite"},
            "counters": {"decode_steps": 10, "moe_top_k": 8,
                         "moe_tokens_routed": 10 * 32 * 7,
                         "moe_local_assignments": 10 * 224,
                         "moe_local_experts_hit": 10 * hit_per_tick},
            "moe": {"d_model": 6144, "d_expert": 2048, "itemsize": 2}}


def test_moe_metrics_read_through_their_own_files():
    cell = manifest.load_cell(CELL)
    cell["layer_metric_files"] = {
        k: v for k, v in cell["layer_metric_files"].items() if "moe_" in k}
    assert len(cell["layer_metric_files"]) == 3
    obs = _obs(ms_per_tick=10.0, hit_per_tick=97)
    got = manifest.read_layer_metrics(cell, obs)
    # Two executions; kernel time 0.5 + 0.5 + 1.0 = 2 x 10 ms over 2.
    assert got["moe_expert_ms.serve"]["value"] == pytest.approx(10.0)
    need = flops_moe.expert_kernels_cost(224, 97, 6144, 2048)["bytes"] / 819e9
    assert got["moe_expert_roofline.serve"]["value"] == pytest.approx(
        100 * need / 10e-3)
    assert 0 < got["moe_expert_roofline.serve"]["value"] < 100
    assert got["moe_local_share_pct.serve"]["value"] == pytest.approx(12.5)
    assert got["moe_expert_roofline.serve"]["unit"] == "%"


@pytest.mark.parametrize("obs", [
    {}, {"trace": None, "counters": {}},
    {**_obs(10.0, 97), "moe": None},
    {**_obs(10.0, 97), "counters": {"decode_steps": 10}},
    _obs(10.0, 97, platform="cpu"),
    {**_obs(10.0, 97), "trace": _trace([], [], (0.0, 1.0))},
], ids=["empty", "no_trace", "no_shapes", "no_counters", "cpu",
        "no_kernels"])
def test_roofline_reader_gives_nothing_where_there_is_nothing(obs):
    """A program without the kernels, the counters or a chip (the
    parent, a rehearsal) leaves the metric out and does not raise."""
    reader = manifest.load_reader("moe_roofline")
    assert reader.read(obs, pattern="rlt_moe_", module="^jit__decode") is None


def test_serve_mixed_is_the_mix_the_issue_gives():
    mix = manifest.load_json("traffic", "serve-mixed", manifest.BENCH_DIR)
    sizes = traffic.block_sizes(mix)
    prompts = [p for p, _ in sizes]
    news = [n for _, n in sizes]
    assert len(sizes) == 64 and mix["arrivals"] == {
        "kind": "closed_loop", "callers": 32}
    assert 64 <= min(prompts) and max(prompts) <= 3072
    assert 64 <= min(news) and max(news) <= 384
    assert np.median(prompts) == pytest.approx(443, abs=2)
    assert np.mean(prompts) == pytest.approx(777, abs=2)
    assert max(p + n for p, n in sizes) <= 4096      # max_model_len
    reqs = traffic.requests(mix, 2**31 + 11, 19200, 128)
    assert all(1 <= t < 19200 for r in reqs for t in r.prompt)
    # Another seed: the same sizes in another order.
    other = traffic.requests(mix, 7, 19200, 64)
    assert sorted(len(r.prompt) for r in other) == sorted(prompts)


def test_cell_warms_every_bucket_its_traffic_reaches():
    cell = manifest.load_cell(CELL)
    buckets = cell["system"]["serve_config"]["prefill_buckets"]

    def bucket(n):
        return min(b for b in buckets if b >= n)

    reached = {bucket(p) for p, _ in traffic.block_sizes(cell["traffic_file"])}
    warmed = {bucket(n) for n in cell["system"]["warmup_prompt_lens"]}
    assert reached <= warmed
    assert max(buckets) == 3072     # bucket 4096 leaves under 1 GB (PERF.md)


def test_window_opens_on_a_block_edge_and_the_host_overlaps_the_device():
    cell = manifest.load_cell(CELL)
    system = cell["system"]
    # The lead-in is the traffic's first block, by count (the driver has
    # no option for it); the cell asks for one reply frame a tick and
    # for the next decode to be dispatched before the tokens are booked.
    assert "lead_in_s" not in system
    for serve_config in (system["serve_config"],
                         cell["rehearsal"]["system"]["serve_config"]):
        assert serve_config["coalesce_replies"] is True
        assert serve_config["decode_lookahead"] is True


def test_taken_counts_what_the_callers_took():
    import threading

    from benchmarks.drivers.serve_moe_closed import _Taken

    counter, got = _Taken(), []

    def take():
        for _ in range(500):
            got.append(next(counter))

    threads = [threading.Thread(target=take) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counter.taken == 4000 and sorted(got) == list(range(4000))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_config_file_holds_every_number_of_the_catalog_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    doc = manifest.load_json("configs", "k-exaone-236b-a23b-ep8",
                             manifest.BENCH_DIR)
    assert doc["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if doc.get(k) != v}
    assert differs == set(doc["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) or "width" in k
                   for k in doc["reduced"] if k != "vocab_size")
    assert doc["num_hidden_layers"] == len(doc["layer_types"]) == 8
    assert doc["layer_types"] == row["config"]["layer_types"][:8]
    assert doc["mlp_layer_types"] == row["config"]["mlp_layer_types"][:8]


def test_reference_in_lower_precision_departs_from_itself():
    """What sets the cell's tolerances: the reference with its matmul
    inputs rounded to bf16, and to float8, against itself in float32."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import exaone_moe_ref as ref

    cfg = {"n_head": 4, "n_kv_head": 2, "head_dim": 8, "window": 8,
           "top_k": 2, "routed_scale": 2.5, "rms_eps": 1e-5,
           "rope_theta": 1e6, "experts_held": (0, 4),
           "layer_types": ("sliding", "full"),
           "mlp_types": ("dense", "sparse")}
    d, f, e, v = 32, 16, 8, 64
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 40))

    def w(*shape):
        return jax.random.normal(next(ks), shape) * 0.2

    def attn():
        return {"wq": w(d, 32), "wk": w(d, 16), "wv": w(d, 16),
                "wo": w(32, d), "q_norm": jnp.ones(8), "k_norm": jnp.ones(8),
                "attn_out_norm": jnp.ones(d), "ffn_out_norm": jnp.ones(d)}

    params = {"embed": w(v, d), "head": w(d, v), "final_norm": jnp.ones(d),
              "layers": [
                  {**attn(), "w_gate": w(d, 48), "w_up": w(d, 48),
                   "w_down": w(48, d)},
                  {**attn(), "router": w(d, e), "router_bias": jnp.zeros(e),
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

"""The benchmark's own arithmetic against the program's, and the peaks."""

import pytest

from benchmarks import flops
from benchmarks.lib import manifest


@pytest.mark.parametrize("name", ["gpt2-medium", "gpt2-large"])
def test_model_flops_equal_the_programs_today(name):
    from ray_lightning_tpu.models import GPTConfig
    from ray_lightning_tpu.telemetry.step_stats import model_flops_per_token

    cfg = GPTConfig(**manifest.load_json(
        "configs", name, manifest.BENCH_DIR)["fields"])
    for attn in ("full", "causal"):
        assert flops.model_flops_per_token(cfg, attn) == \
            model_flops_per_token(cfg, attn)
    if name == "gpt2-medium":
        assert flops.model_flops_per_token(cfg) == pytest.approx(2.42e9, rel=5e-3)


def test_peaks_known_kind_and_unknown_kind():
    p = flops.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            flops.peaks_for(kind)


def test_attention_cost_and_bound():
    cost = flops.attention_kernel_cost(8, 16, 1024, 64)
    assert cost["forward"]["flops"] == 2 * 8 * 16 * 1024 * 1024 * 64 * 2 / 2
    assert cost["backward"]["flops"] == 2 * cost["forward"]["flops"]
    assert cost["forward"]["bytes"] == 4 * 8 * 16 * 1024 * 64 * 2
    r = flops.roofline_seconds(cost["forward"], flops.peaks_for("TPU v5 lite"))
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(cost["forward"]["flops"] / 197e12)

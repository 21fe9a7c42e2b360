"""What every served family is held to, one case a family: its
configuration and the published file, the forward, the prefill and the
decode through its cache, and the engine through the client plane,
against the family's plain reference (``benchmarks/reference/``) at its
tiny preset; what it refuses, by its name; its cell's rehearsal.  A new
family is one row of ``FAMILIES`` here and a file of what only it has
(``test_exaone_moe.py``, ``test_sarvam_mla.py``, ``test_mimo_v2.py``).

Tolerances.  The program and the reference compute the same float32
sums in another order (``sarvam_mla``'s decode, in the absorbed form, a
different product of the same matrices), so they agree to a few ulps of
values of order 1: measured 1.5e-7 on logits of magnitude 0.4 (forward)
and 4e-7 through the cache.  ``F32_TOL = 2e-5`` leaves a hundred times
that and is a hundred times under what bfloat16 gives where float32 is
stated (``test_bf16_where_float32_is_stated_fails_the_tolerance``).
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import exaone_moe_ref, mimo_v2_ref, sarvam_mla_ref
from ray_lightning_tpu.models import exaone_moe, mimo_v2, sarvam_mla
from ray_lightning_tpu.serve import ServeClient, ServeConfig, ServeEngine
from utils import draw_tokens, reference_logits, tiny_family

F32_TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_LEN = 48        # every tiny-preset sequence here, padded for the reference
PROMPTS = (9, 13, 15)    # the engine cases' prompts: one prefill bucket (16)


class Exaone:       # the tiny preset: ``test_exaone_moe.py``
    name = "exaone_moe"
    program, ref = exaone_moe, exaone_moe_ref
    Module, Config = exaone_moe.ExaoneMoE, exaone_moe.ExaoneMoEConfig
    preset = staticmethod(exaone_moe.exaone_moe_tiny)
    gains = ("q_norm", "k_norm", "attn_out_norm", "ffn_out_norm")
    # ``rlt_paged_decode`` with grouped queries tiles heads of 128.
    pallas = dict(n_layer=4, n_head=16, n_kv_head=2, head_dim=128, window=16)
    pallas_prompts = (27, 22)
    bad = (dict(experts_held=(8, 20)), dict(vocab_held=(0, 999)),
           dict(layer_types=("full",) * 3), dict(n_kv_head=3),
           dict(mlp_types=("moe",) * 8))
    # 40 takes the banded sliding form (5 whole windows), 24 the plain
    # mask beyond the window, 5 stays inside it.
    lengths = dict(short=5, ragged=24, banded=40)
    config_file = "k-exaone-236b-a23b-ep8.json"
    cell = "k-exaone-236b-a23b-ep8.serve-mixed"
    why = "window rings"
    depth, reduced = 8, {"num_hidden_layers", "num_experts", "vocab_size"}
    shares = 4          # of the preset's 16 experts, 4 a share

    @staticmethod
    def published(cfg, doc):
        assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
                cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.top_k,
                cfg.window) == (6144, 64, 8, 128, 18432, 2048, 128, 8, 128)
        assert cfg.n_vocab_held == 19200 == 150 * 128
        assert cfg.layer_types == tuple(
            {"sliding_attention": "sliding", "full_attention": "full"}[t]
            for t in doc["layer_types"])
        assert cfg.mlp_types == tuple(doc["mlp_layer_types"])
        assert (doc["hidden_size"], doc["intermediate_size"],
                doc["moe_intermediate_size"], doc["num_experts_per_tok"],
                doc["sliding_window"], doc["head_dim"]) == (
            6144, 18432, 2048, 8, 128, 128)

    @staticmethod
    def decoded(cfg, cache, pool, block_size, reached):
        ring = cfg.window // block_size + 1
        assert cache.window_blocks == ring
        assert reached > cfg.window + ring * block_size  # the ring wrapped

    @staticmethod
    def served(cfg, engine, overlap):
        c = engine.stats.counters
        share = c["moe_local_assignments"] / (
            c["moe_tokens_routed"] * cfg.top_k)
        assert 0.15 < share < 0.35          # 4 of 16 experts held
        # Sliding layers read the slot's ring (3 blocks here, 5 in the
        # cell) a slot a layer a tick, never more.
        ticks_slots = c["tokens_out"] - c["prefills"]
        assert c["decode_kv_blocks_read_window"] == ticks_slots * 3 * 6
        assert c["decode_kv_blocks_read"] == (
            c["decode_kv_blocks_read_window"]
            + c["decode_kv_blocks_read_full"])
        assert engine.scheduler.snapshot()["window_blocks_live"] == 0

    rehearsed = staticmethod(lambda line, phases: None)


class Sarvam:       # the tiny preset: ``test_sarvam_mla.py``
    name = "sarvam_mla"
    program, ref = sarvam_mla, sarvam_mla_ref
    Module, Config = sarvam_mla.SarvamMLA, sarvam_mla.SarvamMLAConfig
    preset = staticmethod(sarvam_mla.sarvam_mla_tiny)
    gains = ("q_norm", "kv_norm", "attn_norm", "ffn_norm")
    # ``rlt_mla_decode`` tiles 16 heads, a latent of 128 and a rotary
    # key of 64 in rows of 256; an original context of 64 positions.
    pallas = dict(n_layer=2, n_head=16, kv_lora_rank=128,
                  qk_rope_head_dim=64, rope_original_len=64)
    pallas_prompts = (43, 38)
    bad = (dict(experts_held=(4, 4)), dict(experts_held=(0, 17)),
           dict(vocab_held=(0, 999)), dict(qk_rope_head_dim=7),
           dict(first_dense=9))
    # 40 runs past the tiny config's original 16 positions.
    lengths = dict(short=5, past_original=40)
    config_file = "sarvam-105b-ep8.json"
    cell = "sarvam-105b-ep8.serve-longctx"
    why = "latent rows"
    depth, reduced = 8, {"num_hidden_layers", "num_experts", "vocab_size"}
    shares = 8

    @staticmethod
    def published(cfg, doc):
        assert (cfg.d_model, cfg.n_head, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.top_k,
                cfg.routed_scale) == (
            4096, 64, 512, 128, 64, 128, 16384, 2048, 128, 8, 2.5)
        assert (cfg.rope_factor, cfg.rope_original_len, cfg.rope_beta_fast,
                cfg.rope_beta_slow, cfg.rope_theta, cfg.rms_eps) == (
            40, 4096, 32, 1, 1e4, 1e-6)
        assert cfg.mlp_types == ("dense",) + ("sparse",) * 7
        assert cfg.n_vocab_held == 32768
        assert (doc["hidden_size"], doc["intermediate_size"],
                doc["moe_intermediate_size"], doc["num_experts_per_tok"],
                doc["kv_lora_rank"], doc["q_head_dim"], doc["head_dim"]) == (
            4096, 16384, 2048, 8, 512, 192, 576)
        assert doc["reduced"] == ["num_hidden_layers", "num_experts",
                                  "vocab_size"]
        assert "8 chips share each layer" in doc["deployment"]

    @staticmethod
    def decoded(cfg, cache, pool, block_size, reached):
        assert pool["kv"].shape == (cfg.n_layer, cache.num_blocks,
                                    block_size, cfg.pool_row)
        assert reached > cfg.rope_original_len
        # The padding lanes stay zero: never written, never read as data.
        assert not np.asarray(pool["kv"][..., cfg.cache_row:]).any()

    @staticmethod
    def served(cfg, engine, overlap):
        c = engine.stats.counters
        assert c["latent_row_bytes"] == cfg.cache_row * 4   # float32 here
        if not overlap:
            # One at a time: a request of prompt n decodes 25 tokens at
            # lengths n .. n + 24, each attending its own row too.
            want = sum(sum(n + t + 1 for t in range(25))
                       for n in PROMPTS)
            assert c["decode_latent_positions"] == want * cfg.n_layer
        assert "decode_kv_blocks_read_window" not in c      # one kind
        assert engine.family.two_kind is False

    @staticmethod
    def rehearsed(line, phases):
        assert 0 < line["metrics"]["kv_read_share_pct.serve"]["value"] <= 100
        # No chip: the kernel's time and its roofline share are left out.
        assert "mla_decode_roofline.serve" not in line["metrics"]
        check, in_window = (phases["reference_check"],
                            phases["reference_check_window"])
        assert check["sequence_lengths"][-1] > check["original_context"]
        # What the window itself served, every slot live: held to the
        # reference after it closes, past the original context too.
        assert in_window["ok"] and in_window["worst_logit_gap"] < F32_TOL
        assert in_window["tokens"] > 0
        assert (in_window["sequence_lengths"][-1]
                > in_window["original_context"])


class MiMo:         # the tiny preset: ``test_mimo_v2.py``
    name = "mimo_v2"
    program, ref = exaone_moe, mimo_v2_ref
    Module, Config = mimo_v2.MiMoV2, mimo_v2.MiMoV2Config
    preset = staticmethod(mimo_v2.mimo_v2_tiny)
    gains = ("attn_norm", "ffn_norm")
    # ``rlt_paged_decode`` at the published head widths: 16 query heads
    # on 2 K/V heads of 192 (keys) and 128 (values) in the full layer.
    pallas = dict(n_layer=2, n_head=16, n_kv_head=2, swa_n_kv_head=4,
                  head_dim=192, v_head_dim=128, rope_dim=64, window=16)
    pallas_prompts = (27, 22)
    bad = (dict(experts_held=(8, 20)), dict(vocab_held=(0, 999)),
           dict(swa_n_kv_head=3), dict(rope_dim=5),
           dict(layer_types=("full",) * 3))
    lengths = dict(short=5, ragged=24, banded=40)
    config_file = "mimo-v2-flash-ep16.json"
    cell = "mimo-v2-flash-ep16.serve-longprompt"
    why = "window rings"
    depth = 12
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    shares = 16

    @staticmethod
    def published(cfg, doc):
        full, ring = cfg.geometry("full"), cfg.geometry("sliding")
        assert (cfg.d_model, cfg.n_head, cfg.d_ff, cfg.d_expert,
                cfg.n_experts, cfg.top_k, cfg.window) == (
            4096, 64, 16384, 2048, 256, 8, 128)
        assert (full.n_kv_head, ring.n_kv_head, full.head_dim,
                ring.head_dim, full.v_head_dim, ring.v_head_dim) == (
            doc["num_key_value_heads"], doc["swa_num_key_value_heads"],
            doc["head_dim"], doc["swa_head_dim"], doc["v_head_dim"],
            doc["swa_v_head_dim"]) == (4, 8, 192, 192, 128, 128)
        assert (full.rope_theta, ring.rope_theta) == (
            doc["rope_theta"], doc["swa_rope_theta"])
        assert full.rope_dim == ring.rope_dim == int(
            doc["head_dim"] * doc["partial_rotary_factor"]) == 64
        assert full.value_scale == doc["attention_value_scale"]
        assert (full.sink, ring.sink) == (
            doc["add_full_attention_sink_bias"],
            doc["add_swa_attention_sink_bias"])
        assert cfg.layer_types == tuple(
            ("full", "sliding")[t] for t in doc["hybrid_layer_pattern"])
        assert cfg.mlp_types == tuple(
            ("dense", "sparse")[t] for t in doc["moe_layer_freq"])
        assert cfg.n_vocab_held == doc["vocab_size"] == 149 * 128
        assert not cfg.shared_expert and cfg.routed_scale == 1.0
        assert doc["n_shared_experts"] is None
        assert "16 chips share each layer" in doc["deployment"]

    @staticmethod
    def decoded(cfg, cache, pool, block_size, reached):
        Exaone.decoded(cfg, cache, pool, block_size, reached)
        # Each kind's K and V rows at their own widths.
        for names, g in ((("k", "v"), cfg.geometry("full")),
                         (("wk", "wv"), cfg.geometry("sliding"))):
            assert (pool[names[0]].shape[-1], pool[names[1]].shape[-1]) == (
                g.k_width, g.v_width)

    @staticmethod
    def served(cfg, engine, overlap):
        c = engine.stats.counters
        share = c["moe_local_assignments"] / (
            c["moe_tokens_routed"] * cfg.top_k)
        assert 0.15 < share < 0.35          # 4 of 16 experts held
        if not overlap:
            # One at a time: a request of prompt n decodes 25 tokens at
            # lengths n .. n + 24, each attending its own row too, in
            # each of the preset's two full layers.
            want = sum(sum(n + t + 1 for t in range(25)) for n in PROMPTS)
            assert c["decode_full_positions"] == want * 2
        assert engine.family.two_kind and engine.family.name == "mimo_v2"

    @staticmethod
    def rehearsed(line, phases):
        assert 0 < line["metrics"]["kv_read_share_pct.serve"]["value"] <= 100
        # No chip: the kernel's time and its roofline share are left out.
        assert "decode_attn_roofline.serve" not in line["metrics"]
        assert phases["reference_check_window"]["ok"]


FAMILIES = (Exaone, Sarvam, MiMo)
per_family = pytest.mark.parametrize(
    "fam", FAMILIES, ids=[f.name for f in FAMILIES])


@functools.cache
def built(fam, pallas=False):
    """The family's tiny preset ``(cfg, module, params)``, or the preset
    its decode kernel tiles: built once for the cases that share it."""
    over = fam.pallas if pallas else {}
    return tiny_family(fam.preset, fam.Module, fam.gains,
                       seed=1 if over else 0, **over)


@functools.cache
def forward(fam, moe_impl="auto"):
    return jax.jit(fam.Module(built(fam)[0], moe_impl=moe_impl).forward)


def _gap(got, want):
    return float(jnp.abs(got - want).max())


# -- the config ---------------------------------------------------------------

@pytest.mark.parametrize("fam,bad", [
    pytest.param(f, bad, id=f"{f.name}-{i}")
    for f in FAMILIES for i, bad in enumerate(f.bad)])
def test_config_refuses_what_is_not_a_share_or_a_shape(fam, bad):
    with pytest.raises(ValueError):
        fam.preset(**bad)


@per_family
def test_config_file_holds_the_published_widths_uncut(fam):
    with open(os.path.join(ROOT, "benchmarks/configs", fam.config_file)) as f:
        doc = json.load(f)
    fields = dict(doc["fields"])
    for key in ("experts_held", "vocab_held"):
        fields[key] = tuple(fields[key])
    cfg = fam.Config(**fields)
    assert cfg.n_layer == fam.depth and cfg.n_experts_held == 16
    # Widths under the source's own keys, and what was cut under its name.
    fam.published(cfg, doc)
    assert set(doc["reduced"]) >= fam.reduced
    for key in ("changed", "assumed", "deployment", "published",
                "reduced_how"):
        assert doc[key]


# -- forward against the reference ------------------------------------------

@pytest.mark.parametrize("fam,n,moe_impl", [
    pytest.param(f, n, impl, id=f"{f.name}-{impl}-{label}")
    for f in FAMILIES for impl in ("xla", "pallas")
    for label, n in f.lengths.items()])
def test_forward_matches_the_reference(fam, n, moe_impl):
    cfg, _, params = built(fam)
    toks = draw_tokens(n)
    got = forward(fam, moe_impl)(params, toks[None])[0]
    want, routing = reference_logits(fam.ref, cfg, params, toks, REF_LEN)
    assert got.shape == (n, cfg.n_vocab_held) and len(routing) == cfg.n_sparse
    assert _gap(got, want) < F32_TOL


@per_family
def test_bf16_where_float32_is_stated_fails_the_tolerance(fam):
    """The reference with every matmul's inputs rounded to bfloat16, and
    the program on weights that passed through bfloat16."""
    cfg, _, params = built(fam)
    toks = draw_tokens(24)
    want, _ = reference_logits(fam.ref, cfg, params, toks, REF_LEN)
    low, _ = reference_logits(fam.ref, cfg, params, toks, REF_LEN,
                              precision="bfloat16")
    assert _gap(low, want) > 50 * F32_TOL
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
    got = forward(fam)(rounded, toks[None])[0]
    assert _gap(got, want) > 50 * F32_TOL


# -- prefill, then decode through the family's cache ------------------------

@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@per_family
def test_prefill_then_decode_matches_the_full_forward(fam, attn_impl):
    """Logits, not tokens, at every decode tick after the prompts, across
    block boundaries and as far as the family's own state turns
    (``fam.decoded``: the window's ring wraps, the original context is
    passed); two slots at different lengths in one decode batch, one
    idle.  ``pallas``: the family's decode kernel under the interpreter
    at the preset it tiles, blocks of 16, from prompts long enough that
    the decode has few ticks to go."""
    cfg, module, params = built(fam, attn_impl == "pallas")
    Bs, plens, ticks = (16, fam.pallas_prompts, 28) \
        if attn_impl == "pallas" else (4, (11, 9), 16)
    family = module.serve_family()
    served = family.prepare_params(params, jnp.float32)
    W = 3
    longest = max(plens) + ticks
    seqs = [draw_tokens(longest, 11 + n) for n in plens]
    M = -(-longest // Bs)
    want = [reference_logits(fam.ref, cfg, params, s, longest)[0]
            for s in seqs]
    cache = family.make_cache(2 * M + 1, Bs, W, jnp.float32)
    pool = cache.init_pool()
    ids = [cache.allocator.alloc(M) for _ in seqs]
    tables = jnp.asarray(ids + [[0] * M])
    if family.two_kind:
        R = cache.window_blocks
        rings = [cache.window_allocator.alloc(R) for _ in seqs]
        tables = (tables, jnp.asarray(rings + [[0] * R]))
    prefill = jax.jit(functools.partial(fam.program.paged_prefill, cfg))
    for i, (s, n, w) in enumerate(zip(seqs, plens, want)):
        bucket = -(-n // Bs) * Bs
        padded = jnp.zeros((bucket,), jnp.int32).at[:n].set(s[:n])
        blocks = jnp.asarray(ids[i][:bucket // Bs])
        if family.two_kind:
            blocks = (blocks, jnp.asarray(rings[i]))
        logits, pool, _ = prefill(served, pool, padded, jnp.int32(n), blocks)
        assert _gap(logits, w[n - 1]) < F32_TOL
    step = jax.jit(lambda pool, lens, toks: fam.program.paged_decode_step(
        cfg, served, pool, tables, lens, toks, attn_impl=attn_impl))
    worst = 0.0
    for t in range(ticks):
        lens = jnp.asarray([plens[0] + t, plens[1] + t, 0])
        toks = jnp.asarray([seqs[0][plens[0] + t], seqs[1][plens[1] + t], 0])
        logits, pool, counts = step(pool, lens, toks)
        for i in range(2):
            worst = max(worst, _gap(logits[i], want[i][plens[i] + t]))
        # The idle slot is out of the routing: 2 rows x k choices a layer.
        assert int(counts[0]) <= 2 * cfg.top_k * cfg.n_sparse
    fam.decoded(cfg, cache, pool, Bs, min(plens) + ticks)
    assert worst < F32_TOL, worst


@pytest.mark.parametrize("overlap", [False, True])
@per_family
def test_engine_serves_the_family_through_the_client_plane(fam, overlap):
    """Every served token is the reference's greedy choice along the
    served sequence; the family's counters count what the decode read
    (``fam.served``).  ``overlap``: as the benchmark's cells run it, one
    reply frame a tick, the next decode dispatched before the tokens are
    booked, and the three requests side by side."""
    cfg, module, params = built(fam)
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=3, block_size=4, max_model_len=64,
        coalesce_replies=overlap, decode_lookahead=overlap)).start()
    client = ServeClient(engine.queue_handle())
    try:
        prompts = [np.asarray(draw_tokens(n, 20 + n)).tolist()
                   for n in PROMPTS]
        if overlap:
            rids = [client.submit(p, 26) for p in prompts]
            served = [client.result(r, 120) for r in rids]
        else:
            served = [list(client.stream(p, 26)) for p in prompts]
    finally:
        client.close()
        engine.stop()
    assert (engine.stats.counters.get("decode_ahead", 0) > 0) == overlap
    for p, s in zip(prompts, served):
        assert len(s) == 26
        logits, _ = reference_logits(fam.ref, cfg, params,
                                     jnp.asarray(p + s), REF_LEN)
        rows = np.asarray(logits[len(p) - 1:len(p) - 1 + len(s)])
        gap = rows.max(-1) - rows[np.arange(len(s)), s]
        assert gap.max() < F32_TOL
    c = engine.stats.counters
    assert c["moe_tokens_routed"] == (
        c["tokens_out"] - c["prefills"]) * cfg.n_sparse
    fam.served(cfg, engine, overlap)
    assert engine.scheduler.snapshot()["blocks_live"] == 0


@pytest.mark.parametrize("fam", [Exaone, MiMo], ids=["exaone_moe", "mimo_v2"])
def test_engine_serves_the_same_tokens_on_the_decode_kernels(fam):
    """The preset the decode kernels tile, served by an engine whose
    module takes them (``attn_impl="pallas"``: ``rlt_paged_decode`` on the
    full layers, ``rlt_ring_decode`` on the window layers, both under
    the interpreter) and by one on the XLA paths: the same greedy tokens,
    past the window and round the ring.  Only the kernel engine counts
    ``decode_ring_positions``: a request of prompt ``n`` decodes at
    lengths ``n .. n + 10``, each tick attending at most the window's
    positions, its own row included, in every window layer."""
    cfg, _, params = built(fam, pallas=True)
    prompts = [np.asarray(draw_tokens(n, 30 + n)).tolist()
               for n in fam.pallas_prompts]
    served, counters = {}, {}
    for impl in ("xla", "pallas"):
        engine = ServeEngine(fam.Module(cfg, attn_impl=impl), params,
                             ServeConfig(num_slots=2, block_size=16,
                                         max_model_len=64,
                                         coalesce_replies=False,
                                         decode_lookahead=False))
        try:
            served[impl] = [engine.generate(p, 12, timeout=300)
                            for p in prompts]
        finally:
            engine.stop()
        counters[impl] = engine.stats.counters
    assert served["pallas"] == served["xla"]
    n_ring = len(cfg.layers_of("sliding"))
    want = sum(min(n + t, cfg.window - 1) + 1
               for n in fam.pallas_prompts for t in range(11))
    assert counters["pallas"]["decode_ring_positions"] == n_ring * want
    assert counters["xla"]["decode_ring_positions"] == 0


# -- what the family refuses ------------------------------------------------

@pytest.mark.parametrize("config,draft,names", [
    (dict(prefix_cache=True), False, "prefix_cache"),
    (dict(prefill_chunk=8), False, "prefill_chunk"),
    (dict(max_adapters=2, adapter_rank=4), False, "LoRA"),
    (dict(spec_k=2), True, "spec_k"),
], ids=["prefix_cache", "prefill_chunk", "lora", "speculation"])
@per_family
def test_engine_refuses_by_the_familys_name(fam, config, draft, names):
    cfg, module, params = built(fam)
    extra = dict(draft_module=module, draft_params=params) if draft else {}
    with pytest.raises(ValueError,
                       match=f"{fam.name} family.*{names}.*{fam.why}"):
        ServeEngine(module, params, ServeConfig(
            num_slots=2, block_size=4, max_model_len=32, **config), **extra)


@per_family
def test_block_transfer_is_refused_by_the_familys_name(fam):
    cfg, module, params = built(fam)
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=2, block_size=4, max_model_len=32))
    with pytest.raises(ValueError, match=f"export_blocks.*{fam.name}"):
        engine.export_resident()
    with pytest.raises(ValueError, match=f"import_blocks.*{fam.name}"):
        engine.submit([1, 2, 3], 2, _handoff={"kv": {}, "logits": None})
    with pytest.raises(ValueError, match=f"export_blocks.*{fam.name}"):
        engine.cache.export_blocks(engine._pool, [1])
    with pytest.raises(ValueError):                 # ids past the held slice
        engine.submit([1, cfg.n_vocab_held], 2)


# -- the cell's rehearsal ------------------------------------------------------

@pytest.fixture(scope="module", autouse=True)
def rehearsals(tmp_path_factory):
    """Every family's cell rehearsed (``benchmarks/run.py --rehearsal``,
    a process of its own and 35-50 s each), started side by side when
    the module starts so that they run beside its other cases; the case
    that reads one waits for it.  ``{name: (process, stdout, stderr)}``."""
    logs = tmp_path_factory.mktemp("rehearsals")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    runs = {}
    for fam in FAMILIES:
        out, err = (open(logs / f"{fam.name}.{ext}", "w+")
                    for ext in ("out", "err"))
        runs[fam.name] = (subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
             "--workload", fam.cell, "--seed", "3000000019", "--seconds",
             "2", "--trace", "1", "--rehearsal"],
            stdout=out, stderr=err, env=env, cwd=ROOT), out, err)
    yield runs
    for process, out, err in runs.values():
        process.kill()
        process.wait()
        out.close()
        err.close()


@per_family
def test_cell_rehearsal_end_to_end(rehearsals, fam):
    process, out, err = rehearsals[fam.name]
    code = process.wait()
    out.seek(0)
    err.seek(0)
    assert code == 0, err.read()[-2000:]
    rows = [json.loads(row) for row in out.read().splitlines()
            if row.startswith("{")]
    line = rows[-1]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    share = line["metrics"]["moe_local_share_pct.serve"]["value"]
    assert 20 < share < 30                          # 4 of 16 held
    phases = {row["phase"]: row for row in rows if "phase" in row}
    check = phases["reference_check"]
    assert check["ok"] and check["worst_logit_gap"] < F32_TOL
    assert check["program_forward"]["score_err"] < F32_TOL
    assert check["program_forward"]["logit_rms"] < F32_TOL
    assert check["program_forward"]["expert_choice_flips"] == 0
    # The float8 reference is far outside what float32 agreement allows.
    assert check["lowprec_reference"]["logit_rms"] > 1e3 * F32_TOL
    fam.rehearsed(line, phases)

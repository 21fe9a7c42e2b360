"""The ``sarvam_mla`` family against its plain reference
(``benchmarks/reference/sarvam_mla_ref.py``) at the tiny preset: the same
kinds as the served configuration (layer 0 dense, then sparse layers of
16 experts top-4 with a shared one and a drawn selection bias, a latent
of 16 beside a rotary key of 8, 4 heads, an original context of 16
positions stretched 8 times by YaRN), float32 weights.

Tolerances.  The program and the reference compute the same float32
sums in another order (the decode, in the absorbed form, a different
product of the same matrices), so they agree to a few ulps of values of
order 1: measured 1.0e-7 on logits of magnitude 0.5 (forward), 6e-8
(prefill) and 1.2e-7 through the cache.  ``F32_TOL = 2e-5`` leaves a
hundred times that and is a hundred times under what bfloat16 gives
where float32 is stated (held by
``test_bf16_where_float32_is_stated_fails_the_tolerance``).
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sarvam_mla_ref as ref
from ray_lightning_tpu.models import sarvam_mla as sm
from ray_lightning_tpu.models.exaone_moe import rope
from ray_lightning_tpu.models.sarvam_mla import (
    SarvamMLA, SarvamMLAConfig, sarvam_mla_tiny,
)
from ray_lightning_tpu.ops import paged_attention as pa
from ray_lightning_tpu.serve import ServeClient, ServeConfig, ServeEngine

F32_TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _uneven_gains(params, names, seed=7):
    """Gains that are not 1, so that a norm left out or misplaced shows."""
    key = jax.random.PRNGKey(seed)
    for i, p in enumerate(params["layers"]):
        for j, name in enumerate(names):
            k = jax.random.fold_in(key, 16 * i + j)
            p[name] = 1.0 + 0.3 * jax.random.normal(k, p[name].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = sarvam_mla_tiny(experts_held=(4, 8), vocab_held=(0, 128))
    module = SarvamMLA(cfg)
    params = _uneven_gains(
        module.init_params(jax.random.PRNGKey(0)),
        ("q_norm", "kv_norm", "attn_norm", "ffn_norm"))
    return cfg, module, params


def _tokens(n, seed=1, vocab=128):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab)


# -- the config ---------------------------------------------------------------

def test_yarn_frequencies_and_scale_by_hand():
    """At the published sizes: 64 rotary columns, theta 1e4, factor 40
    over an original 4096 positions, beta 32 / 1."""
    cfg = SarvamMLAConfig()
    # Index of the frequency that turns n times in 4096 positions:
    # 64 ln(4096 / (2 pi n)) / (2 ln 1e4) = 10.47 (n = 32), 22.51 (n = 1).
    assert 64 * math.log(4096 / (64 * math.pi)) / (2 * math.log(1e4)) \
        == pytest.approx(10.47, abs=0.01)
    assert cfg.yarn_range == (10, 23)
    inv = cfg.rope_inv_freq
    f = 1e4 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)    # unscaled
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)
    # Half way up the ramp (i = 16.5 lies between): i = 16 is 6 / 13.
    ramp = 6 / 13
    assert inv[16] == pytest.approx(f[16] / 40 * ramp + f[16] * (1 - ramp),
                                    rel=1e-6)
    # m = 0.1 ln 40 + 1 = 1.36889; sigma = 192^-0.5 m^2.
    assert 0.1 * math.log(40) + 1 == pytest.approx(1.36889, abs=1e-5)
    assert cfg.softmax_scale == pytest.approx(0.135234, abs=1e-6)
    assert cfg.rope_attention_factor == 1.0
    assert (cfg.cache_row, cfg.pool_row, cfg.q_head_dim) == (576, 640, 192)
    # The reference works them out on its own.
    r_inv, r_factor, low, high = ref.yarn_parameters(ref.config_of(cfg))
    assert (low, high, r_factor) == (10, 23, 1.0)
    np.testing.assert_allclose(np.asarray(r_inv), inv, rtol=1e-6)
    assert ref.softmax_scale(ref.config_of(cfg)) == pytest.approx(
        cfg.softmax_scale)


def test_rope_takes_its_frequencies_as_data():
    """The default frequencies handed in as data are the default; a
    factor scales cos and sin."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 2, 8))
    pos = jnp.arange(5)
    inv = 1e4 ** (-jnp.arange(0, 8, 2) / 8)
    np.testing.assert_array_equal(rope(x, pos, 1e4),
                                  rope(x, pos, 1e4, inv_freq=inv))
    # (cos, sin scaled before the sum or the sum after: float32 ulps.)
    np.testing.assert_allclose(rope(x, pos, 1e4, factor=1.5),
                               1.5 * rope(x, pos, 1e4), atol=1e-6)


@pytest.mark.parametrize("bad", [
    dict(experts_held=(4, 4)), dict(experts_held=(0, 17)),
    dict(vocab_held=(0, 999)), dict(qk_rope_head_dim=7),
    dict(first_dense=9)])
def test_config_refuses_what_is_not_a_share_or_a_shape(bad):
    with pytest.raises(ValueError):
        sarvam_mla_tiny(**bad)


def test_config_file_holds_the_published_widths_uncut():
    with open(os.path.join(
            ROOT, "benchmarks/configs/sarvam-105b-ep8.json")) as f:
        doc = json.load(f)
    fields = dict(doc["fields"])
    for key in ("experts_held", "vocab_held"):
        fields[key] = tuple(fields[key])
    cfg = SarvamMLAConfig(**fields)
    assert (cfg.d_model, cfg.n_head, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.d_ff, cfg.d_expert,
            cfg.n_experts, cfg.top_k, cfg.routed_scale) == (
        4096, 64, 512, 128, 64, 128, 16384, 2048, 128, 8, 2.5)
    assert (cfg.rope_factor, cfg.rope_original_len, cfg.rope_beta_fast,
            cfg.rope_beta_slow, cfg.rope_theta, cfg.rms_eps) == (
        40, 4096, 32, 1, 1e4, 1e-6)
    assert cfg.n_layer == 8 and cfg.mlp_types == ("dense",) + ("sparse",) * 7
    assert cfg.n_experts_held == 16 and cfg.n_vocab_held == 32768
    # Widths under the source's own keys, and what was cut under its name.
    assert (doc["hidden_size"], doc["intermediate_size"],
            doc["moe_intermediate_size"], doc["num_experts_per_tok"],
            doc["kv_lora_rank"], doc["q_head_dim"], doc["head_dim"]) == (
        4096, 16384, 2048, 8, 512, 192, 576)
    assert doc["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key in ("changed", "assumed", "deployment", "published",
                "reduced_how"):
        assert doc[key]
    assert "8 chips share each layer" in doc["deployment"]


# -- forward against the reference ------------------------------------------

@pytest.mark.parametrize("n", [5, 40], ids=["short", "past_original"])
@pytest.mark.parametrize("moe_impl", ["xla", "pallas"])
def test_forward_matches_the_reference(tiny, n, moe_impl):
    """``n`` = 40 runs past the tiny config's original 16 positions."""
    cfg, _, params = tiny
    module = SarvamMLA(cfg, moe_impl=moe_impl)
    toks = _tokens(n)
    got = module.forward(params, toks[None])[0]
    want, routing = ref.forward(ref.config_of(cfg), params, toks)
    assert got.shape == (n, cfg.n_vocab_held) and len(routing) == cfg.n_sparse
    assert float(jnp.abs(got - want).max()) < F32_TOL


def test_prepared_tree_gives_the_same_forward(tiny):
    """``W_uk`` / ``W_uv`` rearranged once are the ``wkvb`` they came
    from: the served tree has no ``wkvb`` and the same logits."""
    cfg, module, params = tiny
    served = module.serve_family().prepare_params(params, jnp.float32)
    assert all("wkvb" not in p and p["w_uk"].shape == (4, 8, 16)
               and p["w_uv"].shape == (4, 16, 8) for p in served["layers"])
    assert served["layers"][0]["wq"] is params["layers"][0]["wq"]
    toks = _tokens(12)[None]
    np.testing.assert_array_equal(module.forward(served, toks),
                                  module.forward(params, toks))


def test_flash_forward_takes_a_value_width_of_its_own(tiny):
    """The prefill's kernel path (head width 16, values of 8) under the
    interpreter against the masked form, and no backward there."""
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    cfg, _, params = tiny
    toks = _tokens(128)[None]
    flash = SarvamMLA(cfg, attn_impl="flash").forward(params, toks)
    plain = SarvamMLA(cfg, attn_impl="xla").forward(params, toks)
    assert float(jnp.abs(flash - plain).max()) < F32_TOL
    q = jnp.ones((1, 128, 2, 16))
    with pytest.raises(NotImplementedError, match="one head width"):
        jax.grad(lambda v: flash_attention(q, q, v).sum())(
            jnp.ones((1, 128, 2, 8)))


def test_bf16_where_float32_is_stated_fails_the_tolerance(tiny):
    cfg, module, params = tiny
    toks = _tokens(24)
    want, _ = ref.forward(ref.config_of(cfg), params, toks)
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
    got = module.forward(low, toks[None])[0]
    assert float(jnp.abs(got - want).max()) > 50 * F32_TOL


def test_absorbed_attention_is_the_unabsorbed(tiny):
    """The mixer alone: one query against a sequence's rows, as decode
    scores it (``[q_n W_uk^T | q_r]`` against ``[c | k_r]``, ``P c`` then
    ``W_uv``) and as the published form does (per-head keys and values)."""
    cfg, _, params = tiny
    p = params["layers"][1]
    T = 21
    h = jax.random.normal(jax.random.PRNGKey(3), (1, T, cfg.d_model))
    positions = jnp.arange(T)[None]
    q_n, q_r, rows = sm.latent_projections(cfg, p, h, positions)
    full = sm.attend_sequence(cfg, p, q_n, q_r, rows, "xla")[0, -1]
    q = sm.absorbed_queries(cfg, p, q_n[0, -1:], q_r[0, -1:])
    assert q.shape == (1, cfg.n_head, cfg.pool_row)
    padded = sm._pad_row(cfg, rows[0])
    pool = padded[:-1].reshape(1, 5, 4, cfg.pool_row)      # 20 cached
    att = pa.mla_decode_attention(
        q, padded[-1:], pool, jnp.int32(0), jnp.arange(5)[None],
        jnp.asarray([T - 1]), rank=cfg.kv_lora_rank,
        scale=cfg.softmax_scale, impl="xla")
    _, w_uv = sm.up_projections(cfg, p)
    got = jnp.einsum("whr,hrv->whv", att, w_uv).reshape(-1)
    assert float(jnp.abs(got - full).max()) < F32_TOL


# -- prefill, then decode through the latent cache --------------------------

@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_prefill_then_decode_matches_the_full_forward(tiny, attn_impl):
    """Logits, not tokens, at every decode tick after the prompts: across
    block boundaries and past the original context (16 positions; 64 in
    the ``pallas`` preset); two slots at different lengths in one decode
    batch, one slot idle.  ``pallas`` runs ``rlt_mla_decode`` under the
    interpreter at a preset it tiles (16 heads, a latent of 128 and a
    rotary key of 64 in rows of 256, blocks of 16)."""
    cfg, module, params = tiny
    Bs, ticks = 4, 30
    if attn_impl == "pallas":
        cfg = sarvam_mla_tiny(
            n_layer=2, n_head=16, kv_lora_rank=128, qk_rope_head_dim=64,
            rope_original_len=64, experts_held=(4, 8), vocab_held=(0, 128))
        module = SarvamMLA(cfg)
        params = _uneven_gains(module.init_params(jax.random.PRNGKey(1)),
                               ("q_norm", "kv_norm"))
        Bs, ticks = 16, 70
    fam = module.serve_family()
    served = fam.prepare_params(params, jnp.float32)
    W, plens = 3, [11, 6]
    seqs = [_tokens(n + ticks, 11 + n) for n in plens]
    M = -(-max(map(len, seqs)) // Bs)
    want = [ref.forward(ref.config_of(cfg), params, s)[0] for s in seqs]
    cache = fam.make_cache(2 * M + 1, Bs, W, jnp.float32)
    pool = cache.init_pool()
    assert pool["kv"].shape == (cfg.n_layer, 2 * M + 1, Bs, cfg.pool_row)
    ids = [cache.allocator.alloc(M) for _ in seqs]
    for s, n, blocks, w in zip(seqs, plens, ids, want):
        bucket = -(-n // Bs) * Bs
        padded = jnp.zeros((bucket,), jnp.int32).at[:n].set(s[:n])
        logits, pool, _ = sm.paged_prefill(
            cfg, served, pool, padded, jnp.int32(n),
            jnp.asarray(blocks[:bucket // Bs]))
        assert float(jnp.abs(logits - w[n - 1]).max()) < F32_TOL
    tables = jnp.asarray(ids + [[0] * M])
    step = jax.jit(lambda pool, lens, toks: sm.paged_decode_step(
        cfg, served, pool, tables, lens, toks, attn_impl=attn_impl))
    worst = 0.0
    for t in range(ticks):
        lens = jnp.asarray([plens[0] + t, plens[1] + t, 0])
        toks = jnp.asarray([seqs[0][plens[0] + t], seqs[1][plens[1] + t], 0])
        logits, pool, counts = step(pool, lens, toks)
        for i in range(2):
            worst = max(worst, float(
                jnp.abs(logits[i] - want[i][plens[i] + t]).max()))
        # The idle slot is out of the routing: 2 rows x k choices a layer.
        assert int(counts[0]) <= 2 * cfg.top_k * cfg.n_sparse
    assert min(plens) + ticks > cfg.rope_original_len
    # The padding lanes stay zero: never written, never read as data.
    assert not np.asarray(pool["kv"][..., cfg.cache_row:]).any()
    assert worst < F32_TOL, worst


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_decode_kernel_under_the_interpreter(dtype):
    """``rlt_mla_decode`` against the XLA path of the same arithmetic:
    ragged lengths (an empty cache, a partial block, the edge of a chunk
    of 512, past it), an idle slot, blocks scattered over the pool, a
    layer index that is not 0.  float32: the same sums in another order
    (1e-5).  bfloat16 pool and queries: both paths multiply the stored
    values exactly and accumulate in float32, and both round the
    probabilities to bfloat16 where they meet the values, the kernel
    before it normalises and the XLA path after: 8 bits each way on
    outputs of order 1 (1e-2)."""
    H, r, dr, Bs, M, L = 16, 128, 64, 16, 40, 2
    row = 256
    lens = jnp.asarray([0, 5, 512, 530, 0, 639], jnp.int32)
    W = lens.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = jax.random.normal(ks[0], (L, W * M + 1, Bs, row)).astype(dtype)
    pool = pool.at[..., r + dr:].set(0)
    q = jax.random.normal(ks[1], (W, H, row)).at[..., r + dr:].set(0)
    cur = jax.random.normal(ks[2], (W, row)).at[..., r + dr:].set(0)
    tables = 1 + jax.random.permutation(ks[3], W * M).reshape(W, M)
    args = (q.astype(dtype), cur.astype(dtype), pool, jnp.int32(1),
            tables.astype(jnp.int32), lens)
    kw = dict(rank=r, scale=0.11)
    want = pa.mla_decode_attention(*args, impl="xla", **kw)
    got = pa.mla_decode_attention(*args, impl="pallas", **kw)
    assert got.shape == (W, H, r) and got.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert float(jnp.abs(got - want).max()) < tol, float(
        jnp.abs(got - want).max())
    # A slot with nothing cached attends its own row alone.
    np.testing.assert_allclose(
        got[0], jnp.broadcast_to(cur.astype(dtype)[0, :r], (H, r)),
        rtol=1e-6)


def test_mla_decode_kernel_rounds_its_probabilities_within_bf16():
    """What ``round_probs`` costs, against arithmetic that does not
    round: the kernel (and its XLA twin, which rounds the same way, so
    the comparison above cannot see it) hands the probabilities to the
    ``P @ c`` matmul in the pool's bfloat16; exact arithmetic on the
    same stored values keeps them in float64.  A bfloat16 rounding moves
    a probability by at most 2^-9 of itself, so an output (a convex
    combination of the cached ``c``) by at most 2^-9 of the largest
    ``|c|`` a slot holds: that bound is the tolerance, and the measured
    error (0.4 of the bound over five positions, 0.07 of it over
    hundreds, where the roundings' signs mix: 2.4e-3 and 5.5e-4) has to
    be above float32's noise, or the kernel no longer rounds and the
    configuration file's ``compute_dtype`` note is stale."""
    H, r, dr, Bs, M, L = 16, 128, 64, 16, 40, 1
    row = 256
    lens = jnp.asarray([5, 512, 639], jnp.int32)
    W = lens.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    pool = jax.random.normal(ks[0], (L, W * M + 1, Bs, row)).astype(
        jnp.bfloat16).at[..., r + dr:].set(0)
    q = jax.random.normal(ks[1], (W, H, row)).astype(
        jnp.bfloat16).at[..., r + dr:].set(0)
    cur = jax.random.normal(ks[2], (W, row)).astype(
        jnp.bfloat16).at[..., r + dr:].set(0)
    tables = (1 + jax.random.permutation(ks[3], W * M).reshape(W, M)).astype(
        jnp.int32)
    scale = 0.11
    got = np.asarray(pa.mla_decode_attention(
        q, cur, pool, jnp.int32(0), tables, lens, rank=r, scale=scale,
        impl="pallas"), np.float64)
    rows = np.asarray(pool[0].astype(jnp.float32), np.float64)[
        np.asarray(tables)].reshape(W, M * Bs, row)
    q64 = np.asarray(q.astype(jnp.float32), np.float64)
    cur64 = np.asarray(cur.astype(jnp.float32), np.float64)
    worst = 0.0
    for w in range(W):
        ctx = np.concatenate([rows[w, :int(lens[w])], cur64[w][None]])
        s = q64[w] @ ctx.T * scale
        probs = np.exp(s - s.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        err = np.abs(got[w] - probs @ ctx[:, :r]).max()
        assert err <= 2.0 ** -9 * np.abs(ctx[:, :r]).max(), (w, err)
        worst = max(worst, err)
    assert worst > 1e-5, worst


def test_mla_kernel_refuses_what_it_cannot_tile():
    pool = jnp.zeros((1, 4, 16, 256), jnp.float32)
    assert pa.mla_decode_tiles(pool, 16, 128)
    assert not pa.mla_decode_tiles(pool, 4, 128)         # heads under a tile
    assert not pa.mla_decode_tiles(pool, 16, 96)         # values not lanes
    assert not pa.mla_decode_tiles(pool[..., :192], 16, 128)
    assert not pa.mla_decode_supported(pool, 16, 128)    # no TPU here
    with pytest.raises(ValueError, match="rlt_mla_decode does not tile"):
        pa.mla_decode_attention(
            jnp.zeros((2, 4, 256)), jnp.zeros((2, 256)), pool, 0,
            jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32),
            rank=128, scale=1.0)


# -- the router ---------------------------------------------------------------

def test_a_bias_changes_the_chosen_set_and_not_the_gates(tiny):
    """The drawn bias is not zero; it selects and does not weigh: with
    it the chosen set differs from the scores' own top-k on some rows,
    and the gates are the chosen scores over their sum whatever chose
    them."""
    from ray_lightning_tpu.ops.moe import sigmoid_topk_routing

    cfg, _, params = tiny
    p = params["layers"][2]
    assert float(jnp.abs(p["router_bias"]).max()) > 0
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    bias = 20 * p["router_bias"]            # large enough to flip choices
    idx, gates, z = sigmoid_topk_routing(
        x, p["router"], bias, cfg.top_k, cfg.routed_scale,
        return_scores=True)
    idx0, gates0 = sigmoid_topk_routing(
        x, p["router"], jnp.zeros_like(bias), cfg.top_k, cfg.routed_scale)
    changed = (np.sort(np.asarray(idx), -1)
               != np.sort(np.asarray(idx0), -1)).any(-1)
    assert 0 < changed.sum()
    chosen = jnp.take_along_axis(z, idx, -1)
    np.testing.assert_allclose(
        gates, cfg.routed_scale * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)
    # The reference's router under the same bias agrees on both.
    z_ref, idx_ref, gates_ref = ref.router(
        ref.config_of(cfg), dict(p, router_bias=bias), x)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.asarray(idx_ref), -1))
    np.testing.assert_allclose(z, z_ref, atol=1e-6)
    order, order_ref = np.argsort(idx, -1), np.argsort(idx_ref, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        cfg.routed_scale * np.take_along_axis(
            np.asarray(gates_ref), order_ref, -1), rtol=1e-5)


# -- the engine ----------------------------------------------------------------

@pytest.mark.parametrize("overlap", [False, True])
def test_engine_serves_the_family_through_the_client_plane(tiny, overlap):
    """Every served token is the reference's greedy choice along the
    served sequence (its argmax at that position); the latent counters
    count what the decode attended."""
    cfg, module, params = tiny
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=3, block_size=4, max_model_len=64,
        coalesce_replies=overlap, decode_lookahead=overlap)).start()
    client = ServeClient(engine.queue_handle())
    try:
        prompts = [np.asarray(_tokens(n, 20 + n)).tolist()
                   for n in (5, 13, 22)]
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
        logits, _ = ref.forward(ref.config_of(cfg), params,
                                jnp.asarray(p + s))
        rows = np.asarray(logits[len(p) - 1:len(p) - 1 + len(s)])
        gap = rows.max(-1) - rows[np.arange(len(s)), s]
        assert gap.max() < F32_TOL
    c = engine.stats.counters
    assert c["latent_row_bytes"] == cfg.cache_row * 4       # float32 here
    assert c["moe_tokens_routed"] == (
        c["tokens_out"] - c["prefills"]) * cfg.n_sparse
    if not overlap:
        # One at a time: a request of prompt n decodes 25 tokens at
        # lengths n .. n + 24, each attending its own row too.
        want = sum(sum(n + t + 1 for t in range(25)) for n in (5, 13, 22))
        assert c["decode_latent_positions"] == want * cfg.n_layer
    assert "decode_kv_blocks_read_window" not in c          # one kind
    assert engine.family.two_kind is False
    assert engine.scheduler.snapshot()["blocks_live"] == 0


@pytest.mark.parametrize("config,kwargs,names", [
    (dict(prefix_cache=True), {}, "prefix_cache"),
    (dict(prefill_chunk=8), {}, "prefill_chunk"),
    (dict(max_adapters=2, adapter_rank=4), {}, "LoRA"),
    (dict(spec_k=2), {"draft": True}, "spec_k"),
], ids=["prefix_cache", "prefill_chunk", "lora", "speculation"])
def test_engine_refuses_by_the_familys_name(tiny, config, kwargs, names):
    cfg, module, params = tiny
    extra = {}
    if kwargs.get("draft"):
        extra = dict(draft_module=module, draft_params=params)
    with pytest.raises(ValueError,
                       match=f"sarvam_mla family.*{names}.*latent rows"):
        ServeEngine(module, params, ServeConfig(
            num_slots=2, block_size=4, max_model_len=32, **config), **extra)


def test_block_transfer_is_refused_by_the_familys_name(tiny):
    cfg, module, params = tiny
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=2, block_size=4, max_model_len=32))
    with pytest.raises(ValueError, match="export_blocks.*sarvam_mla"):
        engine.export_resident()
    with pytest.raises(ValueError, match="import_blocks.*sarvam_mla"):
        engine.submit([1, 2, 3], 2, _handoff={"kv": {}, "logits": None})
    with pytest.raises(ValueError, match="export_blocks.*sarvam_mla"):
        engine.cache.export_blocks(engine._pool, [1])
    with pytest.raises(ValueError):                 # ids past the held slice
        engine.submit([1, cfg.n_vocab_held], 2)


def test_every_family_says_what_it_refuses():
    """One attribute on the three families; GPT's is empty, and
    ``two_kind`` means ring tables and nothing else."""
    from ray_lightning_tpu.models.exaone_moe import ServeFamily as Exaone
    from ray_lightning_tpu.serve.kv_cache import GPTServeFamily

    assert GPTServeFamily.refuses == () and not GPTServeFamily.two_kind
    assert set(Exaone.refuses) == set(sm.ServeFamily.refuses) == {
        "prefix_cache", "spec_k", "draft", "adapters", "prefill_chunk",
        "block_transfer"}
    assert Exaone.two_kind and not sm.ServeFamily.two_kind
    assert "window ring" in Exaone.refuses_why
    assert "latent rows" in sm.ServeFamily.refuses_why


# -- the cell's rehearsal ------------------------------------------------------

def test_cell_rehearsal_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
         "--workload", "sarvam-105b-ep8.serve-longctx", "--seed",
         "3000000019", "--seconds", "2", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    share = line["metrics"]["moe_local_share_pct.serve"]["value"]
    assert 20 < share < 30                          # 4 of 16 held
    assert 0 < line["metrics"]["kv_read_share_pct.serve"]["value"] <= 100
    # No chip: the kernel's time and its roofline share are left out.
    assert "mla_decode_roofline.serve" not in line["metrics"]
    check, in_window = (
        [json.loads(row) for row in out.stdout.splitlines()
         if row.startswith('{"phase": "%s"' % phase)][0]
        for phase in ("reference_check", "reference_check_window"))
    assert check["ok"] and check["worst_logit_gap"] < F32_TOL
    assert check["sequence_lengths"][-1] > check["original_context"]
    # What the window itself served, every slot live: held to the
    # reference after it closes, past the original context too.
    assert in_window["ok"] and in_window["worst_logit_gap"] < F32_TOL
    assert in_window["tokens"] > 0
    assert in_window["sequence_lengths"][-1] > in_window["original_context"]
    assert check["program_forward"]["score_err"] < F32_TOL
    assert check["program_forward"]["logit_rms"] < F32_TOL
    assert check["program_forward"]["expert_choice_flips"] == 0
    # The float8 reference is far outside what float32 agreement allows.
    assert check["lowprec_reference"]["logit_rms"] > 1e3 * F32_TOL

"""The ``exaone_moe`` family against its plain reference
(``benchmarks/reference/exaone_moe_ref.py``) at the tiny preset: the same
kinds in the same ratios as the served configuration (8 layers ``LLLG
LLLG``, layer 0 dense, 16 experts top-4, 4 held, window 8, 4 query / 2
K/V heads), float32 weights.

Tolerances.  The program and the reference compute the same float32
sums in another order, so they agree to a few ulps of values of order
1: measured 1.5e-7 on logits of magnitude 0.4 (forward) and 4e-7
through the cache.  ``F32_TOL = 2e-5`` leaves a hundred times that and
is a hundred times under what bfloat16 gives where float32 is stated
(3e-3, held by ``test_bf16_where_float32_is_stated_fails_the_tolerance``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import exaone_moe_ref as ref
from ray_lightning_tpu.models import exaone_moe as em
from ray_lightning_tpu.models.exaone_moe import (
    ExaoneMoE, ExaoneMoEConfig, exaone_moe_tiny,
)
from ray_lightning_tpu.ops import moe as moe_ops
from ray_lightning_tpu.serve import ServeClient, ServeConfig, ServeEngine

F32_TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = exaone_moe_tiny(experts_held=(4, 8), vocab_held=(0, 128))
    module = ExaoneMoE(cfg)
    params = module.init_params(jax.random.PRNGKey(0))
    # Gains that are not 1, so that a norm left out or misplaced shows.
    key = jax.random.PRNGKey(7)
    for i, p in enumerate(params["layers"]):
        for j, name in enumerate(("q_norm", "k_norm", "attn_out_norm",
                                  "ffn_out_norm")):
            k = jax.random.fold_in(key, 16 * i + j)
            p[name] = 1.0 + 0.3 * jax.random.normal(k, p[name].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)
    return cfg, module, params


def _tokens(n, seed=1, vocab=128):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab)


# -- the config ---------------------------------------------------------------

def test_published_pattern_is_the_default():
    cfg = ExaoneMoEConfig()
    assert cfg.layer_types[:8] == ("sliding",) * 3 + ("full",) \
        + ("sliding",) * 3 + ("full",)
    assert cfg.mlp_types[0] == "dense" and set(cfg.mlp_types[1:]) == {"sparse"}
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_ff,
            cfg.d_expert, cfg.n_experts, cfg.top_k, cfg.window) == (
        6144, 64, 8, 128, 18432, 2048, 128, 8, 128)
    assert cfg.n_experts_held == 128 and cfg.n_vocab_held == 153600


@pytest.mark.parametrize("bad", [
    dict(experts_held=(8, 20)), dict(vocab_held=(0, 999)),
    dict(layer_types=("full",) * 3), dict(n_kv_head=3),
    dict(mlp_types=("moe",) * 8),
])
def test_config_refuses_what_is_not_a_share_or_a_pattern(bad):
    with pytest.raises(ValueError):
        exaone_moe_tiny(**bad)


def test_config_file_holds_the_published_widths_uncut():
    with open(os.path.join(
            ROOT, "benchmarks/configs/k-exaone-236b-a23b-ep8.json")) as f:
        doc = json.load(f)
    fields = dict(doc["fields"])
    for key in ("experts_held", "vocab_held"):
        fields[key] = tuple(fields[key])
    cfg = ExaoneMoEConfig(**fields)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_ff,
            cfg.d_expert, cfg.n_experts, cfg.top_k, cfg.window) == (
        6144, 64, 8, 128, 18432, 2048, 128, 8, 128)
    assert cfg.n_layer == 8 and cfg.n_experts_held == 16
    assert cfg.n_vocab_held == 19200 == 150 * 128
    assert cfg.layer_types == tuple(
        {"sliding_attention": "sliding", "full_attention": "full"}[t]
        for t in doc["layer_types"])
    assert cfg.mlp_types == tuple(doc["mlp_layer_types"])
    # Widths under the source's own keys, and what was cut under its name.
    assert (doc["hidden_size"], doc["intermediate_size"],
            doc["moe_intermediate_size"], doc["num_experts_per_tok"],
            doc["sliding_window"], doc["head_dim"]) == (
        6144, 18432, 2048, 8, 128, 128)
    assert set(doc["reduced"]) >= {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    for key in ("changed", "assumed", "deployment", "published"):
        assert doc[key]


# -- forward against the reference ------------------------------------------

@pytest.mark.parametrize("n", [5, 24, 40], ids=["short", "ragged", "banded"])
@pytest.mark.parametrize("moe_impl", ["xla", "pallas"])
def test_forward_matches_the_reference(tiny, n, moe_impl):
    """``n`` = 40 takes the banded sliding form (5 whole windows), 24 the
    plain mask beyond the window, 5 stays inside it."""
    cfg, _, params = tiny
    module = ExaoneMoE(cfg, moe_impl=moe_impl)
    toks = _tokens(n)
    got = module.forward(params, toks[None])[0]
    want, _ = ref.forward(ref.config_of(cfg), params, toks)
    assert got.shape == (n, cfg.n_vocab_held)
    assert float(jnp.abs(got - want).max()) < F32_TOL


def test_forward_batches_rows_independently(tiny):
    cfg, module, params = tiny
    toks = jnp.stack([_tokens(16, 3), _tokens(16, 4)])
    both = module.forward(params, toks)
    for i in range(2):
        alone = module.forward(params, toks[i:i + 1])[0]
        assert float(jnp.abs(both[i] - alone).max()) < F32_TOL


def test_bf16_where_float32_is_stated_fails_the_tolerance(tiny):
    cfg, _, params = tiny
    toks = _tokens(24)
    want, _ = ref.forward(ref.config_of(cfg), params, toks)
    low, _ = ref.forward(ref.config_of(cfg), params, toks,
                         precision="bfloat16")
    assert float(jnp.abs(low - want).max()) > 20 * F32_TOL
    bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
    got = ExaoneMoE(dataclasses.replace(cfg, param_dtype="bfloat16")).forward(
        bf16, toks[None])[0]
    assert float(jnp.abs(got - want).max()) > 20 * F32_TOL


def test_loss_has_gradients_on_the_xla_path(tiny):
    cfg, _, params = tiny
    module = ExaoneMoE(cfg, attn_impl="xla", moe_impl="xla")
    batch = jnp.stack([_tokens(17, 5), _tokens(17, 6)])
    loss, grads = jax.value_and_grad(module._loss)(params, batch)
    assert np.isfinite(float(loss))
    assert float(jnp.abs(grads["layers"][1]["e_gate"]).max()) > 0
    assert float(jnp.abs(grads["layers"][3]["wk"]).max()) > 0


# -- prefill, then decode through the two-kind cache ------------------------

@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_prefill_then_decode_matches_the_full_forward(tiny, attn_impl):
    """Logits, not tokens, at every decode tick after the prompts: past
    the window and past the ring (window / Bs + 1 blocks), so the ring
    wraps; two slots at different lengths in one decode batch, one slot
    idle.  ``pallas`` runs ``rlt_paged_decode`` with grouped queries
    under the interpreter, at a preset it tiles (heads of 128)."""
    cfg, _, params = tiny
    Bs, ticks = 4, 30
    if attn_impl == "pallas":
        cfg = exaone_moe_tiny(
            n_layer=4, n_head=16, n_kv_head=2, head_dim=128, window=16,
            experts_held=(4, 8), vocab_held=(0, 128))
        params = ExaoneMoE(cfg).init_params(jax.random.PRNGKey(1))
        Bs, ticks = 16, 48
    W, R = 3, cfg.window // Bs + 1
    plens = [11, 6]
    seqs = [_tokens(n + ticks, 11 + n) for n in plens]
    M = -(-max(map(len, seqs)) // Bs)
    want = [ref.forward(ref.config_of(cfg), params, s)[0] for s in seqs]
    cache = em.TwoKindKVCache(cfg, 2 * M + 1, Bs, W, jnp.float32)
    assert cache.window_blocks == R
    pool = cache.init_pool()
    full_ids = [cache.allocator.alloc(M) for _ in seqs]
    ring_ids = [cache.window_allocator.alloc(R) for _ in seqs]
    for s, n, ids, ring, w in zip(seqs, plens, full_ids, ring_ids, want):
        bucket = -(-n // Bs) * Bs
        padded = jnp.zeros((bucket,), jnp.int32).at[:n].set(s[:n])
        logits, pool, _ = em.paged_prefill(
            cfg, params, pool, padded, jnp.int32(n),
            (jnp.asarray(ids[:bucket // Bs]), jnp.asarray(ring)))
        assert float(jnp.abs(logits - w[n - 1]).max()) < F32_TOL
    tables = (jnp.asarray(full_ids + [[0] * M]),
              jnp.asarray(ring_ids + [[0] * R]))
    step = jax.jit(lambda pool, lens, toks: em.paged_decode_step(
        cfg, params, pool, tables, lens, toks, attn_impl=attn_impl))
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
    assert min(plens) + ticks > cfg.window + R * Bs     # the ring wrapped
    assert worst < F32_TOL, worst


@pytest.mark.parametrize("overlap", [False, True])
def test_engine_serves_the_family_through_the_client_plane(tiny, overlap):
    """``overlap``: as the benchmark's cell runs it, one reply frame a
    tick and the next decode dispatched before the tokens are booked;
    the three requests then run side by side."""
    cfg, module, params = tiny
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=3, block_size=4, max_model_len=64,
        coalesce_replies=overlap, decode_lookahead=overlap)).start()
    client = ServeClient(engine.queue_handle())
    try:
        prompts = [np.asarray(_tokens(n, 20 + n)).tolist() for n in (5, 13, 22)]
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
    assert c["moe_tokens_routed"] == (
        c["tokens_out"] - c["prefills"]) * cfg.n_sparse
    share = c["moe_local_assignments"] / (c["moe_tokens_routed"] * cfg.top_k)
    assert 0.15 < share < 0.35          # 4 of 16 experts held
    # Sliding layers read the slot's ring (3 blocks here, 5 in the cell)
    # a slot a layer a tick, never more.
    ticks_slots = c["tokens_out"] - c["prefills"]
    assert c["decode_kv_blocks_read_window"] == ticks_slots * 3 * 6
    assert c["decode_kv_blocks_read"] == (
        c["decode_kv_blocks_read_window"] + c["decode_kv_blocks_read_full"])
    snap = engine.scheduler.snapshot()
    assert snap["window_blocks_live"] == 0 and snap["blocks_live"] == 0


# -- the share ------------------------------------------------------------------

def _sarvam_share_case():
    from benchmarks.reference import sarvam_mla_ref
    from ray_lightning_tpu.models.sarvam_mla import (
        SarvamMLA, sarvam_mla_tiny,
    )

    return sarvam_mla_tiny(), SarvamMLA, sarvam_mla_ref, 8


@pytest.mark.parametrize("family", ["exaone_moe", "sarvam_mla"])
def test_all_shares_add_up_to_the_uncut_layer(tiny, family):
    """The parts all the shares give (four of 4 experts for
    ``exaone_moe``; eight of 2, under a drawn selection bias, for
    ``sarvam_mla``), the shared expert counted once, are the uncut layer
    (16 of 16 experts)."""
    cfg, Module, ref_mod, shares = (tiny[0], ExaoneMoE, ref, 4) \
        if family == "exaone_moe" else _sarvam_share_case()
    whole_cfg = dataclasses.replace(cfg, experts_held=(0, 16))
    module = Module(whole_cfg)
    p = module.init_params(jax.random.PRNGKey(3))["layers"][2]
    assert (float(jnp.abs(p["router_bias"]).max()) > 0) == (
        family == "sarvam_mla")
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model))
    whole, counts = em.feed_forward(whole_cfg, "sparse", p, x, None, "xla")
    assert int(counts[0]) == 24 * cfg.top_k
    shared = em.swiglu(x, p, "s_")
    routed = jnp.zeros_like(x)
    held = 16 // shares
    for lo in range(0, 16, held):
        part_cfg = dataclasses.replace(cfg, experts_held=(lo, lo + held))
        part = dict(p, **{k: p[k][lo:lo + held]
                          for k in ("e_gate", "e_up", "e_down")})
        f, _ = em.feed_forward(part_cfg, "sparse", part, x, None, "xla")
        routed = routed + (f - shared)
    assert float(jnp.abs(routed + shared - whole).max()) < F32_TOL
    # And the uncut layer is the reference's.
    rcfg = dict(ref_mod.config_of(whole_cfg))
    want, _ = ref_mod.sparse_ffn(rcfg, p, x, "float32")
    assert float(jnp.abs(whole - want).max()) < F32_TOL


# -- the dropless layer -------------------------------------------------------

def _skewed_routing(s, e, k, seed):
    """Most rows choose experts 0 and 1; some experts get nothing."""
    key = jax.random.PRNGKey(seed)
    scores = jax.random.uniform(key, (s, e))
    scores = scores.at[:, 0].add(2.0).at[:, 1].add(1.0).at[:, 5].add(-5.0)
    gates, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), gates / gates.sum(-1, keepdims=True)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("held", [(0, 8), (4, 12), (5, 6)])
def test_dropless_layer_is_a_loop_over_experts_under_a_skewed_router(
        impl, held):
    s, d, f, e, k = 37, 32, 16, 16, 4
    lo, hi = held
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (s, d))
    wg = jax.random.normal(ks[1], (e, d, f)) * 0.2
    wu = jax.random.normal(ks[2], (e, d, f)) * 0.2
    wd = jax.random.normal(ks[3], (e, f, d)) * 0.2
    idx, gates = _skewed_routing(s, e, k, 9)
    valid = jnp.arange(s) != 3                 # one row taken out
    got, counts = moe_ops.dropless_moe(
        x, idx, gates, wg[lo:hi], wu[lo:hi], wd[lo:hi], lo,
        row_valid=valid, impl=impl)
    want = np.zeros((s, d), np.float32)
    n_local, hit = 0, set()
    for row in range(s):
        if row == 3:
            continue
        for j in range(k):
            ex = int(idx[row, j])
            if lo <= ex < hi:
                a = x[row] @ wg[ex]
                h = a * jax.nn.sigmoid(a) * (x[row] @ wu[ex])
                want[row] += float(gates[row, j]) * np.asarray(h @ wd[ex])
                n_local += 1
                hit.add(ex)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert (int(counts[0]), int(counts[1])) == (n_local, len(hit))


@pytest.mark.parametrize("sizes", [
    [0, 0, 0, 0], [40, 0, 0, 3], [1, 1, 1, 1], [15, 17, 0, 16], [0, 0, 64, 0],
], ids=["none", "skewed", "ones", "straddling", "one_full"])
def test_grouped_matmul_kernel_under_the_interpreter(sizes):
    """Visits of (expert, tile) pairs: empty experts, experts that share
    a tile, experts that span tiles, rows past the groups left alone."""
    m, d, f = 64, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    xs = jax.random.normal(ks[0], (m, d))
    wg = jax.random.normal(ks[1], (4, d, f)) * 0.2
    wu = jax.random.normal(ks[2], (4, d, f)) * 0.2
    wd = jax.random.normal(ks[3], (4, f, d)) * 0.2
    sizes = jnp.asarray(sizes, jnp.int32)
    got = moe_ops.grouped_swiglu(xs, sizes, wg, wu, wd, impl="pallas")
    want = moe_ops.grouped_swiglu(xs, sizes, wg, wu, wd, impl="xla")
    n = int(sizes.sum())
    assert float(jnp.abs(got[:n] - want[:n]).max()) < F32_TOL if n else True
    gid, tid, _, nact = moe_ops._gmm_visits(sizes, m, 16)
    visits = {(int(g), int(t)) for g, t in zip(gid[:int(nact[0])],
                                               tid[:int(nact[0])])}
    assert all(int(sizes[g]) > 0 for g, _ in visits)   # no empty expert read
    assert len(visits) == int(nact[0]) <= m // 16 + 3


def test_router_scores_in_float32_and_gates_normalised(tiny):
    cfg, _, params = tiny
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (10, cfg.d_model))
    idx, gates, z = moe_ops.sigmoid_topk_routing(
        x.astype(jnp.bfloat16), p["router"], p["router_bias"], cfg.top_k,
        cfg.routed_scale, return_scores=True)
    assert z.dtype == jnp.float32 and gates.dtype == jnp.float32
    assert np.allclose(np.asarray(gates.sum(-1)), cfg.routed_scale, atol=1e-5)
    # The bias selects and does not weigh.
    bias = jnp.zeros((cfg.n_experts,)).at[7].set(10.0)
    idx2, gates2 = moe_ops.sigmoid_topk_routing(
        x, p["router"], bias, cfg.top_k, 1.0)
    assert bool((idx2 == 7).any(-1).all())
    z2 = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.take_along_axis(z2, idx2, -1)
    assert np.allclose(np.asarray(gates2),
                       np.asarray(chosen / chosen.sum(-1, keepdims=True)),
                       atol=1e-6)


# -- grouped-query paged decode kernel --------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_paged_decode_kernel_under_the_interpreter(dtype):
    """16 query heads on 2 K/V heads of 128 against the gathered form."""
    from ray_lightning_tpu.ops.paged_attention import paged_decode_attention

    W, Hq, Hkv, Dh, Bs, M, N = 3, 16, 2, 128, 16, 4, 14
    cfg = ExaoneMoEConfig(n_layer=4, n_head=Hq, n_kv_head=Hkv, head_dim=Dh,
                          d_model=64, vocab_size=64)
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    pool_k = jax.random.normal(ks[0], (2, N, Bs, Hkv * Dh)).astype(dtype)
    pool_v = jax.random.normal(ks[1], (2, N, Bs, Hkv * Dh)).astype(dtype)
    q = jax.random.normal(ks[2], (W, Hq * Dh)).astype(dtype)
    k_cur = jax.random.normal(ks[3], (W, Hkv * Dh)).astype(dtype)
    v_cur = jax.random.normal(ks[4], (W, Hkv * Dh)).astype(dtype)
    tables = jnp.asarray([[3, 7, 1, 9], [2, 5, 0, 0], [0, 0, 0, 0]])
    lens = jnp.asarray([50, 17, 0])
    got = paged_decode_attention(
        q, k_cur, v_cur, pool_k, pool_v, jnp.int32(1), tables, lens,
        n_head=Hq, n_kv_head=Hkv, scale=Dh ** -0.5)

    def ctx(pool, row):
        got = pool[1][tables].reshape(W, M * Bs, -1)
        return jnp.concatenate([got, row[:, None]], 1).reshape(
            W, M * Bs + 1, Hkv, Dh)

    vis = jnp.concatenate([jnp.arange(M * Bs)[None] < lens[:, None],
                           jnp.ones((W, 1), bool)], 1)
    want = em.attend_one(cfg, q.reshape(W, Hq, Dh), ctx(pool_k, k_cur),
                         ctx(pool_v, v_cur), vis)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2   # bf16: 8 bits of the inputs
    assert float(jnp.abs(got - want).max()) < tol


def test_gqa_kernel_refuses_what_it_cannot_tile():
    from ray_lightning_tpu.ops.paged_attention import paged_decode_attention

    pool = jnp.zeros((1, 4, 16, 2 * 64), jnp.float32)
    with pytest.raises(ValueError, match="rlt_paged_decode does not tile"):
        paged_decode_attention(
            jnp.zeros((1, 8 * 64)), jnp.zeros((1, 128)), jnp.zeros((1, 128)),
            pool, pool, jnp.int32(0), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), n_head=8, n_kv_head=2, scale=1.0)


# -- the allocator's accounting --------------------------------------------

def _scheduler(num_slots=2, num_blocks=13, ring=3):
    from ray_lightning_tpu.serve.kv_cache import BlockAllocator
    from ray_lightning_tpu.serve.scheduler import Scheduler

    return Scheduler(num_slots, BlockAllocator(num_blocks), 4, 8, [4, 8, 16],
                     window_allocator=BlockAllocator(num_slots * ring + 1),
                     window_blocks=ring)


def _request(n, rid):
    from ray_lightning_tpu.serve.scheduler import Request

    return Request(rid=rid, prompt=list(range(1, n + 1)), max_new_tokens=4)


def test_admission_claims_window_and_full_blocks_and_release_frees_both():
    sch = _scheduler()
    sch.submit(_request(6, "a"))
    (slot, req, bucket), = sch.poll()[0]
    assert bucket == 8
    # ceil(bucket / Bs) full blocks and the ring: a slot costs both.
    assert len(sch._blocks[slot]) == 2 and len(sch._window[slot]) == 3
    assert sch.allocator.live_blocks == 2
    assert sch.window_allocator.live_blocks == 3
    assert list(sch.window_tables[slot]) == sch._window[slot]
    assert 0 not in sch._window[slot]              # never the trash block
    sch.finish(slot)
    assert sch.allocator.live_blocks == 0
    assert sch.window_allocator.live_blocks == 0
    assert list(sch.window_tables[slot]) == [0, 0, 0]


def test_a_dry_window_pool_holds_admission_and_leaks_nothing():
    from ray_lightning_tpu.serve.kv_cache import BlockAllocator

    sch = _scheduler()
    sch.window_allocator = BlockAllocator(3 + 1)   # one ring only
    sch.submit(_request(6, "a"))
    sch.submit(_request(6, "b"))
    admitted, _ = sch.poll()
    assert [r.rid for _, r, _ in admitted] == ["a"]
    assert sch.queue_depth == 1
    assert sch.allocator.live_blocks == 2          # b's full blocks went back
    sch.finish(admitted[0][0])
    admitted, _ = sch.poll()
    assert [r.rid for _, r, _ in admitted] == ["b"]


def test_one_kind_scheduler_is_as_it_was():
    from ray_lightning_tpu.serve.kv_cache import BlockAllocator
    from ray_lightning_tpu.serve.scheduler import Scheduler

    sch = Scheduler(2, BlockAllocator(9), 4, 8, [4, 8])
    assert sch.window_tables is None and sch.window_blocks == 0
    sch.submit(_request(3, "a"))
    (slot, _, _), = sch.poll()[0]
    assert sch._window[slot] == []
    assert "window_blocks_free" not in sch.snapshot()


def test_two_kind_cache_sizes_its_pools():
    cfg = exaone_moe_tiny()
    cache = em.TwoKindKVCache(cfg, 33, 4, 5, jnp.float32)
    pool = cache.init_pool()
    assert pool["k"].shape == (2, 33, 4, cfg.kv_width)
    assert pool["wk"].shape == (6, 5 * 3 + 1, 4, cfg.kv_width)
    served = ExaoneMoEConfig(n_layer=8)
    assert em.TwoKindKVCache(served, 9, 32, 32).window_blocks == 5


# -- what the family refuses ------------------------------------------------

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
    with pytest.raises(ValueError, match=f"exaone_moe family.*{names}"):
        ServeEngine(module, params, ServeConfig(
            num_slots=2, block_size=4, max_model_len=32, **config), **extra)


def test_block_transfer_is_refused_by_the_familys_name(tiny):
    cfg, module, params = tiny
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=2, block_size=4, max_model_len=32))
    with pytest.raises(ValueError, match="export_blocks.*exaone_moe"):
        engine.export_resident()
    with pytest.raises(ValueError, match="import_blocks.*exaone_moe"):
        engine.submit([1, 2, 3], 2, _handoff={"kv": {}, "logits": None})
    with pytest.raises(ValueError, match="export_blocks.*exaone_moe"):
        engine.cache.export_blocks(engine._pool, [1])
    with pytest.raises(ValueError):                 # ids past the held slice
        engine.submit([1, cfg.n_vocab_held], 2)


# -- the cell's rehearsal ------------------------------------------------------

def test_cell_rehearsal_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
         "--workload", "k-exaone-236b-a23b-ep8.serve-mixed", "--seed",
         "3000000019", "--seconds", "2", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    share = line["metrics"]["moe_local_share_pct.serve"]["value"]
    assert 20 < share < 30                          # 4 of 16 held
    check = [json.loads(row) for row in out.stdout.splitlines()
             if row.startswith('{"phase": "reference_check"')][0]
    assert check["ok"] and check["worst_logit_gap"] < F32_TOL
    assert check["program_forward"]["score_err"] < F32_TOL
    assert check["program_forward"]["logit_rms"] < F32_TOL
    assert check["program_forward"]["expert_choice_flips"] == 0
    # The float8 reference is far outside what float32 agreement allows.
    assert check["lowprec_reference"]["logit_rms"] > 1e3 * F32_TOL

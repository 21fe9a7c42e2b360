"""What only the ``exaone_moe`` family has, against its plain reference
(``benchmarks/reference/exaone_moe_ref.py``) at the tiny preset (8 layers
``LLLG LLLG``, layer 0 dense, 16 experts top-4, 4 held, window 8, 4 query
/ 2 K/V heads, float32 weights): the dropless experts and their kernels,
the shares of a layer, grouped queries in ``rlt_paged_decode``, the
two-kind scheduler.  What every served family is held to is in
``test_serve_families.py``, with the tolerance's account.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import exaone_moe as em
from ray_lightning_tpu.models.exaone_moe import (
    ExaoneMoE, ExaoneMoEConfig, exaone_moe_tiny,
)
from ray_lightning_tpu.ops import moe as moe_ops
from ray_lightning_tpu.ops.paged_attention import paged_decode_attention
from ray_lightning_tpu.serve.kv_cache import BlockAllocator
from ray_lightning_tpu.serve.scheduler import Request, Scheduler
from test_serve_families import F32_TOL, FAMILIES, Exaone
from utils import draw_tokens as _tokens, tiny_family


@pytest.fixture(scope="module")
def tiny():
    return tiny_family(Exaone.preset, Exaone.Module, Exaone.gains)


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


# -- forward against the reference ------------------------------------------

def test_forward_batches_rows_independently(tiny):
    cfg, module, params = tiny
    toks = jnp.stack([_tokens(16, 3), _tokens(16, 4)])
    forward = jax.jit(module.forward)
    both = forward(params, toks)
    for i in range(2):
        alone = forward(params, toks[i:i + 1])[0]
        assert float(jnp.abs(both[i] - alone).max()) < F32_TOL


def test_loss_has_gradients_on_the_xla_path(tiny):
    cfg, _, params = tiny
    module = ExaoneMoE(cfg, attn_impl="xla", moe_impl="xla")
    batch = jnp.stack([_tokens(17, 5), _tokens(17, 6)])
    loss, grads = jax.jit(jax.value_and_grad(module._loss))(params, batch)
    assert np.isfinite(float(loss))
    assert float(jnp.abs(grads["layers"][1]["e_gate"]).max()) > 0
    assert float(jnp.abs(grads["layers"][3]["wk"]).max()) > 0


# -- the share ------------------------------------------------------------------

@pytest.mark.parametrize("fam", FAMILIES, ids=[f.name for f in FAMILIES])
def test_all_shares_add_up_to_the_uncut_layer(fam):
    """The parts all the shares give (four of 4 experts for
    ``exaone_moe``; eight of 2, under a drawn selection bias, for
    ``sarvam_mla``), the shared expert counted once, are the uncut layer
    (16 of 16 experts)."""
    cfg, Module, ref_mod = fam.preset(), fam.Module, fam.ref
    shares = 4 if fam is Exaone else 8
    whole_cfg = dataclasses.replace(cfg, experts_held=(0, 16))
    module = Module(whole_cfg)
    p = module.init_params(jax.random.PRNGKey(3))["layers"][2]
    assert (float(jnp.abs(p["router_bias"]).max()) > 0) == (
        fam is not Exaone)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model))
    feed_forward = jax.jit(em.feed_forward, static_argnums=(0, 1, 4, 5))
    whole, counts = feed_forward(whole_cfg, "sparse", p, x, None, "xla")
    assert int(counts[0]) == 24 * cfg.top_k
    shared = em.swiglu(x, p, "s_")
    routed = jnp.zeros_like(x)
    held = 16 // shares
    for lo in range(0, 16, held):
        part_cfg = dataclasses.replace(cfg, experts_held=(lo, lo + held))
        part = dict(p, **{k: p[k][lo:lo + held]
                          for k in ("e_gate", "e_up", "e_down")})
        f, _ = feed_forward(part_cfg, "sparse", part, x, None, "xla")
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
    pool = jnp.zeros((1, 4, 16, 2 * 64), jnp.float32)
    with pytest.raises(ValueError, match="rlt_paged_decode does not tile"):
        paged_decode_attention(
            jnp.zeros((1, 8 * 64)), jnp.zeros((1, 128)), jnp.zeros((1, 128)),
            pool, pool, jnp.int32(0), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), n_head=8, n_kv_head=2, scale=1.0)


# -- the allocator's accounting --------------------------------------------

def _scheduler(num_slots=2, num_blocks=13, ring=3):
    return Scheduler(num_slots, BlockAllocator(num_blocks), 4, 8, [4, 8, 16],
                     window_allocator=BlockAllocator(num_slots * ring + 1),
                     window_blocks=ring)


def _request(n, rid):
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

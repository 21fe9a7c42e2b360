"""What only the ``sarvam_mla`` family has, against its plain reference
(``benchmarks/reference/sarvam_mla_ref.py``) at the tiny preset (layer 0
dense, then sparse layers of 16 experts top-4 with a shared one and a
drawn selection bias, a latent of 16 beside a rotary key of 8, 4 heads,
an original context of 16 positions stretched 8 times by YaRN, float32
weights): YaRN by hand, the absorbed attention, the decode kernel under
the interpreter, the bias-selected router.  What every served family is
held to is in ``test_serve_families.py``, with the tolerance's account.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sarvam_mla_ref as ref
from ray_lightning_tpu.models import sarvam_mla as sm
from ray_lightning_tpu.models.exaone_moe import rope
from ray_lightning_tpu.models.sarvam_mla import SarvamMLA, SarvamMLAConfig
from ray_lightning_tpu.ops import paged_attention as pa
from test_serve_families import F32_TOL, Sarvam
from utils import draw_tokens as _tokens, tiny_family


@pytest.fixture(scope="module")
def tiny():
    return tiny_family(Sarvam.preset, Sarvam.Module, Sarvam.gains)


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


# -- forward against the reference ------------------------------------------

def test_prepared_tree_gives_the_same_forward(tiny):
    """``W_uk`` / ``W_uv`` rearranged once are the ``wkvb`` they came
    from: the served tree has no ``wkvb`` and the same logits."""
    cfg, module, params = tiny
    served = module.serve_family().prepare_params(params, jnp.float32)
    assert all("wkvb" not in p and p["w_uk"].shape == (4, 8, 16)
               and p["w_uv"].shape == (4, 16, 8) for p in served["layers"])
    assert served["layers"][0]["wq"] is params["layers"][0]["wq"]
    toks = _tokens(12)[None]
    forward = jax.jit(module.forward)
    np.testing.assert_array_equal(forward(served, toks),
                                  forward(params, toks))


def test_flash_forward_takes_a_value_width_of_its_own(tiny):
    """The prefill's kernel path (head width 16, values of 8) under the
    interpreter against the masked form, and no backward there."""
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    cfg, _, params = tiny
    toks = _tokens(128)[None]
    flash = jax.jit(SarvamMLA(cfg, attn_impl="flash").forward)(params, toks)
    plain = jax.jit(SarvamMLA(cfg, attn_impl="xla").forward)(params, toks)
    assert float(jnp.abs(flash - plain).max()) < F32_TOL
    q = jnp.ones((1, 128, 2, 16))
    with pytest.raises(NotImplementedError, match="one head width"):
        jax.grad(lambda v: flash_attention(q, q, v).sum())(
            jnp.ones((1, 128, 2, 8)))


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


# -- the decode kernel ---------------------------------------------------------

def _mla_inputs(lens, layers, dtype, seed):
    """``(q, cur, pool, tables, lens)`` for slots of ``lens`` cached
    positions: 16 heads, a latent of 128 and a rotary key of 64 in rows
    of 256 (the padding lanes zero), blocks of 16, 40 a slot scattered
    over a pool of ``layers``."""
    H, data, Bs, M, row, W = 16, 128 + 64, 16, 40, 256, len(lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)

    def draw(key, *shape):
        return jax.random.normal(key, shape + (row,)).astype(
            dtype).at[..., data:].set(0)

    tables = 1 + jax.random.permutation(ks[3], W * M).reshape(W, M)
    return (draw(ks[1], W, H), draw(ks[2], W),
            draw(ks[0], layers, W * M + 1, Bs), tables.astype(jnp.int32),
            jnp.asarray(lens, jnp.int32))


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
    q, cur, pool, tables, lens = _mla_inputs(
        [0, 5, 512, 530, 0, 639], 2, dtype, seed=0)
    (W, H, _), r = q.shape, 128
    args = (q, cur, pool, jnp.int32(1), tables, lens)
    kw = dict(rank=r, scale=0.11)
    want = pa.mla_decode_attention(*args, impl="xla", **kw)
    got = pa.mla_decode_attention(*args, impl="pallas", **kw)
    assert got.shape == (W, H, r) and got.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert float(jnp.abs(got - want).max()) < tol, float(
        jnp.abs(got - want).max())
    # A slot with nothing cached attends its own row alone.
    np.testing.assert_allclose(
        got[0], jnp.broadcast_to(cur[0, :r], (H, r)), rtol=1e-6)


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
    q, cur, pool, tables, lens = _mla_inputs(
        [5, 512, 639], 1, jnp.bfloat16, seed=1)
    W, r, row, rows_a_slot = len(lens), 128, 256, tables.shape[1] * 16
    scale = 0.11
    got = np.asarray(pa.mla_decode_attention(
        q, cur, pool, jnp.int32(0), tables, lens, rank=r, scale=scale,
        impl="pallas"), np.float64)
    rows = np.asarray(pool[0].astype(jnp.float32), np.float64)[
        np.asarray(tables)].reshape(W, rows_a_slot, row)
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


# -- what the families refuse --------------------------------------------------

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

"""What only the ``gigachat35`` family has, against its plain reference
(``benchmarks/reference/gigachat35_ref.py``) at the tiny preset (5
layers ``L F L L L``, layer 0 dense, 16 experts top-4, 4 held, 2 key and
4 value heads of 8 in the linear layers, a latent of 16 and a query
latent of 24, float32 weights): the gated delta rule's chunked prefill
against the reference's token-by-token recurrence, its state a row a
slot (written whole by the prefill, rewritten by the decode, rebuilt by
recompute preemption, unseen by the next request of the slot), the
decode kernel ``rlt_gdn_decode`` under the interpreter, the MLPs' clamp,
and each departure from the published layer failing the parity
tolerance.  What every served family is held to (forward, prefill then
decode through the pool, the engine, the refusals, the shares, the
cell's rehearsal) is in ``test_serve_families.py``, row ``GigaChat``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import gigachat35_ref as ref
from ray_lightning_tpu.models import exaone_moe as em
from ray_lightning_tpu.models import gigachat35 as gc
from ray_lightning_tpu.ops import gated_delta as gd
from ray_lightning_tpu.serve import ServeConfig, ServeEngine
from test_serve_families import F32_TOL, GigaChat
from utils import draw_tokens, reference_logits, tiny_family


@pytest.fixture(scope="module")
def tiny():
    """The preset with decays near 1 (``A_log`` small): the state keeps
    what it was written, so a state lost or stale shows in the logits."""
    cfg, module, params = tiny_family(GigaChat.preset, GigaChat.Module,
                                      GigaChat.gains)
    for p in params["layers"]:
        if "A_log" in p:
            p["A_log"] = jnp.log(jnp.linspace(0.005, 0.5, p["A_log"].size))
    return cfg, module, params


def _recurrence_operands(seed, T, H=4, K=8, V=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gd.l2norm(jax.random.normal(ks[0], (T, H, K))) * K ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (T, H, K)))
    v = jax.random.normal(ks[2], (T, H, V))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (T, H))) * 0.2
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta


def test_published_pattern_is_the_default():
    cfg = gc.GigaChat35Config()
    assert cfg.full_layers == tuple(range(3, 40, 4))
    assert cfg.layer_types.count("linear") == 30
    assert cfg.mlp_types == ("dense",) * 3 + ("sparse",) * 37
    assert cfg.conv_channels == 16384 and cfg.lin_value_width == 8192
    assert (cfg.cache_row, cfg.pool_row) == (576, 640)
    # use_mla_scaling_factor: 192^-0.5 (0.1 ln 8 + 1)^2.
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    family = gc.GigaChat35(gc.gigachat35_tiny()).serve_family()
    assert family.slot_state == 4 and family.n_latent == 1
    assert gc.ServeFamily(gc.GigaChat35(cfg)).state_row_bytes(
        jnp.bfloat16) == 64 * 128 * 128 * 4 + 3 * 16384 * 2


@pytest.mark.parametrize("T,valid", [(150, 150), (150, 100)],
                         ids=["three_chunks", "padded_past_a_boundary"])
def test_chunked_scan_is_the_token_by_token_recurrence(T, valid):
    """The prefill's chunks of 64 against the reference's recurrence, from
    a state that is not zero; positions past ``valid`` (a bucket's
    padding, beta = g = 0) leave the state where the prompt left it."""
    q, k, v, g, beta = _recurrence_operands(T, T)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (4, 8, 8)) * 0.3
    live = (jnp.arange(T) < valid)[:, None]
    o, s = jax.jit(gd.chunked)(
        q[None], k[None], v[None], jnp.where(live, g, 0.0)[None],
        jnp.where(live, beta, 0.0)[None], s0[None])
    want_o, want_s = ref.recurrence(q[:valid], k[:valid], v[:valid],
                                    g[:valid], beta[:valid], s0)
    assert float(jnp.abs(o[0, :valid] - want_o).max()) < 1e-5
    assert float(jnp.abs(s[0] - want_s).max()) < 1e-5


def test_prefill_over_a_chunk_boundary_then_decode(tiny):
    """A 70-token prompt in a bucket of 80 (two chunks, ten padding
    positions), then twelve decode ticks: logits against the reference's
    full forward."""
    cfg, module, params = tiny
    family = module.serve_family()
    n, ticks, Bs = 70, 12, 4
    toks = draw_tokens(n + ticks, 5)
    want, _ = reference_logits(ref, cfg, params, toks, 96)
    cache = family.make_cache(32, Bs, 2, jnp.float32)
    pool = cache.init_pool()
    ids = cache.allocator.alloc(24)
    padded = jnp.zeros((80,), jnp.int32).at[:n].set(toks[:n])
    logits, pool, _ = jax.jit(functools.partial(gc.paged_prefill, cfg))(
        params, pool, padded, jnp.int32(n),
        (jnp.asarray(ids[:20]), jnp.int32(1)))
    assert float(jnp.abs(logits - want[n - 1]).max()) < F32_TOL
    tables = jnp.asarray([[0] * 24, ids])
    step = jax.jit(lambda pool, lens, t: gc.paged_decode_step(
        cfg, params, pool, tables, lens, t, attn_impl="xla"))
    for t in range(ticks):
        logits, pool, _ = step(pool, jnp.asarray([0, n + t]),
                               jnp.asarray([0, toks[n + t]]))
        assert float(jnp.abs(logits[1] - want[n + t]).max()) < F32_TOL


def test_a_reused_slot_gives_a_fresh_engines_logits(tiny):
    """Slot 0 serves one request (prefill and eight ticks), then another:
    the second's logits are bit for bit those of the same request on a
    fresh pool.  The prefill writes the slot's state rows whole."""
    cfg, module, params = tiny
    family = module.serve_family()
    Bs = 4
    prefill = jax.jit(functools.partial(gc.paged_prefill, cfg))
    step = jax.jit(lambda pool, tables, lens, toks: gc.paged_decode_step(
        cfg, params, pool, tables, lens, toks, attn_impl="xla"))

    def serve(pool, cache, prompt, ticks):
        ids = cache.allocator.alloc(8)
        padded = jnp.zeros((16,), jnp.int32).at[:len(prompt)].set(prompt)
        first, pool, _ = prefill(params, pool, padded, jnp.int32(len(prompt)),
                                 (jnp.asarray(ids[:4]), jnp.int32(0)))
        out, tok = [first], int(first.argmax())
        tables = jnp.asarray([ids])
        for t in range(ticks):
            logits, pool, _ = step(pool, tables,
                                   jnp.asarray([len(prompt) + t]),
                                   jnp.asarray([tok]))
            out.append(logits[0])
            tok = int(logits[0].argmax())
        cache.allocator.free(ids)
        return pool, out

    a, b = draw_tokens(13, 40), draw_tokens(11, 41)
    cache = family.make_cache(17, Bs, 1, jnp.float32)
    pool, _ = serve(cache.init_pool(), cache, a, 8)
    _, reused = serve(pool, cache, b, 8)
    fresh_cache = family.make_cache(17, Bs, 1, jnp.float32)
    _, fresh = serve(fresh_cache.init_pool(), fresh_cache, b, 8)
    for got, want in zip(reused, fresh):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _greedy_gap(cfg, params, prompt, served):
    logits, _ = reference_logits(ref, cfg, params,
                                 jnp.asarray(prompt + served), 64)
    rows = np.asarray(logits[len(prompt) - 1:len(prompt) - 1 + len(served)])
    return float((rows.max(-1) - rows[np.arange(len(served)), served]).max())


def test_recompute_preemption_rebuilds_the_state(tiny):
    """A pool too small for three slots: requests are preempted and
    re-admitted with what they had generated, and every served token is
    still the reference's greedy choice along the served sequence."""
    cfg, module, params = tiny
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=3, block_size=4, max_model_len=48, num_blocks=17))
    try:
        prompts = [np.asarray(draw_tokens(n, 50 + n)).tolist()
                   for n in (9, 12, 14)]
        handles = [engine.submit(p, 30) for p in prompts]
        engine.run_until_idle()
        served = [h.result(timeout=60) for h in handles]
    finally:
        engine.stop()
    c = engine.stats.counters
    assert c["preempted"] >= 1
    assert c["state_rows_written"] == c["prefills"] > 3
    for p, s in zip(prompts, served):
        assert len(s) == 30
        assert _greedy_gap(cfg, params, p, s) < F32_TOL


def test_an_admission_riding_the_pipeline_serves_its_tokens(tiny):
    """An admission dispatched while a decode runs ahead (its first token
    fed to the next decode on the device) gives the tokens the serial
    loop gives the same request alone."""
    cfg, module, params = tiny
    long_p = np.asarray(draw_tokens(10, 60)).tolist()
    late_p = np.asarray(draw_tokens(13, 61)).tolist()
    engine = ServeEngine(module, params, ServeConfig(
        num_slots=2, block_size=4, max_model_len=64))
    try:
        first = engine.submit(long_p, 24)
        for _ in range(50):
            engine.step()
            if engine._ahead is not None:
                break
        assert engine._ahead is not None
        late = engine.submit(late_p, 16)
        engine.run_until_idle()
        got = late.result(timeout=60)
        first.result(timeout=60)
    finally:
        engine.stop()
    assert engine.stats.counters["admit_fed_on_device"] >= 1
    serial = ServeEngine(module, params, ServeConfig(
        num_slots=2, block_size=4, max_model_len=64,
        coalesce_replies=False, decode_lookahead=False))
    try:
        alone = serial.generate(late_p, 16, timeout=60)
    finally:
        serial.stop()
    assert got == alone


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_clamp_engages_past_the_limit(tiny, impl):
    """Rows scaled a thousandfold put the MLPs' pre-activations far past
    10: the dense MLP, the shared expert and the experts (the grouped
    kernels' epilogue under ``pallas``) give the reference's clamped
    sums, and not the unclamped ones."""
    cfg, _, params = tiny
    rcfg = ref.config_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.d_model)) * 1e3
    dense, sparse = params["layers"][0], params["layers"][2]
    ff = jax.jit(em.feed_forward, static_argnums=(0, 1, 4, 5))
    got, _ = ff(cfg, "dense", dense, x, None, impl)
    want = ref.dense_ffn(rcfg, dense, x, "float32")
    loose = ref.dense_ffn(dict(rcfg, swiglu_limit=None), dense, x, "float32")
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    assert float(jnp.abs(loose - want).max()) > 0.1 * scale
    got, _ = ff(cfg, "sparse", sparse, x, None, impl)
    want, _ = ref.sparse_ffn(rcfg, sparse, x, "float32")
    loose, _ = ref.sparse_ffn(dict(rcfg, swiglu_limit=None), sparse, x,
                              "float32")
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    assert float(jnp.abs(loose - want).max()) > 0.1 * scale


@pytest.mark.parametrize("lens", [[0, 3, 0], [0, 0, 0]],
                         ids=["idle_round_a_live_slot", "all_idle"])
def test_decode_kernel_under_the_interpreter(lens):
    """``rlt_gdn_decode`` at its tile (states of 128 x 128, 64 value
    heads over 32 key heads) against the XLA step: outputs and the whole
    pool, another layer's and every idle slot's state left as they
    were."""
    L, W, H, Hk = 2, 3, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    pool = jax.random.normal(ks[0], (L, W, H, 128, 128)) * 0.1
    q = gd.l2norm(jax.random.normal(ks[1], (W, Hk, 128))) * 128 ** -0.5
    k = gd.l2norm(jax.random.normal(ks[2], (W, Hk, 128)))
    v = jax.random.normal(ks[3], (W, H, 128))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (W, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (W, H)))
    lens = jnp.asarray(lens)
    step = jax.jit(gd.decode_step, static_argnames="impl")
    want_o, want_s = step(pool, 1, q, k, v, g, beta, lens, impl="xla")
    got_o, got_s = step(pool, 1, q, k, v, g, beta, lens, impl="pallas")
    assert float(jnp.abs(got_o - want_o).max()) < 1e-5
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5
    idle = np.asarray(lens) == 0
    np.testing.assert_array_equal(np.asarray(got_s[1][idle]),
                                  np.asarray(pool[1][idle]))
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(pool[0]))
    assert not np.asarray(got_o[idle]).any()


def test_decode_kernel_is_taken_only_where_it_tiles():
    pool = jnp.zeros((1, 2, 64, 128, 128), jnp.float32)
    assert not gd.decode_supported(pool, 32)        # not a TPU here
    qk, v, gb = (jnp.zeros((2, 32, 128)), jnp.zeros((2, 64, 128)),
                 jnp.zeros((2, 64)))
    with pytest.raises(ValueError, match="impl"):
        gd.decode_step(pool, 0, qk, qk, v, gb, gb,
                       jnp.zeros((2,), jnp.int32), impl="mosaic")


@pytest.mark.parametrize("state_dtype,fails", [(jnp.float32, False),
                                               (jnp.bfloat16, True)])
def test_a_bf16_state_fails_the_decode_tolerance(state_dtype, fails):
    """Three hundred decode steps of slowly decaying heads against the
    reference's recurrence: a state held in float32 between steps stays
    within the decode tolerance; one rounded to bfloat16 between steps
    (rounded here, in the test: the program has no such switch) leaves
    it within a few hundred steps."""
    T = 300
    q, k, v, _, beta = _recurrence_operands(3, T)
    g = jnp.full((T, 4), -0.002)
    q4, k4 = q.reshape(T, 1, 4, 8), k.reshape(T, 1, 4, 8)
    want, _ = ref.recurrence(q, k, v, g, beta, jnp.zeros((4, 8, 8)))

    @jax.jit
    def run(pool):
        def one(pool, xs):
            qt, kt, vt, gt, bt = xs
            o, pool = gd.decode_step(pool, 0, qt, kt, vt[None], gt[None],
                                     bt[None], jnp.ones((1,), jnp.int32),
                                     impl="xla")
            return pool.astype(state_dtype).astype(jnp.float32), o[0]

        return jax.lax.scan(one, pool, (q4, k4, v, g, beta))[1]

    got = run(jnp.zeros((1, 1, 4, 8, 8), jnp.float32))
    err = float(jnp.abs(got - want).max())
    assert (err > F32_TOL) == fails, err


def _departed(cfg, name):
    """A config class that leaves out one piece of the published layer."""
    over = {"half_split_rope": {"rope_interleave": False},
            "plain_gains": {"zero_centred": False}}[name]
    cls = type("Departed", (gc.GigaChat35Config,), over)
    return cls(**{f.name: getattr(cfg, f.name)
                  for f in dataclasses.fields(cfg)})


@pytest.mark.parametrize("name", ["half_split_rope", "plain_gains"])
def test_each_departure_fails_the_parity_tolerance(tiny, name):
    """The tolerance every family is held to is tight enough to see the
    rotation's pairing and the zero-centred gains: a program without
    either is over fifty times the tolerance from the reference."""
    cfg, module, params = tiny
    toks = draw_tokens(40)
    want, _ = reference_logits(ref, cfg, params, toks, 48)
    got = jax.jit(module.forward)(params, toks[None])[0]
    assert float(jnp.abs(got - want).max()) < F32_TOL
    other = gc.GigaChat35(_departed(cfg, name))
    got = jax.jit(other.forward)(params, toks[None])[0]
    assert float(jnp.abs(got - want).max()) > 50 * F32_TOL

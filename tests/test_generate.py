"""KV-cache decode: teacher-forcing parity with the full forward.

Strategy ≙ the repo's grad-parity discipline applied to inference: the
training-path full forward (``GPT.forward``) is the reference; greedy
decoding through the cache must pick exactly the tokens the full forward
would, step by step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.generate import (
    _sample, decode_step, generate, init_kv_cache, prefill,
)
from ray_lightning_tpu.models.gpt import GPT, GPTConfig
from utils import tiny_gpt


@pytest.fixture(scope="module")
def model():
    return tiny_gpt(seq_len=32)


def test_decode_logits_match_full_forward(model):
    """Feeding tokens one-by-one through the cache reproduces the full
    forward's next-token logits at every position."""
    m, params = model
    cfg = m.config
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    full = m.forward(params, tokens)  # (B, 8, V)

    cache = init_kv_cache(cfg, 2, 8)
    for t in range(8):
        step_logits, cache = decode_step(
            cfg, params, cache, tokens[:, t], jnp.int32(t)
        )
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(full[:, t]),
            rtol=1e-4, atol=1e-4,
        )


def test_prefill_matches_sequential_decode(model):
    """One fused prefill pass == feeding the prompt token-by-token:
    identical last-position logits AND identical cache contents."""
    m, params = model
    cfg = m.config
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0,
                                cfg.vocab_size)
    fused_logits, fused_cache = prefill(
        cfg, params, init_kv_cache(cfg, 2, 10), tokens
    )
    seq_cache = init_kv_cache(cfg, 2, 10)
    for t in range(6):
        seq_logits, seq_cache = decode_step(
            cfg, params, seq_cache, tokens[:, t], jnp.int32(t)
        )
    np.testing.assert_allclose(np.asarray(fused_logits),
                               np.asarray(seq_logits), rtol=1e-4, atol=1e-4)
    for k in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(fused_cache[k]), np.asarray(seq_cache[k]),
            rtol=1e-5, atol=1e-5,
        )


def test_topk_one_equals_greedy(model):
    """top_k=1 sampling at any temperature is exactly greedy decoding."""
    m, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 3), 0,
                                m.config.vocab_size)
    greedy = generate(m, params, prompt, 5)
    topk1 = generate(m, params, prompt, 5, temperature=1.3, top_k=1,
                     rng=jax.random.PRNGKey(11))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(topk1))


def test_top_p_nucleus_masks_tail():
    """top-p keeps the smallest prefix of sorted probs reaching the mass
    and never samples outside it; always keeps the argmax token."""
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    # nucleus at 0.6: exclusive-cumsum {0, .5, .8, .95} < 0.6 keeps the
    # top two tokens.
    draws = [
        int(_sample(logits, jax.random.PRNGKey(i), 1.0, None, 0.6)[0])
        for i in range(50)
    ]
    assert set(draws) <= {0, 1} and 0 in draws
    # tiny top_p still keeps exactly the argmax
    draws = [
        int(_sample(logits, jax.random.PRNGKey(i), 1.0, None, 1e-6)[0])
        for i in range(10)
    ]
    assert set(draws) == {0}


def test_greedy_generation_matches_argmax_rollout(model):
    """jit-compiled greedy generate == python loop of full forwards."""
    m, params = model
    cfg = m.config
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0,
                                cfg.vocab_size)
    out = jax.jit(
        lambda p, pr: generate(m, p, pr, max_new_tokens=6)
    )(params, prompt)
    assert out.shape == (2, 10)
    np.testing.assert_array_equal(np.asarray(out[:, :4]),
                                  np.asarray(prompt))

    # Reference rollout: repeatedly run the FULL forward and take argmax.
    cur = np.asarray(prompt)
    for _ in range(6):
        logits = m.forward(params, jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), cur)


def test_eos_freezes_finished_sequences(model):
    """Once a row samples eos, every later position repeats eos; rows
    that never sample it are unaffected (match the no-eos output)."""
    m, params = model
    cfg = m.config
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 3), 0,
                                cfg.vocab_size)
    base = generate(m, params, prompt, 8)
    # Pick the token row 0 greedily emits first as the "eos" id: row 0
    # must freeze right there; use an id row 1 never emits to leave it
    # untouched.
    eos = int(base[0, 3])
    out = generate(m, params, prompt, 8, eos_token_id=eos)
    got = np.asarray(out)
    ref = np.asarray(base)
    assert (got[0, 3:] == eos).all(), "finished row did not freeze"
    # Unconditional per-row property: identical to the no-eos rollout up
    # to and including each row's first eos, frozen at eos after it.
    for r in range(got.shape[0]):
        hits = np.where(ref[r, 3:] == eos)[0]
        cut = 3 + (hits[0] + 1 if hits.size else ref.shape[1])
        np.testing.assert_array_equal(got[r, :cut], ref[r, :cut])
        assert (got[r, cut:] == eos).all()
    # jit parity (the scan carry gained a done mask).
    jout = jax.jit(
        lambda p, pr: generate(m, p, pr, 8, eos_token_id=eos)
    )(params, prompt)
    np.testing.assert_array_equal(np.asarray(jout), got)


def test_sampled_generation_reproducible(model):
    m, params = model
    prompt = jnp.zeros((1, 2), jnp.int32)
    a = generate(m, params, prompt, 5, temperature=0.8,
                 rng=jax.random.PRNGKey(7))
    b = generate(m, params, prompt, 5, temperature=0.8,
                 rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (1, 7)


def test_generate_accepts_host_param_pytree(model):
    """``trainer.params`` is a numpy pytree — generate() must accept it
    (numpy leaves cannot be gather-indexed by traced tokens)."""
    m, params = model
    host_params = jax.tree.map(np.asarray, params)
    prompt = np.zeros((1, 2), np.int32)
    out = generate(m, host_params, prompt, 3)
    ref = generate(m, params, jnp.asarray(prompt), 3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_under_tp_mesh(model):
    """The decode loop is GSPMD-cleanly shardable: jitted over a
    (data, tensor) mesh with the module's Megatron param specs and a
    batch-sharded prompt, generation runs and matches the unsharded
    tokens (serving story for TP-sharded models)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_lightning_tpu.parallel.sharding import (
        params_shardings_for_module,
    )

    m, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(5), (4, 5), 0,
                                m.config.vocab_size)
    ref = generate(m, params, prompt, 6)

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "tensor"))
    sharded_params = jax.device_put(
        params, params_shardings_for_module(m, params, mesh)
    )
    sharded_prompt = jax.device_put(
        prompt, NamedSharding(mesh, P("data", None))
    )
    with mesh:
        out = jax.jit(
            lambda p, pr: generate(m, p, pr, max_new_tokens=6)
        )(sharded_params, sharded_prompt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_validates_args(model):
    m, params = model
    prompt = jnp.zeros((1, 30), jnp.int32)
    with pytest.raises(ValueError, match="exceeds"):
        generate(m, params, prompt, 10)
    with pytest.raises(ValueError, match=">= 0"):
        generate(m, params, prompt, -1)
    small = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="top_k"):
        generate(m, params, small, 2, temperature=1.0, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        generate(m, params, small, 2, temperature=1.0, top_p=1.5)
    with pytest.raises(ValueError, match="temperature > 0"):
        generate(m, params, small, 2, top_k=5)
    with pytest.raises(ValueError, match="at least one token"):
        generate(m, params, jnp.zeros((1, 0), jnp.int32), 2)
    # Oversized top_k clamps to the vocab (HF behavior) instead of
    # erroring from inside lax.top_k.
    out = generate(m, params, small, 2, temperature=1.0,
                   top_k=m.config.vocab_size + 7)
    assert out.shape == (1, 4)


def test_moe_greedy_generation_matches_argmax_rollout():
    """MoE decode == python loop of full MoE forwards.

    capacity_factor = n_experts guarantees zero capacity drops, which
    makes per-step routing identical to whole-batch routing (the caveat
    documented on generate())."""
    from dataclasses import replace

    cfg = GPTConfig.tiny_moe()
    cfg = replace(cfg, moe_capacity_factor=float(cfg.n_experts))
    m = GPT(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 4), 0,
                                cfg.vocab_size)
    out = jax.jit(
        lambda p, pr: generate(m, p, pr, max_new_tokens=5)
    )(params, prompt)
    assert out.shape == (2, 9)

    cur = np.asarray(prompt)
    for _ in range(5):
        logits = m.forward(params, jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), cur)

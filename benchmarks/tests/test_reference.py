"""``reference/gpt2_ref.py`` against the program at a tiny size on the
CPU, both in float32: ``GPT.forward``, the training loss and its
gradient, and prefill-then-decode through ``PagedKVCache`` at logit
level.

Tolerance 2e-4 on logits of spread ~0.3 and 1e-4 relative on the loss:
both sides are float32 and differ only in the order of summation (the
program's chunked cross-entropy, its fused LayerNorm statistics, the
CPU's default matmul against ``highest``); a bf16 compute path misses
it by two orders (measured 1e-2 here), so a program that silently
dropped to bf16 under a float32 configuration would fail.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import gpt2_ref
from ray_lightning_tpu.models import GPT, GPTConfig

ATOL = 2e-4
CFG = GPTConfig(vocab_size=512, n_layer=3, n_head=4, d_model=64, seq_len=64)


@pytest.fixture(scope="module")
def setup():
    module = GPT(CFG, attn_impl="auto")
    params = module.init_params(jax.random.PRNGKey(3))
    # biases and gains away from their initial 0 / 1, so they are tested
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) if x.ndim <= 2
              and x.shape[-1] != CFG.vocab_size else x
              for x, k in zip(leaves, keys)]
    params = jax.tree.unflatten(tree, leaves)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, CFG.vocab_size, size=(2, CFG.seq_len + 1)), jnp.int32)
    return module, params, tokens


def test_forward_matches_and_scan_equals_loop(setup):
    module, params, tokens = setup
    ref = gpt2_ref.from_stacked(params, CFG.n_layer)
    want = gpt2_ref.forward(ref, tokens[:, :-1], CFG.n_head)
    loop = gpt2_ref.forward(ref, tokens[:, :-1], CFG.n_head, unroll=True)
    got = module.forward(params, tokens[:, :-1])
    assert float(jnp.abs(want - loop).max()) < 1e-5
    assert float(jnp.abs(got - want).max()) < ATOL


def test_training_loss_and_gradient_match(setup):
    module, params, tokens = setup
    rng = jax.random.PRNGKey(0)
    got, g_got = jax.value_and_grad(
        lambda p: module.training_step(p, {"tokens": tokens}, rng)[0])(params)
    want, g_want = jax.value_and_grad(
        lambda p: gpt2_ref.loss(gpt2_ref.from_stacked(p, CFG.n_layer),
                                tokens, CFG.n_head))(params)
    assert abs(float(got) - float(want)) < 1e-4 * float(want)
    for path in (("blocks", "qkv_w"), ("wte",), ("blocks", "ln2_b")):
        a, b = g_got, g_want
        for k in path:
            a, b = a[k], b[k]
        assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) < 1e-3, path


def test_bf16_compute_would_fail_the_tolerance(setup):
    module, params, tokens = setup
    want = gpt2_ref.forward(gpt2_ref.from_stacked(params, CFG.n_layer),
                            tokens[:, :-1], CFG.n_head)
    low = GPT(CFG, attn_impl="auto")
    low.precision = "bf16"
    assert float(jnp.abs(low.forward(params, tokens[:, :-1]) - want).max()) \
        > 10 * ATOL


def test_prefill_then_decode_through_the_paged_cache_matches(setup):
    from ray_lightning_tpu.serve.kv_cache import (
        PagedKVCache, paged_decode_step, paged_prefill,
    )

    _, params, tokens = setup
    bs, bucket, plen, steps = 8, 32, 21, 6
    seq = tokens[0, :plen + steps]
    want = gpt2_ref.forward(gpt2_ref.from_stacked(params, CFG.n_layer),
                            seq[None], CFG.n_head)[0]
    cache = PagedKVCache(CFG, num_blocks=12, block_size=bs)
    pool = cache.init_pool()
    table = np.zeros((1, CFG.seq_len // bs), np.int32)
    table[0, :bucket // bs] = cache.allocator.alloc(bucket // bs)
    padded = jnp.zeros((bucket,), jnp.int32).at[:plen].set(seq[:plen])
    logits, pool = paged_prefill(
        CFG, params, pool, padded, jnp.int32(plen),
        jnp.asarray(table[0, :bucket // bs]))
    assert float(jnp.abs(logits - want[plen - 1]).max()) < ATOL
    for j in range(steps):
        pos = plen + j
        logits, pool = paged_decode_step(
            CFG, params, pool, jnp.asarray(table),
            jnp.asarray([pos], jnp.int32), seq[pos][None])
        assert float(jnp.abs(logits[0] - want[pos]).max()) < ATOL, j

"""Numerical parity tests for the flash attention kernel.

Strategy ≙ SURVEY §6 "grad-parity verification" (hard-part #5): the XLA
einsum attention is the reference; the Pallas flash kernel (interpreter
on CPU) must match it forward and backward to float32 tolerance on a
fixed seed.  (The ring is held to the same reference in
``test_ops_ring.py``, the fused head, layer norm and the kernel switch
in ``test_ops_fused.py``.)
"""

import jax
import jax.numpy as jnp
import pytest

from ray_lightning_tpu.ops.attention import xla_causal_attention
from ray_lightning_tpu.ops.flash_attention import flash_attention
from utils import assert_grads_match

B, S, H, D = 2, 256, 4, 64


@pytest.fixture(scope="module")
def qkv():
    rng = jax.random.PRNGKey(0)
    return tuple(
        jax.random.normal(r, (B, S, H, D)) for r in jax.random.split(rng, 3)
    )


def test_flash_forward_matches_xla(qkv):
    q, k, v = qkv
    ref = xla_causal_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_flash_grad_matches_xla(qkv):
    assert_grads_match(
        lambda q, k, v: flash_attention(q, k, v, block_q=128, block_k=128),
        xla_causal_attention, qkv, 1e-4)


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
def test_flash_grad_uneven_blocks(qkv, block_q, block_k):
    """The dq/dkv kernels walk each other's axis in the *other* block
    size — both divisibility directions must stay correct."""
    assert_grads_match(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=block_q, block_k=block_k),
        xla_causal_attention, qkv, 1e-4)


def test_flash_grad_matches_xla_bf16(qkv):
    """bf16 inputs: f32 accumulators inside the kernels keep the error at
    bf16-rounding scale (the VERDICT-specified 1e-2 budget)."""
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)

    def loss_flash(q, k, v):
        return (flash_attention(
            q, k, v, block_q=128, block_k=128).astype(jnp.float32) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v).astype(jnp.float32) ** 2).sum()

    g1 = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        denom = max(float(jnp.abs(b.astype(jnp.float32)).max()), 1.0)
        rel = float(
            jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
        ) / denom
        assert rel < 1e-2


def _walk_inputs(s, heads, d, dv):
    rq, rk, rv = jax.random.split(jax.random.PRNGKey(s + d), 3)
    return (jax.random.normal(rq, (1, s, heads, d)),
            jax.random.normal(rk, (1, s, heads, d)),
            jax.random.normal(rv, (1, s, heads, dv)))


# (S, heads, q/k width, value width, block_q, block_k): every class of
# width and block count the benchmark's three cells run, and the edges of
# the diagonal square's sub-blocks.
_WALKS = {
    "64-1block": (512, 2, 64, 64, None, None),
    "64-2blocks-fit-cell": (1024, 2, 64, 64, None, None),
    "64-3blocks": (1536, 1, 64, 64, None, None),
    "128-exaone": (1024, 2, 128, 128, None, None),
    "192-128-sarvam": (1024, 2, 192, 128, None, None),
    "S768": (768, 2, 64, 64, None, None),
    "S1280": (1280, 1, 64, 64, None, None),
    "S3072-128": (3072, 2, 128, 128, None, None),
    "S6144-192-128": (6144, 1, 192, 128, None, None),
    "S640-block+128": (640, 2, 64, 64, None, None),
    "S384-block+128": (384, 4, 64, 64, 128, 128),
    "sub-edges-256": (768, 1, 64, 64, 256, 256),
    "sub-edges-q512-k128": (1024, 1, 64, 64, 512, 128),
    "sub-edges-q128-k512": (1024, 1, 64, 64, 128, 512),
    "eight-heads": (256, 8, 64, 64, None, None),
}


@pytest.mark.parametrize("case", sorted(_WALKS))
def test_flash_walk_forward(case):
    """The tile walk at each shape class: full key tiles below the query
    tile, then the diagonal square in key sub-blocks."""
    s, heads, d, dv, block_q, block_k = _WALKS[case]
    q, k, v = _walk_inputs(s, heads, d, dv)
    scale = 0.1 if d == 192 else None     # sarvam's: not a power of two
    ref = xla_causal_attention(q, k, v, scale)
    out = flash_attention(q, k, v, scale, block_q=block_q, block_k=block_k)
    assert out.shape == ref.shape
    assert float(jnp.abs(out - ref).max()) < 1e-5


@pytest.mark.parametrize("case", sorted(
    c for c, w in _WALKS.items() if w[2] == w[3] and w[0] <= 1536))
def test_flash_walk_grad(case):
    """Gradients over the same walks (equal widths: the backward takes
    one head width)."""
    s, heads, d, dv, block_q, block_k = _WALKS[case]
    assert_grads_match(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=block_q, block_k=block_k),
        xla_causal_attention, _walk_inputs(s, heads, d, dv), 1e-4)


def test_flash_grad_several_key_spans():
    """A sequence too long for one backward program a head: the dq
    partials are several planes, summed outside the kernel."""
    from ray_lightning_tpu.ops import flash_attention as fa

    walk = fa._pick_walk(1024, 64, 4, 0.125, 256, 256)
    assert walk.span == 1024
    walk = walk._replace(span=512)
    assert_grads_match(
        lambda q, k, v: fa._flash(0.125, walk, q, k, v),
        xla_causal_attention, _walk_inputs(1024, 1, 64, 64), 1e-4)


def test_flash_rejects_lane_misaligned_block_k(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, k, v, block_q=128, block_k=64)


def test_flash_rejects_ragged_seq(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=100)

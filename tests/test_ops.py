"""Numerical parity tests for the attention ops.

Strategy ≙ SURVEY §6 "grad-parity verification" (hard-part #5): the XLA
einsum attention is the reference; the Pallas flash kernel (interpreter on
CPU) and the ring sequence-parallel implementation must match it forward
and backward to float32 tolerance on a fixed seed.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from ray_lightning_tpu.ops.attention import xla_causal_attention
from ray_lightning_tpu.ops.flash_attention import flash_attention
from ray_lightning_tpu.ops.ring_attention import ring_attention_sharded

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
    q, k, v = qkv

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=128, block_k=128) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 1e-4


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
def test_flash_grad_uneven_blocks(qkv, block_q, block_k):
    """The dq/dkv kernels walk each other's axis in the *other* block
    size — both divisibility directions must stay correct."""
    q, k, v = qkv

    def loss_flash(q, k, v):
        return (flash_attention(
            q, k, v, block_q=block_q, block_k=block_k) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 1e-4


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

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
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
    q, k, v = _walk_inputs(s, heads, d, dv)

    def loss_flash(q, k, v):
        return (flash_attention(
            q, k, v, block_q=block_q, block_k=block_k) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 1e-4


def test_flash_grad_several_key_spans():
    """A sequence too long for one backward program a head: the dq
    partials are several planes, summed outside the kernel."""
    from ray_lightning_tpu.ops import flash_attention as fa

    q, k, v = _walk_inputs(1024, 1, 64, 64)
    walk = fa._pick_walk(1024, 64, 4, 0.125, 256, 256)
    assert walk.span == 1024
    walk = walk._replace(span=512)

    def loss_flash(q, k, v):
        return (fa._flash(0.125, walk, q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 1e-4


def test_flash_rejects_lane_misaligned_block_k(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, k, v, block_q=128, block_k=64)


def test_flash_rejects_ragged_seq(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=100)


@pytest.mark.parametrize("mesh_shape,axes", [
    ((8,), ("sp",)),
    ((2, 4), ("data", "sp")),
    ((1, 8), ("data", "sp")),
])
def test_ring_forward_matches_xla(qkv, mesh_shape, axes):
    q, k, v = qkv
    mesh = Mesh(mesh_utils.create_device_mesh(mesh_shape), axes)
    data_axis = "data" if "data" in axes else None
    ref = xla_causal_attention(q, k, v)
    out = ring_attention_sharded(q, k, v, mesh, data_axis=data_axis)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_ring_grad_matches_xla(qkv):
    """Full grad parity: dq AND dk/dv through the ppermute re-scan."""
    q, k, v = qkv
    mesh = Mesh(mesh_utils.create_device_mesh((2, 4)), ("data", "sp"))

    def loss_ring(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        err = float(jnp.abs(a - b).max())
        assert err < 1e-4, f"{name} max err {err}"


def test_ring_under_jit(qkv):
    """Ring attention composes with jit (the training-step context)."""
    q, k, v = qkv
    mesh = Mesh(mesh_utils.create_device_mesh((8,)), ("sp",))
    fn = jax.jit(
        lambda q, k, v: ring_attention_sharded(
            q, k, v, mesh, data_axis=None
        )
    )
    ref = xla_causal_attention(q, k, v)
    assert float(jnp.abs(fn(q, k, v) - ref).max()) < 1e-5


# -- fused LM-head cross-entropy (ops/cross_entropy.py) ----------------------

class TestFusedCrossEntropy:
    """Chunked-vs-naive parity (VERDICT r3 item #1: f32, 1e-5)."""

    def _inputs(self, V=515, B=2, T=32, d=64):
        rng = jax.random.PRNGKey(42)
        kx, kw, kt = jax.random.split(rng, 3)
        x = jax.random.normal(kx, (B, T, d), jnp.float32)
        wte = jax.random.normal(kw, (V, d), jnp.float32) * 0.1
        targets = jax.random.randint(kt, (B, T), 0, V)
        return x, wte, targets

    @pytest.mark.parametrize("num_chunks", [1, 3, 4])
    def test_loss_parity_f32(self, num_chunks):
        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy, naive_lm_head_cross_entropy)
        x, wte, t = self._inputs()  # V=515: exercises padded last chunk
        fused = fused_lm_head_cross_entropy(
            x, wte, t, num_chunks=num_chunks, compute_dtype=jnp.float32)
        naive = naive_lm_head_cross_entropy(
            x, wte, t, compute_dtype=jnp.float32)
        assert fused.shape == t.shape
        assert float(jnp.abs(fused - naive).max()) < 1e-5

    def test_grad_parity_f32(self):
        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy, naive_lm_head_cross_entropy)
        x, wte, t = self._inputs()

        def loss_f(x, w):
            return fused_lm_head_cross_entropy(
                x, w, t, num_chunks=4, compute_dtype=jnp.float32).mean()

        def loss_n(x, w):
            return naive_lm_head_cross_entropy(
                x, w, t, compute_dtype=jnp.float32).mean()

        gf = jax.grad(loss_f, argnums=(0, 1))(x, wte)
        gn = jax.grad(loss_n, argnums=(0, 1))(x, wte)
        for a, b, name in zip(gf, gn, ("dx", "dwte")):
            err = float(jnp.abs(a - b).max())
            assert err < 1e-5, f"{name} max err {err}"

    def test_bf16_close_to_f32(self):
        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy, naive_lm_head_cross_entropy)
        x, wte, t = self._inputs()
        fused = jax.jit(
            lambda x, w: fused_lm_head_cross_entropy(x, w, t, num_chunks=4)
        )(x, wte).mean()
        naive = naive_lm_head_cross_entropy(
            x, wte, t, compute_dtype=jnp.float32).mean()
        assert abs(float(fused) - float(naive)) < 5e-2

    def test_sharded_under_mesh(self):
        """Fused CE under a dp×tp GSPMD mesh: batch sharded over data,
        wte feature-sharded over tensor — matches the replicated result."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy, naive_lm_head_cross_entropy)
        x, wte, t = self._inputs(V=512, B=4, T=32, d=64)
        mesh = Mesh(
            mesh_utils.create_device_mesh((2, 4)), ("data", "tensor"))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        ws = jax.device_put(wte, NamedSharding(mesh, P(None, "tensor")))
        ts = jax.device_put(t, NamedSharding(mesh, P("data", None)))

        fused = jax.jit(
            lambda x, w, t: fused_lm_head_cross_entropy(
                x, w, t, num_chunks=4, compute_dtype=jnp.float32)
        )(xs, ws, ts)
        naive = naive_lm_head_cross_entropy(
            x, wte, t, compute_dtype=jnp.float32)
        assert float(jnp.abs(fused - naive).max()) < 1e-5


class TestFusedCEPallas:
    """Kernel-path (use_pallas=True) parity vs the naive head, run under
    the Pallas interpreter on the CPU mesh (same program as TPU)."""

    def _inputs(self, V=515, B=4, T=128, d=128):
        rng = jax.random.PRNGKey(7)
        kx, kw, kt = jax.random.split(rng, 3)
        x = jax.random.normal(kx, (B, T, d), jnp.float32)
        wte = jax.random.normal(kw, (V, d), jnp.float32) * 0.1
        targets = jax.random.randint(kt, (B, T), 0, V)
        return x, wte, targets

    # (4,128): token count divides _CE_BLOCK_T; (2,33): ragged -> padded.
    @pytest.mark.parametrize("B,T", [(4, 128), (2, 33)])
    def test_loss_and_grad_parity_f32(self, B, T):
        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy, naive_lm_head_cross_entropy)
        x, wte, t = self._inputs(B=B, T=T)

        def loss_p(x, w):
            return fused_lm_head_cross_entropy(
                x, w, t, compute_dtype=jnp.float32, use_pallas=True).mean()

        def loss_n(x, w):
            return naive_lm_head_cross_entropy(
                x, w, t, compute_dtype=jnp.float32).mean()

        lp = loss_p(x, wte)
        ln = loss_n(x, wte)
        assert abs(float(lp) - float(ln)) < 1e-5
        gp = jax.grad(loss_p, argnums=(0, 1))(x, wte)
        gn = jax.grad(loss_n, argnums=(0, 1))(x, wte)
        for a, b, name in zip(gp, gn, ("dx", "dwte")):
            err = float(jnp.abs(a - b).max())
            assert err < 1e-5, f"{name} max err {err}"

    def test_misaligned_d_falls_back_to_scan(self):
        """d=64 is not lane-aligned: use_pallas must silently take the
        scan path and still match."""
        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy, naive_lm_head_cross_entropy)
        x, wte, t = self._inputs(d=64)
        fused = fused_lm_head_cross_entropy(
            x, wte, t, compute_dtype=jnp.float32, use_pallas=True)
        naive = naive_lm_head_cross_entropy(
            x, wte, t, compute_dtype=jnp.float32)
        assert float(jnp.abs(fused - naive).max()) < 1e-5

    # jit > shard_map island > pallas: the multi-chip replicated-head
    # path (one dwte psum is the only collective).
    @pytest.mark.parametrize("pallas", [True, False])
    def test_sharded_island_parity(self, pallas):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy_sharded,
            naive_lm_head_cross_entropy)

        x, wte, t = self._inputs(B=8, T=64)
        mesh = Mesh(
            mesh_utils.create_device_mesh((2, 2, 2)),
            ("data", "fsdp", "tensor"),
        )
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "fsdp"))))
        ts = jax.device_put(t, NamedSharding(mesh, P(("data", "fsdp"))))
        ws = jax.device_put(wte, NamedSharding(mesh, P()))

        def loss_s(x, w):
            return fused_lm_head_cross_entropy_sharded(
                x, w, ts, mesh, compute_dtype=jnp.float32,
                use_pallas=pallas).mean()

        def loss_n(x, w):
            return naive_lm_head_cross_entropy(
                x, w, t, compute_dtype=jnp.float32).mean()

        lv, gv = jax.jit(jax.value_and_grad(loss_s, argnums=(0, 1)))(
            xs, ws)
        ln, gn = jax.value_and_grad(loss_n, argnums=(0, 1))(x, wte)
        assert abs(float(lv) - float(ln)) < 1e-5
        for a, b, name in zip(gv, gn, ("dx", "dwte")):
            err = float(jnp.abs(a - b).max())
            assert err < 1e-5, f"{name} max err {err}"

    def test_sharded_rejects_indivisible_batch(self):
        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy_sharded)
        import numpy as np

        x, wte, t = self._inputs(B=3, T=64)
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        with pytest.raises(ValueError, match="not divisible"):
            fused_lm_head_cross_entropy_sharded(
                x, wte, t, mesh, compute_dtype=jnp.float32)

    def test_batch_only_mesh_gate(self):
        """GPT engages the shard_map island only for batch-only GSPMD
        meshes with unsharded params."""
        import numpy as np

        from ray_lightning_tpu.models.gpt import GPT

        class Ctx:
            mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
            step_mode = "gspmd"
            zero_stage = 1

        assert GPT._batch_only_mesh(Ctx, batch_dim=8)
        # Indivisible batch: the island can't pad uneven shards -> veto.
        assert not GPT._batch_only_mesh(Ctx, batch_dim=6)
        for attr, bad in (("step_mode", "shard_map"), ("zero_stage", 3)):
            ctx = type("C", (Ctx,), {attr: bad})
            assert not GPT._batch_only_mesh(ctx, batch_dim=8)
        tp = type("C", (Ctx,), {"mesh": Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2),
            ("data", "tensor"))})
        assert not GPT._batch_only_mesh(tp, batch_dim=8)
        assert not GPT._batch_only_mesh(
            type("C", (), {"mesh": None}), batch_dim=8)


class TestFusedLayerNorm:
    """Pallas LN kernels (interpret mode) vs the XLA reference math."""

    def _inputs(self, n=700, d=256):  # n=700: exercises token padding
        rng = jax.random.PRNGKey(11)
        kx, kg, kb = jax.random.split(rng, 3)
        x = jax.random.normal(kx, (4, n // 4, d), jnp.float32) * 3 + 1
        g = jax.random.normal(kg, (d,), jnp.float32) * 0.5 + 1
        b = jax.random.normal(kb, (d,), jnp.float32)
        return x, g, b

    def test_forward_and_grad_parity(self):
        from ray_lightning_tpu.ops.layer_norm import layer_norm

        x, g, b = self._inputs()

        def lp(x, g, b):
            return (layer_norm(x, g, b, use_pallas=True) ** 2).mean()

        def ln(x, g, b):
            return (layer_norm(x, g, b, use_pallas=False) ** 2).mean()

        yp = layer_norm(x, g, b, use_pallas=True)
        yn = layer_norm(x, g, b, use_pallas=False)
        assert float(jnp.abs(yp - yn).max()) < 1e-5
        gp = jax.grad(lp, argnums=(0, 1, 2))(x, g, b)
        gn = jax.grad(ln, argnums=(0, 1, 2))(x, g, b)
        for a, c, name in zip(gp, gn, ("dx", "dg", "db")):
            err = float(jnp.abs(a - c).max())
            assert err < 1e-5, f"{name} max err {err}"

    def test_bf16_input(self):
        from ray_lightning_tpu.ops.layer_norm import layer_norm

        x, g, b = self._inputs(n=512, d=128)
        xb = x.astype(jnp.bfloat16)
        yp = layer_norm(xb, g, b, use_pallas=True)
        yn = layer_norm(xb, g, b, use_pallas=False)
        assert yp.dtype == jnp.bfloat16
        assert float(jnp.abs(
            yp.astype(jnp.float32) - yn.astype(jnp.float32)
        ).max()) < 2e-2

    def test_misaligned_d_falls_back(self):
        from ray_lightning_tpu.ops.layer_norm import layer_norm

        x, g, b = self._inputs(n=64, d=96)  # 96 % 128 != 0
        yp = layer_norm(x, g, b, use_pallas=True)  # silently XLA
        yn = layer_norm(x, g, b, use_pallas=False)
        assert float(jnp.abs(yp - yn).max()) == 0.0


@pytest.mark.parametrize("mesh_shape,axes", [
    ((8,), ("sp",)),
    ((2, 4), ("data", "sp")),
])
def test_zigzag_ring_forward_matches_xla(qkv, mesh_shape, axes):
    """Zig-zag (causally balanced) layout: same math, permuted shards."""
    q, k, v = qkv
    mesh = Mesh(mesh_utils.create_device_mesh(mesh_shape), axes)
    data_axis = "data" if "data" in axes else None
    ref = xla_causal_attention(q, k, v)
    out = ring_attention_sharded(
        q, k, v, mesh, data_axis=data_axis, layout="zigzag")
    assert float(jnp.abs(out - ref).max()) < 1e-5


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_zigzag_ring_grad_matches_xla(qkv):
    q, k, v = qkv
    mesh = Mesh(mesh_utils.create_device_mesh((2, 4)), ("data", "sp"))

    def loss_ring(q, k, v):
        return (ring_attention_sharded(
            q, k, v, mesh, layout="zigzag") ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        err = float(jnp.abs(a - b).max())
        assert err < 1e-4, f"{name} max err {err}"


def test_zigzag_indices_partition():
    from ray_lightning_tpu.ops.ring_attention import zigzag_indices

    idx = zigzag_indices(16, 4)
    # Shard j holds chunks j and 2n-1-j of 8 chunks (chunk = 2 rows).
    assert list(idx[:4]) == [0, 1, 14, 15]      # shard 0: chunks 0, 7
    assert list(idx[4:8]) == [2, 3, 12, 13]     # shard 1: chunks 1, 6
    assert sorted(idx) == list(range(16))       # a true permutation
    with pytest.raises(ValueError, match="divisible"):
        zigzag_indices(20, 8)


class TestKernelDisableSwitch:
    """RLT_DISABLE_KERNELS: the on-hardware A/B switch must force the
    XLA path per family and be reflected by the selection predicates
    (``GPT.kernel_paths`` reports the path from exactly these)."""

    def test_family_disable_forces_fallback(self, monkeypatch):
        from ray_lightning_tpu.ops import kernel_probe

        monkeypatch.setenv("RLT_DISABLE_KERNELS", "ce, ln")
        assert kernel_probe.kernel_family_disabled("ce")
        assert kernel_probe.kernel_family_disabled("ln")
        assert not kernel_probe.kernel_family_disabled("flash")
        # The per-family gates read the switch (and nothing else that
        # could differ between two calls with the same shapes).
        from ray_lightning_tpu.ops.cross_entropy import _pallas_fwd_ok
        from ray_lightning_tpu.ops.layer_norm import _kernel_selected

        assert _pallas_fwd_ok(128, jnp.float32) is False
        assert _kernel_selected(128, True) is False
        monkeypatch.setenv("RLT_DISABLE_KERNELS", "flash")
        assert _pallas_fwd_ok(128, jnp.float32) is True
        assert _kernel_selected(128, True) is True

    def test_flash_disable_switch(self, monkeypatch):
        import jax.numpy as jnp

        from ray_lightning_tpu.ops.attention import _flash_supported

        q = jnp.zeros((1, 256, 4, 64), jnp.float32)
        monkeypatch.setenv("RLT_DISABLE_KERNELS", "flash")
        assert _flash_supported(q) is False

    def test_flash_selection_reads_the_mesh(self, monkeypatch):
        """A Mosaic kernel cannot be partitioned by GSPMD: on a
        multi-device mesh ``auto`` takes flash only where a shard_map
        island can hold it (batch-only axes, divisible batch) or the
        caller's body is already per-device."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from ray_lightning_tpu.ops import attention as att

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q = jax.ShapeDtypeStruct((8, 256, 4, 64), jnp.bfloat16)
        devs = np.array(jax.devices()[:4])
        dp = Mesh(devs.reshape(4), ("data",))
        dp_fsdp = Mesh(devs.reshape(2, 2), ("data", "fsdp"))
        tp = Mesh(devs.reshape(2, 2), ("data", "tensor"))
        sp = Mesh(devs.reshape(4), ("sp",))
        assert att._flash_supported(q) is True            # one device
        assert att._flash_supported(q, dp) is True        # island
        assert att._flash_supported(q, dp_fsdp) is True
        assert att._flash_supported(q, tp) is False       # heads sharded
        assert att._flash_supported(q, sp) is False       # seq sharded
        assert att._flash_supported(q, tp, manual=True) is True
        odd = jax.ShapeDtypeStruct((6, 256, 4, 64), jnp.bfloat16)
        assert att._flash_supported(odd, dp) is False     # 6 % 4
        # An explicit impl="flash" where no island fits is an error,
        # never a silent change of path.
        x = jnp.zeros((6, 256, 4, 64), jnp.bfloat16)
        with pytest.raises(ValueError, match="per device"):
            att.causal_attention(x, x, x, impl="flash", mesh=dp)

    def test_flash_island_matches_xla_on_cpu_mesh(self, monkeypatch):
        """The island's arithmetic (interpreted kernel per device) on a
        4-device data mesh, forward and backward, against the XLA
        reference."""
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ray_lightning_tpu.ops import attention as att

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        sh = NamedSharding(mesh, P("data"))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.device_put(
            jax.random.normal(kk, (4, 128, 2, 64), jnp.float32), sh
        ) for kk in ks)

        def loss(impl, **kw):
            return lambda q, k, v: (att.causal_attention(
                q, k, v, impl=impl, **kw) ** 2).sum()

        got = jax.jit(jax.value_and_grad(
            loss("flash", mesh=mesh), argnums=(0, 1, 2)))(q, k, v)
        ref = jax.jit(jax.value_and_grad(
            loss("xla"), argnums=(0, 1, 2)))(q, k, v)
        assert abs(float(got[0]) - float(ref[0])) < 1e-3 * abs(
            float(ref[0]))
        for a, b in zip(got[1], ref[1]):
            assert float(jnp.abs(a - b).max()) < 2e-4

    def test_disabled_ce_still_correct(self, monkeypatch):
        """Numerics with the family disabled: the scan fallback answers."""
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy, naive_lm_head_cross_entropy)

        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(k1, (2, 16, 128), jnp.float32)
        w = jax.random.normal(k2, (256, 128), jnp.float32) * 0.1
        t = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 256)
        monkeypatch.setenv("RLT_DISABLE_KERNELS", "ce")
        fused = fused_lm_head_cross_entropy(
            x, w, t, compute_dtype=jnp.float32, use_pallas=True)
        naive = naive_lm_head_cross_entropy(x, w, t,
                                            compute_dtype=jnp.float32)
        assert float(jnp.abs(fused - naive).max()) < 1e-5

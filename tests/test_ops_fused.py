"""Numerical parity tests for the fused LM-head cross-entropy and the
fused layer norm against their plain forms (Pallas kernels under the
interpreter on CPU), and the switch that turns a kernel family off.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_lightning_tpu.ops.cross_entropy import (
    fused_lm_head_cross_entropy, fused_lm_head_cross_entropy_sharded,
    naive_lm_head_cross_entropy,
)
from ray_lightning_tpu.ops.layer_norm import layer_norm


# -- fused LM-head cross-entropy (ops/cross_entropy.py) ----------------------

def _ce_inputs(seed, V, B, T, d):
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (B, T, d), jnp.float32)
    wte = jax.random.normal(kw, (V, d), jnp.float32) * 0.1
    return x, wte, jax.random.randint(kt, (B, T), 0, V)


class TestFusedCrossEntropy:
    """Chunked-vs-naive parity (VERDICT r3 item #1: f32, 1e-5)."""

    def _inputs(self, V=515, B=2, T=32, d=64):
        return _ce_inputs(42, V, B, T, d)

    @pytest.mark.parametrize("num_chunks", [1, 3, 4])
    def test_loss_parity_f32(self, num_chunks):
        x, wte, t = self._inputs()  # V=515: exercises padded last chunk
        fused = fused_lm_head_cross_entropy(
            x, wte, t, num_chunks=num_chunks, compute_dtype=jnp.float32)
        naive = naive_lm_head_cross_entropy(
            x, wte, t, compute_dtype=jnp.float32)
        assert fused.shape == t.shape
        assert float(jnp.abs(fused - naive).max()) < 1e-5

    def test_grad_parity_f32(self):
        x, wte, t = self._inputs()

        def loss_f(x, w):
            return fused_lm_head_cross_entropy(
                x, w, t, num_chunks=4, compute_dtype=jnp.float32).mean()

        def loss_n(x, w):
            return naive_lm_head_cross_entropy(
                x, w, t, compute_dtype=jnp.float32).mean()

        gf = jax.jit(jax.grad(loss_f, argnums=(0, 1)))(x, wte)
        gn = jax.jit(jax.grad(loss_n, argnums=(0, 1)))(x, wte)
        for a, b, name in zip(gf, gn, ("dx", "dwte")):
            err = float(jnp.abs(a - b).max())
            assert err < 1e-5, f"{name} max err {err}"

    def test_bf16_close_to_f32(self):
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
        return _ce_inputs(7, V, B, T, d)

    # (4,128): token count divides _CE_BLOCK_T; (2,33): ragged -> padded.
    @pytest.mark.parametrize("B,T", [(4, 128), (2, 33)])
    def test_loss_and_grad_parity_f32(self, B, T):
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
        gp = jax.jit(jax.grad(loss_p, argnums=(0, 1)))(x, wte)
        gn = jax.jit(jax.grad(loss_n, argnums=(0, 1)))(x, wte)
        for a, b, name in zip(gp, gn, ("dx", "dwte")):
            err = float(jnp.abs(a - b).max())
            assert err < 1e-5, f"{name} max err {err}"

    def test_misaligned_d_falls_back_to_scan(self):
        """d=64 is not lane-aligned: use_pallas must silently take the
        scan path and still match."""
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
        x, wte, t = self._inputs(B=3, T=64)
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        with pytest.raises(ValueError, match="not divisible"):
            fused_lm_head_cross_entropy_sharded(
                x, wte, t, mesh, compute_dtype=jnp.float32)

    def test_batch_only_mesh_gate(self):
        """GPT engages the shard_map island only for batch-only GSPMD
        meshes with unsharded params."""
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
        x, g, b = self._inputs()

        def lp(x, g, b):
            return (layer_norm(x, g, b, use_pallas=True) ** 2).mean()

        def ln(x, g, b):
            return (layer_norm(x, g, b, use_pallas=False) ** 2).mean()

        yp = layer_norm(x, g, b, use_pallas=True)
        yn = layer_norm(x, g, b, use_pallas=False)
        assert float(jnp.abs(yp - yn).max()) < 1e-5
        gp = jax.jit(jax.grad(lp, argnums=(0, 1, 2)))(x, g, b)
        gn = jax.jit(jax.grad(ln, argnums=(0, 1, 2)))(x, g, b)
        for a, c, name in zip(gp, gn, ("dx", "dg", "db")):
            err = float(jnp.abs(a - c).max())
            assert err < 1e-5, f"{name} max err {err}"

    def test_bf16_input(self):
        x, g, b = self._inputs(n=512, d=128)
        xb = x.astype(jnp.bfloat16)
        yp = layer_norm(xb, g, b, use_pallas=True)
        yn = layer_norm(xb, g, b, use_pallas=False)
        assert yp.dtype == jnp.bfloat16
        assert float(jnp.abs(
            yp.astype(jnp.float32) - yn.astype(jnp.float32)
        ).max()) < 2e-2

    def test_misaligned_d_falls_back(self):
        x, g, b = self._inputs(n=64, d=96)  # 96 % 128 != 0
        yp = layer_norm(x, g, b, use_pallas=True)  # silently XLA
        yn = layer_norm(x, g, b, use_pallas=False)
        assert float(jnp.abs(yp - yn).max()) == 0.0


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
        from ray_lightning_tpu.ops.attention import _flash_supported

        q = jnp.zeros((1, 256, 4, 64), jnp.float32)
        monkeypatch.setenv("RLT_DISABLE_KERNELS", "flash")
        assert _flash_supported(q) is False

    def test_flash_selection_reads_the_mesh(self, monkeypatch):
        """A Mosaic kernel cannot be partitioned by GSPMD: on a
        multi-device mesh ``auto`` takes flash only where a shard_map
        island can hold it (batch-only axes, divisible batch) or the
        caller's body is already per-device."""
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

"""GPT flagship-model tests: training moves weights, parallel flavors agree.

≙ the reference test taxonomy (SURVEY §4): ``train_test`` weights-changed,
plus the TPU-specific addition — loss parity between the plain data mesh
and the TP/FSDP/ZeRO-sharded mesh (sharding must be a no-op numerically).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models.gpt import GPT, GPTConfig, SyntheticLMDataModule
from ray_lightning_tpu.parallel.strategies import LocalStrategy


def tiny():
    return GPTConfig.tiny()


def make_trainer(**kw):
    kw.setdefault("max_epochs", 1)
    kw.setdefault("limit_train_batches", 2)
    kw.setdefault("limit_val_batches", 1)
    kw.setdefault("enable_checkpointing", False)
    return Trainer(**kw)


def fit_metrics(strategy, attn_impl="xla", **model_kw):
    cfg = tiny()
    tr = make_trainer(strategy=strategy)
    tr.fit(GPT(cfg, attn_impl=attn_impl, **model_kw),
           SyntheticLMDataModule(cfg, batch_size=8, num_batches=2))
    return tr


def test_gpt_trains_and_moves_weights():
    tr = fit_metrics(LocalStrategy())
    assert np.isfinite(tr.callback_metrics["train_loss"])
    # Loss near ln(vocab) for random tokens — the model is wired correctly.
    assert 4.0 < tr.callback_metrics["train_loss"] < 8.0
    assert tr.state is not None


def test_gpt_tp_fsdp_parity_with_data_mesh():
    """ZeRO-3 + tensor parallel must be numerically identical to plain DP."""
    base = fit_metrics(LocalStrategy())
    sharded = fit_metrics(
        LocalStrategy(mesh_axes={"data": 2, "fsdp": 2, "tensor": 2},
                      zero_stage=3)
    )
    assert base.callback_metrics["train_loss"] == pytest.approx(
        sharded.callback_metrics["train_loss"], rel=1e-5
    )
    assert base.callback_metrics["val_loss"] == pytest.approx(
        sharded.callback_metrics["val_loss"], rel=1e-5
    )


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_gpt_ring_attention_training():
    """Sequence-parallel (ring attention) flavor trains and agrees."""
    base = fit_metrics(LocalStrategy())
    ring = fit_metrics(
        LocalStrategy(mesh_axes={"data": 2, "sp": 4}),
        attn_impl="ring",
    )
    assert base.callback_metrics["train_loss"] == pytest.approx(
        ring.callback_metrics["train_loss"], rel=1e-4
    )


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_gpt_zigzag_ring_training():
    """Zig-zag (causally balanced) sequence parallelism trains and agrees
    with the plain local run — the in/out permutations cancel."""
    base = fit_metrics(LocalStrategy())
    ring = fit_metrics(
        LocalStrategy(mesh_axes={"data": 2, "sp": 4}),
        attn_impl="ring", ring_layout="zigzag",
    )
    assert base.callback_metrics["train_loss"] == pytest.approx(
        ring.callback_metrics["train_loss"], rel=1e-4
    )


def test_param_partition_specs_cover_params():
    model = GPT(tiny())
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    specs = model.param_partition_specs()
    p_leaves = jax.tree_util.tree_leaves(params)
    s_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    assert len(p_leaves) == len(s_leaves)


def test_state_shardings_follow_tp_specs():
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    from ray_lightning_tpu.core.module import TrainState
    from ray_lightning_tpu.parallel.sharding import (
        state_shardings_for_module,
    )

    model = GPT(tiny())
    mesh = Mesh(
        mesh_utils.create_device_mesh((2, 2, 2)),
        ("data", "fsdp", "tensor"),
    )
    tx = model.configure_optimizers()

    def make(rng):
        return TrainState.create(model.init_params(rng), tx)

    abstract = jax.eval_shape(make, jax.random.PRNGKey(0))
    sh = state_shardings_for_module(model, abstract, mesh, zero_stage=1)
    # TP spec honored on params:
    assert sh.params["blocks"]["qkv_w"].spec == P(None, None, "tensor")
    # Optimizer moments inherit the param TP spec + the fsdp zero axis:
    mu_qkv = jax.tree_util.tree_leaves_with_path(sh.opt_state)
    hits = [
        s for path, s in mu_qkv
        if any(getattr(k, "key", None) == "qkv_w" for k in path)
    ]
    assert hits, "no optimizer-moment sharding found for qkv_w"
    for s in hits:
        assert "tensor" in jax.tree_util.tree_leaves(tuple(s.spec)) or (
            s.spec and "tensor" in str(s.spec)
        )
        assert "fsdp" in str(s.spec)


def test_adamw_momentum_stored_bf16():
    """The default optimizer keeps the first moment in bf16 (HBM-bound
    update reads/writes half the bytes for that state) while the second
    moment stays f32; mu_dtype='float32' opts out."""
    from dataclasses import replace

    import optax

    model = GPT(tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    state = model.configure_optimizers().init(params)
    adam = next(
        s for s in jax.tree_util.tree_leaves(
            state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState)
        ) if isinstance(s, optax.ScaleByAdamState)
    )
    assert all(
        leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(adam.mu)
    )
    assert all(
        leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(adam.nu)
    )

    f32_model = GPT(replace(tiny(), mu_dtype="float32"))
    f32_state = f32_model.configure_optimizers().init(params)
    adam32 = next(
        s for s in jax.tree_util.tree_leaves(
            f32_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState)
        ) if isinstance(s, optax.ScaleByAdamState)
    )
    assert all(
        leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(adam32.mu)
    )


def test_gpt_remat_matches_no_remat():
    """jax.checkpoint is numerically inert: remat only trades FLOPs for
    activation memory."""
    base = fit_metrics(LocalStrategy())
    cfg = tiny()
    tr = make_trainer(strategy=LocalStrategy())
    tr.fit(GPT(cfg, remat=True),
           SyntheticLMDataModule(cfg, batch_size=8, num_batches=2))
    assert base.callback_metrics["train_loss"] == pytest.approx(
        tr.callback_metrics["train_loss"], rel=1e-6
    )


def test_kernel_ln_under_remat_matches_xla_ln(monkeypatch):
    """Fused-LN custom_vjp composes with jax.checkpoint: a rematerialized
    training step with the kernel LN forced on (interpret mode — the
    single-TPU-chip configuration) matches the XLA-LN step."""
    import ray_lightning_tpu.models.gpt as gptmod

    cfg = tiny()
    m = GPT(cfg, remat=True)
    params = m.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, cfg.seq_len + 1), 0, cfg.vocab_size)

    def loss(params):
        return m.training_step(params, {"tokens": tokens}, None)[0]

    l_base, g_base = jax.value_and_grad(loss)(params)

    # Spy on the kernel entry so the test fails loudly if the gate ever
    # silently falls back to XLA (which would compare XLA against XLA).
    from ray_lightning_tpu.ops import layer_norm as lnmod

    kernel_calls = []
    real_fused = lnmod._fused_ln

    def spying_fused(x, g, b):
        kernel_calls.append(x.shape)
        return real_fused(x, g, b)

    monkeypatch.setattr(lnmod, "_fused_ln", spying_fused)
    orig = gptmod._layer_norm
    monkeypatch.setattr(
        gptmod, "_layer_norm",
        lambda x, g, b, up=False: orig(x, g, b, use_pallas=True))
    l_k, g_k = jax.value_and_grad(loss)(params)
    assert kernel_calls, "fused LN kernel path was never taken"
    assert float(l_base) == pytest.approx(float(l_k), abs=1e-5)
    for a, b_, in zip(jax.tree.leaves(g_base), jax.tree.leaves(g_k)):
        assert float(jnp.abs(a - b_).max()) < 1e-4


def test_gpt_shard_map_flavor_trains():
    """The Horovod-duality (shard_map) flavor must trace GPT cleanly —
    the residual sharding anchor is a gspmd-only concept and must no-op
    inside a Manual-axes body."""
    from ray_lightning_tpu.parallel.strategies import HorovodRayStrategy

    base = fit_metrics(LocalStrategy())
    cfg = tiny()
    tr = make_trainer(strategy=HorovodRayStrategy(num_workers=1))
    tr.fit(GPT(cfg), SyntheticLMDataModule(cfg, batch_size=8, num_batches=2))
    assert base.callback_metrics["train_loss"] == pytest.approx(
        tr.callback_metrics["train_loss"], rel=1e-5
    )


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
@pytest.mark.parametrize("policy", ["dots+flash", "dots+flash-out", "dots"])
def test_remat_policy_variants_same_numerics(policy):
    """remat_policy only changes WHAT the backward saves, never the
    math: loss and grads must match the no-remat baseline.

    attn_impl='flash' explicitly (interpret-mode Pallas on the CPU
    mesh): under 'auto' the CPU path takes the XLA einsum, no flash_*
    checkpoint_name residuals exist, and all three policies would
    compile the same program — the arms must differ to be tested.
    head_dim 64 to satisfy the kernel's lane constraint."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = GPTConfig(vocab_size=512, n_layer=2, n_head=4, d_model=256,
                    seq_len=128, warmup_steps=2)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, cfg.seq_len + 1)),
        jnp.int32)

    def loss_fn(model):
        params = model.init_params(jax.random.PRNGKey(0))

        def loss(p):
            l, _ = model.training_step(p, {"tokens": tokens}, jax.random.PRNGKey(1))
            return l

        val, grads = jax.jit(jax.value_and_grad(loss))(params)
        return float(val), grads

    base_val, base_grads = loss_fn(GPT(cfg, attn_impl="flash", remat=False))
    val, grads = loss_fn(
        GPT(cfg, attn_impl="flash", remat=True, remat_policy=policy))
    assert val == pytest.approx(base_val, rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(base_grads),
                    jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_remat_policy_rejects_unknown():
    with pytest.raises(ValueError, match="remat_policy"):
        GPT(GPTConfig.tiny(), remat_policy="everything")


@pytest.mark.slow  # same budget class as the other remat-variant fits
def test_remat_bf16_resid_close_numerics():
    """The "bf16-resid" arm stores the layer-scan carry in bf16 — by
    design a ROUNDING of the residual stream at block boundaries (the
    same rounding precision='bf16' applies everywhere), so loss/grads
    track the exact arms within bf16 tolerance rather than matching
    bitwise.  Flash attention explicitly, like the exact-parity test:
    the named flash residuals must exist for the save-set to differ."""
    import jax
    import numpy as np

    import jax.numpy as jnp

    cfg = GPTConfig(vocab_size=512, n_layer=2, n_head=4, d_model=256,
                    seq_len=128, warmup_steps=2)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, cfg.seq_len + 1)),
        jnp.int32)

    def loss_fn(model):
        params = model.init_params(jax.random.PRNGKey(0))

        def loss(p):
            l, _ = model.training_step(
                p, {"tokens": tokens}, jax.random.PRNGKey(1))
            return l

        val, grads = jax.jit(jax.value_and_grad(loss))(params)
        return float(val), grads

    base_val, base_grads = loss_fn(
        GPT(cfg, attn_impl="flash", remat=True,
            remat_policy="dots+flash-out"))
    val, grads = loss_fn(
        GPT(cfg, attn_impl="flash", remat=True,
            remat_policy="bf16-resid"))
    assert val == pytest.approx(base_val, rel=1e-3)
    assert np.isfinite(val)
    for a, b in zip(jax.tree_util.tree_leaves(base_grads),
                    jax.tree_util.tree_leaves(grads)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(b).all()
        # bf16 rounding of the residual stream: absolute tolerance at
        # the bf16 ulp scale of the gradient magnitudes involved.
        np.testing.assert_allclose(a, b, rtol=0.05, atol=2e-3)


def test_remat_bf16_resid_without_remat_is_exact():
    """Without remat nothing is saved per layer, so the bf16-resid
    carry rounding must NOT engage — the forward equals the default
    policy's bitwise."""
    import jax
    import numpy as np

    import jax.numpy as jnp

    cfg = GPTConfig.tiny()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    params = GPT(cfg).init_params(jax.random.PRNGKey(0))
    ref = GPT(cfg, remat=False).forward(params, tokens)
    got = GPT(cfg, remat=False, remat_policy="bf16-resid").forward(
        params, tokens)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_residual_save_bytes_accounting():
    """The analytic model of what each remat policy saves per step:
    arm ordering must match the design — dots+flash (double-save) >
    dots+flash-out > bf16-resid(f32 run) > dots — and the bf16 carry
    must save exactly half the carry bytes of an f32 run."""
    from ray_lightning_tpu.models.gpt import residual_save_bytes

    cfg = GPTConfig.tiny()
    B = 16
    flash = residual_save_bytes(cfg, B, "dots+flash", "f32")
    flash_out = residual_save_bytes(cfg, B, "dots+flash-out", "f32")
    bf16r = residual_save_bytes(cfg, B, "bf16-resid", "f32")
    dots = residual_save_bytes(cfg, B, "dots", "f32")
    assert flash > flash_out > bf16r > dots
    carry_f32 = cfg.n_layer * B * cfg.seq_len * cfg.d_model * 4
    assert flash_out - bf16r == carry_f32 // 2
    # On a bf16-precision run the carry is already 2 bytes — the arm
    # changes nothing.
    assert (residual_save_bytes(cfg, B, "bf16-resid", "bf16")
            == residual_save_bytes(cfg, B, "dots+flash-out", "bf16"))


def test_decay_mask_exempts_norms_biases_everywhere():
    """The weight-decay mask must exempt LN params and biases at every
    nesting level — stacked blocks and MoE tensors carry extra leading
    dims that break any raw ndim rule."""
    from ray_lightning_tpu.models.optim import decay_mask
    from ray_lightning_tpu.models import ViT, ViTConfig

    p = GPT(GPTConfig.tiny_moe()).init_params(jax.random.PRNGKey(0))
    m = decay_mask(p)
    assert m["wte"] is True  # tied to the LM head — a matrix
    assert m["wpe"] is False  # positional table — exempt in both families
    assert m["ln_f_g"] is False and m["ln_f_b"] is False
    b = m["blocks"]
    assert b["qkv_w"] and b["moe_in_w"] and b["moe_out_w"] and b["gate_w"]
    assert not (b["qkv_b"] or b["moe_in_b"] or b["moe_out_b"]
                or b["ln1_g"] or b["ln2_b"])

    pv = ViT(ViTConfig.tiny()).init_params(jax.random.PRNGKey(0))
    mv = decay_mask(pv)
    assert mv["patch_w"] and mv["head_w"] and mv["blocks"]["mlp_in_w"]
    assert not (mv["pos"] or mv["patch_b"] or mv["head_b"]
                or mv["blocks"]["mlp_in_b"] or mv["blocks"]["ln1_g"])


class TestByteLMDataModule:
    def _write_text(self, tmp_path, n=4096):
        p = tmp_path / "corpus.txt"
        text = ("the quick brown fox jumps over the lazy dog. " * 200)
        p.write_bytes(text.encode()[:n])
        return str(p)

    def test_windows_shape_and_bos(self, tmp_path):
        from ray_lightning_tpu.models import ByteLMDataModule

        dm = ByteLMDataModule(self._write_text(tmp_path), seq_len=64,
                              batch_size=4)
        dm.set_shard(0, 1)
        dm.setup("fit")
        batch = next(iter(dm.train_dataloader()))
        assert batch["tokens"].shape == (4, 65)
        assert batch["tokens"].dtype == np.int32
        assert (batch["tokens"][:, 0] == 256).all()  # BOS
        assert batch["tokens"].max() < ByteLMDataModule.vocab_size

    @pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
    def test_gpt_trains_on_real_text(self, tmp_path):
        """End-to-end: byte-level GPT on real text, loss clearly below
        uniform (ln 384 ≈ 5.95) after one epoch on repetitive text."""
        from ray_lightning_tpu.models import ByteLMDataModule

        dm = ByteLMDataModule(self._write_text(tmp_path, n=8192),
                              seq_len=64, batch_size=8)
        cfg = GPTConfig(vocab_size=ByteLMDataModule.vocab_size,
                        n_layer=2, n_head=4, d_model=128, seq_len=64,
                        warmup_steps=2, lr=3e-3)
        tr = Trainer(strategy=LocalStrategy(), max_epochs=2,
                     enable_checkpointing=False,
                     default_root_dir=str(tmp_path))
        tr.fit(GPT(cfg), dm)
        assert tr.callback_metrics["train_loss"] < 4.0

    def test_too_short_file_rejected(self, tmp_path):
        from ray_lightning_tpu.models import ByteLMDataModule

        p = tmp_path / "tiny.txt"
        p.write_bytes(b"short")
        dm = ByteLMDataModule(str(p), seq_len=64)
        with pytest.raises(ValueError, match="too short"):
            dm.setup("fit")

    def test_decode_bytes_roundtrip(self):
        from ray_lightning_tpu.models import decode_bytes

        toks = [256] + [ord(c) for c in "hello"] + [300]
        assert decode_bytes(np.asarray(toks)) == "hello"


def test_bytelm_requires_full_batches(tmp_path):
    """A file passing a naive 'two windows' check but yielding ZERO full
    train batches must be rejected, not silently train nothing."""
    from ray_lightning_tpu.models import ByteLMDataModule

    p = tmp_path / "small.txt"
    p.write_bytes(b"x" * 600)  # 9 windows at seq_len=64 < 8 train + 8 val
    dm = ByteLMDataModule(str(p), seq_len=64, batch_size=8)
    with pytest.raises(ValueError, match="too short"):
        dm.setup("fit")


def test_bytelm_val_is_file_tail(tmp_path):
    """Temporal holdout: validation windows come from the END of the
    file (documented contract — val on unseen later text)."""
    from ray_lightning_tpu.models import ByteLMDataModule

    p = tmp_path / "ab.txt"
    # First 2/3 'a' bytes, final third 'b' bytes.
    p.write_bytes(b"a" * 4000 + b"b" * 2000)
    dm = ByteLMDataModule(str(p), seq_len=50, batch_size=4)
    dm.set_shard(0, 1)
    dm.setup("fit")
    val = next(iter(dm.val_dataloader()))["tokens"]
    assert (val[:, 1:] == ord("b")).all()  # tail-only content

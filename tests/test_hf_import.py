"""HF GPT-2 import: logits parity between a randomly-initialized
``transformers`` GPT-2 and the imported in-framework GPT — the
migration-path guarantee for users arriving from the torch ecosystem.

No downloads (zero-egress environment): a tiny random-init HF model is
the oracle.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.gpt import GPT
from ray_lightning_tpu.utils.hf_import import (
    gpt_config_from_hf,
    import_gpt2,
)


@pytest.fixture(scope="module", autouse=True)
def _torch_stack():
    """``torch`` and ``transformers`` are imported where these cases
    run, not where every xdist worker collects them (7 s of import in
    each of seven processes)."""
    global torch, transformers
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")


def _tiny_hf(vocab=97, n_layer=2, n_head=4, d=64, seq=32):
    config = transformers.GPT2Config(
        vocab_size=vocab, n_positions=seq, n_embd=d,
        n_layer=n_layer, n_head=n_head,
        attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0,
    )
    torch.manual_seed(0)
    model = transformers.GPT2LMHeadModel(config)
    model.eval()
    return model


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_logits_parity_with_transformers():
    hf = _tiny_hf()
    cfg, params = import_gpt2(hf)
    model = GPT(cfg, attn_impl="xla")
    model.precision = "f32"

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int64)

    with torch.no_grad():
        ref = hf(torch.from_numpy(tokens)).logits.numpy()

    ours = np.asarray(
        jax.jit(model.forward)(params, jnp.asarray(tokens, jnp.int32))
    )
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_imported_params_train_under_strategy(tmp_path):
    """Imported weights drop into the normal fit path (sharded mesh):
    the loss moves and stays finite."""
    from ray_lightning_tpu.core.trainer import Trainer
    from ray_lightning_tpu.models.gpt import SyntheticLMDataModule
    from ray_lightning_tpu.parallel.strategies import LocalStrategy

    hf = _tiny_hf()
    cfg, params = import_gpt2(hf)
    model = GPT(cfg, attn_impl="xla")
    model.initial_params = params  # seed the fit from imported weights

    trainer = Trainer(
        strategy=LocalStrategy(mesh_axes={"data": 2, "fsdp": 2, "tensor": 2},
                               zero_stage=3),
        max_epochs=1, limit_train_batches=2, limit_val_batches=1,
        enable_checkpointing=False, default_root_dir=str(tmp_path),
    )
    trainer.fit(model, SyntheticLMDataModule(cfg, batch_size=8,
                                             num_batches=2))
    assert np.isfinite(trainer.callback_metrics["train_loss"])


def test_generation_parity_greedy():
    """Greedy decode agrees with HF's greedy generate on the same
    imported weights — the end-to-end inference parity check."""
    from ray_lightning_tpu.models.generate import generate

    hf = _tiny_hf()
    cfg, params = import_gpt2(hf)
    model = GPT(cfg, attn_impl="xla")
    model.precision = "f32"

    prompt = np.asarray([[5, 17, 3, 42]], dtype=np.int64)
    new = 8
    with torch.no_grad():
        ref = hf.generate(
            torch.from_numpy(prompt), max_new_tokens=new, do_sample=False,
            pad_token_id=0,
        ).numpy()[:, prompt.shape[1]:]

    ours = np.asarray(generate(
        model, params, jnp.asarray(prompt, jnp.int32), max_new_tokens=new,
    ))[:, prompt.shape[1]:]
    np.testing.assert_array_equal(ours, ref)


def test_import_rejects_incompatible_activation():
    config = transformers.GPT2Config(
        vocab_size=64, n_positions=16, n_embd=32, n_layer=1, n_head=2,
        activation_function="relu",
    )
    with pytest.raises(ValueError, match="activation"):
        gpt_config_from_hf(config)


def test_import_rejects_attention_variants():
    base = dict(vocab_size=64, n_positions=16, n_embd=32, n_layer=1,
                n_head=2)
    with pytest.raises(ValueError, match="inverse_layer_idx"):
        gpt_config_from_hf(transformers.GPT2Config(
            **base, scale_attn_by_inverse_layer_idx=True))
    with pytest.raises(ValueError, match="reorder_and_upcast"):
        gpt_config_from_hf(transformers.GPT2Config(
            **base, reorder_and_upcast_attn=True))
    with pytest.raises(ValueError, match="n_inner"):
        gpt_config_from_hf(transformers.GPT2Config(**base, n_inner=100))
    with pytest.raises(ValueError, match="scale_attn_weights"):
        gpt_config_from_hf(transformers.GPT2Config(
            **base, scale_attn_weights=False))


def test_resume_skips_preset_transfer(tmp_path):
    """With resume_from_checkpoint set, initial_params must not be
    shipped to the device at all (it would be immediately overwritten)."""
    from ray_lightning_tpu.core.loop import FitConfig, run_fit
    from ray_lightning_tpu.models import BoringModel, BoringDataModule

    x_dm = BoringDataModule()
    cfg = FitConfig(max_epochs=1, seed=0, default_root_dir=str(tmp_path))
    m = BoringModel()
    run_fit(m, x_dm, cfg, callbacks=[])
    p = str(tmp_path / "b.ckpt")
    m.trainer.save_checkpoint(p)

    class Exploding(dict):
        """initial_params stand-in that detonates on any tree access."""

        def __iter__(self):
            raise AssertionError("preset consumed despite resume")

    m2 = BoringModel()
    m2.initial_params = Exploding()
    cfg2 = FitConfig(max_epochs=2, seed=0, default_root_dir=str(tmp_path),
                     resume_from_checkpoint=p)
    run_fit(m2, x_dm, cfg2, callbacks=[])  # must not touch the preset


def test_export_roundtrip_logits_parity():
    """Train here, serve with HF: export reproduces the in-framework
    logits, and import(export(x)) is the identity on weights."""
    from ray_lightning_tpu.utils import export_gpt2

    hf = _tiny_hf()
    cfg, params = import_gpt2(hf)
    # Perturb so we are not merely exporting what we imported.
    params["blocks"]["mlp_in_w"] = params["blocks"]["mlp_in_w"] + 0.01

    model = GPT(cfg, attn_impl="xla")
    model.precision = "f32"
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)).astype(np.int64)
    ours = np.asarray(jax.jit(model.forward)(
        params, jnp.asarray(tokens, jnp.int32)))

    exported = export_gpt2(params, cfg)
    with torch.no_grad():
        ref = exported(torch.from_numpy(tokens)).logits.numpy()
    np.testing.assert_allclose(ref, ours, rtol=2e-4, atol=2e-4)

    cfg2, params2 = import_gpt2(exported)
    assert cfg2 == cfg
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params2)):
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-6, atol=1e-6)


def test_export_rejects_unmerged_lora():
    from ray_lightning_tpu.models import GPT as _GPT
    from ray_lightning_tpu.models.gpt import GPTConfig as _Cfg
    from ray_lightning_tpu.utils import export_gpt2

    cfg = _Cfg(vocab_size=97, n_layer=1, n_head=2, d_model=32,
               seq_len=16, lora_rank=2)
    params = _GPT(cfg).init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="merge_lora"):
        export_gpt2(jax.device_get(params), cfg)


def test_export_rejects_moe_and_wide_mlp():
    from ray_lightning_tpu.models.gpt import GPTConfig as _Cfg
    from ray_lightning_tpu.utils import export_gpt2

    with pytest.raises(ValueError, match="MoE"):
        export_gpt2({"blocks": {}}, _Cfg.tiny_moe(n_experts=2))
    with pytest.raises(ValueError, match="mlp_ratio"):
        export_gpt2({"blocks": {}}, _Cfg(vocab_size=64, n_layer=1,
                                         n_head=2, d_model=32, seq_len=16,
                                         mlp_ratio=2))

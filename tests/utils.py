"""Assertion helpers (≙ reference ``tests/utils.py:213-272``)."""

from __future__ import annotations

import functools
import os
import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.trainer import Trainer


@functools.lru_cache(maxsize=None)
def _generate_program(module, n):
    from ray_lightning_tpu.models.generate import generate

    return jax.jit(lambda params, prompt: generate(module, params, prompt, n))


def reference_tokens(module, params, prompt, n):
    """Static-path greedy reference continuation: ``generate()`` under
    jit, one program a (module, prompt length, n) for the worker's life
    (called eagerly, ``generate`` compiles its scan anew every time)."""
    out = _generate_program(module, n)(
        params, jnp.asarray([prompt], jnp.int32))
    return np.asarray(out)[0, len(prompt):].tolist()


def rlt_top_once(directory):
    """``tools/rlt_top.py --once`` over ``directory``, exit code 0."""
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "rlt_top.py"),
         "--once", str(directory)], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    return out


def tiny_gpt(n_layer=2, seq_len=64):
    """The serving tests' model: ``(module, params)`` of a GPT of
    ``n_layer`` x 64, 4 heads, 128 tokens, XLA attention."""
    from ray_lightning_tpu.models.gpt import GPT, GPTConfig

    module = GPT(GPTConfig(
        vocab_size=128, n_layer=n_layer, n_head=4, d_model=64,
        seq_len=seq_len, warmup_steps=1), attn_impl="xla")
    return module, module.init_params(jax.random.PRNGKey(0))


def rand_prompt(seed, length, vocab=128):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, size=(length,)).tolist()


def assert_grads_match(fn, ref, args, tol):
    """``d sum(f(*args) ** 2)`` by every argument, ``fn``'s against
    ``ref``'s, both under jit, to ``tol`` (largest absolute error)."""
    def grads(f):
        return jax.jit(jax.grad(lambda *a: (f(*a) ** 2).sum(),
                                argnums=tuple(range(len(args)))))(*args)

    for i, (a, b) in enumerate(zip(grads(fn), grads(ref))):
        err = float(jnp.abs(a - b).max())
        assert err < tol, f"argument {i}: max err {err}"


def draw_tokens(n, seed=1, vocab=128):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab)


def tiny_family(preset, Module, gains, seed=0, **over):
    """A served family at its tiny preset, one chip's share of it (4 of
    16 experts, half the vocabulary): ``(cfg, module, params)``, float32,
    the norm gains ``gains`` drawn away from 1 so that a norm left out
    or misplaced shows."""
    cfg = preset(experts_held=(4, 8), vocab_held=(0, 128), **over)
    module = Module(cfg)
    params = module.init_params(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(7)
    for i, p in enumerate(params["layers"]):
        for j, name in enumerate(gains):
            k = jax.random.fold_in(key, 16 * i + j)
            p[name] = 1.0 + 0.3 * jax.random.normal(k, p[name].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)
    return cfg, module, params


def reference_logits(ref, cfg, params, toks, pad_to, precision="float32"):
    """A family's plain reference over ``toks (n,)`` padded to ``pad_to``:
    a causal decoder's rows do not see what follows them, and one length
    is one compilation a layer kind.  ``(logits (n, V), routings)``."""
    n = toks.shape[0]
    padded = jnp.zeros((pad_to,), toks.dtype).at[:n].set(toks)
    logits, routings = ref.forward(ref.config_of(cfg), params, padded,
                                   precision)
    return logits[:n], routings


def get_trainer(strategy=None, max_epochs: int = 1, tmp_path=".", **kwargs):
    """≙ reference ``get_trainer`` (``tests/utils.py:213-233``)."""
    return Trainer(
        strategy=strategy,
        max_epochs=max_epochs,
        default_root_dir=str(tmp_path),
        log_every_n_steps=1,
        **kwargs,
    )


def _flat_norm_delta(a, b) -> float:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return float(
        sum(
            np.linalg.norm(np.asarray(x) - np.asarray(y))
            for x, y in zip(la, lb)
        )
    )


def train_test(trainer: Trainer, module, datamodule) -> None:
    """Weights must move under training (≙ ``tests/utils.py:236-245``)."""
    initial = jax.device_get(
        jax.jit(module.init_params)(jax.random.PRNGKey(trainer.config.seed))
    )
    trainer.fit(module, datamodule)
    assert trainer.params is not None
    delta = _flat_norm_delta(initial, trainer.params)
    assert delta > 0.1, f"params barely moved: ‖Δ‖={delta}"


def load_test(trainer: Trainer, module, datamodule, tmp_path) -> None:
    """Checkpoint roundtrip (≙ ``tests/utils.py:248-253``)."""
    trainer.fit(module, datamodule)
    path = str(tmp_path / "model.ckpt")
    trainer.save_checkpoint(path)
    from ray_lightning_tpu.utils.state_stream import load_state_stream

    payload = load_state_stream(open(path, "rb").read())
    restored = payload["state"].params
    assert _flat_norm_delta(restored, trainer.params) == 0.0


def predict_test(trainer: Trainer, module, datamodule) -> None:
    """Post-train accuracy ≥ 0.5 (≙ ``tests/utils.py:256-272``)."""
    trainer.fit(module, datamodule)
    metrics = trainer.validate(module, datamodule)
    acc = metrics.get("val_acc")
    assert acc is not None and acc >= 0.5, f"val_acc={acc}"

"""GPT-2, plainly: the yardstick the system's outputs are held to.

Forward pass, next-token loss and (through ``jax.grad``) gradients of
the GPT-2 decoder of Radford et al. 2019 in straightforward
``jax.numpy``: float32 throughout, every matmul at
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes), one plain layer function applied layer
after layer, the full ``(T, T)`` attention matrix, no kernel, no cache,
no rematerialisation, no batching tricks.  It imports nothing from
``ray_lightning_tpu``; ``from_stacked`` below only re-reads the
program's parameter *arrays* (per-layer leaves stacked on a leading
axis) as float32.

The layers are applied by a Python ``for`` loop (``unroll=True``) or by
``jax.lax.scan`` over the stacked leaves (the default): the same
``layer`` function either way, and ``benchmarks/tests`` holds the two to
each other.  The scan is there for one reason: unrolled, the 24-layer
float32-highest forward+backward took 237 s to compile for the chip and
the 36-layer forward 175 s (sandbox compiles for a described v5e,
PR 23), which every cold run of a cell would pay.

The layer, as published (pre-LayerNorm decoder block):

    h   = x + Proj(Attention(LN1(x)))        causal, heads of d/n_head,
                                             scores scaled by 1/sqrt(d_h)
    x'  = h + W2 . gelu_tanh(W1 . LN2(h))    hidden 4 d, tanh GELU
    out = LN_f(x_L) . wte^T                  tied output head

with learned absolute position embeddings added to the token
embeddings, LayerNorm eps 1e-5 with gain and bias, and biases on every
projection.  The loss is the mean over all positions of the
cross-entropy of position t's logits against token t+1.

Departures from the published model, all in the *configuration* and
none in the equations: the vocabulary is padded from 50257 to 50304
rows (the extra rows are ordinary rows of the random table), and the
weights are random.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def from_stacked(params: Dict[str, Any], n_layer: int) -> Dict[str, Any]:
    """The program's parameter tree as this file's: float32, the
    per-layer leaves under ``layers`` stacked on a leading axis of
    ``n_layer``."""
    f32 = jnp.float32
    layers = {k: v.astype(f32) for k, v in params["blocks"].items()}
    for k, v in layers.items():
        if v.shape[0] != n_layer:
            raise ValueError(f"leaf {k} has {v.shape[0]} layers, "
                             f"expected {n_layer}")
    return {
        "wte": params["wte"].astype(f32),
        "wpe": params["wpe"].astype(f32),
        "layers": layers,
        "ln_f_g": params["ln_f_g"].astype(f32),
        "ln_f_b": params["ln_f_b"].astype(f32),
    }


def layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def attention(x, p, n_head: int):
    """Causal multi-head self-attention over ``x`` (B, T, d)."""
    B, T, d = x.shape
    dh = d // n_head
    qkv = x @ p["qkv_w"] + p["qkv_b"]
    q, k, v = (
        z.reshape(B, T, n_head, dh).transpose(0, 2, 1, 3)
        for z in (qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:])
    )
    scores = (q @ k.transpose(0, 1, 3, 2)) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = (weights @ v).transpose(0, 2, 1, 3).reshape(B, T, d)
    return out @ p["proj_w"] + p["proj_b"]


def layer(x, p, n_head: int):
    """One pre-LayerNorm decoder block; ``p`` holds one layer's leaves."""
    x = x + attention(layer_norm(x, p["ln1_g"], p["ln1_b"]), p, n_head)
    h = layer_norm(x, p["ln2_g"], p["ln2_b"])
    h = gelu_tanh(h @ p["mlp_in_w"] + p["mlp_in_b"])
    return x + h @ p["mlp_out_w"] + p["mlp_out_b"]


def forward(ref_params: Dict[str, Any], tokens, n_head: int,
            unroll: bool = False):
    """tokens (B, T) int32 -> logits (B, T, V) float32."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[1]
        x = ref_params["wte"][tokens] + ref_params["wpe"][:T]
        layers = ref_params["layers"]
        if unroll:
            n_layer = next(iter(layers.values())).shape[0]
            for i in range(n_layer):
                x = layer(x, {k: v[i] for k, v in layers.items()}, n_head)
        else:
            x, _ = jax.lax.scan(
                lambda x, p: (layer(x, p, n_head), None), x, layers)
        x = layer_norm(x, ref_params["ln_f_g"], ref_params["ln_f_b"])
        return x @ ref_params["wte"].T


def loss(ref_params: Dict[str, Any], tokens, n_head: int,
         unroll: bool = False):
    """tokens (B, T+1): mean next-token cross-entropy over B*T."""
    logits = forward(ref_params, tokens[:, :-1], n_head, unroll)
    targets = tokens[:, 1:]
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)

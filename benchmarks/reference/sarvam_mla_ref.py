"""The sarvam_mla decoder, plainly: the yardstick for the served share.

The forward pass of ``sarvam-105b``'s decoder layers in straightforward
``jax.numpy``: float32 throughout, every matmul at
``jax.default_matmul_precision("highest")``, the PUBLISHED
(un-absorbed) latent attention with per-head keys and values and a
masked score matrix, a Python loop over layers and, inside a sparse
layer, over the experts held; no kernel, no cache, no batching, no
sorting, no absorbed form.  It imports nothing from
``ray_lightning_tpu``; it reads the program's parameter *arrays* (bf16
as stored, ``wkvb`` as ``init_params`` made it) a layer and an expert at
a time and upcasts what it touches, so it fits beside the engine.  The
pieces that are the same mathematics as ``exaone_moe_ref.py``'s (the
rounding, the matmul, RMSNorm, the SiLU-gated MLP, the sigmoid router
and the sparse feed-forward over the experts held) are that file's,
imported.

The layer, from the model's ``config.json`` (every size, no
``q_lora_rank`` so ``q_proj`` is direct, ``first_k_dense_replace``,
SiLU gates, ``num_shared_experts``, ``routed_scaling_factor``,
``moe_router_enable_expert_bias``, the ``rope_scaling`` block) and,
where the config is silent, from ``transformers``' ``deepseek_v3``
(whose keys this config reproduces: ``DeepseekV3Attention``, the
router) and ``modeling_rope_utils._compute_yarn_parameters``:

    h        = RMSNorm_d(x)                         norm on the branch's INPUT
    q        = RMSNorm_192(h Wq) per head           (use_qk_norm, learned gain)
    q_n, q_r = q[:128], q[128:]
    c, k_r   = (h Wkva)[:512], (h Wkva)[512:]       k_r: ONE key for all heads
    c        = RMSNorm_512(c)                       kv_a_layernorm
    k_n, v   = (c Wkvb)[:128], (c Wkvb)[128:]       per head
    q_r, k_r = RoPE_yarn(q_r), RoPE_yarn(k_r)       half-split rotation
    a        = softmax([q_n|q_r] [k_n|k_r]^T * sigma + causal mask) v
               sigma = 192^-0.5 * (0.1 ln 40 + 1)^2 = 0.135234
    x        = x + a Wo
    x        = x + F(RMSNorm_d(x))
    F dense  = (silu(h Wg) * (h Wu)) Wd             layer 0, width 16384
    F sparse = s * sum_{e in T} g_e Expert_e(h) + Shared(h)
               z = sigmoid(h Wr) (float32), T = top_k(z + bias),
               g_e = z_e / sum_{j in T} z_j
    logits   = RMSNorm_d(x_L) W_head

    RoPE_yarn, 32 frequencies f_i = theta^(-2i/64):
      low, high = floor, ceil of the indices that turn 32 times and once
                  in the original 4096 positions (10, 23)
      ramp_i    = clip((i - low) / (high - low), 0, 1)
      inv_i     = (f_i / 40) ramp_i + f_i (1 - ramp_i)
      cos, sin times mscale(1) / mscale(mscale_all_dim) = 1

Departures from the published model, each in the configuration and none
in the equations:

* **the share**: only experts ``experts_held`` of the router's outputs
  are summed (what the other chips' experts would add is absent, here as
  in the program, and the partial result goes on to the next layer); the
  embedding and the head are the ``vocab_held`` rows;
* **depth**: the layers in ``params`` (the first 8 of 32);
* the selection bias and the weights are random; the rotation is the
  half-split form (a column permutation of random weights against the
  interleaved one).

Memory, not mathematics: scores are computed ``Q_ROWS`` query rows at a
time, the dense feed-forward ``F_COLS`` of its width at a time (a sum
over the hidden width is a sum of its blocks), the experts one at a
time.

``precision`` other than ``"float32"`` rounds every matmul's inputs to
that dtype first (``"bfloat16"``, ``"float8_e4m3fn"``): what the model
gives when computed below the stated precision, for setting tolerances.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.exaone_moe_ref import (  # noqa: F401 (router: the tests')
    NEG, _f32, _mlp, _mm, _rms, _round, router, sparse_ffn,
)

Q_ROWS = 256    # query rows scored at a time
F_COLS = 2048   # columns of the dense feed-forward taken at a time


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_parameters(cfg: Mapping[str, Any]):
    """``(inv_freq (dr/2,), attention_factor, low, high)``."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    factor, original = cfg["rope_factor"], cfg["rope_original_len"]

    def correction(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(cfg["rope_beta_fast"])), 0)
    high = min(math.ceil(correction(cfg["rope_beta_slow"])), dim - 1)
    f = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    top = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dim // 2) - low) / (top - low), 0.0, 1.0)
    inv = (f / factor) * ramp + f * (1.0 - ramp)
    attention_factor = (_mscale(factor, cfg["rope_mscale"])
                        / _mscale(factor, cfg["rope_mscale_all_dim"]))
    return inv, attention_factor, low, high


def softmax_scale(cfg: Mapping[str, Any]) -> float:
    m = _mscale(cfg["rope_factor"], cfg["rope_mscale_all_dim"])
    q_head_dim = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return q_head_dim ** -0.5 * m * m


def _rope(cfg, x):
    """x (T, H, dr), positions 0..T-1, half-split rotation."""
    t, _, dr = x.shape
    inv, factor, _, _ = yarn_parameters(cfg)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * factor
    rot = jnp.concatenate([-x[..., dr // 2:], x[..., : dr // 2]], -1)
    return x * cos + rot * sin


def attention(cfg: Mapping[str, Any], p, h, precision: str):
    t = h.shape[0]
    H, r = cfg["n_head"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = _mm(h, p["wq"], precision).reshape(t, H, dn + dr)
    q = _rms(q, p["q_norm"], cfg["rms_eps"])
    ckr = _mm(h, p["wkva"], precision)
    c = _rms(ckr[:, :r], p["kv_norm"], cfg["rms_eps"])
    kv = _mm(c, p["wkvb"], precision).reshape(t, H, dn + dv)
    k_r = _rope(cfg, ckr[:, None, r:])                  # (t, 1, dr)
    q = jnp.concatenate([q[..., :dn], _rope(cfg, q[..., dn:])], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (t, H, dr))], -1)
    qr, kr, vr = (_round(z, precision) for z in (q, k, kv[..., dn:]))
    j = jnp.arange(t)[None, :]
    rows = []
    for q0 in range(0, t, Q_ROWS):     # the (T, T) scores, a band of rows at a time
        i = jnp.arange(q0, min(q0 + Q_ROWS, t))[:, None]
        s = jnp.einsum("qhd,shd->hqs", qr[q0:q0 + Q_ROWS], kr) \
            * softmax_scale(cfg)
        pr = jax.nn.softmax(jnp.where(j <= i, s, NEG), axis=-1)
        rows.append(jnp.einsum("hqs,shv->qhv", _round(pr, precision), vr))
    a = jnp.concatenate(rows, 0).reshape(t, H * dv)
    return _mm(a, p["wo"], precision)


def dense_ffn(p, x, precision: str):
    """The SiLU-gated MLP, ``F_COLS`` hidden columns at a time."""
    width = p["w_gate"].shape[1]
    out = jnp.zeros_like(x)
    for c0 in range(0, width, F_COLS):
        cols = slice(c0, min(c0 + F_COLS, width))
        out = out + _mlp(x, p["w_gate"][:, cols], p["w_up"][:, cols],
                         p["w_down"][cols], precision)
    return out


def layer(cfg, p, x, mlp: str, precision: str):
    x = x + attention(cfg, p, _rms(x, p["attn_norm"], cfg["rms_eps"]),
                      precision)
    h = _rms(x, p["ffn_norm"], cfg["rms_eps"])
    if mlp == "dense":
        f, routing = dense_ffn(p, h, precision), None
    else:
        f, routing = sparse_ffn(cfg, p, h, precision)
    return x + f, routing


def forward(cfg: Mapping[str, Any], params: Dict[str, Any], tokens,
            precision: str = "float32"
            ) -> Tuple[jax.Array, List[Tuple[jax.Array, jax.Array]]]:
    """tokens ``(T,)`` -> ``(logits (T, V_held) float32, per sparse
    layer (scores (T, E), chosen (T, k)))``.  Each layer is one jitted
    call so that only one layer's float32 copies are alive at a time."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        routings = []
        for p, mlp in zip(params["layers"], cfg["mlp_types"]):
            x, routing = _layer_jit(cfg, p, x, mlp, precision)
            if routing is not None:
                routings.append(routing)
        h = _rms(x, params["final_norm"], cfg["rms_eps"])
        return _mm(h, params["head"], precision), routings


def _layer_jit(cfg, p, x, mlp, precision):
    key = (mlp, precision, tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in cfg.items())))
    fn = _JITS.get(key)
    if fn is None:
        def run(p, x):
            with jax.default_matmul_precision("highest"):
                return layer(cfg, p, x, mlp, precision)

        fn = _JITS[key] = jax.jit(run)
    return fn(p, x)


_JITS: Dict[Any, Any] = {}

CONFIG_NAMES = (
    "n_head", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "top_k", "routed_scale", "rms_eps", "rope_theta",
    "rope_factor", "rope_original_len", "rope_beta_fast", "rope_beta_slow",
    "rope_mscale", "rope_mscale_all_dim", "experts_held", "mlp_types")


def config_of(cfg_obj) -> Dict[str, Any]:
    """The plain mapping this file reads, from any object with the
    program's field names (no import of the program needed)."""
    return {n: getattr(cfg_obj, n) for n in CONFIG_NAMES}

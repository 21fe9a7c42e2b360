"""The gigachat35 decoder, plainly: the yardstick for the served share.

The forward pass of ``GigaChat3.5-432B-A28B``'s decoder layers in
straightforward ``jax.numpy``: float32 throughout, every matmul at
``jax.default_matmul_precision("highest")``, the linear layers'
recurrence run TOKEN BY TOKEN (never in chunks: independent of the
program's prefill), the latent attention in its published (un-absorbed)
form with a masked score matrix, a Python loop over layers and, inside
a sparse layer, a loop over the experts held; no kernel, no cache, no
batching.  It imports nothing from ``ray_lightning_tpu``; it reads the
program's parameter *arrays* (bf16 as stored, ``wkvb`` as
``init_params`` made it) a layer and an expert at a time and upcasts
what it touches, so it fits beside the engine.  The pieces that are the
same mathematics as ``exaone_moe_ref.py``'s (the rounding, the matmul,
RMSNorm, the sigmoid router) and ``sarvam_mla_ref.py``'s (YaRN's
frequencies and softmax scale, the half-split rotation) are those
files', imported.

The layer, from the model's ``config.json`` and, where it is silent,
the readings the configuration file lists under ``assumed``::

    Norm(x)  = x / rms(x) * (1 + w), eps 1e-6        zero-centred gains
    block    : x = x + Norm(Mixer(Norm(x)));  x = x + Norm(F(Norm(x)))

    linear   : [q|k|v|z] = h W_qkvz;  [b|a] = h W_ba
               [q|k|v] = silu(causal depthwise conv over the last 4 inputs)
               q, k = l2norm (eps 1e-6), q *= Dk^-0.5; value head j reads
               key head j // (Hv / Hk)
               beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
               S_t = exp(g_t) S_{t-1};  S_t += k_t (beta_t (v_t - S_t^T k_t))^T
               o_t = S_t^T q_t;  y = Norm_Dv(o_t) * 2 sigmoid(z_t)
               Mixer = y W_o
    latent   : q = RMSNorm(h W_qa) W_qb -> H x (128 nope + 64 rope)
               [c | k_r] = h W_kva;  c = RMSNorm(c);  k_n, v = c W_kvb
               q_r, k_r rotated in adjacent pairs (even columns, then odd,
               half-split), YaRN factor 8 over 32768
               a = softmax((q_n.k_n + q_r.k_r) * 192^-0.5 * mscale^2) v
               Mixer = (a * sigmoid(h W_g)) W_o
    F dense  = (silu(min(h Wg, 10)) * clip(h Wu, -10, 10)) Wd
    F sparse = 2.5 * sum_{e in T, e held} g_e Expert_e(h) + Shared(h), the
               same clamp; z = sigmoid(h Wr), T = top_8(z + bias),
               g_e = z_e / sum_{j in T} z_j
    logits   = Norm(x_L) W_head

Departures from the published model, each in the configuration and none
in the equations: the share (experts ``experts_held``, vocabulary rows
``vocab_held``), the depth (the layers in ``params``), the two
multi-token-prediction layers not loaded, random weights.

Memory, not mathematics: scores ``Q_ROWS`` query rows at a time, the
dense feed-forward ``F_COLS`` of its width at a time, the experts one at
a time, each as a loop the compiler sees once.

``precision`` other than ``"float32"`` rounds every matmul's inputs, and
the recurrence's q, k and v, to that dtype first (``"bfloat16"``,
``"float8_e4m3fn"``): what the model gives when computed below the
stated precision, for setting tolerances.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.exaone_moe_ref import (
    NEG, _f32, _mm, _rms, _round, router,
)
from benchmarks.reference.sarvam_mla_ref import _rope, softmax_scale

Q_ROWS = 256    # query rows scored at a time
F_COLS = 2048   # columns of the dense feed-forward taken at a time


def _norm(x, w, eps):
    """The zero-centred RMSNorm: gain ``1 + w``."""
    return _rms(x, 1.0 + _f32(w), eps)


def _mlp(cfg, x, wg, wu, wd, precision):
    lim = cfg["swiglu_limit"]
    a = _mm(x, wg, precision)
    b = _mm(x, wu, precision)
    if lim is not None:
        a, b = jnp.minimum(a, lim), jnp.clip(b, -lim, lim)
    return _mm(a * jax.nn.sigmoid(a) * b, wd, precision)


def recurrence(q, k, v, g, beta, state):
    """The gated delta rule a position at a time: q, k ``(T, H, K)``, v
    ``(T, H, V)``, g, beta ``(T, H)``, state ``(H, K, V)`` -> ``(o (T, H,
    V), the state after the last position)``."""
    def one(s, xs):                     # one position, every value head
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        mem = (s * k_t[:, :, None]).sum(1)                  # (H, V)
        s = s + k_t[:, :, None] * ((v_t - mem) * b_t[:, None])[:, None, :]
        return s, (s * q_t[:, :, None]).sum(1)

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    return o, state


def linear_attention(cfg: Mapping[str, Any], p, h, precision: str):
    t = h.shape[0]
    hk, hv = cfg["lin_key_heads"], cfg["lin_value_heads"]
    dk, dv = cfg["lin_key_dim"], cfg["lin_value_dim"]
    c = 2 * hk * dk + hv * dv
    qkvz = _mm(h, p["w_qkvz"], precision)
    ba = _mm(h, p["w_ba"], precision)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        ba[:, hv:] + _f32(p["dt_bias"]))
    width = cfg["conv_width"]
    xp = jnp.pad(qkvz[:, :c], ((width - 1, 0), (0, 0)))
    w = _f32(p["conv_w"])
    conv = jax.nn.silu(sum(w[j] * xp[j:j + t] for j in range(width)))
    q = conv[:, :hk * dk].reshape(t, hk, dk)
    k = conv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)

    def l2(z):
        return z / jnp.sqrt((z * z).sum(-1, keepdims=True) + 1e-6)

    rep = hv // hk
    q = jnp.repeat(l2(q) * dk ** -0.5, rep, axis=1)
    k = jnp.repeat(l2(k), rep, axis=1)
    q, k, v = (_round(z, precision) for z in (q, k, v))
    o, _ = recurrence(q, k, v, g, beta,
                      jnp.zeros((hv, dk, dv), jnp.float32))
    y = _norm(o, p["o_norm"], cfg["lin_norm_eps"])
    y = y * cfg["gate_scale"] * jax.nn.sigmoid(
        qkvz[:, c:].reshape(t, hv, dv))
    return _mm(y.reshape(t, hv * dv), p["w_o"], precision)


def _interleaved_rope(cfg, x):
    """Adjacent pairs rotate: the even columns, then the odd ones,
    rotated in halves (``rope_interleave``)."""
    return _rope(cfg, jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1))


def latent_attention(cfg: Mapping[str, Any], p, h, precision: str):
    t = h.shape[0]
    H, r = cfg["n_head"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = _mm(_rms(_mm(h, p["wqa"], precision), p["q_a_norm"], cfg["rms_eps"]),
            p["wqb"], precision).reshape(t, H, dn + dr)
    ckr = _mm(h, p["wkva"], precision)
    c = _rms(ckr[:, :r], p["kv_norm"], cfg["rms_eps"])
    kv = _mm(c, p["wkvb"], precision).reshape(t, H, dn + dv)
    k_r = _interleaved_rope(cfg, ckr[:, None, r:])
    q = jnp.concatenate([q[..., :dn], _interleaved_rope(cfg, q[..., dn:])], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (t, H, dr))], -1)
    n = -(-t // Q_ROWS)
    qr = jnp.pad(_round(q, precision), ((0, n * Q_ROWS - t), (0, 0), (0, 0)))
    kr, vr = _round(k, precision), _round(kv[..., dn:], precision)
    j = jnp.arange(t)[None, :]

    def band(b):                        # one band of query rows
        i = b * Q_ROWS + jnp.arange(Q_ROWS)[:, None]
        rows = jax.lax.dynamic_slice_in_dim(qr, b * Q_ROWS, Q_ROWS)
        s = jnp.einsum("qhd,shd->hqs", rows, kr) * softmax_scale(cfg)
        pr = jax.nn.softmax(jnp.where(j <= i, s, NEG), axis=-1)
        return jnp.einsum("hqs,shv->qhv", _round(pr, precision), vr)

    a = jax.lax.map(band, jnp.arange(n)).reshape(n * Q_ROWS, H * dv)[:t]
    a = a * jax.nn.sigmoid(_mm(h, p["w_gate"], precision))
    return _mm(a, p["wo"], precision)


def dense_ffn(cfg, p, x, precision: str):
    """The clamped SiLU-gated MLP, ``F_COLS`` hidden columns at a time."""
    width = p["w_gate"].shape[1]
    step = min(F_COLS, width)

    def block(c, out):
        def cols(w, axis):
            return jax.lax.dynamic_slice_in_dim(w, c * step, step, axis)

        return out + _mlp(cfg, x, cols(p["w_gate"], 1), cols(p["w_up"], 1),
                          cols(p["w_down"], 0), precision)

    return jax.lax.fori_loop(0, width // step, block, jnp.zeros_like(x))


def sparse_ffn(cfg, p, x, precision: str):
    """The held experts' part, scaled, and the shared expert, clamped."""
    z, idx, gates = router(cfg, p, x)
    lo, hi = cfg["experts_held"]

    def one_expert(routed, held):               # one expert at a time
        e, wg, wu, wd = held
        gate = jnp.where(idx == e, gates, 0.0).sum(-1, keepdims=True)
        return routed + gate * _mlp(cfg, x, wg, wu, wd, precision), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(lo, hi), p["e_gate"], p["e_up"], p["e_down"]))
    shared = _mlp(cfg, x, p["s_gate"], p["s_up"], p["s_down"], precision)
    return cfg["routed_scale"] * routed + shared, (z, idx)


def layer(cfg, p, x, kind: str, mlp: str, precision: str):
    eps = cfg["rms_eps"]
    mixer = latent_attention if kind == "latent" else linear_attention
    x = x + _norm(mixer(cfg, p, _norm(x, p["attn_norm"], eps), precision),
                  p["attn_out_norm"], eps)
    h = _norm(x, p["ffn_norm"], eps)
    if mlp == "dense":
        f, routing = dense_ffn(cfg, p, h, precision), None
    else:
        f, routing = sparse_ffn(cfg, p, h, precision)
    return x + _norm(f, p["ffn_out_norm"], eps), routing


def forward(cfg: Mapping[str, Any], params: Dict[str, Any], tokens,
            precision: str = "float32"
            ) -> Tuple[jax.Array, List[Tuple[jax.Array, jax.Array]]]:
    """tokens ``(T,)`` -> ``(logits (T, V_held) float32, per sparse
    layer (scores (T, E), chosen (T, k)))``.  Each layer is one jitted
    call so that only one layer's float32 copies are alive at a time."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        routings = []
        for p, kind, mlp in zip(params["layers"], cfg["layer_types"],
                                cfg["mlp_types"]):
            x, routing = _layer_jit(cfg, p, x, kind, mlp, precision)
            if routing is not None:
                routings.append(routing)
        h = _norm(x, params["final_norm"], cfg["rms_eps"])
        return _mm(h, params["head"], precision), routings


def _layer_jit(cfg, p, x, kind, mlp, precision):
    key = (kind, mlp, precision, tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in cfg.items())))
    fn = _JITS.get(key)
    if fn is None:
        def run(p, x):
            with jax.default_matmul_precision("highest"):
                return layer(cfg, p, x, kind, mlp, precision)

        fn = _JITS[key] = jax.jit(run)
    return fn(p, x)


_JITS: Dict[Any, Any] = {}

CONFIG_NAMES = (
    "n_head", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "top_k", "routed_scale", "rms_eps", "rope_theta",
    "rope_factor", "rope_original_len", "rope_beta_fast", "rope_beta_slow",
    "rope_mscale", "rope_mscale_all_dim", "experts_held", "layer_types",
    "mlp_types", "lin_key_heads", "lin_value_heads", "lin_key_dim",
    "lin_value_dim", "conv_width", "lin_norm_eps", "gate_scale",
    "swiglu_limit")


def config_of(cfg_obj) -> Dict[str, Any]:
    """The plain mapping this file reads, from any object with the
    program's field names (no import of the program needed)."""
    return {n: getattr(cfg_obj, n) for n in CONFIG_NAMES}

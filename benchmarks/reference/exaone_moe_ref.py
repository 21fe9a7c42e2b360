"""The exaone_moe decoder, plainly: the yardstick for the served share.

The forward pass of ``K-EXAONE-236B-A23B``'s decoder layers in
straightforward ``jax.numpy``: float32 throughout, every matmul at
``jax.default_matmul_precision("highest")``, the full ``(T, T)`` score
matrix with a mask, a Python loop over layers and, inside a sparse
layer, over the experts held, no kernel, no cache, no batching, no
sorting.  It imports nothing from ``ray_lightning_tpu``; it reads the
program's parameter *arrays* (bf16 as stored) a layer and an expert at a
time and upcasts what it touches, so it fits beside the engine.

The layer, from the model's ``config.json`` (sizes, ``layer_types``,
``mlp_layer_types``, sigmoid scoring, ``norm_topk_prob``,
``routed_scaling_factor``, one shared expert, untied head, default
RoPE) and, where the config is silent, from the closest published
sibling (``transformers`` ``exaone4``: norm placement, q/k norm, which
layers rotate; ``deepseek_v3``: the router's selection bias):

    q, k, v = x Wq, x Wk, x Wv                 64 / 8 / 8 heads of 128
    q, k    = RMSNorm_head(q), RMSNorm_head(k) learned gain per head dim
    q, k    = RoPE(q), RoPE(k)                 sliding layers only, half-split
    a       = softmax(q k^T / sqrt(128) + mask) v      head h uses K/V head h // 8
              mask: causal; sliding layers also i - window < j
    x       = x + RMSNorm(a Wo)                norm on the branch's OUTPUT
    x       = x + RMSNorm(F(x))
    F dense  = (silu(x Wg) * (x Wu)) Wd
    F sparse = s * sum_{e in T} g_e Expert_e(x) + Shared(x)
               z = sigmoid(x Wr) (float32), T = top_k(z + bias),
               g_e = z_e / sum_{j in T} z_j
    logits  = RMSNorm(x_L) W_head

Departures from the published model, each in the configuration and none
in the equations:

* **the share**: only experts ``experts_held`` of the router's outputs
  are summed (what the other chips' experts would add is absent, here as
  in the program, and the partial result goes on to the next layer); the
  embedding and the head are the ``vocab_held`` rows;
* **depth**: the layers in ``params`` (the first 8 of 48);
* the multi-token-prediction layer is not loaded; the selection bias is
  zero; weights are random.

``precision`` other than ``"float32"`` rounds every matmul's inputs to
that dtype first (``"bfloat16"``, ``"float8_e4m3fn"``): what the model
gives when computed below the stated precision, for setting tolerances.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

NEG = -1e30
Q_ROWS = 512    # query rows scored at a time (memory, not mathematics)


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _round(a, precision: str):
    if precision == "float32":
        return a
    dt = jnp.dtype(precision)
    top = float(jnp.finfo(dt).max)      # saturate: float8_e4m3fn has no inf
    return jnp.clip(a, -top, top).astype(dt).astype(jnp.float32)


def _mm(x, w, precision: str):
    return jnp.dot(_round(x, precision), _round(_f32(w), precision))


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(gain)


def _rope(x, theta):
    """x (T, H, Dh), positions 0..T-1, half-split rotation."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], -1)
    return x * cos + rot * sin


def _mlp(x, wg, wu, wd, precision):
    a = _mm(x, wg, precision)
    return _mm(a * jax.nn.sigmoid(a) * _mm(x, wu, precision), wd, precision)


def attention(cfg: Mapping[str, Any], p, x, kind: str, precision: str):
    t = x.shape[0]
    hq, hkv, dh = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    q = _mm(x, p["wq"], precision).reshape(t, hq, dh)
    k = _mm(x, p["wk"], precision).reshape(t, hkv, dh)
    v = _mm(x, p["wv"], precision).reshape(t, hkv, dh)
    q = _rms(q, p["q_norm"], cfg["rms_eps"])
    k = _rms(k, p["k_norm"], cfg["rms_eps"])
    if kind == "sliding":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    g = hq // hkv                      # query heads to a K/V head
    qg = _round(q, precision).reshape(t, hkv, g, dh)
    kr, vr = _round(k, precision), _round(v, precision)
    j = jnp.arange(t)[None, :]
    rows = []
    for q0 in range(0, t, Q_ROWS):     # the (T, T) scores, a band of rows at a time
        i = jnp.arange(q0, min(q0 + Q_ROWS, t))[:, None]
        vis = j <= i
        if kind == "sliding":
            vis = vis & (j > i - cfg["window"])
        s = jnp.einsum("qkgd,skd->kgqs", qg[q0:q0 + Q_ROWS], kr) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(vis, s, NEG), axis=-1)
        rows.append(jnp.einsum("kgqs,skd->qkgd", _round(pr, precision), vr))
    a = jnp.concatenate(rows, 0).reshape(t, hq * dh)
    return _mm(a, p["wo"], precision)


def router(cfg, p, x):
    """Scores (T, E) in float32, the chosen set and its gates."""
    z = jax.nn.sigmoid(jnp.dot(x, _f32(p["router"])))
    _, idx = jax.lax.top_k(z + _f32(p["router_bias"]), cfg["top_k"])
    chosen = jnp.take_along_axis(z, idx, -1)
    gates = chosen / chosen.sum(-1, keepdims=True)
    return z, idx, gates


def sparse_ffn(cfg, p, x, precision: str):
    z, idx, gates = router(cfg, p, x)
    lo, hi = cfg["experts_held"]

    def one_expert(routed, held):               # one expert at a time
        e, wg, wu, wd = held
        g = jnp.where(idx == e, gates, 0.0).sum(-1, keepdims=True)
        return routed + g * _mlp(x, wg, wu, wd, precision), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(lo, hi), p["e_gate"], p["e_up"], p["e_down"]))
    shared = _mlp(x, p["s_gate"], p["s_up"], p["s_down"], precision)
    return cfg["routed_scale"] * routed + shared, (z, idx)


def layer(cfg, p, x, kind: str, mlp: str, precision: str):
    x = x + _rms(attention(cfg, p, x, kind, precision),
                 p["attn_out_norm"], cfg["rms_eps"])
    if mlp == "dense":
        f, routing = _mlp(x, p["w_gate"], p["w_up"], p["w_down"],
                          precision), None
    else:
        f, routing = sparse_ffn(cfg, p, x, precision)
    return x + _rms(f, p["ffn_out_norm"], cfg["rms_eps"]), routing


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
        h = _rms(x, params["final_norm"], cfg["rms_eps"])
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


def config_of(cfg_obj) -> Dict[str, Any]:
    """The plain mapping this file reads, from any object with the
    program's field names (no import of the program needed)."""
    names = ("n_head", "n_kv_head", "head_dim", "window", "top_k",
             "routed_scale", "rms_eps", "rope_theta", "experts_held",
             "layer_types", "mlp_types")
    return {n: getattr(cfg_obj, n) for n in names}

"""The mimo_v2 decoder, plainly: the yardstick for the served share.

The forward pass of ``MiMo-V2-Flash``'s decoder layers in
straightforward ``jax.numpy``: float32 throughout, every matmul at
``jax.default_matmul_precision("highest")``, masked score matrices, a
Python loop over layers and, inside a sparse layer, over the experts
held; no kernel, no cache, no batching, no sorting.  It imports nothing
from ``ray_lightning_tpu``; it reads the program's parameter *arrays*
(bf16 as stored) a layer and an expert at a time and upcasts what it
touches, so it fits beside the engine.  The pieces that are the same
mathematics as ``exaone_moe_ref.py``'s (the rounding, the matmul,
RMSNorm, the SiLU-gated MLP, the sigmoid router) are that file's,
imported.

The layer, from the model's ``config.json`` (every size,
``hybrid_layer_pattern``, ``moe_layer_freq``, ``partial_rotary_factor``,
``attention_value_scale``, ``add_swa_attention_sink_bias``, the
``swa_*`` keys, sigmoid scoring with ``noaux_tc`` in one group,
``norm_topk_prob``, no shared expert, no routed scale, untied head):

    h        = RMSNorm(x)                           eps 1e-5, pre-norm
    q        = h Wq -> 64 heads x 192;  k = h Wk -> Hkv x 192
    v        = 0.707 (h Wv) -> Hkv x 128            Hkv = 4 full, 8 window
    q, k     = RoPE on columns [0, 64) of each head, half-split,
               theta 5e6 (full) / 1e4 (window); columns [64, 192) pass
    s_ij     = q_i . k_j / sqrt(192), query head h reads K/V head h // (64 / Hkv)
    full     : j <= i;              a_i = softmax_j(s_ij) v
    window   : i - 128 < j <= i;    a_i = sum_j exp(s_ij) v_j / (exp(b_h) + sum_j exp(s_ij))
    x        = x + a Wo
    x        = x + F(RMSNorm(x))
    F dense  = (silu(h Wg) * (h Wu)) Wd             layer 0, width 16384
    F sparse = sum_{e in T, e held} g_e Expert_e(h)  width 2048 each
               z = sigmoid(h Wr) (float32), T = top_8(z + bias), g_e = z_e / sum_{j in T} z_j
    logits   = RMSNorm(x_L) W_head

Assumed where the config is silent (each in the configuration file):
no q/k norm; the sink a logit with no value (gpt-oss's form); the
window convention exaone's; the score scale 192^-0.5; the value scale
on v (attention is linear in v: the same as scaling the output).

Departures from the published model, each in the configuration and none
in the equations:

* **the share**: only experts ``experts_held`` of the router's 256
  outputs are summed (what the other chips' experts would add is absent,
  here as in the program, and the partial result goes on to the next
  layer); the embedding and the head are the ``vocab_held`` rows;
* **depth**: the layers in ``params`` (the first 12 of 48);
* the three multi-token-prediction layers are not loaded; the weights,
  the sinks and the selection bias are random.

Memory, not mathematics: scores are computed ``Q_ROWS`` query rows at a
time (a window layer's against the keys its rows can see), the dense
feed-forward ``F_COLS`` of its width at a time, the experts one at a
time; each as a loop the compiler sees once (``lax.map``,
``fori_loop``, ``scan``), so that a layer compiles in seconds at any
length.

``precision`` other than ``"float32"`` rounds every matmul's inputs to
that dtype first (``"bfloat16"``, ``"float8_e4m3fn"``): what the model
gives when computed below the stated precision, for setting tolerances.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.exaone_moe_ref import (
    NEG, _f32, _mlp, _mm, _rms, _round, router,
)

Q_ROWS = 256    # query rows scored at a time
F_COLS = 2048   # columns of the dense feed-forward taken at a time


def _rope(x, theta, dim):
    """x (T, H, D), positions 0..T-1: the first ``dim`` columns of each
    head rotated (half-split, frequencies theta^(-2i/dim)), the rest
    passed."""
    t = x.shape[0]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr = x[..., :dim]
    rot = jnp.concatenate([-xr[..., dim // 2:], xr[..., :dim // 2]], -1)
    return jnp.concatenate([xr * cos + rot * sin, x[..., dim:]], -1)


def _kind(cfg: Mapping[str, Any], kind: str):
    """``(K/V heads, rotary theta, window or None, sink)`` of a kind: the
    window layers have the sink (``add_swa_attention_sink_bias``), the
    full ones none (``add_full_attention_sink_bias``)."""
    if kind == "full":
        return cfg["n_kv_head"], cfg["rope_theta"], None, False
    return cfg["swa_n_kv_head"], cfg["swa_rope_theta"], cfg["window"], True


def attention(cfg: Mapping[str, Any], p, h, kind: str, precision: str):
    t = h.shape[0]
    H, dk, dv = cfg["n_head"], cfg["head_dim"], cfg["v_head_dim"]
    hkv, theta, window, sink = _kind(cfg, kind)
    q = _mm(h, p["wq"], precision).reshape(t, H, dk)
    k = _mm(h, p["wk"], precision).reshape(t, hkv, dk)
    v = cfg["value_scale"] * _mm(h, p["wv"], precision).reshape(t, hkv, dv)
    q, k = (_rope(z, theta, cfg["rope_dim"]) for z in (q, k))
    g = H // hkv                       # query heads to a K/V head
    n = -(-t // Q_ROWS)                # bands of query rows
    qg = jnp.pad(_round(q, precision).reshape(t, hkv, g, dk),
                 ((0, n * Q_ROWS - t), (0, 0), (0, 0), (0, 0)))
    kr, vr = _round(k, precision), _round(v, precision)
    b = _f32(p["sink"]).reshape(hkv, g, 1) if sink else None
    if window is not None:
        # A band's keys: the window before it and its own rows.
        lead = ((window, n * Q_ROWS - t), (0, 0), (0, 0))
        kr, vr = jnp.pad(kr, lead), jnp.pad(vr, lead)

    def band(c):                       # one band of query rows
        q0 = c * Q_ROWS
        i = q0 + jnp.arange(Q_ROWS)[:, None]
        if window is None:
            j, keys, vals = jnp.arange(t)[None, :], kr, vr
        else:
            span = Q_ROWS + window
            j = q0 - window + jnp.arange(span)[None, :]
            keys = jax.lax.dynamic_slice_in_dim(kr, q0, span)
            vals = jax.lax.dynamic_slice_in_dim(vr, q0, span)
        vis = (j <= i) & (j >= 0)
        if window is not None:
            vis = vis & (j > i - window)
        rows = jax.lax.dynamic_slice_in_dim(qg, q0, Q_ROWS)
        s = jnp.einsum("qkgd,skd->kgqs", rows, keys) * dk ** -0.5
        s = jnp.where(vis, s, NEG)
        top = s.max(-1)
        if b is not None:
            top = jnp.maximum(top, b)
        e = jnp.exp(s - top[..., None])
        denom = e.sum(-1)
        if b is not None:
            denom = denom + jnp.exp(b - top)
        pr = e / denom[..., None]
        return jnp.einsum("kgqs,skd->qkgd", _round(pr, precision), vals)

    a = jax.lax.map(band, jnp.arange(n)).reshape(n * Q_ROWS, H * dv)[:t]
    return _mm(a, p["wo"], precision)


def dense_ffn(p, x, precision: str):
    """The SiLU-gated MLP, ``F_COLS`` hidden columns at a time."""
    width = p["w_gate"].shape[1]
    step = min(F_COLS, width)

    def block(c, out):
        def cols(w, axis):
            return jax.lax.dynamic_slice_in_dim(w, c * step, step, axis)

        return out + _mlp(x, cols(p["w_gate"], 1), cols(p["w_up"], 1),
                          cols(p["w_down"], 0), precision)

    return jax.lax.fori_loop(0, width // step, block, jnp.zeros_like(x))


def sparse_ffn(cfg, p, x, precision: str):
    """The held experts' part, no shared expert, no scale."""
    z, idx, gates = router(cfg, p, x)
    lo, hi = cfg["experts_held"]

    def one_expert(routed, held):               # one expert at a time
        e, wg, wu, wd = held
        gate = jnp.where(idx == e, gates, 0.0).sum(-1, keepdims=True)
        return routed + gate * _mlp(x, wg, wu, wd, precision), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(lo, hi), p["e_gate"], p["e_up"], p["e_down"]))
    return routed, (z, idx)


def layer(cfg, p, x, kind: str, mlp: str, precision: str):
    x = x + attention(cfg, p, _rms(x, p["attn_norm"], cfg["rms_eps"]),
                      kind, precision)
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

CONFIG_NAMES = (
    "n_head", "n_kv_head", "swa_n_kv_head", "head_dim", "v_head_dim",
    "rope_dim", "rope_theta", "swa_rope_theta", "value_scale", "window",
    "top_k", "rms_eps", "experts_held",
    "layer_types", "mlp_types")


def config_of(cfg_obj) -> Dict[str, Any]:
    """The plain mapping this file reads, from any object with the
    program's field names (no import of the program needed)."""
    return {n: getattr(cfg_obj, n) for n in CONFIG_NAMES}

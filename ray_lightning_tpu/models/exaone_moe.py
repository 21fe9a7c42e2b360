"""A decoder described as a layer pattern: the ``exaone_moe`` family.

Every layer names a **mixer kind** (``sliding`` / ``full`` attention)
and a **feed-forward kind** (``dense`` / ``sparse``).  Each kind defines
once, in this file, the three things the system needs of it:

* its full-sequence forward (training ``forward`` and the serving
  prefill run the same code);
* its cache state in the serving pool (``full``: blocks that grow with
  the sequence, by a block table; ``sliding``: a ring of
  ``ceil(window / Bs) + 1`` blocks a slot);
* its one-token decode against that state.

The block is the family's: RMSNorm on each branch's *output* (no norm
on its input), RMSNorm per head on q and k, rotary positions on the
sliding layers only (half-split form), fewer K/V heads than query
heads, SiLU-gated MLPs without biases, an untied head; the sparse
feed-forward is sigmoid-scored top-k routing over all experts with a
shared expert (``ops/moe.py``, dropless).  The config holds the model's
shape and **the share this chip holds** of a deployment that divides
each layer over several chips: a range of the routed experts and a
range of the vocabulary's rows.  What the absent experts would have
added is left out, and that partial result goes on to the next layer;
nothing stands in for the other chips or their exchange.

Weights live in the compute dtype (bf16 when served), made on the
device from the seed a layer and an expert at a time.  Layers are a
list, not stacked: they differ in kind and in shape.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.ops.attention import _NEG_INF
from ray_lightning_tpu.ops.moe import dropless_moe, sigmoid_topk_routing

__all__ = ["AttnGeometry", "ExaoneMoE", "ExaoneMoEConfig", "exaone_moe_tiny"]

FAMILY = "exaone_moe"


@dataclasses.dataclass(frozen=True)
class AttnGeometry:
    """One mixer kind's attention, as data: what ``qkv``,
    ``attend_sequence``, ``attend_one``, the pool and the decode read of
    it (a config's :meth:`~ExaoneMoEConfig.geometry`)."""

    n_kv_head: int
    head_dim: int               # query and key columns a head
    v_head_dim: int             # value columns a head
    rope_dim: int               # leading columns of a q / k head that rotate
    rope_theta: float
    window: Optional[int]       # keys i - window < j <= i; None: all j <= i
    qk_norm: bool = False       # RMSNorm per head on q and k
    sink: bool = False          # a learned logit per query head, no value
    value_scale: float = 1.0    # v = value_scale * (x Wv)

    @property
    def k_width(self) -> int:
        """One cache position of one layer: all K heads side by side."""
        return self.n_kv_head * self.head_dim

    @property
    def v_width(self) -> int:
        return self.n_kv_head * self.v_head_dim


@dataclasses.dataclass(frozen=True)
class ExaoneMoEConfig:
    """The model's shape (no optimizer fields) and this chip's share."""

    vocab_size: int = 153600
    d_model: int = 6144
    n_layer: int = 48
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128
    d_ff: int = 18432             # the dense feed-forward's width
    d_expert: int = 2048          # every expert's, and the shared one's
    n_experts: int = 128          # the router's outputs
    top_k: int = 8
    routed_scale: float = 2.5
    window: int = 128
    # Per layer; None = the published pattern: three sliding layers then
    # a full one; the first layer dense, every other sparse.
    layer_types: Optional[Tuple[str, ...]] = None
    mlp_types: Optional[Tuple[str, ...]] = None
    rms_eps: float = 1e-5
    rope_theta: float = 1e6
    seq_len: int = 262144         # positions the model declares
    # The share: experts [lo, hi) of n_experts, vocabulary rows [lo, hi).
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None
    param_dtype: str = "bfloat16"

    # The family's norm placement (:func:`decoder_block`): exaone4's, on
    # each branch's output.  Not a field: no configuration changes it.
    norms = "post"
    # Gains stored as published: the gain itself (zero-centred ones are
    # ``1 + w``; :func:`gain`).
    zero_centred = False
    # A shared expert beside the routed ones (:func:`feed_forward`).
    shared_expert = True
    # The gated MLPs' clamp (``swiglu_limit``; :func:`swiglu`): none.
    swiglu_limit = None

    def __post_init__(self):
        L = self.n_layer
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                "full" if i % 4 == 3 else "sliding" for i in range(L)))
        if self.mlp_types is None:
            object.__setattr__(self, "mlp_types", tuple(
                "dense" if i == 0 else "sparse" for i in range(L)))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "mlp_types", tuple(self.mlp_types))
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", (0, self.n_experts))
        if self.vocab_held is None:
            object.__setattr__(self, "vocab_held", (0, self.vocab_size))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "vocab_held", tuple(self.vocab_held))
        if len(self.layer_types) != L or len(self.mlp_types) != L:
            raise ValueError("layer_types / mlp_types must name every layer")
        bad = (set(self.layer_types) - {"sliding", "full"}) | (
            set(self.mlp_types) - {"dense", "sparse"})
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_experts} experts")
        lo, hi = self.vocab_held
        if not 0 <= lo < hi <= self.vocab_size:
            raise ValueError(f"vocab_held {self.vocab_held} is not a range "
                             f"of the {self.vocab_size} rows")

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_vocab_held(self) -> int:
        return self.vocab_held[1] - self.vocab_held[0]

    def geometry(self, kind: str) -> AttnGeometry:
        """Both kinds: the same heads, q/k normed; sliding layers rotate
        every column and see the window."""
        sliding = kind == "sliding"
        return AttnGeometry(
            self.n_kv_head, self.head_dim, self.head_dim,
            rope_dim=self.head_dim if sliding else 0,
            rope_theta=self.rope_theta,
            window=self.window if sliding else None, qk_norm=True)

    def layers_of(self, kind: str) -> List[int]:
        return [i for i, t in enumerate(self.layer_types) if t == kind]

    @property
    def n_sparse(self) -> int:
        return sum(t == "sparse" for t in self.mlp_types)


def exaone_moe_tiny(**over) -> ExaoneMoEConfig:
    """The CPU tests' preset: the same kinds in the same ratios (two
    periods ``LLLG LLLG``, layer 0 dense, 16 experts top-4, window 8,
    4 query / 2 K/V heads), every width tiny."""
    base = dict(vocab_size=256, d_model=32, n_layer=8, n_head=4,
                n_kv_head=2, head_dim=8, d_ff=64, d_expert=16,
                n_experts=16, top_k=4, window=8, seq_len=256,
                param_dtype="float32")
    base.update(over)
    return ExaoneMoEConfig(**base)


# ---------------------------------------------------------------------------
# the block's pieces, each written once
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: Optional[jax.Array] = None,
         factor: float = 1.0) -> jax.Array:
    """Rotary positions, half-split form.  x ``(..., S, H, Dh)``,
    positions ``(..., S)``.  The ``Dh / 2`` frequencies are
    ``theta ** (-2i / Dh)`` unless a family hands its own as data
    (``inv_freq``, YaRN's blend of scaled and unscaled ones) with the
    ``factor`` its cos and sin are multiplied by."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv     # (..., S, Dh/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[..., None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : dh // 2], x32[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def gain(cfg, w: jax.Array) -> jax.Array:
    """A norm's gain as the family stores it: ``1 + w`` where its gains
    are zero-centred (``cfg.zero_centred``), else ``w``."""
    return w + 1.0 if cfg.zero_centred else w


def swiglu(x: jax.Array, p: Dict[str, jax.Array], pre: str,
           limit: Optional[float] = None) -> jax.Array:
    """``(silu(x Wg) * (x Wu)) Wd``; with a ``limit``, the gate's
    pre-activation clamped from above and the up projection both ways
    first (``swiglu_limit``)."""
    a = jnp.dot(x, p[pre + "gate"], preferred_element_type=jnp.float32)
    b = jnp.dot(x, p[pre + "up"], preferred_element_type=jnp.float32)
    if limit is not None:
        a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
    h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
    return _mm(h, p[pre + "down"])


def _partial_rope(x: jax.Array, positions: jax.Array,
                  g: AttnGeometry) -> jax.Array:
    """Rotary positions on a head's first ``g.rope_dim`` columns (their
    own frequencies, ``theta ** (-2i / rope_dim)``); the rest pass."""
    if g.rope_dim == x.shape[-1]:
        return rope(x, positions, g.rope_theta)
    r = g.rope_dim
    return jnp.concatenate(
        [rope(x[..., :r], positions, g.rope_theta), x[..., r:]], axis=-1)


def qkv(cfg: ExaoneMoEConfig, p, x: jax.Array, positions: jax.Array,
        g: AttnGeometry):
    """x ``(..., S, d)`` -> q ``(..., S, Hq, Dh)``, k ``(..., S, Hkv,
    Dh)``, v ``(..., S, Hkv, Dv)``: projected (v scaled), q and k
    normalised per head and rotated as the kind's geometry says."""
    lead = x.shape[:-1]
    q = _mm(x, p["wq"]).reshape(lead + (cfg.n_head, g.head_dim))
    k = _mm(x, p["wk"]).reshape(lead + (g.n_kv_head, g.head_dim))
    if g.value_scale == 1.0:
        v = _mm(x, p["wv"])
    else:
        v = (jnp.dot(x, p["wv"], preferred_element_type=jnp.float32)
             * g.value_scale).astype(x.dtype)
    v = v.reshape(lead + (g.n_kv_head, g.v_head_dim))
    if g.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if g.rope_dim:
        q = _partial_rope(q, positions, g)
        k = _partial_rope(k, positions, g)
    return q, k, v


def _grouped(g: AttnGeometry, q):
    """``(..., Hq, Dh)`` -> ``(..., Hkv, G, Dh)``: query head ``h``
    attends K/V head ``h // G``."""
    hq, dh = q.shape[-2:]
    return q.reshape(q.shape[:-2] + (g.n_kv_head, hq // g.n_kv_head, dh))


def _probs(scores, sink):
    """Softmax over the last axis.  With a ``sink`` (one logit a query
    head, broadcastable to the scores but their last axis) the sink is
    one more key that no value follows: ``exp(s_j) / (exp(b) + sum_j
    exp(s_j))``."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    col = jnp.broadcast_to(sink.astype(jnp.float32),
                           scores.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([scores, col], axis=-1),
                          axis=-1)[..., :-1]


def _softmax_pv(scores, v, eq, sink=None):
    probs = _probs(scores, sink)
    return jnp.einsum(eq, probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def attend_sequence(g: AttnGeometry, q, k, v, attn_impl: str,
                    sink=None) -> jax.Array:
    """Causal attention over whole sequences.  q ``(B, T, Hq, Dh)``,
    k ``(B, T, Hkv, Dh)``, v ``(B, T, Hkv, Dv)`` -> ``(B, T, Hq*Dv)``.
    With a window, key ``j`` is visible to query ``i`` iff ``i - window
    < j <= i``; ``sink`` ``(Hq,)`` float32 is each query head's sink
    logit (:func:`_probs`).

    No ``T x T`` score tensor at serving sizes: layers without a window
    go through the flash kernel (K/V heads repeated to the query heads),
    windowed ones through a banded form, each ``window``-sized chunk of
    queries against its own and the previous chunk of keys.  Short or
    ragged sequences (a bucket under 128, the tests' sizes) take the
    plain masked form."""
    B, T, Hq, Dh = q.shape
    G = Hq // g.n_kv_head
    Dv = v.shape[-1]
    scale = Dh ** -0.5
    w = g.window
    if sink is not None:
        sink = sink.reshape(g.n_kv_head, G)[:, :, None, None]
    if w is None and sink is None and attn_impl == "flash":
        from ray_lightning_tpu.ops.flash_attention import flash_attention

        out = flash_attention(q, jnp.repeat(k, G, axis=2),
                              jnp.repeat(v, G, axis=2), scale=scale)
        return out.reshape(B, T, Hq * Dv)
    qg = _grouped(g, q)
    if w is not None and T > w and T % w == 0:
        n = T // w
        qc = qg.reshape(B, n, w, g.n_kv_head, G, Dh)

        def two_chunks(z):
            zc = z.reshape(B, n, w, g.n_kv_head, z.shape[-1])
            prev = jnp.pad(zc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
            return jnp.concatenate([prev[:, :-1], zc], axis=2)

        kc, vc = two_chunks(k), two_chunks(v)
        s = jnp.einsum("bcqkgd,bcskd->bckgqs", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        qi = jnp.arange(w)[:, None] + w          # in the two-chunk frame
        kj = jnp.arange(2 * w)[None, :]
        vis = (kj <= qi) & (kj > qi - w)
        first = kj >= w                          # chunk 0 has no previous
        vis = jnp.where(jnp.arange(n)[:, None, None] == 0, vis & first, vis)
        s = jnp.where(vis[None, :, None, None], s, _NEG_INF)
        out = _softmax_pv(s, vc, "bckgqs,bcskd->bcqkgd", sink)
        return out.reshape(B, T, Hq * Dv).astype(q.dtype)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qi = jnp.arange(T)[:, None]
    kj = jnp.arange(T)[None, :]
    vis = kj <= qi
    if w is not None:
        vis = vis & (kj > qi - w)
    s = jnp.where(vis, s, _NEG_INF)
    out = _softmax_pv(s, v, "bkgqs,bskd->bqkgd", sink)
    return out.reshape(B, T, Hq * Dv).astype(q.dtype)


def attend_one(g: AttnGeometry, q, keys, values, visible,
               sink=None) -> jax.Array:
    """One query token a slot against gathered cache positions.  q
    ``(W, Hq, Dh)``; keys ``(W, S, Hkv, Dh)``, values ``(W, S, Hkv,
    Dv)``; visible ``(W, S)`` -> ``(W, Hq*Dv)`` float32.  Products of
    the stored values in float32, as the paged kernel computes them."""
    s = jnp.einsum("wkgd,wskd->wkgs", _grouped(g, q).astype(jnp.float32),
                   keys.astype(jnp.float32)) * q.shape[-1] ** -0.5
    s = jnp.where(visible[:, None, None, :], s, _NEG_INF)
    if sink is not None:
        sink = sink.reshape(g.n_kv_head, -1)[:, :, None]
    out = jnp.einsum("wkgs,wskd->wkgd", _probs(s, sink),
                     values.astype(jnp.float32))
    return out.reshape(q.shape[0], q.shape[1] * values.shape[-1])


def ring_visible(pos, ring: int, window: int) -> jax.Array:
    """``(W, ring)`` bool: the ring locations a slot's token at ``pos
    (W,)`` sees.  Location ``u`` holds position ``pos - ((pos - u) mod
    ring)``; the location the current token will take still holds the
    one it evicts.  Visible: not negative, inside the window, not
    ``pos``."""
    held = pos[:, None] - (pos[:, None] - jnp.arange(ring)[None, :]) % ring
    return ((held >= 0) & (held > pos[:, None] - window)
            & (held != pos[:, None]))


def attend_gathered(g: AttnGeometry, q, k_row, v_row, k_pool, v_pool,
                    tables, visible, sink=None) -> jax.Array:
    """The XLA path of a decode token's attention: each slot's blocks
    ``tables (W, T)`` gathered from one layer's pools ``(N, Bs, row)``,
    its own K and V rows after them, through :func:`attend_one`;
    ``visible (W, T*Bs)`` masks the gathered positions (the own row is
    always seen)."""
    W, S = visible.shape

    def ctx(pool, row):
        got = pool[tables].reshape(W, S, -1)
        got = jnp.concatenate([got, row[:, None]], axis=1)
        return got.reshape(W, S + 1, g.n_kv_head, -1)

    return attend_one(g, q, ctx(k_pool, k_row), ctx(v_pool, v_row),
                      jnp.concatenate([visible, jnp.ones((W, 1), bool)],
                                      axis=1), sink)


def feed_forward(cfg: ExaoneMoEConfig, kind: str, p, x: jax.Array,
                 row_valid: Optional[jax.Array], moe_impl: str,
                 routing: Optional[list] = None):
    """x ``(S, d)`` -> ``(F(x), counts int32[2])``.  ``dense``: one
    SiLU-gated MLP.  ``sparse``: the held experts' part of ``scale *
    sum_e gate_e Expert_e(x)``, plus the shared expert, once, where the
    family has one (``cfg.shared_expert``).  A ``routing`` list is
    handed each sparse layer's ``(chosen experts, scores)``, for
    comparisons with a reference.  Every gated MLP here, the experts
    among them, clamps by ``cfg.swiglu_limit`` (:func:`swiglu`)."""
    limit = cfg.swiglu_limit
    if kind == "dense":
        return swiglu(x, p, "w_", limit), jnp.zeros((2,), jnp.int32)
    idx, gates, z = sigmoid_topk_routing(
        x, p["router"], p["router_bias"], cfg.top_k, cfg.routed_scale,
        return_scores=True)
    if routing is not None:
        routing.append((idx, z))
    routed, counts = dropless_moe(
        x, idx, gates, p["e_gate"], p["e_up"], p["e_down"],
        cfg.experts_held[0], row_valid=row_valid, impl=moe_impl,
        limit=limit)
    if not cfg.shared_expert:
        return routed, counts
    return routed + swiglu(x, p, "s_", limit), counts


def _residual(cfg, x, branch, gain):
    return x + rms_norm(branch.astype(x.dtype), gain, cfg.rms_eps)


def decoder_block(cfg, p, x, mixer, mlp: str, row_valid, moe_impl: str,
                  routing: Optional[list] = None):
    """One layer: ``x`` plus its mixer's branch, then plus its
    feed-forward's.  ``mixer(h)`` is the layer's mixer of whatever kind
    with its output projection; the feed-forward sees rows ``(S, d)``.
    Where the norms sit belongs to the family (``cfg.norms``): on each
    branch's output (``"post"``: ``x + norm(branch(x))``, gains
    ``attn_out_norm`` / ``ffn_out_norm``), on its input (``"pre"``: ``x
    + branch(norm(x))``, gains ``attn_norm`` / ``ffn_norm``) or on both
    (``"pre_post"``: ``x + norm'(branch(norm(x)))``, all four gains).
    Returns ``(x, counts int32[2])``."""
    if cfg.norms == "pre_post":
        def norm(z, name):
            return rms_norm(z, gain(cfg, p[name]), cfg.rms_eps)

        x = _residual(cfg, x, mixer(norm(x, "attn_norm")),
                      gain(cfg, p["attn_out_norm"]))
        h = norm(x, "ffn_norm")
        f, c = feed_forward(cfg, mlp, p, h.reshape(-1, h.shape[-1]),
                            row_valid, moe_impl, routing)
        return _residual(cfg, x, f.reshape(x.shape),
                         gain(cfg, p["ffn_out_norm"])), c
    if cfg.norms == "pre":
        x = x + mixer(rms_norm(x, p["attn_norm"], cfg.rms_eps)).astype(x.dtype)
        h = rms_norm(x, p["ffn_norm"], cfg.rms_eps)
        f, c = feed_forward(cfg, mlp, p, h.reshape(-1, h.shape[-1]),
                            row_valid, moe_impl, routing)
        return x + f.reshape(x.shape).astype(x.dtype), c
    x = _residual(cfg, x, mixer(x), p["attn_out_norm"])
    f, c = feed_forward(cfg, mlp, p, x.reshape(-1, x.shape[-1]), row_valid,
                        moe_impl, routing)
    return _residual(cfg, x, f.reshape(x.shape), p["ffn_out_norm"]), c


def _resolve_attn(attn_impl: str, T: int) -> str:
    if attn_impl != "auto":
        return attn_impl
    from ray_lightning_tpu.ops.kernel_probe import kernel_family_disabled

    on = (jax.default_backend() == "tpu" and T % 128 == 0
          and not kernel_family_disabled("flash"))
    return "flash" if on else "xla"


def sequence_forward(cfg: ExaoneMoEConfig, params, tokens: jax.Array,
                     row_valid: Optional[jax.Array] = None,
                     attn_impl: str = "auto", moe_impl: str = "auto",
                     routing: Optional[list] = None):
    """The trunk over whole sequences: tokens ``(B, T)`` (ids within the
    held vocabulary slice) -> ``(hidden (B, T, d) before the final norm,
    per-layer (k (B, T, Hkv*Dh), v (B, T, Hkv*Dv)), counts int32[2])``."""
    B, T = tokens.shape
    attn_impl = _resolve_attn(attn_impl, T)
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    valid = None if row_valid is None else row_valid.reshape(B * T)
    kv, counts = [], jnp.zeros((2,), jnp.int32)
    for p, kind, mlp in zip(params["layers"], cfg.layer_types,
                            cfg.mlp_types):
        def mixer(h, p=p, g=cfg.geometry(kind)):
            q, k, v = qkv(cfg, p, h, positions, g)
            kv.append((k.reshape(B, T, g.k_width),
                       v.reshape(B, T, g.v_width)))
            return _mm(attend_sequence(g, q, k, v, attn_impl,
                                       p["sink"] if g.sink else None),
                       p["wo"])

        x, c = decoder_block(cfg, p, x, mixer, mlp, valid, moe_impl, routing)
        counts = counts + c
    return x, kv, counts


def head_logits(cfg: ExaoneMoEConfig, params, x: jax.Array) -> jax.Array:
    h = rms_norm(x, gain(cfg, params["final_norm"]), cfg.rms_eps)
    return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the serving cache: two kinds of state in one manager
# ---------------------------------------------------------------------------

class TwoKindKVCache:
    """Full layers keep ``(Lg, N, Bs, row)`` and a block table that
    grows with the sequence; sliding layers keep ``(Ls, Nw, Bs, row)``
    (each kind's K and V rows of their own widths, ``Hkv*Dh`` and
    ``Hkv*Dv`` of its geometry) and a ring of ``window_blocks =
    ceil(window / Bs) + 1``
    blocks a slot: position ``p`` lives in ring block ``(p // Bs) %
    window_blocks`` at offset ``p % Bs``, so a write evicts the position
    one ring length back, which the window has already passed.  Both
    pools have an allocator of their own and admission counts both: a
    slot costs ``window_blocks + ceil(len / Bs)`` blocks."""

    def __init__(self, cfg: ExaoneMoEConfig, num_blocks: int,
                 block_size: int, num_slots: int, dtype=jnp.bfloat16,
                 family=None):
        from ray_lightning_tpu.serve.kv_cache import BlockAllocator

        self.cfg = cfg
        self.family = family or ServeFamily     # named in its refusals
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.dtype = dtype
        self.window_blocks = -(-cfg.window // block_size) + 1
        # Every slot's ring and the trash block.
        self.num_window_blocks = num_slots * self.window_blocks + 1
        self.allocator = BlockAllocator(num_blocks)
        self.window_allocator = BlockAllocator(self.num_window_blocks)

    def init_pool(self) -> Dict[str, jax.Array]:
        cfg, Bs = self.cfg, self.block_size
        full, ring = cfg.geometry("full"), cfg.geometry("sliding")
        full_at = (len(cfg.layers_of("full")), self.num_blocks, Bs)
        ring_at = (len(cfg.layers_of("sliding")), self.num_window_blocks, Bs)
        return {"k": jnp.zeros(full_at + (full.k_width,), self.dtype),
                "v": jnp.zeros(full_at + (full.v_width,), self.dtype),
                "wk": jnp.zeros(ring_at + (ring.k_width,), self.dtype),
                "wv": jnp.zeros(ring_at + (ring.v_width,), self.dtype)}

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def export_blocks(self, pool, ids):
        raise ValueError(
            f"export_blocks is not supported for the {self.family.name} "
            f"family: {self.family.refuses_why}")


def paged_prefill(cfg: ExaoneMoEConfig, params, pool, tokens, prompt_len,
                  block_ids, compute_dtype=None, attn_impl: str = "auto",
                  moe_impl: str = "auto", **unused):
    """One prompt (``tokens (T,)`` right-padded to a bucket) through
    :func:`sequence_forward`; its K/V written into the slot's blocks of
    both pools.  ``block_ids`` is ``(full (T // Bs,), ring
    (window_blocks,))``.  Returns ``(logits (V_held,) float32 at
    position prompt_len - 1, pool, counts)``."""
    full_ids, ring_ids = block_ids
    T = tokens.shape[0]
    Bs = pool["k"].shape[2]
    R = ring_ids.shape[0]
    valid = jnp.arange(T) < prompt_len
    x, kv, counts = sequence_forward(
        cfg, params, tokens[None], row_valid=valid[None],
        attn_impl=attn_impl, moe_impl=moe_impl)
    h_last = jax.lax.dynamic_index_in_dim(
        x[0], prompt_len - 1, axis=0, keepdims=False)
    logits = head_logits(cfg, params, h_last)
    # Ring location u holds the latest prompt position congruent to it.
    u = jnp.arange(R * Bs)
    src = u + (prompt_len - 1 - u) // (R * Bs) * (R * Bs)
    src = jnp.clip(src, 0, T - 1)       # u past the prompt: masked later
    out = dict(pool)
    for name, j in (("k", 0), ("v", 1)):
        full = jnp.stack([kv[i][j][0] for i in cfg.layers_of("full")])
        out[name] = pool[name].at[:, full_ids].set(
            full.reshape(full.shape[0], T // Bs, Bs, -1))
        ring = jnp.stack([kv[i][j][0][src] for i in cfg.layers_of("sliding")])
        out["w" + name] = pool["w" + name].at[:, ring_ids].set(
            ring.reshape(ring.shape[0], R, Bs, -1))
    return logits, out, counts


def paged_decode_step(cfg: ExaoneMoEConfig, params, pool, block_tables,
                      seq_lens, tokens, compute_dtype=None,
                      attn_impl: str = "auto", moe_impl: str = "auto",
                      **unused):
    """One token for every slot.  ``block_tables`` is ``(full (W, M),
    ring (W, window_blocks))``; ``seq_lens (W,)`` the positions already
    cached (0 = an idle slot, taken out of the routing).  Returns
    ``(logits (W, V_held) float32, pool, counts)``.

    Full layers: the ``rlt_paged_decode`` kernel walks blocks ``0 ..
    len // Bs`` of the slot's table (``auto`` on a TPU; the XLA gather
    elsewhere).  Sliding layers: the ``rlt_ring_decode`` kernel reads the
    slot's ring where it lies (:func:`ring_kernel_taken`); elsewhere the
    ring is gathered (``window + Bs`` positions).  Either masks a ring
    location by the absolute position it holds.  The pool is only read
    inside the layer loop; every layer's new row is scattered into it
    afterwards, one scatter a tensor."""
    from ray_lightning_tpu.ops.paged_attention import (
        paged_decode_attention, paged_decode_supported, ring_decode_attention,
    )

    full_tables, ring_tables = block_tables
    ring_kernel = ring_kernel_taken(cfg, attn_impl, pool)
    if attn_impl == "auto":
        attn_impl = "pallas" if paged_decode_supported(pool["k"]) else "xla"
    W, M = full_tables.shape
    Bs = pool["k"].shape[2]
    R = ring_tables.shape[1]
    RB = R * Bs
    pos = seq_lens
    active = seq_lens > 0
    rows = jnp.arange(W)
    x = params["embed"][tokens]
    ring_vis = ring_visible(pos, RB, cfg.window)
    full_vis = jnp.arange(M * Bs)[None, :] < pos[:, None]
    new = {"k": [], "v": [], "wk": [], "wv": []}
    counts = jnp.zeros((2,), jnp.int32)
    n_full = n_ring = 0
    for p, kind, mlp in zip(params["layers"], cfg.layer_types,
                            cfg.mlp_types):
        def mixer(h, p=p, kind=kind, g=cfg.geometry(kind), n_full=n_full,
                  n_ring=n_ring):
            q, k, v = qkv(cfg, p, h[:, None], pos[:, None], g)
            q, k, v = q[:, 0], k[:, 0], v[:, 0]
            k_row = k.reshape(W, g.k_width).astype(pool["k"].dtype)
            v_row = v.reshape(W, g.v_width).astype(pool["v"].dtype)
            if kind == "full" and attn_impl == "pallas":
                att = paged_decode_attention(
                    q.reshape(W, -1), k_row, v_row, pool["k"], pool["v"],
                    jnp.int32(n_full), full_tables, pos,
                    n_head=cfg.n_head, n_kv_head=g.n_kv_head,
                    scale=g.head_dim ** -0.5)
            elif kind != "full" and ring_kernel:
                att = ring_decode_attention(
                    q.reshape(W, -1), k_row, v_row, pool["wk"], pool["wv"],
                    jnp.int32(n_ring), ring_tables, pos,
                    n_head=cfg.n_head, n_kv_head=g.n_kv_head,
                    scale=g.head_dim ** -0.5, window=g.window,
                    sink=p["sink"] if g.sink else None)
            else:
                names, n, tables, vis = (
                    (("k", "v"), n_full, full_tables, full_vis)
                    if kind == "full" else
                    (("wk", "wv"), n_ring, ring_tables, ring_vis))
                att = attend_gathered(
                    g, q, k_row, v_row, pool[names[0]][n], pool[names[1]][n],
                    tables, vis, p["sink"] if g.sink else None)
            pre = "" if kind == "full" else "w"
            new[pre + "k"].append(k_row)
            new[pre + "v"].append(v_row)
            return _mm(att.astype(h.dtype), p["wo"])

        x, c = decoder_block(cfg, p, x, mixer, mlp, active, moe_impl)
        counts = counts + c
        n_full += kind == "full"
        n_ring += kind != "full"
    logits = head_logits(cfg, params, x)
    # Every index explicit, one row a slot a layer (PERF.md, PR 25: a
    # slice over the layer axis makes XLA re-lay the pool).
    out = {}
    blk = jnp.take_along_axis(
        full_tables, jnp.minimum(pos // Bs, M - 1)[:, None], axis=1)[:, 0]
    ring_blk = ring_tables[rows, (pos % RB) // Bs]
    for names, b in ((("k", "v"), blk), (("wk", "wv"), ring_blk)):
        for name in names:
            stacked = jnp.stack(new[name])
            layer = jnp.arange(stacked.shape[0], dtype=jnp.int32)
            out[name] = pool[name].at[
                (layer[:, None], b[None, :], (pos % Bs)[None, :])
            ].set(stacked)
    return logits, out, counts


def ring_kernel_taken(cfg: ExaoneMoEConfig, attn_impl: str, pool) -> bool:
    """Whether the sliding layers' decode runs ``rlt_ring_decode``:
    ``pallas`` asks for it, ``auto`` takes it where
    ``ring_decode_supported`` passes for the ring pools (a TPU, rows it
    tiles); else the ring is gathered."""
    from ray_lightning_tpu.ops.paged_attention import ring_decode_supported

    if attn_impl != "auto":
        return attn_impl == "pallas"
    return ring_decode_supported(pool["wk"], pool["wv"], cfg.n_head,
                                 cfg.geometry("sliding").n_kv_head)


class ServeFamily:
    """What :class:`~ray_lightning_tpu.serve.engine.ServeEngine` asks a
    module for: the pool's layout and the prefill and decode programs.
    (``serve/kv_cache.py`` ``gpt_family`` is the same seam for ``GPT``.)"""

    name = FAMILY
    two_kind = True      # a window ring beside the block table
    refuses = ("prefix_cache", "spec_k", "draft", "adapters",
               "prefill_chunk", "block_transfer")
    refuses_why = (
        "it is served with two kinds of cache state, block tables and "
        "window rings: a sequence's state is a block table and a "
        "window ring")

    def __init__(self, module: "ExaoneMoE"):
        self.cfg = module.config
        self.vocab_size = self.cfg.n_vocab_held
        self.n_sparse = self.cfg.n_sparse
        self.n_full = len(self.cfg.layers_of("full"))
        self.n_ring = len(self.cfg.layers_of("sliding"))
        self.attn_impl = module.attn_impl
        kw = dict(attn_impl=module.attn_impl, moe_impl=module.moe_impl)
        self.prefill = functools.partial(paged_prefill, self.cfg, **kw)
        self.decode = functools.partial(paged_decode_step, self.cfg, **kw)

    def walk_layers(self, pool) -> int:
        """Layers whose decode attention runs ``rlt_paged_decode`` on
        ``pool``: the full ones where the decode takes the kernel (the
        sliding ones read their ring whole: :meth:`ring_layers`)."""
        from ray_lightning_tpu.ops.paged_attention import (
            paged_decode_supported,
        )
        kernel = self.attn_impl == "pallas" or (
            self.attn_impl == "auto" and paged_decode_supported(pool["k"]))
        return self.n_full if kernel else 0

    def ring_layers(self, pool) -> int:
        """Sliding layers whose decode attention runs ``rlt_ring_decode``
        on ``pool`` (:func:`ring_kernel_taken`): all or none."""
        return self.n_ring if ring_kernel_taken(
            self.cfg, self.attn_impl, pool) else 0

    def make_cache(self, num_blocks: int, block_size: int, num_slots: int,
                   dtype) -> TwoKindKVCache:
        return TwoKindKVCache(self.cfg, num_blocks, block_size, num_slots,
                              dtype, family=self)

    def prepare_params(self, tree: Dict[str, Any],
                       compute_dtype) -> Dict[str, Any]:
        """The tree as it came, no leaf copied: ``init_params`` makes
        the weights in ``param_dtype``, which is the compute dtype, and
        the router in float32, which it has to stay; a second copy of
        any of it would not fit beside the first."""
        return tree


def init_tree(cfg, rng: jax.Array, mixer_leaves,
              router_bias_std: float = 0.0) -> Dict[str, Any]:
    """A pattern-described decoder's weights in ``param_dtype``, made on
    the device, one jitted call a layer and inside it one expert at a
    time: no float32 copy of the whole model ever exists.
    ``mixer_leaves(w, keys, kind)`` gives a layer's mixer tensors and
    the block's norm gains (``w(key, shape)`` draws a matrix); the
    feed-forward's, the tables and the final norm are every family's.
    The router scores in float32 (a choice must not flip on rounding);
    its selection bias is a float32 buffer, zero unless a family draws
    it (``router_bias_std``)."""
    dt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(rng, cfg.n_layer + 2)
    d = cfg.d_model

    def w(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(dt)

    @functools.partial(jax.jit, static_argnums=(1,))
    def table(key, shape):
        return w(key, shape)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def layer(key, kind, mlp):
        ks = jax.random.split(key, 12)
        p = mixer_leaves(w, ks[:4], kind)
        if mlp == "dense":
            p.update(w_gate=w(ks[4], (d, cfg.d_ff)),
                     w_up=w(ks[5], (d, cfg.d_ff)),
                     w_down=w(ks[6], (cfg.d_ff, d)))
            return p
        f, eh = cfg.d_expert, cfg.n_experts_held

        def experts(key, shape):
            return jax.lax.map(lambda k: w(k, shape),
                               jax.random.split(key, eh))

        bias = jnp.zeros((cfg.n_experts,), jnp.float32)
        if router_bias_std:
            bias = jax.random.normal(
                ks[11], (cfg.n_experts,), jnp.float32) * router_bias_std
        p.update(
            router=jax.random.normal(ks[4], (d, cfg.n_experts),
                                     jnp.float32) * 0.02,
            router_bias=bias,
            e_gate=experts(ks[5], (d, f)), e_up=experts(ks[6], (d, f)),
            e_down=experts(ks[7], (f, d)))
        if cfg.shared_expert:
            p.update(s_gate=w(ks[8], (d, f)), s_up=w(ks[9], (d, f)),
                     s_down=w(ks[10], (f, d)))
        return p

    vh = cfg.n_vocab_held
    return {
        "embed": table(keys[0], (vh, d)),
        "head": table(keys[1], (d, vh)),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": [layer(keys[i + 2], kind, mlp)
                   for i, (kind, mlp) in enumerate(
                       zip(cfg.layer_types, cfg.mlp_types))],
    }


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------

class ExaoneMoE(TpuModule):
    """``TpuModule`` of the family.  ``attn_impl`` (``auto`` | ``xla`` |
    ``flash`` for the full-sequence pass, and ``auto`` | ``xla`` |
    ``pallas`` is derived for decode) and ``moe_impl`` (``auto`` |
    ``xla`` | ``pallas``) choose kernels; ``auto`` takes them on a TPU."""

    def __init__(self, config: ExaoneMoEConfig, attn_impl: str = "auto",
                 moe_impl: str = "auto", lr: float = 3e-4):
        super().__init__()
        self.config = config
        self.attn_impl = attn_impl
        self.moe_impl = moe_impl
        self.lr = lr
        self.precision = ("bf16" if config.param_dtype == "bfloat16"
                          else "f32")

    # The family's trunk over whole sequences (a subclass of another
    # family names its own).
    _sequence_forward = staticmethod(sequence_forward)

    def _compute_dtype(self):
        return jnp.dtype(self.config.param_dtype)

    def serve_family(self) -> ServeFamily:
        return ServeFamily(self)

    # -- parameters ---------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        d = cfg.d_model

        def mixer_leaves(w, ks, kind):
            g = cfg.geometry(kind)      # both kinds: the same widths
            hq = cfg.n_head * g.head_dim
            return {
                "wq": w(ks[0], (d, hq)), "wk": w(ks[1], (d, g.k_width)),
                "wv": w(ks[2], (d, g.v_width)), "wo": w(ks[3], (hq, d)),
                "q_norm": jnp.ones((cfg.head_dim,), jnp.float32),
                "k_norm": jnp.ones((cfg.head_dim,), jnp.float32),
                "attn_out_norm": jnp.ones((d,), jnp.float32),
                "ffn_out_norm": jnp.ones((d,), jnp.float32),
            }

        return init_tree(cfg, rng, mixer_leaves)

    # -- forward ------------------------------------------------------------
    def forward(self, params, tokens: jax.Array) -> jax.Array:
        """tokens ``(B, T)`` -> logits ``(B, T, V_held)`` float32, by
        the mixer and feed-forward code the serving prefill runs."""
        x, _, _ = self._sequence_forward(
            self.config, params, tokens, attn_impl=self.attn_impl,
            moe_impl=self.moe_impl)
        return head_logits(self.config, params, x)

    def _loss(self, params, batch):
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        logits = self.forward(params, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return -picked.mean()

    def training_step(self, params, batch, rng):
        loss = self._loss(params, batch)
        return loss, {"train_loss": loss}

    def validation_step(self, params, batch):
        return {"val_loss": self._loss(params, batch)}

    def configure_optimizers(self):
        import optax

        return optax.adamw(self.lr)

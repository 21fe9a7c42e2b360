"""The ``gigachat35`` family: GigaChat 3.5's decoder on the pattern
decoder of ``models/exaone_moe.py``.

Two **mixer kinds**:

* ``linear``: the gated delta rule (Gated DeltaNet).  ``h W_qkvz`` gives
  queries and keys (``lin_key_heads`` of ``lin_key_dim``), values and an
  output gate (``lin_value_heads`` of ``lin_value_dim``); ``h W_ba`` a
  write strength ``beta = sigmoid(b)`` and a decay ``g = -exp(A_log)
  softplus(a + dt_bias)`` per value head.  The q|k|v channels pass a
  causal depthwise convolution of width ``conv_width`` and a SiLU; q and
  k are L2-normalised (q scaled by ``lin_key_dim^-0.5``) and value head
  ``j`` reads key head ``j // (value heads / key heads)``.  The
  recurrence (``ops/gated_delta.py``) keeps one float32 state of
  ``lin_key_dim x lin_value_dim`` a value head; its output is RMS-normed
  per head with a zero-centred gain and gated by ``gate_scale *
  sigmoid(z)``.  Its cache state is not paged: a slot's row of the
  states and of the convolution's last ``conv_width - 1`` inputs, which
  the prefill writes whole and the decode rewrites in place every tick
  (``rlt_gdn_decode``).
* ``latent``: ``models/sarvam_mla.py``'s latent attention (one cached
  row ``[c | k_r]`` a position, ``rlt_mla_decode``, YaRN), with the
  query through its own latent (``q_lora_rank``), the rotary columns in
  adjacent pairs (``rope_interleave``) and the attention's output gated
  per element by ``sigmoid(h W_g)`` (``gated_attention``).

The block norms each branch's input and its output (``pre_post``), every
block gain zero-centred (``1 + w``); the dense MLP, the shared expert
and the experts clamp (``swiglu_limit``); the sparse feed-forward is
sarvam's (sigmoid top-k by ``score + bias``, a shared expert with no
gate, ``routed_scaling_factor``).  The share (``experts_held``,
``vocab_held``), the feed-forward, the block and the head are the
pattern decoder's; the latent pieces sarvam's, imported.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.exaone_moe import (
    _mm, _resolve_attn, decoder_block, gain, head_logits, init_tree,
    rms_norm,
)
from ray_lightning_tpu.models.sarvam_mla import (
    LatentKVCache, SarvamMLA, SarvamMLAConfig,
    ServeFamily as SarvamServeFamily, _pad_row, attend_sequence,
    decode_latent, latent_impl, latent_projections,
)
from ray_lightning_tpu.ops import gated_delta as gd

__all__ = ["GigaChat35", "GigaChat35Config", "gigachat35_tiny"]

FAMILY = "gigachat35"


@dataclasses.dataclass(frozen=True)
class GigaChat35Config(SarvamMLAConfig):
    """The model's shape (``config.json``'s sizes under the pattern
    decoder's names) and this chip's share.  The latent layers' keys are
    sarvam's; ``lin_*`` and ``conv_width`` are the linear layers'."""

    vocab_size: int = 128256
    d_model: int = 7168
    n_layer: int = 40
    n_head: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = 1536
    d_ff: int = 18432
    d_expert: int = 2048
    n_experts: int = 256
    top_k: int = 8
    routed_scale: float = 2.5
    first_dense: int = 3
    rms_eps: float = 1e-6
    rope_theta: float = 1e5
    rope_factor: float = 8.0
    rope_original_len: int = 32768
    seq_len: int = 262144
    # full_attention_layers; None: the published period, every fourth
    # layer from layer 3 latent, the others linear.
    full_layers: Optional[Tuple[int, ...]] = None
    lin_key_heads: int = 32
    lin_value_heads: int = 64
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    conv_width: int = 4
    lin_norm_eps: float = 1e-6
    gate_scale: float = 2.0           # linear_sigmoid_gate_scale
    swiglu_limit: Optional[float] = 10.0

    norms = "pre_post"
    zero_centred = True
    shared_expert = True
    rope_interleave = True

    def __post_init__(self):
        if self.full_layers is None:
            object.__setattr__(self, "full_layers", tuple(
                i for i in range(self.n_layer) if i % 4 == 3))
        object.__setattr__(self, "full_layers", tuple(self.full_layers))
        super().__post_init__()
        if not all(0 <= i < self.n_layer for i in self.full_layers):
            raise ValueError(f"full_layers {self.full_layers} are not "
                             f"layers of {self.n_layer}")
        if self.lin_value_heads % self.lin_key_heads:
            raise ValueError("lin_value_heads must be a multiple of "
                             "lin_key_heads")
        if self.conv_width < 2:
            raise ValueError("conv_width must be at least 2")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("latent" if i in self.full_layers else "linear"
                     for i in range(self.n_layer))

    def layers_of(self, kind: str) -> List[int]:
        return [i for i, t in enumerate(self.layer_types) if t == kind]

    @property
    def conv_channels(self) -> int:
        """The q|k|v channels the convolution runs over."""
        return (2 * self.lin_key_heads * self.lin_key_dim
                + self.lin_value_heads * self.lin_value_dim)

    @property
    def lin_value_width(self) -> int:
        return self.lin_value_heads * self.lin_value_dim


def gigachat35_tiny(**over) -> GigaChat35Config:
    """The CPU tests' preset: the cut's five layers (``L F L L L``, layer
    0 dense), 16 experts top-4, 4 latent heads over a latent of 16 and a
    query latent of 24, 2 key and 4 value heads of 8 in the linear
    layers, an original context of 16 positions stretched 8 times, every
    width tiny."""
    base = dict(vocab_size=256, d_model=32, n_layer=5, full_layers=(1,),
                first_dense=1, n_head=4, kv_lora_rank=16, q_lora_rank=24,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                d_ff=64, d_expert=16, n_experts=16, top_k=4,
                lin_key_heads=2, lin_value_heads=4, lin_key_dim=8,
                lin_value_dim=8, rope_factor=8.0, rope_original_len=16,
                seq_len=128, param_dtype="float32")
    base.update(over)
    return GigaChat35Config(**base)


# ---------------------------------------------------------------------------
# the linear mixer, each form written once
# ---------------------------------------------------------------------------

def linear_projections(cfg: GigaChat35Config, p, h: jax.Array):
    """h ``(..., d)`` (the block's normed input) -> ``(the convolution's
    inputs (..., C) in h's dtype, the output gate z (..., Hv*Dv), g,
    beta (..., Hv) float32)``."""
    qkvz = _mm(h, p["w_qkvz"])
    c, hv = cfg.conv_channels, cfg.lin_value_heads
    ba = jnp.dot(h, p["w_ba"], preferred_element_type=jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    return qkvz[..., :c], qkvz[..., c:], g, beta


def _convolved(cfg: GigaChat35Config, p, windows: List[jax.Array]):
    """``silu(sum_j w_j x_j)`` over the ``conv_width`` inputs ``windows``
    (oldest first), in float32, split into q, k ``(..., Hk, Dk)`` (L2-
    normed, q scaled) and v ``(..., Hv, Dv)``."""
    w = p["conv_w"].astype(jnp.float32)
    acc = sum(w[j] * x.astype(jnp.float32) for j, x in enumerate(windows))
    acc = jax.nn.silu(acc)
    hk, dk = cfg.lin_key_heads, cfg.lin_key_dim
    lead = acc.shape[:-1]
    q = acc[..., :hk * dk].reshape(lead + (hk, dk))
    k = acc[..., hk * dk:2 * hk * dk].reshape(lead + (hk, dk))
    v = acc[..., 2 * hk * dk:].reshape(
        lead + (cfg.lin_value_heads, cfg.lin_value_dim))
    return gd.l2norm(q) * dk ** -0.5, gd.l2norm(k), v


def _linear_output(cfg: GigaChat35Config, p, o, z, dtype):
    """``Norm(o) * gate_scale * sigmoid(z)`` per value head, then
    ``W_o``: o ``(..., Hv, Dv)`` float32, z ``(..., Hv*Dv)``."""
    y = rms_norm(o, gain(cfg, p["o_norm"]), cfg.lin_norm_eps)
    y = y * (cfg.gate_scale * jax.nn.sigmoid(
        z.astype(jnp.float32).reshape(o.shape)))
    return _mm(y.reshape(o.shape[:-2] + (-1,)).astype(dtype), p["w_o"])


def linear_sequence(cfg: GigaChat35Config, p, h: jax.Array,
                    valid: Optional[jax.Array]):
    """Whole sequences, chunked: h ``(B, T, d)``; ``valid (B, T)`` takes
    positions out of the state (``beta = g = 0``: a prompt's padding).
    Returns ``(the mixer's output (B, T, d), (the state after the last
    valid position (B, Hv, Dk, Dv) float32, the convolution's inputs
    after ``conv_width - 1`` zero rows (B, conv_width - 1 + T, C)))``."""
    B, T, _ = h.shape
    x, z, g, beta = linear_projections(cfg, p, h)
    lag = cfg.conv_width - 1
    xp = jnp.pad(x, ((0, 0), (lag, 0), (0, 0)))
    q, k, v = _convolved(cfg, p, [xp[:, j:j + T]
                                  for j in range(cfg.conv_width)])
    if valid is not None:
        g, beta = jnp.where(valid[..., None], g, 0.0), jnp.where(
            valid[..., None], beta, 0.0)
    group = cfg.lin_value_heads // cfg.lin_key_heads
    state = jnp.zeros((B, cfg.lin_value_heads, cfg.lin_key_dim,
                       cfg.lin_value_dim), jnp.float32)
    o, state = gd.chunked(jnp.repeat(q, group, axis=2),
                          jnp.repeat(k, group, axis=2), v, g, beta, state)
    return _linear_output(cfg, p, o, z, h.dtype), (state, xp)


def linear_decode(cfg: GigaChat35Config, p, h: jax.Array, layer, states,
                  tails, seq_lens, impl: str):
    """One position a slot: h ``(W, d)``; ``states (L_lin, W, Hv, Dk,
    Dv)`` and ``tails (W, conv_width - 1, C)`` this layer's convolution
    inputs.  Returns ``(the mixer's output (W, d), states, the slots' new
    tails)``; an idle slot's state is left as it was."""
    x, z, g, beta = linear_projections(cfg, p, h)
    window = jnp.concatenate([tails, x[:, None].astype(tails.dtype)], axis=1)
    q, k, v = _convolved(cfg, p, [window[:, j]
                                  for j in range(cfg.conv_width)])
    o, states = gd.decode_step(states, layer, q, k, v, g, beta, seq_lens,
                               impl=impl)
    return _linear_output(cfg, p, o, z, h.dtype), states, window[:, 1:]


def state_impl(cfg: GigaChat35Config, attn_impl: str, states) -> str:
    """The linear layers' decode on the state pool: ``auto`` takes
    ``rlt_gdn_decode`` where it tiles the pool (a TPU), else the XLA
    step; ``pallas`` / ``xla`` as asked."""
    if attn_impl != "auto":
        return attn_impl
    return ("pallas" if gd.decode_supported(states, cfg.lin_key_heads)
            else "xla")


def _gated(p, h, a):
    """The latent attention's output ``a (..., H*dv)`` gated per element
    by ``sigmoid(h W_g)``."""
    gate = jax.nn.sigmoid(jnp.dot(h, p["w_gate"],
                                  preferred_element_type=jnp.float32))
    return (a.astype(jnp.float32) * gate).astype(h.dtype)


def sequence_forward(cfg: GigaChat35Config, params, tokens: jax.Array,
                     row_valid: Optional[jax.Array] = None,
                     attn_impl: str = "auto", moe_impl: str = "auto",
                     routing: Optional[list] = None):
    """The trunk over whole sequences: tokens ``(B, T)`` (ids within the
    held vocabulary slice) -> ``(hidden (B, T, d) before the final norm,
    per layer its cache state: a latent layer's rows (B, T, r + dr), a
    linear layer's ``(state, convolution inputs)``, counts int32[2])``."""
    B, T = tokens.shape
    attn_impl = _resolve_attn(attn_impl, T)
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    valid = None if row_valid is None else row_valid.reshape(B * T)
    cached: List[Any] = []
    counts = jnp.zeros((2,), jnp.int32)
    for p, kind, mlp in zip(params["layers"], cfg.layer_types,
                            cfg.mlp_types):
        if kind == "latent":
            def mixer(h, p=p):
                q_n, q_r, rows = latent_projections(cfg, p, h, positions)
                cached.append(rows)
                a = attend_sequence(cfg, p, q_n, q_r, rows, attn_impl)
                return _mm(_gated(p, h, a), p["wo"])
        else:
            def mixer(h, p=p):
                y, state = linear_sequence(cfg, p, h, row_valid)
                cached.append(state)
                return y

        x, c = decoder_block(cfg, p, x, mixer, mlp, valid, moe_impl, routing)
        counts = counts + c
    return x, cached, counts


# ---------------------------------------------------------------------------
# the serving cache: latent rows by block table, recurrent state by slot
# ---------------------------------------------------------------------------

class HybridCache(LatentKVCache):
    """The latent layers' pool ``kv (L_lat, N, Bs, pool_row)``, one block
    table a slot and one allocator (``LatentKVCache``'s), and beside it a
    row a SLOT of the linear layers' state, not paged: ``S (L_lin, slots,
    Hv, Dk, Dv)`` float32 and ``conv (L_lin, slots, conv_width - 1, C)``.
    A slot's prefill writes its rows whole; its decode rewrites them in
    place each tick."""

    def __init__(self, cfg: GigaChat35Config, num_blocks: int,
                 block_size: int, num_slots: int, dtype=jnp.bfloat16):
        super().__init__(cfg, num_blocks, block_size, dtype)
        self.num_slots = num_slots

    def init_pool(self) -> Dict[str, jax.Array]:
        cfg = self.cfg
        n_lin = len(cfg.layers_of("linear"))
        return {
            "kv": jnp.zeros((len(cfg.layers_of("latent")), self.num_blocks,
                             self.block_size, cfg.pool_row), self.dtype),
            "S": jnp.zeros((n_lin, self.num_slots, cfg.lin_value_heads,
                            cfg.lin_key_dim, cfg.lin_value_dim),
                           jnp.float32),
            "conv": jnp.zeros((n_lin, self.num_slots, cfg.conv_width - 1,
                               cfg.conv_channels), self.dtype),
        }

    def export_blocks(self, pool, ids):
        raise ValueError(
            f"export_blocks is not supported for the {FAMILY} family: "
            f"{ServeFamily.refuses_why}")


def paged_prefill(cfg: GigaChat35Config, params, pool, tokens, prompt_len,
                  block_ids, compute_dtype=None, attn_impl: str = "auto",
                  moe_impl: str = "auto", **unused):
    """One prompt (``tokens (T,)`` right-padded to a bucket) through
    :func:`sequence_forward`.  ``block_ids`` is ``(the slot's blocks
    (T // Bs,), the slot)``: the latent rows go into the blocks, the
    linear layers' state after the prompt and the convolution's last
    inputs into the slot's rows, whole.  Returns ``(logits (V_held,)
    float32 at position prompt_len - 1, pool, counts)``."""
    block_ids, slot = block_ids
    T = tokens.shape[0]
    Bs = pool["kv"].shape[2]
    valid = jnp.arange(T) < prompt_len
    x, cached, counts = sequence_forward(
        cfg, params, tokens[None], row_valid=valid[None],
        attn_impl=attn_impl, moe_impl=moe_impl)
    h_last = jax.lax.dynamic_index_in_dim(
        x[0], prompt_len - 1, axis=0, keepdims=False)
    logits = head_logits(cfg, params, h_last)
    lat, lin = cfg.layers_of("latent"), cfg.layers_of("linear")
    rows = _pad_row(cfg, jnp.stack([cached[i][0] for i in lat]))
    lag = cfg.conv_width - 1
    # xp holds lag zero rows, then the prompt: its rows [n, n + lag) are
    # the prompt's last lag inputs (zeros where it is shorter).
    tails = jnp.stack([jax.lax.dynamic_slice_in_dim(
        cached[i][1][0], prompt_len, lag) for i in lin])
    out = dict(pool)
    out["kv"] = pool["kv"].at[:, block_ids].set(
        rows.astype(pool["kv"].dtype).reshape(len(lat), T // Bs, Bs,
                                              cfg.pool_row))
    out["S"] = pool["S"].at[:, slot].set(
        jnp.stack([cached[i][0][0] for i in lin]))
    out["conv"] = pool["conv"].at[:, slot].set(tails.astype(
        pool["conv"].dtype))
    return logits, out, counts


def paged_decode_step(cfg: GigaChat35Config, params, pool, block_tables,
                      seq_lens, tokens, compute_dtype=None,
                      attn_impl: str = "auto", moe_impl: str = "auto",
                      **unused):
    """One token for every slot.  ``block_tables (W, M)`` are the latent
    layers'; the slots' rows of the state pool are the slots themselves.
    ``seq_lens (W,)`` the positions already cached (0 = an idle slot,
    taken out of the routing and out of the state update).  Returns
    ``(logits (W, V_held) float32, pool, counts)``.

    Latent layers: ``rlt_mla_decode`` (sarvam's :func:`decode_latent`),
    their new rows scattered after the layer loop.  Linear layers: the
    convolution's step and the gates in XLA, the recurrence by
    ``rlt_gdn_decode`` on a TPU (:func:`state_impl`), which rewrites the
    state pool in place; the convolution's tails are written after the
    loop, the idle slots' kept."""
    kv = pool["kv"]
    lat_impl = latent_impl(cfg, attn_impl, kv)
    lin_impl = state_impl(cfg, attn_impl, pool["S"])
    W, M = block_tables.shape
    Bs = kv.shape[2]
    pos = seq_lens
    active = seq_lens > 0
    x = params["embed"][tokens]
    rows: List[jax.Array] = []
    tails: List[jax.Array] = []
    held = {"S": pool["S"]}
    counts = jnp.zeros((2,), jnp.int32)
    n_lat = n_lin = 0
    for p, kind, mlp in zip(params["layers"], cfg.layer_types,
                            cfg.mlp_types):
        if kind == "latent":
            def mixer(h, p=p, i=n_lat):
                a, row = decode_latent(cfg, p, h, pos, kv, jnp.int32(i),
                                       block_tables, lat_impl)
                rows.append(row)
                return _mm(_gated(p, h, a), p["wo"])
        else:
            def mixer(h, p=p, i=n_lin):
                y, held["S"], tail = linear_decode(
                    cfg, p, h, jnp.int32(i), held["S"], pool["conv"][i],
                    seq_lens, lin_impl)
                tails.append(tail)
                return y

        x, c = decoder_block(cfg, p, x, mixer, mlp, active, moe_impl)
        counts = counts + c
        n_lat += kind == "latent"
        n_lin += kind == "linear"
    logits = head_logits(cfg, params, x)
    # Every index explicit, one row a slot a layer.
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(pos // Bs, M - 1)[:, None], axis=1)[:, 0]
    layer = jnp.arange(n_lat, dtype=jnp.int32)
    kv = kv.at[(layer[:, None], blk[None, :], (pos % Bs)[None, :])].set(
        jnp.stack(rows))
    conv = jnp.where(active[None, :, None, None],
                     jnp.stack(tails).astype(pool["conv"].dtype),
                     pool["conv"])
    return logits, {"kv": kv, "S": held["S"], "conv": conv}, counts


class ServeFamily(SarvamServeFamily):
    """sarvam's seam (one block table a slot for the latent rows, the
    latent decode kernel's counters, ``wkvb`` rearranged once) with a
    second kind of state, a row a slot: ``slot_state`` (the linear layers
    that hold one) makes the engine hand the prefill its slot."""

    name = FAMILY
    refuses_why = (
        "a sequence's state is latent rows and, in its linear layers, a "
        "recurrent state a slot that only this family's prefill and "
        "decode programs read and write (a prefix claim, a chunk or a "
        "block transfer would need a snapshot of that state)")

    def __init__(self, module: "GigaChat35"):
        self.cfg = cfg = module.config
        self.vocab_size = cfg.n_vocab_held
        self.n_sparse = cfg.n_sparse
        self.n_latent = len(cfg.layers_of("latent"))
        self.slot_state = len(cfg.layers_of("linear"))
        self.attn_impl = module.attn_impl
        kw = dict(attn_impl=module.attn_impl, moe_impl=module.moe_impl)
        self.prefill = functools.partial(paged_prefill, cfg, **kw)
        self.decode = functools.partial(paged_decode_step, cfg, **kw)

    def walk_layers(self, pool) -> int:
        """Latent layers whose decode runs ``rlt_mla_decode``."""
        kernel = latent_impl(self.cfg, self.attn_impl, pool["kv"])
        return self.n_latent if kernel == "pallas" else 0

    def state_row_bytes(self, dtype) -> int:
        """One slot's state in one linear layer: the float32 states and
        the convolution's last inputs."""
        cfg = self.cfg
        return (cfg.lin_value_heads * cfg.lin_key_dim * cfg.lin_value_dim * 4
                + (cfg.conv_width - 1) * cfg.conv_channels
                * jnp.dtype(dtype).itemsize)

    def make_cache(self, num_blocks: int, block_size: int, num_slots: int,
                   dtype) -> HybridCache:
        return HybridCache(self.cfg, num_blocks, block_size, num_slots, dtype)


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------

class GigaChat35(SarvamMLA):
    """``TpuModule`` of the family: ``ExaoneMoE``'s (its ``attn_impl``
    and ``moe_impl``, its loss and optimizer) with this family's weights,
    trunk and serving programs."""

    ROUTER_BIAS_STD = 0.01
    _sequence_forward = staticmethod(sequence_forward)

    def serve_family(self) -> ServeFamily:
        return ServeFamily(self)

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        """Weights normal with std 0.02; the block and output gains
        zero-centred at 0 (gain 1); the convolution's taps normal with
        std 0.3; ``A_log = log U(0, 16)`` and ``dt_bias = 1``, as
        Qwen3-Next initialises them; norms, ``A_log`` and ``dt_bias`` in
        float32."""
        cfg = self.config
        d, H, r = cfg.d_model, cfg.n_head, cfg.kv_lora_rank
        hv = cfg.lin_value_heads

        def block_gains():
            return {name: jnp.zeros((d,), jnp.float32) for name in (
                "attn_norm", "attn_out_norm", "ffn_norm", "ffn_out_norm")}

        def mixer_leaves(w, ks, kind):
            if kind == "latent":
                qr = cfg.q_lora_rank
                more = jax.random.split(ks[3], 3)
                return {
                    "wqa": w(ks[0], (d, qr)),
                    "q_a_norm": jnp.ones((qr,), jnp.float32),
                    "wqb": w(more[0], (qr, H * cfg.q_head_dim)),
                    "wkva": w(ks[1], (d, cfg.cache_row)),
                    "kv_norm": jnp.ones((r,), jnp.float32),
                    "wkvb": w(ks[2], (r, H * (cfg.qk_nope_head_dim
                                              + cfg.v_head_dim))),
                    "w_gate": w(more[1], (d, H * cfg.v_head_dim)),
                    "wo": w(more[2], (H * cfg.v_head_dim, d)),
                    **block_gains(),
                }
            more = jax.random.split(ks[3], 3)
            return {
                "w_qkvz": w(ks[0], (d, cfg.conv_channels
                                    + cfg.lin_value_width)),
                "w_ba": w(ks[1], (d, 2 * hv)),
                "conv_w": w(ks[2], (cfg.conv_width, cfg.conv_channels),
                            std=0.3),
                "A_log": jnp.log(jax.random.uniform(
                    more[0], (hv,), jnp.float32, 0.0, 16.0)),
                "dt_bias": jnp.ones((hv,), jnp.float32),
                "o_norm": jnp.zeros((cfg.lin_value_dim,), jnp.float32),
                "w_o": w(more[1], (cfg.lin_value_width, d)),
                **block_gains(),
            }

        tree = init_tree(cfg, rng, mixer_leaves,
                         router_bias_std=self.ROUTER_BIAS_STD)
        tree["final_norm"] = jnp.zeros((d,), jnp.float32)
        return tree
